"""Seconds of the step's compilation spent in jit's ``trace`` stage, from the
program's record of the step (``prof/introspect``): ``trace`` is jax
tracing the Python, ``lower`` the jaxpr to StableHLO, ``backend`` XLA or
the read from the persistent cache.  The three sum to ``compile.step_s``."""


def read(run):
    return run.step_record.get("trace_seconds")
