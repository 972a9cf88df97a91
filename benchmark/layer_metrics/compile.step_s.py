"""Seconds ``prof/introspect`` clocked for lowering and compiling the step
(cold) or reading it from the persistent cache (later runs)."""


def read(run):
    return run.step_record.get("compile_seconds")
