"""Device time per step and chip of the Gated DeltaNet mixers: the
operations under ``hvd_compute_grads`` inside a ``gdn`` module's scope:
projections, convolutions, L2 norms, gates, the delta rule, the output's
norm and gate and its projection, forward, backward and recomputation.
None where the program has no such scope."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/gdn/")
