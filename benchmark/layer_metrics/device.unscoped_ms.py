"""Device time per step and chip of operations that carry neither of the
program's scopes (``hvd_compute_grads``, ``hvd_reduce_and_update``): layout
copies, prefetch waits and whatever XLA left without a source."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step()
