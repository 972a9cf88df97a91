"""Device time per step and chip of a looped decoder's layers: the
operations under ``hvd_compute_grads`` inside a ``block_<i>`` scope, all
passes of the loop, forward, backward and recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/block_")
