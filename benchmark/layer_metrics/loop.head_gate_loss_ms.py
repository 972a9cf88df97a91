"""Device time per step and chip of what ``hvd_compute_grads`` does outside
a looped decoder's layers, forward and backward: the embedding and, after
every pass of the loop, the final norm, the head, the exit gate; the
expected loss over the passes."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", without=("/block_",))
