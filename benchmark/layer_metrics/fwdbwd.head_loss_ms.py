"""Device time per step and chip of what ``hvd_compute_grads`` does outside
the transformer's blocks, forward and backward: embeddings, the final
LayerNorm, the tied output head over the whole vocabulary, the loss."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", without=("/block_",))
