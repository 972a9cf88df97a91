"""Device time per step and chip of the full-attention mixers beside the
Gated DeltaNet layers: the operations under ``hvd_compute_grads`` inside an
``attn`` module's scope: projections, the norms of q and k, both flash
kernels and the output projection, forward, backward and
recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/attn/")
