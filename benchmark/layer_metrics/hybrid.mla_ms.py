"""Device time per step and chip of the latent-attention mixers (MLA): the
operations under ``hvd_compute_grads`` inside an ``attn`` module's scope,
which in a hybrid model only the MLA layers have: the latent projections
(``attn/mla``), both flash kernels and the output projection."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/attn/")
