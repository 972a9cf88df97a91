"""Device time per step and chip of attention's backward pass: the
operations under ``hvd_compute_grads`` whose scope path is a transposed
(backward) one inside a block's ``attn`` module — the chunked XLA backward
of ``ops/pallas_kernels.py`` and the projections' gradients."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_compute_grads", "transpose(", "/attn/")
