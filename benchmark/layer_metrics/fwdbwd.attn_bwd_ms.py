"""Device time per step and chip of attention's backward pass: the
operations under ``hvd_compute_grads`` whose scope path is a transposed
(backward) one inside a block's ``attn`` module — the fused backward kernel
of ``ops/pallas_kernels.py`` (under ``attn/flash_bwd/``, delta and the turn
of dq inside it), the joining of dq, dk and dv, and the projections'
gradients."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_compute_grads", "transpose(", "/attn/")
