"""Device time per step and chip of the gated attention layers of a
Qwen3-Next cell: the operations under ``hvd_compute_grads`` inside an
``attn`` module's scope: projections, the per-head q and k norms
(``attn/qk_norm``), rope, both flash kernels, the sums of dk and dv over a
group, the channel gate (``attn/gate``) and the output projection, forward,
backward and recomputation.  None where the program has no such scope."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/attn/")
