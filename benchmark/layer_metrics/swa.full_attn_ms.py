"""Device time per step and chip of the full-attention layers: the
operations under ``hvd_compute_grads`` inside an ``attn`` module's scope
and not under its ``window`` scope: projections, rope, both flash kernels,
the sum of dk and dv over a group of query heads, the gate and the output
projection, forward, backward and recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_compute_grads", "/attn/", without=("/attn/window",))
