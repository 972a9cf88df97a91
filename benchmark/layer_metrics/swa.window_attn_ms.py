"""Device time per step and chip of the sliding-window attention layers:
the operations under ``hvd_compute_grads`` inside an ``attn`` module's
``window`` scope (which only a window layer's attention has): its
projections, rope, both flash kernels, the sum of dk and dv over a group of
query heads, the gate and the output projection, forward, backward and
recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/attn/window")
