"""Tiles the flash forward kernel's walks visit for one head of a
sliding-window layer over one row: the program's gauge
``model.attn.tiles_per_head{kind=window}``, set from the kernels' own tile
rule where the model is traced (31 at 8192 tokens, tiles and a window of
512; the causal triangle would be 136).  None from a program without the
gauge."""


def read(run):
    from horovod_tpu import metrics

    return metrics.get_gauge("model.attn.tiles_per_head", {"kind": "window"})
