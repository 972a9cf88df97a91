"""Delta-rule layers of the compiled step whose three short convolutions,
SiLU and q's and k's L2 norms ran as the convolution's kernel pair of
``ops/kda_kernels.py`` (a head width the chip's kernels do not take falls
to XLA, and the layer does not count): the program's gauge
``model.conv.kernel_layers``, set where the model is traced.  None from a
program without the gauge."""


def read(run):
    from horovod_tpu import metrics

    return metrics.get_gauge("model.conv.kernel_layers")
