"""Gated DeltaNet layers of the compiled step whose core ran as the kernel
pair of ``ops/kda_kernels.py`` (a key or value width the chip's kernels
do not take falls to the plain chunked form, and the layer does not
count): the program's gauge ``model.gdn.kernel_layers``, set where the
model is traced.  None from a program without the gauge."""


def read(run):
    from horovod_tpu import metrics

    return metrics.get_gauge("model.gdn.kernel_layers")
