"""Expert layers of the compiled step whose grouped product ran as the
kernel pair of ``ops/expert_kernels.py``, a call a tile under
``moe/experts``: the program's gauge ``model.moe.kernel_layers``, set where
the model is traced.  None from a program without the gauge."""


def read(run):
    from horovod_tpu import metrics

    return metrics.get_gauge("model.moe.kernel_layers")
