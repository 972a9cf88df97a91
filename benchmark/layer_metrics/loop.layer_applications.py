"""Layers the model called in one trace of its forward pass: the program's
gauge ``model.layer_applications`` (``models/transformer.py``), layers
times passes of the loop.  None from a program without the gauge."""


def read(run):
    from horovod_tpu import metrics

    return metrics.get_gauge("model.layer_applications")
