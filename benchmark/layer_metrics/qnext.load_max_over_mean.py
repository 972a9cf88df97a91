"""The busiest held expert's pairs over the mean, over every expert layer
of a step of a Qwen3-Next cell: the program's gauge
``model.moe.load_max_over_mean``.  None from a program without it."""


def read(run):
    from horovod_tpu import metrics

    return metrics.get_gauge("model.moe.load_max_over_mean")
