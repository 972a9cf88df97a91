"""The program's own MFU gauge, ``prof.mfu{workload=train_step}``: XLA's
operation count of the compiled step over the program's step clock and
the chip's peak.  In percent.  It differs from ``mfu`` by XLA's count
against the required one (recomputation counted, the inside of a kernel
not)."""


def read(run):
    from horovod_tpu import metrics

    value = metrics.get_gauge("prof.mfu", {"workload": "train_step"})
    return None if value is None else 100.0 * value
