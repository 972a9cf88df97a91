"""Median duration of the program's ``hvd_train_step`` span: the call of
the step's ``Compiled``, which enqueues it."""

from benchmark.trace import spans


def read(run):
    return spans.duration_ms_p50(run, "hvd_train_step")
