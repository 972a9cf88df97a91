"""Median time from one ``hvd_step`` span's start to the next on the
profiler's host plane: the program's own step clock (what it observes as
``train.step_seconds``), which should equal ``entry.step_ms_p50``."""

from benchmark.trace import spans


def read(run):
    return spans.interval_ms_p50(run, "hvd_step")
