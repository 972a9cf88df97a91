"""Median duration of the program's ``hvd_step_resolve`` span: finding the
step's variant and its ``Compiled`` (state specs, tree structures, the
argument signature over every leaf of the carried state)."""

from benchmark.trace import spans


def read(run):
    return spans.duration_ms_p50(run, "hvd_step_resolve")
