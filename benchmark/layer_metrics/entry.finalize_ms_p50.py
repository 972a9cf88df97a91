"""Median duration of the program's ``hvd_step_finalize`` span: folding
the finished step's span tree into histograms, the flight recorder and
the profiling plane — what the tracing itself costs a step."""

from benchmark.trace import spans


def read(run):
    return spans.duration_ms_p50(run, "hvd_step_finalize")
