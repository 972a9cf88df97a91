"""Median time a step waited for "the next batch is on the device" (the
benchmark's span around the generator's hand-over)."""

from benchmark.harness import percentile


def read(run):
    p = percentile(run.wait_seconds, 50)
    return None if p is None else 1e3 * p
