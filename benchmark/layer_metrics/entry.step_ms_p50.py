"""Median gap between step completions inside the window (host clock)."""

from benchmark.harness import percentile


def read(run):
    p = percentile(run.step_seconds(), 50)
    return None if p is None else 1e3 * p
