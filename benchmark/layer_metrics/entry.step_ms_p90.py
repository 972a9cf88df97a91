"""90th percentile of the gaps between step completions inside the window
(host clock): with a hundred steps, ten lie beyond it."""

from benchmark.harness import percentile


def read(run):
    p = percentile(run.step_seconds(), 90)
    return None if p is None else 1e3 * p
