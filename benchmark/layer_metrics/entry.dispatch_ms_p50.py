"""Median time the host spends inside ``step(...)`` — the enqueue — from
the benchmark's span around the call."""

from benchmark.harness import percentile


def read(run):
    p = percentile(run.dispatch_seconds, 50)
    return None if p is None else 1e3 * p
