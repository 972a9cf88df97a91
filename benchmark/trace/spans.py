"""The program's own spans on the profiler's host plane.

``horovod_tpu/trace`` enters a ``jax.profiler.TraceAnnotation`` named
``hvd_<span>`` with every span it opens, so a traced run holds them on
``/host:CPU`` on the clock of the device planes.  Everything here reads
those events from a reduced trace (``reduce.Reduced``) inside the first
chip's steady window, and gives None where the program made no such span —
a program from before the spans existed, or a level that makes none.
Nothing here reads a clock of its own, and nothing is imported from the
program.
"""

from __future__ import annotations

from typing import List, Optional

from ..harness import percentile, say
from . import xplane


def events(reduced, name: str) -> List[xplane.Event]:
    """The host plane's events called ``name`` that start inside the first
    chip's steady window, by start.  An annotation's keyword arguments may
    ride behind a ``#`` in the event's name.  Finding none is said on an
    earlier line of the run's output."""
    host = reduced.trace.host()
    found = []
    if host is not None and reduced.chips:
        lo, hi = reduced.chips[0].window
        found = [
            e for line in host.lines.values() for e in line
            if lo <= e.start < hi
            and (e.name == name or e.name.startswith(name + "#"))
        ]
    if not found:
        say(f"program_spans.{name}", "none in the steady window")
    return sorted(found, key=lambda e: e.start)


def duration_ms_p50(run, name: str) -> Optional[float]:
    """Median duration of the spans called ``name``, in ms."""
    reduced = run.reduced()
    if reduced is None:
        return None
    p50 = percentile([e.end - e.start for e in events(reduced, name)], 50)
    return None if p50 is None else p50 / 1e6


def interval_ms_p50(run, name: str) -> Optional[float]:
    """Median time from the start of one span called ``name`` to the start
    of the next, in ms."""
    reduced = run.reduced()
    if reduced is None:
        return None
    starts = [e.start for e in events(reduced, name)]
    p50 = percentile([b - a for a, b in zip(starts, starts[1:])], 50)
    return None if p50 is None else p50 / 1e6
