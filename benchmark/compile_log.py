"""Every XLA program this process builds, from jax's own monitoring
events (a copy of ``chip_smoke.CompileLog``, with the time of each event so
that the ones inside the measured window can be counted)."""

from __future__ import annotations

import time
from typing import List, Tuple

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"


class CompileLog:
    """While the block is open: ``builds`` holds (end time, seconds) of
    each backend compile — a read from the persistent cache counts as one,
    it is just short —, ``reads`` the times the persistent cache served a
    program and ``writes`` the times it took one in.  Times are
    ``time.perf_counter()``."""

    def __init__(self):
        self.builds: List[Tuple[float, float]] = []
        self.reads: List[float] = []
        self.writes: List[float] = []

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.builds.append((time.perf_counter(), seconds))

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.reads.append(time.perf_counter())
        elif event == _CACHE_MISS:  # recorded when the entry is written
            self.writes.append(time.perf_counter())

    def __enter__(self) -> "CompileLog":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)

    def builds_between(self, start: float, end: float) -> int:
        """Backend compiles (cache reads included) that ended in
        [start, end]."""
        return sum(1 for t, _ in self.builds if start <= t <= end)
