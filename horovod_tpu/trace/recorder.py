"""Flight recorder: the last N steps' span trees, dumped on anomaly.

The reference's stall check tells you a collective is stuck *now*; a
postmortem needs what happened *just before*.  The recorder keeps a
bounded ring of the most recent steps' span trees (plus background
spans from the service loop) per rank, and writes the whole ring to
``HVD_TPU_TRACE_DIR`` when something anomalous happens:

* **slow step** — step time exceeding ``HVD_TPU_TRACE_ANOMALY_Z`` x
  the rolling p50 of recent steps (the z-test a human eyeballing a
  step-time plot runs).  The step time is the interval the step span
  carries (``interval_s``: ``TrainStep``'s entry to entry), not the
  span's own duration, which is the dispatch; a span that carries
  none is judged on its duration;
* **fault site** — any armed :mod:`horovod_tpu.faults` injection
  firing (``trace/__init__.on_fault``), so a scripted game-day run
  leaves span evidence of the window around the fault;
* **remesh** — a membership change pausing survivors
  (``elastic/remesh.py``);
* **service death** — the async exchange service degrading to inline
  dispatch (``svc/service.py`` ``_kill``).

Without ``HVD_TPU_TRACE_DIR`` the dump stays in memory (the last one
is queryable — ``last_dump()`` — and counted), so fault-heavy test
suites pay no file IO.  ``trace.anomaly_dumps`` counts dumps;
``trace.last_anomaly_dump`` gauges the latest dump index, which the
driver's ``/trace`` endpoint surfaces per rank.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from ..utils import env
from .tracer import step_seconds

DEFAULT_RING = 16
DEFAULT_Z = 3.0
# Rolling window the p50 baseline is computed over, and the minimum
# history before the z-test can fire (a compile-slow first step must
# not dump an empty ring).
_BASELINE_WINDOW = 64
_MIN_HISTORY = 5
# Ignore sub-10ms excursions outright: on a fast CPU loop the p50 can
# be microseconds and z x p50 would flag scheduler jitter.
_MIN_EXCESS_S = 0.010


def ring_size() -> int:
    return max(1, env.get_int(env.TRACE_RING, DEFAULT_RING))


def anomaly_z() -> float:
    return max(1.0, env.get_float(env.TRACE_ANOMALY_Z, DEFAULT_Z))


def trace_dir() -> Optional[str]:
    return env.get_env(env.TRACE_DIR) or None


DEFAULT_DUMP_KEEP = 64


def dump_keep() -> int:
    """On-disk retention: newest N dumps kept per rank (0 = unbounded)."""
    return max(0, env.get_int(env.TRACE_DUMP_KEEP, DEFAULT_DUMP_KEEP))


class FlightRecorder:
    """Per-process ring of recent step span trees + anomaly dumps."""

    def __init__(self, capacity: Optional[int] = None):
        cap = ring_size() if capacity is None else int(capacity)
        self._lock = threading.Lock()
        self._ring: deque = deque(maxlen=cap)
        self._background: deque = deque(maxlen=cap)
        self._durs: deque = deque(maxlen=_BASELINE_WINDOW)
        self._dump_seq = 0
        self._last_dump: Optional[Dict[str, Any]] = None
        self._last_dump_path: Optional[str] = None

    # ------------------------------------------------------- ingestion

    def on_step(self, span) -> None:
        """Record one finished step tree; run the slow-step check
        against the rolling p50 of the steps before it, on the step's
        time (:func:`step_seconds`)."""
        from .. import metrics

        step_s = step_seconds(span)
        with self._lock:
            baseline = sorted(self._durs)
            self._ring.append({
                "kind": "step",
                "step": span.attrs.get("step_num") if span.attrs else None,
                "wall_ts": time.time(),
                "dur_s": span.dur,
                "step_s": step_s,
                "spans": span.to_dict(),
            })
            self._durs.append(step_s)
        metrics.inc_counter("trace.steps")
        if len(baseline) >= _MIN_HISTORY:
            p50 = baseline[len(baseline) // 2]
            z = anomaly_z()
            if step_s > z * p50 and step_s - p50 > _MIN_EXCESS_S:
                self.dump(
                    "slow_step",
                    step_seconds=step_s, rolling_p50=p50, z=z,
                )

    def on_background(self, span) -> None:
        """Root spans finalized outside any step (the service loop's
        dispatch spans): ring alongside the steps, FIFO like them."""
        with self._lock:
            self._background.append({
                "kind": "background",
                "wall_ts": time.time(),
                "dur_s": span.dur,
                "spans": span.to_dict(),
            })

    # ----------------------------------------------------------- dumps

    def dump(self, reason: str, **detail: Any) -> Optional[str]:
        """Write the ring (steps + background spans) as one JSON dump;
        returns the file path, or None when no ``HVD_TPU_TRACE_DIR`` is
        configured (the dump is still retained in memory and counted).
        Never raises — the recorder must not take down the path it
        observes."""
        from .. import events, metrics
        from .context import _rank

        from .tracer import get_tracer

        tracer = get_tracer()
        with self._lock:
            if not self._ring and not self._background:
                return None
            self._dump_seq += 1
            seq = self._dump_seq
            payload = {
                "reason": reason,
                "detail": detail,
                "rank": _rank(),
                "seq": seq,
                "wall_ts": time.time(),
                # Clock anchor (mono zero <-> wall epoch, the Timeline
                # scheme): lets merge_timeline.py re-base the dump's
                # monotonic span times onto the shared wall clock.
                "mono0": tracer.mono0,
                "epoch_wall_us": tracer.epoch_wall_us,
                "steps": list(self._ring),
                "background": list(self._background),
            }
            self._last_dump = payload
        metrics.inc_counter("trace.anomaly_dumps")
        metrics.inc_counter(f"trace.anomaly_dumps.{reason.split(':')[0]}")
        metrics.set_gauge("trace.last_anomaly_dump", seq)
        path: Optional[str] = None
        d = trace_dir()
        if d:
            try:
                os.makedirs(d, exist_ok=True)
                path = os.path.join(
                    d, f"flight_rank{payload['rank']}_{seq}.json"
                )
                tmp = path + ".tmp"
                with open(tmp, "w") as fh:
                    json.dump(payload, fh, default=str)
                os.replace(tmp, path)
                self._prune_dumps(d, payload["rank"])
            except OSError as e:
                from ..utils.logging import get_logger

                get_logger().warning("flight-recorder dump failed: %s", e)
                path = None
        with self._lock:
            self._last_dump_path = path
        events.emit(
            events.TRACE_ANOMALY, reason=reason, seq=seq, path=path,
            **{k: v for k, v in detail.items()
               if isinstance(v, (int, float, str))},
        )
        return path

    @staticmethod
    def _prune_dumps(d: str, rank: Any) -> None:
        """Oldest-first retention on this rank's on-disk dumps: a
        long-running chaos-heavy job must not grow ``HVD_TPU_TRACE_DIR``
        without bound.  Keeps the newest ``HVD_TPU_TRACE_DUMP_KEEP``
        (0 = unbounded); pruned files count into
        ``trace.dumps_pruned``.  Never raises."""
        keep = dump_keep()
        if keep <= 0:
            return
        import re

        prefix = f"flight_rank{rank}_"
        found: List[tuple] = []
        try:
            for name in os.listdir(d):
                if not (name.startswith(prefix) and name.endswith(".json")):
                    continue
                m = re.match(re.escape(prefix) + r"(\d+)\.json$", name)
                if m:
                    found.append((int(m.group(1)), name))
        except OSError:
            return
        if len(found) <= keep:
            return
        found.sort()
        pruned = 0
        for _, name in found[:-keep]:
            try:
                os.remove(os.path.join(d, name))
                pruned += 1
            except OSError:
                pass
        if pruned:
            from .. import metrics

            metrics.inc_counter("trace.dumps_pruned", pruned)

    # ------------------------------------------------------ inspection

    def steps(self) -> List[Dict[str, Any]]:
        with self._lock:
            return list(self._ring)

    def last_dump(self) -> Optional[Dict[str, Any]]:
        with self._lock:
            return self._last_dump

    def last_dump_path(self) -> Optional[str]:
        with self._lock:
            return self._last_dump_path

    @property
    def dump_seq(self) -> int:
        with self._lock:
            return self._dump_seq

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


_recorder: Optional[FlightRecorder] = None
_recorder_lock = threading.Lock()


def get_recorder() -> FlightRecorder:
    global _recorder
    with _recorder_lock:
        if _recorder is None:
            _recorder = FlightRecorder()
        return _recorder


def reset() -> None:
    global _recorder
    with _recorder_lock:
        _recorder = None


def trigger_dump(reason: str, **detail: Any) -> Optional[str]:
    """External anomaly trigger (fault sites, remesh, service death):
    dump the current ring if there is one.  Safe to call from any
    thread, never raises."""
    try:
        if not _has_data():
            return None
        return get_recorder().dump(reason, **detail)
    except Exception:  # pragma: no cover - defensive
        return None


def _has_data() -> bool:
    rec = _recorder
    return rec is not None and (len(rec) > 0 or len(rec._background) > 0)
