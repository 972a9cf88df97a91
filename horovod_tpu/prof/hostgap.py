"""Host-gap profiler: how much of a step call does the host spend
outside every executor call, and how long is a step?

ROADMAP's claim — "host round-trips per cycle bound small-step
throughput" — had no instrument.  This module is it.  Every finalized
step span tree (the tracer hands them over from ``_finalize_root``) is
attributed:

* **step time** = :func:`trace.tracer.step_seconds`: the entry-to-entry
  interval the span carries (``TrainStep`` gives it), or the span's own
  duration where it carries none.  The span's duration alone is the
  *dispatch* — a step returns futures —, a few ms of a 200 ms step on a
  chip.  The rolling p50 of the step time (:func:`step_p50`) is the
  sentinel's observed step time, and ``prof/mfu.py`` divides the step's
  FLOPs by it (``step_p50_s`` in the stats it is handed);
* **busy** = the union of intervals covered, inside the call, by
  executor-call and emission spans (``exec`` executor calls,
  ``dispatch``, ``exchange``/``bucket`` emission, and the
  ``rs_ici``/``ag_ici``/``dcn`` rail phases) — union, not sum, so
  pipelined/overlapped phases are not double counted;
* **gap** = the call's duration minus busy — host time inside the call
  that no executor call covers: scheduling, negotiation, bookkeeping
  and round-trips between dispatches, the time the single-dispatch
  refactor squeezes out of a multi-dispatch step.  It is host time, not
  device idle time: whether the *device* idles only a device trace can
  say (the benchmark's ``device.idle_share``);
* **dispatches** = call-shaped span count in the tree plus the delta
  of the service loop's ``svc.dispatches`` counter since the previous
  step — the per-step dispatch count whose target is 1.

Published per step: ``prof.host_gap_seconds`` (histogram),
``prof.host_gap_frac`` (gauge: the gap as a share of the step time, not
of the dispatch) + ``prof.dispatches_per_step`` (gauge), and a
``prof.dispatches_per_step_hist`` histogram on count buckets.  The
attribution itself (:func:`attribute`) is a pure function over a span
tree so the math is testable on synthetic trees.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, List, Optional, Tuple

from .. import metrics
from ..trace.tracer import step_seconds
from .config import check_every, enabled

# Span phases that cover an executor call or the emission of one (the
# host is then busy *for* the device or the wire).  The
# rail phases mirror trace.tracer.RAIL_PHASES; "exec"/"dispatch" are
# the executor-call and service-dispatch phases; "exchange"/"bucket"
# cover the sched/xir emission path.
DEVICE_PHASES = frozenset((
    "exec", "dispatch", "exchange", "bucket", "rs_ici", "ag_ici", "dcn",
))

# Dispatch counting looks only at the call-shaped phases, not at the
# rail sub-phases one dispatch fans into.
DISPATCH_PHASES = frozenset(("exec", "dispatch"))

COUNT_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128, 256, 512, 1024)

_lock = threading.Lock()
_state: Dict[str, Any] = {"svc_dispatches": None, "durs": [], "steps": 0}
_WINDOW = 256


def _union_seconds(intervals: List[Tuple[float, float]]) -> float:
    if not intervals:
        return 0.0
    intervals.sort()
    total, cur0, cur1 = 0.0, intervals[0][0], intervals[0][1]
    for t0, t1 in intervals[1:]:
        if t0 > cur1:
            total += cur1 - cur0
            cur0, cur1 = t0, t1
        else:
            cur1 = max(cur1, t1)
    return total + (cur1 - cur0)


def _count_dispatches(span: Any, *, root: bool = True) -> int:
    """Dispatch count of one span tree, aware of the single-dispatch
    step shape (``HVD_TPU_ONESTEP``): a span carrying a truthy
    ``onestep`` attr IS exactly one host round-trip — its one exec span
    covers exchange + update, so the subtree neither undercounts to 0
    (when the executor wrapper lost its exec span) nor double-counts
    the stitched epilogue.  Trees without ``onestep`` marks count every
    call-shaped (``exec``/``dispatch``) span, same as the flat walk
    this replaces."""
    attrs = span.attrs or {}
    if attrs.get("onestep") and (root or span.phase in DISPATCH_PHASES):
        # A marked step root or call-shaped span is one dispatch no
        # matter what nests under it; marked emission spans (phase
        # "exchange"/"bucket") are not round-trips and fall through.
        return 1
    n = 0 if root or span.phase not in DISPATCH_PHASES else 1
    for child in span.children:
        n += _count_dispatches(child, root=False)
    return n


def attribute(span: Any) -> Dict[str, Any]:
    """Pure busy/host-gap attribution of one step span tree.

    Returns ``{step_s, wall_s, busy_s, gap_s, dispatches,
    tenant_busy_s}``: ``step_s`` is the step's time
    (``trace.tracer.step_seconds``), ``wall_s`` the call's own duration
    that busy and gap split, and ``tenant_busy_s`` maps tenant name to
    that tenant's own busy-interval union — the split ``prof/mfu.py``
    prices per-tenant MFU with."""
    wall = span.dur
    intervals: List[Tuple[float, float]] = []
    per_tenant: Dict[str, List[Tuple[float, float]]] = {}
    dispatches = _count_dispatches(span)
    for s in span.walk():
        if s is span:
            continue
        phase = s.phase
        rail = s.attrs.get("rail") if s.attrs else None
        if phase not in DEVICE_PHASES and rail not in ("ici", "dcn"):
            continue
        # only leaves of the device-work subtree count as intervals;
        # a parent exec span already covers its rail children, and the
        # union makes nesting harmless anyway.
        iv = (s.t0, s.t1)
        intervals.append(iv)
        if s.tenant:
            per_tenant.setdefault(s.tenant, []).append(iv)
    busy = min(_union_seconds(intervals), wall) if wall > 0 else 0.0
    return {
        "step_s": step_seconds(span),
        "wall_s": wall,
        "busy_s": busy,
        "gap_s": max(wall - busy, 0.0),
        "dispatches": dispatches,
        "tenant_busy_s": {
            t: _union_seconds(ivs) for t, ivs in sorted(per_tenant.items())
        },
    }


def _svc_dispatch_delta() -> int:
    """How many service-loop dispatches landed since the last step —
    the async half of the dispatch count (the service thread's spans
    root their own trees, not the step's)."""
    current = metrics.get_counter("svc.dispatches") or 0
    with _lock:
        last = _state["svc_dispatches"]
        _state["svc_dispatches"] = current
    if last is None:
        return 0
    return max(current - last, 0)


def on_step(span: Any) -> Optional[Dict[str, Any]]:
    """Attribute one finalized step span and publish the gauges; the
    tracer calls this through ``prof.on_step_span``.  Returns the
    stats dict (tests read it), or None when profiling is off."""
    if not enabled():
        return None
    stats = attribute(span)
    stats["dispatches"] += _svc_dispatch_delta()
    metrics.observe("prof.host_gap_seconds", stats["gap_s"])
    if stats["step_s"] > 0:
        metrics.set_gauge(
            "prof.host_gap_frac",
            min(stats["gap_s"] / stats["step_s"], 1.0),
        )
    metrics.set_gauge("prof.dispatches_per_step", float(stats["dispatches"]))
    metrics.observe("prof.dispatches_per_step_hist", stats["dispatches"],
                    buckets=COUNT_BUCKETS)
    with _lock:
        durs = _state["durs"]
        durs.append(stats["step_s"])
        del durs[:-_WINDOW]
        _state["steps"] += 1
        steps = _state["steps"]
    # MFU is priced on the rolling p50, not on this step alone: one
    # held-up step (a checkpoint, a profiler stopping) must not read
    # as the job's utilization on the next scrape or sentinel check.
    stats["step_p50_s"] = step_p50()
    from . import mfu

    mfu.on_step(span, stats)
    cadence = check_every()
    if cadence and steps % cadence == 0:
        # Off the step path: the sentinel's baseline-store disk
        # roundtrip (and a possible capture start) runs on a
        # single-flight background thread, never in step-finalize.
        from . import baseline

        baseline.check_async()
    return stats


def step_p50() -> Optional[float]:
    """Rolling p50 of recent step times (entry-to-entry intervals
    where the step span carries one) — the sentinel's observed step
    time."""
    with _lock:
        durs = sorted(_state["durs"])
    if not durs:
        return None
    return durs[len(durs) // 2]


def summary() -> Dict[str, Any]:
    """The ``/prof`` host-gap block for this process."""
    return {
        "steps": _state["steps"],
        "step_p50_s": step_p50(),
        "host_gap_p50_s": metrics.quantile("prof.host_gap_seconds", 0.5),
        "host_gap_p99_s": metrics.quantile("prof.host_gap_seconds", 0.99),
        "host_gap_frac": metrics.get_gauge("prof.host_gap_frac"),
        "dispatches_per_step": metrics.get_gauge("prof.dispatches_per_step"),
    }


def reset() -> None:
    """Clear rolling state (test isolation)."""
    with _lock:
        _state["svc_dispatches"] = None
        _state["durs"] = []
        _state["steps"] = 0
