"""Online MFU: cost-analysis FLOPs over measured step time.

ROADMAP item 3's ResNet MFU >= 0.30 target was argued from bench
guesses (analytic FLOPs/image x images/sec); this module computes the
same ratio online from what XLA says the step actually does.  Per
finalized step span:

* the ``exec`` spans in the tree name the introspected programs that
  ran (``prof/introspect.py`` stamps each executor call with its
  program key);
* each program's cost-analysis FLOPs divided by the step time — the
  rolling p50 of the spans' entry-to-entry intervals
  (``prof/hostgap.py``; this step's own where there is no history),
  never the dispatch, and not one held-up step alone — against the device peak
  from :mod:`prof.peak` (the shared datasheet table), becomes
  ``prof.mfu{workload=...}``.  XLA's count is of the program it built
  (recomputation counted, the inside of a Mosaic kernel not), per
  device.  The value is not clamped: above 1 it is a fault to see (a
  wrong peak, a wrong clock), not to hide;
* total step FLOPs split across tenants proportionally to each
  tenant's device-busy seconds (the host-gap attribution's
  ``tenant_busy_s``) becomes ``prof.mfu{tenant=...}`` — device-time
  accounting through the same trace tenant slot the arbiter's
  fairness story uses.

Backends whose ``cost_analysis`` is unavailable never register FLOPs,
and a CPU backend has no peak, so there every gauge here stays absent.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional

from .. import metrics
from . import introspect, peak
from .config import enabled

_lock = threading.Lock()
# Last computed per-workload MFU (the sentinel's observed MFU reads
# the max over workloads — "the" workload in a single-model process).
_last_mfu: Dict[str, float] = {}


def on_step(span: Any, stats: Dict[str, Any]) -> None:
    """Price one finalized step; called by ``hostgap.on_step``.  Never
    raises past its own guard — MFU is observability, not a step
    dependency."""
    if not enabled():
        return
    wall = stats.get("step_p50_s") or stats.get("step_s") or 0.0
    if wall <= 0:
        return
    per_workload: Dict[str, float] = {}
    total_flops = 0.0
    for s in span.walk():
        if s.phase != "exec":
            continue
        rec = introspect.get(s.attrs.get("program") if s.attrs else None)
        if not rec or not rec.get("flops"):
            continue
        w = rec.get("workload") or rec.get("kind") or "unknown"
        per_workload[w] = per_workload.get(w, 0.0) + rec["flops"]
        total_flops += rec["flops"]
    if total_flops <= 0:
        return
    resolved = peak.default_peak_tflops()
    if resolved is None:
        return
    peak_tflops, _source = resolved
    if peak_tflops <= 0:
        return
    denom = wall * peak_tflops * 1e12
    with _lock:
        for w, fl in per_workload.items():
            v = fl / denom
            metrics.set_gauge("prof.mfu", v, {"workload": w})
            _last_mfu[w] = v
    metrics.set_gauge("prof.flops_per_step", total_flops)
    tenant_busy = stats.get("tenant_busy_s") or {}
    busy_total = sum(tenant_busy.values())
    if busy_total > 0:
        for tenant, busy in tenant_busy.items():
            share = busy / busy_total
            metrics.set_gauge(
                "prof.mfu", total_flops * share / denom,
                {"tenant": tenant},
            )


def publish(workload: str, achieved_tflops: float,
            peak_tflops: Optional[float] = None) -> Optional[float]:
    """Direct MFU publication for an offline measurement, so it shows
    up on ``/prof`` like any online workload.  None when no peak is
    given and the device has none."""
    if peak_tflops is None:
        resolved = peak.default_peak_tflops()
        if resolved is None:
            return None
        peak_tflops = resolved[0]
    if peak_tflops <= 0:
        return None
    v = achieved_tflops / peak_tflops
    metrics.set_gauge("prof.mfu", v, {"workload": workload})
    with _lock:
        _last_mfu[workload] = v
    return v


def last() -> Dict[str, float]:
    """Last computed per-workload MFU values (a copy)."""
    with _lock:
        return dict(_last_mfu)


def observed() -> Optional[float]:
    """The sentinel's scalar: max MFU over workloads, or None."""
    with _lock:
        return max(_last_mfu.values()) if _last_mfu else None


def reset() -> None:
    with _lock:
        _last_mfu.clear()
