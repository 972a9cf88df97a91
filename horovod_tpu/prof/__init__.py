"""Profiling plane (PR 17).

The trace package (PR 13) gives the host's spans; this package prices
them — what XLA built, how long a step is, what share of the device's
peak that is — the blind spot behind ROADMAP items 3 (MFU target argued
from bench guesses) and 4 (host round-trips claimed, never measured).
What the *device* did inside a step only a device trace says (the
benchmark's ``device.*`` metrics).  Four instruments, one knob
(``HVD_TPU_PROF``, default on):

* :mod:`prof.introspect` — every compiled executor (svc cache, train
  step, stale step) AOT-lowered so XLA cost/memory analysis and wall
  compile time land in ``prof.*`` series keyed by program signature;
* :mod:`prof.hostgap` — the step clock (the entry-to-entry interval
  the step span carries), the host time inside a step call that no
  executor call covers, and service dispatch counts, from the PR 13
  span trees (``prof.host_gap_seconds``, ``prof.dispatches_per_step``
  — ROADMAP item 4's before/after instrument);
* :mod:`prof.mfu` — cost-analysis FLOPs over measured step time
  against the shared device peak table (``prof.mfu`` per workload and
  per tenant);
* :mod:`prof.baseline` — persisted perf baselines on the
  ``ScheduleStore`` machinery, compared every N steps; a confirmed
  regression emits ``PROF_REGRESSION`` and opens a bounded
  ``jax.profiler`` capture window (:mod:`prof.capture`).

Everything is host-side: profiling inserts no ops into any compiled
program (an AOT-compiled call runs the same HLO as the jit call it
replaces), so ``on`` vs ``off`` losses are bitwise identical, and
``off`` restores the unwrapped executors exactly.  Served by ``GET
/prof`` (``runner/telemetry_http.py``).  See docs/observability.md.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from .config import enabled, set_enabled_override  # noqa: F401
from .introspect import program_key, wrap as wrap_executor  # noqa: F401


def on_step_span(span: Any) -> None:
    """Tracer hook: one finalized step span tree.  Drives host-gap,
    MFU, and the sentinel cadence.  Never raises — the tracer's
    finalize path must survive any profiling bug."""
    if not enabled():
        return
    try:
        from . import hostgap

        hostgap.on_step(span)
    except Exception:  # pragma: no cover - defensive
        pass


def note_emission(src: str, n_ops: int) -> None:
    """Emission-path hook (sched/execute, xir/interp): count collective
    programs emitted and their op fan-out per source — the static half
    of the dispatches-per-step story.  Never raises."""
    if not enabled():
        return
    try:
        from .. import metrics

        metrics.inc_counter("prof.emissions")
        metrics.set_gauge("prof.emitted_ops", float(n_ops), {"src": src})
    except Exception:  # pragma: no cover - defensive
        pass


def maybe_capture(reason: str) -> bool:
    """Open a bounded ``jax.profiler`` capture window (see
    :mod:`prof.capture`); the SLO watchdog calls this on a confirmed
    breach."""
    try:
        from . import capture

        return capture.maybe_capture(reason)
    except Exception:  # pragma: no cover - defensive
        return False


def _rails_view() -> Dict[str, Any]:
    """The ``/prof`` rail digest: the measured ``topo.rail_busy_frac``
    gauges keyed by canonical rail tag AND the resolved backend
    family's display label (gpu relabels ``ici``/``dcn`` to
    ``nvlink``/``ib``; on tpu the two spellings coincide), plus the
    label map itself so consumers never have to guess the family."""
    from .. import metrics
    from ..topo import model as topo_model

    try:
        labels = topo_model.rail_labels()
    except Exception:  # pragma: no cover - defensive
        labels = {"ici": "ici", "dcn": "dcn"}
    busy: Dict[str, Any] = {}
    for rail in ("ici", "dcn"):
        v = metrics.get_gauge("topo.rail_busy_frac", {"rail": rail})
        busy[rail] = v
        label = labels.get(rail, rail)
        if label != rail:
            busy[label] = v
    return {"labels": labels, "busy_frac": busy}


def _rank_view(snap: Dict[str, Any]) -> Dict[str, Any]:
    """The per-rank ``/prof`` digest from one worker's metrics
    snapshot (the existing KV push payload — no new wire format)."""
    from .. import metrics

    hists = metrics.histograms_by_prefix("prof.", snap)
    gap = hists.get("prof.host_gap_seconds")
    mfu_g: Dict[str, float] = {}
    tenant_mfu: Dict[str, float] = {}
    for g in metrics.gauges_by_prefix("prof.mfu", snap):
        labels = g.get("labels", {})
        if "workload" in labels:
            mfu_g[labels["workload"]] = g["value"]
        elif "tenant" in labels:
            tenant_mfu[labels["tenant"]] = g["value"]

    def gauge(name: str) -> Optional[float]:
        for g in metrics.gauges_by_prefix(name, snap):
            if g.get("name") == name and not g.get("labels"):
                return g["value"]
        return None

    return {
        "host_gap_p50_s": metrics.hist_quantile(gap, 0.5) if gap else None,
        "host_gap_p99_s": metrics.hist_quantile(gap, 0.99) if gap else None,
        "host_gap_frac": gauge("prof.host_gap_frac"),
        "dispatches_per_step": gauge("prof.dispatches_per_step"),
        "mfu": mfu_g,
        "tenant_mfu": tenant_mfu,
        "regression": gauge("prof.regression"),
        "compiles": snap.get("counters", {}).get("prof.compiles", 0),
        "emissions": snap.get("counters", {}).get("prof.emissions", 0),
    }


def prof_payload(
    per_rank: Optional[Dict[Any, Dict[str, Any]]] = None,
) -> Dict[str, Any]:
    """The ``GET /prof`` body: introspection table, host-gap summary,
    MFU, capture stats, and the sentinel's last verdict — plus a
    per-rank digest when the driver passes its KV snapshots.  Always
    returns a dict (the endpoint's empty-data-200 contract)."""
    from . import baseline, capture, hostgap, introspect, mfu, peak

    payload: Dict[str, Any] = {"enabled": enabled()}
    try:
        payload["programs"] = introspect.ranked()
        payload["host_gap"] = hostgap.summary()
        cached = peak.cached_peak()
        payload["mfu"] = {
            "workload": mfu.last(),
            "peak_tflops": cached[0] if cached else None,
            "peak_source": cached[1] if cached else None,
        }
        payload["rails"] = _rails_view()
        payload["capture"] = capture.stats()
        sentinel = baseline.get_sentinel()
        payload["baseline"] = {
            "db": sentinel.store.path if sentinel.store else None,
            "last": sentinel.last(),
        }
    except Exception as e:  # pragma: no cover - defensive
        payload["error"] = str(e)
    if per_rank:
        ranks: Dict[str, Any] = {}
        for rank, snap in sorted(per_rank.items(), key=lambda kv: str(kv[0])):
            try:
                ranks[str(rank)] = _rank_view(snap or {})
            except Exception:  # pragma: no cover - defensive
                ranks[str(rank)] = {"error": "unreadable snapshot"}
        payload["ranks"] = ranks
    return payload


def reset() -> None:
    """Clear every prof module's process state (test isolation)."""
    from . import baseline, capture, hostgap, introspect, mfu, peak

    introspect.reset()
    hostgap.reset()
    mfu.reset()
    baseline.reset()
    capture.reset()
    peak.reset()
