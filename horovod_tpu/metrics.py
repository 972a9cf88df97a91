"""Metric averaging across ranks + the process-local metrics registry.

Reference: ``MetricAverageCallback`` (``horovod/_keras/callbacks.py:49``)
allreduce-averages epoch metrics so every rank logs the same numbers.

The registry is the observability surface for the fault-tolerance and
hot-path instrumentation (``faults.py`` / ``utils/retry.py`` /
``elastic/`` / ``ops/eager.py``): three metric families, all
process-local (the elastic driver and each worker keep their own) and
deliberately dependency-free so the runner can bump them before any
mesh exists:

* **counters** — monotonically increasing (``retry.*.attempts``,
  ``elastic.blacklist``, ``collective.allreduce.bytes``, ...)
* **gauges** — last-write-wins values, optionally labeled
  (``stall.stalled{op="allreduce.grad"}``)
* **histograms** — fixed-bucket distributions (per-collective dispatch
  latency, retry attempt latency, checkpoint write/restore time,
  ``remesh.phase_seconds``)

The zero-downtime remesh (``elastic/remesh.py``) reports through the
``remesh.*`` family: worker-side ``remesh.{attempts,success,fallback,
shed,joins}`` + per-phase ``remesh.phase.<name>`` counters, driver-side
``remesh.driver_{attempts,success,fallback}``, and the
``remesh.phase_seconds`` histogram — the counters a
kill-and-resize postmortem reads first (docs/fault_tolerance.md).

Two export renderers: :func:`render_prometheus` (text exposition
format, ``hvd_tpu_`` family prefix, scraped by the elastic driver's
``/metrics`` endpoint — ``runner/telemetry_http.py``) and
:func:`snapshot` / :func:`render_json` (the JSON form workers push
through the KV store).
"""

from __future__ import annotations

import collections
import contextlib
import json
import threading
from bisect import bisect_left
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from . import runtime
from .process_sets import ProcessSet

_counter_lock = threading.Lock()
_counters: Dict[str, int] = {}
# gauge key: (name, tuple(sorted(labels.items()))) -> float
_gauges: Dict[Tuple[str, Tuple[Tuple[str, str], ...]], float] = {}
_histograms: Dict[str, "_Histogram"] = {}

# Default bucket ladders (seconds / bytes), Prometheus-conventional.
LATENCY_BUCKETS: Tuple[float, ...] = (
    0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
    0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0, 60.0,
)
BYTES_BUCKETS: Tuple[float, ...] = (
    1 << 10, 1 << 14, 1 << 18, 1 << 20, 1 << 22, 1 << 24,
    1 << 26, 1 << 28, 1 << 30,
)


# Gauges whose value the device computes inside a compiled step
# (``trace_gauge``): the bags open while a step is traced, innermost
# last, and the device arrays of finished calls not yet read.
_trace_bags: List[Dict[str, Any]] = []
_deferred: "collections.deque" = collections.deque(maxlen=8)


@contextlib.contextmanager
def traced_gauges():
    """Collects what the function traced under it gives to
    :func:`trace_gauge`: yields the dict, name -> traced value.
    ``TrainStep`` opens one around the loss function and returns the
    values with the step's outputs."""
    bag: Dict[str, Any] = {}
    _trace_bags.append(bag)
    try:
        yield bag
    finally:
        _trace_bags.pop()


def trace_gauge(name: str, value) -> None:
    """A gauge set from inside a traced step: ``value`` is a traced
    scalar at the level of the loss (not inside a ``jax.checkpoint`` or a
    loop), and reaches the registry once a call of the compiled step has
    computed it.  Outside a collecting step it is dropped."""
    if _trace_bags:
        _trace_bags[-1][name] = value


def defer_gauges(values: Dict[str, Any]) -> None:
    """Device scalars of a call just enqueued, for
    :func:`fold_ready_gauges`; only the newest few calls are kept."""
    if values:
        _deferred.append(values)


def fold_ready_gauges() -> None:
    """Sets the gauges of every deferred call the device has finished,
    oldest first, and waits for none."""
    while _deferred and all(v.is_ready() for v in _deferred[0].values()):
        # one fetch for the call's scalars together
        for name, value in jax.device_get(_deferred.popleft()).items():
            set_gauge(name, float(value))


class _Histogram:
    """Fixed upper-bound buckets + sum + count (no lock of its own:
    every mutation happens under the module lock)."""

    __slots__ = ("bounds", "counts", "sum", "count")

    def __init__(self, bounds: Sequence[float]):
        self.bounds: Tuple[float, ...] = tuple(sorted(bounds))
        self.counts: List[int] = [0] * (len(self.bounds) + 1)  # +inf slot
        self.sum = 0.0
        self.count = 0

    def observe(self, value: float) -> None:
        self.counts[bisect_left(self.bounds, value)] += 1
        self.sum += value
        self.count += 1

    def quantile(self, q: float) -> Optional[float]:
        return hist_quantile(self.to_dict(), q)

    def to_dict(self) -> Dict[str, Any]:
        return {
            "buckets": list(self.bounds),
            "counts": list(self.counts),
            "sum": self.sum,
            "count": self.count,
        }


def hist_quantile(hist: Dict[str, Any], q: float) -> Optional[float]:
    """Quantile estimate from a fixed-bucket histogram dict (the
    ``to_dict`` / snapshot shape) by linear interpolation inside the
    bucket the target rank lands in — the standard Prometheus
    ``histogram_quantile`` estimator.  Observations beyond the last
    finite bound clamp to it (no interpolation toward +inf).  ``None``
    on an empty histogram."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"quantile must be in [0, 1], got {q}")
    bounds = hist.get("buckets") or []
    counts = hist.get("counts") or []
    total = hist.get("count", 0)
    if total <= 0 or not bounds:
        return None
    target = q * total
    cumulative = 0
    lo = 0.0
    for bound, n in zip(bounds, counts):
        if n > 0 and cumulative + n >= target:
            frac = (target - cumulative) / n
            return lo + (float(bound) - lo) * frac
        cumulative += n
        lo = float(bound)
    return float(bounds[-1])


def inc_counter(name: str, value: int = 1) -> int:
    """Bump a process-local named counter; returns the new value.
    Dotted names namespace by subsystem (``retry.discovery.attempts``,
    ``elastic.blacklist``, ``checkpoint.fallback``, ...)."""
    with _counter_lock:
        _counters[name] = _counters.get(name, 0) + value
        return _counters[name]


def get_counter(name: str) -> int:
    with _counter_lock:
        return _counters.get(name, 0)


def get_counters(prefix: str = "") -> Dict[str, int]:
    """Snapshot of all counters (optionally filtered by name prefix)."""
    with _counter_lock:
        return {
            k: v for k, v in sorted(_counters.items())
            if k.startswith(prefix)
        }


def reset_counters(prefix: str = "") -> None:
    """Clear counters (optionally only those under ``prefix``) — test
    isolation hook.  Gauges and histograms under the prefix clear too
    (one reset hook covers the whole registry)."""
    with _counter_lock:
        for store in (_counters, _histograms):
            if not prefix:
                store.clear()
            else:
                for k in [k for k in store if k.startswith(prefix)]:
                    del store[k]
        for key in [k for k in _gauges if k[0].startswith(prefix)]:
            del _gauges[key]


def set_gauge(name: str, value: float,
              labels: Optional[Dict[str, str]] = None) -> None:
    """Set a last-write-wins gauge.  ``labels`` makes one family carry
    several series (e.g. the stall inspector's currently-stalled op
    names, one series per op)."""
    key = (name, tuple(sorted((labels or {}).items())))
    with _counter_lock:
        _gauges[key] = float(value)


def get_gauge(name: str,
              labels: Optional[Dict[str, str]] = None) -> Optional[float]:
    key = (name, tuple(sorted((labels or {}).items())))
    with _counter_lock:
        return _gauges.get(key)


def clear_gauge(name: str) -> None:
    """Drop every series of a gauge family (used before re-publishing a
    membership-style gauge so stale labeled series disappear)."""
    with _counter_lock:
        for key in [k for k in _gauges if k[0] == name]:
            del _gauges[key]


def observe(name: str, value: float,
            buckets: Sequence[float] = LATENCY_BUCKETS) -> None:
    """Record one observation into the named histogram (created on
    first touch with ``buckets``; later calls reuse the existing
    ladder)."""
    with _counter_lock:
        hist = _histograms.get(name)
        if hist is None:
            hist = _histograms[name] = _Histogram(buckets)
        hist.observe(float(value))


def get_histogram(name: str) -> Optional[Dict[str, Any]]:
    with _counter_lock:
        hist = _histograms.get(name)
        return hist.to_dict() if hist else None


def histograms_by_prefix(
    prefix: str, snap: Optional[Dict[str, Any]] = None
) -> Dict[str, Dict[str, Any]]:
    """All histograms whose name starts with ``prefix`` (from a
    snapshot dict, or this process's live registry) — the extraction
    the trace straggler detector reads per-phase summaries through
    (``trace.phase_seconds.*``)."""
    if snap is not None:
        hists = snap.get("histograms", {})
        return {k: v for k, v in hists.items() if k.startswith(prefix)}
    with _counter_lock:
        return {
            k: h.to_dict() for k, h in sorted(_histograms.items())
            if k.startswith(prefix)
        }


def gauges_by_prefix(
    prefix: str, snap: Optional[Dict[str, Any]] = None
) -> List[Dict[str, Any]]:
    """All gauges whose name starts with ``prefix``, as
    ``[{name, labels, value}]`` rows (from a snapshot dict, or this
    process's live registry) — the extraction ``GET /prof`` folds
    per-rank ``prof.*`` gauges through."""
    if snap is not None:
        return [
            g for g in snap.get("gauges", [])
            if str(g.get("name", "")).startswith(prefix)
        ]
    with _counter_lock:
        return [
            {"name": k[0], "labels": dict(k[1]), "value": v}
            for k, v in sorted(_gauges.items())
            if k[0].startswith(prefix)
        ]


def quantile(name: str, q: float) -> Optional[float]:
    """Interpolated quantile of the named histogram (p50: ``q=0.5``,
    p99: ``q=0.99``); None when the histogram is absent or empty.  The
    extraction the topology fitter reads measured per-cell latencies
    through (``topo/fit.py``)."""
    with _counter_lock:
        hist = _histograms.get(name)
        if hist is None:
            return None
        snap = hist.to_dict()
    return hist_quantile(snap, q)


def snapshot() -> Dict[str, Any]:
    """JSON-serializable snapshot of the whole registry — the payload
    elastic workers push to the driver through the KV store."""
    with _counter_lock:
        return {
            "counters": dict(sorted(_counters.items())),
            "gauges": [
                {"name": k[0], "labels": dict(k[1]), "value": v}
                for k, v in sorted(_gauges.items())
            ],
            "histograms": {
                k: h.to_dict() for k, h in sorted(_histograms.items())
            },
        }


def render_json() -> str:
    return json.dumps(snapshot(), sort_keys=True)


def _prom_name(name: str) -> str:
    return "".join(
        c if c.isalnum() or c == "_" else "_" for c in name
    )


def _prom_labels(labels: Dict[str, str]) -> str:
    if not labels:
        return ""
    def esc(v: Any) -> str:
        return str(v).replace("\\", "\\\\").replace('"', '\\"')
    inner = ",".join(
        f'{_prom_name(k)}="{esc(v)}"' for k, v in sorted(labels.items())
    )
    return "{" + inner + "}"


def render_prometheus(snap: Optional[Dict[str, Any]] = None,
                      prefix: str = "hvd_tpu",
                      extra_labels: Optional[Dict[str, str]] = None) -> str:
    """Prometheus text exposition of a registry snapshot (this
    process's by default).  ``extra_labels`` stamps every series — the
    driver uses ``{"rank": "<r>"}`` to fold worker pushes into one
    scrape without name collisions."""
    snap = snap if snap is not None else snapshot()
    base = dict(extra_labels or {})
    lines: List[str] = []
    for name, value in snap.get("counters", {}).items():
        fam = f"{prefix}_{_prom_name(name)}_total"
        lines.append(f"# TYPE {fam} counter")
        lines.append(f"{fam}{_prom_labels(base)} {value}")
    for g in snap.get("gauges", []):
        fam = f"{prefix}_{_prom_name(g['name'])}"
        lines.append(f"# TYPE {fam} gauge")
        lines.append(
            f"{fam}{_prom_labels({**base, **g.get('labels', {})})} "
            f"{g['value']}"
        )
    for name, h in snap.get("histograms", {}).items():
        fam = f"{prefix}_{_prom_name(name)}"
        lines.append(f"# TYPE {fam} histogram")
        cumulative = 0
        for bound, n in zip(h["buckets"], h["counts"]):
            cumulative += n
            lines.append(
                f"{fam}_bucket{_prom_labels({**base, 'le': repr(float(bound))})} "
                f"{cumulative}"
            )
        lines.append(
            f"{fam}_bucket{_prom_labels({**base, 'le': '+Inf'})} "
            f"{h['count']}"
        )
        # Pre-computed quantile estimates (summary-style lines): what a
        # dashboard without PromQL — or the topology fitter reading a
        # scrape — needs from the fixed-bucket ladder.
        for q in (0.5, 0.99):
            est = hist_quantile(h, q)
            if est is not None:
                lines.append(
                    f"{fam}{_prom_labels({**base, 'quantile': str(q)})} "
                    f"{est}"
                )
        lines.append(f"{fam}_sum{_prom_labels(base)} {h['sum']}")
        lines.append(f"{fam}_count{_prom_labels(base)} {h['count']}")
    return "\n".join(lines) + "\n"


def metric_average(value: Any, process_set: Optional[ProcessSet] = None) -> Any:
    """Average a host-side scalar (or pytree of scalars) across processes.

    Single-process worlds return the value unchanged (each metric is
    already global).  With ``process_set``, only processes owning a rank
    in the set participate; processes outside it get their value back
    unchanged (mirroring the reference's process_set-scoped collectives).
    """
    rt = runtime.get_runtime()
    if rt.process_count == 1:
        return value

    from jax.experimental import multihost_utils

    if process_set is None:
        member_procs = list(range(rt.process_count))
    else:
        member_procs = sorted(
            {rt.devices[r].process_index for r in process_set.ranks}
        )
    leaves, treedef = jax.tree.flatten(value)
    arr = np.asarray([float(l) for l in leaves], dtype=np.float64)
    gathered = np.asarray(multihost_utils.process_allgather(arr))
    if rt.process_rank not in member_procs:
        return value
    mean = gathered[member_procs].mean(axis=0)
    return jax.tree.unflatten(treedef, [float(m) for m in mean])
