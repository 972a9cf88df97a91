"""Span tracer: the host-side clock on every exchange phase.

Horovod's ``HOROVOD_TIMELINE`` records one NEGOTIATE/QUEUE/op phase
span per tensor request (``timeline.cc``) — the artifact that lets an
operator say *where a slow step's time went*.  Our exchange path grew
the same stations one subsystem at a time (queue → negotiation → cache
→ lowering → rail execution, PRs 3–12) but kept only PR 2's inline
timers; this module adds the spans.

Mechanics: spans are **host-side** — they wrap Python work (queue
waits, the lowering pass, trace-time emission of rail phases), never
insert ops into a traced step, and therefore cannot perturb values;
``HVD_TPU_TRACE=off`` reduces every ``span()`` call to one shared
no-op object (zero allocation).  Nesting rides a thread-local stack:
a span opened while another is open on the same thread becomes its
child, so the step span (``TrainStep.__call__``) naturally parents the
exchange/bucket/rail spans emitted while the step traces.  Cross-
thread correlation (producer thread → service loop) uses the
:class:`~horovod_tpu.trace.context.TraceContext` carried by the
submission instead of the stack.

Every finalized root tree is:

* folded into the ``trace.phase_seconds.<phase>`` histograms (the
  per-rank summaries the heartbeat KV push ships to the driver's
  straggler detector — ``trace/straggler.py``);
* handed to the flight recorder (``trace/recorder.py``) for the
  last-N-steps anomaly ring;
* streamed to the per-rank Chrome trace at level ``full``
  (``trace/export.py``).

Step spans additionally derive the measured per-rail utilization
gauges ``topo.rail_busy_frac{rail=ici|dcn}`` from the rail-phase spans
(the pipeliner's overlap claims as a measurement, not a counter).

One clock with the device: entering a span also enters a
``jax.profiler.TraceAnnotation`` named ``hvd_<span name>`` (the span's
attributes ride as keyword arguments, never inside the name), so
whenever a profile is open — ``prof/capture.py``'s bounded window, a
``jax.profiler.trace`` of the user's — every program span sits on the
profiler's ``/host:CPU`` plane beside the device planes.  With no
profile open an annotation is a flag check; at ``off`` none is made.
"""

from __future__ import annotations

import atexit
import itertools
import threading
import time
from typing import Any, Dict, List, Optional

from jax.profiler import TraceAnnotation

from ..utils import env

LEVELS = ("off", "summary", "full")

# Phases with a rail attribution (the RailChain vocabulary,
# xir/pipeline.py): busy-fraction accounting groups spans by this map.
RAIL_PHASES = {"rs_ici": "ici", "ag_ici": "ici", "dcn": "dcn"}

_level_override: Optional[str] = None
_span_counter = itertools.count(1)


def set_level_override(level: Optional[str]) -> None:
    """Pin the trace level without touching the environment (the sched
    config-override pattern tests use)."""
    global _level_override
    if level is not None and level not in LEVELS:
        raise ValueError(f"trace level must be one of {LEVELS}, got {level!r}")
    _level_override = level


def level() -> str:
    """``HVD_TPU_TRACE`` policy: ``off`` | ``summary`` (default) |
    ``full``.  ``1/true/yes/on`` spell ``full`` (an explicit enable
    means you want the per-rank trace files)."""
    if _level_override is not None:
        return _level_override
    raw = (env.get_env(env.TRACE, "summary") or "summary").strip().lower()
    if raw in ("0", "false", "no", "none", ""):
        return "off"
    if raw in ("1", "true", "yes", "on"):
        return "full"
    if raw not in LEVELS:
        from ..utils.logging import get_logger

        get_logger().warning(
            "HVD_TPU_TRACE=%r is not one of %s; using 'summary'",
            raw, LEVELS,
        )
        return "summary"
    return raw


def enabled() -> bool:
    return level() != "off"


class Span:
    """One timed phase.  Times are ``time.monotonic()`` seconds; the
    wall anchor for cross-rank merging lives on the tracer (sampled
    back to back at startup, the Timeline scheme)."""

    __slots__ = ("name", "phase", "t0", "t1", "trace_id", "span_id",
                 "parent_id", "producer", "tenant", "attrs", "children")

    def __init__(self, name: str, phase: str, t0: float,
                 trace_id: str = "", span_id: str = "",
                 parent_id: str = "", producer: str = "",
                 tenant: str = "",
                 attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.phase = phase
        self.t0 = t0
        self.t1 = t0
        self.trace_id = trace_id
        self.span_id = span_id
        self.parent_id = parent_id
        self.producer = producer
        self.tenant = tenant
        self.attrs = attrs or {}
        self.children: List["Span"] = []

    @property
    def dur(self) -> float:
        return max(self.t1 - self.t0, 0.0)

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "name": self.name, "phase": self.phase,
            "t0": self.t0, "dur": self.dur,
        }
        if self.trace_id:
            d["trace_id"] = self.trace_id
        if self.span_id:
            d["span_id"] = self.span_id
        if self.parent_id:
            d["parent_id"] = self.parent_id
        if self.producer:
            d["producer"] = self.producer
        if self.tenant:
            d["tenant"] = self.tenant
        if self.attrs:
            d["attrs"] = dict(self.attrs)
        if self.children:
            d["children"] = [c.to_dict() for c in self.children]
        return d

    def walk(self):
        yield self
        for c in self.children:
            yield from c.walk()


def step_seconds(span: Span) -> float:
    """The step's time as a finished step span tells it: the
    entry-to-entry interval it carries (``interval_s``), or — a first
    step, a step of another caller than ``TrainStep``, a hand-made
    tree — its own duration."""
    interval = (span.attrs or {}).get("interval_s")
    return span.dur if interval is None else interval


class _NoopSpan:
    """The shared do-nothing span ``HVD_TPU_TRACE=off`` hands back —
    one module-level instance, so the off path allocates nothing."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


NOOP = _NoopSpan()


def annotation_name(name: str) -> str:
    """The profiler-side name of the span ``name``."""
    return "hvd_" + name


class _ActiveSpan:
    """Context manager around one live span on this thread's stack and
    the profiler annotation that puts it on the device trace's clock.
    A ``step`` span's finalization additionally feeds the flight
    recorder's anomaly check and the rail-utilization gauges."""

    __slots__ = ("_tracer", "_span", "_step", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span, step: bool = False):
        self._tracer = tracer
        self._span = span
        self._step = step
        self._annotation = None

    def __enter__(self) -> Span:
        span = self._span
        # The attributes are formatted only while a profile is open
        # (the annotation checks the profiler's flag first).
        self._annotation = TraceAnnotation(
            annotation_name(span.name), **span.attrs)
        self._annotation.__enter__()
        self._tracer._push(span)
        return span

    def __exit__(self, *exc):
        self._span.t1 = time.monotonic()
        # The annotation ends with the span: folding a finished step
        # (``_finalize_root``) is timed under a name of its own.
        self._annotation.__exit__(None, None, None)
        self._tracer._pop(self._span, step=self._step)
        return False


class Tracer:
    """Process-wide span collector (one per process, like the metrics
    registry — per-rank attribution happens at merge time)."""

    def __init__(self):
        from .context import _rank

        self._tl = threading.local()
        self._lock = threading.Lock()
        # Two clocks back to back: monotonic anchors span math, wall
        # anchors the cross-rank merge (the Timeline scheme).
        self.mono0 = time.monotonic()
        self.epoch_wall_us = time.time() * 1e6
        self.rank = _rank()
        self._writer = None
        self._writer_failed = False
        self._step_idx = 0

    # ----------------------------------------------------------- stack

    def _stack(self) -> List[Span]:
        st = getattr(self._tl, "stack", None)
        if st is None:
            st = self._tl.stack = []
        return st

    def _push(self, span: Span) -> None:
        st = self._stack()
        if st and not span.parent_id:
            span.parent_id = st[-1].span_id
            if not span.trace_id:
                span.trace_id = st[-1].trace_id
                span.producer = span.producer or st[-1].producer
        if st and not span.tenant:
            span.tenant = st[-1].tenant
        st.append(span)

    def _pop(self, span: Span, step: bool = False) -> None:
        st = self._stack()
        while st and st[-1] is not span:  # tolerate unbalanced exits
            st.pop()
        if st:
            st.pop()
        if st:
            st[-1].children.append(span)
        else:
            self._finalize_root(span, step=step)

    # ------------------------------------------------------------- API

    def span(self, name: str, phase: str, ctx=None, **attrs):
        """Open one span (context manager).  ``ctx`` — a TraceContext —
        pins correlation explicitly (cross-thread); otherwise the
        enclosing span on this thread (or the thread's installed
        context) supplies it."""
        from .context import current

        ctx = ctx if ctx is not None else current()
        sp = Span(
            name, phase, time.monotonic(),
            trace_id=getattr(ctx, "trace_id", ""),
            parent_id=getattr(ctx, "span_id", "") if ctx else "",
            producer=getattr(ctx, "producer", ""),
            tenant=getattr(ctx, "tenant", ""),
            span_id=f"s{next(_span_counter)}",
            attrs=attrs or None,
        )
        return _ActiveSpan(self, sp)

    def step(self, **attrs):
        """Open the per-step root span (``TrainStep.__call__`` wraps
        the whole call in one).  Finalization runs the flight
        recorder's anomaly check and publishes the per-rail busy
        fractions measured from the rail-phase spans underneath.

        The span's own duration is the *dispatch* (a step returns
        futures).  A caller that knows the step's time passes it as
        ``interval_s`` — ``TrainStep`` gives the time since its
        previous entry — and the recorder, the sentinel and MFU then
        run on that; a span without one keeps its own duration."""
        self._step_idx += 1
        sp = Span(
            "step", "step", time.monotonic(),
            span_id=f"s{next(_span_counter)}",
            attrs={"step_num": self._step_idx, **attrs},
        )
        return _ActiveSpan(self, sp, step=True)

    def record_complete(self, name: str, phase: str, t0: float,
                        t1: Optional[float] = None, ctx=None,
                        **attrs) -> Span:
        """Record an already-elapsed interval as one span (queue waits
        and negotiation windows are only known at their end).  Attaches
        to the calling thread's open span when one exists, else
        finalizes as a root immediately."""
        from .context import current

        ctx = ctx if ctx is not None else current()
        sp = Span(
            name, phase, t0,
            trace_id=getattr(ctx, "trace_id", ""),
            parent_id=getattr(ctx, "span_id", "") if ctx else "",
            producer=getattr(ctx, "producer", ""),
            tenant=getattr(ctx, "tenant", ""),
            span_id=f"s{next(_span_counter)}",
            attrs=attrs or None,
        )
        sp.t1 = time.monotonic() if t1 is None else t1
        st = self._stack()
        if st:
            if not sp.trace_id:
                sp.trace_id = st[-1].trace_id
                sp.parent_id = sp.parent_id or st[-1].span_id
            st[-1].children.append(sp)
        else:
            self._finalize_root(sp)
        return sp

    # ------------------------------------------------------- finalize

    def _finalize_root(self, span: Span, step: bool = False) -> None:
        if not step:
            self._fold_root(span, step=False)
            return
        # The tracing's own cost, measured by itself: the step's tree
        # has ended, so folding it has an annotation and a histogram of
        # its own.
        from .. import metrics

        t0 = time.monotonic()
        with TraceAnnotation(annotation_name("step_finalize")):
            self._fold_root(span, step=True)
        metrics.observe("trace.finalize_seconds", time.monotonic() - t0)

    def _fold_root(self, span: Span, step: bool) -> None:
        from .. import metrics

        n = 0
        for s in span.walk():
            n += 1
            metrics.observe(f"trace.phase_seconds.{s.phase}", s.dur)
            # Per-tenant phase attribution (the multi-tenant arbiter's
            # observability half, docs/multitenant.md): tenant-tagged
            # spans additionally fold into trace.tenant_seconds.<tenant>
            # .<phase> so the driver's straggler detector can say WHICH
            # tenant a slow phase belongs to.  Untagged worlds pay
            # nothing.
            if s.tenant:
                metrics.observe(
                    f"trace.tenant_seconds.{s.tenant}.{s.phase}", s.dur
                )
        metrics.inc_counter("trace.spans", n)
        if step:
            metrics.fold_ready_gauges()
            self._publish_rail_utilization(span)
            # Profiling plane (prof/): step clock, host gap, MFU and
            # the sentinel all derive from the finalized step tree.
            # The hook never raises and is a no-op at HVD_TPU_PROF=off.
            from .. import prof

            prof.on_step_span(span)
        from . import recorder

        rec = recorder.get_recorder()
        if step:
            rec.on_step(span)
        else:
            rec.on_background(span)
        if level() == "full":
            w = self._ensure_writer()
            if w is not None:
                w.write_tree(span)

    def _publish_rail_utilization(self, step_span: Span) -> None:
        """``topo.rail_busy_frac{rail=}``: the fraction of the step the
        rail-phase spans kept each network busy.  Measured from spans,
        so the pipeliner's overlap is visible as the two fractions'
        sum exceeding what a serialized schedule could reach."""
        from .. import metrics

        busy = {"ici": 0.0, "dcn": 0.0}
        seen = False
        for s in step_span.walk():
            rail = s.attrs.get("rail") if s.attrs else None
            rail = rail or RAIL_PHASES.get(s.phase)
            if rail in busy:
                busy[rail] += s.dur
                seen = True
        if not seen or step_span.dur <= 0:
            return
        for rail, t in busy.items():
            metrics.set_gauge(
                "topo.rail_busy_frac", min(t / step_span.dur, 1.0),
                {"rail": rail},
            )

    # --------------------------------------------------------- export

    def _ensure_writer(self):
        if self._writer is not None or self._writer_failed:
            return self._writer
        path_dir = env.get_env(env.TRACE_DIR)
        if not path_dir:
            self._writer_failed = True
            return None
        try:
            import os

            from .export import TraceWriter

            os.makedirs(path_dir, exist_ok=True)
            self._writer = TraceWriter(
                os.path.join(path_dir, f"trace_rank{self.rank}.json"),
                rank=self.rank, mono0=self.mono0,
                epoch_wall_us=self.epoch_wall_us,
            )
        except OSError as e:
            from ..utils.logging import get_logger

            get_logger().warning("cannot open trace writer: %s", e)
            self._writer_failed = True
        return self._writer

    def close(self) -> None:
        if self._writer is not None:
            self._writer.close()
            self._writer = None
        self._writer_failed = False


_tracer: Optional[Tracer] = None
_tracer_lock = threading.Lock()


def get_tracer() -> Tracer:
    global _tracer
    with _tracer_lock:
        if _tracer is None:
            _tracer = Tracer()
    return _tracer


def reset() -> None:
    """Drop the tracer (and its writer): test isolation + elastic
    restarts — the next span builds a fresh one against the current
    rank/clock."""
    global _tracer
    with _tracer_lock:
        t, _tracer = _tracer, None
    if t is not None:
        t.close()
    from . import recorder

    recorder.reset()


@atexit.register
def _close_at_exit() -> None:  # pragma: no cover - interpreter teardown
    t = _tracer
    if t is not None:
        t.close()


# Module-level conveniences (the public spelling call sites use).

def span(name: str, phase: str, ctx=None, **attrs):
    if level() == "off":
        return NOOP
    return get_tracer().span(name, phase, ctx=ctx, **attrs)


def step(**attrs):
    if level() == "off":
        return NOOP
    return get_tracer().step(**attrs)


def record_complete(name: str, phase: str, t0: float,
                    t1: Optional[float] = None, ctx=None, **attrs):
    if level() == "off":
        return None
    return get_tracer().record_complete(
        name, phase, t0, t1=t1, ctx=ctx, **attrs
    )
