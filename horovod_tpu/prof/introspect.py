"""Compiled-step introspection: what did XLA actually build?

Every executor the stack compiles — the svc executor cache's per-program
and fused executors, the optimizer's train step, the stale-gradient step
fn — is wrapped in a :class:`ProfiledExecutor`.  The wrapper compiles
ahead-of-time (``fn.lower(*args).compile()``) instead of letting the
first call trigger tracing implicitly; an AOT-compiled call runs the
same HLO as the jit call it replaces, so results are bitwise identical
— the wrapper only *observes* the compile.  Per program signature it
records into the metrics registry:

* ``prof.flops`` / ``prof.bytes_accessed`` gauges — XLA
  ``cost_analysis`` (the measured replacement for ROADMAP item 3's
  bench-guess FLOPs), labeled ``{key, kind}``;
* ``prof.peak_hbm_bytes`` gauge — ``memory_analysis`` argument +
  output + temp footprint;
* ``prof.compile_seconds`` histogram + ``prof.compiles`` counter —
  wall compile time (satellite 3's re-lowering cost signal rides the
  same clock through the svc cache's ``on_compile`` callback).  The
  program's record keeps it in its three parts — ``trace_seconds``
  (``fn.trace``: jax tracing the Python), ``lower_seconds``
  (``.lower()``: jaxpr to StableHLO) and ``backend_seconds``
  (``.compile()``: XLA, or the read from the persistent cache, which
  ``cache_hit`` tells apart) — each under a span of its own
  (``hvd_compile_trace`` / ``_lower`` / ``_backend``);
  ``compile_seconds`` stays their sum.

What the wrapper may hide, and what it may not.  A callable with no
``lower`` (not a jit function) has nothing to introspect and is called
raw.  A failed AOT compile is the failure plain ``jit`` would have hit
on the same call, so it propagates as itself — compiling again through
the raw fn would only double the wait.  The cache key folds in each
leaf's *sharding* alongside shape and dtype (same-shape inputs arriving
with a new sharding after an elastic resize compile their own variant
instead of hitting a stale ``Compiled``); what the key cannot see — a
layout or committedness drift — the cached ``Compiled`` rejects with a
``TypeError``/``ValueError`` *before* it executes or donates anything,
and only that demotes the signature to the raw fn (``prof.fallbacks``),
which reshards or recompiles as jit would.  An error from the execution
itself propagates: by then donated arguments are gone, and a retry
would report deleted buffers instead of the real fault.
``HVD_TPU_PROF=off`` never constructs a wrapper at all.
"""

from __future__ import annotations

import hashlib
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from .. import metrics
from .config import enabled

# Per-signature compile map sentinel: the cached Compiled rejected this
# argument signature (or the fn cannot be lowered at all); call the raw
# fn forever after.
_FALLBACK = object()

# Registry of every program the plane has introspected:
# key -> {kind, workload, flops, bytes_accessed, peak_hbm_bytes,
#         compile_seconds, trace_seconds, lower_seconds,
#         backend_seconds, cache_hit, compiles, calls, fallback}
_programs: Dict[str, Dict[str, Any]] = {}
_lock = threading.Lock()


def program_key(program: Any) -> str:
    """Stable short digest of an XIR program's signature (or any
    object's repr) — the ``key`` label every ``prof.*`` series and the
    ``/prof`` program table are keyed by."""
    try:
        payload = repr(program.signature())
    except Exception:
        payload = repr(program)
    return hashlib.sha256(payload.encode()).hexdigest()[:12]


def _cost_scalar(cost: Any, name: str) -> Optional[float]:
    try:
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        v = cost.get(name)
        return None if v is None else float(v)
    except Exception:
        return None


def _peak_hbm_bytes(compiled: Any) -> Optional[float]:
    """Argument + output + temp footprint from ``memory_analysis`` —
    donated (aliased) bytes are counted once, not twice."""
    try:
        mem = compiled.memory_analysis()
    except Exception:
        return None
    if mem is None:
        return None
    total, seen = 0.0, False
    for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes"):
        v = getattr(mem, attr, None)
        if isinstance(v, (int, float)):
            total += float(v)
            seen = True
    alias = getattr(mem, "alias_size_in_bytes", None)
    if isinstance(alias, (int, float)):
        total -= float(alias)
    return max(total, 0.0) if seen else None


def _args_signature(args: Tuple[Any, ...]) -> Any:
    import jax

    leaves, treedef = jax.tree.flatten(args)
    # Sharding is part of the key: jax shardings are hashable and
    # equality-comparable, so the object itself participates in the
    # dict lookup.  Hosts-side leaves (numpy, scalars) have none.
    return treedef, tuple(
        (getattr(l, "shape", ()),
         str(getattr(l, "dtype", type(l).__name__)),
         getattr(l, "sharding", None))
        for l in leaves
    )


class ProfiledExecutor:
    """AOT-compiling wrapper around one jitted executor.

    Calls are routed through a per-argument-signature compiled cache
    (jit keeps its own equivalent cache internally, so call counts and
    recompiles match the unwrapped path); the first sighting of a
    signature pays the same compile the jit call would have, but
    through ``lower()``/``compile()`` so cost/memory analysis and the
    compile wall-clock are observable."""

    __slots__ = ("_fn", "key", "kind", "workload", "_on_compile",
                 "_compiled", "_last", "_lock", "__weakref__")

    def __init__(self, fn: Callable, key: str, kind: str,
                 workload: Optional[str] = None,
                 on_compile: Optional[Callable[[float], None]] = None):
        self._fn = fn
        self.key = key
        self.kind = kind
        self.workload = workload or kind
        self._on_compile = on_compile
        self._compiled: Dict[Any, Any] = {}
        self._last: Any = None
        self._lock = threading.Lock()
        with _lock:
            _programs.setdefault(key, {
                "kind": kind, "workload": self.workload,
                "flops": None, "bytes_accessed": None,
                "peak_hbm_bytes": None, "compile_seconds": 0.0,
                "trace_seconds": 0.0, "lower_seconds": 0.0,
                "backend_seconds": 0.0, "cache_hit": None,
                "compiles": 0, "calls": 0, "fallback": False,
            })

    # ----------------------------------------------------------- call
    def __call__(self, *args: Any) -> Any:
        if not enabled():
            return self._fn(*args)
        try:
            sig, compiled = self.lookup(args)
        except Exception:  # unflattenable args or an unhashable leaf
            return self._fn(*args)
        if compiled is None:
            compiled = self.compile(sig, args)
        return self.run(sig, compiled, args)

    # The three stations of a call, public so that a caller with spans
    # of its own (``TrainStep.__call__``) can name each: resolve the
    # signature, build on a miss, enqueue.
    def lookup(self, args: Tuple[Any, ...]) -> Tuple[Any, Any]:
        """(argument signature, what is cached for it: a ``Compiled``,
        None before its first compile, or the fallback mark)."""
        sig = _args_signature(args)
        with self._lock:
            return sig, self._compiled.get(sig)

    def run(self, sig: Any, compiled: Any, args: Tuple[Any, ...],
            span_name: Optional[str] = None) -> Any:
        """Call ``compiled`` (from :meth:`lookup` or :meth:`compile`)
        under the executor's ``exec`` span, ``exec.<workload>`` unless
        the caller names it."""
        with _lock:
            rec = _programs.get(self.key)
            if rec is not None:
                rec["calls"] += 1
        if compiled is _FALLBACK:
            return self._fn(*args)
        from .. import trace

        try:
            with trace.span(span_name or f"exec.{self.workload}", "exec",
                            program=self.key):
                out = compiled(*args)
            self._last = compiled
            return out
        except (TypeError, ValueError):
            # The Compiled's own pre-execution argument check: an
            # aval/layout/committedness mismatch the signature cannot
            # see (e.g. same-shape inputs whose placement changed after
            # an elastic resize).  Nothing has run or been donated yet;
            # plain jit would transparently recompile, so demote the
            # signature to the raw fn forever.
            self._mark_fallback(sig)
        return self._fn(*args)

    def compiled(self) -> Any:
        """The ``Compiled`` the last call ran (calls that fell back to
        the raw fn do not count), or None before one."""
        return self._last

    def _mark_fallback(self, sig: Any) -> None:
        with self._lock:
            self._compiled[sig] = _FALLBACK
        with _lock:
            rec = _programs.get(self.key)
            if rec is not None:
                rec["fallback"] = True
        metrics.inc_counter("prof.fallbacks")

    # ----------------------------------------------------- delegation
    def __getattr__(self, name: str) -> Any:
        # Anything not on the wrapper (``lower``, ``trace``, jit
        # internals) resolves against the wrapped executor, so code
        # that introspects the jit fn — HLO dumps, the bucket
        # profiler — sees the same surface it would unwrapped.
        return getattr(object.__getattribute__(self, "_fn"), name)

    # -------------------------------------------------------- compile
    def compile(self, sig: Any, args: Tuple[Any, ...]) -> Any:
        """Build the ``Compiled`` for ``sig`` in jit's own three stages,
        each under its span and on the record."""
        if not hasattr(self._fn, "trace"):
            self._mark_fallback(sig)
            return _FALLBACK
        from .. import trace

        clock = time.monotonic
        t0 = clock()
        with trace.span("compile_trace", "compile", program=self.key):
            traced = self._fn.trace(*args)
        t1 = clock()
        with trace.span("compile_lower", "compile", program=self.key):
            lowered = traced.lower()
        t2 = clock()
        with trace.span("compile_backend", "compile",
                        program=self.key) as span, _CacheHit() as cache:
            compiled = lowered.compile()
            if span is not None:
                span.attrs["cache_hit"] = cache.hit
        t3 = clock()
        with self._lock:
            self._compiled[sig] = compiled
        self._record(compiled, (t1 - t0, t2 - t1, t3 - t2), cache.hit)
        if self._on_compile is not None:
            try:
                self._on_compile(t3 - t0)
            except Exception:
                pass
        return compiled

    def _record(self, compiled: Any, parts: Tuple[float, float, float],
                cache_hit: bool) -> None:
        try:
            cost = compiled.cost_analysis()
        except Exception:
            cost = None
        flops = _cost_scalar(cost, "flops")
        nbytes = _cost_scalar(cost, "bytes accessed")
        hbm = _peak_hbm_bytes(compiled)
        labels = {"key": self.key, "kind": self.kind}
        if flops is not None:
            metrics.set_gauge("prof.flops", flops, labels)
        if nbytes is not None:
            metrics.set_gauge("prof.bytes_accessed", nbytes, labels)
        if hbm is not None:
            metrics.set_gauge("prof.peak_hbm_bytes", hbm, labels)
        metrics.inc_counter("prof.compiles")
        metrics.observe("prof.compile_seconds", sum(parts))
        with _lock:
            rec = _programs.get(self.key)
            if rec is not None:
                rec["compiles"] += 1
                for field, dt in zip(("trace_seconds", "lower_seconds",
                                      "backend_seconds"), parts):
                    rec[field] += dt
                rec["compile_seconds"] = (
                    rec["trace_seconds"] + rec["lower_seconds"]
                    + rec["backend_seconds"])
                rec["cache_hit"] = cache_hit  # of the last compile
                # keep the largest variant's numbers (re-lowers for a
                # new shape overwrite only upward)
                for field, v in (("flops", flops),
                                 ("bytes_accessed", nbytes),
                                 ("peak_hbm_bytes", hbm)):
                    if v is not None and (rec[field] is None
                                          or v > rec[field]):
                        rec[field] = v


class _CacheHit:
    """While open: did jax's persistent compilation cache serve a
    program (its own monitoring event)?"""

    _EVENT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        self.hit = False

    def _on_event(self, event: str, **_: Any) -> None:
        if event == self._EVENT:
            self.hit = True

    def __enter__(self) -> "_CacheHit":
        import jax.monitoring

        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc: Any) -> None:
        import jax.monitoring

        jax.monitoring.unregister_event_listener(self._on_event)


def wrap(fn: Callable, key: str, kind: str,
         workload: Optional[str] = None,
         on_compile: Optional[Callable[[float], None]] = None) -> Callable:
    """Wrap a jitted executor for introspection — or return it
    untouched when profiling is off (the bitwise-off contract's
    structural half: off means the wrapper never exists)."""
    if not enabled():
        return fn
    return ProfiledExecutor(fn, key, kind,
                            workload=workload, on_compile=on_compile)


def get(key: Optional[str]) -> Optional[Dict[str, Any]]:
    """The registry record for one program key (a copy), or None."""
    if key is None:
        return None
    with _lock:
        rec = _programs.get(key)
        return dict(rec) if rec is not None else None


def ranked() -> List[Dict[str, Any]]:
    """Every introspected program, most expensive re-lowering first —
    the ``/prof`` program table."""
    with _lock:
        rows = [dict(r, key=k) for k, r in _programs.items()]
    rows.sort(key=lambda r: r.get("compile_seconds") or 0.0, reverse=True)
    return rows


def reset() -> None:
    """Clear the program registry (test isolation)."""
    with _lock:
        _programs.clear()
