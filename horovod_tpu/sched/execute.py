"""Execute stage: emit the planned per-bucket collectives.

Where the reference's background loop dispatches one fused NCCL call
per cycle tick (``operations.cc:381`` ``RunLoopOnce``), this stage
emits one XLA collective per bucket into the traced step, sequenced by
``lax.optimization_barrier``: bucket *k+1*'s inputs are barrier-tied to
a scalar carried out of bucket *k*'s collective, so XLA must issue the
collectives in schedule order — and, because each bucket depends only
on its own gradient leaves (plus that token), the latency-hiding
scheduler is free to overlap bucket *k*'s wire time with the backward
compute still producing bucket *k+1*'s gradients.

Observability: ``sched.*`` counters/gauges/histograms in the metrics
registry (see docs/observability.md) plus one ``SCHED_EXCHANGE``
timeline lane event per bucket.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .. import metrics
from ..ops import fusion
from .plan import (
    Bucket,
    BucketSchedule,
    SchedConfig,
    build_schedule,
    current_config,
    wire_bytes,
)


def _chain(tensors: List[jax.Array], token: Optional[jax.Array]):
    """Tie ``tensors`` to the previous bucket's ``token`` through an
    optimization barrier (identity on values; ordering-only edge)."""
    if token is None:
        return tensors, None
    out = lax.optimization_barrier(tuple(tensors) + (token,))
    return list(out[:-1]), out[-1]


class _PhasedBucket:
    """One decomposable bucket's rail phases: ``rs`` (ICI
    reduce-scatter), ``mid`` (the DCN leg — hop, or RS + shard update +
    AG in reduce_scatter mode), ``ag`` (ICI all-gather back to the flat
    buffer).  Built per bucket by :func:`hier_phase_factory`; the three
    closures emit exactly the ops the serialized reducer would, so a
    phase-emitted bucket is bitwise identical to its serialized twin."""

    __slots__ = ("rs", "mid", "ag")

    def __init__(self, rs, mid, ag):
        self.rs, self.mid, self.ag = rs, mid, ag


def hier_phase_factory(
    *,
    axis,
    average: bool = False,
    rs_mode: bool = False,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    shard_update: Optional[Callable[[jax.Array], jax.Array]] = None,
    pmean: bool = False,
):
    """Phase decomposition of the hier bucket reducers for the rail
    pipeliner (``xir/pipeline.py``): returns ``factory(bucket) ->
    _PhasedBucket | None``.  ``None`` marks the bucket serialized (not
    ``hier``, mixed dtypes, or a non-factoring axis) and the exchange
    falls back to its ``reduce_flat`` for that bucket.

    Three flavors, each mirroring its serialized reducer op for op:

    * default — :func:`hier_allreduce_flat` (prescale → staged Sum →
      postscale/average);
    * ``rs_mode=True`` — :func:`hier_reduce_scatter_flat` on floating
      buckets (the RS+AG decomposition with the optional ZeRO
      ``shard_update`` riding the DCN leg), allreduce flavor otherwise;
    * ``pmean=True`` — ``hierarchical_all_reduce(op=Average)``, the
      ``sync_gradients_bucketed`` hier pmean.
    """
    from ..ops.traced import _scale
    from ..topo import (
        dcn_all_gather_phase,
        dcn_reduce_scatter_phase,
        dcn_sum_phase,
        ici_all_gather_phase,
        ici_reduce_scatter_phase,
        phase_context,
    )

    def factory(bucket: Bucket) -> Optional[_PhasedBucket]:
        from ..xir import pipeline as railpipe

        if not railpipe.decomposable(bucket):
            return None
        ctx = phase_context(axis)
        if ctx is None:
            return None
        wire = bucket.wire
        k, s = ctx["k"], ctx["s"]
        n_axis = k * s
        cell: dict = {}
        floating = bool(bucket.wire_dtypes) and jnp.issubdtype(
            jnp.dtype(bucket.wire_dtypes[0]), jnp.floating
        )

        if pmean:
            # hierarchical_all_reduce(op=Average, wire): slice sum →
            # DCN sum → gather, /(s*k) before the dtype cast.
            def rs(f):
                cell["V"], cell["dtype"] = f.size, f.dtype
                flat = f.reshape(-1)
                pad = (-f.size) % k
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                return ici_reduce_scatter_phase(flat, ctx)

            def mid(shard):
                return dcn_sum_phase(shard, ctx, wire)

            def ag(shard):
                out = ici_all_gather_phase(shard, ctx)[: cell["V"]]
                out = out / (s * k)
                return out.astype(cell["dtype"])

            return _PhasedBucket(rs, mid, ag)

        if rs_mode and floating:
            # hier_reduce_scatter_flat: both DCN legs (and the shard
            # update between them) ride the DCN rail.
            quant = wire in ("int8", "fp8")
            unit = k * s
            if quant:
                from ..ops.quantized import quant_block

                unit *= quant_block()

            def rs(f):
                cell["n"] = f.shape[0]
                g = _scale(f, prescale_factor)
                flat = g.reshape(-1)
                pad = (-flat.shape[0]) % unit
                if pad:
                    flat = jnp.pad(flat, (0, pad))
                return ici_reduce_scatter_phase(flat, ctx)

            def mid(shard_k):
                shard = dcn_reduce_scatter_phase(shard_k, ctx, wire)
                post = (
                    postscale_factor / n_axis if average
                    else postscale_factor
                )
                shard = _scale(shard, post)
                if shard_update is not None:
                    shard = shard_update(shard)
                return dcn_all_gather_phase(shard, ctx, wire)

            def ag(out_k):
                return ici_all_gather_phase(out_k, ctx)[: cell["n"]]

            return _PhasedBucket(rs, mid, ag)

        # hier_allreduce_flat: prescale → staged Sum → postscale.
        def rs(f):
            cell["V"], cell["dtype"] = f.size, f.dtype
            g = _scale(f, prescale_factor)
            flat = g.reshape(-1)
            pad = (-g.size) % k
            if pad:
                flat = jnp.pad(flat, (0, pad))
            return ici_reduce_scatter_phase(flat, ctx)

        def mid(shard):
            return dcn_sum_phase(shard, ctx, wire)

        def ag(shard):
            out = ici_all_gather_phase(shard, ctx)[: cell["V"]]
            out = out.astype(cell["dtype"])
            post = (
                postscale_factor / n_axis if average else postscale_factor
            )
            return _scale(out, post)

        return _PhasedBucket(rs, mid, ag)

    return factory


def record_wire_metrics(schedule: BucketSchedule) -> None:
    """Publish the per-wire payload gauges for one planned exchange:
    ``sched.wire_bytes{wire=}`` (bytes/step on each wire format) and
    ``sched.compression_ratio`` (dense bytes / wire bytes — 1.0 when
    every bucket is dense)."""
    per_wire: dict = {}
    for b in schedule.buckets:
        per_wire[b.wire] = per_wire.get(b.wire, 0) + wire_bytes(b)
    total_wire = sum(per_wire.values())
    for w, nbytes in per_wire.items():
        metrics.set_gauge("sched.wire_bytes", nbytes, {"wire": w})
        metrics.inc_counter(f"sched.wire_bytes.{w}", nbytes)
    if total_wire > 0:
        metrics.set_gauge(
            "sched.compression_ratio", schedule.total_bytes / total_wire
        )
    record_topo_metrics(schedule)


def record_topo_metrics(
    schedule: BucketSchedule, axis_size: Optional[int] = None
) -> None:
    """Publish the network-class split of one planned exchange from the
    topology byte model: ``topo.dcn_bytes`` / ``topo.ici_bytes``
    (per-rank bytes/step over each network, gauges + running counters)
    and the per-lowering bucket counts.  A hier bucket's DCN figure is
    flat's divided by the ICI degree, so the gauge ratio reads the
    subsystem's savings directly."""
    from ..topo import model as topo_model

    topo = topo_model.current()
    dcn = ici = 0
    per_lower: dict = {}
    for b in schedule.buckets:
        by = topo.lowering_bytes(
            "all_reduce", b.nbytes, b.lowering, axis_size
        )
        dcn += by["dcn"]
        ici += by["ici"]
        per_lower[b.lowering] = per_lower.get(b.lowering, 0) + 1
    metrics.set_gauge("topo.dcn_bytes", dcn)
    metrics.set_gauge("topo.ici_bytes", ici)
    metrics.inc_counter("topo.dcn_bytes_total", dcn)
    metrics.inc_counter("topo.ici_bytes_total", ici)
    for lo, count in per_lower.items():
        metrics.set_gauge("topo.buckets", count, {"lowering": lo})


def _bucket_timeline(timeline, bi: int, bucket: Bucket) -> None:
    """One SCHED_EXCHANGE event per bucket plus TOPO_PHASE lane events
    for hierarchical buckets (shared by the serialized and pipelined
    emissions — a slow hop stays identifiable either way)."""
    timeline.record_op(
        f"bucket{bi}[n={len(bucket.indices)},"
        f"dtype={'+'.join(bucket.wire_dtypes)},"
        f"wire={bucket.wire},lower={bucket.lowering}]",
        "SCHED_EXCHANGE", wire_bytes(bucket),
    )
    if bucket.lowering in ("hier", "hier_adasum"):
        from ..topo import model as topo_model

        by = topo_model.current().lowering_bytes(
            "all_reduce", bucket.nbytes, bucket.lowering
        )
        dcn_phase = (
            "adasum_dcn" if bucket.lowering == "hier_adasum" else "ar_dcn"
        )
        for phase, nb in (
            ("rs_ici", by["ici"] // 2),
            (dcn_phase, by["dcn"]),
            ("ag_ici", by["ici"] // 2),
        ):
            timeline.record_op(f"bucket{bi}.{phase}", "TOPO_PHASE", nb)


def _exchange_pipelined(
    wire: Sequence[jax.Array],
    buckets: Sequence[Bucket],
    reduce_flat: Callable[[jax.Array, Bucket], jax.Array],
    phases: Callable[[Bucket], Optional[_PhasedBucket]],
    timeline: Any,
) -> List[jax.Array]:
    """Rail-chained emission (``HVD_TPU_XIR_PIPELINE``): decomposable
    buckets split into ICI/DCN phases chained per rail — the ICI chain
    runs RS(i), RS(i+1), AG(i), RS(i+2), AG(i+1), … while each DCN hop
    chains only against the previous DCN hop, so bucket *i*'s
    cross-slice hop overlaps bucket *i+1*'s reduce-scatter and bucket
    *i−1*'s all-gather.  Non-decomposable buckets serialize against
    BOTH rails (full ordering, exactly their serialized behavior).
    Values are bitwise identical to the serialized emission: every
    barrier is identity and per-bucket op order never changes."""
    from .. import trace
    from ..xir import pipeline as railpipe

    reduced: List[jax.Array] = list(wire)
    rail = railpipe.RailChain()
    # (bi, bucket, meta, phased, dcn_out) — bucket i's ICI all-gather,
    # held back until bucket i+1's reduce-scatter has entered the ICI
    # chain (the overlap window the pipeline.overlap_windows counter
    # reads).
    deferred = None
    overlaps = 0

    def _flush():
        nonlocal deferred
        bi_, bucket_, meta_, pb_, mid_ = deferred
        deferred = None
        (mid_,) = rail.tie([mid_], ("ici",))
        with trace.span(
            f"bucket{bi_}.ag", "bucket", bucket=bi_,
            nbytes=bucket_.nbytes,
        ), jax.named_scope(
            f"hvd_sched_bucket{bi_}_{bucket_.nbytes}B_{bucket_.wire}"
            f"_{bucket_.lowering}_ag"
        ):
            out = pb_.ag(mid_)
            rail.bump(out, ("ici",))
            leaves = fusion.unflatten_group([out], meta_)
        for i, t in zip(bucket_.indices, leaves):
            reduced[i] = t

    for bi, bucket in enumerate(buckets):
        pb = phases(bucket)
        ins = [wire[i] for i in bucket.indices]
        if timeline is not None:
            _bucket_timeline(timeline, bi, bucket)
        if pb is None:
            # Serialized bucket inside the pipeline: flush the pending
            # all-gather first, then order against both rails.
            if deferred is not None:
                _flush()
            ins = rail.tie(ins, ("ici", "dcn"))
            with trace.span(
                f"bucket{bi}", "bucket", bucket=bi,
                nbytes=bucket.nbytes, wire=bucket.wire,
                lowering=bucket.lowering,
            ), jax.named_scope(
                f"hvd_sched_bucket{bi}_{bucket.nbytes}B_{bucket.wire}"
                f"_{bucket.lowering}"
            ):
                flats, meta = fusion.flatten_group(ins)
                outs = [reduce_flat(f, bucket) for f in flats]
                rail.bump(outs[0], ("ici", "dcn"))
                leaves = fusion.unflatten_group(outs, meta)
            for i, t in zip(bucket.indices, leaves):
                reduced[i] = t
        else:
            ins = rail.tie(ins, ("ici",))
            with trace.span(
                f"bucket{bi}.rs", "bucket", bucket=bi,
                nbytes=bucket.nbytes,
            ), jax.named_scope(
                f"hvd_sched_bucket{bi}_{bucket.nbytes}B_{bucket.wire}"
                f"_{bucket.lowering}_rs"
            ):
                flats, meta = fusion.flatten_group(ins)
                shard = pb.rs(flats[0])
            rail.bump(shard, ("ici",))
            if deferred is not None:
                # Bucket i's RS is on the chain; bucket i-1's AG may
                # now follow it — its DCN hop already ran concurrently.
                _flush()
                overlaps += 1
            (shard,) = rail.tie([shard], ("dcn",))
            with trace.span(
                f"bucket{bi}.dcn", "bucket", bucket=bi,
                nbytes=bucket.nbytes, wire=bucket.wire,
            ), jax.named_scope(
                f"hvd_sched_bucket{bi}_{bucket.nbytes}B_{bucket.wire}"
                f"_{bucket.lowering}_dcn"
            ):
                mid = pb.mid(shard)
            rail.bump(mid, ("dcn",))
            deferred = (bi, bucket, meta, pb, mid)
        metrics.observe(
            "sched.bytes_per_bucket", bucket.nbytes,
            buckets=metrics.BYTES_BUCKETS,
        )
    if deferred is not None:
        _flush()
    metrics.inc_counter("sched.pipeline.overlap_windows", overlaps)
    metrics.set_gauge("sched.pipeline.overlap_windows_per_step", overlaps)
    return reduced


def exchange(
    wire: Sequence[jax.Array],
    schedule: BucketSchedule,
    reduce_flat: Callable[[jax.Array, Bucket], jax.Array],
    *,
    timeline: Any = None,
    kind: str = "dense_grad",
    axis: Any = None,
    phases: Optional[Callable[[Bucket], Optional[_PhasedBucket]]] = None,
) -> List[jax.Array]:
    """Run ``schedule`` over the ``wire`` leaves: per bucket, flatten ->
    one collective per dtype (via ``reduce_flat(flat, bucket)``) ->
    slice back out.  Returns the reduced leaves in original flatten
    order.

    The schedule is first expressed as an explicit exchange program
    (:func:`~horovod_tpu.xir.from_schedule` — one op per bucket
    carrying the (wire, lowering, bucket, ef) tuple), and this loop
    interprets that program: the op record is authoritative for the
    per-bucket dispatch.

    Values are independent of bucketing: XLA collectives are
    elementwise over the buffer, so concat order never changes a sum —
    with a dense wire the scheduler is numerics-identical to a per-leaf
    reduction by construction.  A bucket whose ``wire`` is quantized
    trades that identity for compressed wire bytes (the reducer routes
    it through ops/quantized.py).

    ``phases`` (a :func:`hier_phase_factory`) opts the schedule into
    the rail pipeliner: when ``HVD_TPU_XIR_PIPELINE`` engages
    (``xir.pipeline.engaged``), decomposable hier buckets emit as
    ICI/DCN phases chained **per rail** instead of per bucket, so
    bucket *i*'s cross-slice DCN hop overlaps bucket *i+1*'s ICI
    reduce-scatter and bucket *i−1*'s ICI all-gather.  Ordering-only:
    f32 dense losses are bitwise identical to the serialized emission
    in every mode.
    """
    from .. import prof, svc, trace, xir
    from ..xir import pipeline as railpipe

    t0 = time.perf_counter()
    program = xir.from_schedule(schedule, kind=kind, axis=axis)
    if program.trace is None and trace.enabled():
        # Trace correlation for the whole submission: the context rides
        # the program into the service (queue/negotiation/cache spans)
        # and back out to the rail-phase spans emitted below.  A caller
        # context that predates tenant tagging is back-filled with the
        # process tenant so the per-tenant phase attribution
        # (docs/multitenant.md) covers the dense-grad pipeline too.
        ctx = trace.current_context() or trace.new_context(f"sched.{kind}")
        if not ctx.tenant:
            default = trace.context.default_tenant()
            if default:
                ctx = dataclasses.replace(ctx, tenant=default)
        program = program.with_trace(ctx)
    axis_size = None
    if isinstance(axis, str):
        try:
            axis_size = lax.axis_size(axis)
        except Exception:
            axis_size = None
    # Async exchange service (svc/): the bucketed pipeline is a
    # *producer* — the program is submitted to the service at trace
    # time and the (ResponseCache-resolved) copy it hands back drives
    # the emission below.  A repeat signature costs zero re-lowering; a
    # dead service falls back to the local program
    # (svc.fallback_sync).  The ops are equal either way, so
    # HVD_TPU_SVC on/off stays bitwise identical on this path.
    if svc.enabled():
        program = svc.get_service().submit_traced(
            program, producer=f"sched.{kind}",
            axis_size=axis_size, store=False,
        )
    metrics.inc_counter("xir.programs")
    metrics.inc_counter(f"xir.programs.{kind}")
    metrics.inc_counter("xir.ops", len(program.ops))
    # Emission accounting for the profiling plane (trace-time, like
    # the counters above): how many collective programs — and ops —
    # one step's schedule emits, per source.
    prof.note_emission(f"sched.{kind}", len(program.ops))
    # Rail pipelining (xir/pipeline.py): needs a phase factory from the
    # caller and an engaged knob/cost-model verdict.  Values are
    # bitwise identical either way; the branch only changes ordering
    # edges.
    pipelined = bool(
        phases is not None and railpipe.engaged(schedule, axis_size)
    )
    metrics.set_gauge(
        "sched.pipeline.engaged", 1.0 if pipelined else 0.0,
        {"mode": railpipe.mode()},
    )
    # Interpret the program: the op record drives each bucket's dispatch.
    buckets = [
        dataclasses.replace(bucket, wire=op.wire, lowering=op.lowering)
        for bucket, op in zip(schedule.buckets, program.ops)
    ]
    with trace.span(
        f"exchange.{kind}", "exchange", ctx=program.trace,
        kind=kind, buckets=len(schedule), pipelined=pipelined,
    ):
        if pipelined:
            reduced = _exchange_pipelined(
                wire, buckets, reduce_flat, phases, timeline
            )
        else:
            reduced = list(wire)
            token: Optional[jax.Array] = None
            for bi, bucket in enumerate(buckets):
                if timeline is not None:
                    _bucket_timeline(timeline, bi, bucket)
                # The scope holds all the device does for the bucket:
                # the tie to the bucket before, packing the leaves, the
                # collective, and cutting the leaves out again.
                with trace.span(
                    f"bucket{bi}", "bucket", bucket=bi,
                    nbytes=bucket.nbytes, wire=bucket.wire,
                    lowering=bucket.lowering,
                ), jax.named_scope(
                    f"hvd_sched_bucket{bi}_{bucket.nbytes}B_{bucket.wire}"
                    f"_{bucket.lowering}"
                ):
                    ins, token = _chain(
                        [wire[i] for i in bucket.indices], token
                    )
                    flats, meta = fusion.flatten_group(ins)
                    outs = [reduce_flat(f, bucket) for f in flats]
                    # Scalar carried out of this bucket's collective: the
                    # next bucket's inputs are barrier-tied to it,
                    # enforcing issue order without touching values.
                    token = outs[0].reshape(-1)[0]
                    leaves = fusion.unflatten_group(outs, meta)
                for i, t in zip(bucket.indices, leaves):
                    reduced[i] = t
                metrics.observe(
                    "sched.bytes_per_bucket", bucket.nbytes,
                    buckets=metrics.BYTES_BUCKETS,
                )
    metrics.inc_counter("sched.plans")
    metrics.inc_counter("sched.buckets", len(schedule))
    metrics.inc_counter("sched.exchange_bytes", schedule.total_bytes)
    metrics.set_gauge("sched.buckets_per_step", len(schedule))
    metrics.set_gauge("sched.bytes_per_step", schedule.total_bytes)
    record_wire_metrics(schedule)
    # Emission cost of the exchange subgraph (trace-time under jit; the
    # device-side wire time is the profiler's/timeline's to attribute).
    metrics.observe("sched.exchange_seconds", time.perf_counter() - t0)
    return reduced


def quantized_exchange_flat(
    f: jax.Array,
    *,
    axis,
    average: bool,
    wire: str,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    shard_update: Optional[Callable[[jax.Array], jax.Array]] = None,
    residual: Optional[jax.Array] = None,
    process_set=None,
):
    """One bucket's quantized ``reduce_scatter + all_gather`` exchange
    (the ops/quantized.py phase primitives on a flat buffer): blockwise
    quantize → ``all_to_all`` wire → fp32 dequant-accumulate shard →
    optional ``shard_update`` (the ZeRO-1 hook, fed **fp32**) →
    re-quantize → tiled ``all_gather`` → dequant.

    ``residual`` engages error feedback: the wire carries
    ``quantize(f·prescale + residual)`` and the new residual
    ``e − dequant(q)`` is returned alongside (None ⇒ no EF, returns
    ``(out, None)``).  Serves both scheduler modes — for a quantized
    bucket the RS+AG decomposition *is* the allreduce.
    """
    from ..ops.quantized import (
        quantized_all_gather,
        quantized_reduce_scatter,
    )
    from ..ops.traced import Sum, _scale

    n = f.shape[0]
    g = _scale(f.astype(jnp.float32), prescale_factor)
    if residual is not None:
        g = g + residual.astype(jnp.float32)
        shard, r_new = quantized_reduce_scatter(
            g, axis, op=Sum, process_set=process_set, wire=wire, ef=True,
        )
    else:
        shard = quantized_reduce_scatter(
            g, axis, op=Sum, process_set=process_set, wire=wire,
        )
        r_new = None
    world = lax.axis_size(axis) if process_set is None else None
    if world is None:
        from ..ops.quantized import _axis_groups

        world = _axis_groups(axis, process_set)[1]
    if average:
        postscale_factor = postscale_factor / world
    shard = _scale(shard, postscale_factor)
    if shard_update is not None:
        shard = shard_update(shard)
    out = quantized_all_gather(
        shard, axis, process_set=process_set, wire=wire
    )[:n]
    return out.astype(f.dtype), r_new


def bf16_wire(reduce_dense: Callable[[jax.Array], jax.Array]):
    """Wrap a dense flat reducer with a bf16 cast around the wire (the
    per-bucket ``wire="bf16"`` lowering — same scheme as
    ``Compression.bf16`` but chosen per bucket by the plan/tuner).  The
    casts run as single VMEM-tiled kernels
    (``ops/pallas_kernels.cast_buffer``, the reference's ScaleBuffer
    device kernel) instead of separate astype + multiply HLOs; values
    are identical to a plain astype pair."""

    def reduce(f: jax.Array) -> jax.Array:
        if not jnp.issubdtype(f.dtype, jnp.floating) \
                or f.dtype == jnp.bfloat16:
            return reduce_dense(f)
        from ..ops.pallas_kernels import cast_buffer

        return cast_buffer(reduce_dense(cast_buffer(f, jnp.bfloat16)),
                           f.dtype)

    return reduce


def reduce_scatter_flat(
    f: jax.Array,
    *,
    axis,
    average: bool,
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    shard_update: Optional[Callable[[jax.Array], jax.Array]] = None,
) -> jax.Array:
    """One bucket's ``reduce_scatter + all_gather`` exchange
    (arXiv:2004.13336's weight-update sharding decomposition): each
    rank receives its 1/N shard of the reduced buffer, optionally runs
    ``shard_update`` on it (the ZeRO-1 hook — optimizer work on the
    slice), and all-gathers the result.  Total wire bytes equal one
    allreduce; with ``shard_update`` the optimizer state and update
    math shrink N-fold.
    """
    from ..ops.traced import _scale

    world = lax.axis_size(axis)
    n = f.shape[0]
    pad = (-n) % world
    g = _scale(f, prescale_factor)
    if pad:
        g = jax.numpy.pad(g, (0, pad))
    shard = lax.psum_scatter(g, axis, scatter_dimension=0, tiled=True)
    if average:
        postscale_factor = postscale_factor / world
    shard = _scale(shard, postscale_factor)
    if shard_update is not None:
        shard = shard_update(shard)
    out = lax.all_gather(shard, axis, tiled=True)
    return out[:n] if pad else out


def hier_allreduce_flat(
    f: jax.Array,
    *,
    axis,
    average: bool,
    wire: str = "off",
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
) -> jax.Array:
    """One bucket's hierarchical allreduce (the ``lowering="hier"``
    exchange in ``HVD_TPU_SCHED_MODE=allreduce``): intra-slice
    reduce_scatter over ICI → cross-slice all_reduce over DCN on the
    1/k shard → intra-slice all_gather (topo/hierarchical.py).  A
    quantized/bf16 ``wire`` compresses only the DCN hop."""
    from ..ops.traced import Sum as _Sum, _scale
    from ..topo import hierarchical_all_reduce

    n = lax.axis_size(axis)
    g = _scale(f, prescale_factor)
    out = hierarchical_all_reduce(g, axis, op=_Sum, wire=wire)
    if average:
        postscale_factor = postscale_factor / n
    return _scale(out, postscale_factor)


def hier_adasum_flat(
    f: jax.Array,
    *,
    axis,
    average: bool,
    wire: str = "off",
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
) -> jax.Array:
    """One bucket's hierarchical-Adasum exchange (the
    ``lowering="hier_adasum"`` bucket in either ``HVD_TPU_SCHED_MODE``):
    intra-slice sum over ICI → Adasum's adaptive combination across
    slices on the 1/k DCN shard → intra-slice all_gather
    (topo/hierarchical.py).  ``average=True`` combines per-slice *mean*
    gradients (the reference postscale semantics); a quantized/bf16
    ``wire`` compresses only the DCN gather, EF-free like ``hier``."""
    from ..ops.traced import Average as _Avg, Sum as _Sum, _scale
    from ..topo import hierarchical_adasum_all_reduce

    g = _scale(f, prescale_factor)
    out = hierarchical_adasum_all_reduce(
        g, axis, op=(_Avg if average else _Sum), wire=wire
    )
    return _scale(out, postscale_factor)


def hier_reduce_scatter_flat(
    f: jax.Array,
    *,
    axis,
    average: bool,
    wire: str = "off",
    prescale_factor: float = 1.0,
    postscale_factor: float = 1.0,
    shard_update: Optional[Callable[[jax.Array], jax.Array]] = None,
) -> jax.Array:
    """One bucket's hierarchical ``reduce_scatter + all_gather``
    exchange (``HVD_TPU_SCHED_MODE=reduce_scatter`` under
    ``lowering="hier"``): both phases stage through the ICI/DCN
    hierarchy, ``shard_update`` (the ZeRO-1 hook) runs on the
    1/(s·k) shard between them, and only the cross-slice hops carry a
    compressed ``wire``.  The shard layout is the hierarchy's own and
    is inverted exactly by the matching all_gather, so the composed
    result equals the flat decomposition elementwise."""
    from ..ops.traced import Sum as _Sum, _scale
    from ..topo import (
        hierarchical_all_gather,
        hierarchical_reduce_scatter,
    )

    n = f.shape[0]
    world = lax.axis_size(axis)
    g = _scale(f, prescale_factor)
    shard = hierarchical_reduce_scatter(g, axis, op=_Sum, wire=wire)
    if average:
        postscale_factor = postscale_factor / world
    shard = _scale(shard, postscale_factor)
    if shard_update is not None:
        shard = shard_update(shard)
    out = hierarchical_all_gather(shard, axis, wire=wire)
    return out[:n]


def sync_gradients_bucketed(
    grads: Any,
    param_shard_axes: Any = None,
    axes: Sequence[str] = (),
    cfg: Optional[SchedConfig] = None,
    *,
    residuals: Any = None,
):
    """Scheduler-mode :func:`~horovod_tpu.parallel.grad_sync.sync_gradients`.

    Same per-parameter rule (pmean over every sync axis the parameter is
    NOT sharded over; divide by the axis size where it IS sharded), but
    the pmeans are exchanged as a bucketed pipeline: leaves are grouped
    by their mean-axes set (a hybrid mesh has one group per distinct
    ``param_shard_axes`` combination), each group planned into
    reverse-backward buckets, one fused ``pmean`` per bucket.  The
    divide-by-axis-size scaling stays per-leaf and local (no wire
    traffic), so hybrid-mesh semantics are respected exactly —
    bit-for-bit equal to the per-leaf path (pmean is elementwise) when
    the wire is dense.

    ``cfg.wire`` (``HVD_TPU_SCHED_WIRE``): quantized buckets whose
    mean-axes set is a *single* axis route through the quantized RS+AG
    primitives; multi-axis pmean groups stay dense (the all_to_all
    phase has no multi-axis form).  ``residuals`` — a pytree matching
    ``grads`` — engages error feedback on those quantized buckets; the
    call then returns ``(synced, new_residuals)`` for the caller's
    state (see docs/quantization.md).
    """
    from ..parallel.grad_sync import _parse
    from ..parallel.tensor import _axis_present

    if cfg is None:
        cfg = current_config()
    present = tuple(a for a in axes if _axis_present(a))
    leaves, treedef = jax.tree.flatten(grads)
    res_leaves = None
    if residuals is not None:
        res_leaves = jax.tree.flatten(residuals)[0]
        if len(res_leaves) != len(leaves):
            raise ValueError("residuals structure does not match grads")
    if param_shard_axes is None:
        shard_strs = [""] * len(leaves)
    else:
        shard_strs = jax.tree.flatten(param_shard_axes)[0]
        if len(shard_strs) != len(leaves):
            raise ValueError(
                "param_shard_axes structure does not match grads"
            )

    out = list(leaves)
    res_out = list(res_leaves) if res_leaves is not None else None
    groups: dict = {}  # mean_over tuple -> [leaf indices]
    for i, s in enumerate(shard_strs):
        sharded = _parse(s)
        mean_over = tuple(a for a in present if a not in sharded)
        if mean_over:
            groups.setdefault(mean_over, []).append(i)

    for mean_over, idxs in groups.items():
        sizes = [
            int(leaves[i].size) * leaves[i].dtype.itemsize for i in idxs
        ]
        dtypes = [str(leaves[i].dtype) for i in idxs]
        # Quantized wire needs one named axis for its all_to_all phase;
        # so does the hierarchical lowering (its groups factor one
        # axis) — multi-axis pmean groups stay flat and dense.
        wire_req = cfg.wire
        if wire_req in ("int8", "fp8") and len(mean_over) != 1:
            wire_req = "off"
        lower_req = cfg.lowering if len(mean_over) == 1 else "flat"
        schedule = build_schedule(
            sizes, dtypes, cfg, wire=wire_req, lowering=lower_req,
            axis_size=(
                lax.axis_size(mean_over[0]) if len(mean_over) == 1
                else None
            ),
        )

        def reduce_flat(f, bucket, _m=mean_over, _idxs=idxs):
            # bucket.indices are positions in this group's leaf list;
            # _idxs maps them back to global flatten indices.
            if bucket.lowering == "hier_adasum" and len(_m) == 1:
                # Hierarchical Adasum pmean: slice means combined
                # adaptively across slices; the bucket's wire rides
                # only the DCN gather, EF-free like hier.
                from ..ops.traced import Average as _Avg
                from ..topo import hierarchical_adasum_all_reduce

                return hierarchical_adasum_all_reduce(
                    f, _m[0], op=_Avg, wire=bucket.wire
                )
            if bucket.lowering == "hier" and len(_m) == 1:
                # Hierarchical pmean: the ICI/DCN staging with the
                # bucket's wire on the DCN hop only.  EF residuals do
                # not apply here — the quantization error lives on the
                # slice-summed 1/k shard, not the gradient — so hier
                # quantized buckets run EF-free (docs/topology.md).
                from ..ops.traced import Average as _Avg
                from ..topo import hierarchical_all_reduce

                return hierarchical_all_reduce(
                    f, _m[0], op=_Avg, wire=bucket.wire
                )
            if bucket.wire in ("int8", "fp8"):
                res_flat = None
                if res_out is not None:
                    bucket_res = [res_out[_idxs[j]] for j in bucket.indices]
                    rf, rmeta = fusion.flatten_group(bucket_res)
                    res_flat = rf[0]
                red, r_new = quantized_exchange_flat(
                    f, axis=_m[0], average=True, wire=bucket.wire,
                    residual=res_flat,
                )
                if r_new is not None:
                    for j, r in zip(
                        bucket.indices,
                        fusion.unflatten_group([r_new], rmeta),
                    ):
                        res_out[_idxs[j]] = r.astype(
                            res_out[_idxs[j]].dtype
                        )
                return red
            if bucket.wire == "bf16":
                return bf16_wire(lambda x: lax.pmean(x, _m))(f)
            return lax.pmean(f, _m)

        reduced = exchange(
            [leaves[i] for i in idxs], schedule, reduce_flat,
            axis=mean_over[0] if len(mean_over) == 1 else tuple(mean_over),
            # Rail pipelining for hier pmean buckets: the factory's
            # pmean flavor replicates hierarchical_all_reduce(Average)
            # phase for phase, so engaged == serialized bitwise.
            phases=(
                hier_phase_factory(axis=mean_over[0], pmean=True)
                if len(mean_over) == 1 else None
            ),
        )
        for i, t in zip(idxs, reduced):
            out[i] = t

    for i, s in enumerate(shard_strs):
        sharded = _parse(s)
        scale = 1
        for a in present:
            if a in sharded:
                scale *= lax.axis_size(a)
        if scale != 1:
            out[i] = out[i] / scale
    synced = jax.tree.unflatten(treedef, out)
    if res_out is not None:
        return synced, jax.tree.unflatten(treedef, res_out)
    return synced
