"""Interpreter: run an exchange program by emitting phase primitives.

The interpreter gives a lowered :class:`~horovod_tpu.xir.ir.ExchangeProgram`
meaning inside a traced step: each op emits exactly the primitive the
pre-IR call sites used —

* ``wire="off"``, ``lowering="flat"`` → the stock ``lax`` collective
  with identical arguments, so an IR-routed exchange is **bitwise
  identical** to the direct call (the parity contract
  tests/test_collective_matrix.py's XIR column pins against plain
  ``lax``);
* ``wire="bf16"`` → the cast-around-the-wire scheme
  (``sched/execute.bf16_wire``'s semantics, applied per op);
* ``wire="int8"/"fp8"`` on reduce-shaped ops → the
  ``ops/quantized.py`` phase primitives (with optional error
  feedback);
* ``lowering="hier"`` on reduce-shaped ops → the
  ``topo/hierarchical.py`` ICI/DCN staging.

Observability per program: the planned bytes land in the *existing*
``sched.wire_bytes{wire=}`` and ``topo.dcn_bytes``/``topo.ici_bytes``
families — labeled with ``kind=`` so MoE / Ulysses / sparse traffic
reads as its own series instead of clobbering the dense-gradient
gauges — plus ``xir.*`` counters and one timeline lane per workload
kind (``MOE_EXCHANGE``, ``ULYSSES_EXCHANGE``, ...).  All recording
happens at trace time, like the scheduler's own exchange metrics: the
gauges describe the planned program, the device profiler owns the
wall-clock attribution.
"""

from __future__ import annotations

from typing import Any, List, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .. import metrics
from ..exceptions import HorovodTpuError
from ..utils import env
from . import ir, lower as lower_mod

# ----------------------------------------------------- onestep knob
#
# Whole-step emission (HVD_TPU_ONESTEP, ROADMAP item 4): fold every
# dispatch unit a step would launch separately — fused service
# buffers, bucket chains, the optimizer-update closure — into ONE
# compiled program, so the host pays a single dispatch round-trip per
# step.  Same mode grammar and override pattern as the rail pipeliner
# (xir/pipeline.py): off | on | auto (default).  Engagement is a
# scheduling decision only; the stitched emission is bitwise-identical
# to the per-unit one (optimization_barrier ties are identity on
# values and per-unit op order never changes).

ONESTEP_MODES = ("off", "on", "auto")

_onestep_override: Optional[str] = None


def set_onestep_override(mode: Optional[str]) -> None:
    """Trace/test-time knob override (the sched config-override
    pattern): pin the whole-step emission without touching the
    environment."""
    global _onestep_override
    if mode is not None and mode not in ONESTEP_MODES:
        raise HorovodTpuError(
            f"onestep mode override must be one of {ONESTEP_MODES}, "
            f"got {mode!r}"
        )
    _onestep_override = mode


def onestep_mode() -> str:
    """``HVD_TPU_ONESTEP`` policy: ``off`` | ``on`` | ``auto``
    (default).  ``off`` keeps every per-unit dispatch path exactly as
    it was; ``auto`` folds when a step has >= 2 dispatch units; ``on``
    always folds."""
    if _onestep_override is not None:
        return _onestep_override
    raw = (env.get_env(env.ONESTEP, "auto") or "auto").strip().lower()
    if raw in ("0", "false", "no", "none", ""):
        raw = "off"
    if raw in ("1", "true", "yes"):
        raw = "on"
    if raw not in ONESTEP_MODES:
        raise HorovodTpuError(
            f"HVD_TPU_ONESTEP must be off|on|auto, got {raw!r}"
        )
    return raw


def onestep_engaged(n_units: int) -> bool:
    """Whether whole-step emission folds ``n_units`` dispatch units
    (fused buffers, solo programs, the update closure) into one
    program.  ``off`` never folds; ``on`` always does; ``auto`` folds
    only when there are at least two units — with one unit the fold
    would change nothing."""
    m = onestep_mode()
    if m == "off":
        return False
    if m == "on":
        return n_units >= 1
    return n_units >= 2


def emit_step(reduced: Sequence[Any], update, *, src: str = "sched"):
    """Stitch a caller's update closure onto freshly-reduced exchange
    outputs INSIDE the same traced emission: the closure's inputs are
    barrier-tied to the reduced tensors (identity on values), so XLA
    sees one program with an explicit exchange→update edge instead of
    two independently dispatched subgraphs.  Returns whatever the
    closure returns.  Values are bitwise-identical to applying the
    closure after the exchange returns — the tie adds ordering edges
    only."""
    from .. import prof, trace

    leaves = list(reduced)
    arrays = [i for i, t in enumerate(leaves)
              if isinstance(t, jax.Array) or hasattr(t, "dtype")]
    if arrays:
        tied = lax.optimization_barrier(
            tuple(leaves[i] for i in arrays)
        )
        for i, t in zip(arrays, tied):
            leaves[i] = t
    metrics.inc_counter("xir.onestep.steps")
    prof.note_emission(f"onestep.{src}", 1)
    with trace.span(
        "onestep.update", "exchange", onestep=1, src=src,
    ), jax.named_scope(f"hvd_onestep_update_{src}"):
        return update(leaves)


def execute_onestep(programs: Sequence[ir.ExchangeProgram],
                    args_lists: Sequence[Sequence[Any]],
                    *,
                    axis_size: Optional[int] = None,
                    process_set=None,
                    store: bool = False,
                    update=None) -> Any:
    """Whole-step emission of a program list: every program's ops —
    and optionally the caller's ``update`` closure over the full
    output list — lower into ONE traced region under a single
    ``onestep``-marked span, instead of one :func:`execute` call (= one
    potential dispatch) per program.  Per-program op order is
    preserved exactly, so outputs are bitwise-identical to N separate
    :func:`execute` calls; the fold only removes dispatch boundaries.
    Returns one output list per program (or, with ``update``, whatever
    the closure returns when applied to that list-of-lists)."""
    from .. import trace

    programs = [
        p if p.lowered else lower_mod.lower(p, axis_size, store=store)
        for p in programs
    ]
    for p, args in zip(programs, args_lists):
        if len(args) != len(p.ops):
            raise HorovodTpuError(
                f"program {p.kind!r} has {len(p.ops)} ops but "
                f"{len(args)} payloads were passed"
            )
    metrics.inc_counter("xir.onestep.programs", len(programs))
    outs: List[List[Any]] = []
    with trace.span(
        "exchange.onestep", "exchange", onestep=1,
        programs=len(programs),
        kind="+".join(p.kind for p in programs),
    ):
        for p, args in zip(programs, args_lists):
            account(p, axis_size)
            prog_outs = []
            for op, x in zip(p.ops, args):
                with jax.named_scope(
                    f"hvd_onestep_{p.kind}_{op.op}{op.bucket}"
                    f"_{op.wire}_{op.lowering}"
                ):
                    prog_outs.append(
                        run_op(op, x, process_set=process_set)
                    )
            outs.append(prog_outs)
        if update is not None:
            flat = [t for prog in outs for t in prog]
            tied = emit_step(flat, lambda ts: ts, src="execute")
            it = iter(tied)
            outs = [[next(it) for _ in prog] for prog in outs]
            return update(outs)
    return outs


def wire_request() -> str:
    """The wire format non-gradient IR workloads request
    (``HVD_TPU_XIR_WIRE``, default ``off``).  Deliberately NOT
    inherited from ``HVD_TPU_SCHED_WIRE``: that knob compresses
    *gradients* (error feedback absorbs the rounding); these ops move
    activations and embedding rows, where compression is a separate
    numerics decision.  Eligibility gating per op class still applies —
    shuffle ops cap at bf16."""
    raw = env.get_env("XIR_WIRE", "off") or "off"
    w = raw.strip().lower()
    if w in ("none", "0", "false", "no"):
        w = "off"
    if w == "e4m3":
        w = "fp8"
    if w not in ir.WIRE_CHOICES:
        raise HorovodTpuError(
            f"HVD_TPU_XIR_WIRE must be one of {ir.WIRE_CHOICES}, "
            f"got {raw!r}"
        )
    return w


def _axis_n(op: ir.ExchangeOp) -> int:
    if op.groups is not None:
        return len(op.groups[0])
    if isinstance(op.axis, tuple):
        n = 1
        for a in op.axis:
            n *= lax.axis_size(a)
        return n
    return lax.axis_size(op.axis)


def _bf16_around(x: jax.Array, run) -> jax.Array:
    if not jnp.issubdtype(x.dtype, jnp.floating) or x.dtype == jnp.bfloat16:
        return run(x)
    # The down/up casts around the wire are single VMEM-tiled kernels
    # (ops/pallas_kernels.cast_buffer — the reference's ScaleBuffer
    # device kernel), not separate convert HLOs; values are identical
    # to a plain astype pair.
    from ..ops.pallas_kernels import cast_buffer

    return cast_buffer(run(cast_buffer(x, jnp.bfloat16)), x.dtype)


def _run_all_reduce(op: ir.ExchangeOp, x: jax.Array, residual=None):
    from ..ops.traced import Average, Sum

    mean = (op.attr("reduce") or "sum") == "mean"
    red = Average if mean else Sum
    if op.lowering == "hier_adasum":
        from ..topo import hierarchical_adasum_all_reduce

        return hierarchical_adasum_all_reduce(
            x, op.axis, op=red, wire=op.wire
        )
    if op.lowering == "hier":
        from ..topo import hierarchical_all_reduce

        return hierarchical_all_reduce(x, op.axis, op=red, wire=op.wire)
    if op.wire in ("int8", "fp8"):
        if op.ef and residual is not None:
            from ..ops.quantized import quantized_allreduce_ef

            return quantized_allreduce_ef(
                x, residual, op.axis, op=red, wire=op.wire,
                backend=op.attr("qbackend"),
            )
        from ..ops.quantized import quantized_allreduce

        return quantized_allreduce(
            x, op.axis, op=red, wire=op.wire,
            groups=[list(g) for g in op.groups] if op.groups else None,
            backend=op.attr("qbackend"),
        ).astype(x.dtype)

    def dense(v):
        if op.groups is not None:
            from ..ops.traced import _grouped_sum

            y = _grouped_sum(
                v, op.axis, [list(g) for g in op.groups],
                len(op.groups[0]),
            )
        elif isinstance(op.axis, tuple):
            y = lax.psum(v, op.axis)
        else:
            y = lax.psum(v, op.axis)
        return y / _axis_n(op) if mean else y

    if op.wire == "bf16":
        return _bf16_around(x, dense)
    return dense(x)


def _run_reduce_scatter(op: ir.ExchangeOp, x: jax.Array):
    from ..ops.traced import Average, Sum

    mean = (op.attr("reduce") or "sum") == "mean"
    red = Average if mean else Sum
    if op.lowering == "hier_adasum":
        # A standalone adasum reduce_scatter has no meaning: the
        # adaptive combine needs the paired all_gather (the scheduler's
        # RS+AG exchange drives hier_adasum buckets through
        # sched/execute.hier_adasum_flat, never this runner).
        raise HorovodTpuError(
            "reduce_scatter ops cannot run lowering='hier_adasum' "
            "standalone; use the scheduler's paired RS+AG exchange "
            "(or an all_reduce op)"
        )
    if op.lowering == "hier":
        from ..topo import hierarchical_reduce_scatter

        return hierarchical_reduce_scatter(
            x, op.axis, op=red, wire=op.wire
        )
    if op.wire in ("int8", "fp8"):
        from ..ops.quantized import quantized_reduce_scatter

        out = quantized_reduce_scatter(
            x, op.axis, op=red, wire=op.wire,
            groups=[list(g) for g in op.groups] if op.groups else None,
            backend=op.attr("qbackend"),
        )
        return out.astype(x.dtype) if hasattr(out, "astype") else out
    n = _axis_n(op)
    if x.shape[0] % n != 0:
        raise HorovodTpuError(
            f"reduce_scatter payload of {x.shape[0]} rows does not "
            f"divide over {n} participants; pad before building the op"
        )

    def dense(v):
        shard = lax.psum_scatter(
            v, op.axis, scatter_dimension=0, tiled=True,
            axis_index_groups=(
                [list(g) for g in op.groups] if op.groups else None
            ),
        )
        return shard / n if mean else shard

    if op.wire == "bf16":
        return _bf16_around(x, dense)
    return dense(x)


def _run_all_gather(op: ir.ExchangeOp, x: jax.Array):
    if op.lowering == "hier":
        from ..topo import hierarchical_all_gather

        return hierarchical_all_gather(x, op.axis, wire=op.wire)
    if op.wire in ("int8", "fp8"):
        from ..ops.quantized import quantized_all_gather

        return quantized_all_gather(
            x, op.axis, wire=op.wire,
            groups=[list(g) for g in op.groups] if op.groups else None,
            backend=op.attr("qbackend"),
        ).astype(x.dtype)

    def dense(v):
        return lax.all_gather(
            v, op.axis, tiled=True,
            axis_index_groups=(
                [list(g) for g in op.groups] if op.groups else None
            ),
        )

    if op.wire == "bf16":
        return _bf16_around(x, dense)
    return dense(x)


def _run_all_to_all(op: ir.ExchangeOp, x: jax.Array):
    split = int(op.attr("split_axis"))
    concat = int(op.attr("concat_axis"))

    def dense(v):
        return lax.all_to_all(
            v, op.axis, split_axis=split, concat_axis=concat, tiled=True,
            axis_index_groups=(
                [list(g) for g in op.groups] if op.groups else None
            ),
        )

    if op.wire == "bf16":
        return _bf16_around(x, dense)
    return dense(x)


def _run_permute(op: ir.ExchangeOp, x: jax.Array):
    perm = [tuple(p) for p in (op.attr("perm") or ())]

    def dense(v):
        return lax.ppermute(v, op.axis, perm)

    if op.wire == "bf16":
        return _bf16_around(x, dense)
    return dense(x)


def _run_gather_sparse(op: ir.ExchangeOp, x, process_set=None):
    """x = (indices, values); returns the gathered pair, same order of
    collectives as the pre-IR ``sparse_allreduce`` (indices first)."""
    from ..ops import traced

    indices, values = x
    idx = traced.allgather(indices, axis=op.axis, process_set=process_set)
    if op.wire == "bf16":
        vals = _bf16_around(
            values,
            lambda v: traced.allgather(
                v, axis=op.axis, process_set=process_set
            ),
        )
    else:
        vals = traced.allgather(
            values, axis=op.axis, process_set=process_set
        )
    return idx, vals


_RUNNERS = {
    "all_reduce": _run_all_reduce,
    "reduce_scatter": _run_reduce_scatter,
    "all_gather": _run_all_gather,
    "all_to_all": _run_all_to_all,
    "permute": _run_permute,
}


def run_op(op: ir.ExchangeOp, x, *, process_set=None, residual=None):
    """Execute one lowered op on its payload.  ``process_set`` feeds
    the sparse gather (the op's signature carries only the rank tuple);
    ``residual`` engages error feedback on EF-eligible reduce ops
    (the call then returns ``(out, new_residual)``)."""
    if op.lowering == "auto":
        op = op.replace(lowering=lower_mod.resolve_lowering(op))
    if op.op == "gather_dense_from_sparse":
        return _run_gather_sparse(op, x, process_set=process_set)
    if op.op == "all_reduce":
        return _run_all_reduce(op, x, residual=residual)
    return _RUNNERS[op.op](op, x)


def account(program: ir.ExchangeProgram,
            axis_size: Optional[int] = None,
            timeline: Any = None) -> None:
    """Publish one program's planned traffic: ``xir.*`` counters, the
    kind-labeled ``sched.wire_bytes{wire=,kind=}`` +
    ``topo.dcn_bytes{kind=}``/``topo.ici_bytes{kind=}`` gauge series,
    the shared ``topo.*_bytes_total`` running counters, and one
    timeline-lane event per op (lane = ``<KIND>_EXCHANGE``)."""
    per_wire, net = lower_mod.program_bytes(program, axis_size)
    kind = program.kind
    metrics.inc_counter("xir.programs")
    metrics.inc_counter(f"xir.programs.{kind}")
    metrics.inc_counter("xir.ops", len(program.ops))
    for w, nbytes in per_wire.items():
        metrics.set_gauge(
            "sched.wire_bytes", nbytes, {"wire": w, "kind": kind}
        )
        metrics.inc_counter(f"sched.wire_bytes.{w}", nbytes)
    metrics.set_gauge("topo.dcn_bytes", net["dcn"], {"kind": kind})
    metrics.set_gauge("topo.ici_bytes", net["ici"], {"kind": kind})
    metrics.inc_counter("topo.dcn_bytes_total", net["dcn"])
    metrics.inc_counter("topo.ici_bytes_total", net["ici"])
    if timeline is None:
        from ..runtime import get_runtime_or_none

        rt = get_runtime_or_none()
        timeline = rt.timeline if rt is not None else None
    if timeline is not None:
        lane = f"{kind.upper()}_EXCHANGE"
        for op in program.ops:
            timeline.record_op(
                f"{op.op}{op.bucket}[wire={op.wire},"
                f"lower={op.lowering}]",
                lane, lower_mod.op_wire_nbytes(op),
            )


def execute_merged(programs: Sequence[ir.ExchangeProgram],
                   args_lists: Sequence[Sequence[Any]],
                   *,
                   axis_size: Optional[int] = None,
                   process_set=None,
                   store: bool = False) -> List[List[Any]]:
    """Run several co-scheduled programs as ONE rail-interleaved
    emission (the cross-workload merge of ``xir/pipeline.py``): when
    the programs' rails are disjoint — a slice-local MoE all_to_all or
    Ulysses flip riding the dense-grad hop loop — their ops emit in
    the merged order with per-rail ``optimization_barrier`` chains, so
    each workload's collectives land in the other's idle windows.

    Values are identical to executing each program separately (the
    chains are ordering-only and the programs share no payloads);
    ineligible combinations — pipelining off, overlapping rails —
    fall back to the **same-rail concatenation mode** instead when the
    service fusion buffer is on (``svc/fuse.py``): ops in the same
    fusion class coalesce into one padded buffer behind ONE collective
    (elementwise reductions commute with concatenation, so f32 dense
    values stay bitwise identical), still rail-interleaved with the
    remaining solo ops.  Only when neither mode applies does the call
    degrade to plain sequential execution, so the entry point is
    always safe to call.  Returns one output list per program, in
    input order."""
    from . import pipeline

    programs = [
        p if p.lowered else lower_mod.lower(p, axis_size, store=store)
        for p in programs
    ]
    for p, args in zip(programs, args_lists):
        if len(args) != len(p.ops):
            raise HorovodTpuError(
                f"program {p.kind!r} has {len(p.ops)} ops but "
                f"{len(args)} payloads were passed"
            )
    merged = pipeline.merge(programs, axis_size)
    if merged is None:
        units = pipeline.merge_concat(programs, axis_size)
        if units is not None:
            return _execute_concat(
                programs, args_lists, units,
                axis_size=axis_size, process_set=process_set,
            )
        return [
            execute(p, a, axis_size=axis_size, process_set=process_set,
                    store=store)
            for p, a in zip(programs, args_lists)
        ]
    from .. import trace

    metrics.inc_counter("xir.pipeline.merged_programs", len(programs))
    for p in programs:
        account(p, axis_size)
    rail = pipeline.RailChain()
    outs: List[List[Any]] = [[None] * len(p.ops) for p in programs]
    with trace.span(
        "exchange.merged", "exchange",
        kind="+".join(p.kind for p in programs),
    ):
        for pi, oi in pipeline.merge_order(programs, axis_size):
            op = programs[pi].ops[oi]
            r = pipeline.op_rail(op, axis_size)
            x = args_lists[pi][oi]
            leaves = list(x) if isinstance(x, tuple) else [x]
            leaves = rail.tie(leaves, (r,))
            x = tuple(leaves) if isinstance(x, tuple) else leaves[0]
            # The merged op's span is rail-attributed at the RailChain
            # boundary it chains on: the measured rail_busy_frac sees
            # the rider's traffic on the rail the merge placed it on.
            with trace.span(
                f"{programs[pi].kind}.{op.op}{op.bucket}",
                "merged_op", rail=r,
                ctx=programs[pi].trace, kind=programs[pi].kind,
            ), jax.named_scope(
                f"hvd_xir_merged_{programs[pi].kind}_{op.op}"
                f"{op.bucket}_{r}"
            ):
                out = run_op(op, x, process_set=process_set)
            rail.bump(out[0] if isinstance(out, tuple) else out, (r,))
            outs[pi][oi] = out
    return outs


def _execute_concat(programs, args_lists, units, *,
                    axis_size=None, process_set=None):
    """The same-rail concatenation emission: each ``("fused", members)``
    unit packs its members' payloads into one block-aligned flat buffer
    (``svc/fuse.pack_group``) and runs ONE collective; solo units run
    as-is.  All units chain through a shared :class:`~horovod_tpu.xir.
    pipeline.RailChain` on their dominant rail, so the fused buffers
    compose with PR 11 rail interleaving — and the whole emission is
    priced by ``lower.estimate_program_cost`` via
    ``svc/fuse.estimate_concat_gain``.  Values are identical to
    sequential execution (bitwise for dense reductions): concatenation
    commutes with elementwise reduction and the chains are ordering-
    only."""
    from .. import trace
    from ..svc import fuse
    from . import pipeline

    metrics.inc_counter("xir.fusion.merged_programs", len(programs))
    for p in programs:
        account(p, axis_size)
    rail = pipeline.RailChain()
    outs: List[List[Any]] = [[None] * len(p.ops) for p in programs]
    with trace.span(
        "exchange.fused", "exchange",
        kind="+".join(p.kind for p in programs),
    ):
        for kind, members in units:
            ops = [programs[pi].ops[oi] for pi, oi in members]
            xs = [args_lists[pi][oi] for pi, oi in members]
            if kind == "solo" or len(members) == 1:
                op, x = ops[0], xs[0]
                r = pipeline.op_rail(op, axis_size)
                leaves = list(x) if isinstance(x, tuple) else [x]
                leaves = rail.tie(leaves, (r,))
                x = tuple(leaves) if isinstance(x, tuple) else leaves[0]
                with trace.span(
                    f"{programs[members[0][0]].kind}.{op.op}{op.bucket}",
                    "merged_op", rail=r,
                ), jax.named_scope(
                    f"hvd_xir_concat_solo_{op.op}{op.bucket}_{r}"
                ):
                    out = run_op(op, x, process_set=process_set)
                rail.bump(out[0] if isinstance(out, tuple) else out, (r,))
                outs[members[0][0]][members[0][1]] = out
                continue
            fused_op = fuse.concat_ops(
                ops, [int(op.attr("nbytes") or 0) for op in ops]
            )
            align = fuse.align_elems(
                fused_op.wire, fused_op.attr("dtype")
            )
            r = pipeline.op_rail(fused_op, axis_size)
            with trace.span(
                "fuse.concat", "fuse", rail=r, members=len(members),
            ), jax.named_scope(
                f"hvd_xir_concat_{fused_op.op}_{r}_m{len(members)}"
            ):
                buf, layout = fuse.pack_group(xs, align)
                buf = rail.tie([buf], (r,))[0]
                fused_out = run_op(
                    fused_op, buf, process_set=process_set
                )
                rail.bump(fused_out, (r,))
                metrics.inc_counter("xir.fusion.buffers")
                metrics.inc_counter("xir.fusion.members", len(members))
                for (pi, oi), out in zip(
                    members, fuse.unpack_group(fused_out, layout)
                ):
                    outs[pi][oi] = out
    return outs


def execute(program: ir.ExchangeProgram,
            args: Sequence[Any],
            *,
            axis_size: Optional[int] = None,
            process_set=None,
            store: bool = True) -> List[Any]:
    """Lower (if needed) and run a program: op *i* consumes ``args[i]``
    and produces output *i*.  The standalone entry point the non-
    gradient workloads use — the bucketed dense-gradient path drives
    the interpreter through ``sched/execute.py`` instead (its payloads
    interleave with backward compute and EF state)."""
    from .. import trace

    if len(args) != len(program.ops):
        raise HorovodTpuError(
            f"program has {len(program.ops)} ops but {len(args)} "
            "payloads were passed"
        )
    if program.trace is None and trace.enabled():
        program = program.with_trace(
            trace.current_context()
            or trace.new_context(f"xir.{program.kind}")
        )
    if not program.lowered:
        # Service producer path (svc/): non-gradient workloads submit
        # their plan at trace time too — a repeat signature resolves
        # from the ResponseCache with zero re-lowering.  Emission
        # stays right here, so SVC on/off is bitwise identical.
        from .. import svc as _svc

        if _svc.enabled():
            program = _svc.get_service().submit_traced(
                program, producer=f"xir.{program.kind}",
                axis_size=axis_size, store=store,
            )
        else:
            program = lower_mod.lower(program, axis_size, store=store)
    elif store:
        program = lower_mod._store_sync(program)
    account(program, axis_size)
    # Emission accounting for the profiling plane: programs/ops emitted
    # through the interpreter, per kind — published at trace time like
    # account() above.
    from .. import prof

    prof.note_emission(f"xir.{program.kind}", len(program.ops))
    outs = []
    with trace.span(
        f"exchange.{program.kind}", "exchange", ctx=program.trace,
        kind=program.kind, ops=len(program.ops),
    ):
        for op, x in zip(program.ops, args):
            with jax.named_scope(
                f"hvd_xir_{program.kind}_{op.op}{op.bucket}_{op.wire}"
                f"_{op.lowering}"
            ):
                outs.append(run_op(op, x, process_set=process_set))
    return outs
