"""Unified exchange IR: one plan→lower→execute pipeline for every
collective-shaped workload.

``xir`` closes the gap ROADMAP item 2 names: the scheduler's
(bucket, wire, lowering, groups) tuple becomes an explicit,
deterministic :class:`~horovod_tpu.xir.ir.ExchangeProgram`, and the
workloads that used to call raw ``lax`` — MoE all_to_all
(``parallel/moe.py``), Ulysses head/sequence flips
(``parallel/ulysses.py``), sparse embedding exchange
(``ops/sparse.py``), pipeline ppermute (``parallel/pipeline.py``),
FSDP RS+AG (``optim/zero.py``) — route through the same three stages
the dense-gradient path already enjoys:

* **plan** — builders in :mod:`~horovod_tpu.xir.ir` (or
  :func:`from_schedule` for a ``sched/`` bucket schedule);
* **lower** — :mod:`~horovod_tpu.xir.lower` resolves flat-vs-hier per
  op from the (fitted) topology cost model, gates wire compression by
  op-class eligibility, and keys the program in the persistent tune DB
  with its workload kind;
* **execute** — :mod:`~horovod_tpu.xir.interp` emits the existing
  phase primitives (``ops/quantized.py``, ``topo/hierarchical.py``,
  stock ``lax``) with per-exchange metrics and timeline lanes.

A fourth pass — **schedule** (:mod:`~horovod_tpu.xir.pipeline`, the
rail pipeliner) — phase-interleaves the ICI and DCN rails across
buckets and merges co-scheduled programs with disjoint rails
(``HVD_TPU_XIR_PIPELINE``; ordering-only, losses bitwise-identical).

See docs/exchange_ir.md.
"""

from . import interp, ir, lower, pipeline  # noqa: F401
from .interp import (  # noqa: F401
    account,
    execute,
    execute_merged,
    run_op,
    wire_request,
)
from .ir import (  # noqa: F401
    KINDS,
    OPS,
    REDUCE_OPS,
    WIRE_CHOICES,
    ExchangeOp,
    ExchangeProgram,
    all_gather,
    all_reduce,
    all_to_all,
    eligible_lowering,
    eligible_wire,
    gather_dense_from_sparse,
    permute,
    program,
    reduce_scatter,
)
from .lower import (  # noqa: F401
    estimate_program_cost,
    lower as lower_program,
    op_network_bytes,
    op_wire_nbytes,
    program_bytes,
    resolve_lowering,
    tuner_key,
)


def from_schedule(schedule, kind: str = "dense_grad",
                  ef: bool = False, axis=None) -> ExchangeProgram:
    """The dense-gradient bridge: express a
    :class:`~horovod_tpu.sched.plan.BucketSchedule` as an exchange
    program — one op per bucket, already lowered (the plan stage
    resolved wire + lowering per bucket).  ``mode="allreduce"`` buckets
    become ``all_reduce`` ops; ``mode="reduce_scatter"`` buckets become
    ``reduce_scatter`` ops tagged ``paired_all_gather`` (the RS+AG
    decomposition with the optional ZeRO-1 shard update between the
    phases).  ``ef`` marks quantized buckets error-feedback eligible.
    """
    from ..runtime import WORLD_AXIS

    if axis is None:
        axis = WORLD_AXIS
    ops = []
    for bi, b in enumerate(schedule.buckets):
        dtype = b.wire_dtypes[0] if b.wire_dtypes else None
        if schedule.mode == "reduce_scatter":
            op = reduce_scatter(
                axis, wire=b.wire, lowering=b.lowering, bucket=bi,
                ef=ef and b.wire in ("int8", "fp8"),
                nbytes=b.nbytes, dtype=dtype,
            ).replace(attrs={"paired_all_gather": True,
                             "leaves": len(b.indices)})
        else:
            op = all_reduce(
                axis, wire=b.wire, lowering=b.lowering, bucket=bi,
                ef=ef and b.wire in ("int8", "fp8"),
                nbytes=b.nbytes, dtype=dtype,
            ).replace(attrs={"leaves": len(b.indices)})
        ops.append(op)
    return program(kind, ops)
