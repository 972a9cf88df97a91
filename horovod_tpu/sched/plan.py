"""Plan stage: build a :class:`BucketSchedule` from gradient metadata.

The reference's scheduling state lives in the controller loop: tensors
become ready in backward order, ``FuseResponses`` fuses consecutive
ready responses (``controller.cc:793``), and the cycle dispatches one
fused collective per tick.  Under XLA the whole step is one program, so
the plan is computed host-side at trace time and *is* the schedule: an
ordered tuple of buckets, each a set of gradient-leaf indices that
share one wire collective.

Ordering: buckets are emitted in **reverse-backward** order — the order
gradients become available during the backward pass (last layer first),
observed by the ``hooks`` module's grad-boundary taps when available,
else assumed to be the reversed pytree flatten order.  Combined with
``lax.optimization_barrier`` sequencing in the execute stage, this hands
XLA's latency-hiding scheduler a chain of collectives it can overlap
with the remaining backward compute.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence, Tuple

from ..ops import fusion
from ..utils import env


# Per-bucket wire formats the plan stage can assign.  "off" keeps the
# bucket on the dense (or compressor-cast) wire; "bf16" casts the
# bucket's flat buffer around the collective; "int8"/"fp8" route the
# bucket through the quantized phase primitives (ops/quantized.py).
WIRE_CHOICES = ("off", "bf16", "int8", "fp8")

# Per-bucket lowerings the plan stage can assign.  "flat" is today's
# single-collective exchange; "hier" stages it as intra-slice
# reduce_scatter (ICI) -> cross-slice all_reduce (DCN, 1/k payload) ->
# intra-slice all_gather (topo/hierarchical.py); "hier_adasum" keeps
# hier's staging but combines across slices with Adasum's adaptive
# summation (arXiv:2006.02924) — float buckets on cross-slice
# topologies only, and never picked by "auto" (it changes the
# reduction algorithm; it is requested by knob / tuner / the Adasum
# optimizer preset).  The sum-preserving pair is chosen per bucket by
# the topology cost model under HVD_TPU_TOPO_LOWER=auto.
LOWER_CHOICES = ("flat", "hier", "hier_adasum")


def _canon_lowering(lowering: str) -> str:
    lo = (lowering or "auto").strip().lower()
    if lo in ("off", "none", "0", "false", "no", ""):
        lo = "flat"
    if lo in ("on", "1", "true", "yes", "hierarchical"):
        lo = "hier"
    if lo == "adasum":
        lo = "hier_adasum"
    if lo not in LOWER_CHOICES + ("auto",):
        raise ValueError(
            f"HVD_TPU_TOPO_LOWER must be auto|flat|hier|hier_adasum, "
            f"got {lowering!r}"
        )
    return lo


def _canon_wire_choice(wire: str) -> str:
    w = (wire or "off").strip().lower()
    if w in ("none", "0", "false", "no", ""):
        w = "off"
    if w == "e4m3":
        w = "fp8"
    if w not in WIRE_CHOICES:
        raise ValueError(
            f"HVD_TPU_SCHED_WIRE must be one of {WIRE_CHOICES}, "
            f"got {wire!r}"
        )
    return w


@dataclasses.dataclass(frozen=True)
class SchedConfig:
    """Knobs of the bucketed overlap scheduler (``HVD_TPU_SCHED_*``)."""

    mode: str = "allreduce"  # "allreduce" | "reduce_scatter"
    bucket_bytes: Optional[int] = None  # None -> fusion threshold knob
    look_ahead: int = 3
    wire: str = "off"  # "off" | "bf16" | "int8" | "fp8"
    wire_ef: bool = True  # error-feedback residuals for quantized wires
    # "auto" | "flat" | "hier" | "hier_adasum" (HVD_TPU_TOPO_LOWER)
    lowering: str = "auto"

    def __post_init__(self):
        if self.mode not in ("allreduce", "reduce_scatter"):
            raise ValueError(
                f"HVD_TPU_SCHED_MODE must be 'allreduce' or "
                f"'reduce_scatter', got {self.mode!r}"
            )
        object.__setattr__(self, "wire", _canon_wire_choice(self.wire))
        object.__setattr__(self, "lowering", _canon_lowering(self.lowering))

    @classmethod
    def from_env(cls) -> "SchedConfig":
        bucket_bytes = env.get_int(env.SCHED_BUCKET_BYTES, -1)
        return cls(
            mode=(env.get_env(env.SCHED_MODE, "allreduce") or "allreduce")
            .strip().lower(),
            bucket_bytes=None if bucket_bytes < 0 else bucket_bytes,
            look_ahead=env.get_int(env.SCHED_LOOK_AHEAD, 3),
            wire=env.get_env(env.SCHED_WIRE, "off") or "off",
            wire_ef=env.get_bool(env.SCHED_WIRE_EF, True),
            lowering=env.get_env(env.TOPO_LOWER, "auto") or "auto",
        )


# Trace-time config override (the fusion-threshold override pattern):
# tests and probe variants pin a config without touching the env.
_config_override: Optional[SchedConfig] = None


def set_config_override(cfg: Optional[SchedConfig]) -> None:
    global _config_override
    _config_override = cfg


def current_config() -> SchedConfig:
    return (
        _config_override if _config_override is not None
        else SchedConfig.from_env()
    )


@dataclasses.dataclass(frozen=True)
class Bucket:
    """One fused exchange: leaf ``indices`` (original flatten order)
    sharing a wire collective of ``nbytes`` total.  ``wire`` is the
    bucket's wire format (``WIRE_CHOICES``): the plan requests it, the
    execute stage lowers it (quantized formats through the
    ops/quantized.py phase primitives)."""

    indices: Tuple[int, ...]
    nbytes: int
    wire_dtypes: Tuple[str, ...]  # distinct dtypes, flatten order
    pinned: bool = False  # from an explicit user group
    wire: str = "off"
    # Exchange lowering (LOWER_CHOICES): "flat" = one collective,
    # "hier" = the ICI/DCN two-level staging.  The plan requests it
    # from the topology cost model; the execute stage lowers it (and
    # downgrades to flat where the reduction shape cannot factor).
    lowering: str = "flat"


@dataclasses.dataclass(frozen=True)
class BucketSchedule:
    """Ordered exchange plan for one gradient pytree."""

    buckets: Tuple[Bucket, ...]
    mode: str
    total_bytes: int

    def __len__(self) -> int:
        return len(self.buckets)

    def signature(self) -> Tuple:
        """Hashable identity: two schedules with equal signatures emit
        identical exchange programs (determinism tests key on this)."""
        return (
            self.mode,
            tuple((b.indices, b.nbytes, b.wire_dtypes, b.pinned, b.wire,
                   b.lowering)
                  for b in self.buckets),
        )


def build_schedule(
    sizes_bytes: Sequence[int],
    dtypes: Sequence[str],
    cfg: Optional[SchedConfig] = None,
    *,
    order: Optional[Sequence[int]] = None,
    pinned: Sequence[Sequence[int]] = (),
    wire: Optional[str] = None,
    lowering: Optional[str] = None,
    axis_size: Optional[int] = None,
) -> BucketSchedule:
    """Plan the exchange for leaves of ``sizes_bytes``/``dtypes``.

    ``order`` is the backward-readiness order of leaf indices (first
    element = first gradient available); ``None`` assumes the reversed
    flatten order (parameters registered last finish their backward
    first).  ``pinned`` buckets (explicit user groups,
    ``DistributedOptimizer(groups=...)``) fuse atomically and are
    emitted where their *earliest-ready* member falls in the order.

    ``wire`` overrides ``cfg.wire`` as the requested per-bucket wire
    format; each bucket gets it only when eligible
    (:func:`eligible_wire` — quantized wires need a single floating
    dtype), else falls back to ``"off"`` for that bucket.

    ``lowering`` overrides ``cfg.lowering`` (``HVD_TPU_TOPO_LOWER``):
    ``"auto"`` asks the topology cost model per bucket — large buckets
    on a multi-slice topology go ``"hier"``, sub-threshold ones stay
    ``"flat"`` (``axis_size`` sizes the reduction axis for the model;
    None prices the full world).  On a single-slice topology every
    bucket is ``"flat"``, so the schedule — and the emitted program —
    is identical to the pre-topology one.

    Pure function of its arguments plus the process-wide topology
    (identical on every rank — env-forced or discovered from the same
    ``jax.devices()`` order): same metadata + config -> identical
    schedule (plan determinism is load-bearing — every SPMD rank must
    emit the same collectives in the same order).
    """
    if cfg is None:
        cfg = current_config()
    wire = _canon_wire_choice(cfg.wire if wire is None else wire)
    lowering = _canon_lowering(
        cfg.lowering if lowering is None else lowering
    )
    if cfg.bucket_bytes is None and lowering in ("auto", "hier"):
        # Rail pipeliner split points (HVD_TPU_XIR_PIPELINE=on only —
        # "auto" is reorder-only so the plan stays identical): pick the
        # bucket size whose equal-split schedule the max-of-rails model
        # prices cheapest under the fitted per-rail bandwidths.
        from ..xir import pipeline as railpipe

        pipe_bytes = railpipe.plan_bucket_bytes(
            sum(int(s) for s in sizes_bytes), axis_size
        )
        if pipe_bytes is not None:
            cfg = dataclasses.replace(cfg, bucket_bytes=pipe_bytes)
    n = len(sizes_bytes)
    if order is None:
        order = range(n - 1, -1, -1)
    order = [i for i in order if 0 <= i < n]
    if len(set(order)) != n:
        # Incomplete / duplicated observation: fall back to the assumed
        # reverse-backward order rather than dropping leaves.
        order = list(range(n - 1, -1, -1))

    pinned_set = set()
    pinned_buckets: List[Tuple[int, Bucket]] = []
    rank_of = {leaf: pos for pos, leaf in enumerate(order)}
    for group in pinned:
        idx = tuple(int(i) for i in group)
        if not idx:
            continue
        pinned_set.update(idx)
        pinned_buckets.append((
            min(rank_of[i] for i in idx),
            _make_bucket(idx, sizes_bytes, dtypes, pinned=True,
                         wire=wire, lowering=lowering,
                         axis_size=axis_size),
        ))

    free = [i for i in order if i not in pinned_set]
    planned = fusion.bucket_plan(
        [sizes_bytes[i] for i in free],
        [dtypes[i] for i in free],
        cfg.bucket_bytes,
        look_ahead=cfg.look_ahead,
    )
    planned_buckets: List[Tuple[int, Bucket]] = []
    for b in planned:
        idx = tuple(sorted(free[j] for j in b))
        planned_buckets.append((
            min(rank_of[i] for i in idx),
            _make_bucket(idx, sizes_bytes, dtypes, wire=wire,
                         lowering=lowering, axis_size=axis_size),
        ))

    ordered = [
        b for _, b in sorted(
            pinned_buckets + planned_buckets, key=lambda p: p[0]
        )
    ]
    return BucketSchedule(
        buckets=tuple(ordered),
        mode=cfg.mode,
        total_bytes=sum(b.nbytes for b in ordered),
    )


def eligible_wire(wire: str, wire_dtypes: Sequence[str]) -> str:
    """Downgrade a requested wire format to what the bucket supports.

    Quantized wires (int8/fp8) need one floating dtype per bucket (the
    residual/scale bookkeeping tracks a single flat buffer); bf16 needs
    floating leaves.  Ineligible buckets fall back to ``"off"`` — the
    dense (or compressor) wire — never to a half-applied quantization.
    """
    if wire == "off":
        return wire
    import jax.numpy as jnp

    floating = all(
        jnp.issubdtype(jnp.dtype(d), jnp.floating) for d in wire_dtypes
    )
    if not floating:
        return "off"
    if wire in ("int8", "fp8") and len(set(wire_dtypes)) != 1:
        return "off"
    return wire


def resolve_lowering(
    requested: str, nbytes: int, axis_size: Optional[int] = None,
    wire_dtypes: Sequence[str] = (),
) -> str:
    """Resolve a requested lowering ("auto"/"flat"/"hier"/
    "hier_adasum") to the concrete per-bucket choice.  "auto" asks the
    topology cost model (flat vs hier only — it never switches the
    reduction algorithm to hier_adasum); a single-slice topology (or
    non-factorable axis) always resolves flat, so the pre-topology
    schedule is reproduced exactly — including for a hier_adasum
    request, which must be bitwise-identical to flat there.  A
    hier_adasum request on a non-floating bucket (``wire_dtypes``)
    also resolves flat: the adaptive coefficients divide by norms."""
    if requested == "flat":
        return "flat"
    from ..topo import model as topo_model

    topo = topo_model.current()
    n = topo.world if axis_size is None else axis_size
    s, _ = topo.factor_axis(n)
    if s == 1:
        return "flat"
    if requested == "hier_adasum":
        import jax.numpy as jnp

        floating = all(
            jnp.issubdtype(jnp.dtype(d), jnp.floating)
            for d in wire_dtypes
        )
        if wire_dtypes and not floating:
            return "flat"
        return "hier_adasum"
    if requested == "hier":
        return "hier"
    return topo.choose_lowering("all_reduce", nbytes, n)


def _make_bucket(
    indices: Tuple[int, ...],
    sizes_bytes: Sequence[int],
    dtypes: Sequence[str],
    pinned: bool = False,
    wire: str = "off",
    lowering: str = "auto",
    axis_size: Optional[int] = None,
) -> Bucket:
    wire_dtypes = tuple(dict.fromkeys(dtypes[i] for i in indices))
    nbytes = sum(int(sizes_bytes[i]) for i in indices)
    return Bucket(
        indices=indices,
        nbytes=nbytes,
        wire_dtypes=wire_dtypes,
        pinned=pinned,
        wire=eligible_wire(wire, wire_dtypes),
        lowering=resolve_lowering(lowering, nbytes, axis_size,
                                  wire_dtypes),
    )


def wire_bytes(bucket: Bucket, block: Optional[int] = None) -> int:
    """One-phase wire payload bytes of a bucket under its wire format
    (the apples-to-apples number behind ``sched.wire_bytes{wire=}`` and
    the compression-ratio gauge): dense bytes for ``off``, 2
    bytes/element for ``bf16``, 1 byte/element + fp32 block scales for
    the quantized formats."""
    if bucket.wire == "off":
        return bucket.nbytes
    import jax.numpy as jnp

    itemsize = jnp.dtype(bucket.wire_dtypes[0]).itemsize
    elems = bucket.nbytes // itemsize
    if bucket.wire == "bf16":
        return elems * 2
    if block is None:
        from ..ops.quantized import quant_block

        block = quant_block()
    return elems + 4 * (-(-elems // block))
