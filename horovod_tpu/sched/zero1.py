"""Bucketed ZeRO-1: the scheduler's ``reduce_scatter+all_gather`` mode
with per-bucket sharded optimizer updates.

``optim/zero.zero_train_step`` already decomposes the exchange as one
whole-model ``psum_scatter -> shard update -> all_gather`` (following
arXiv:2004.13336).  This module re-cuts that pipeline at bucket
granularity using the plan stage: each bucket reduce-scatters as soon
as its gradients exist, runs the optimizer on its 1/N slice, and
all-gathers its updates — so the all-gather of bucket *k* overlaps the
reduce-scatter of bucket *k+1* instead of the whole model serializing
through three global collectives.  Optimizer state still shrinks
N-fold (each rank holds 1/N of every bucket).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax

from .. import metrics
from ..optim.zero import _state_spec
from ..runtime import WORLD_AXIS
from .plan import BucketSchedule, SchedConfig, build_schedule, current_config


@dataclass(frozen=True)
class _BucketLayout:
    """Host-side layout of one bucket's flat buffer."""

    indices: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]  # elements per member leaf
    dtype: jnp.dtype
    n: int  # valid elements
    padded: int  # n rounded up to a shard-count multiple
    shard_len: int
    wire: str = "off"  # per-bucket wire format (plan.WIRE_CHOICES)
    # per-bucket lowering (plan.LOWER_CHOICES): "hier"/"hier_adasum"
    # shard over the ICI sub-axis only — k = slice_size shards,
    # replicated across slices — so the optimizer update and its
    # all_gather never cross DCN; only the 1/k gradient reduction
    # (plain sum for "hier", adaptive summation for "hier_adasum") does.
    lowering: str = "flat"
    shards: int = 0  # world (flat) or slice_size (hier)


def _layouts(
    params, world: int, cfg: SchedConfig
) -> Tuple[List[_BucketLayout], BucketSchedule]:
    leaves = jax.tree.leaves(params)
    sizes_bytes = [int(l.size) * jnp.dtype(l.dtype).itemsize for l in leaves]
    dtypes = [str(jnp.dtype(l.dtype)) for l in leaves]
    schedule = build_schedule(sizes_bytes, dtypes, cfg, axis_size=world)
    from ..topo import model as topo_model

    s_dcn, k_ici = topo_model.current().factor_axis(world)
    layouts = []
    for b in schedule.buckets:
        if len(b.wire_dtypes) != 1:
            raise ValueError(
                "bucketed ZeRO requires single-dtype buckets "
                f"(got {b.wire_dtypes}); pinned mixed-dtype groups are "
                "not supported here"
            )
        shapes = tuple(tuple(leaves[i].shape) for i in b.indices)
        sizes = tuple(
            int(leaves[i].size) for i in b.indices
        )
        n = sum(sizes)
        lowering = b.lowering if s_dcn > 1 else "flat"
        # Hier buckets shard over the ICI sub-axis only: k shards per
        # slice, the shard replicated across slices, so the optimizer
        # update and its all_gather stay on ICI.
        shards = k_ici if lowering in ("hier", "hier_adasum") else world
        unit = shards
        if b.wire in ("int8", "fp8"):
            # Quantized shards must stay block-aligned so the
            # post-update all_gather can re-quantize without repadding.
            from ..ops.quantized import quant_block

            unit = shards * quant_block()
        padded = -(-n // unit) * unit
        layouts.append(_BucketLayout(
            indices=b.indices, shapes=shapes, sizes=sizes,
            dtype=jnp.dtype(b.wire_dtypes[0]), n=n, padded=padded,
            shard_len=padded // shards, wire=b.wire,
            lowering=lowering, shards=shards,
        ))
    return layouts, schedule


def bucket_layouts(
    params, world: int, cfg: Optional[SchedConfig] = None
) -> List[_BucketLayout]:
    """Public layout rebuild for a given world size (the elastic
    remesh entry point): the deterministic host-side description of how
    ``bucketed_zero_step`` shards ``params``' buckets over ``world``
    ranks.  ``elastic/remesh.plan_reshard`` pairs the old and new
    worlds' layout lists to compute the shard exchange; the layouts are
    a pure function of (params metadata, world, cfg) so every rank —
    and the driver — derives the identical plan."""
    if cfg is None:
        cfg = current_config()
    layouts, _ = _layouts(params, world, cfg)
    return layouts


def _bucket_flat(leaves, layout: _BucketLayout) -> jax.Array:
    flat = jnp.concatenate(
        [leaves[i].reshape(-1) for i in layout.indices]
    ) if len(layout.indices) > 1 else leaves[layout.indices[0]].reshape(-1)
    if layout.padded != layout.n:
        flat = jnp.pad(flat, (0, layout.padded - layout.n))
    return flat


def _bucket_unflat(flat: jax.Array, layout: _BucketLayout):
    out, off = [], 0
    for shape, size in zip(layout.shapes, layout.sizes):
        out.append(flat[off:off + size].reshape(shape))
        off += size
    return out


def bucketed_zero_step(
    loss_fn,
    tx: optax.GradientTransformation,
    *,
    axis=WORLD_AXIS,
    cfg: Optional[SchedConfig] = None,
    pre_update=None,
):
    """Compiled SPMD step with bucket-granular ZeRO-1 sharding.

    Call convention matches ``optim.zero.zero_train_step``:
    ``step.init(params)`` then ``step(params, opt_state, batch) ->
    (params, opt_state, loss)``.  Params stay replicated; the optimizer
    state is a tuple of per-bucket states whose array leaves live
    sharded over ``axis`` (1/N per chip).  ``pre_update`` (e.g.
    ``optim.zero.clip_by_global_norm``) runs on the full list of
    gradient shards before any bucket's optimizer update — global
    reductions see every shard.

    ``cfg.wire`` (``HVD_TPU_SCHED_WIRE``): quantized buckets run the
    ZeRO pipeline end-to-end on the quantized wire — the per-bucket
    reduce-scatter quantizes ``g + r`` (EF residual in the bucket's
    state when ``cfg.wire_ef``), the sharded optimizer update consumes
    the dequantized **fp32** shard, and only the post-update
    ``all_gather`` re-quantizes.  A quantized bucket's state entry
    becomes ``{"tx": <inner state>, "ef": <residual>}``; with
    ``wire="off"`` the state structure is unchanged from PR 3.

    ``cfg.lowering`` (``HVD_TPU_TOPO_LOWER``): on a multi-slice
    topology, ``hier`` buckets shard over the **ICI sub-axis** — k =
    slice_size shards, replicated across slices — so the optimizer
    update and its all_gather never cross DCN; only the slice-local
    gradient shard's cross-slice sum does (and only that hop carries a
    compressed wire).  Optimizer state shrinks k-fold instead of
    N-fold: the slice-vs-world sharding trade documented in
    docs/topology.md.  ``hier_adasum`` buckets shard identically but
    the cross-slice hop adaptively combines the per-slice *mean*
    shards (Adasum, arXiv:2006.02924) before the sharded update — the
    large-batch lowering, docs/adasum.md.  Single-slice topologies
    resolve every bucket flat and reproduce the PR 3/4 behavior
    exactly.
    """
    from jax.sharding import PartitionSpec as P

    from .. import runtime as _rt

    if cfg is None:
        cfg = current_config()
    rt = _rt.get_runtime()
    mesh = rt.mesh
    world = rt.size
    meta: dict = {}

    def _set_layout(params_like):
        meta["layouts"], meta["schedule"] = _layouts(
            params_like, world, cfg
        )

    def _ef_on(lay: _BucketLayout) -> bool:
        # Hier buckets run EF-free: their quantization (if any) lives on
        # the cross-slice hop of the slice-summed shard, not on the
        # gradient, so a gradient-shaped residual has nothing to absorb.
        return (
            cfg.wire_ef and lay.wire in ("int8", "fp8")
            and lay.lowering not in ("hier", "hier_adasum")
        )

    def _shard_index(lay: _BucketLayout, idx):
        # Hier-family buckets shard over the ICI sub-axis: position
        # within the slice (slice-major device order, topo/ contract).
        if lay.lowering in ("hier", "hier_adasum"):
            return lax.rem(idx, lay.shards)
        return idx

    def _intra_groups():
        from ..topo import model as topo_model

        intra, _ = topo_model.current().axis_groups(world)
        return intra

    def init_body(params):
        leaves = jax.tree.leaves(params)
        idx = lax.axis_index(axis)
        states = []
        for lay in meta["layouts"]:
            flat = _bucket_flat(leaves, lay)
            shard = lax.dynamic_slice(
                flat, (_shard_index(lay, idx) * lay.shard_len,),
                (lay.shard_len,),
            )
            st = tx.init(shard)
            if _ef_on(lay):
                st = {"tx": st, "ef": jnp.zeros((lay.padded,), jnp.float32)}
            states.append(st)
        return tuple(states)

    def step_body(params, opt_states, batch):
        from ..ops.quantized import (
            quantized_all_gather,
            quantized_reduce_scatter,
        )
        from ..ops.traced import Sum

        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        gleaves, treedef = jax.tree.flatten(grads)
        pleaves = jax.tree.leaves(params)
        idx = lax.axis_index(axis)
        layouts = meta["layouts"]

        # Phase 1: per-bucket reduce-scatter, barrier-chained so buckets
        # issue in reverse-backward order and overlap the backward.
        # Quantized buckets ride the int8/fp8 wire (ops/quantized.py);
        # the dequant-accumulated shard is fp32 either way, so the
        # sharded optimizer update below always runs in full precision.
        #
        # Rail pipelining (xir/pipeline.py): when engaged, hier buckets
        # chain their ICI reduce-scatter on the ICI rail and their
        # cross-slice hop on the DCN rail — bucket i's DCN hop then
        # overlaps bucket i+1's ICI reduce-scatter.  hier_adasum and
        # flat buckets serialize against both rails (docs/adasum.md);
        # ordering-only, values bitwise-identical either way.
        from ..xir import pipeline as railpipe

        gshards = []
        new_residuals = []
        rails = railpipe.RailChain()
        use_rails = railpipe.engaged(meta["schedule"], world)
        pipe_overlaps = 0
        token = None
        intra = (
            _intra_groups()
            if any(lay.lowering in ("hier", "hier_adasum")
                   for lay in layouts) else None
        )
        for lay, st in zip(layouts, opt_states):
            g = _bucket_flat(gleaves, lay)
            if use_rails:
                bucket_rails = (
                    ("ici",) if lay.lowering == "hier"
                    else ("ici", "dcn")
                )
                (g,) = rails.tie([g], bucket_rails)
            elif token is not None:
                g, token = lax.optimization_barrier((g, token))
            if lay.lowering in ("hier", "hier_adasum"):
                # ICI reduce_scatter to the slice-local 1/k shard, then
                # the cross-slice hop over DCN — the only slow-network
                # leg, and the only one the bucket's wire compresses.
                # "hier" sums across slices (then /world = global
                # mean); "hier_adasum" adaptively combines the
                # per-slice means (arXiv:2006.02924) on the 1/k shard
                # before the sharded update.
                from ..topo import dcn_adasum, dcn_all_reduce

                shard = lax.psum_scatter(
                    g, axis, scatter_dimension=0, tiled=True,
                    axis_index_groups=intra,
                )
                if use_rails and lay.lowering == "hier":
                    # ICI phase done: release the ICI rail before the
                    # cross-slice hop so the next bucket's ICI
                    # reduce-scatter can overlap this bucket's DCN leg.
                    rails.bump(shard, ("ici",))
                    (shard,) = rails.tie([shard], ("dcn",))
                    pipe_overlaps += 1
                if lay.lowering == "hier_adasum":
                    shard = shard / lay.shards  # slice mean
                    shard = dcn_adasum(shard, axis, wire=lay.wire)
                else:
                    shard = dcn_all_reduce(shard, axis, wire=lay.wire)
                    shard = shard / world
                new_residuals.append(None)
            elif lay.wire in ("int8", "fp8"):
                if _ef_on(lay):
                    e = g.astype(jnp.float32) + st["ef"]
                    shard, r_new = quantized_reduce_scatter(
                        e, axis, op=Sum, wire=lay.wire, ef=True,
                    )
                    new_residuals.append(r_new)
                else:
                    shard = quantized_reduce_scatter(
                        g, axis, op=Sum, wire=lay.wire,
                    )
                    new_residuals.append(None)
                shard = shard / world
            else:
                shard = lax.psum_scatter(
                    g, axis, scatter_dimension=0, tiled=True
                ) / world
                new_residuals.append(None)
            if use_rails:
                rails.bump(
                    shard,
                    ("dcn",) if lay.lowering == "hier"
                    else ("ici", "dcn"),
                )
            else:
                token = shard.reshape(-1)[0]
            gshards.append(shard)
        if use_rails:
            metrics.inc_counter(
                "sched.pipeline.overlap_windows", max(pipe_overlaps - 1, 0)
            )
        if pre_update is not None:
            gshards = pre_update(gshards)

        # Phase 2: shard update + all-gather per bucket; only the
        # post-update gather re-quantizes on a quantized bucket.
        uleaves = [None] * len(gleaves)
        new_states = []
        for lay, shard, state, r_new in zip(
            layouts, gshards, opt_states, new_residuals
        ):
            tx_state = state["tx"] if _ef_on(lay) else state
            pflat = _bucket_flat(pleaves, lay)
            pshard = lax.dynamic_slice(
                pflat, (_shard_index(lay, idx) * lay.shard_len,),
                (lay.shard_len,),
            )
            ushard, tx_state = tx.update(
                shard.astype(lay.dtype), tx_state, pshard
            )
            if _ef_on(lay):
                new_states.append({"tx": tx_state, "ef": r_new})
            else:
                new_states.append(tx_state)
            if lay.lowering in ("hier", "hier_adasum"):
                # ICI-only gather: every slice holds the full shard
                # set, so the updated parameters reassemble without
                # touching DCN (dense — the wire compressed only the
                # gradient's cross-slice hop).
                uflat = lax.all_gather(
                    ushard, axis, tiled=True, axis_index_groups=intra
                )[:lay.n]
            elif lay.wire in ("int8", "fp8"):
                uflat = quantized_all_gather(
                    ushard, axis, wire=lay.wire
                )[:lay.n].astype(lay.dtype)
            else:
                uflat = lax.all_gather(ushard, axis, tiled=True)[:lay.n]
            for i, u in zip(lay.indices, _bucket_unflat(uflat, lay)):
                uleaves[i] = u
        updates = jax.tree.unflatten(treedef, uleaves)
        params = optax.apply_updates(params, updates)
        return params, tuple(new_states), lax.pmean(loss, axis)

    def state_spec():
        def abstract_init():
            states = []
            for lay in meta["layouts"]:
                st = tx.init(jnp.zeros((lay.shard_len,), lay.dtype))
                if _ef_on(lay):
                    st = {
                        "tx": st,
                        "ef": jnp.zeros((lay.padded,), jnp.float32),
                    }
                states.append(st)
            return tuple(states)

        return _state_spec(jax.eval_shape(abstract_init), axis)

    def _record():
        from .execute import record_wire_metrics

        sched = meta["schedule"]
        metrics.set_gauge("sched.buckets_per_step", len(sched))
        metrics.set_gauge("sched.bytes_per_step", sched.total_bytes)
        metrics.inc_counter("sched.zero_steps_built")
        record_wire_metrics(sched)

    class _Step:
        def __init__(self):
            self._fn = None

        @property
        def schedule(self) -> BucketSchedule:
            return meta["schedule"]

        def init(self, params):
            _set_layout(params)
            _record()
            f = jax.shard_map(
                init_body, mesh=mesh, in_specs=(P(),),
                out_specs=state_spec(), check_vma=False,
            )
            return jax.jit(f)(params)

        def __call__(self, params, opt_states, batch):
            if "layouts" not in meta:
                raise RuntimeError(
                    "bucketed_zero_step: call init(params) first"
                )
            if self._fn is None:
                specs = _state_spec(opt_states, axis)
                batch_spec = jax.tree.map(lambda _: P(axis), batch)
                self._fn = jax.jit(jax.shard_map(
                    step_body, mesh=mesh,
                    in_specs=(P(), specs, batch_spec),
                    out_specs=(P(), specs, P()),
                    check_vma=False,
                ), donate_argnums=(0, 1))
            return self._fn(params, opt_states, batch)

    return _Step()
