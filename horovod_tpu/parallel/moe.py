"""Expert parallelism: top-k routed MoE with all_to_all dispatch.

SURVEY.md §2.5: the reference's only EP-relevant primitive is the
``alltoall`` collective (``EnqueueTensorAlltoall``,
``operations.cc:1630``) — routing itself lives above Horovod.  Here the
full GShard/Switch pattern is native: experts are sharded over the
``ep`` mesh axis, tokens are dispatched to their experts with one
``all_to_all``, processed by per-expert MLPs as one batched einsum
(keeps the MXU busy across experts), and combined back with a second
``all_to_all``.  Static capacity (tokens/expert) keeps every shape
fixed for XLA; overflow tokens are dropped (zero combine weight) and
ride the residual connection, the standard Switch behaviour.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.ad_checkpoint import checkpoint_name

from ..ops import expert_kernels
from .mesh import EP_AXIS
from .tensor import TensorParallelMLP, _axis_present


def _top_k_gating(
    logits: jax.Array, k: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k routing with per-expert capacity.

    logits: [S, E] (f32).  Returns (combine [S, E, C], dispatch bool
    [S, E, C], aux load-balancing loss scalar).
    """
    s, e = logits.shape
    gates = jax.nn.softmax(logits, axis=-1)

    remaining = gates
    location_base = jnp.zeros((e,), jnp.int32)  # tokens already assigned
    combine = jnp.zeros((s, e, capacity), jnp.float32)
    importance = jnp.zeros((e,), jnp.float32)
    load = jnp.zeros((e,), jnp.float32)

    for _ in range(k):
        choice = jnp.argmax(remaining, axis=-1)  # [S]
        onehot = jax.nn.one_hot(choice, e, dtype=jnp.float32)  # [S, E]
        gate_val = jnp.sum(gates * onehot, axis=-1)  # [S]
        # Position of each token within its chosen expert's buffer, in
        # token order, offset by assignments from earlier choices.
        pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # [S, E]
        pos_tok = jnp.sum(pos, axis=-1).astype(jnp.int32) + location_base[choice]
        keep = pos_tok < capacity
        slot = jax.nn.one_hot(
            jnp.where(keep, pos_tok, capacity), capacity + 1, dtype=jnp.float32
        )[:, :capacity]
        combine = combine + (
            (gate_val * keep)[:, None] * onehot
        )[..., None] * slot[:, None, :]
        location_base = location_base + jnp.sum(
            onehot * keep[:, None], axis=0
        ).astype(jnp.int32)
        importance = importance + jnp.mean(gates * onehot, axis=0)
        load = load + jnp.mean(onehot, axis=0)
        remaining = remaining * (1.0 - onehot)

    # Switch-style auxiliary loss: E · Σ_e mean-gate_e · token-frac_e,
    # computed from the first-choice statistics accumulated above.
    aux = e * jnp.sum(importance / k * load / k)
    dispatch = combine > 0.0
    return combine, dispatch, aux


def _routed_all_to_all(x: jax.Array, axis: str, split_axis: int,
                       concat_axis: int, bucket: int = 0) -> jax.Array:
    """One MoE all_to_all through the exchange IR (``xir``): the op
    carries the payload metadata the tuner/store key and the byte
    gauges need, and the interpreter emits ``lax.all_to_all``
    on the dense wire.  Wire requests
    (``HVD_TPU_XIR_WIRE`` / ``HVD_TPU_SCHED_WIRE``) gate through
    shuffle-op eligibility: bf16 casts the wire, int8/fp8 stay off."""
    from .. import xir

    op = xir.all_to_all(
        axis, split_axis=split_axis, concat_axis=concat_axis,
        wire=xir.wire_request(), bucket=bucket,
        nbytes=x.size * x.dtype.itemsize, dtype=x.dtype,
    )
    return xir.execute(
        xir.program("moe", [op]), [x], axis_size=lax.axis_size(axis)
    )[0]


def moe_alltoall_dispatch(x: jax.Array, axis: str = EP_AXIS) -> jax.Array:
    """[E, C, d] local dispatch buffers → [E_local, n·C, d] expert shards
    (one all_to_all over the ep axis); inverse of itself with the
    reshape transposed — see MoELayer for the round trip."""
    return _routed_all_to_all(x, axis, split_axis=0, concat_axis=1)


def moe_alltoall_combine(y: jax.Array, axis: str = EP_AXIS) -> jax.Array:
    """Inverse all_to_all: send each n·C slice back to its source rank
    ([E_local, n·C, d] → [E, C, d])."""
    return _routed_all_to_all(y, axis, split_axis=1, concat_axis=0,
                              bucket=1)


class MoELayer(nn.Module):
    """Mixture-of-experts FFN sharded over the ``ep`` axis.

    ``num_experts_local`` experts per device (global E = n·local);
    returns (output [B,T,d], aux_loss).  Outside shard_map it degrades
    to a single-device MoE with E = num_experts_local (the test path).
    """

    num_experts_local: int
    hidden: int
    k: int = 2
    capacity_factor: float = 1.25
    axis: str = EP_AXIS
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        b, t, d = x.shape
        n = lax.axis_size(self.axis) if _axis_present(self.axis) else 1
        e = n * self.num_experts_local
        s = b * t
        capacity = max(1, int(s * self.capacity_factor * self.k / e))

        xf = x.reshape(s, d)
        # Router always in f32: tiny matmul, numerically load-bearing.
        logits = nn.Dense(e, dtype=jnp.float32, name="router")(
            xf.astype(jnp.float32)
        )
        combine, dispatch, aux = _top_k_gating(logits, self.k, capacity)

        buf = jnp.einsum(
            "sec,sd->ecd", dispatch.astype(xf.dtype), xf
        )  # [E, C, d]
        if n > 1:
            buf = moe_alltoall_dispatch(buf, self.axis)  # [E_loc, n·C, d]
        else:
            buf = buf.reshape(self.num_experts_local, n * capacity, d)

        wi = self.param(
            "wi", nn.initializers.lecun_normal(),
            (self.num_experts_local, d, self.hidden), jnp.float32,
        )
        wo = self.param(
            "wo", nn.initializers.lecun_normal(),
            (self.num_experts_local, self.hidden, d), jnp.float32,
        )
        compute_dtype = self.dtype or x.dtype
        h = jnp.einsum(
            "ecd,edh->ech", buf.astype(compute_dtype),
            wi.astype(compute_dtype),
        )
        h = nn.gelu(h)
        y = jnp.einsum("ech,ehd->ecd", h, wo.astype(compute_dtype))

        if n > 1:
            y = moe_alltoall_combine(y, self.axis)
        else:
            y = y.reshape(e, capacity, d)
        out = jnp.einsum("sec,ecd->sd", combine.astype(y.dtype), y)
        return out.reshape(b, t, d), aux


# ---------------------------------------------------------------------------
# Dropless experts: sigmoid scores, group-limited top-k, sorted dispatch and
# a grouped product over the experts this device holds
# ---------------------------------------------------------------------------


def route_group_limited(scores: jax.Array, bias: jax.Array, k: int,
                        n_group: int, topk_group: int, scale: float
                        ) -> Tuple[jax.Array, jax.Array]:
    """DeepSeek-V3's router without an auxiliary loss (arXiv:2412.19437
    section 2.1.2).  ``scores`` [S, E] are the sigmoids of the router's
    logits, ``bias`` [E] the selection bias (a buffer: it moves the
    choice and never the weight).  The experts lie in ``n_group`` groups
    of E / n_group; a group's score is the sum of its two largest
    ``scores + bias``, the best ``topk_group`` groups stay, and the ``k``
    largest ``scores + bias`` among their experts are chosen.  Returns
    (ids [S, k] int32, weights [S, k]): ``scale * s_i / sum of the chosen
    s``."""
    s, e = scores.shape
    choice = scores + lax.stop_gradient(bias)
    if n_group > 1:
        grouped = choice.reshape(s, n_group, e // n_group)
        group_score = jnp.sum(lax.top_k(grouped, 2)[0], axis=-1)
        kept = lax.top_k(group_score, topk_group)[1]            # [S, g]
        group_kept = jnp.any(
            kept[..., None] == jnp.arange(n_group), axis=1)     # [S, G]
        choice = jnp.where(
            group_kept[..., None], grouped, -jnp.inf).reshape(s, e)
    # a name, so that a caller's ``jax.checkpoint`` policy can keep the
    # choice (k integers a token) and not sort for it again
    ids = checkpoint_name(
        lax.top_k(lax.stop_gradient(choice), k)[1], "moe_route")
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    weights = scale * chosen / (
        jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
    return ids.astype(jnp.int32), weights


# Rows of a grouped product's tile.  A tile costs much the same whatever
# it holds (its expert's matrices read, their gradients' blocks read and
# written), so the loop's time goes by the number of tiles: at 256 rows a
# lightly loaded expert is one tile and only a heavy one is more, and the
# step's time moves less with the router's draw than at 128 (PERF.md, PR 32).
_TILE = 256


def tile_rows(expected_pairs: float) -> int:
    """Rows of a tile where a held expert expects ``expected_pairs`` pairs
    of a batch: the fewest multiples of ``_TILE`` (at most four) that hold
    one and a half times that.  An expert whose expected load fills a tile
    would take one tile or two by the routers' draw, and the step's time
    would follow the seed (0.5% between seeds at 256 pairs an expert and
    tiles of 256; PERF.md, PR 34); with room for the draw it is one tile
    an expert, whatever the seed."""
    return _TILE * min(4, max(1, -(-int(1.5 * expected_pairs) // _TILE)))


def _plan(local: jax.Array, n_held: int, tile: int):
    """The sorted dispatch of the (token, expert) pairs ``local`` [S, k]
    (the held expert's index, or ``n_held`` for a pair another device
    serves): the pairs' order by expert, each held expert's count and
    start in that order, and its tiles of ``tile`` rows.  The bound on
    the pairs is all S x k of them, so none can be lost."""
    flat = local.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    counts = jnp.bincount(flat, length=n_held + 1)[:n_held].astype(jnp.int32)
    starts = jnp.cumsum(counts) - counts
    tiles = -(-counts // tile)
    return order, counts, starts, jnp.cumsum(tiles), tiles


def _tile_rows(j, plan, k: int, tile: int):
    """(expert, token of every row, whether the row is a pair, the pair's
    index) of tile ``j`` of the plan."""
    order, counts, starts, tile_end, tiles = plan
    e = jnp.sum(j >= tile_end).astype(jnp.int32)
    offset = (j - (tile_end[e] - tiles[e])) * tile + jnp.arange(
        tile, dtype=jnp.int32)
    valid = offset < counts[e]
    pair = order[jnp.minimum(starts[e] + offset, order.shape[0] - 1)]
    return e, pair // k, valid, pair


def _real_rows(j, plan, flat_w, k: int, tile: int):
    """Tile ``j`` as the rows XLA moves around its kernel: (expert; whether
    the tile is its expert's first; where each row lies in [S, d], a
    padding row past the end, so that a gather fills it with zeros and a
    scatter drops it; where its pair lies in [S * k], likewise; the rows'
    routing weights [tile, 1], 0 on padding)."""
    e, token, valid, pair = _tile_rows(j, plan, k, tile)
    past = jnp.arange(tile, dtype=jnp.int32) + flat_w.shape[0]
    first = j == plan[3][e] - plan[4][e]
    return (e, first, jnp.where(valid, token, past),
            jnp.where(valid, pair, past),
            jnp.where(valid, flat_w[pair], 0.0)[:, None])


def _slabs(a):
    """[S, d] as [S, d / 128, 128] where d is whole 128-lane slabs: under
    the (8, 128) tiling a token's row is then tiles of its own (whole ones
    where d is a multiple of 1024), and a scatter-add of rows adds tiles
    where on [S, d] it picks a sublane out of eight tokens' tiles: 47 us
    for 126 at 512 rows of 2048 (PERF.md, PR 35)."""
    lanes = expert_kernels.LANES
    return a.reshape(a.shape[0], -1, lanes) if a.shape[1] % lanes == 0 else a


def _take(x, rows):
    """Rows of ``_slabs(x)`` as [tile, d]."""
    return x.at[rows].get(mode="fill", fill_value=0).reshape(
        rows.shape[0], -1)


def _add(total, rows, part):
    """``part`` [tile, d] added to rows of ``total``, ``_slabs``' layout
    or scalars."""
    return total.at[rows].add(
        part.reshape(part.shape[:1] + total.shape[1:]), mode="drop")


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def grouped_experts(x, weights, wg, wu, wd, local, tile=_TILE):
    """sum over the pairs on held experts of ``w * E_e(x_token)``: x
    [S, d] in the compute type, weights [S, k] float32, the held experts'
    SwiGLU matrices wg, wu [n, d, f] and wd [n, f, d] **as the parameters
    are, float32**, ``local`` [S, k] int32 (``_plan``).  Returns [S, d]
    float32.

    The pairs are sorted by expert and multiplied tile by tile, a tile of
    ``tile`` rows of one expert (``tile_rows``) by one call of
    ``ops/expert_kernels``' kernel, which reads the expert's matrices where
    they lie and casts them block by block in VMEM; the loop runs over the
    tiles the batch really produced (a dynamic trip count), so the work
    follows the load: there is no capacity, no [S, E, C] tensor and no
    buffer of the worst case's size, and the static bound is every pair.
    XLA moves a tile's real rows only: a padding row is gathered as zeros
    and its product is dropped.  A token may name an expert twice."""
    return _grouped_fwd(x, weights, wg, wu, wd, local, tile)[0]


def _grouped_fwd(x, weights, wg, wu, wd, local, tile):
    k = local.shape[1]
    if not expert_kernels.takes(x.shape[1], wg.shape[2]):
        raise ValueError(
            f"experts {x.shape[1]} wide with {wg.shape[2]} hidden channels: "
            "the chip's kernels take whole 128-lane slabs of both")
    flat_w = weights.reshape(-1)
    with jax.named_scope("dispatch"):
        plan = _plan(local, wg.shape[0], tile)
    slabs = _slabs(x)

    def one_tile(j, out):
        with jax.named_scope("dispatch"):
            e, _, rows, _, w = _real_rows(j, plan, flat_w, k, tile)
            xt = _take(slabs, rows)
        with jax.named_scope("experts"):
            y = expert_kernels.tile_forward(e, xt, w, wg, wu, wd)
        with jax.named_scope("combine"):
            return _add(out, rows, y)

    out = lax.fori_loop(
        0, plan[3][-1], one_tile, jnp.zeros(slabs.shape, jnp.float32))
    return out.reshape(x.shape), (x, weights, wg, wu, wd, local, plan)


def _grouped_bwd(tile, res, dout):
    x, weights, wg, wu, wd, local, plan = res
    k = local.shape[1]
    flat_w = weights.reshape(-1)
    slabs, dslabs = _slabs(x), _slabs(dout.astype(x.dtype))

    def one_tile(j, carry):
        dx, dw, grads = carry
        with jax.named_scope("dispatch"):
            e, first, rows, pairs, w = _real_rows(j, plan, flat_w, k, tile)
            xt, dyt = _take(slabs, rows), _take(dslabs, rows)
        with jax.named_scope("experts"):
            dxt, dwt, *grads = expert_kernels.tile_backward(
                e, first, xt, dyt, w, wg, wu, wd, *grads)
        with jax.named_scope("combine"):
            return (_add(dx, rows, dxt), _add(dw, pairs, dwt[:, 0]),
                    tuple(grads))

    zeros = lambda like: jnp.zeros(like.shape, jnp.float32)
    dx, dw, grads = lax.fori_loop(
        0, plan[3][-1], one_tile,
        (zeros(slabs), zeros(flat_w), (zeros(wg), zeros(wu), zeros(wd))))
    return (dx.reshape(x.shape).astype(x.dtype),
            dw.reshape(weights.shape).astype(weights.dtype),
            *(g.astype(m.dtype) for g, m in zip(grads, (wg, wu, wd))),
            np.zeros(local.shape, jax.dtypes.float0))


grouped_experts.defvjp(_grouped_fwd, _grouped_bwd)


class ExpertFFN(nn.Module):
    """A routed-expert FFN as a device of an expert-parallel job holds it:
    a router as wide as the layer (``num_experts``), the SwiGLU experts
    ``experts_held`` = (first, past the last) of it, and one shared expert.

        s = sigmoid(x W_r);  ids, w = route_group_limited(s, b, ...)
        y = Shared(x) + sum over chosen i held here of w_i E_i(x)

    With every expert held this is the whole layer; with a range it is
    this device's part, which the other devices' parts complete by a sum
    (the shared expert counted once).  No token is dropped.  Takes the
    normed input in float32 (the router's type) and returns (y [B, T, d]
    in ``dtype``, the held experts' loads [n_held] float32, the pairs
    each served)."""

    num_experts: int
    experts_held: Tuple[int, int]
    hidden: int
    k: int
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    dtype: Optional[jnp.dtype] = None

    @nn.compact
    def __call__(self, x: jax.Array) -> Tuple[jax.Array, jax.Array]:
        b, t, d = x.shape
        lo, hi = self.experts_held
        n_held = hi - lo
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} is no range of the "
                f"{self.num_experts} experts")
        dtype = self.dtype or x.dtype
        xf = x.reshape(b * t, d)
        with jax.named_scope("router"):
            w_r = self.param("router", nn.initializers.lecun_normal(),
                             (d, self.num_experts), jnp.float32)
            bias = self.param("router_bias", nn.initializers.normal(0.01),
                              (self.num_experts,), jnp.float32)
            scores = jax.nn.sigmoid(jnp.dot(
                xf.astype(jnp.float32), w_r,
                precision=lax.Precision.HIGHEST))
            ids, weights = route_group_limited(
                scores, bias, self.k, self.n_group, self.topk_group,
                self.routed_scaling)
            held = jnp.logical_and(ids >= lo, ids < hi)
            local = jnp.where(held, ids - lo, n_held)
            load = jnp.sum(
                local[..., None] == jnp.arange(n_held), axis=(0, 1))

        # float32, as they are: the tile's kernel casts the block it reads
        def experts(name, shape):
            return self.param(name, nn.initializers.lecun_normal(
                in_axis=-2, out_axis=-1), shape, jnp.float32)

        wg = experts("wg", (n_held, d, self.hidden))
        wu = experts("wi", (n_held, d, self.hidden))
        wd = experts("wo", (n_held, self.hidden, d))
        xc = xf.astype(dtype)
        routed = grouped_experts(
            xc, weights, wg, wu, wd, local,
            tile_rows(b * t * self.k / self.num_experts))
        with jax.named_scope("shared"):
            shared = TensorParallelMLP(
                hidden=self.hidden, features=d, dtype=dtype, act=nn.silu,
                gated=True, use_bias=False, name="shared")(xc)
        y = routed.astype(dtype) + shared
        return y.reshape(b, t, d), load.astype(jnp.float32)
