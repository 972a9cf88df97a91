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

from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .mesh import EP_AXIS
from .tensor import _axis_present


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
