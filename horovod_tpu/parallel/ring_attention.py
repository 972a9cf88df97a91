"""Ring attention: context parallelism over an ICI ring.

Capability add mandated by SURVEY.md §5 ("long-context / sequence
parallelism — absent" in the reference; the nearest primitives are
``alltoall`` and process sets).  Design is TPU-first: the sequence is
sharded over a mesh axis, each device keeps its Q block resident and
streams K/V blocks around the ring with ``lax.ppermute`` while
accumulating the attention output with an online (flash-style) softmax.
Per step each device does one [T_loc × T_loc] block attention — MXU
matmuls — while the next K/V block is in flight on ICI, so compute
hides the communication for T_loc·D ≳ per-hop latency·bandwidth.

Memory is O(T_loc²) per block score matrix and O(T_loc·D) state —
never O(T²) — which is what makes million-token contexts feasible.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import SP_AXIS

_NEG_INF = -1e30


def full_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    segment_ids: Optional[jax.Array] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain softmax attention, f32 accumulation: [B, T, H, D] → same.

    The single-device reference semantics that ``ring_attention`` and
    ``ulysses_attention`` must match bit-for-bit up to fp error.
    ``segment_ids`` ([B, T]) restricts attention to same-segment keys
    (packed sequences).  ``k`` and ``v`` may hold fewer heads than ``q``
    (query head h reads head ``h // (H / G)``); ``window`` keeps of a
    query's keys under the causal mask itself and the ``window - 1``
    before it.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if k.shape[2] != q.shape[2]:
        k, v = (jnp.repeat(x, q.shape[2] // x.shape[2], axis=2)
                for x in (k, v))
    s = jnp.einsum(
        "bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale, k.astype(jnp.float32)
    )
    if causal:
        tq, tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), dtype=bool), k=tk - tq)
        if window is not None:
            mask = jnp.logical_and(mask, jnp.triu(
                jnp.ones((tq, tk), dtype=bool), k=tk - tq - window + 1))
        s = jnp.where(mask, s, _NEG_INF)
    if segment_ids is not None:
        segmask = (
            segment_ids[:, :, None] == segment_ids[:, None, :]
        )[:, None]  # [B, 1, Tq, Tk]
        s = jnp.where(segmask, s, _NEG_INF)
        # no fully-masked row is possible: q and k share one segment
        # array, so every query matches at least its own key (diagonal)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32)).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = SP_AXIS,
    causal: bool = False,
    scale: Optional[float] = None,
) -> jax.Array:
    """Exact attention over a sequence sharded on ``axis``.

    Args: q/k/v of shape [B, T_local, H, D] per device, where the global
    sequence is the concatenation of blocks in axis order.  Must be
    called inside ``shard_map`` (or pmap) over ``axis``.  Returns the
    local [B, T_local, H, D] output block, exactly equal (up to fp) to
    the corresponding slice of ``full_attention`` on the gathered
    sequence.
    """
    n = lax.axis_size(axis)
    idx = lax.axis_index(axis)
    b, t, h, d = q.shape
    if scale is None:
        scale = d ** -0.5
    qf = q.astype(jnp.float32) * scale
    q_pos = idx * t + jnp.arange(t)  # global positions of local queries

    # Online-softmax state: output accum, row sum, row max ([B, H, T]).
    # pcast marks the accumulators device-varying so the fori_loop carry
    # type matches its (varying) outputs under shard_map.
    o = lax.pcast(jnp.zeros((b, t, h, d), jnp.float32), (axis,), to="varying")
    l = lax.pcast(jnp.zeros((b, h, t), jnp.float32), (axis,), to="varying")
    m = lax.pcast(
        jnp.full((b, h, t), _NEG_INF, jnp.float32), (axis,), to="varying"
    )

    shift = [(j, (j + 1) % n) for j in range(n)]

    def block_update(o, l, m, kb, vb, i):
        # After i rotations device `idx` holds the K/V block originally
        # owned by device (idx - i) mod n.
        kv_block = (idx - i) % n
        k_pos = kv_block * t + jnp.arange(t)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf, kb.astype(jnp.float32))
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            s = jnp.where(mask, s, _NEG_INF)
        blk_max = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, blk_max)
        # Fully-masked-so-far rows keep m == -inf; subtract 0 there so
        # exp(-inf - 0) == 0 instead of exp(nan).
        m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe[..., None])
        if causal:
            p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(jnp.where(m <= _NEG_INF, _NEG_INF, m) - m_safe)
        l = l * corr + jnp.sum(p, axis=-1)
        o = o * corr.transpose(0, 2, 1)[..., None] + jnp.einsum(
            "bhqk,bkhd->bqhd", p, vb.astype(jnp.float32)
        )
        return o, l, m_new

    def step(i, carry):
        o, l, m, kb, vb = carry
        # Launch the next hop first: the block matmuls below have no
        # data dependence on it, so XLA overlaps compute with the ICI
        # transfer (double buffering).
        kb_next = lax.ppermute(kb, axis, shift)
        vb_next = lax.ppermute(vb, axis, shift)
        o, l, m = block_update(o, l, m, kb, vb, i)
        return o, l, m, kb_next, vb_next

    # n-1 rotations, n block updates: the last block computes on the
    # final carried buffers with no trailing (dead) ppermute.
    o, l, m, k, v = lax.fori_loop(0, n - 1, step, (o, l, m, k, v))
    o, l, m = block_update(o, l, m, k, v, n - 1)
    l = l.transpose(0, 2, 1)[..., None]  # [B, T, H, 1]
    return (o / jnp.where(l == 0.0, 1.0, l)).astype(q.dtype)
