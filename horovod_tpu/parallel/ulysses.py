"""Ulysses-style sequence parallelism via all_to_all.

The second long-context strategy from SURVEY.md §5: where ring
attention streams K/V around the ring, Ulysses re-shards — an
all_to_all flips the sharding from sequence-sharded/head-replicated to
head-sharded/sequence-complete, runs ordinary full attention on H/n
local heads, and flips back.  Two all_to_alls move 2·[B,T_loc,H,D]
per device vs. ring's n ppermute hops of [B,T_loc,H,D] K+V; Ulysses
wins when heads ≥ devices and the per-device full-sequence score
matrix fits HBM, ring wins for extreme T.  This is the TPU-native use
of the reference's ``alltoall`` collective
(``horovod/common/operations.cc:1630``, ``NCCLAlltoall``).
"""

from __future__ import annotations

from typing import Callable, Optional

import jax
from jax import lax

from .mesh import SP_AXIS
from .ring_attention import full_attention


def ulysses_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    axis: str = SP_AXIS,
    causal: bool = False,
    attn_fn: Optional[Callable[..., jax.Array]] = None,
) -> jax.Array:
    """Attention over a sequence sharded on ``axis`` via head exchange.

    q/k/v: [B, T_local, H, D] per device with H divisible by the axis
    size.  Must run inside ``shard_map`` over ``axis``.  ``attn_fn``
    (default exact ``full_attention``) sees [B, T_global, H/n, D].
    """
    n = lax.axis_size(axis)
    h = q.shape[2]
    if h % n != 0:
        raise ValueError(f"heads ({h}) must be divisible by axis size {n}")

    def _flip(x, split_axis, concat_axis, bucket):
        # One head/sequence re-shard through the exchange IR: the
        # interpreter emits lax.all_to_all on the dense wire, bf16
        # wire requests cast around it, and the flip's bytes land in the
        # ULYSSES_EXCHANGE lane + kind-labeled gauges.
        from .. import xir

        op = xir.all_to_all(
            axis, split_axis=split_axis, concat_axis=concat_axis,
            wire=xir.wire_request(), bucket=bucket,
            nbytes=x.size * x.dtype.itemsize, dtype=x.dtype,
        )
        return xir.execute(
            xir.program("ulysses", [op]), [x], axis_size=n
        )[0]

    def seq_to_heads(x, bucket=0):
        # [B, T_loc, H, D] -> [B, T_global, H/n, D]
        return _flip(x, 2, 1, bucket)

    def heads_to_seq(x):
        return _flip(x, 1, 2, 3)

    q, k, v = seq_to_heads(q, 0), seq_to_heads(k, 1), seq_to_heads(v, 2)
    out = (attn_fn or full_attention)(q, k, v, causal=causal)
    return heads_to_seq(out)
