"""Delta-Adasum optimizer (reference ``_DistributedAdasumOptimizer``,
``horovod/torch/optimizer.py:335-503``).

Where the plain ``DistributedOptimizer(op=Adasum)`` adaptively combines
*gradients*, the reference's Adasum optimizer applies the inner
optimizer *locally* first and adaptively combines the resulting
parameter *deltas* — this preserves Adasum's scale-invariance through
optimizers with per-parameter state (Adam etc.), which is the variant
the Adasum paper (arXiv:2006.02924) recommends.

Since PR 10 this is a thin preset over the ``DistributedOptimizer``
reduction machinery with the exchange lowering pinned to
``hier_adasum``: the delta reduction rides the bucketed overlap
scheduler — reverse-backward buckets, cost-model byte accounting, the
persistent tune DB, and (on cross-slice topologies) the hierarchical
staging that sums deltas over ICI and applies Adasum's adaptive
dot-product combination only on the DCN hop, where divergence actually
lives (docs/adasum.md).  A quantized ``compression`` compresses just
that DCN leg.  Single-slice topologies resolve the pin to ``flat`` and
reduce through the flat VHDD tree, exactly as before.
"""

from __future__ import annotations

from typing import Optional

import jax
import optax

from ..compression import Compression, Compressor
from ..ops import traced
from ..process_sets import ProcessSet
from ..runtime import WORLD_AXIS


def DistributedAdasumOptimizer(
    optimizer: optax.GradientTransformation,
    *,
    compression: type[Compressor] = Compression.none,
    process_set: Optional[ProcessSet] = None,
    fusion_threshold_bytes: Optional[int] = None,
    axis=WORLD_AXIS,
) -> optax.GradientTransformation:
    """Wrap an optax transform: local update -> Adasum of the deltas.

    The returned transform's ``update`` must run in SPMD context (inside
    ``shard_map``), like ``DistributedOptimizer``.
    """

    def init_fn(params):
        return optimizer.init(params)

    def update_fn(grads, state, params=None):
        from .distributed_optimizer import _reduce_gradients

        with jax.named_scope("hvd_update"):
            updates, state = optimizer.update(grads, state, params)
        reduced = _reduce_gradients(
            updates,
            axis=axis,
            op=traced.Adasum,
            compression=compression,
            prescale_factor=1.0,
            postscale_factor=1.0,
            process_set=process_set,
            fusion_threshold_bytes=fusion_threshold_bytes,
            lowering="hier_adasum",
        )
        return reduced, state

    return optax.GradientTransformation(init_fn, update_fn)
