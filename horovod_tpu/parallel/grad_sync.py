"""Gradient synchronization for hybrid-parallel training.

The reference's DistributedOptimizer allreduces every gradient over the
world (``horovod/torch/optimizer.py:506``) because all params are
replicated under pure DP.  Under hybrid parallelism the rule is
per-parameter.  Derivation: inside ``shard_map``, ``jax.grad`` seeds
every device's (replicated-after-psum) loss with 1, and collective
transposes (psum↔psum, ppermute↔inverse-ppermute, all_to_all↔inverse)
route cotangents across devices — so each device's raw gradient is
``d(Σ_devices L_i)/dθ_local``.  To recover the gradient of the MEAN
per-device loss:

* **pmean** over every sync axis the parameter is NOT sharded over
  (replicated copies each collect a partial contribution);
* **divide by the axis size** for every sync axis the parameter IS
  sharded over (its raw gradient already aggregates all devices'
  contributions via the collective transposes, but counts the
  model-axis-replicated loss ``axis_size`` times).

This one rule covers dp (classic allreduce-average), sp (ring/Ulysses
cotangents arrive via ppermute/all_to_all transposes), tp (Megatron
replicated-vs-sharded split), and ep (expert grads arrive via the
all_to_all transpose).

``param_shard_axes`` pytrees use space-separated axis-name strings
("", "tp", "ep") as leaves so they stay pytree-compatible.
"""

from __future__ import annotations

from typing import Sequence, Tuple

from .mesh import DP_AXIS, EP_AXIS, SP_AXIS, TP_AXIS


def _parse(axes: str) -> Tuple[str, ...]:
    return tuple(a for a in axes.split() if a)


def sync_gradients(
    grads,
    param_shard_axes=None,
    axes: Sequence[str] = (DP_AXIS, SP_AXIS, TP_AXIS, EP_AXIS),
):
    """Synchronize a gradient pytree inside shard_map.

    ``param_shard_axes``: pytree matching ``grads`` whose leaves are
    space-separated axis names the corresponding PARAMETER is sharded
    over ("" = fully replicated).  None ⇒ all parameters replicated
    (pure DP/SP — every grad pmean'd over the sync axes).

    ``axes``: mesh axes to synchronize over; names not bound in the
    current shard_map are skipped, so one call site works across mesh
    shapes.

    The pmeans go through the bucketed overlap scheduler (``sched/``):
    per-parameter semantics are those of the rule above (pmean is
    elementwise, so bucketing never moves a value), exchanged as
    reverse-backward ordered fused buckets XLA can overlap with
    compute.
    """
    from ..sched import sync_gradients_bucketed

    return sync_gradients_bucketed(grads, param_shard_axes, axes)
