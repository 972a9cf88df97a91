"""Tensor (operator) parallelism: Megatron-style sharded dense layers.

SURVEY.md §2.5 lists TP as absent from the reference (whose only
building block for it is process sets).  TPU-native design: weights are
sharded over the ``tp`` mesh axis and the layers are written for
``shard_map`` — each device holds a [in, out/n] (column) or [in/n, out]
(row) shard, matmuls stay large and MXU-shaped, and the only
communication is one ``psum`` at the row-parallel output (the classic
f/g conjugate pair).  A column→row pair (MLP, attention out-proj)
therefore costs exactly one all-reduce per layer on the forward pass,
and XLA inserts the mirrored collectives for the backward pass
automatically since everything is a differentiable pure function.
"""

from __future__ import annotations

from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .mesh import TP_AXIS

Dtype = Any


def _axis_present(axis: str) -> bool:
    """True when called under shard_map/pjit with this named axis bound."""
    try:
        lax.axis_size(axis)
        return True
    except NameError:
        return False


class ColumnParallelDense(nn.Module):
    """Dense with output features sharded over ``axis``.

    ``features`` is the GLOBAL output width; each device holds and
    produces a ``features / tp`` column shard.  The output stays
    sharded — feed it to a RowParallelDense to contract the sharded
    dimension back.  No communication in forward.
    """

    features: int
    axis: str = TP_AXIS
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        n = lax.axis_size(self.axis) if _axis_present(self.axis) else 1
        if self.features % n != 0:
            raise ValueError(
                f"features ({self.features}) not divisible by "
                f"'{self.axis}' axis size {n}"
            )
        return nn.Dense(
            self.features // n,
            use_bias=self.use_bias,
            dtype=self.dtype,
            kernel_init=self.kernel_init,
        )(x)


class RowParallelDense(nn.Module):
    """Dense with input features sharded over ``axis``; partial products
    are summed with one ``psum`` (the Megatron g-operator).

    ``features`` is the GLOBAL output width.  The bias is added after
    the psum (once, not n times).  Outside shard_map (single-device
    test path) the psum is skipped.
    """

    features: int
    axis: str = TP_AXIS
    use_bias: bool = True
    dtype: Optional[Dtype] = None
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        y = nn.Dense(
            self.features,
            use_bias=False,
            dtype=self.dtype,
            kernel_init=self.kernel_init,
        )(x)
        if _axis_present(self.axis):
            y = lax.psum(y, self.axis)
        if self.use_bias:
            bias = self.param(
                "bias", nn.initializers.zeros_init(), (self.features,),
                jnp.float32,
            )
            y = y + jnp.asarray(bias, y.dtype)
        return y


class TensorParallelMLP(nn.Module):
    """Transformer MLP block sharded column→row: one psum per block.

    ``hidden`` and ``features`` are GLOBAL widths; the hidden dimension
    is sharded ``hidden / tp`` per device.  ``gated`` makes it the gated
    unit of current decoders, ``wo(act(wg(x)) * wi(x))`` (SwiGLU with
    ``act=nn.silu``): the gate ``wg`` is a second column shard beside
    ``wi``, so the pairing and its one psum stay as they are.
    """

    hidden: int
    features: int
    axis: str = TP_AXIS
    dtype: Optional[Dtype] = None
    act: Callable = nn.gelu
    gated: bool = False
    use_bias: bool = True

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        def column(name):
            return ColumnParallelDense(
                self.hidden, axis=self.axis, use_bias=self.use_bias,
                dtype=self.dtype, name=name)

        if self.gated:
            h = self.act(column("wg")(x)) * column("wi")(x)
        else:
            h = self.act(column("wi")(x))
        return RowParallelDense(
            self.features, axis=self.axis, use_bias=self.use_bias,
            dtype=self.dtype, name="wo"
        )(h)
