"""The comparison that decides ``correct``.

The system and the plain reference train on the same seeded sample from
the same weights, and their losses must agree before every one of the
first optimizer steps.  The check costs the system no second step shape:
each chip's sample (a few rows, different on every chip) is tiled to the
chip's share of the cell's batch, so the mean loss and its gradient — and
BatchNorm's batch moments — equal the sample's, and the system runs the one
program the window runs.  The reference takes the samples alone, one
chip's at a time, on one device, averages their losses and gradients as
the exchange should, and steps a plain optax optimizer.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Sequence

import numpy as np


def tile_for_chips(sample, chips: int, rows_per_chip: int):
    """``sample`` holds ``chips`` x s rows (every array of the tree, along
    its first axis); each chip's s rows are repeated to ``rows_per_chip``
    and the chips' blocks concatenated in mesh order."""
    import jax

    def tile(a: np.ndarray) -> np.ndarray:
        s, rem = divmod(a.shape[0], chips)
        if rem or s == 0 or rows_per_chip % s:
            raise ValueError(
                f"a sample of {a.shape[0]} rows cannot be tiled to "
                f"{chips} x {rows_per_chip}")
        reps = (rows_per_chip // s,) + (1,) * (a.ndim - 1)
        return np.concatenate(
            [np.tile(a[c * s:(c + 1) * s], reps) for c in range(chips)])

    return jax.tree.map(tile, sample)


def chunks_for_chips(sample, chips: int) -> List[Any]:
    """The sample of each chip alone."""
    import jax

    return [
        jax.tree.map(
            lambda a: a[c * (a.shape[0] // chips):
                        (c + 1) * (a.shape[0] // chips)], sample)
        for c in range(chips)
    ]


def float32_copy_on(tree, device):
    """A float32 copy of a (possibly replicated) tree on one device, taken
    before the system's first step donates the original."""
    import jax
    import jax.numpy as jnp

    def copy(x):
        x = jax.device_put(x, device)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return jnp.array(x, jnp.float32, copy=True)
        return jnp.array(x, copy=True)

    return jax.tree.map(copy, tree)


def reference_losses(loss: Callable, model: Dict[str, Any], optimizer,
                     params, chunks: Sequence[Any], steps: int,
                     device) -> List[float]:
    """Losses of the plain reference before each of ``steps`` optimizer
    steps on the mean of the chunks' gradients, all in float32 at the
    highest matmul precision on ``device``."""
    import jax
    import optax

    def value_and_grad(p, batch):
        return jax.value_and_grad(lambda q: loss(q, model, batch))(p)

    def update(p, state, grads):
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state

    value_and_grad = jax.jit(value_and_grad)
    update = jax.jit(update)
    chunks = [jax.device_put(c, device) for c in chunks]
    n = float(len(chunks))
    out = []
    with jax.default_matmul_precision("highest"):
        state = optimizer.init(params)
        for k in range(steps):
            total, grads = value_and_grad(params, chunks[0])
            for chunk in chunks[1:]:
                value, g = value_and_grad(params, chunk)
                total = total + value
                grads = jax.tree.map(lambda a, b: a + b, grads, g)
            out.append(float(total) / n)
            if k + 1 < steps:
                grads = jax.tree.map(lambda a: a / n, grads)
                params, state = update(params, state, grads)
    return out


def losses_agree(system: Sequence[float], reference: Sequence[float],
                 rtol: float) -> bool:
    system, reference = np.asarray(system), np.asarray(reference)
    return bool(
        system.shape == reference.shape
        and np.all(np.isfinite(system)) and np.all(np.isfinite(reference))
        and np.all(np.abs(system - reference) <= rtol * np.abs(reference)))


def replicas_identical(mesh, axis: str) -> Callable[[Any], bool]:
    """A function that says whether every chip of ``mesh`` holds the same
    bits for every leaf of a replicated tree (one compiled program per
    tree structure)."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def same(tree):
        ok = jnp.bool_(True)
        for leaf in jax.tree.leaves(tree):
            if jnp.issubdtype(leaf.dtype, jnp.floating):
                bits = lax.bitcast_convert_type(
                    leaf, jnp.dtype(f"uint{leaf.dtype.itemsize * 8}"))
            else:
                bits = leaf
            ok = jnp.logical_and(ok, jnp.all(
                lax.pmax(bits, axis) == lax.pmin(bits, axis)))
        return ok

    program = jax.jit(jax.shard_map(
        same, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))
    return lambda tree: bool(program(tree))
