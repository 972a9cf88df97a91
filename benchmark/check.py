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

The two are never on the chips together (``harness.run_cell``): the system
takes its steps during set-up and runs its window; when its state is gone
the weights are made again from the seed, seen to be the same by their
``fingerprint``, and the reference trains alone, 16 bytes a parameter.
"""

from __future__ import annotations

import dataclasses
import itertools
import math
import sys
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


def float32_on(tree, device):
    """The tree on one device with every floating leaf in float32.  The
    harness hands it weights it has made for the reference alone (the
    system's own are donated to its first step, and gone by then), so a
    leaf that is float32 already is taken as it is."""
    import jax
    import jax.numpy as jnp

    def place(x):
        x = jax.device_put(x, device)
        if jnp.issubdtype(x.dtype, jnp.floating):
            return x.astype(jnp.float32)
        return x

    return jax.tree.map(place, tree)


def _bits(leaf):
    """A floating leaf as the unsigned integers of its bits (traced)."""
    import jax.numpy as jnp
    from jax import lax

    if jnp.issubdtype(leaf.dtype, jnp.floating):
        return lax.bitcast_convert_type(
            leaf, jnp.dtype(f"uint{leaf.dtype.itemsize * 8}"))
    return leaf


def fingerprint(tree) -> int:
    """The bits of every leaf of a (possibly replicated) tree summed as
    wrapping uint32: the same number for the same weights, so that the
    reference is seen to start from what the system started from."""
    import jax
    import jax.numpy as jnp

    def bits_sum(tree):
        total = jnp.uint32(0)
        for leaf in jax.tree.leaves(tree):
            total = total + jnp.sum(_bits(leaf).astype(jnp.uint32),
                                    dtype=jnp.uint32)
        return total

    return int(jax.jit(bits_sum)(tree))


def bytes_in_use(device) -> int:
    """What the allocator holds on ``device`` now; 0 where the backend
    keeps no statistics."""
    return int((device.memory_stats() or {}).get("bytes_in_use", 0))


def program_bytes(compiled) -> int:
    """What XLA says a compiled program holds while it runs: arguments,
    results and temporaries, a donated argument counted once; 0 where the
    backend does not say."""
    m = compiled.memory_analysis()
    if m is None:
        return 0
    return int(m.argument_size_in_bytes + m.output_size_in_bytes
               + m.temp_size_in_bytes - m.alias_size_in_bytes)


@dataclasses.dataclass
class Trained:
    """The reference's run."""

    losses: List[float]   # before each optimizer step
    live_bytes: int       # the most the allocator held between its programs
    #                       (parameters, moments, gradients, the samples)
    program_bytes: Dict[str, int]  # ``program_bytes`` of each of its programs


def train_reference(loss: Callable, model: Dict[str, Any], optimizer,
                    params, chunks: Sequence[Any], steps: int,
                    device) -> Trained:
    """Losses of the plain reference before each of ``steps`` optimizer
    steps on the mean of the chunks' gradients, all in float32 at the
    highest matmul precision on ``device``.

    What it overwrites is donated (``params`` too: the caller's are gone
    after the first update) and a step's gradients are dropped before the
    next step's are made, so at its fullest it holds parameters, two
    moments and one tree of gradients, 16 bytes a parameter, with a second
    tree of gradients while a further chunk's are added; its programs are
    released with it."""
    import jax
    import optax

    def value_and_grad(p, batch):
        return jax.value_and_grad(lambda q: loss(q, model, batch))(p)

    def add(total, grads, value, g):
        return total + value, jax.tree.map(lambda a, b: a + b, grads, g)

    def update(p, state, grads):
        grads = jax.tree.map(lambda a: a / n, grads)
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state

    def compiled(fn, donate, *like):
        return jax.jit(fn, donate_argnums=donate).lower(*like).compile()

    chunks = [jax.device_put(c, device) for c in chunks]
    n = float(len(chunks))
    losses, live = [], 0
    with jax.default_matmul_precision("highest"):
        state = optimizer.init(params)
        programs = {
            "value_and_grad": compiled(value_and_grad, (), params, chunks[0])}
        value_like, grads_like = programs["value_and_grad"].out_info
        if len(chunks) > 1:
            programs["add"] = compiled(
                add, (1,), value_like, grads_like, value_like, grads_like)
        programs["update"] = compiled(
            update, (0, 1), params, state, grads_like)
        for k in range(steps):
            total, grads = programs["value_and_grad"](params, chunks[0])
            for chunk in chunks[1:]:
                value, g = programs["value_and_grad"](params, chunk)
                live = max(live, bytes_in_use(device))
                total, grads = programs["add"](total, grads, value, g)
                del value, g
            live = max(live, bytes_in_use(device))
            losses.append(float(total) / n)
            if k + 1 < steps:
                params, state = programs["update"](params, state, grads)
            del total, grads  # not beside the next step's
    return Trained(losses, live, {
        name: program_bytes(p) for name, p in programs.items()})


def loss_gaps(system: Sequence[float], reference: Sequence[float]
              ) -> List[float]:
    """|system - reference| / |reference| before each step; the largest
    float (the result line is JSON, which has no infinity) where a loss is
    missing on one side or not finite."""
    gaps = []
    for s, r in itertools.zip_longest(system, reference, fillvalue=math.nan):
        gap = abs(s - r) / abs(r) if r else math.nan
        gaps.append(gap if math.isfinite(gap) else sys.float_info.max)
    return gaps


def losses_agree(system: Sequence[float], reference: Sequence[float],
                 rtol: float) -> bool:
    return all(gap <= rtol for gap in loss_gaps(system, reference))


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
            bits = _bits(leaf)
            ok = jnp.logical_and(ok, jnp.all(
                lax.pmax(bits, axis) == lax.pmin(bits, axis)))
        return ok

    program = jax.jit(jax.shard_map(
        same, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False))
    return lambda tree: bool(program(tree))
