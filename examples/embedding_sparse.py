"""Embedding training with sparse (IndexedSlices) gradients.

The TPU-native equivalent of training an embedding-heavy model under
the reference's sparse gradient path (``tensorflow/__init__.py:95-162``
allgathers the touched slices instead of allreducing the dense table;
``torch/optimizer.py`` exposes ``sparse_as_dense`` to opt out).

Run: ``python examples/embedding_sparse.py [--sparse-as-dense]``.

A skip-gram-style task on synthetic token co-occurrences: only the
batch's touched embedding rows cross the wire each step —
``dense_grad_to_indexed_slices`` recovers the sparsity from JAX's dense
gradient, and ``DistributedOptimizer`` reduces those rows as an
allgather-of-slices.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd

shard_map = jax.shard_map

VOCAB, DIM = 2048, 64


def synthetic_pairs(n, seed=0):
    """(center, context) pairs with simple structure: context tends to
    be center+1 mod VOCAB, so the embedding geometry is learnable."""
    rng = np.random.RandomState(seed)
    center = rng.randint(0, VOCAB, n).astype(np.int32)
    context = (center + rng.choice([1, 2], n)) % VOCAB
    return center, context.astype(np.int32)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=200)
    parser.add_argument("--batch-size", type=int, default=64,
                        help="per-chip batch size")
    parser.add_argument("--lr", type=float, default=0.5)
    parser.add_argument("--sparse-as-dense", action="store_true",
                        help="densify before reduction (reference "
                        "torch sparse_as_dense knob)")
    parser.add_argument("--num-samples", type=int, default=65536)
    args = parser.parse_args()

    hvd.init()
    n = hvd.size()
    global_batch = args.batch_size * n

    params = {
        "emb": jax.random.normal(jax.random.PRNGKey(0), (VOCAB, DIM)) * 0.1,
        "out": jax.random.normal(jax.random.PRNGKey(1), (DIM, VOCAB)) * 0.1,
    }
    params = hvd.broadcast_parameters(params, root_rank=0)
    tx = hvd.DistributedOptimizer(
        optax.sgd(args.lr), sparse_as_dense=args.sparse_as_dense
    )

    nnz = args.batch_size  # capacity: per-chip batch touches <= B rows

    def loss_fn(p, batch):
        center, context = batch
        h = p["emb"][center]                      # [B, D]
        logits = h @ p["out"]                     # [B, V]
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, context
        ).mean()

    def step_body(p, opt_state, center, context):
        loss, grads = jax.value_and_grad(loss_fn)(p, (center, context))
        # Recover the embedding grad's sparsity: only `center`'s rows
        # are non-zero in the dense gradient.
        grads = dict(grads)
        grads["emb"] = hvd.dense_grad_to_indexed_slices(
            grads["emb"], center, nnz=nnz
        )
        updates, opt_state = tx.update(grads, opt_state, p)
        p = optax.apply_updates(p, updates)
        return p, opt_state, jax.lax.pmean(loss, hvd.WORLD_AXIS)

    mesh = hvd.mesh()

    def make_step():
        return jax.jit(shard_map(
            step_body, mesh=mesh,
            in_specs=(P(), P(), P(hvd.WORLD_AXIS), P(hvd.WORLD_AXIS)),
            out_specs=(P(), P(), P()),
            check_vma=False,
        ))

    opt_state = tx.init(params)
    center, context = synthetic_pairs(args.num_samples)
    steps = min(args.steps, args.num_samples // global_batch)

    # The sparse exchange runs through the exchange IR: the
    # allgather-of-slices is a gather_dense_from_sparse program
    # (docs/exchange_ir.md).
    step = make_step()
    for i in range(steps):
        lo = i * global_batch
        c = jnp.asarray(center[lo : lo + global_batch])
        t = jnp.asarray(context[lo : lo + global_batch])
        params, opt_state, loss = step(params, opt_state, c, t)
        if hvd.rank() == 0 and (i % 50 == 0 or i == steps - 1):
            mode = "dense" if args.sparse_as_dense else "sparse"
            print(f"step {i:4d}  loss {float(loss):.4f}  ({mode} reduction)")

    hvd.shutdown()


if __name__ == "__main__":
    main()
