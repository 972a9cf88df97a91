"""Shared benchmark harness: the canonical DP training step.

One implementation of the fwd+bwd+allreduce+update setup used by the
root ``bench.py``, ``examples/synthetic_benchmark.py``, and
``tools/scaling_bench.py`` — the reference's tf_cnn_benchmarks-style
methodology (``docs/benchmarks.rst:67-80``) — so the step protocol
lives in one place.
"""

from __future__ import annotations

from typing import Optional, Tuple


def build_dp_step(hvd, model, image_size: int, *,
                  compression=None,
                  lr: float = 0.01,
                  momentum: Optional[float] = 0.9) -> Tuple:
    """Build the data-parallel training step for an image model.

    Returns ``(step, params, batch_stats, opt_state)``; ``batch_stats``
    is None for models without BatchNorm (e.g. VGG) and the step then
    takes/returns no stats.  Initial parameters are broadcast from
    rank 0 like every reference benchmark script.
    """
    import jax
    import jax.numpy as jnp
    import optax

    variables = model.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, image_size, image_size, 3)), train=True,
    )
    params = variables["params"]
    batch_stats = variables.get("batch_stats")
    params = hvd.broadcast_parameters(params, root_rank=0)

    tx = hvd.DistributedOptimizer(
        optax.sgd(lr, momentum=momentum),
        compression=compression if compression is not None
        else hvd.Compression.none,
    )

    if batch_stats is not None:
        def loss_fn(p, stats, batch):
            x, y = batch
            logits, updated = model.apply(
                {"params": p, "batch_stats": stats}, x, train=True,
                mutable=["batch_stats"],
            )
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()
            return loss, updated["batch_stats"]

        step = hvd.distributed_train_step(loss_fn, tx, stateful=True)
    else:
        def loss_fn(p, batch):
            x, y = batch
            logits = model.apply({"params": p}, x, train=True)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y
            ).mean()

        step = hvd.distributed_train_step(loss_fn, tx)
    opt_state = step.init(params)
    return step, params, batch_stats, opt_state


def timed_throughput(step, params, batch_stats, opt_state, batch,
                     iters: int, warmup: int = 3) -> Tuple[float, Tuple]:
    """Run ``warmup`` + ``iters`` steps; return (seconds, final state).

    ``block_until_ready`` on the last loss fences each phase: dispatch
    is asynchronous, so without it the clock would time the enqueue.
    """
    import time

    def one():
        nonlocal params, batch_stats, opt_state
        if batch_stats is not None:
            params, batch_stats, opt_state, loss = step(
                params, batch_stats, opt_state, batch
            )
        else:
            params, opt_state, loss = step(params, opt_state, batch)
        return loss

    import jax

    loss = None
    for _ in range(warmup):
        loss = one()
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(iters):
        loss = one()
    jax.block_until_ready(loss)
    return time.perf_counter() - t0, (params, batch_stats, opt_state)
