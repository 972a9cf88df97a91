"""Fully sharded (ZeRO-3/FSDP) GPT pretraining.

Capability add beyond the reference (which replicates optimizer state
and parameters on every rank): ``hvd.fsdp_train_step`` keeps params AND
optimizer state as 1/N flat shards between steps — per-chip persistent
memory is ``(1 + adam moments)/N`` of the model.

Run: ``python examples/fsdp_gpt.py [--steps N] [--small]``.
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu.models import gpt_small, gpt_tiny
from horovod_tpu.models.transformer import token_cross_entropy


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--steps", type=int, default=30)
    parser.add_argument("--batch-per-chip", type=int, default=2)
    parser.add_argument("--seq", type=int, default=64)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--small", action="store_true",
                        help="124M GPT-2-small instead of tiny")
    args = parser.parse_args()

    hvd.init()
    n = hvd.size()
    build = gpt_small if args.small else gpt_tiny
    model = build(attn_impl="full", max_len=args.seq)
    cfg = model.cfg

    b = args.batch_per_chip * n
    rng = np.random.RandomState(0)
    data = rng.randint(0, cfg.vocab_size, (64, args.seq + 1)).astype(np.int32)

    def loss_fn(params, batch):
        toks, tgt = batch[:, :-1], batch[:, 1:]
        logits, aux = model.apply(params, toks)
        # gather-form CE: no vocab-sized one-hot temporary
        return token_cross_entropy(logits, tgt) + 0.01 * aux

    params = model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, args.seq), jnp.int32)
    )
    n_params = sum(x.size for x in jax.tree.leaves(params))

    # FSDP's per-step all_gather + reduce_scatter run through the
    # exchange IR (docs/exchange_ir.md).
    step = hvd.fsdp_train_step(loss_fn, optax.adamw(args.lr))
    pshards, opt_state = step.init(params)
    del params  # full copy no longer needed: it lives sharded now

    shard_elems = pshards.size // n
    if hvd.rank() == 0:
        print(f"params {n_params/1e6:.1f}M; per-chip shard "
              f"{shard_elems/1e6:.2f}M elems "
              f"(x3 with adam moments) vs {n_params/1e6:.1f}M replicated")

    for i in range(args.steps):
        lo = (i * b) % (len(data) - b + 1)
        batch = jnp.asarray(data[lo : lo + b])
        pshards, opt_state, loss = step(pshards, opt_state, batch)
        if hvd.rank() == 0 and (i % 10 == 0 or i == args.steps - 1):
            print(f"step {i:3d}  loss {float(loss):.4f}")

    # eval path: re-materialize full params once
    full = step.gather(pshards)
    logits, _ = model.apply(full, jnp.asarray(data[:1, : args.seq]))
    if hvd.rank() == 0:
        print("gathered eval logits:", tuple(logits.shape))
    hvd.shutdown()


if __name__ == "__main__":
    main()
