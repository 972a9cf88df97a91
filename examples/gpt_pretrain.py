"""GPT pre-training with hybrid parallelism — the long-context flagship.

Capability add beyond the reference (SURVEY.md §5: Horovod has no
TP/SP/CP; its building blocks are alltoall + process sets): a GPT
language model trained over a ``dp × sp × tp`` mesh with

  - ring attention (``attn_impl="ring"``) streaming KV blocks around the
    ``sp`` axis via ``ppermute`` — sequence length scales with chips;
  - Megatron-style column/row tensor parallelism over ``tp``;
  - per-parameter mixed gradient sync (pmean over dp, psum for
    TP-sharded params) via ``sync_gradients``;
  - flash attention Pallas kernel inside each shard
    (``attn_impl="flash"``) when sequence fits on-chip.

Run (8-way virtual CPU mesh for a smoke test)::

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/gpt_pretrain.py --dp 2 --sp 2 --tp 2 --steps 20
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd

shard_map = jax.shard_map
from horovod_tpu.models import gpt_small, gpt_tiny
from horovod_tpu.models.transformer import (
    packed_token_cross_entropy,
    param_shard_axes,
    token_cross_entropy,
)
from horovod_tpu.parallel import make_mesh, sync_gradients


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--dp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1)
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--steps", type=int, default=20)
    parser.add_argument("--batch-per-dp", type=int, default=2)
    parser.add_argument("--seq-per-sp", type=int, default=128)
    parser.add_argument("--lr", type=float, default=3e-4)
    parser.add_argument("--small", action="store_true",
                        help="124M GPT-2-small config instead of tiny")
    parser.add_argument("--attn", default="ring",
                        choices=["ring", "ulysses", "flash", "full"])
    parser.add_argument("--remat", action="store_true",
                        help="jax.checkpoint each block (long-context "
                        "activation memory)")
    parser.add_argument("--packed", action="store_true",
                        help="sequence packing: variable-length documents "
                        "share fixed rows under segment-id attention "
                        "masking (requires --attn flash/full, --sp 1)")
    args = parser.parse_args()
    if args.packed and (args.sp > 1 or args.attn not in ("flash", "full")):
        raise SystemExit(
            "--packed requires --sp 1 and --attn flash|full (packed rows "
            "are whole by construction; see docs/parallelism.md)"
        )

    hvd.init()
    mesh = make_mesh(dp=args.dp, sp=args.sp, tp=args.tp)
    build = gpt_small if args.small else gpt_tiny
    model = build(attn_impl=args.attn, max_len=args.seq_per_sp * args.sp,
                  remat=args.remat)
    cfg = model.cfg

    b = args.batch_per_dp * args.dp
    t = args.seq_per_sp * args.sp
    rng = np.random.RandomState(0)
    # Synthetic corpus: next-token prediction on structured random data.
    data = rng.randint(0, cfg.vocab_size, (64, t + 1)).astype(np.int32)
    if args.packed:
        # Variable-length "documents" packed into fixed rows: every
        # position does useful work instead of padding.
        from horovod_tpu.data.packing import (
            pack_documents,
            packing_efficiency,
        )

        docs = [
            rng.randint(
                0, cfg.vocab_size,
                int(np.clip(rng.lognormal(np.log(t / 3.0), 0.7), 8, t)),
            ).astype(np.int32)
            for _ in range(256)
        ]
        ptoks, psegs = pack_documents(docs, t)
        if hvd.rank() == 0:
            print(f"packed {len(docs)} docs into {len(ptoks)} rows, "
                  f"efficiency {packing_efficiency(psegs):.2f}")

    tx = optax.adamw(args.lr, b1=0.9, b2=0.95, weight_decay=0.1)
    shard_axes = None  # filled after init

    tok_spec = P("dp" if args.dp > 1 else None,
                 "sp" if args.sp > 1 else None)

    def init_step(toks):
        return model.init(jax.random.PRNGKey(0), toks)

    init_f = jax.jit(shard_map(
        init_step, mesh=mesh, in_specs=(tok_spec,),
        out_specs=P(),  # replicated container; TP params device-vary
        check_vma=False,
    ))
    toks0 = jnp.asarray(data[:b, :t])
    params = init_f(toks0)
    shard_axes = {"params": param_shard_axes(params["params"], cfg)}
    opt_state = jax.jit(shard_map(
        tx.init, mesh=mesh, in_specs=(P(),), out_specs=P(),
        check_vma=False,
    ))(params)

    def train_step(params, opt_state, toks, aux_in):
        """One SPMD step; ``aux_in`` is the shifted targets (dense mode)
        or the segment ids (--packed)."""
        def loss_fn(p):
            if args.packed:
                logits, aux = model.apply(p, toks, aux_in)
                ce = packed_token_cross_entropy(logits, toks, aux_in)
            else:
                logits, aux = model.apply(p, toks)
                # gather-form CE: no vocab-sized one-hot temporary
                ce = token_cross_entropy(logits, aux_in)
            return ce + 0.01 * aux  # aux = MoE load-balance (0 w/o MoE)

        loss, grads = jax.value_and_grad(loss_fn)(params)
        grads = sync_gradients(grads, shard_axes)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        axes = [a for a in ("dp", "sp", "tp") if a in mesh.axis_names]
        return params, opt_state, jax.lax.pmean(loss, tuple(axes))

    step_f = jax.jit(shard_map(
        train_step, mesh=mesh,
        in_specs=(P(), P(), tok_spec, tok_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    ), donate_argnums=(0, 1))

    losses = []
    t0 = time.time()
    for i in range(args.steps):
        if args.packed:
            rows = rng.randint(0, len(ptoks), b)
            toks = jnp.asarray(ptoks[rows])
            targets = jnp.asarray(psegs[rows])  # segment ids
        else:
            rows = rng.randint(0, len(data), b)
            toks = jnp.asarray(data[rows, :t])
            targets = jnp.asarray(data[rows, 1:t + 1])
        params, opt_state, loss = step_f(params, opt_state, toks, targets)
        losses.append(float(loss))
    jax.block_until_ready(loss)
    dt = time.time() - t0
    if hvd.rank() == 0:
        tok_s = args.steps * b * t / dt
        print(f"attn={args.attn} mesh dp{args.dp}/sp{args.sp}/tp{args.tp}: "
              f"loss {losses[0]:.3f} -> {losses[-1]:.3f}, "
              f"{tok_s:,.0f} tok/s")


if __name__ == "__main__":
    main()
