"""GPT-style decoder-only transformer, wired for hybrid parallelism.

The reference has no model code (Horovod sits below the model; its
model zoo is the example scripts, SURVEY.md §2 L8) — but the TPU build
must demonstrate long-context and model parallelism as first-class
(SURVEY.md §5, §7 step 9), and that requires a transformer to hang them
on.  TPU-first choices:

* bf16 activations with fp32 LayerNorm/softmax/params (MXU-friendly).
* attention impl selectable per config: "full" (single device),
  "ring" (context parallel over the sp axis — parallel/ring_attention),
  "ulysses" (all_to_all sequence parallel — parallel/ulysses).
* QKV/out projections are column/row tensor-parallel over the tp axis
  (one psum per attention + one per MLP, the Megatron pairing).
* optional MoE FFN sharded over the ep axis (parallel/moe).

* the block is GPT-2's by default (LayerNorm, learned positions, fused
  QKV with bias, GELU MLP, tied head) and a current decoder's by
  settings of :class:`TransformerConfig`: RMSNorm, rotary positions,
  separate bias-free projections, a gated SiLU MLP, norms after each
  sublayer as well as before it, an untied head.
* ``ut_steps`` > 1 makes it a looped LM (Universal-Transformer loop,
  arXiv:2510.25741): the blocks are made once and the whole stack is
  applied ``ut_steps`` times with the same weights, with the final norm,
  the head and an exit gate after every pass
  (:func:`looped_token_cross_entropy` is its loss).

All modules degrade gracefully outside shard_map: tp/sp/ep axes absent
⇒ plain dense single-device transformer (the test and entry() path).
"""

from __future__ import annotations

import contextlib
import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .. import metrics
from ..parallel.mesh import EP_AXIS, SP_AXIS, TP_AXIS
from ..parallel.moe import MoELayer
from ..parallel.ring_attention import full_attention, ring_attention
from ..parallel.tensor import (
    ColumnParallelDense,
    RowParallelDense,
    TensorParallelMLP,
    _axis_present,
)
from ..parallel.ulysses import ulysses_attention
from ..ops.pallas_kernels import (
    flash_attention,
    flash_attention_qkv,
    rope as rope_kernel,
)

Dtype = Any


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    model_dim: int = 768
    num_heads: int = 12          # GLOBAL head count
    head_dim: int = 64
    ff_dim: int = 3072           # GLOBAL feed-forward width
    max_len: int = 2048
    dtype: Any = jnp.bfloat16
    causal: bool = True
    # Parallelism:
    attn_impl: str = "flash"     # "flash" | "full" | "ring" | "ulysses"
    sp_axis: str = SP_AXIS
    tp_axis: str = TP_AXIS
    remat: bool = False          # jax.checkpoint each block (long-context)
    # What a rematerialised block keeps for its backward pass besides its
    # input: any of "flash_qkv" (q, k, v as the attention kernel read
    # them: [B, T, H·D], the projections' own layout, after rope) and
    # "flash_out" (its output, [B, T, H·D], and row logsumexp), the names
    # ``ops/pallas_kernels.py`` gives its residuals.  With both the
    # backward re-runs neither the kernel nor the q/k/v projections.
    remat_save: Tuple[str, ...] = ()
    # The block (defaults are GPT-2's):
    norm: str = "layernorm"      # "layernorm" | "rmsnorm"
    norm_eps: float = 1e-6
    positions: str = "learned"   # "learned" (wpe) | "rope"
    rope_theta: float = 10000.0
    use_bias: bool = True        # biases of the projections and the MLP
    fused_qkv: bool = True       # one qkv matmul, or separate q, k, v
    mlp: str = "gelu"            # "gelu" | "gated_silu" (SwiGLU)
    post_norm: bool = False      # a norm after each sublayer too
    tie_head: bool = True        # logits through wte's transpose
    # The loop: the stack is applied ut_steps times with the same weights;
    # with exit_gate the head and a per-token gate follow every pass and
    # the model returns all of them (see Transformer).
    ut_steps: int = 1
    exit_gate: bool = False
    # MoE (0 ⇒ dense FFN everywhere):
    moe_every: int = 0           # use MoE FFN in every k-th block
    num_experts_local: int = 1
    moe_k: int = 2
    moe_capacity_factor: float = 1.25
    ep_axis: str = EP_AXIS


def _tp_degree(axis: str) -> int:
    return lax.axis_size(axis) if _axis_present(axis) else 1


def _norm(cfg: TransformerConfig, name: str) -> nn.Module:
    """The configuration's normalisation, in fp32 — the numerically
    load-bearing reductions."""
    if cfg.norm == "layernorm":
        return nn.LayerNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                            name=name)
    if cfg.norm == "rmsnorm":
        return nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                          name=name)
    raise ValueError(
        f"unknown norm {cfg.norm!r}; expected 'layernorm' or 'rmsnorm'")


def rope_tables(positions: jax.Array, head_dim: int,
                theta: float) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) of the rotary angles, [..., head_dim / 2] float32:
    pair i turns by ``position * theta ** (-2 i / head_dim)``."""
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jax.Array, rope: Tuple[jax.Array, jax.Array]) -> jax.Array:
    """Rotate [B, T, H, D] by the tables of :func:`rope_tables` ([T, D/2]
    or [B, T, D/2]), rotate-half pairing: element i pairs with i + D/2.
    The kernel works on [B, T, H·D], where the projections leave q and k
    and the attention kernels take them."""
    b, t, h, d = x.shape
    return rope_kernel(
        x.reshape(b, t, h * d), *rope, heads=h).reshape(x.shape)


class Attention(nn.Module):
    """Multi-head attention: tp-sharded projections + sp-sharded
    sequence (ring or Ulysses)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 segment_ids: Optional[jax.Array] = None,
                 rope: Optional[Tuple[jax.Array, jax.Array]] = None
                 ) -> jax.Array:
        cfg = self.cfg
        if cfg.attn_impl not in ("flash", "full", "ring", "ulysses"):
            raise ValueError(
                f"unknown attn_impl {cfg.attn_impl!r}; expected "
                "'flash', 'full', 'ring', or 'ulysses'"
            )
        tp = _tp_degree(cfg.tp_axis)
        if cfg.num_heads % tp != 0:
            raise ValueError(
                f"num_heads {cfg.num_heads} not divisible by tp degree {tp}"
            )
        h_local = cfg.num_heads // tp
        b, t, _ = x.shape

        def column(parts: int, name: str) -> jax.Array:
            return ColumnParallelDense(
                parts * cfg.num_heads * cfg.head_dim, axis=cfg.tp_axis,
                use_bias=cfg.use_bias, dtype=cfg.dtype, name=name,
            )(x)

        if cfg.fused_qkv:
            qkv = column(3, "qkv")
            parts = qkv.reshape(b, t, 3, h_local, cfg.head_dim)
            q, k, v = parts[:, :, 0], parts[:, :, 1], parts[:, :, 2]
        else:
            q, k, v = (
                column(1, name).reshape(b, t, h_local, cfg.head_dim)
                for name in ("q", "k", "v"))
        if rope is not None:
            q, k = apply_rope(q, rope), apply_rope(k, rope)

        if segment_ids is not None and cfg.attn_impl not in ("flash", "full"):
            raise ValueError(
                "packed sequences (segment_ids) require attn_impl='flash' "
                "or 'full'; sequence-parallel impls do not support packing"
            )
        # With the sp axis absent the sequence is unsharded, so plain
        # full attention is the correct lowering for every impl.
        if cfg.attn_impl == "ring" and _axis_present(cfg.sp_axis):
            out = ring_attention(q, k, v, axis=cfg.sp_axis, causal=cfg.causal)
        elif cfg.attn_impl == "ulysses" and _axis_present(cfg.sp_axis):
            # The post-exchange [B, T_global, H/n, D] attention is the
            # fused Pallas kernel — full sequence, fraction of the heads.
            out = ulysses_attention(
                q, k, v, axis=cfg.sp_axis, causal=cfg.causal,
                attn_fn=flash_attention,
            )
        elif _axis_present(cfg.sp_axis) and lax.axis_size(cfg.sp_axis) > 1:
            # flash/full attend only within the local shard: on a
            # sequence-sharded mesh that silently drops cross-shard
            # attention, so refuse rather than return wrong logits.
            raise ValueError(
                f"attn_impl={cfg.attn_impl!r} is shard-local but the "
                f"sequence axis {cfg.sp_axis!r} is present in the mesh; "
                "use attn_impl='ring' or 'ulysses' for sequence parallelism"
            )
        elif cfg.attn_impl == "flash" and cfg.fused_qkv and rope is None:
            # the kernels read q, k and v out of the projection in place
            out = flash_attention_qkv(qkv, h_local, cfg.causal,
                                      segment_ids=segment_ids)
        elif cfg.attn_impl == "flash":
            out = flash_attention(q, k, v, cfg.causal,
                                  segment_ids=segment_ids)
        else:
            out = full_attention(q, k, v, causal=cfg.causal,
                                 segment_ids=segment_ids)

        out = out.reshape(b, t, h_local * cfg.head_dim)
        return RowParallelDense(
            cfg.model_dim, axis=cfg.tp_axis, use_bias=cfg.use_bias,
            dtype=cfg.dtype, name="proj"
        )(out)


class Block(nn.Module):
    """Pre-norm transformer block; FFN is dense-TP or MoE.  With
    ``cfg.post_norm`` each sublayer's output is normed again before it
    joins the residual ("sandwich")."""

    cfg: TransformerConfig
    use_moe: bool = False

    @nn.compact
    def __call__(
        self, x: jax.Array, segment_ids: Optional[jax.Array] = None,
        rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.mlp not in ("gelu", "gated_silu"):
            raise ValueError(
                f"unknown mlp {cfg.mlp!r}; expected 'gelu' or 'gated_silu'")
        h = _norm(cfg, "ln_attn")(x)
        y = Attention(cfg, name="attn")(h.astype(cfg.dtype), segment_ids,
                                        rope)
        if cfg.post_norm:
            y = _norm(cfg, "ln_attn_post")(y)
        x = x + y.astype(x.dtype)
        h = _norm(cfg, "ln_mlp")(x)
        h = h.astype(cfg.dtype)
        aux = jnp.zeros((), jnp.float32)
        if self.use_moe:
            y, aux = MoELayer(
                num_experts_local=cfg.num_experts_local,
                hidden=cfg.ff_dim // max(1, cfg.num_experts_local),
                k=cfg.moe_k,
                capacity_factor=cfg.moe_capacity_factor,
                axis=cfg.ep_axis,
                dtype=cfg.dtype,
                name="moe",
            )(h)
        else:
            gated = cfg.mlp == "gated_silu"
            y = TensorParallelMLP(
                hidden=cfg.ff_dim,
                features=cfg.model_dim,
                axis=cfg.tp_axis,
                dtype=cfg.dtype,
                act=nn.silu if gated else nn.gelu,
                gated=gated,
                use_bias=cfg.use_bias,
                name="mlp",
            )(h)
        if cfg.post_norm:
            y = _norm(cfg, "ln_mlp_post")(y)
        return x + y.astype(x.dtype), aux


def _positions(cfg: TransformerConfig, b: int, t: int,
               segment_ids: Optional[jax.Array]) -> jax.Array:
    """GLOBAL positions of a row's tokens: [T], offset by this device's
    sequence-block index when sharded over sp, or [B, T] restarting at
    each packed document, so that every document sees the positions it
    would see alone in the row."""
    pos = jnp.arange(t)
    t_global = t
    if _axis_present(cfg.sp_axis):
        if segment_ids is not None and lax.axis_size(cfg.sp_axis) > 1:
            raise ValueError(
                "packed sequences cannot be sequence-sharded; drop "
                "the sp axis or the segment_ids"
            )
        t_global = t * lax.axis_size(cfg.sp_axis)
        pos = pos + lax.axis_index(cfg.sp_axis) * t
    if t_global > cfg.max_len:
        raise ValueError(
            f"sequence length {t_global} exceeds max_len {cfg.max_len}"
        )
    if segment_ids is None:
        return pos
    idx = jnp.broadcast_to(jnp.arange(t)[None], (b, t))
    is_start = jnp.concatenate(
        [jnp.ones((b, 1), bool),
         segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1,
    )
    start_idx = lax.cummax(jnp.where(is_start, idx, 0), axis=1)
    return idx - start_idx


class Transformer(nn.Module):
    """Decoder-only LM.  Input: int32 token ids [B, T_local] (T_local =
    T_global / sp when the sequence is sharded).  Returns (logits
    [B, T_local, vocab], moe_aux_loss scalar); with ``cfg.exit_gate``
    (a looped LM) the logits and the exit gate's pre-sigmoid values of
    every pass, (logits [S, B, T_local, vocab], exit_logits
    [S, B, T_local], moe_aux_loss)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, tokens: jax.Array,
                 segment_ids: Optional[jax.Array] = None):
        cfg = self.cfg
        if cfg.positions not in ("learned", "rope"):
            raise ValueError(
                f"unknown positions {cfg.positions!r}; expected 'learned' "
                "or 'rope'")
        b, t = tokens.shape
        emb = nn.Embed(
            cfg.vocab_size, cfg.model_dim,
            embedding_init=nn.initializers.normal(0.02), name="wte",
        )
        pos = _positions(cfg, b, t, segment_ids)
        rope = None
        with jax.named_scope("embed"):
            x = emb(tokens)
            if cfg.positions == "learned":
                wpe = self.param(
                    "wpe", nn.initializers.normal(0.02),
                    (cfg.max_len, cfg.model_dim), jnp.float32,
                )
                x = x + jnp.take(wpe, pos, axis=0)
            else:
                rope = rope_tables(pos, cfg.head_dim, cfg.rope_theta)
            x = x.astype(cfg.dtype)
        if cfg.tie_head:
            head = emb.embedding
        else:
            head = self.param(
                "head", nn.initializers.normal(0.02),
                (cfg.vocab_size, cfg.model_dim), jnp.float32,
            )

        # remat: recompute block activations in backward instead of
        # storing them (jax.checkpoint) — the standard FLOPs-for-HBM
        # trade that unlocks larger batch/sequence (long-context);
        # cfg.remat_save names what is kept all the same.
        block_cls = Block
        if cfg.remat:
            block_cls = nn.remat(
                Block, policy=jax.checkpoint_policies.save_only_these_names(
                    *cfg.remat_save))
        # Made once, called ut_steps times: one set of weights a layer.
        blocks = [
            block_cls(
                cfg, use_moe=cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0,
                name=f"block_{i}")
            for i in range(cfg.num_layers)
        ]
        ln_f = _norm(cfg, "ln_f")
        gate = (nn.Dense(1, dtype=jnp.float32, name="exit_gate")
                if cfg.exit_gate else None)

        aux_total = jnp.zeros((), jnp.float32)
        applications = 0
        logits, exit_logits = [], []
        for step in range(cfg.ut_steps):
            with (jax.named_scope(f"ut_{step}") if cfg.ut_steps > 1
                  else contextlib.nullcontext()):
                for block in blocks:
                    x, aux = block(x, segment_ids, rope)
                    aux_total = aux_total + aux
                    applications += 1
                # the normed state is what the next pass starts from
                x = ln_f(x)
                if gate is not None or step == cfg.ut_steps - 1:
                    # The head matmul is ~25% of GPT-2-small's FLOPs at
                    # T=1024 — run it in the compute dtype (bf16 hits the
                    # MXU at full rate; fp32 runs at ~1/8) and cast up for
                    # the fp32 softmax/loss downstream.
                    with jax.named_scope("head"):
                        logits.append((
                            x.astype(cfg.dtype) @ head.T.astype(cfg.dtype)
                        ).astype(jnp.float32))
                if gate is not None:
                    with jax.named_scope("exit_gate"):
                        exit_logits.append(gate(x)[..., 0])
                x = x.astype(cfg.dtype)
        metrics.set_gauge("model.layer_applications", applications)
        metrics.set_gauge("model.ut_steps", cfg.ut_steps)
        if gate is None:
            return logits[-1], aux_total
        return jnp.stack(logits), jnp.stack(exit_logits), aux_total


def param_shard_axes(params, cfg: TransformerConfig):
    """Pytree (matching ``params``) of space-separated mesh-axis names
    each parameter is sharded over, for ``parallel.sync_gradients``.

    Rules mirror the module structure: attention qkv (or q, k, v) /proj
    kernels and MLP wi/wg/wo kernels are tp-sharded (column/row); MoE expert weights
    are ep-sharded; embeddings / LayerNorms / psum-side biases / router
    are replicated.
    """

    def classify(path) -> str:
        keys = [getattr(k, "key", str(k)) for k in path]
        joined = "/".join(str(k) for k in keys)
        leaf = keys[-1] if keys else ""
        if "/moe/" in f"/{joined}/":
            return cfg.ep_axis if leaf in ("wi", "wo") else ""
        if "/attn/" in f"/{joined}/":
            if any(f"/{n}/" in f"/{joined}/" for n in ("qkv", "q", "k", "v")):
                return cfg.tp_axis  # column shard: kernel and bias
            if "/proj/" in f"/{joined}/" and leaf == "kernel":
                return cfg.tp_axis  # row shard; proj bias is replicated
            return ""
        if "/mlp/" in f"/{joined}/":
            if "/wi/" in f"/{joined}/" or "/wg/" in f"/{joined}/":
                return cfg.tp_axis
            if "/wo/" in f"/{joined}/" and leaf == "kernel":
                return cfg.tp_axis
            return ""
        return ""

    return jax.tree_util.tree_map_with_path(
        lambda path, _: classify(path), params
    )


def gpt_small(**overrides) -> Transformer:
    """124M-class config (GPT-2 small) — the flagship LM benchmark."""
    cfg = TransformerConfig(
        vocab_size=50304,  # GPT-2 vocab padded to a multiple of 128 (MXU)
        num_layers=12, model_dim=768, num_heads=12, head_dim=64,
        ff_dim=3072, max_len=1024,
    )
    cfg = dataclasses.replace(cfg, **overrides)
    return Transformer(cfg)


def gpt_tiny(**overrides) -> Transformer:
    """Tiny config for tests and the multi-chip dryrun."""
    cfg = TransformerConfig(
        vocab_size=256, num_layers=2, model_dim=64, num_heads=4,
        head_dim=16, ff_dim=128, max_len=256, dtype=jnp.float32,
    )
    cfg = dataclasses.replace(cfg, **overrides)
    return Transformer(cfg)


def _next_token_valid(segment_ids: jax.Array) -> jax.Array:
    """[B, T-1] weights of packed rows: position t predicts token t+1
    only when both live in the same document, and padding (segment id 0)
    is excluded."""
    return jnp.logical_and(
        segment_ids[:, 1:] == segment_ids[:, :-1],
        segment_ids[:, 1:] > 0,
    ).astype(jnp.float32)


def packed_token_cross_entropy(
    logits: jax.Array, tokens: jax.Array, segment_ids: jax.Array
) -> jax.Array:
    """Next-token cross-entropy for PACKED rows: position t predicts
    token t+1 only when both live in the same document (no loss across
    document boundaries), and padding (segment id 0) is excluded.
    Mean over valid positions — equal total weight to what the same
    documents would contribute unpacked.
    """
    import optax

    with jax.named_scope("loss"):
        l32 = logits[:, :-1].astype(jnp.float32)
        targets = tokens[:, 1:].astype(jnp.int32)
        ce = optax.softmax_cross_entropy_with_integer_labels(l32, targets)
        w = _next_token_valid(segment_ids)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)


def token_cross_entropy(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """Mean next-token cross-entropy WITHOUT materializing a
    ``(B, T, vocab)`` one-hot or normalized-probability tensor — the
    standard LM-loss shape for TPU memory bandwidth (on a 50k vocab at
    batch 16 x 1024 tokens the one-hot formulation allocates an extra
    ~3 GB fp32 temporary per step).  Delegates to optax's integer-label
    CE (the same logsumexp-minus-gather form) with fp32 accumulation.
    """
    import optax

    with jax.named_scope("loss"):
        l32 = logits.astype(jnp.float32)
        return jnp.mean(optax.softmax_cross_entropy_with_integer_labels(
            l32, targets.astype(jnp.int32)
        ))


def looped_token_cross_entropy(
    logits: jax.Array, exit_logits: jax.Array, targets: jax.Array,
    beta: float, weights: Optional[jax.Array] = None,
) -> jax.Array:
    """The looped LM's training loss (arXiv:2510.25741, stage one): the
    cross-entropy expected under the exit distribution, less ``beta``
    times that distribution's entropy.

    ``logits`` [S, B, T, vocab] and ``exit_logits`` [S, B, T] are the
    model's per-pass outputs, ``targets`` [B, T].  With lambda_t =
    sigmoid(exit_logits[t]) a token leaves after pass t with probability
    p_t = lambda_t * prod_{j<t} (1 - lambda_j), and after the last pass
    with whatever is left, p_S = prod_{j<S} (1 - lambda_j); per token the
    loss is sum_t p_t CE_t - beta H(p), averaged over the tokens (weighted
    by ``weights`` [B, T] where given).  S = 1 is the plain cross-entropy.
    The products are taken as sums of log-sigmoids, so a saturated gate
    gives 0 log 0 = 0 and not a NaN.
    """
    import optax

    with jax.named_scope("loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32),
            jnp.broadcast_to(targets.astype(jnp.int32), logits.shape[:-1]))
        z = exit_logits.astype(jnp.float32)
        stay = jax.nn.log_sigmoid(-z)
        stayed = jnp.cumsum(stay, axis=0) - stay    # log prod_{j<t}
        log_p = jnp.concatenate(
            [(stayed + jax.nn.log_sigmoid(z))[:-1], stayed[-1:]])
        p = jnp.exp(log_p)
        per_token = jnp.sum(p * (ce + beta * log_p), axis=0)
        if weights is None:
            return jnp.mean(per_token)
        return jnp.sum(per_token * weights) / jnp.maximum(
            jnp.sum(weights), 1.0)


def packed_looped_token_cross_entropy(
    logits: jax.Array, exit_logits: jax.Array, tokens: jax.Array,
    segment_ids: jax.Array, beta: float,
) -> jax.Array:
    """:func:`looped_token_cross_entropy` for PACKED rows, with
    :func:`packed_token_cross_entropy`'s targets and weights."""
    return looped_token_cross_entropy(
        logits[:, :, :-1], exit_logits[:, :, :-1], tokens[:, 1:], beta,
        _next_token_valid(segment_ids))
