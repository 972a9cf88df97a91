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
* a mixer and an FFN per layer (``layer_kinds``, ``ffn_kinds``): softmax
  attention over all earlier tokens ("full") or over a window of them
  ("window"), with a head count and a rotary rule of the layer's own
  (``layer_heads``, ``rope_rules``) and fewer key/value heads than query
  heads (``num_kv_heads``), latent attention ("mla": keys and values from a
  compressed latent, DeepSeek-V2) or the delta rule with a per-channel
  decay ("kda", ops/kda), the last two with a sigmoid gate a head, or with
  one decay a head ("gdn", Gated DeltaNet, with a SiLU gate a channel and
  key heads that value heads may share); a dense MLP, the capacity MoE, or
  dropless routed experts of which this device holds a range ("experts").

* the block is GPT-2's by default (LayerNorm, learned positions, fused
  QKV with bias, GELU MLP, tied head) and a current decoder's by
  settings of :class:`TransformerConfig`: RMSNorm, rotary positions,
  separate bias-free projections, a gated SiLU MLP, norms after each
  sublayer as well as before it or instead of it (OLMo 2), q and k normed
  over their whole projections or a head at a time, no positions at all,
  an untied head, RMSNorms whose weight is zero-centred (Qwen3-Next's),
  a sigmoid gate a channel on softmax attention's output.
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
import math
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from .. import metrics
from ..parallel.mesh import EP_AXIS, SP_AXIS, TP_AXIS
from ..parallel.moe import ExpertFFN, MoELayer
from ..parallel.ring_attention import full_attention, ring_attention
from ..parallel.tensor import (
    ColumnParallelDense,
    RowParallelDense,
    TensorParallelMLP,
    _axis_present,
)
from ..parallel.ulysses import ulysses_attention
from ..ops import kda_kernels
from ..ops.kda import conv_silu_heads, kda, rms_gate_heads
from ..ops.pallas_kernels import (
    flash_attention,
    flash_attention_qkv,
    flash_tiles_per_head,
    rope as rope_kernel,
)

Dtype = Any


@dataclasses.dataclass(frozen=True)
class RopeRule:
    """Rotary positions of one kind of layer: the first ``dim`` channels
    of every head turn (0: all of them), pair i by ``position * theta **
    (-2 i / dim)``.  With ``factor`` > 1 the frequencies are YaRN's
    (arXiv:2309.00071, as the ``transformers`` library computes
    ``rope_type: yarn``): interpolated by ``factor`` below the pair that
    turns ``beta_slow`` times in ``original_max_len`` positions, as they
    were above the one that turns ``beta_fast`` times, a linear ramp
    between; cos and sin times ``attention_factor`` (None: 0.1 ln factor +
    1), at every length."""

    theta: float = 10000.0
    dim: int = 0
    factor: float = 1.0
    original_max_len: int = 0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: Optional[float] = None


@dataclasses.dataclass(frozen=True)
class TransformerConfig:
    vocab_size: int = 32000
    num_layers: int = 12
    model_dim: int = 768
    num_heads: int = 12          # GLOBAL head count
    # Key/value heads, each read by num_heads / num_kv_heads consecutive
    # query heads (0: as many as the layer's query heads).
    num_kv_heads: int = 0
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
    norm: str = "layernorm"      # "layernorm" | "rmsnorm" |
    #                              "zero_centred_rmsnorm" (times 1 + w, w
    #                              from zeros: Qwen3-Next's)
    norm_eps: float = 1e-6
    positions: str = "learned"   # "learned" (wpe) | "rope" | "none"
    rope_theta: float = 10000.0
    use_bias: bool = True        # biases of the projections and the MLP
    fused_qkv: bool = True       # one qkv matmul, or separate q, k, v
    mlp: str = "gelu"            # "gelu" | "gated_silu" (SwiGLU)
    post_norm: bool = False      # a norm on each sublayer's output
    pre_norm: bool = True        # and one on its input (OLMo 2 has none)
    # softmax attention's q and k each RMSNormed over the whole projection,
    # all heads at once (OLMo 2's), before the heads are split
    qk_norm: bool = False
    # softmax attention's q and k each normed a head, over its head_dim
    # channels, by the model's ``norm`` kind (Qwen3's q_norm and k_norm)
    qk_head_norm: bool = False
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
    # A mixer and an FFN for every layer, one name a layer; empty means
    # "full" everywhere and what ``moe_every`` says.
    layer_kinds: Tuple[str, ...] = ()   # "full" | "window" | "mla" | "kda"
    #                                     | "gdn"
    ffn_kinds: Tuple[str, ...] = ()     # "dense" | "moe" | "experts"
    # "window": softmax attention whose queries see themselves and the
    # ``window - 1`` tokens before them (of their own document).
    window: int = 0
    # Query heads of each layer where the layers differ (empty:
    # ``num_heads`` everywhere), and the rotary rule of a kind of mixer
    # (a kind without one turns ``rope_dim`` or the whole head by
    # ``rope_theta``).
    layer_heads: Tuple[int, ...] = ()
    rope_rules: Tuple[Tuple[str, RopeRule], ...] = ()
    # softmax attention's output times sigmoid(x W_g), one gate a head
    attn_gate: bool = False
    # ... or one gate a channel, W_gate as wide as the heads (Qwen3-Next's:
    # the second half of each head of its published query projection)
    attn_channel_gate: bool = False
    # "mla" (arXiv:2405.04434 section 2.1): k and v of every head from one
    # normed latent of ``kv_lora_rank``, and a rope key of ``rope_dim`` the
    # heads share; q and k are qk_nope_dim + rope_dim wide, v ``head_dim``.
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    rope_dim: int = 0            # 0: the whole head turns ("full")
    rope_interleave: bool = False  # pairs (2i, 2i+1), not (i, i + D/2)
    # "kda" (arXiv:2510.26692 section 3): heads of ``head_dim`` keys and
    # values, a causal depthwise convolution of ``kda_conv`` taps, and
    # g = kda_lower_bound * sigmoid(exp(A_log) (x W_f + dt_bias)).
    kda_conv: int = 4            # the taps of both delta-rule mixers
    kda_lower_bound: float = -5.0
    # "gdn" (Gated DeltaNet, arXiv:2412.06464): ``num_heads`` value heads
    # of ``gdn_value_dim`` over ``gdn_key_heads`` key heads (0: as many)
    # of ``gdn_key_dim``, value head h reading key head h // (num_heads /
    # gdn_key_heads); one decay a head g = -exp(A_log) softplus(x W_a +
    # dt_bias), beta = 2 sigmoid(x W_b) with ``gdn_allow_neg_eigval``
    # (eigenvalues down to -1, arXiv:2411.12537) and sigmoid(x W_b)
    # without, the output RMSNormed a head times SiLU(x W_z).
    gdn_key_dim: int = 0
    gdn_value_dim: int = 0
    gdn_key_heads: int = 0
    gdn_allow_neg_eigval: bool = True
    # "experts": a router over ``num_experts`` with ``experts_per_token``
    # chosen inside the best ``topk_group`` of ``n_group`` groups; this
    # device holds ``experts_held`` = (first, past the last) of them, each
    # a SwiGLU of ``expert_ff_dim``, and one shared expert of that width.
    # ``router_scoring``: "sigmoid" scores with a selection bias
    # (DeepSeek-V3's) or "softmax" over all experts without one (Qwen's);
    # ``shared_expert_gate``: the shared expert times sigmoid(x w_sg), one
    # gate a token.
    num_experts: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    expert_ff_dim: int = 0
    experts_per_token: int = 1
    n_group: int = 1
    topk_group: int = 1
    routed_scaling: float = 1.0
    router_scoring: str = "sigmoid"
    shared_expert_gate: bool = False


# The name is the benchmark's: tests/benchmark/test_benchmark_hybridmoe_
# faults.py patches ``kda_chunk_major`` on this module to plant its faults,
# so the mixer's core goes through this name, looked up at call time.  It is
# ``ops.kda.kda``, on [B, T, H·d]; nothing is chunk-major any more, and a
# benchmark PR can rename it with its test.
kda_chunk_major = kda
MIXERS = ("full", "window", "mla", "kda", "gdn")
FFNS = ("dense", "moe", "experts")


def layer_kind(cfg: TransformerConfig, i: int) -> Tuple[str, str]:
    """(mixer, FFN) of layer ``i``."""
    mixer = cfg.layer_kinds[i] if cfg.layer_kinds else "full"
    if cfg.ffn_kinds:
        ffn = cfg.ffn_kinds[i]
    else:
        ffn = ("moe" if cfg.moe_every > 0 and (i + 1) % cfg.moe_every == 0
               else "dense")
    if mixer not in MIXERS or ffn not in FFNS:
        raise ValueError(
            f"layer {i}: unknown kind {(mixer, ffn)!r}; mixers are "
            f"{MIXERS}, FFNs {FFNS}")
    return mixer, ffn


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
    if cfg.norm == "zero_centred_rmsnorm":
        return ZeroCentredRMSNorm(epsilon=cfg.norm_eps, name=name)
    raise ValueError(
        f"unknown norm {cfg.norm!r}; expected 'layernorm', 'rmsnorm' or "
        "'zero_centred_rmsnorm'")


class ZeroCentredRMSNorm(nn.Module):
    """x / rms(x) * (1 + w) in float32, w from zeros (Qwen3-Next's
    RMSNorm): the weight decay pulls the norm's gain towards 1, not 0."""

    epsilon: float = 1e-6

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        scale = self.param("scale", nn.initializers.zeros, (x.shape[-1],),
                           jnp.float32)
        x = x.astype(jnp.float32)
        return x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                             + self.epsilon) * (1.0 + scale)


def _norm_weight(cfg: TransformerConfig, features: int, name: str
                 ) -> jax.Array:
    """What an RMSNorm of ``cfg.norm`` over ``features`` channels
    multiplies by, for a norm computed elsewhere, under the module's
    names: w (from ones) or, zero-centred, 1 + w (w from zeros)."""
    if cfg.norm == "rmsnorm":
        return _NormScale(features, name=name)()
    if cfg.norm == "zero_centred_rmsnorm":
        return 1.0 + _NormScale(features, nn.initializers.zeros, name=name)()
    raise ValueError(f"a norm a head is an RMSNorm's, not {cfg.norm!r}'s")


def rope_tables(positions: jax.Array, head_dim: int, theta: float,
                yarn: Optional[RopeRule] = None
                ) -> Tuple[jax.Array, jax.Array]:
    """(cos, sin) of the rotary angles, [..., head_dim / 2] float32:
    pair i turns by ``position * theta ** (-2 i / head_dim)``, or by
    YaRN's frequencies and times its factor where ``yarn`` scales
    (:class:`RopeRule`; ``head_dim`` is the width that turns)."""
    inv_freq = 1.0 / theta ** (
        jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim)
    factor = 1.0
    if yarn is not None and yarn.factor > 1.0:
        low, high = yarn_ramp(head_dim, theta, yarn)
        ramp = jnp.clip(
            (jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
            / (high - low), 0.0, 1.0)
        inv_freq = inv_freq / yarn.factor * ramp + inv_freq * (1.0 - ramp)
        factor = yarn.attention_factor
        if factor is None:
            factor = 0.1 * math.log(yarn.factor) + 1.0
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    if factor != 1.0:
        return jnp.cos(angles) * factor, jnp.sin(angles) * factor
    return jnp.cos(angles), jnp.sin(angles)


def yarn_ramp(dim: int, theta: float, rule: RopeRule) -> Tuple[float, float]:
    """(low, high): the pairs between which YaRN's frequencies pass from
    as they were (below ``low``) to interpolated (above ``high``): those
    that turn ``beta_fast`` and ``beta_slow`` times in the original
    length, rounded outwards and kept inside the table."""
    def pair(turns):
        return (dim * math.log(rule.original_max_len / (turns * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(pair(rule.beta_fast)), 0)
    high = min(math.ceil(pair(rule.beta_slow)), dim - 1)
    return float(low), float(high if high != low else high + 0.001)


def _rope_by_kind(cfg: TransformerConfig, positions: jax.Array):
    """{mixer kind: its (cos, sin)}: the kind's rule where
    ``cfg.rope_rules`` has one, else the model's (``rope_dim`` or the whole
    head by ``rope_theta``), each table made once."""
    rules = dict(cfg.rope_rules)
    kinds = set(cfg.layer_kinds) or {"full"}
    tables = {}
    if kinds - set(rules):
        tables[None] = rope_tables(
            positions, cfg.rope_dim or cfg.head_dim, cfg.rope_theta)
    for kind in sorted(kinds & set(rules)):
        rule = rules[kind]
        tables[kind] = rope_tables(
            positions, rule.dim or cfg.head_dim, rule.theta, rule)
    return {kind: tables.get(kind, tables.get(None)) for kind in kinds}


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
    sequence (ring or Ulysses).  ``latent`` makes it latent attention
    (``cfg.kv_lora_rank`` and beside it; see ``_latent_qkv``).  A layer
    has ``heads`` query heads (0: ``cfg.num_heads``) over
    ``cfg.num_kv_heads`` key/value heads, and with ``window`` its queries
    see themselves and the ``window - 1`` tokens before them; the tables
    it is called with are its kind's rotary rule.  A window layer's
    operations lie under a ``window`` scope inside the module's."""

    cfg: TransformerConfig
    latent: bool = False
    heads: int = 0
    window: int = 0

    @nn.compact
    def __call__(self, x: jax.Array,
                 segment_ids: Optional[jax.Array] = None,
                 rope: Optional[Tuple[jax.Array, jax.Array]] = None
                 ) -> jax.Array:
        with (jax.named_scope("window") if self.window
              else contextlib.nullcontext()):
            return self._mix(x, segment_ids, rope)

    @nn.nowrap  # no scope of its own: the kernels' paths stay attn/...
    def _mix(self, x, segment_ids, rope):
        cfg = self.cfg
        if cfg.attn_impl not in ("flash", "full", "ring", "ulysses"):
            raise ValueError(
                f"unknown attn_impl {cfg.attn_impl!r}; expected "
                "'flash', 'full', 'ring', or 'ulysses'"
            )
        tp = _tp_degree(cfg.tp_axis)
        heads, kv_heads = attention_heads(cfg, self.heads)
        if heads % tp != 0 or kv_heads % tp != 0:
            raise ValueError(
                f"num_heads {heads} (key/value heads {kv_heads}) not "
                f"divisible by tp degree {tp}"
            )
        h_local = heads // tp
        window = self.window or None
        b, t, _ = x.shape

        def column(parts: int, name: str, width: int = cfg.head_dim,
                   heads: int = heads) -> jax.Array:
            return ColumnParallelDense(
                parts * heads * width, axis=cfg.tp_axis,
                use_bias=cfg.use_bias, dtype=cfg.dtype, name=name,
            )(x)

        scale = None
        if self.latent:
            q, k, v, scale = self._latent_qkv(x, column, h_local, rope)
        elif cfg.fused_qkv:
            if kv_heads != heads:
                raise ValueError(
                    "fewer key/value heads than query heads take separate "
                    "projections: set fused_qkv=False")
            qkv = column(3, "qkv")
            parts = qkv.reshape(b, t, 3, h_local, cfg.head_dim)
            q, k, v = parts[:, :, 0], parts[:, :, 1], parts[:, :, 2]
        elif cfg.qk_norm:
            if tp > 1:
                raise ValueError(
                    "qk_norm normalises the whole q and k projections: "
                    "they cannot be split over the tp axis")
            q, k, v = (column(1, name, heads=n) for name, n in (
                ("q", heads), ("k", kv_heads), ("v", kv_heads)))
            q, k = (nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                               name=f"{name}_norm")(a).astype(cfg.dtype)
                    for name, a in (("q", q), ("k", k)))
            q, k, v = (a.reshape(b, t, -1, cfg.head_dim) for a in (q, k, v))
        elif cfg.qk_head_norm:
            q, k, v = (column(1, name, heads=n) for name, n in (
                ("q", heads), ("k", kv_heads), ("v", kv_heads)))
            with jax.named_scope("qk_norm"):
                q, k = (self._head_norm(a, name) for name, a in (
                    ("q", q), ("k", k)))
            q, k, v = (a.reshape(b, t, -1, cfg.head_dim) for a in (q, k, v))
        else:
            q, k, v = (
                column(1, name, heads=n).reshape(
                    b, t, n // tp, cfg.head_dim)
                for name, n in (("q", heads), ("k", kv_heads),
                                ("v", kv_heads)))
        if rope is not None and not self.latent:
            q, k = apply_rope(q, rope), apply_rope(k, rope)

        if segment_ids is not None and cfg.attn_impl not in ("flash", "full"):
            raise ValueError(
                "packed sequences (segment_ids) require attn_impl='flash' "
                "or 'full'; sequence-parallel impls do not support packing"
            )
        if ((window or kv_heads != heads) and _axis_present(cfg.sp_axis)
                and cfg.attn_impl in ("ring", "ulysses")):
            raise ValueError(
                "a window and grouped key/value heads are the flash "
                "kernels' and full_attention's: the sequence-parallel "
                "impls take neither")
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
        elif (cfg.attn_impl == "flash" and cfg.fused_qkv and rope is None
              and not self.latent):
            # the kernels read q, k and v out of the projection in place
            out = flash_attention_qkv(qkv, h_local, cfg.causal,
                                      segment_ids=segment_ids)
        elif cfg.attn_impl == "flash":
            out = flash_attention(q, k, v, cfg.causal, scale=scale,
                                  segment_ids=segment_ids, window=window)
        else:
            out = full_attention(q, k, v, causal=cfg.causal, scale=scale,
                                 segment_ids=segment_ids, window=window)

        if self.latent:
            # its heads come back as wide as its padded q and k
            out = _gate_heads(x, out[..., :cfg.head_dim])
        elif cfg.attn_gate:
            out = _gate_heads(x, out)
        elif cfg.attn_channel_gate:
            out = _gate_channels(x, out.reshape(b, t, -1))
        out = out.reshape(b, t, h_local * cfg.head_dim)
        return RowParallelDense(
            cfg.model_dim, axis=cfg.tp_axis, use_bias=cfg.use_bias,
            dtype=cfg.dtype, name="proj"
        )(out)

    def _head_norm(self, a: jax.Array, name: str) -> jax.Array:
        """[B, T, n·D] as projected, RMSNormed a head over its D channels
        by ``cfg.norm``'s weight (``q_norm`` / ``k_norm``, one D-wide
        weight the heads share), in float32, as ``cfg.dtype``: the norms'
        kernel on the projection's layout (``ops/kda.rms_gate_heads``, a
        gate of ones), not a re-layout to [B, T, n, D] each way."""
        cfg = self.cfg
        b, t, lanes = a.shape
        weight = _norm_weight(cfg, cfg.head_dim, f"{name}_norm")
        ones = jnp.ones((b, t, lanes // cfg.head_dim), jnp.float32)
        return rms_gate_heads(a.astype(jnp.float32), weight, ones,
                              cfg.norm_eps, cfg.dtype)

    def _latent_qkv(self, x, column, h_local: int, rope):
        """Latent attention's q, k, v as the kernels take them, heads of
        one width, and the scale of its scores.  With c the latent and
        k_r the rope key,  [c, k_r] = x W_dkv,  [k_n, v] = RMSNorm(c)
        W_ukv,  q = [q_n, rope(q_r)],  k = [k_n, rope(k_r)] with k_r the
        same for every head; scores over qk_nope_dim + rope_dim.  The
        kernels take square heads, so q and k are padded with zeros to
        whole 128-lane columns (192 -> 256) and v to the same width: the
        scores and the first ``head_dim`` lanes of the output are exact,
        the padded lanes cost the kernel ``qk_nope_dim + rope_dim`` :
        ``width`` of its time.  The compiled kernels of the other
        widths are untouched."""
        cfg = self.cfg
        b, t, _ = x.shape
        nope, turn = cfg.qk_nope_dim, cfg.rope_dim
        if rope is None:
            raise ValueError("latent attention turns its rope key: set "
                             "positions='rope'")
        with jax.named_scope("mla"):
            q = column(1, "q", nope + turn).reshape(b, t, h_local, -1)
            down = nn.Dense(cfg.kv_lora_rank + turn, use_bias=False,
                            dtype=cfg.dtype, name="kv_down")(x)
            latent = nn.RMSNorm(epsilon=cfg.norm_eps, dtype=jnp.float32,
                                name="kv_norm")(down[..., :cfg.kv_lora_rank])
            up = ColumnParallelDense(
                attention_heads(cfg, self.heads)[0] * (nope + cfg.head_dim),
                axis=cfg.tp_axis,
                use_bias=False, dtype=cfg.dtype, name="kv_up",
            )(latent.astype(cfg.dtype)).reshape(b, t, h_local, -1)
            k_rope = down[..., None, cfg.kv_lora_rank:]       # [B, T, 1, r]
            q_rope = q[..., nope:]
            if cfg.rope_interleave:
                # pairs (2i, 2i+1) as the kernel's (i, i + r/2): the same
                # permutation of q's and k's channels leaves q.k as it was
                order = jnp.concatenate(
                    [jnp.arange(0, turn, 2), jnp.arange(1, turn, 2)])
                q_rope, k_rope = q_rope[..., order], k_rope[..., order]
            q_rope, k_rope = apply_rope(q_rope, rope), apply_rope(k_rope, rope)
            width = nope + turn
            if width > 128:  # whole 128-lane columns
                width = -(-width // 128) * 128
            pad = lambda a: jnp.pad(
                a, ((0, 0),) * 3 + ((0, width - a.shape[-1]),))
            q = pad(jnp.concatenate([q[..., :nope], q_rope], axis=-1))
            k = pad(jnp.concatenate(
                [up[..., :nope],
                 jnp.broadcast_to(k_rope, (b, t, h_local, turn))], axis=-1))
            v = pad(up[..., nope:])
        return q, k, v, float(nope + turn) ** -0.5


def attention_heads(cfg: TransformerConfig, heads: int = 0
                    ) -> Tuple[int, int]:
    """(query heads, key/value heads) of a layer of ``heads`` query heads
    (0: the model's ``num_heads``)."""
    heads = heads or cfg.num_heads
    kv_heads = cfg.num_kv_heads or heads
    if heads % kv_heads:
        raise ValueError(
            f"{kv_heads} key/value heads do not divide {heads} query heads")
    return heads, kv_heads


def _gate_heads(x: jax.Array, out: jax.Array) -> jax.Array:
    """[B, T, H, D] times sigmoid(x W_g), one gate a head: the output gate
    of latent attention and, with ``attn_gate``, of softmax attention
    (the delta-rule mixer applies its own by chunk)."""
    gate = nn.Dense(out.shape[2], use_bias=False, dtype=jnp.float32,
                    name="gate")(x.astype(jnp.float32))
    return (out * jax.nn.sigmoid(gate)[..., None]).astype(out.dtype)


def _gate_channels(x: jax.Array, out: jax.Array) -> jax.Array:
    """[B, T, H·D] times sigmoid(x W_gate), one gate a channel: softmax
    attention's output gate with ``attn_channel_gate`` (Qwen3-Next's), on
    the heads' own layout; W_gate and the gate float32."""
    gate = nn.Dense(out.shape[-1], use_bias=False, dtype=jnp.float32,
                    name="gate")(x.astype(jnp.float32))
    with jax.named_scope("gate"):
        return (out * jax.nn.sigmoid(gate)).astype(out.dtype)


def _short_conv(x: jax.Array, taps: jax.Array,
                segment_ids: Optional[jax.Array]) -> jax.Array:
    """Causal depthwise convolution along T: y_t = sum_j taps[j] x_{t-j},
    [B, T, C] by [K, C], float32; a packed row's documents do not see
    each other (``segment_ids`` [B, T, 1]).  ``ops/kda_kernels.
    short_conv_silu`` runs this very function on a block of rows and its
    halo, and its VJP in the backward, so the shifts are slices and
    concatenations, which Mosaic lowers both ways.  The benchmark's fault
    tests patch it on this module, so the mixers hand on the name as it is
    at call time."""
    t = x.shape[1]
    x = x.astype(jnp.float32)

    def delayed(a, j, fill):
        j = min(j, t)
        return jnp.concatenate([jnp.full_like(a[:, :j], fill), a[:, :t - j]],
                               axis=1)

    y = x * taps[0]
    for j in range(1, taps.shape[0]):
        shifted = delayed(x, j, 0.0)
        if segment_ids is not None:
            shifted = jnp.where(segment_ids == delayed(segment_ids, j, -1),
                                shifted, 0.0)
        y = y + shifted * taps[j]
    return y


class _NormScale(nn.Module):
    """The weight of an ``nn.RMSNorm`` over ``features`` channels, under
    its names, for a norm computed elsewhere (from ``init``: ones, or
    zeros for a zero-centred one)."""

    features: int
    init: Any = nn.initializers.ones

    @nn.compact
    def __call__(self) -> jax.Array:
        return self.param("scale", self.init, (self.features,),
                          jnp.float32)


class KDAMixer(nn.Module):
    """Kimi delta attention: the delta rule with a per-channel bounded
    decay over heads of ``head_dim`` keys and values (``ops/kda.py`` has
    the recurrence and its chunked form).

        q = L2(SiLU(Conv(x W_q))) / sqrt(d),  k = L2(SiLU(Conv(x W_k))),
        v = SiLU(Conv(x W_v)),  beta = sigmoid(x W_b) a head,
        g = lower_bound * sigmoid(exp(A_log) (x W_f + dt_bias)) a channel,
        out = W_o (RMSNorm_head(o) * sigmoid(x W_g)_head)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        h, d = cfg.num_heads, cfg.head_dim
        if _axis_present(cfg.sp_axis) and lax.axis_size(cfg.sp_axis) > 1:
            raise ValueError(
                "the kda mixer's state runs along the whole row: it cannot "
                "be sequence-sharded")

        def projected(name):
            return nn.Dense(h * d, use_bias=False, dtype=cfg.dtype,
                            name=name)(x)

        # q, k, v, g stay [B, T, H·d] as projected, through convolution,
        # SiLU, the norms and the core (ops/kda.py: its kernels read a
        # chunk of one head as a block of that array), and o comes back
        # the same way: no [B, T, H, d] and nothing chunk-major is made.
        # q and k are normed in float32 and handed on as the matmuls will
        # take them.
        def convolved(name, scale=None):
            taps = self.param(
                f"conv_{name}", nn.initializers.normal(0.5),
                (cfg.kda_conv, h * d), jnp.float32)
            y = projected(name)
            with jax.named_scope("conv"):
                return conv_silu_heads(y, taps, segment_ids, _short_conv, h,
                                       scale, cfg.dtype)

        q, k, v = (convolved("q", d ** -0.5), convolved("k", 1.0),
                   convolved("v"))
        raw_gate = projected("f")
        with jax.named_scope("gate"):
            a_log = self.param(
                "A_log", lambda key, shape: jnp.log(jax.random.uniform(
                    key, shape, jnp.float32, 1.0, 2.0)), (h,))
            dt_bias = self.param(
                "dt_bias", lambda key, shape: jax.random.uniform(
                    key, shape, jnp.float32, -4.0, -1.0), (h * d,))
            g = cfg.kda_lower_bound * jax.nn.sigmoid(
                jnp.repeat(jnp.exp(a_log), d)
                * (raw_gate.astype(jnp.float32) + dt_bias))
            beta = jax.nn.sigmoid(nn.Dense(
                h, use_bias=False, dtype=jnp.float32, name="b")(
                    x.astype(jnp.float32)))
        with jax.named_scope("core"):
            o = kda_chunk_major(q, k, v, g, beta, segment_ids)
        o = rms_gate_heads(
            o, _NormScale(d, name="o_norm")(),
            jax.nn.sigmoid(nn.Dense(
                h, use_bias=False, dtype=jnp.float32, name="gate")(
                    x.astype(jnp.float32))),
            cfg.norm_eps, cfg.dtype)
        return RowParallelDense(
            cfg.model_dim, axis=cfg.tp_axis, use_bias=False,
            dtype=cfg.dtype, name="proj",
        )(o)


def _a_log_init(key, shape):
    """log A, A uniform in [1, 16] (fla's GatedDeltaNet draws (0, 16])."""
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias_init(key, shape):
    """softplus^-1(dt), dt log-uniform in [1e-3, 0.1] (fla's, Mamba 2's)."""
    dt = jnp.exp(jax.random.uniform(
        key, shape, jnp.float32, math.log(1e-3), math.log(0.1)))
    return dt + jnp.log(-jnp.expm1(-dt))


class GDNMixer(nn.Module):
    """Gated DeltaNet (arXiv:2412.06464; fla's ``GatedDeltaNet``): the
    delta rule with one decay a head over heads of ``gdn_key_dim`` keys
    and ``gdn_value_dim`` values (``ops/kda.py``, its kernels take both
    widths where they lie in the projections), ``num_heads`` value heads
    over ``gdn_key_heads`` key heads: value head h reads key head h //
    group (Qwen3-Next's: 32 over 16), never repeated.

        q = L2(SiLU(Conv(x W_q))) / sqrt(dk),  k = L2(SiLU(Conv(x W_k))),
        v = SiLU(Conv(x W_v)),  beta = 2 sigmoid(x W_b) a head (the
        transition's eigenvalues reach -1; sigmoid(x W_b) without
        ``gdn_allow_neg_eigval``),  g = -exp(A_log) softplus(x W_a +
        dt_bias) a head,  out = W_o (RMSNorm_head(o) * SiLU(x W_z)) a
        channel.

    Scopes: ``conv`` (the convolutions, SiLU and the L2 norms), ``gate``
    (g and beta), ``core`` (the delta rule), ``norm`` (the output's norm
    and gate)."""

    cfg: TransformerConfig

    @nn.compact
    def __call__(self, x: jax.Array,
                 segment_ids: Optional[jax.Array] = None) -> jax.Array:
        cfg = self.cfg
        h, dk, dv = cfg.num_heads, cfg.gdn_key_dim, cfg.gdn_value_dim
        hk = cfg.gdn_key_heads or h
        if _axis_present(cfg.sp_axis) and lax.axis_size(cfg.sp_axis) > 1:
            raise ValueError(
                "the gdn mixer's state runs along the whole row: it cannot "
                "be sequence-sharded")
        if h % hk:
            raise ValueError(
                f"{hk} key heads do not divide {h} value heads")

        def projected(name, width, dtype=cfg.dtype):
            return nn.Dense(width, use_bias=False, dtype=dtype,
                            name=name)(x.astype(dtype))

        # [B, T, H·d] throughout, as the projections leave them
        def convolved(name, heads, width, scale=None):
            taps = self.param(f"conv_{name}", nn.initializers.normal(0.5),
                              (cfg.kda_conv, heads * width), jnp.float32)
            y = projected(name, heads * width)
            with jax.named_scope("conv"):
                return conv_silu_heads(y, taps, segment_ids, _short_conv,
                                       heads, scale, cfg.dtype)

        q, k, v = (convolved("q", hk, dk, dk ** -0.5),
                   convolved("k", hk, dk, 1.0), convolved("v", h, dv))
        with jax.named_scope("gate"):
            a_log = self.param("A_log", _a_log_init, (h,))
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,))
            g = -jnp.exp(a_log) * jax.nn.softplus(
                projected("a", h, jnp.float32) + dt_bias)
            beta = jax.nn.sigmoid(projected("b", h, jnp.float32))
            if cfg.gdn_allow_neg_eigval:
                beta = 2.0 * beta
        with jax.named_scope("core"):
            # the group is named only where value heads share key heads:
            # otherwise the call keeps its six operands, the form the
            # benchmark's fault tests stand in for
            grouped = {} if hk == h else {"group": h // hk}
            o = kda(q, k, v, g, beta, segment_ids, **grouped)
        z = projected("z", h * dv)
        with jax.named_scope("norm"):
            o = rms_gate_heads(
                o, _NormScale(dv, name="o_norm")(),
                nn.silu(z.astype(jnp.float32)), cfg.norm_eps, cfg.dtype)
        return RowParallelDense(
            cfg.model_dim, axis=cfg.tp_axis, use_bias=False,
            dtype=cfg.dtype, name="proj",
        )(o)


class Block(nn.Module):
    """Pre-norm transformer block: a mixer (``kind``: softmax attention,
    whole or windowed, latent attention or the delta rule a channel or a
    head) and an FFN (``ffn``: dense-TP, the capacity MoE or dropless
    experts).  With ``cfg.post_norm`` each sublayer's output is normed
    again before it joins the residual ("sandwich"), and without
    ``cfg.pre_norm`` only there (OLMo 2: h = x + Norm(Mixer(x))).
    Returns (x, the capacity MoE's auxiliary loss, the held experts' loads
    or None)."""

    cfg: TransformerConfig
    kind: str = "full"
    ffn: str = "dense"
    heads: int = 0  # query heads of this layer's attention; 0: num_heads

    @nn.compact
    def __call__(
        self, x: jax.Array, segment_ids: Optional[jax.Array] = None,
        rope: Optional[Tuple[jax.Array, jax.Array]] = None,
    ) -> Tuple[jax.Array, jax.Array]:
        cfg = self.cfg
        if cfg.mlp not in ("gelu", "gated_silu"):
            raise ValueError(
                f"unknown mlp {cfg.mlp!r}; expected 'gelu' or 'gated_silu'")
        if not (cfg.pre_norm or cfg.post_norm):
            raise ValueError("a block needs pre_norm, post_norm or both")
        h = _norm(cfg, "ln_attn")(x) if cfg.pre_norm else x
        if self.kind == "kda":
            y = KDAMixer(cfg, name="kda")(h.astype(cfg.dtype), segment_ids)
        elif self.kind == "gdn":
            y = GDNMixer(cfg, name="gdn")(h.astype(cfg.dtype), segment_ids)
        else:
            y = Attention(
                cfg, latent=self.kind == "mla", heads=self.heads,
                window=cfg.window if self.kind == "window" else 0,
                name="attn")(h.astype(cfg.dtype), segment_ids, rope)
        if cfg.post_norm:
            y = _norm(cfg, "ln_attn_post")(y)
        x = x + y.astype(x.dtype)
        h = _norm(cfg, "ln_mlp")(x) if cfg.pre_norm else x
        aux = jnp.zeros((), jnp.float32)
        load = None
        if self.ffn == "experts":
            # takes the normed state in float32, the router's own type
            y, load = ExpertFFN(
                num_experts=cfg.num_experts, experts_held=cfg.experts_held,
                hidden=cfg.expert_ff_dim, k=cfg.experts_per_token,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                routed_scaling=cfg.routed_scaling, dtype=cfg.dtype,
                scoring=cfg.router_scoring,
                shared_gate=cfg.shared_expert_gate, name="moe",
            )(h)
        elif self.ffn == "moe":
            y, aux = MoELayer(
                num_experts_local=cfg.num_experts_local,
                hidden=cfg.ff_dim // max(1, cfg.num_experts_local),
                k=cfg.moe_k,
                capacity_factor=cfg.moe_capacity_factor,
                axis=cfg.ep_axis,
                dtype=cfg.dtype,
                name="moe",
            )(h.astype(cfg.dtype))
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
            )(h.astype(cfg.dtype))
        if cfg.post_norm:
            y = _norm(cfg, "ln_mlp_post")(y)
        return x + y.astype(x.dtype), aux, load


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
        if cfg.positions not in ("learned", "rope", "none"):
            raise ValueError(
                f"unknown positions {cfg.positions!r}; expected 'learned', "
                "'rope' or 'none'")
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
            elif cfg.positions == "rope":
                rope = _rope_by_kind(cfg, pos)
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
        kinds = [layer_kind(cfg, i) for i in range(cfg.num_layers)]
        layer_heads = cfg.layer_heads or (0,) * cfg.num_layers
        if len(layer_heads) != cfg.num_layers:
            raise ValueError(
                f"layer_heads names {len(layer_heads)} layers of "
                f"{cfg.num_layers}")
        blocks = [
            block_cls(cfg, kind=mixer, ffn=ffn, heads=layer_heads[i],
                      name=f"block_{i}")
            for i, (mixer, ffn) in enumerate(kinds)
        ]
        ln_f = _norm(cfg, "ln_f")
        gate = (nn.Dense(1, dtype=jnp.float32, name="exit_gate")
                if cfg.exit_gate else None)

        aux_total = jnp.zeros((), jnp.float32)
        applications = 0
        loads = []  # of every application of a layer with held experts
        logits, exit_logits = [], []
        for step in range(cfg.ut_steps):
            with (jax.named_scope(f"ut_{step}") if cfg.ut_steps > 1
                  else contextlib.nullcontext()):
                for block in blocks:
                    x, aux, load = block(
                        x, segment_ids, rope and rope[block.kind])
                    aux_total = aux_total + aux
                    applications += 1
                    if load is not None:
                        loads.append(load)
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
        for name in MIXERS + FFNS:
            metrics.set_gauge(
                "model.layer_kinds", sum(name in kind for kind in kinds),
                {"kind": name})
        # the delta-rule layers whose core ran as ops/kda_kernels' pair
        kda_layers = (sum("kda" in kind for kind in kinds)
                      * kda_kernels.takes(cfg.head_dim))
        # and those with one decay a head (both widths taken, or neither)
        gdn_layers = (sum("gdn" in kind for kind in kinds)
                      * (kda_kernels.takes(cfg.gdn_key_dim)
                         and kda_kernels.takes(cfg.gdn_value_dim)))
        metrics.set_gauge("model.kda.kernel_layers", kda_layers)
        metrics.set_gauge("model.gdn.kernel_layers", gdn_layers)
        # the chunks a grid step of those layers' kernel calls owns
        itemsize = jnp.dtype(cfg.dtype).itemsize
        plans = [kda_kernels.step_plan(t, cfg.num_heads, cfg.head_dim,
                                       cfg.head_dim, itemsize=itemsize)
                 ] if kda_layers else []
        if gdn_layers:
            plans.append(kda_kernels.step_plan(
                t, cfg.num_heads, cfg.gdn_key_dim, cfg.gdn_value_dim,
                cfg.num_heads // (cfg.gdn_key_heads or cfg.num_heads), True,
                itemsize))
        metrics.set_gauge("model.delta_rule.chunks_per_step",
                          max((chunks for chunks, _ in plans), default=0))
        if any("gdn" in kind for kind in kinds):
            metrics.set_gauge("model.gdn.value_heads_per_key",
                              cfg.num_heads // (cfg.gdn_key_heads
                                                or cfg.num_heads))
        # the mixers whose three short convolutions, SiLU and norms ran as
        # ops/kda_kernels' convolution pair: it takes the same widths
        metrics.set_gauge("model.conv.kernel_layers", kda_layers + gdn_layers)
        for i, (name, _) in enumerate(kinds):
            if name in ("full", "window") and cfg.attn_impl == "flash":
                labels = {"kind": name}
                heads, kv_heads = attention_heads(cfg, layer_heads[i])
                metrics.set_gauge(
                    "model.attn.kv_groups", heads // kv_heads, labels)
                # of the flash forward kernel's walks, one head, one row
                metrics.set_gauge(
                    "model.attn.tiles_per_head", flash_tiles_per_head(
                        t, cfg.causal,
                        cfg.window if name == "window" else None), labels)
        if loads:
            held = cfg.experts_held[1] - cfg.experts_held[0]
            metrics.set_gauge("model.moe.experts_held", held)
            # the expert layers whose grouped product ran as
            # ops/expert_kernels' pair: all of them (a width the chip's
            # kernels do not take is refused where the layer is traced)
            metrics.set_gauge("model.moe.kernel_layers", len(loads))
            # what the batch really asked of the held experts: read from
            # the step's outputs when they are there (metrics.trace_gauge)
            loads = lax.stop_gradient(jnp.stack(loads))
            metrics.trace_gauge("model.moe.pairs_per_step", jnp.sum(loads))
            metrics.trace_gauge(
                "model.moe.load_max_over_mean",
                jnp.max(loads) / jnp.maximum(jnp.mean(loads), 1e-9))
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
            return cfg.ep_axis if leaf in ("wg", "wi", "wo") else ""
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
