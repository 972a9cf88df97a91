"""Laguna-XS.2 (``poolside/Laguna-XS.2`` config.json, model_type
``laguna``): a decoder whose layers attend causally over all earlier tokens
(``full_attention``) or over a window of them (``sliding_attention``), with
more query heads than key/value heads and a head count and rotary rule by
layer type, and whose FFN is a dense SwiGLU in the leading layer and routed
experts with one shared expert elsewhere.

Every layer: ``h = x + Attn(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; a
final RMSNorm, an untied head, the mean next-token cross-entropy.

Attention, with u the normed input, heads of d = head_dim, H query heads
(``num_attention_heads_per_layer``) and G = num_key_value_heads:
    q = u W_q (H heads), k = u W_k, v = u W_v (G heads); query head h
    reads key/value head h // (H / G): k and v are repeated to H heads.
    Rotary positions (``rope_parameters`` of the layer's type) on the first
    r = partial_rotary_factor * d channels of every head of q and k, pair
    (i, i + r/2), the other channels unturned; ``default``: inv_freq_j =
    theta^(-2j/r); ``yarn``: ext_j = theta^(-2j/r), int_j = ext_j / factor,
    low = floor(r ln(L / (beta_fast 2 pi)) / (2 ln theta)), high = ceil(r
    ln(L / (beta_slow 2 pi)) / (2 ln theta)) with L the original length,
    ramp_j = clip((j - low) / (high - low), 0, 1), inv_freq_j = int_j
    ramp_j + ext_j (1 - ramp_j), and cos and sin times attention_factor.
    Scores q_t.k_s / sqrt(d), a materialised matrix, allowed where s <= t
    (full) or 0 <= t - s < sliding_window (sliding), inside one document;
    softmax; out = W_o (o * sigmoid(u W_g)_head), one gate a head.

Experts: s = sigmoid(x W_r) over the router's whole width; the
``num_experts_per_tok`` largest s + b are chosen (b a buffer); weights
``moe_routed_scaling_factor * s_i / sum of the chosen s``;
    FFN(x) = Shared(x) + sum over chosen i held here of w_i E_i(x).

Departures from the publication, each because the configuration file says
so: the layers are the published layers ``layers_held``; of a layer's
experts the range ``experts_held`` is here, and what the others would add
is left out; the vocabulary is the slice the configuration holds; packed
rows (``segment_ids``) attend inside their document only, count positions
from its first token and take no loss across a boundary or on padding; the
dense loss is the mean over all T positions with the row rolled left by one.

For memory only, and changing no value: every layer, inside it its mixer
and its FFN, and every expert's SwiGLU are wrapped in ``jax.checkpoint``, attention is taken ``QUERY_BLOCK``
queries at a time and the loss ``LOSS_BLOCK`` positions at a time, the held
experts are looped over with ``lax.scan``.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256        # rows of the score matrix held at a time
LOSS_BLOCK = 1024        # positions of a row whose logits are held at a time


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _dense(p):
    return p["Dense_0"]["kernel"]


# ------------------------------------------------------------- attention
def rotary_tables(rule: Dict[str, Any], head_dim: int, pos):
    """(cos, sin) [B, T, r/2] of the layer type's rule, r the channels of
    a head that turn."""
    r = int(round(rule["partial_rotary_factor"] * head_dim))
    theta = float(rule["rope_theta"])
    j = jnp.arange(r // 2, dtype=jnp.float32)
    inv_freq = theta ** (-2.0 * j / r)
    factor = 1.0
    if rule["rope_type"] == "yarn":
        length = rule["original_max_position_embeddings"]

        def pair(turns):
            return (r * math.log(length / (turns * 2 * math.pi))
                    / (2 * math.log(theta)))

        low = max(math.floor(pair(rule["beta_fast"])), 0)
        high = min(math.ceil(pair(rule["beta_slow"])), r - 1)
        if high == low:
            high += 0.001
        ramp = jnp.clip((j - low) / (high - low), 0.0, 1.0)
        inv_freq = inv_freq / rule["factor"] * ramp + inv_freq * (1.0 - ramp)
        factor = rule["attention_factor"]
    angles = pos.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles) * factor, jnp.sin(angles) * factor


def _rotate(x, cos, sin):
    """[B, T, H, d]: the first r channels turned, pair (i, i + r/2)."""
    half = cos.shape[-1]
    a, b, rest = x[..., :half], x[..., half:2 * half], x[..., 2 * half:]
    cos, sin = cos[:, :, None], sin[:, :, None]
    return jnp.concatenate(
        [a * cos - b * sin, b * cos + a * sin, rest], axis=-1)


def _attention(q, k, v, idx, seg, window):
    """Softmax attention inside each document under the causal mask (and
    the window, where there is one), a block of queries at a time; the
    scale is in q.  Query head h reads key/value head h // (H / G): k
    and v are repeated to q's heads (block by block, for memory only)."""
    b, t, h, _ = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(q_blk, idx_q, seg_q, k, v):
        k, v = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k)
        distance = idx_q[:, :, None] - idx[:, None, :]
        allowed = jnp.logical_and(
            distance >= 0, seg_q[:, :, None] == seg[:, None, :])
        if window is not None:
            allowed = jnp.logical_and(allowed, distance < window)
        weights = jax.nn.softmax(
            jnp.where(allowed[:, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    def blocks(x):  # [B, T, ...] -> [T / block, B, block, ...]
        return jnp.moveaxis(
            x.reshape((b, t // block, block) + x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: rows(*a, k, v), (blocks(q), blocks(idx),
                                                 blocks(seg)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def _attn(p, x, model, layer_type, heads, pos, idx, seg):
    b, t, _ = x.shape
    d, groups = model["head_dim"], model["num_key_value_heads"]
    q = (x @ _dense(p["q"])).reshape(b, t, heads, d)
    k = (x @ _dense(p["k"])).reshape(b, t, groups, d)
    v = (x @ _dense(p["v"])).reshape(b, t, groups, d)
    cos, sin = rotary_tables(model["rope_parameters"][layer_type], d, pos)
    q = _rotate(q, cos, sin) / jnp.sqrt(jnp.float32(d))
    k = _rotate(k, cos, sin)
    window = (model["sliding_window"] if layer_type == "sliding_attention"
              else None)
    o = _attention(q, k, v, idx, seg, window)
    o = o * jax.nn.sigmoid(x @ p["gate"]["kernel"])[..., None]
    return o.reshape(b, t, heads * d) @ _dense(p["proj"])


# --------------------------------------------------------------- experts
def route(scores, bias, model):
    """(ids [S, k], weights [S, k]) of the chosen experts."""
    _, ids = jax.lax.top_k(scores + bias, model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (model["moe_routed_scaling_factor"] * chosen
                 / jnp.sum(chosen, axis=-1, keepdims=True))


def _experts(p, x, model):
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    ids, weights = route(jax.nn.sigmoid(xf @ p["router"]),
                         p["router_bias"], model)
    first, past = model["experts_held"]
    shared = p["shared"]
    y = _swiglu(xf, _dense(shared["wg"]), _dense(shared["wi"]),
                _dense(shared["wo"]))

    @jax.checkpoint
    def part(e, gate, up, down):  # every token through held expert e
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w[:, None] * _swiglu(xf, gate, up, down)

    y, _ = jax.lax.scan(
        lambda y, held: (y + part(*held), None), y,
        (jnp.arange(past - first), p["wg"], p["wi"], p["wo"]))
    return y.reshape(b, t, d)


# ----------------------------------------------------------------- model
def _layer(blk, x, model, i, pos, idx, seg):
    eps = model["rms_norm_eps"]

    @jax.checkpoint
    def mixer(p, scale, x):
        return _attn(p, _rms_norm(x, scale, eps), model,
                     model["layer_types"][i],
                     model["num_attention_heads_per_layer"][i], pos, idx, seg)

    @jax.checkpoint
    def ffn(p, scale, x):
        n = _rms_norm(x, scale, eps)
        if model["mlp_layer_types"][i] == "dense":
            return _swiglu(n, _dense(p["wg"]), _dense(p["wi"]),
                           _dense(p["wo"]))
        return _experts(p, n, model)

    x = x + mixer(blk["attn"], blk["ln_attn"]["scale"], x)
    dense = model["mlp_layer_types"][i] == "dense"
    return x + ffn(blk["mlp" if dense else "moe"], blk["ln_mlp"]["scale"], x)


def _hidden(params, model: Dict[str, Any], tokens, segment_ids=None):
    """[B, T] token ids -> the final normed state [B, T, hidden]."""
    b, t = tokens.shape
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids)
    idx = jnp.broadcast_to(jnp.arange(t), (b, t))
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pos = idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    p = params["params"]
    x = p["wte"]["embedding"][tokens]
    for n, i in enumerate(model["layers_held"]):
        layer = jax.checkpoint(
            lambda blk, x, i=i: _layer(blk, x, model, i, pos, idx, seg))
        x = layer(p[f"block_{n}"], x)
    return _rms_norm(x, p["ln_f"]["scale"], model["rms_norm_eps"])


def logits(params, model: Dict[str, Any], tokens, segment_ids=None):
    """[B, T] token ids -> float32 logits [B, T, vocab]."""
    return _hidden(params, model, tokens, segment_ids) @ \
        params["params"]["head"].T


def _cross_entropy(x, head, targets):
    """[B, T] cross-entropies of the logits ``x @ head.T``, a block of
    ``LOSS_BLOCK`` positions of every row at a time."""
    b, t, _ = x.shape
    block = LOSS_BLOCK if t % LOSS_BLOCK == 0 else t

    @jax.checkpoint
    def rows(x_blk, targets_blk):
        lg = x_blk @ head.T
        logz = jax.nn.logsumexp(lg, axis=-1)
        picked = jnp.take_along_axis(
            lg, targets_blk[..., None], axis=-1)[..., 0]
        return logz - picked

    def blocks(a):  # [B, T, ...] -> [T / block, B, block, ...]
        return jnp.moveaxis(
            a.reshape((b, t // block, block) + a.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: rows(*a), (blocks(x), blocks(targets)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t)


def loss(params, model: Dict[str, Any], batch) -> jax.Array:
    """The mean next-token cross-entropy of one batch: ``tokens`` [B, T],
    or ``(tokens, segment_ids)`` for packed rows."""
    head = params["params"]["head"]
    if isinstance(batch, (tuple, list)):
        tokens, seg = batch
        # every position's target is its successor; the last one's and
        # those across a boundary or on padding weigh nothing
        ce = _cross_entropy(_hidden(params, model, tokens, seg), head,
                            jnp.roll(tokens, -1, axis=-1))[:, :-1]
        w = jnp.logical_and(seg[:, 1:] == seg[:, :-1],
                            seg[:, 1:] > 0).astype(jnp.float32)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)
    return jnp.mean(_cross_entropy(
        _hidden(params, model, batch), head, jnp.roll(batch, -1, axis=-1)))
