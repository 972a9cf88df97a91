"""Ling-3.0-flash (``inclusionAI/Ling-3.0-flash`` config.json, model_type
``bailing_hybrid``): a decoder whose layers mix tokens by Kimi delta
attention (KDA, arXiv:2510.26692 section 3) or, every ``layer_group_size``-th
one, by multi-head latent attention (MLA, arXiv:2405.04434 section 2.1), and
whose FFN is a dense SwiGLU in the leading layers and routed experts with
one shared expert elsewhere (the router of arXiv:2412.19437 section 2.1.2).

Every layer: ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``; a
final RMSNorm, an untied head, the mean next-token cross-entropy.

KDA, per head (d = head_dim keys and values) and token t:
    q = L2(SiLU(Conv(x W_q))) / sqrt(d),  k = L2(SiLU(Conv(x W_k))),
    v = SiLU(Conv(x W_v)),  Conv a causal depthwise convolution of
    ``short_conv_kernel_size`` taps;  beta = sigmoid(x W_b) a head;
    g = kda_lower_bound * sigmoid(exp(A_log) (x W_f + dt_bias)) a channel;
    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t;   out = W_o (RMSNorm_head(o) * sigmoid(x W_g)_head).
It is computed as that recurrence, token by token.

MLA: q = x W_q (heads of qk_nope_head_dim | qk_rope_head_dim);
    [c, k_r] = x W_dkv (kv_lora_rank | qk_rope_head_dim);
    [k_n, v] = RMSNorm(c) W_ukv;  rope (interleaved pairs) on q's last part
    and on k_r, which the heads share;  causal softmax of
    (q_n.k_n + q_r.k_r) / sqrt(qk_head_dim);  out = W_o (o * sigmoid(x W_g)).

Experts: s = sigmoid(x W_r) over the router's whole width; the choice is
made on s + b: the experts lie in ``n_group`` groups, a group scores the sum
of its two largest, the best ``topk_group`` groups stay, the
``num_experts_per_tok`` largest among their experts are chosen; weights
``routed_scaling_factor * s_i / sum of the chosen s``;
    FFN(x) = Shared(x) + sum over chosen i held here of w_i E_i(x).

Departures from the publication, each because the configuration file says
so: the layers are the published layers ``layers_held``; of a layer's
experts the range ``experts_held`` is here, and what the others would add
is left out; the vocabulary is the slice the configuration holds; packed
rows (``segment_ids``) attend, convolve and carry a state inside their
document only and take no loss across a boundary or on padding; the dense
loss is the mean over all T positions with the row rolled left by one.

For memory only, and changing no value: every layer is wrapped in
``jax.checkpoint``, the recurrence is checkpointed in blocks of
``RECURRENCE_BLOCK`` tokens, attention is taken ``QUERY_BLOCK`` queries at
a time.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512        # rows of the score matrix held at a time
RECURRENCE_BLOCK = 64    # tokens of the recurrence between two kept states


def layer_kinds(model: Dict[str, Any]):
    """(mixer, FFN) of every held layer, by its published index i: MLA
    where (i + 1) % layer_group_size == 0, KDA elsewhere; dense below
    first_k_dense_replace, experts from there on."""
    return [
        ("mla" if (i + 1) % model["layer_group_size"] == 0 else "kda",
         "dense" if i < model["first_k_dense_replace"] else "experts")
        for i in model["layers_held"]]


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _dense(p):
    return p["Dense_0"]["kernel"]


# ------------------------------------------------------------------- KDA
def _conv(x, taps, pos):
    """y_t = sum_j taps[j] x_{t-j} over the tokens of t's own document:
    ``pos`` [B, T] is a token's distance to its document's first."""
    y = jnp.zeros_like(x)
    for j in range(taps.shape[0]):
        earlier = jnp.roll(x, j, axis=1)
        y = y + jnp.where((pos >= j)[..., None], earlier, 0.0) * taps[j]
    return y


def _delta_rule(q, k, v, g, beta, pos):
    """The recurrence: [B, T, H, d] (beta [B, T, H]) -> o [B, T, H, d]."""
    b, t, h, d = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t, first = xs
        state = jnp.where(first[:, None, None, None], 0.0, state)
        state = jnp.exp(g_t)[..., None] * state
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    block = RECURRENCE_BLOCK if t % RECURRENCE_BLOCK == 0 else t

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    def blocks(a):  # [B, T, ...] -> [T / block, block, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // block, block) + a.shape[1:])

    _, out = jax.lax.scan(
        tokens, jnp.zeros((b, h, d, v.shape[-1]), jnp.float32),
        tuple(blocks(a) for a in (q, k, v, g, beta, pos == 0)))
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


def _kda(p, x, model, pos):
    b, t, _ = x.shape
    h, d = model["num_attention_heads"], model["head_dim"]

    def unit(a):
        return a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                            + 1e-6)

    q, k, v = (
        jax.nn.silu(_conv(x @ p[name]["kernel"], p[f"conv_{name}"], pos)
                    ).reshape(b, t, h, d) for name in ("q", "k", "v"))
    q, k = unit(q) / jnp.sqrt(jnp.float32(d)), unit(k)
    g = model["kda_lower_bound"] * jax.nn.sigmoid(
        jnp.exp(p["A_log"])[:, None]
        * (x @ p["f"]["kernel"] + p["dt_bias"]).reshape(b, t, h, d))
    beta = jax.nn.sigmoid(x @ p["b"]["kernel"])
    o = _delta_rule(q, k, v, g, beta, pos)
    o = _rms_norm(o, p["o_norm"]["scale"], model["rms_norm_eps"])
    o = o * jax.nn.sigmoid(x @ p["gate"]["kernel"])[..., None]
    return o.reshape(b, t, h * d) @ _dense(p["proj"])


# ------------------------------------------------------------------- MLA
def _rope_interleaved(x, pos, theta):
    """[B, T, H, r] turned by its positions, pair i = elements (2i, 2i+1)."""
    r = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = pos.astype(jnp.float32)[..., None, None] * inv_freq
    even, odd = x[..., 0::2], x[..., 1::2]
    turned = jnp.stack([even * jnp.cos(angles) - odd * jnp.sin(angles),
                        odd * jnp.cos(angles) + even * jnp.sin(angles)],
                       axis=-1)
    return turned.reshape(x.shape)


def _attention(q, k, v, idx, seg):
    """Causal softmax attention inside each document, a block of queries
    at a time; the scale is in q."""
    b, t, h, _ = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(q_blk, idx_q, seg_q):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k)
        allowed = jnp.logical_and(
            idx_q[:, :, None] >= idx[:, None, :],
            seg_q[:, :, None] == seg[:, None, :])[:, None]
        weights = jax.nn.softmax(
            jnp.where(allowed, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", weights, v)

    def blocks(x):  # [B, T, ...] -> [T / block, B, block, ...]
        return jnp.moveaxis(
            x.reshape((b, t // block, block) + x.shape[2:]), 1, 0)

    out = jax.lax.map(lambda a: rows(*a), (blocks(q), blocks(idx),
                                           blocks(seg)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def _mla(p, x, model, pos, idx, seg):
    b, t, _ = x.shape
    h = model["num_attention_heads"]
    nope, turn = model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    rank, theta = model["kv_lora_rank"], model["rope_theta"]
    q = (x @ _dense(p["q"])).reshape(b, t, h, nope + turn)
    down = x @ p["kv_down"]["kernel"]
    latent = _rms_norm(down[..., :rank], p["kv_norm"]["scale"],
                       model["rms_norm_eps"])
    up = (latent @ _dense(p["kv_up"])).reshape(
        b, t, h, nope + model["v_head_dim"])
    k_rope = _rope_interleaved(down[..., None, rank:], pos, theta)
    q = jnp.concatenate(
        [q[..., :nope], _rope_interleaved(q[..., nope:], pos, theta)],
        axis=-1) / jnp.sqrt(jnp.float32(model["qk_head_dim"]))
    k = jnp.concatenate(
        [up[..., :nope], jnp.broadcast_to(k_rope, (b, t, h, turn))], axis=-1)
    o = _attention(q, k, up[..., nope:], idx, seg)
    o = o * jax.nn.sigmoid(x @ p["gate"]["kernel"])[..., None]
    return o.reshape(b, t, -1) @ _dense(p["proj"])


# --------------------------------------------------------------- experts
def route(scores, bias, model):
    """(ids [S, k], weights [S, k]) of the chosen experts."""
    s, e = scores.shape
    groups, kept = model["n_group"], model["topk_group"]
    choice = (scores + bias).reshape(s, groups, e // groups)
    group_score = jnp.sum(jnp.sort(choice, axis=-1)[..., -2:], axis=-1)
    threshold = jnp.sort(group_score, axis=-1)[:, groups - kept, None]
    choice = jnp.where((group_score >= threshold)[..., None], choice,
                       -jnp.inf).reshape(s, e)
    _, ids = jax.lax.top_k(choice, model["num_experts_per_tok"])
    chosen = jnp.take_along_axis(scores, ids, axis=-1)
    return ids, (model["routed_scaling_factor"] * chosen
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
    for e in range(past - first):  # every token through every held expert
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        y = y + w[:, None] * _swiglu(xf, p["wg"][e], p["wi"][e], p["wo"][e])
    return y.reshape(b, t, d)


# ----------------------------------------------------------------- model
def _layer(blk, x, model, kind, pos, idx, seg):
    eps = model["rms_norm_eps"]
    mixer, ffn = kind
    n = _rms_norm(x, blk["ln_attn"]["scale"], eps)
    if mixer == "kda":
        x = x + _kda(blk["kda"], n, model, pos)
    else:
        x = x + _mla(blk["attn"], n, model, pos, idx, seg)
    n = _rms_norm(x, blk["ln_mlp"]["scale"], eps)
    if ffn == "dense":
        mlp = blk["mlp"]
        return x + _swiglu(n, _dense(mlp["wg"]), _dense(mlp["wi"]),
                           _dense(mlp["wo"]))
    return x + _experts(blk["moe"], n, model)


def logits(params, model: Dict[str, Any], tokens, segment_ids=None):
    """[B, T] token ids -> float32 logits [B, T, vocab]."""
    b, t = tokens.shape
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids)
    idx = jnp.broadcast_to(jnp.arange(t), (b, t))
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pos = idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    p = params["params"]
    x = p["wte"]["embedding"][tokens]
    for i, kind in enumerate(layer_kinds(model)):
        layer = jax.checkpoint(
            lambda blk, x, kind=kind: _layer(
                blk, x, model, kind, pos, idx, seg))
        x = layer(p[f"block_{i}"], x)
    x = _rms_norm(x, p["ln_f"]["scale"], model["rms_norm_eps"])
    return x @ p["head"].T


def _cross_entropy(lg, targets):
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return logz - picked


def loss(params, model: Dict[str, Any], batch) -> jax.Array:
    """The mean next-token cross-entropy of one batch: ``tokens`` [B, T],
    or ``(tokens, segment_ids)`` for packed rows."""
    if isinstance(batch, (tuple, list)):
        tokens, seg = batch
        ce = _cross_entropy(logits(params, model, tokens, seg)[:, :-1],
                            tokens[:, 1:])
        w = jnp.logical_and(seg[:, 1:] == seg[:, :-1],
                            seg[:, 1:] > 0).astype(jnp.float32)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)
    return jnp.mean(_cross_entropy(
        logits(params, model, batch), jnp.roll(batch, -1, axis=-1)))
