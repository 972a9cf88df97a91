"""Qwen3-Next-80B-A3B (``Qwen/Qwen3-Next-80B-A3B-Instruct`` config.json,
model_type ``qwen3_next``): a decoder whose layer i mixes tokens by causal
softmax attention where (i + 1) % full_attention_interval == 0 and by Gated
DeltaNet elsewhere, with routed experts beside a gated shared expert in
every layer.  N(x) = x / sqrt(mean(x^2) + eps) * (1 + w) is every RMSNorm of
the model (w zero-centred: a layer's two, the final one, q's and k's):

    h = x + Mixer(N_1(x)),   y = h + MoE(N_2(h));

a final N, an untied head, the mean next-token cross-entropy.

Gated DeltaNet, with Hk = linear_num_key_heads key heads of dk =
linear_key_head_dim and H = linear_num_value_heads value heads of dv =
linear_value_head_dim; value head h reads key head h // (H / Hk):
    q = L2(SiLU(Conv(x W_q))) / sqrt(dk),  k = L2(SiLU(Conv(x W_k))) (Hk
    heads),  v = SiLU(Conv(x W_v)) (H heads), Conv a causal depthwise
    convolution of ``linear_conv_kernel_dim`` taps without bias;  beta =
    sigmoid(x W_b) in (0, 1) and g = -exp(A_log) softplus(x W_a + dt_bias),
    one each a value head;
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t;  out = W_o (RMSNorm_head(o) * w * SiLU(x W_z)), w a
    weight of dv shared by the heads (ones at first, not zero-centred),
    the gate one a channel.
It is computed as that recurrence, token by token, on q and k repeated to
the value heads (head h of the repeat is key head h // (H / Hk)).

Gated attention, num_attention_heads query heads and num_key_value_heads
K/V heads of head_dim: q = N_q(x W_q) and k = N_k(x W_k) a head (one
head_dim-wide w each), v = x W_v; rotary positions on the first
partial_rotary_factor * head_dim channels of every head of q and k, pairs
(i, i + r/2), theta rope_theta, the rest unturned; query head h reads K/V
head h // (H / G); softmax of q.k / sqrt(head_dim) over earlier tokens;
out = W_o (o * sigmoid(x W_gate)), one gate a channel.

Experts: p = softmax(x W_r) over the router's whole width in float32; the
``num_experts_per_tok`` largest p are chosen, weights p_i / sum of the
chosen p (norm_topk_prob); S(x) = sigmoid(x w_sg) SwiGLU_shared(x);
    MoE(x) = S(x) + sum over chosen i held here of w_i E_i(x).

Departures from the publication, each because the configuration file says
so: the layers are the published layers ``layers_held``; of a layer's
experts the range ``experts_held`` is here, and what the others would add
is left out; the vocabulary is the slice the configuration holds; packed
rows (``segment_ids``) attend, convolve and carry a state inside their
document only, count positions from its first token and take no loss
across a boundary or on padding; the dense loss is the mean over all T
positions with the row rolled left by one.

For memory only, and changing no value: every layer, inside it its mixer
and its MoE, and every expert's SwiGLU are wrapped in ``jax.checkpoint``;
the position-wise parts, the convolutions (each block of rows with the
taps' rows before it) and the loss are taken ``ROW_BLOCK`` tokens at a
time, each block checkpointed; the recurrence keeps its state every
``RECURRENCE_BLOCK`` tokens and makes each token's step again; attention
is taken ``QUERY_BLOCK`` queries at a time; the held experts are looped
over with ``lax.scan``.  None of ``ops/kda*``, the flash kernels or
``horovod_tpu`` is used.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256        # rows of the score matrix held at a time
RECURRENCE_BLOCK = 64    # tokens of the recurrence between two kept states
ROW_BLOCK = 1024         # tokens of a position-wise part held at a time


def layer_kinds(model: Dict[str, Any]):
    """The mixer of every held layer by its published index i: "full"
    where (i + 1) % full_attention_interval == 0, "gdn" elsewhere."""
    return ["full" if (i + 1) % model["full_attention_interval"] == 0
            else "gdn" for i in model["layers_held"]]


def _norm(x, w, eps):
    """The zero-centred RMSNorm: x / rms(x) * (1 + w)."""
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * (1.0 + w)


def _dense(p):
    return p["Dense_0"]["kernel"]


def _swiglu(x, gate, up, down):
    return (jax.nn.silu(x @ gate) * (x @ up)) @ down


def _by_rows(fn, *arrays, before: int = 0):
    """``fn`` of [B, T, ...] arrays, ``ROW_BLOCK`` tokens at a time, each
    block checkpointed; fn is position-wise, so the value is fn's.  With
    ``before``, the first array's blocks also hold the ``before`` rows
    ahead of them (zeros ahead of the row), for a causal convolution."""
    b, t = arrays[0].shape[:2]
    block = ROW_BLOCK if t % ROW_BLOCK == 0 else t
    blocks = lambda a: jnp.moveaxis(
        a.reshape((b, t // block, block) + a.shape[2:]), 1, 0)
    first = arrays[0]
    if before:
        padded = jnp.pad(first, ((0, 0), (before, 0), (0, 0)))
        first = jnp.stack([padded[:, s:s + block + before]
                           for s in range(0, t, block)])
    else:
        first = blocks(first)
    outs = jax.lax.map(jax.checkpoint(lambda xs: fn(*xs)),
                       (first,) + tuple(blocks(a) for a in arrays[1:]))
    return jax.tree.map(
        lambda out: jnp.moveaxis(out, 0, 1).reshape((b, t) + out.shape[3:]),
        outs)


# ------------------------------------------------------------------- GDN
def _conv(x, taps, pos):
    """y_t = sum_j taps[j] x_{t-j} over the tokens of t's own document:
    x [B, K - 1 + T, C] holds the K - 1 rows before the T it gives,
    ``pos`` [B, T] is a token's distance to its document's first."""
    k, t = taps.shape[0], pos.shape[1]
    y = 0.0
    for j in range(k):
        earlier = x[:, k - 1 - j:k - 1 - j + t]
        y = y + jnp.where((pos >= j)[..., None], earlier, 0.0) * taps[j]
    return y


def _delta_rule(q, k, v, g, beta, pos):
    """The recurrence: q, k [B, T, H, dk], v [B, T, H, dv], g, beta
    [B, T, H] -> o [B, T, H, dv]."""
    b, t, h, dk = q.shape

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t, first = xs
        state = jnp.where(first[:, None, None, None], 0.0, state)
        state = jnp.exp(g_t)[..., None, None] * state
        read = jnp.einsum("bhk,bhkv->bhv", k_t, state)
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - read))
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    block = RECURRENCE_BLOCK if t % RECURRENCE_BLOCK == 0 else t

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(jax.checkpoint(token), state, xs)

    def blocks(a):  # [B, T, ...] -> [T / block, block, B, ...]
        a = jnp.moveaxis(a, 1, 0)
        return a.reshape((t // block, block) + a.shape[1:])

    _, out = jax.lax.scan(
        tokens, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32),
        tuple(blocks(a) for a in (q, k, v, g, beta, pos == 0)))
    return jnp.moveaxis(out.reshape((t,) + out.shape[2:]), 0, 1)


def _gdn(p, x, model, pos):
    b = x.shape[0]
    hk, h = model["linear_num_key_heads"], model["linear_num_value_heads"]
    dk = model["linear_key_head_dim"]
    taps = model["linear_conv_kernel_dim"]

    def unit(a):
        return a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                            + 1e-6)

    def inputs(x, pos):  # x holds the taps - 1 rows before pos's
        rows = pos.shape[1]
        q, k, v = (
            jax.nn.silu(_conv(x @ p[name]["kernel"], p[f"conv_{name}"], pos)
                        ).reshape(b, rows, heads, -1)
            for name, heads in (("q", hk), ("k", hk), ("v", h)))
        now = x[:, taps - 1:]
        beta = jax.nn.sigmoid(now @ p["b"]["kernel"])
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            now @ p["a"]["kernel"] + p["dt_bias"])
        # value head j reads key head j // (H / Hk)
        q, k = (jnp.repeat(a, h // hk, axis=2) for a in (q, k))
        return unit(q) / jnp.sqrt(jnp.float32(dk)), unit(k), v, g, beta

    o = _delta_rule(*_by_rows(inputs, x, pos, before=taps - 1), pos)

    def out(o, x):
        normed = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                              + model["rms_norm_eps"]) * p["o_norm"]["scale"]
        gated = normed * jax.nn.silu(x @ p["z"]["kernel"]).reshape(o.shape)
        return gated.reshape(o.shape[0], -1) @ _dense(p["proj"])

    return _by_rows(lambda o, x: jax.vmap(out)(o, x), o, x)


# --------------------------------------------------- gated attention
def _rotate(x, pos, model):
    """[B, T, H, D]: the first partial_rotary_factor * D channels turned by
    their positions, pairs (i, i + r/2), the rest as they are."""
    d = x.shape[-1]
    r = int(round(model["partial_rotary_factor"] * d))
    inv_freq = 1.0 / float(model["rope_theta"]) ** (
        jnp.arange(0, r, 2, dtype=jnp.float32) / r)
    angles = pos.astype(jnp.float32)[..., None, None] * inv_freq
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    a, b, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin, rest],
                           axis=-1)


def _attention(q, k, v, idx, seg):
    """Causal softmax attention inside each document, a block of queries
    at a time; the scale is in q.  Query head h reads K/V head h // (H /
    G): k and v are repeated to q's heads (block by block, for memory
    only)."""
    b, t, h, _ = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(q_blk, idx_q, seg_q, k, v):
        k, v = (jnp.repeat(a, h // a.shape[2], axis=2) for a in (k, v))
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

    out = jax.lax.map(lambda a: rows(*a, k, v),
                      (blocks(q), blocks(idx), blocks(seg)))
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h, v.shape[-1])


def _full(p, x, model, pos, idx, seg):
    b = x.shape[0]
    d, eps = model["head_dim"], model["rms_norm_eps"]
    heads, groups = model["num_attention_heads"], model["num_key_value_heads"]

    def qkv(x, pos):
        rows = x.shape[1]
        q = _norm((x @ _dense(p["q"])).reshape(b, rows, heads, d),
                  p["q_norm"]["scale"], eps)
        k = _norm((x @ _dense(p["k"])).reshape(b, rows, groups, d),
                  p["k_norm"]["scale"], eps)
        v = (x @ _dense(p["v"])).reshape(b, rows, groups, d)
        q, k = _rotate(q, pos, model), _rotate(k, pos, model)
        return q / jnp.sqrt(jnp.float32(d)), k, v

    o = _attention(*_by_rows(qkv, x, pos), idx, seg)

    def out(o, x):
        o = o.reshape(o.shape[:2] + (-1,))
        return (o * jax.nn.sigmoid(x @ p["gate"]["kernel"])) @ _dense(
            p["proj"])

    return _by_rows(out, o, x)


# --------------------------------------------------------------- experts
def route(logits, model):
    """(ids [S, k], weights [S, k]) of the chosen experts: the k largest
    softmax scores over the router's whole width, renormalised."""
    p = jax.nn.softmax(logits, axis=-1)
    chosen, ids = jax.lax.top_k(p, model["num_experts_per_tok"])
    return ids, chosen / jnp.sum(chosen, axis=-1, keepdims=True)


def _experts(p, x, model):
    b, t, d = x.shape
    xf = x.reshape(b * t, d)
    ids, weights = route(xf @ p["router"], model)
    first, past = model["experts_held"]
    shared = p["shared"]
    y = jax.nn.sigmoid(xf @ p["shared_gate"]) * _swiglu(
        xf, _dense(shared["wg"]), _dense(shared["wi"]), _dense(shared["wo"]))

    @jax.checkpoint
    def part(e, gate, up, down):  # every token through held expert e
        w = jnp.sum(jnp.where(ids == first + e, weights, 0.0), axis=-1)
        return w[:, None] * _swiglu(xf, gate, up, down)

    y, _ = jax.lax.scan(
        lambda y, held: (y + part(*held), None), y,
        (jnp.arange(past - first), p["wg"], p["wi"], p["wo"]))
    return y.reshape(b, t, d)


# ----------------------------------------------------------------- model
def _layer(blk, x, model, kind, pos, idx, seg):
    eps = model["rms_norm_eps"]

    @jax.checkpoint
    def mixer(p, w, x):
        u = _norm(x, w, eps)
        if kind == "gdn":
            return _gdn(p, u, model, pos)
        return _full(p, u, model, pos, idx, seg)

    @jax.checkpoint
    def ffn(p, w, x):
        return _experts(p, _norm(x, w, eps), model)

    x = x + mixer(blk["gdn" if kind == "gdn" else "attn"],
                  blk["ln_attn"]["scale"], x)
    return x + ffn(blk["moe"], blk["ln_mlp"]["scale"], x)


def _hidden(params, model: Dict[str, Any], tokens, segment_ids=None):
    """[B, T] token ids -> the final norm's output, float32 [B, T, d]."""
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
    return _norm(x, p["ln_f"]["scale"], model["rms_norm_eps"])


def logits(params, model: Dict[str, Any], tokens, segment_ids=None):
    """[B, T] token ids -> float32 logits [B, T, vocab]."""
    return _hidden(params, model, tokens, segment_ids) @ \
        params["params"]["head"].T


def _cross_entropy(x, head, targets):
    """Per-token cross-entropy of the logits x @ head.T, by rows."""
    def rows(x, targets):
        lg = x @ head.T
        picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
        return jax.nn.logsumexp(lg, axis=-1) - picked

    return _by_rows(rows, x, targets)


def loss(params, model: Dict[str, Any], batch) -> jax.Array:
    """The mean next-token cross-entropy of one batch: ``tokens`` [B, T],
    or ``(tokens, segment_ids)`` for packed rows."""
    head = params["params"]["head"]
    if isinstance(batch, (tuple, list)):
        tokens, seg = batch
        ce = _cross_entropy(_hidden(params, model, tokens, seg),
                            head, jnp.roll(tokens, -1, axis=-1))[:, :-1]
        w = jnp.logical_and(seg[:, 1:] == seg[:, :-1],
                            seg[:, 1:] > 0).astype(jnp.float32)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)
    return jnp.mean(_cross_entropy(_hidden(params, model, batch), head,
                                   jnp.roll(batch, -1, axis=-1)))
