"""Olmo-Hybrid-7B (``allenai/Olmo-Hybrid-7B`` config.json, model_type
``olmo_hybrid``): a decoder whose layers mix tokens by Gated DeltaNet
(GDN, arXiv:2412.06464, as fla's ``GatedDeltaNet`` computes it) where
``layer_types`` says ``linear_attention`` and by causal softmax attention
over all earlier tokens where it says ``full_attention``, with a dense
SwiGLU in every layer and OLMo 2's norms (arXiv:2501.00656 section 3): each
sublayer's output is normed, its input is not,

    h = x + RMSNorm(Mixer(x)),   y = h + RMSNorm(W_down(SiLU(h W_gate) * h W_up));

a final RMSNorm, an untied head, the mean next-token cross-entropy.

GDN, per head (dk = linear_key_head_dim keys, dv = linear_value_head_dim
values) and token t:
    q = L2(SiLU(Conv(x W_q))) / sqrt(dk),  k = L2(SiLU(Conv(x W_k))),
    v = SiLU(Conv(x W_v)),  Conv a causal depthwise convolution of
    ``linear_conv_kernel_dim`` taps;  beta = sigmoid(x W_b) a head, times 2
    with linear_allow_neg_eigval;  g = -exp(A_log) softplus(x W_a + dt_bias)
    a head;
    S_t = exp(g_t) (I - beta_t k_t k_t^T) S_{t-1} + beta_t k_t v_t^T,
    o_t = S_t^T q_t;   out = W_o (RMSNorm_head(o) * SiLU(x W_z)), the norm's
    scale shared by the heads, the gate one a channel.
It is computed as that recurrence, token by token.

Full attention: q = RMSNorm(x W_q), k = RMSNorm(x W_k) over the whole
projection (hidden_size / num_attention_heads a head), v = x W_v; rotary
positions only where rope_parameters.rope_theta is set (it is null in the
published file); softmax of q.k / sqrt(head) over earlier tokens.

Departures from the publication, each because the configuration file says
so: the layers are the published layers ``layers_held``; the vocabulary is
the slice the configuration holds; packed rows (``segment_ids``) attend,
convolve and carry a state inside their document only and take no loss
across a boundary or on padding; the dense loss is the mean over all T
positions with the row rolled left by one.

For memory only, and changing no value (the system's own
rematerialisation, ``remat`` and ``remat_save``, is no part of this file):
every layer is wrapped in ``jax.checkpoint``; the position-wise parts, the
convolutions (each block of rows with the taps' rows before it) and the
loss are taken ``ROW_BLOCK`` tokens at a time, each block checkpointed;
the recurrence keeps its state every ``RECURRENCE_BLOCK`` tokens and makes
each token's step again; attention is taken ``QUERY_BLOCK`` queries at a
time.  At the cell's size this holds the gradient program's temporaries
to what one chip has beside 16 B a parameter.  None of ``ops/kda*``, the
flash kernels or ``horovod_tpu`` is used.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256        # rows of the score matrix held at a time
RECURRENCE_BLOCK = 64    # tokens of the recurrence between two kept states
ROW_BLOCK = 1024         # tokens of a position-wise part held at a time


def layer_kinds(model: Dict[str, Any]):
    """The mixer of every held layer, from ``layer_types`` at its
    published index: "gdn" or "full"."""
    kinds = {"linear_attention": "gdn", "full_attention": "full"}
    return [kinds[model["layer_types"][i]] for i in model["layers_held"]]


def _rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * scale


def _dense(p):
    return p["Dense_0"]["kernel"]


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
    b, t, _ = x.shape
    h = model["linear_num_key_heads"]
    dk, dv = model["linear_key_head_dim"], model["linear_value_head_dim"]
    eps = model["rms_norm_eps"]

    def unit(a):
        return a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True)
                            + 1e-6)

    taps = model["linear_conv_kernel_dim"]

    def inputs(x, pos):  # x holds the taps - 1 rows before pos's
        rows = pos.shape[1]
        q, k, v = (
            jax.nn.silu(_conv(x @ p[name]["kernel"], p[f"conv_{name}"], pos)
                        ).reshape(b, rows, h, -1) for name in ("q", "k", "v"))
        now = x[:, taps - 1:]
        beta = jax.nn.sigmoid(now @ p["b"]["kernel"])
        if model["linear_allow_neg_eigval"]:
            beta = 2.0 * beta
        g = -jnp.exp(p["A_log"]) * jax.nn.softplus(
            now @ p["a"]["kernel"] + p["dt_bias"])
        return unit(q) / jnp.sqrt(jnp.float32(dk)), unit(k), v, g, beta

    o = _delta_rule(*_by_rows(inputs, x, pos, before=taps - 1), pos)

    def out(o, x):
        o = _rms_norm(o, p["o_norm"]["scale"], eps) * jax.nn.silu(
            x @ p["z"]["kernel"]).reshape(o.shape)
        return o.reshape(o.shape[0], -1) @ _dense(p["proj"])

    return _by_rows(lambda o, x: jax.vmap(out)(o, x), o, x)


# ------------------------------------------------------ full attention
def _rotate(x, pos, theta):
    """[B, T, H, D] turned by its positions, rotate-half pairs (i, i + D/2)."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = pos.astype(jnp.float32)[..., None, None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angles)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angles)] * 2, axis=-1)
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * cos + half * sin


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


def _full(p, x, model, pos, idx, seg):
    b, t, d = x.shape
    h = model["num_attention_heads"]
    head = d // h
    eps, theta = model["rms_norm_eps"], model["rope_parameters"]["rope_theta"]

    def qkv(x, pos):
        rows = x.shape[1]
        q = _rms_norm(x @ _dense(p["q"]), p["q_norm"]["scale"], eps)
        k = _rms_norm(x @ _dense(p["k"]), p["k_norm"]["scale"], eps)
        q, k, v = (a.reshape(b, rows, h, head)
                   for a in (q, k, x @ _dense(p["v"])))
        if theta is not None:
            q, k = _rotate(q, pos, theta), _rotate(k, pos, theta)
        return q / jnp.sqrt(jnp.float32(head)), k, v

    o = _attention(*_by_rows(qkv, x, pos), idx, seg)
    return _by_rows(lambda o: o.reshape(o.shape[:2] + (-1,))
                    @ _dense(p["proj"]), o)


# ----------------------------------------------------------------- model
def _swiglu(p, x):
    return (jax.nn.silu(x @ _dense(p["wg"])) * (x @ _dense(p["wi"]))
            ) @ _dense(p["wo"])


def _layer(blk, x, model, kind, pos, idx, seg):
    eps = model["rms_norm_eps"]
    if kind == "gdn":
        y = _gdn(blk["gdn"], x, model, pos)
    else:
        y = _full(blk["attn"], x, model, pos, idx, seg)
    x = x + _by_rows(
        lambda y: _rms_norm(y, blk["ln_attn_post"]["scale"], eps), y)
    return x + _by_rows(lambda x: _rms_norm(
        _swiglu(blk["mlp"], x), blk["ln_mlp_post"]["scale"], eps), x)


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
    return _rms_norm(x, p["ln_f"]["scale"], model["rms_norm_eps"])


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
