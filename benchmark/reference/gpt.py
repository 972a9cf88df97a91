"""GPT-2 (Radford et al. 2019; ``openai-community/gpt2``): learned
positions, pre-LayerNorm blocks, fused QKV, tanh-approximated GELU
(``gelu_new``), tied output head.

Departures from the publication, each because the system under test is so
(``horovod_tpu/models/transformer.py``) and the configuration file says
it: the LayerNorm epsilon and the vocabulary come from the configuration
file; no dropout; packed rows (``segment_ids``: documents numbered from 1,
0 is padding) attend inside their document only, restart positions at each
document and take no loss across a boundary or on padding; the dense loss
is the mean over all T positions with the row rolled left by one, so the
last position predicts the row's first token (``chip_smoke.py``,
``bench.py``).
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def _layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def logits(params, model: Dict[str, Any], tokens, segment_ids=None):
    """[B, T] token ids -> [B, T, vocab] float32 logits."""
    p = params["params"]
    eps = model["layer_norm_epsilon"]
    heads = model["n_head"]
    b, t = tokens.shape
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids)
    idx = jnp.broadcast_to(jnp.arange(t), (b, t))
    # position inside the document: distance to the document's first token
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pos = idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    allowed = jnp.logical_and(
        idx[:, :, None] >= idx[:, None, :],
        seg[:, :, None] == seg[:, None, :],
    )[:, None]  # [B, 1, Tq, Tk]

    x = p["wte"]["embedding"][tokens] + p["wpe"][pos]
    for layer in range(model["n_layer"]):
        blk = p[f"block_{layer}"]
        h = _layer_norm(x, blk["ln_attn"], eps)
        qkv = (h @ blk["attn"]["qkv"]["Dense_0"]["kernel"]
               + blk["attn"]["qkv"]["Dense_0"]["bias"])
        q, k, v = jnp.moveaxis(qkv.reshape(b, t, 3, heads, -1), 2, 0)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(
            jnp.float32(q.shape[-1]))
        weights = jax.nn.softmax(
            jnp.where(allowed, scores, -jnp.inf), axis=-1)
        out = jnp.einsum("bhqk,bkhd->bqhd", weights, v).reshape(b, t, -1)
        x = x + (out @ blk["attn"]["proj"]["Dense_0"]["kernel"]
                 + blk["attn"]["proj"]["bias"])
        h = _layer_norm(x, blk["ln_mlp"], eps)
        h = _gelu_new(h @ blk["mlp"]["wi"]["Dense_0"]["kernel"]
                      + blk["mlp"]["wi"]["Dense_0"]["bias"])
        x = x + (h @ blk["mlp"]["wo"]["Dense_0"]["kernel"]
                 + blk["mlp"]["wo"]["bias"])
    x = _layer_norm(x, p["ln_f"], eps)
    return x @ p["wte"]["embedding"].T


def _cross_entropy(lg, targets):
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return logz - picked


def loss(params, model: Dict[str, Any], batch) -> jax.Array:
    """Mean next-token cross-entropy of one batch: ``tokens`` [B, T], or
    ``(tokens, segment_ids)`` for packed rows."""
    if isinstance(batch, (tuple, list)):
        tokens, seg = batch
        lg = logits(params, model, tokens, seg)
        ce = _cross_entropy(lg[:, :-1], tokens[:, 1:])
        w = jnp.logical_and(seg[:, 1:] == seg[:, :-1],
                            seg[:, 1:] > 0).astype(jnp.float32)
        return jnp.sum(ce * w) / jnp.maximum(jnp.sum(w), 1.0)
    lg = logits(params, model, batch)
    return jnp.mean(_cross_entropy(lg, jnp.roll(batch, -1, axis=-1)))
