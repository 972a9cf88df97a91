"""Ouro / LoopLM (Zhu et al. 2025, "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741; ``ByteDance/Ouro-2.6B``): a decoder
whose whole stack of layers is applied ``total_ut_steps`` times with the
same weights.  A layer is RMSNorm -> attention (separate bias-free q, k,
v, o; rotary positions, rotate-half pairing; causal softmax) -> RMSNorm ->
residual, then RMSNorm -> SwiGLU -> RMSNorm -> residual ("sandwich"
norms).  After every pass the final RMSNorm, whose output feeds the next
pass, the untied head, and an exit gate lambda = sigmoid(h . wg + bg) per
token.  The loss is the cross-entropy expected under the exit distribution
p_t = lambda_t prod_{j<t}(1 - lambda_j) (the last pass takes what is left),
less ``entropy_beta`` times that distribution's entropy.

Departures from the publication, each because the configuration file says
so: the vocabulary is the slice the configuration holds (ids, logits and
loss over the slice); packed rows (``segment_ids``: documents numbered
from 1, 0 is padding) attend inside their document only, restart their
rotary positions at each document and take no loss across a boundary or
on padding; the dense loss is the mean over all T positions with the row
rolled left by one, so the last position predicts the row's first token
(as ``reference/gpt.py``).

For memory only, and changing no value: every application of a layer is
wrapped in ``jax.checkpoint``, attention is taken ``QUERY_BLOCK`` queries
at a time (each block checkpointed too), so that the float32 scores of a
row of 4096 tokens are never whole in memory, and the passes are a
``lax.scan`` (unrolled, the compiled program alone took 1.1 GB of the
chip), so that the check fits beside the system's state on one chip.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
from jax.scipy.special import xlogy

QUERY_BLOCK = 512  # rows of the score matrix held at a time


def _rms_norm(x, p, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * p["scale"]


def _rotate_half(x):
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([-x2, x1], axis=-1)


def _rope(x, pos, theta):
    """[B, T, H, D] turned by its positions [B, T]."""
    d = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    freqs = pos.astype(jnp.float32)[..., None] * inv_freq     # [B, T, D/2]
    angles = jnp.concatenate([freqs, freqs], axis=-1)[:, :, None]
    return x * jnp.cos(angles) + _rotate_half(x) * jnp.sin(angles)


def _attention(q, k, v, idx, seg):
    """Causal softmax attention inside each document, a block of queries
    at a time."""
    b, t, h, d = q.shape
    block = QUERY_BLOCK if t % QUERY_BLOCK == 0 else t

    @jax.checkpoint
    def rows(q_blk, idx_q, seg_q):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / jnp.sqrt(
            jnp.float32(d))
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
    return jnp.moveaxis(out, 0, 1).reshape(b, t, h * d)


def _layer(blk, x, model, pos, idx, seg):
    eps = model["rms_norm_eps"]
    b, t, _ = x.shape
    heads = model["num_attention_heads"]
    n = _rms_norm(x, blk["ln_attn"], eps)
    q, k, v = (
        (n @ blk["attn"][name]["Dense_0"]["kernel"]).reshape(b, t, heads, -1)
        for name in ("q", "k", "v"))
    q, k = (_rope(a, pos, model["rope_theta"]) for a in (q, k))
    a = _attention(q, k, v, idx, seg) @ blk["attn"]["proj"]["Dense_0"][
        "kernel"]
    x = x + _rms_norm(a, blk["ln_attn_post"], eps)
    n = _rms_norm(x, blk["ln_mlp"], eps)
    mlp = blk["mlp"]
    m = (jax.nn.silu(n @ mlp["wg"]["Dense_0"]["kernel"])
         * (n @ mlp["wi"]["Dense_0"]["kernel"])) @ mlp["wo"]["Dense_0"][
             "kernel"]
    return x + _rms_norm(m, blk["ln_mlp_post"], eps)


def logits_and_gates(trees, model: Dict[str, Any], tokens,
                     segment_ids=None):
    """[B, T] token ids -> (float32 logits [S, B, T, vocab], exit
    probabilities lambda [S, B, T]) of the S = ``total_ut_steps`` passes.
    ``trees`` is the model's one parameter tree, used by every pass, or a
    sequence of S trees, one a pass (the layers, the final norm, the head
    and the gate of pass t are its own; the embedding is the first
    tree's), with which a shared parameter's gradient can be taken apart
    by use."""
    b, t = tokens.shape
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids)
    idx = jnp.broadcast_to(jnp.arange(t), (b, t))
    # position inside the document: distance to the document's first token
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pos = idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    layer = jax.checkpoint(
        lambda blk, x: _layer(blk, x, model, pos, idx, seg))
    shared = isinstance(trees, dict)

    def one_pass(x, tree):
        p = (trees if shared else tree)["params"]
        for i in range(model["num_hidden_layers"]):
            x = layer(p[f"block_{i}"], x)
        x = _rms_norm(x, p["ln_f"], model["rms_norm_eps"])
        gate = jax.nn.sigmoid(
            x @ p["exit_gate"]["kernel"][:, 0] + p["exit_gate"]["bias"][0])
        return x, (x @ p["head"].T, gate)

    first = trees if shared else trees[0]
    x = first["params"]["wte"]["embedding"][tokens]
    # a loop over the passes, for the size of the compiled program only
    _, (logits, gates) = jax.lax.scan(
        one_pass, x,
        None if shared else jax.tree.map(lambda *a: jnp.stack(a), *trees),
        length=model["total_ut_steps"])
    return logits, gates


def _cross_entropy(lg, targets):
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return logz - picked


def expected_loss(lg, lam, targets, beta: float,
                  weights: Optional[jax.Array] = None) -> jax.Array:
    """sum_t p_t CE_t - beta H(p) per token, averaged (by ``weights``
    where given)."""
    ce = _cross_entropy(lg, jnp.broadcast_to(targets, lg.shape[:-1]))
    left = jnp.cumprod(1.0 - lam, axis=0)         # prod_{j<=t} (1 - l_j)
    before = jnp.concatenate([jnp.ones_like(left[:1]), left[:-1]])
    p = jnp.concatenate([(lam * before)[:-1], before[-1:]])
    # xlogy: 0 log 0 = 0.  (Written with ``jnp.where`` around the
    # logarithm, this loss is right alone and wrong, 15.6 for 9.03, as the
    # value of ``jax.value_and_grad`` on the v5e: PERF.md, PR 27.)
    entropy = -jnp.sum(xlogy(p, p), axis=0)
    per_token = jnp.sum(p * ce, axis=0) - beta * entropy
    if weights is None:
        return jnp.mean(per_token)
    return jnp.sum(per_token * weights) / jnp.maximum(jnp.sum(weights), 1.0)


def loss(params, model: Dict[str, Any], batch) -> jax.Array:
    """The expected loss of one batch: ``tokens`` [B, T], or ``(tokens,
    segment_ids)`` for packed rows."""
    beta = model["entropy_beta"]
    if isinstance(batch, (tuple, list)):
        tokens, seg = batch
        lg, lam = logits_and_gates(params, model, tokens, seg)
        w = jnp.logical_and(seg[:, 1:] == seg[:, :-1],
                            seg[:, 1:] > 0).astype(jnp.float32)
        return expected_loss(lg[:, :, :-1], lam[:, :, :-1], tokens[:, 1:],
                             beta, w)
    lg, lam = logits_and_gates(params, model, batch)
    return expected_loss(lg, lam, jnp.roll(batch, -1, axis=-1), beta)
