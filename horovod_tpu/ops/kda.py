"""The delta rule with a per-channel decay (KDA, Kimi Linear,
arXiv:2510.26692 section 3), chunk by chunk.

Per head and token t, with a state ``S`` in R^{dk x dv}, ``S_0 = 0``:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t,         alpha_t = exp(g_t),  g_t < 0 per channel.

:func:`kda_recurrent` is that recurrence, token by token.
:func:`kda_chunked` computes the same in chunks of ``chunk`` tokens (the
WY form of the delta rule): writing ``G_r`` for the chunk's running sum
of ``g`` and ``delta_i = beta_i (v_i - (alpha_i k_i)^T S_{i-1})`` for the
value a token really writes,

    (I + Diag(beta) A) delta = Diag(beta) (V - (K exp G) S_0),
        A_ri = sum_c k_rc k_ic exp(G_rc - G_ic)            (i < r)
    o_r    = (q_r exp G_r)^T S_0 + sum_{i<=r} B_ri delta_i,  B as A with q_r
    S_C    = Diag(exp G_C) S_0 + sum_i (k_i exp(G_C - G_i)) delta_i^T,

so a chunk costs the inverse of one unit-lower-triangular matrix (by
blocks, as matmuls) and a few more matmuls, and only the state passes from
chunk to chunk (a ``lax.scan``).  ``exp(G_r -
G_i)`` does not factor into one matmul over a whole chunk in float32
(``exp(-G_i)`` reaches e^{5 x 64}); it does with the decays referred to
the boundary of the *row's* sub-chunk of ``sub`` tokens: the row's factor
is ``exp(G_r - G_start) <= 1`` and the column's ``exp(G_start - G_i)`` is
at most ``exp(sub x |g|_max)``, e^80 for ``sub`` 16 and ``g > -5``.

A packed row's state restarts at each document (``segment_ids``): pairs
of different documents are masked out of A and B, a token after a
boundary inside its chunk does not see the incoming state, and the
outgoing state holds the chunk's last document only.

Which function is what.  :func:`kda_recurrent` is the definition, token
by token.  :func:`kda_chunk_major` (and :func:`kda_chunked`, which lays
[B, T, H, d] operands out for it) is the plain chunked form, ``jax.numpy``
operations and jax's transpose of them, on chunk-major copies
[n, B, H, C, d] of its operands: what the kernels are tested against
beside the recurrence, and what a head width the chip's kernels do not
take falls to.  :func:`kda` is what the mixer calls: on the projections'
own [B, T, H·d] it runs ``kda_kernels.delta_rule``, a Pallas forward
kernel and a hand-written backward kernel under one ``jax.custom_vjp``
that read a chunk of a head as a block of those arrays and keep the
chunk's triangles, the inverse, U, W and the running state in VMEM.  Of
the forward the backward keeps the state entering each chunk and the
chunk's inverse (``kda_kernels``' docstring has the equations); everything
else it makes again chunk by chunk.  :func:`conv_silu_heads` (the short
convolution, SiLU and q's and k's norms) and :func:`rms_gate_heads` (the
output's norm and gate) are what comes before and after the core, on the
same layout.  No [T, T] array and no [chunk, chunk, dk] array is made
anywhere.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from . import kda_kernels
from .kda_kernels import MAX_EXPONENT as _MAX_EXPONENT


def kda_recurrent(q, k, v, g, beta, segment_ids=None):
    """The recurrence, token by token: q, k, g [B, T, H, dk] (g [B, T, H,
    1]: one decay a head), v [B, T, H, dv], beta [B, T, H] -> o [B, T, H,
    dv], all float32.  The definition the chunked form is tested
    against."""
    b, t, h, dk = q.shape
    if segment_ids is None:
        fresh = jnp.zeros((b, t), bool)
    else:
        fresh = jnp.concatenate(
            [jnp.zeros((b, 1), bool),
             segment_ids[:, 1:] != segment_ids[:, :-1]], axis=1)

    def token(state, xs):
        q_t, k_t, v_t, g_t, beta_t, fresh_t = xs
        state = jnp.where(fresh_t[:, None, None, None], 0.0, state)
        state = state * jnp.exp(g_t)[..., None]
        wrote = beta_t[..., None] * (
            v_t - jnp.einsum("bhk,bhkv->bhv", k_t, state))
        state = state + k_t[..., None] * wrote[..., None, :]
        return state, jnp.einsum("bhk,bhkv->bhv", q_t, state)

    xs = tuple(jnp.moveaxis(a.astype(jnp.float32), 1, 0)
               for a in (q, k, v, g, beta)) + (jnp.moveaxis(fresh, 1, 0),)
    _, out = lax.scan(
        token, jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


def _decayed_products(left, right, g_cum, sub: int, dtype):
    """[..., C, C] of ``sum_c left_rc right_ic exp(G_rc - G_ic)`` for the
    pairs i <= r (the others hold finite numbers the caller masks):
    ``left``, ``right``, ``g_cum`` [..., C, dk], the decays referred to
    the start of row r's sub-chunk.  The factors are made in float32 and
    multiplied as ``dtype``, summed in float32."""
    c, dk = g_cum.shape[-2:]
    n = c // sub
    lead = g_cum.shape[:-2]
    # G at the start of each sub-chunk: the running sum at the last token
    # of the one before, 0 for the first
    start = jnp.concatenate(
        [jnp.zeros(lead + (1, dk), g_cum.dtype),
         g_cum[..., sub - 1:c - 1:sub, :]], axis=-2)       # [..., n, dk]
    rows = (left * jnp.exp(
        g_cum - jnp.repeat(start, sub, axis=-2))).astype(dtype).reshape(
            lead + (n, sub, dk))
    cols = (right[..., None, :, :] * jnp.exp(jnp.minimum(
        start[..., :, None, :] - g_cum[..., None, :, :], _MAX_EXPONENT))
            ).astype(dtype)
    out = jnp.einsum("...ard,...aid->...ari", rows, cols,
                     preferred_element_type=jnp.float32)
    return out.reshape(lead + (c, c))


_LEAF = 16  # edge of the blocks inverted row by row


def _inverse_unit_lower(m):
    """The inverse of unit lower-triangular matrices [..., C, C], C a
    power of two times 16: the diagonal blocks of 16 by forward
    substitution (exact, 15 rows), then pairs of blocks merged,
    [[A, 0], [C, B]]^-1 = [[A^-1, 0], [-B^-1 C A^-1, B^-1]], by matmuls at
    the highest precision (they are tiny).  A triangular solve written as
    matmuls: XLA's own is a loop of C steps on the chip, and so are the
    two more its transpose makes."""
    c = m.shape[-1]
    lead = m.shape[:-2]
    if c <= _LEAF:
        blocks, size = m[..., None, :, :], c
    else:
        size = _LEAF
        k = c // size
        tiled = m.reshape(lead + (k, size, k, size))
        blocks = jnp.stack([tiled[..., i, :, i, :] for i in range(k)],
                           axis=-3)                        # [..., k, 16, 16]
    strict = jnp.tril(blocks, -1)
    unit = jnp.eye(size, dtype=m.dtype)
    rows = [jnp.broadcast_to(unit[0], blocks.shape[:-2] + (size,))]
    for i in range(1, size):  # X_i = e_i - sum_{j<i} N_ij X_j
        rows.append(unit[i] - jnp.einsum(
            "...j,...jk->...k", strict[..., i, :i], jnp.stack(rows, axis=-2),
            precision=lax.Precision.HIGHEST))
    inverse = jnp.stack(rows, axis=-2)                     # [..., k, s, s]
    while size < c:
        pairs = inverse.shape[-3] // 2
        a, b = inverse[..., 0::2, :, :], inverse[..., 1::2, :, :]
        tiled = m.reshape(lead + (pairs, 2, size, pairs, 2, size))
        below = jnp.stack([tiled[..., i, 1, :, i, 0, :]
                           for i in range(pairs)], axis=-3)
        lower = -jnp.einsum(
            "...ij,...jk,...kl->...il", b, below, a,
            precision=lax.Precision.HIGHEST)
        inverse = jnp.concatenate([
            jnp.concatenate([a, jnp.zeros_like(a)], axis=-1),
            jnp.concatenate([lower, b], axis=-1)], axis=-2)
        size *= 2
    return inverse[..., 0, :, :]


def kda_chunk_major(q, k, v, g, beta, seg, sub: int = 16):
    """The chunked form on operands laid out as its loop over the chunks
    reads them: q, k [n, B, H, C, dk], v [n, B, H, C, dv], g as q (or
    [n, B, H, C, 1]: one decay a head) and beta [n, B, H, C, 1] float32,
    ``seg`` [n, B, C] int32 -> o [n, B, H, C, dv] float32.  n chunks of C
    tokens; the caller pads.  q's type is the type of every matmul's
    operands (the decays' factors and the state are made in float32 and
    rounded to it, the sums are float32): float32 is the recurrence to
    rounding, bfloat16 what the MXU multiplies anyway.  With one decay a
    head, exp(G_r - G_i) is one [C, C] matrix, made exactly: no sub-chunks
    and no bound on g."""
    n, b, h, chunk, dk = q.shape
    dv = v.shape[-1]
    dtype = q.dtype
    q, k, v = (a.astype(jnp.float32) for a in (q, k, v))
    if chunk % sub:
        raise ValueError(f"chunk {chunk} is not whole sub-chunks of {sub}")
    # the running sum of g as one small matmul at full precision
    lower_ones = jnp.tril(jnp.ones((chunk, chunk), jnp.float32))
    g_cum = jnp.einsum("ij,...jd->...id", lower_ones, g,
                       precision=lax.Precision.HIGHEST)
    # the documents' masks, the same for every head
    before = jnp.concatenate([seg[:1, :, :1], seg[:-1, :, -1:]], axis=0)
    starts = seg != jnp.concatenate([before, seg[..., :-1]], axis=-1)
    # a token sees the incoming state if no document began in its chunk
    # up to and including itself
    sees_in = (jnp.cumsum(starts, axis=-1) == 0)[:, :, None, :, None]
    same = seg[..., :, None] == seg[..., None, :]           # [n, B, C, C]
    row = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    strict = jnp.logical_and(same, row > col)[:, :, None]
    lower = jnp.logical_and(same, row >= col)[:, :, None]
    in_last = same[:, :, -1, :][:, :, None, :, None]        # [n, B, 1, C, 1]

    if g.shape[-1] == 1:
        # exp(G_r - G_i) on the triangle, 0 above it: a clamp at 0 there
        # would stop the gradient of a difference that rounds to >= 0
        decay = jnp.exp(jnp.where(
            row >= col, g_cum - jnp.swapaxes(g_cum, -1, -2), -1e30))
        products = lambda left, right: jnp.einsum(
            "...rd,...id->...ri", left.astype(dtype), right.astype(dtype),
            preferred_element_type=jnp.float32) * decay
    else:
        products = lambda left, right: _decayed_products(
            left, right, g_cum, sub, dtype)
    a_mat = jnp.where(strict, products(k, k), 0.0)
    b_mat = jnp.where(lower, products(q, k), 0.0).astype(dtype)
    decay_in = jnp.exp(g_cum)                               # <= 1
    k_in = jnp.where(sees_in, k * decay_in, 0.0)
    q_in = jnp.where(sees_in, q * decay_in, 0.0).astype(dtype)
    k_out = jnp.where(
        in_last, k * jnp.exp(g_cum[..., -1:, :] - g_cum), 0.0).astype(dtype)
    carry_decay = jnp.where(sees_in[..., -1, :], decay_in[..., -1, :], 0.0)
    # (I + Diag(beta) A) [U | W] = Diag(beta) [V | K exp G]
    inverse = _inverse_unit_lower(
        jnp.eye(chunk, dtype=jnp.float32) + beta * a_mat)
    solved = jnp.einsum(
        "...ij,...jd->...id", inverse.astype(dtype),
        (beta * jnp.concatenate([v, k_in], axis=-1)).astype(dtype),
        preferred_element_type=jnp.float32)
    u, w = solved[..., :dv], solved[..., dv:].astype(dtype)

    def dot(spec, left, right):
        return jnp.einsum(spec, left, right.astype(dtype),
                          preferred_element_type=jnp.float32)

    def one_chunk(state, xs):
        u_c, w_c, q_c, k_c, b_c, decay_c = xs
        wrote = u_c - dot("bhck,bhkv->bhcv", w_c, state)
        out = (dot("bhck,bhkv->bhcv", q_c, state)
               + dot("bhci,bhiv->bhcv", b_c, wrote))
        state = (state * decay_c[..., None]
                 + dot("bhck,bhcv->bhkv", k_c, wrote))
        return state, out

    _, out = lax.scan(
        one_chunk, jnp.zeros((b, h, dk, dv), jnp.float32),
        (u, w, q_in, k_out, b_mat, carry_decay))
    return out


def chunk_major(a, chunk: int):
    """[B, T, H, d] (T whole chunks) -> [n, B, H, C, d]."""
    b, t, h, d = a.shape
    return jnp.transpose(
        a.reshape(b, t // chunk, chunk, h, d), (1, 0, 3, 2, 4))


def kda_chunked(q, k, v, g, beta, segment_ids: Optional[jax.Array] = None,
                chunk: int = 64, sub: int = 16):
    """:func:`kda_recurrent` in chunks of ``chunk`` tokens, in plain
    ``jax.numpy``: q, k, g [B, T, H, dk] (g [B, T, H, 1]: one decay a
    head), v [B, T, H, dv], beta [B, T, H] -> o [B, T, H, dv] float32; q's
    type is the matmuls' operands'.  A decay a channel must lie above ``-80
    / sub`` (the bounded gate's lower bound is -5 for ``sub`` 16); one a
    head has no bound."""
    b, t, h, _ = q.shape
    pad = -t % chunk
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    beta = beta[..., None]
    if pad:  # tokens that write nothing and decay nothing
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0)))
                            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    n = (t + pad) // chunk
    out = kda_chunk_major(
        *(chunk_major(a, chunk) for a in (q, k, v)),
        *(chunk_major(a.astype(jnp.float32), chunk) for a in (g, beta)),
        jnp.swapaxes(seg.reshape(b, n, chunk), 0, 1), sub)
    # [n, B, H, C, dv] -> [B, T, H, dv]
    return jnp.transpose(out, (1, 0, 3, 2, 4)).reshape(
        b, n * chunk, h, -1)[:, :t]


def kda(q, k, v, g, beta, segment_ids: Optional[jax.Array] = None):
    """The delta rule on the projections' layout: q, k [B, T, H·dk], v
    [B, T, H·dv], g [B, T, H·dk] (a decay a channel) or [B, T, H] (one a
    head, Gated DeltaNet's), beta [B, T, H] -> o [B, T, H·dv] float32, q's
    type the matmuls' operands'.  By the kernels (``kda_kernels``) where
    they take the heads' widths, which re-lay nothing; else by
    :func:`kda_chunked`, whose loop reads chunk-major copies."""
    b, t, _ = q.shape
    heads = beta.shape[-1]
    if all(kda_kernels.takes(a.shape[-1] // heads) for a in (q, v)):
        return kda_kernels.delta_rule(q, k, v, g, beta, segment_ids)
    out = kda_chunked(*(a.reshape(b, t, heads, -1) for a in (q, k, v, g)),
                      beta, segment_ids, kda_kernels.CHUNK, kda_kernels.SUB)
    return out.reshape(b, t, -1)


def conv_silu_heads(x, taps, segment_ids, conv, heads: int, scale, dtype):
    """SiLU(conv(x)) and, ``scale`` given, each head's L2 norm times it
    (q's and k's), in float32, as ``dtype``: x [B, T, H·d] as projected,
    taps [K, H·d] (the parameters' type), ``segment_ids`` [B, T] or None,
    ``conv(x, taps, ids)`` the causal depthwise convolution in float32
    (ids [B, T, 1] or None).  One kernel pair on that layout where the
    kernels take the heads' width (``kda_kernels.short_conv_silu``, which
    runs ``conv`` on a block of rows and its halo); else XLA, the norm
    through [B, T, H, d]."""
    seg = (None if segment_ids is None
           else segment_ids.astype(jnp.int32)[..., None])
    if kda_kernels.takes(x.shape[-1] // heads):
        return kda_kernels.short_conv_silu(x, taps, seg, conv, heads, scale,
                                           dtype)
    y = jax.nn.silu(conv(x, taps, seg))
    if scale is None:
        return y.astype(dtype)
    return unit_heads(y, heads, scale, dtype)


def unit_heads(x, heads: int, scale: float, dtype):
    """``x / |x|`` a head times ``scale``, in float32, as ``dtype``: x
    [B, T, H·d] float32, through [B, T, H, d]."""
    b, t, lanes = x.shape
    x = x.reshape(b, t, heads, -1)
    x = x * lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)
    return (x * scale).astype(dtype).reshape(b, t, lanes)


def rms_gate_heads(x, weight, gate, eps: float, dtype):
    """RMSNorm over each head's channels times ``weight`` [d] and the
    ``gate``, one a head [B, T, H] or one a channel [B, T, H·d], in
    float32, as ``dtype``: x [B, T, H·d] float32 (the mixer's output norm
    and gate)."""
    b, t, lanes = x.shape
    d = weight.shape[0]
    if kda_kernels.takes(d):
        return kda_kernels.head_rms_gate(x, weight, gate, eps, dtype)
    x = x.reshape(b, t, lanes // d, d)
    x = x * lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)
    gate = (gate.reshape(x.shape) if gate.shape[-1] == lanes
            else gate[..., None])
    return (x * weight * gate).astype(dtype).reshape(b, t, lanes)
