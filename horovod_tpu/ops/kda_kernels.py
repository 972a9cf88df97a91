"""The chunked delta rule of ``ops/kda.py`` as a pair of Pallas kernels,
forward and a hand-written backward under one ``jax.custom_vjp``, on the
projections' own layout: q, k, g ``[B, T, H·dk]``, v and o ``[B, T, H·dv]``,
beta ``[B, T, H]``.

A grid step owns one chunk of ``CHUNK`` tokens by one slab of
``_heads_a_step`` heads' lanes; the chunks are walked in order (in reverse
by the backward) with every head's state, ``[dv, dk]`` float32, in a VMEM
scratch.  A chunk's triangles, the inverse, U, W and what the tokens wrote
never leave VMEM.  The state is kept transposed so that its decay, one
factor a key channel, runs along the lanes.

The mathematics and the precision are ``kda.kda_chunk_major``'s: matmul
operands are rounded to q's type where it rounds them, the decays'
factors, the running sum of g, the state and every sum are float32.  Three
things are computed otherwise, to the same rounding: the running sum (the
lower-ones matmul with g as three bfloat16 parts, whose terms are exact),
the 16 x 16 leaves of the unit-lower-triangular inverse (the product
``(I - N)(I + N^2)(I + N^4)(I + N^8)``, exact for a nilpotent N, for 15
row steps), and the inverse's full-precision products (the six products of
bfloat16 parts that a float32 contraction is made of, laid side by side
along the contraction of one matmul: ``_dot``).

What the backward keeps from the forward: the state entering each chunk
(``[B, n, H, dv, dk]`` float32) and the chunk's inverse (``[B, n, H, C, C]``
float32, ten full-precision matmuls to make again); the triangles, U, W and
what was written are made again from q, k, v, g, beta, one chunk at a time.
With ``wrote = U - W S0``, ``out = Q_in S0 + B wrote``, ``S1 = Diag(decay)
S0 + K_out^T wrote`` and ``[U | W] = X Diag(beta) [V | K_in]``,
``X = (I + Diag(beta) A)^-1``:

    dwrote = B^T do + K_out dS1,   dB = do wrote^T,   dK_out = wrote dS1^T,
    dQ_in = do S0^T,   dW = -dwrote S0^T,   ddecay = rowsum(dS1 * S0),
    dS0 = Diag(decay) dS1 + Q_in^T do - W^T dwrote,
    d rhs = X^T [dwrote | dW],   dX = [dwrote | dW] rhs^T,
    d(Diag(beta) A) = -X^T dX X^T on the strict triangle,

then through A, B and the decays' exponentials into q, k and g, the
gradient of the running sum being the reversed running sum.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as pk

# exp() of anything larger is never needed where the mask keeps the entry
# (sub x |g|_max <= 80 is the caller's side of the bargain); the clamp
# keeps the masked entries finite.
MAX_EXPONENT = 80.0
CHUNK = 64   # tokens of a chunk
SUB = 16     # tokens of a sub-chunk: the decays' reference points, and the
#              edge of the inverse's leaves

# a grid step's heads are a leading batch axis of every array in the body
_NN = (((2,), (1,)), ((0,), (0,)))
_NT = (((2,), (2,)), ((0,), (0,)))
_TN = (((1,), (1,)), ((0,), (0,)))


def takes(width: int) -> bool:
    """Whether the kernels take heads ``width`` wide: any width where
    Pallas is interpreted, whole 128-lane slabs on the chip."""
    return pk._interpret() or width % pk._LANES == 0


def _heads_a_step(heads: int) -> int:
    """Heads a grid step owns, four where they divide.  They are a batch
    axis of the body, so each of its small matmuls is issued for every
    head before the next one that depends on it: a head's chunk alone is a
    chain of some forty dependent MXU round trips that nothing hides
    (PERF.md, PR 33: 4.7 ms a layer forward at one head, 2.1 at four; eight
    need more VMEM than a kernel gets unasked)."""
    return next(n for n in (4, 3, 2, 1) if heads % n == 0)


def _parts(x):
    """x float32 as three bfloat16 parts, largest first, whose sum is x to
    float32 rounding."""
    hi = x.astype(jnp.bfloat16)
    rest = x - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def _dot(a, b, dims=_NN, exact: bool = False):
    """A batched matmul with float32 sums.  ``exact``: of float32 operands
    at full precision, the six products of bfloat16 parts that a float32
    contraction is made of (hi·hi, hi·mid, mid·hi, hi·lo, lo·hi, mid·mid)
    side by side along the contraction of ONE matmul: for [64, 64]
    operands three MXU passes of 128 where Mosaic's own float32
    contraction makes six of 64, and the MXU's passes are what a chunk's
    inverse costs (PERF.md, PR 33)."""
    if exact:
        (a1, a2, a3), (b1, b2, b3) = _parts(a), _parts(b)
        (along_a,), (along_b,) = dims[0]
        a = jnp.concatenate([a3, a2, a1, a2, a1, a1], axis=along_a)
        b = jnp.concatenate([b1, b2, b3, b1, b2, b1], axis=along_b)
    return lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _iotas(c: int):
    return (lax.broadcasted_iota(jnp.int32, (c, c), 0),
            lax.broadcasted_iota(jnp.int32, (c, c), 1))


def _running_sum(x, reverse: bool = False):
    """[h, C, d] float32 -> the sum of each row and those before it (after
    it, ``reverse``), to float32 rounding: a matmul by lower ones with x
    as three bfloat16 parts, so every term is exact and the sums are the
    MXU's float32."""
    h, c, d = x.shape
    row, col = _iotas(c)
    ones = jnp.broadcast_to(
        jnp.where(row <= col if reverse else row >= col, 1.0, 0.0
                  ).astype(jnp.bfloat16), (h, c, c))
    hi, mid, low = _parts(x)
    parts = _dot(ones, jnp.concatenate([low, mid, hi], axis=2))
    return parts[..., :d] + parts[..., d:2 * d] + parts[..., 2 * d:]


def _inverse_unit_lower(strict, sub: int):
    """(I + N)^-1 of strictly lower-triangular N [h, C, C] float32, C a
    power of two times ``sub``, as matmuls on the whole matrices at full
    precision: the diagonal blocks of ``sub`` by the product form (N_d^sub
    = 0), then pairs of blocks merged, [[A, 0], [C, B]]^-1 = [[A^-1, 0],
    [-B^-1 C A^-1, B^-1]], which on a block-diagonal X is X - X C X."""
    c = strict.shape[-1]
    row, col = _iotas(c)
    shift = sub.bit_length() - 1
    block = lambda a, s: lax.shift_right_logical(a, s)
    leaves = jnp.where(block(row, shift) == block(col, shift), strict, 0.0)
    inverse = jnp.where(row == col, 1.0, 0.0) - leaves
    power, size = leaves, 2
    while size < sub:
        power = _dot(power, power, exact=True)
        inverse = inverse + _dot(inverse, power, exact=True)
        size *= 2
    size = sub
    while size < c:
        pair = block(row, shift + 1) == block(col, shift + 1)
        below = jnp.where(
            jnp.logical_and(pair, block(row, shift) != block(col, shift)),
            strict, 0.0)
        inverse = inverse - _dot(
            _dot(inverse, below, exact=True), inverse, exact=True)
        shift, size = shift + 1, size * 2
    return inverse


def _chunk(q, k, v, g, beta, strict, lower, sees, last, sub: int):
    """What both passes need of a chunk of h heads, a dict: the decays'
    factors (float32), the matmuls' operands as ``kda_chunk_major`` rounds
    them, the triangles A (strict, float32) and B (lower).  q, k, g
    [h, C, dk], v [h, C, dv], beta [h, C, 1], sees, last [C, 1], strict,
    lower [C, C]."""
    h, c, dk = g.shape
    dtype = q.dtype
    n = c // sub
    kf, qf = k.astype(jnp.float32), q.astype(jnp.float32)
    g_cum = _running_sum(g)
    # G at the start of each sub-chunk: the running sum at the last token
    # of the one before, 0 for the first
    starts = [jnp.zeros((h, 1, dk), jnp.float32)] + [
        g_cum[:, a * sub - 1:a * sub] for a in range(1, n)]
    row_decay = jnp.exp(g_cum - jnp.concatenate(
        [jnp.broadcast_to(s, (h, sub, dk)) for s in starts], axis=1))  # <= 1
    col_decay = [jnp.exp(jnp.minimum(s - g_cum, MAX_EXPONENT))
                 for s in starts]
    k_rows, q_rows = (kf * row_decay).astype(dtype), (
        qf * row_decay).astype(dtype)
    lefts = [jnp.concatenate([k_rows[:, a * sub:(a + 1) * sub],
                              q_rows[:, a * sub:(a + 1) * sub]], axis=1)
             for a in range(n)]
    k_cols = [(kf * d).astype(dtype) for d in col_decay]
    products = [_dot(lefts[a], k_cols[a], _NT) for a in range(n)]
    a_mat = jnp.where(strict, jnp.concatenate(
        [p[:, :sub] for p in products], axis=1), 0.0)
    b_mat = jnp.where(lower, jnp.concatenate(
        [p[:, sub:] for p in products], axis=1), 0.0).astype(dtype)
    decay_in = jnp.exp(g_cum)                               # <= 1
    decay_out = jnp.exp(g_cum[:, c - 1:] - g_cum)
    k_in = jnp.where(sees, kf * decay_in, 0.0)
    rhs_plain = jnp.concatenate([v.astype(jnp.float32), k_in], axis=2)
    return dict(
        kf=kf, qf=qf, g_cum=g_cum, starts=starts, row_decay=row_decay,
        col_decay=col_decay, lefts=lefts, k_cols=k_cols, a_mat=a_mat,
        b_mat=b_mat, decay_in=decay_in, decay_out=decay_out,
        q_in=jnp.where(sees, qf * decay_in, 0.0).astype(dtype),
        k_out=jnp.where(last, kf * decay_out, 0.0).astype(dtype),
        carry=jnp.where(sees[c - 1:], decay_in[:, c - 1:], 0.0),  # [h, 1, dk]
        rhs_plain=rhs_plain, rhs=(beta * rhs_plain).astype(dtype))


def _wrote(parts, inverse, state, dv: int):
    """(what the chunk's tokens write [h, C, dv] float32, W [h, C, dk], the
    state as the matmuls take it, Q_in S0 [h, C, dv]) from the chunk's
    parts, its inverse and the state entering it, [h, dv, dk] float32."""
    dtype = parts["rhs"].dtype
    c = inverse.shape[-1]
    solved = _dot(inverse.astype(dtype), parts["rhs"])
    w = solved[..., dv:].astype(dtype)
    state = state.astype(dtype)
    read = _dot(jnp.concatenate([w, parts["q_in"]], axis=1), state, _NT)
    return solved[..., :dv] - read[:, :c], w, state, read[:, c:]


def _column(block, lane, head):
    """Column ``head`` of a [rows, H] block as [rows, 1]; ``lane`` is the
    block's lane index."""
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def _load(ref, width: int, heads: int):
    """[h, C, width] of a [1, C, h·width] block."""
    return jnp.stack(
        [ref[0, :, j * width:(j + 1) * width] for j in range(heads)])


def _store(ref, value):
    """The reverse of :func:`_load`, in the block's type."""
    width = value.shape[-1]
    for j in range(value.shape[0]):
        ref[0, :, j * width:(j + 1) * width] = value[j].astype(ref.dtype)


def _step(q_ref, k_ref, v_ref, g_ref, beta_ref, seg_col_ref, seg_row_ref,
          sees_ref, last_ref, heads: int, dk: int, dv: int):
    """(the first head of this grid step, its lane mask over a [C, H]
    block, the heads' betas [h, C, 1], the documents' masks, the chunk's
    parts)."""
    first = pl.program_id(2) * heads
    c = q_ref.shape[1]
    row, col = _iotas(c)
    same = seg_col_ref[0] == seg_row_ref[0, 0]
    betas = beta_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    beta = jnp.stack([_column(betas, lane, first + j) for j in range(heads)])
    masks = (jnp.logical_and(same, row > col),
             jnp.logical_and(same, row >= col),
             sees_ref[0] != 0, last_ref[0] != 0)
    parts = _chunk(
        _load(q_ref, dk, heads), _load(k_ref, dk, heads),
        _load(v_ref, dv, heads), _load(g_ref, dk, heads), beta, *masks, SUB)
    return first, lane, beta, masks, parts


def _forward_kernel(*refs, heads: int, dk: int, dv: int, keep: bool):
    """refs: the nine operands of ``_specs``, o, with ``keep`` the states
    and the inverses, the scratch of every head's running state."""
    o_ref, state_ref = refs[9], refs[-1]
    first, _, beta, _, parts = _step(*refs[:9], heads, dk, dv)
    mine = pl.ds(first, heads)

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[mine] = jnp.zeros((heads, dv, dk), jnp.float32)

    dtype = parts["rhs"].dtype
    inverse = _inverse_unit_lower(beta * parts["a_mat"], SUB)
    state = state_ref[mine]
    wrote, _, _, read = _wrote(parts, inverse, state, dv)
    wrote = wrote.astype(dtype)
    _store(o_ref, read + _dot(parts["b_mat"], wrote))
    state_ref[mine] = state * parts["carry"] + _dot(
        wrote, parts["k_out"], _TN)
    if keep:
        refs[10][0, 0] = state
        refs[11][0, 0] = inverse


def _backward_kernel(*refs, heads: int, dk: int, dv: int):
    """refs: the nine operands of ``_specs``, the states, the inverses and
    o's cotangent, the five gradients, the scratch of every head's dS."""
    states_ref, inverse_ref, do_ref = refs[9:12]
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref = refs[12:]
    first, lane, beta, (strict, lower, sees, last), p = _step(
        *refs[:9], heads, dk, dv)
    mine = pl.ds(first, heads)
    c, sub = refs[0].shape[1], SUB
    n = c // sub

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[mine] = jnp.zeros((heads, dv, dk), jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dbeta_ref[0] = jnp.zeros(dbeta_ref.shape[1:], jnp.float32)

    token = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    dtype = p["rhs"].dtype
    kf, qf = p["kf"], p["qf"]
    inverse = inverse_ref[0, 0]
    state_in = states_ref[0, 0]
    wrote, w, state, _ = _wrote(p, inverse, state_in, dv)
    wrote = wrote.astype(dtype)
    do = _load(do_ref, dv, heads).astype(dtype)
    dstate = dstate_ref[mine]                               # d S1, [h, dv, dk]
    dstate_op = dstate.astype(dtype)

    dwrote = (_dot(p["b_mat"], do, _TN)
              + _dot(p["k_out"], dstate_op, _NT))             # [h, C, dv]
    db_mat = jnp.where(lower, _dot(do, wrote, _NT), 0.0)
    stacked = jnp.concatenate([do, -dwrote.astype(dtype)], axis=1)
    through_state = _dot(stacked, state)                    # [h, 2C, dk]
    dq_in, dw = through_state[:, :c], through_state[:, c:]
    dk_out = _dot(wrote, dstate_op)                         # [h, C, dk]
    dcarry = jnp.sum(dstate * state_in, axis=1, keepdims=True)
    dstate_ref[mine] = dstate * p["carry"] + _dot(
        stacked, jnp.concatenate([p["q_in"], w], axis=1), _TN)

    # [U | W] = X rhs,  X = (I + Diag(beta) A)^-1
    dsolved = jnp.concatenate([dwrote, dw], axis=2).astype(dtype)
    drhs = _dot(inverse.astype(dtype), dsolved, _TN)        # [h, C, dv + dk]
    dinverse = _dot(dsolved, p["rhs"], _NT)                 # [h, C, C]
    dstrict = jnp.where(strict, -_dot(
        _dot(inverse, dinverse, _TN, exact=True), inverse, _NT, exact=True),
        0.0)
    da_mat = beta * dstrict
    dbeta = (jnp.sum(dstrict * p["a_mat"], axis=2, keepdims=True)
             + jnp.sum(drhs * p["rhs_plain"], axis=2, keepdims=True))
    dbetas = dbeta_ref[0]
    for j in range(heads):
        dbetas = jnp.where(lane == first + j, dbeta[j], dbetas)
    dbeta_ref[0] = dbetas
    drhs = beta * drhs
    _store(dv_ref, drhs[..., :dv])
    dk_in = drhs[..., dv:]

    # the triangles: rows of sub-chunk a are lefts[a] k_cols[a]^T
    dlefts, dstarts = [], []
    dg_cum, dk_total = jnp.zeros((heads, c, dk), jnp.float32), 0.0
    for a in range(n):
        rows = slice(a * sub, (a + 1) * sub)
        both = jnp.concatenate([da_mat[:, rows], db_mat[:, rows]], axis=1
                               ).astype(dtype)              # [h, 2 sub, C]
        dlefts.append(_dot(both, p["k_cols"][a]))            # [h, 2 sub, dk]
        dcol = _dot(both, p["lefts"][a], _TN)               # [h, C, dk]
        decay = p["col_decay"][a]
        dk_total = dk_total + dcol * decay
        exponent = jnp.where(
            p["starts"][a] - p["g_cum"] < MAX_EXPONENT,
            dcol * kf * decay, 0.0)
        dg_cum = dg_cum - exponent
        dstarts.append(jnp.sum(exponent, axis=1, keepdims=True))
    dk_rows = jnp.concatenate([d[:, :sub] for d in dlefts], axis=1)
    dq_rows = jnp.concatenate([d[:, sub:] for d in dlefts], axis=1)
    exponent = (dk_rows * kf + dq_rows * qf) * p["row_decay"]
    dg_cum = dg_cum + exponent
    for a in range(1, n):
        # G at a sub-chunk's start is the running sum one token before
        dstart = dstarts[a] - jnp.sum(
            exponent[:, a * sub:(a + 1) * sub], axis=1, keepdims=True)
        dg_cum = dg_cum + jnp.where(token == a * sub - 1, dstart, 0.0)
    out_exponent = jnp.where(last, dk_out * kf, 0.0) * p["decay_out"]
    dg_cum = dg_cum - out_exponent + jnp.where(
        sees, dq_in * qf + dk_in * kf, 0.0) * p["decay_in"]
    dg_cum = dg_cum + jnp.where(
        token == c - 1,
        jnp.sum(out_exponent, axis=1, keepdims=True) + dcarry * p["carry"],
        0.0)
    _store(dg_ref, _running_sum(dg_cum, reverse=True))
    _store(dq_ref, dq_rows * p["row_decay"]
           + jnp.where(sees, dq_in * p["decay_in"], 0.0))
    _store(dk_ref, dk_rows * p["row_decay"] + dk_total
           + jnp.where(sees, dk_in * p["decay_in"], 0.0)
           + jnp.where(last, dk_out * p["decay_out"], 0.0))


def _marks(seg):
    """The documents' marks of [B, T] segment ids, T whole chunks: the ids
    a token a row and a row a chunk, whether a token sees the state
    entering its chunk (no document began in the chunk up to and including
    it) and whether it is of the chunk's last document."""
    b, t = seg.shape
    chunks = seg.reshape(b, t // CHUNK, CHUNK)
    before = jnp.concatenate(
        [chunks[:, :1, :1], chunks[:, :-1, -1:]], axis=1)
    starts = chunks != jnp.concatenate([before, chunks[..., :-1]], axis=-1)
    sees = jnp.cumsum(starts, axis=-1) == 0
    last = chunks == chunks[..., -1:]
    column = lambda a: a.astype(jnp.int32).reshape(b, t, 1)
    return column(seg), chunks[:, :, None, :], column(sees), column(last)


def _specs(heads: int, dk: int, dv: int, at):
    """(block specs of q, k, v, g, beta and the four marks; the spec of a
    [B, T, H·dv] array), for a grid (row, chunk, head slab) whose chunk
    ``at(c)`` is."""
    n = _heads_a_step(heads)
    keys = pl.BlockSpec((1, CHUNK, n * dk), lambda b, c, h: (b, at(c), h))
    values = pl.BlockSpec((1, CHUNK, n * dv), lambda b, c, h: (b, at(c), h))
    column = pl.BlockSpec((1, CHUNK, 1), lambda b, c, h: (b, at(c), 0))
    return [
        keys, keys, values, keys,
        pl.BlockSpec((1, CHUNK, heads), lambda b, c, h: (b, at(c), 0)),
        column,
        pl.BlockSpec((1, 1, 1, CHUNK), lambda b, c, h: (b, at(c), 0, 0)),
        column, column], keys, values


def _kept_specs(heads: int, dk: int, dv: int, at):
    """Block specs of what the backward keeps: a state and an inverse a
    chunk and head."""
    n = _heads_a_step(heads)
    whole = lambda b, c, h: (b, at(c), h, 0, 0)
    return [pl.BlockSpec((1, 1, n, dv, dk), whole),
            pl.BlockSpec((1, 1, n, CHUNK, CHUNK), whole)]


_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))


def _forward(q, k, v, g, beta, seg, heads: int, keep: bool):
    b, t, _ = q.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    n = t // CHUNK
    step = _heads_a_step(heads)
    specs, _, values = _specs(heads, dk, dv, lambda c: c)
    out_shape = [pk._sds((b, t, heads * dv), jnp.float32, q)]
    out_specs = [values]
    if keep:
        out_shape += [pk._sds((b, n, heads, dv, dk), jnp.float32, q),
                      pk._sds((b, n, heads, CHUNK, CHUNK), jnp.float32, q)]
        out_specs += _kept_specs(heads, dk, dv, lambda c: c)
    return pl.pallas_call(
        functools.partial(_forward_kernel, heads=step, dk=dk, dv=dv,
                          keep=keep),
        grid=(b, n, heads // step),
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_ORDER, interpret=pk._interpret(),
    )(q, k, v, g, beta, *_marks(seg))


def _backward(q, k, v, g, beta, seg, states, inverses, do, heads: int):
    b, t, _ = q.shape
    dk, dv = q.shape[-1] // heads, v.shape[-1] // heads
    n = t // CHUNK
    step = _heads_a_step(heads)
    at = lambda c: n - 1 - c
    specs, keys, values = _specs(heads, dk, dv, at)
    return pl.pallas_call(
        functools.partial(_backward_kernel, heads=step, dk=dk, dv=dv),
        grid=(b, n, heads // step),
        in_specs=specs + _kept_specs(heads, dk, dv, at) + [values],
        out_specs=[keys, keys, values, keys, specs[4]],
        out_shape=[pk._sds(q.shape, q.dtype, q), pk._sds(k.shape, k.dtype, q),
                   pk._sds(v.shape, v.dtype, q),
                   pk._sds(g.shape, jnp.float32, q),
                   pk._sds(beta.shape, jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_ORDER, interpret=pk._interpret(),
    )(q, k, v, g, beta, *_marks(seg), states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _delta_rule(q, k, v, g, beta, seg, heads: int):
    return _forward(q, k, v, g, beta, seg, heads, keep=False)[0]


def _delta_rule_fwd(q, k, v, g, beta, seg, heads: int):
    out, states, inverses = _forward(q, k, v, g, beta, seg, heads, keep=True)
    return out, (q, k, v, g, beta, seg, states, inverses)


def _delta_rule_bwd(heads: int, kept, do):
    grads = _backward(*kept, do.astype(jnp.float32), heads)
    # integer segment ids carry a float0 (empty) cotangent
    return tuple(grads) + (np.zeros(kept[5].shape, jax.dtypes.float0),)


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def delta_rule(q, k, v, g, beta, segment_ids=None):
    """The chunked delta rule by the kernels: q, k [B, T, H·dk] and v
    [B, T, H·dv] of one type (the matmuls' operands'), g as q and beta
    [B, T, H] float32, ``segment_ids`` [B, T] or None -> o [B, T, H·dv]
    float32.  T is padded to whole chunks with tokens that write nothing
    and decay nothing."""
    b, t, _ = q.shape
    pad = -t % CHUNK
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    out = _delta_rule(q, k, v, g.astype(jnp.float32),
                      beta.astype(jnp.float32), seg, beta.shape[-1])
    return out[:, :t]


# ---------------------------------------------------------------------------
# The per-head norms around the core, on [B, T, H·d] as well: a reduction
# over a head's d channels in XLA goes through [B, T, H, d], which under the
# (8, 128) tiling is another array (a 67 MB copy each way, each pass).
# ---------------------------------------------------------------------------

_NORM_ROWS = 256  # tokens of a norm kernel's block


def _norm_grid(x, heads: int):
    """(grid, the block spec of a [B, T, H·d] array, of a [B, T, H] one,
    heads a block, tokens a block) of the norm kernels: T padded to whole
    blocks by the caller."""
    b, t, lanes = x.shape
    step = _heads_a_step(heads)
    rows = min(_NORM_ROWS, t)
    wide = pl.BlockSpec((1, rows, step * (lanes // heads)),
                        lambda b_, r, h: (b_, r, h))
    narrow = pl.BlockSpec((1, rows, heads), lambda b_, r, h: (b_, r, 0))
    return (b, t // rows, heads // step), wide, narrow, step


def _unit_kernel(x_ref, y_ref, *, heads: int, scale: float):
    width = x_ref.shape[-1] // heads
    for j in range(heads):
        lanes = slice(j * width, (j + 1) * width)
        x = x_ref[0, :, lanes]
        inverse = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-6)
        y_ref[0, :, lanes] = (x * inverse * scale).astype(y_ref.dtype)


def _unit_grad_kernel(x_ref, dy_ref, dx_ref, *, heads: int, scale: float):
    width = x_ref.shape[-1] // heads
    for j in range(heads):
        lanes = slice(j * width, (j + 1) * width)
        x, dy = x_ref[0, :, lanes], dy_ref[0, :, lanes].astype(jnp.float32)
        inverse = lax.rsqrt(jnp.sum(x * x, axis=1, keepdims=True) + 1e-6)
        along = jnp.sum(dy * x, axis=1, keepdims=True)
        dx_ref[0, :, lanes] = scale * inverse * (
            dy - x * (inverse * inverse * along))


def _rows_padded(arrays, rows: int):
    pad = -arrays[0].shape[1] % rows
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays)


def _head_unit(x, heads: int, scale: float, dtype):
    t = x.shape[1]
    (x,) = _rows_padded((x,), min(_NORM_ROWS, t))
    grid, wide, _, step = _norm_grid(x, heads)
    return pl.pallas_call(
        functools.partial(_unit_kernel, heads=step, scale=scale),
        grid=grid, in_specs=[wide], out_specs=wide,
        out_shape=pk._sds(x.shape, dtype, x), interpret=pk._interpret(),
    )(x)[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def head_unit(x, heads: int, scale: float, dtype):
    """``x / |x|`` a head times ``scale``: x [B, T, H·d] float32 ->
    ``dtype``; |x|^2 is the sum of squares over the head's d channels plus
    1e-6."""
    return _head_unit(x, heads, scale, dtype)


def _head_unit_fwd(x, heads, scale, dtype):
    return _head_unit(x, heads, scale, dtype), x


def _head_unit_bwd(heads, scale, dtype, x, dy):
    t = x.shape[1]
    x, dy = _rows_padded((x, dy), min(_NORM_ROWS, t))
    grid, wide, _, step = _norm_grid(x, heads)
    return (pl.pallas_call(
        functools.partial(_unit_grad_kernel, heads=step, scale=scale),
        grid=grid, in_specs=[wide, wide], out_specs=wide,
        out_shape=pk._sds(x.shape, jnp.float32, x),
        interpret=pk._interpret(),
    )(x, dy)[:, :t],)


head_unit.defvjp(_head_unit_fwd, _head_unit_bwd)


def _gated(x_ref, weight_ref, gate_ref, heads: int, eps: float):
    """Per head of the block: (lanes, x / rms(x), 1 / rms(x), weight, the
    head's gate [rows, 1], its lane mask over the [rows, H] block)."""
    width = x_ref.shape[-1] // heads
    first = pl.program_id(2) * heads
    gates = gate_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, gates.shape, 1)
    for j in range(heads):
        lanes = slice(j * width, (j + 1) * width)
        x = x_ref[0, :, lanes]
        inverse = lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
        yield (lanes, x * inverse, inverse, weight_ref[...],
               _column(gates, lane, first + j), lane == first + j)


def _rms_gate_kernel(x_ref, weight_ref, gate_ref, y_ref, *, heads: int,
                     eps: float):
    for lanes, normed, _, weight, gate, _ in _gated(
            x_ref, weight_ref, gate_ref, heads, eps):
        y_ref[0, :, lanes] = (normed * weight * gate).astype(y_ref.dtype)


def _rms_gate_grad_kernel(x_ref, weight_ref, gate_ref, dy_ref, dx_ref,
                          dweight_ref, dgate_ref, *, heads: int, eps: float):
    @pl.when(jnp.logical_and(
        pl.program_id(0) == 0,
        jnp.logical_and(pl.program_id(1) == 0, pl.program_id(2) == 0)))
    def _():
        dweight_ref[...] = jnp.zeros(dweight_ref.shape, jnp.float32)

    @pl.when(pl.program_id(2) == 0)
    def _():
        dgate_ref[0] = jnp.zeros(dgate_ref.shape[1:], jnp.float32)

    dgates, dweight = dgate_ref[0], dweight_ref[...]
    for lanes, normed, inverse, weight, gate, mask in _gated(
            x_ref, weight_ref, gate_ref, heads, eps):
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        through = dy * normed
        dweight = dweight + jnp.sum(through * gate, axis=0, keepdims=True)
        dgates = jnp.where(
            mask, jnp.sum(through * weight, axis=1, keepdims=True), dgates)
        dnormed = dy * weight * gate
        dx_ref[0, :, lanes] = inverse * (dnormed - normed * jnp.mean(
            dnormed * normed, axis=1, keepdims=True))
    dgate_ref[0], dweight_ref[...] = dgates, dweight


def _weight_spec(weight):
    return pl.BlockSpec((1, weight.shape[0]), lambda b_, r, h: (0, 0))


def _head_rms_gate(x, weight, gate, eps: float, dtype):
    t, heads = x.shape[1], gate.shape[-1]
    x, gate = _rows_padded((x, gate), min(_NORM_ROWS, t))
    grid, wide, narrow, step = _norm_grid(x, heads)
    return pl.pallas_call(
        functools.partial(_rms_gate_kernel, heads=step, eps=eps),
        grid=grid, in_specs=[wide, _weight_spec(weight), narrow],
        out_specs=wide, out_shape=pk._sds(x.shape, dtype, x),
        interpret=pk._interpret(),
    )(x, weight[None], gate)[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def head_rms_gate(x, weight, gate, eps: float, dtype):
    """RMSNorm over each head's d channels, times ``weight`` [d] and the
    head's ``gate``: x [B, T, H·d] float32, gate [B, T, H] float32 ->
    ``dtype`` [B, T, H·d]."""
    return _head_rms_gate(x, weight, gate, eps, dtype)


def _head_rms_gate_fwd(x, weight, gate, eps, dtype):
    return _head_rms_gate(x, weight, gate, eps, dtype), (x, weight, gate)


def _head_rms_gate_bwd(eps, dtype, kept, dy):
    x, weight, gate = kept
    t, heads = x.shape[1], gate.shape[-1]
    x, gate, dy = _rows_padded((x, gate, dy), min(_NORM_ROWS, t))
    grid, wide, narrow, step = _norm_grid(x, heads)
    dx, dweight, dgate = pl.pallas_call(
        functools.partial(_rms_gate_grad_kernel, heads=step, eps=eps),
        grid=grid, in_specs=[wide, _weight_spec(weight), narrow, wide],
        out_specs=[wide, _weight_spec(weight), narrow],
        out_shape=[pk._sds(x.shape, jnp.float32, x),
                   pk._sds((1,) + weight.shape, jnp.float32, x),
                   pk._sds(gate.shape, jnp.float32, x)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=pk._interpret(),
    )(x, weight[None], gate, dy)
    return dx[:, :t], dweight[0], dgate[:, :t]


head_rms_gate.defvjp(_head_rms_gate_fwd, _head_rms_gate_bwd)
