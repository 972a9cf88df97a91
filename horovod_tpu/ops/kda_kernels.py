"""The chunked delta rule of ``ops/kda.py`` as a pair of Pallas kernels,
forward and a hand-written backward under one ``jax.custom_vjp``, on the
projections' own layout: q, k, g ``[B, T, H·dk]``, v and o ``[B, T, H·dv]``,
beta ``[B, T, H]``.

A grid step owns one chunk of ``CHUNK`` tokens by one slab of heads'
lanes (``_slab``: ``_heads_a_step`` heads where their keys and values are
whole 128-lane slabs, else every head, the block as wide as the array, so
that heads of 96 or 192 lanes are read where the projection left them);
inside a slab the heads are taken ``_heads_a_step`` at a time, each
group's lanes a static slice of the block.  The chunks are walked in order
(in reverse by the backward) with every head's state, ``[dv, dk]``
float32, in a VMEM scratch.  A chunk's triangles, the inverse, U, W and what the tokens wrote
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

**One decay a head** (``g`` ``[B, T, H]``, Gated DeltaNet's, arXiv:
2412.06464): ``exp(G_r - G_i)`` is then one ``[C, C]`` matrix D, formed
exactly and at most 1 on the triangle, so ``A = (K K^T) * D`` and ``B =
(Q K^T) * D`` are one matmul each and g has no lower bound (no sub-chunk
reference points).  Backward: with ``P_A = dA * D``, ``P_B = dB * D``,
``dQ = P_B K``, ``dK = P_A K + P_A^T K + P_B^T Q`` and ``dG = rowsum(E) -
colsum(E)``, ``E = (dA * K K^T + dB * Q K^T) * D``; the decays of the
state, of K_in, Q_in and K_out are those of the per-channel case summed
over the channels.

**Value heads that share a key head** (``group`` > 1, with one decay a
head: Qwen3-Next's Gated DeltaNet): q and k hold H / group heads,
``[B, T, (H / group)·dk]``, and value head h reads key head ``h // group``
where it lies, as the flash kernels read a key/value head for its query
heads (``pallas_kernels._slab_of``): a grid step's slab holds whole groups
and its block of q and k the slab's key heads, so no repeated copy of q or
k is made.  A key head's dq and dk are the sums of its value heads' parts,
taken in the grid step that owns them all.
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
    Pallas is interpreted, a multiple of 32 lanes on the chip (compiled
    for a v5e at 64, 96, 128, 192 and 256: ``tests/test_tpu_compile.py``);
    a width that is no whole 128-lane slab is a static slice of a block
    as wide as all the heads (``_slab``)."""
    return pk._interpret() or width % 32 == 0


def _heads_a_step(heads: int, group: int = 1) -> int:
    """Heads a grid step owns, four where they divide, whole groups of
    ``group`` value heads that share a key head.  They are a batch axis of
    the body, so each of its small matmuls is issued for every head before
    the next one that depends on it: a head's chunk alone is a chain of
    some forty dependent MXU round trips that nothing hides (PERF.md, PR
    33: 4.7 ms a layer forward at one head, 2.1 at four; eight need more
    VMEM than a kernel gets unasked)."""
    return next(n for n in (4, 3, 2, 1, group)
                if heads % n == 0 and n % group == 0)


def _slab(heads: int, *widths: int, group: int = 1) -> int:
    """Heads whose lanes a grid step's block holds: ``_heads_a_step``
    where that many heads of each width are whole 128-lane slabs (the
    first width is a key's: ``group`` value heads share one), else all of
    them (a block as wide as the array is the one Mosaic takes for any
    width)."""
    n = _heads_a_step(heads, group)
    if all(n // (group if i == 0 else 1) * w % pk._LANES == 0
           for i, w in enumerate(widths)):
        return n
    return heads


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


def _row(x):
    """[h, C, 1] float32 as [h, 1, C], exactly: its bfloat16 parts times
    the identity, each sum one exact term."""
    h, c, _ = x.shape
    row, col = _iotas(c)
    eye = jnp.broadcast_to(
        jnp.where(row == col, 1.0, 0.0).astype(jnp.bfloat16), (h, c, c))
    hi, mid, low = _parts(x)
    parts = _dot(jnp.concatenate([low, mid, hi], axis=2), eye, _TN)
    return parts[:, 0:1] + parts[:, 1:2] + parts[:, 2:3]


def _chunk_per_head(q, k, v, g, beta, strict, lower, sees, last):
    """:func:`_chunk` for one decay a head, g [h, C, 1]: the decays'
    matrix D = exp(G_r - G_i) [h, C, C] (exact, <= 1 where it is kept),
    A and B from one matmul each.  The running sum is made as wide as a
    key, so that the decays of K_in, Q_in, K_out and of the state are
    the per-channel case's arrays (Mosaic cannot broadcast [h, 1, 1] over
    a state's sublanes and lanes at once)."""
    h, c, dk = q.shape
    dtype = q.dtype
    kf, qf = k.astype(jnp.float32), q.astype(jnp.float32)
    g_cum = _running_sum(jnp.broadcast_to(g, (h, c, dk)))   # [h, C, dk]
    column = g_cum[:, :, :1]
    decay = jnp.exp(jnp.minimum(column - _row(column), 0.0))
    kk, qk = _dot(k, k, _NT), _dot(q, k, _NT)
    decay_in = jnp.exp(g_cum)                                # <= 1
    decay_out = jnp.exp(g_cum[:, c - 1:] - g_cum)
    k_in = jnp.where(sees, kf * decay_in, 0.0)
    rhs_plain = jnp.concatenate([v.astype(jnp.float32), k_in], axis=2)
    return dict(
        q=q, k=k, kf=kf, qf=qf, decay=decay, kk=kk, qk=qk,
        a_mat=jnp.where(strict, kk * decay, 0.0),
        b_mat=jnp.where(lower, qk * decay, 0.0).astype(dtype),
        decay_in=decay_in, decay_out=decay_out,
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


def _load(ref, width: int, heads: int, offset: int = 0, group: int = 1):
    """[h, C, width] of heads ``offset`` to ``offset + h`` of a
    [1, C, slab·width] block; with ``group``, head j's is head ``j //
    group`` of the block (a key head shared by value heads)."""
    return jnp.stack([ref[0, :, j // group * width:(j // group + 1) * width]
                      for j in range(offset, offset + heads)])


def _store(ref, value, offset: int = 0, group: int = 1):
    """The reverse of :func:`_load`, in the block's type: with ``group``
    the sum of each ``group`` consecutive heads' parts goes to their key
    head."""
    if group > 1:
        value = jnp.stack([sum(value[j + m] for m in range(group))
                           for j in range(0, value.shape[0], group)])
        offset //= group
    width = value.shape[-1]
    for j in range(value.shape[0]):
        at = (offset + j) * width
        ref[0, :, at:at + width] = value[j].astype(ref.dtype)


def _put(ref, value, offset: int):
    """A group's [h, ...] into a [1, 1, slab, ...] block."""
    if ref.shape[2] == value.shape[0]:
        ref[0, 0] = value
    else:
        ref[0, 0, offset:offset + value.shape[0]] = value


def _take(ref, heads: int, offset: int):
    """The reverse of :func:`_put`."""
    if ref.shape[2] == heads:
        return ref[0, 0]
    return ref[0, 0, offset:offset + heads]


def _step(q_ref, k_ref, v_ref, g_ref, beta_ref, seg_col_ref, seg_row_ref,
          sees_ref, last_ref, heads: int, dk: int, dv: int, slab: int,
          offset: int, per_head: bool, group: int):
    """(the first head of the group of ``heads`` that starts ``offset``
    heads into this grid step's slab, its lane mask over a [C, H] block,
    the heads' betas [h, C, 1], the documents' masks, the chunk's parts);
    ``per_head``: g is a [C, H] block too, one decay a head; ``group``
    value heads read each key head of q's and k's blocks."""
    first = pl.program_id(2) * slab
    if offset:
        first = first + offset
    c = q_ref.shape[1]
    row, col = _iotas(c)
    same = seg_col_ref[0] == seg_row_ref[0, 0]
    betas = beta_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    beta = jnp.stack([_column(betas, lane, first + j) for j in range(heads)])
    masks = (jnp.logical_and(same, row > col),
             jnp.logical_and(same, row >= col),
             sees_ref[0] != 0, last_ref[0] != 0)
    q, k, v = (_load(ref, width, heads, offset, n) for ref, width, n in (
        (q_ref, dk, group), (k_ref, dk, group), (v_ref, dv, 1)))
    if per_head:
        gates = g_ref[0]
        g = jnp.stack([_column(gates, lane, first + j) for j in range(heads)])
        parts = _chunk_per_head(q, k, v, g, beta, *masks)
    else:
        parts = _chunk(q, k, v, _load(g_ref, dk, heads, offset), beta,
                       *masks, SUB)
    return first, lane, beta, masks, parts


def _forward_kernel(*refs, slab: int, heads: int, dk: int, dv: int,
                    keep: bool, per_head: bool, group: int):
    """refs: the nine operands of ``_specs``, o, with ``keep`` the states
    and the inverses, the scratch of every head's running state.  The
    slab's heads are taken ``heads`` at a time."""
    o_ref, state_ref = refs[9], refs[-1]
    for offset in range(0, slab, heads):
        first, _, beta, _, parts = _step(
            *refs[:9], heads, dk, dv, slab, offset, per_head, group)
        mine = pl.ds(first, heads)

        @pl.when(pl.program_id(1) == 0)
        def _():
            state_ref[mine] = jnp.zeros((heads, dv, dk), jnp.float32)

        dtype = parts["rhs"].dtype
        inverse = _inverse_unit_lower(beta * parts["a_mat"], SUB)
        state = state_ref[mine]
        wrote, _, _, read = _wrote(parts, inverse, state, dv)
        wrote = wrote.astype(dtype)
        _store(o_ref, read + _dot(parts["b_mat"], wrote), offset)
        state_ref[mine] = state * parts["carry"] + _dot(
            wrote, parts["k_out"], _TN)
        if keep:
            _put(refs[10], state, offset)
            _put(refs[11], inverse, offset)


def _backward_kernel(*refs, slab: int, heads: int, dk: int, dv: int,
                     per_head: bool, group: int):
    """refs: the nine operands of ``_specs``, the states, the inverses and
    o's cotangent, the five gradients, the scratch of every head's dS."""
    for offset in range(0, slab, heads):
        _backward_group(refs, slab, heads, dk, dv, offset, per_head, group)


def _backward_group(refs, slab: int, heads: int, dk: int, dv: int,
                    offset: int, per_head: bool, group: int):
    states_ref, inverse_ref, do_ref = refs[9:12]
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref = refs[12:]
    first, lane, beta, (strict, lower, sees, last), p = _step(
        *refs[:9], heads, dk, dv, slab, offset, per_head, group)
    mine = pl.ds(first, heads)
    c, sub = refs[0].shape[1], SUB
    n = c // sub

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[mine] = jnp.zeros((heads, dv, dk), jnp.float32)

    if not offset:
        @pl.when(pl.program_id(2) == 0)
        def _():
            dbeta_ref[0] = jnp.zeros(dbeta_ref.shape[1:], jnp.float32)
            if per_head:
                dg_ref[0] = jnp.zeros(dg_ref.shape[1:], jnp.float32)

    token = lax.broadcasted_iota(jnp.int32, (c, dk), 0)
    dtype = p["rhs"].dtype
    kf, qf = p["kf"], p["qf"]
    inverse = _take(inverse_ref, heads, offset)
    state_in = _take(states_ref, heads, offset)
    wrote, w, state, _ = _wrote(p, inverse, state_in, dv)
    wrote = wrote.astype(dtype)
    do = _load(do_ref, dv, heads, offset).astype(dtype)
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
    _store(dv_ref, drhs[..., :dv], offset)
    dk_in = drhs[..., dv:]
    if per_head:
        _per_head_grads(p, da_mat, db_mat, dq_in, dk_in, dk_out, dcarry,
                        sees, last, dq_ref, dk_ref, dg_ref, lane, first,
                        offset, group)
        return

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
    _store(dg_ref, _running_sum(dg_cum, reverse=True), offset)
    _store(dq_ref, dq_rows * p["row_decay"]
           + jnp.where(sees, dq_in * p["decay_in"], 0.0), offset)
    _store(dk_ref, dk_rows * p["row_decay"] + dk_total
           + jnp.where(sees, dk_in * p["decay_in"], 0.0)
           + jnp.where(last, dk_out * p["decay_out"], 0.0), offset)


def _per_head_grads(p, da_mat, db_mat, dq_in, dk_in, dk_out, dcarry, sees,
                    last, dq_ref, dk_ref, dg_ref, lane, first, offset: int,
                    group: int):
    """The backward's last part for one decay a head (the module's
    docstring): q's and k's gradients through A, B and the decays, summed
    over the ``group`` value heads of a key head, and g's, [C, 1] a head,
    into its column of the [C, H] block."""
    h, c, _ = da_mat.shape
    dtype = p["q"].dtype
    kf, qf = p["kf"], p["qf"]
    # P_A = dA * D and P_B = dB * D, stacked along the rows
    both = jnp.concatenate(
        [da_mat * p["decay"], db_mat * p["decay"]], axis=1).astype(dtype)
    rows = _dot(both, p["k"])                               # [P_A K; P_B K]
    cols = _dot(both, jnp.concatenate([p["k"], p["q"]], axis=1), _TN)
    exponent = (da_mat * p["kk"] + db_mat * p["qk"]) * p["decay"]
    ones = jnp.ones((h, c, 1), jnp.float32)
    dg_cum = (jnp.sum(exponent, axis=2, keepdims=True)
              - _dot(exponent, ones, _TN, exact=True))       # [h, C, 1]
    out_exponent = jnp.where(last, dk_out * kf, 0.0) * p["decay_out"]
    dg_cum = dg_cum + jnp.sum(
        jnp.where(sees, dq_in * qf + dk_in * kf, 0.0) * p["decay_in"]
        - out_exponent, axis=2, keepdims=True)
    token = lax.broadcasted_iota(jnp.int32, (c, 1), 0)
    dg_cum = dg_cum + jnp.where(
        token == c - 1,
        jnp.sum(jnp.sum(out_exponent, axis=2, keepdims=True), axis=1,
                keepdims=True)
        + jnp.sum(dcarry * p["carry"], axis=2, keepdims=True), 0.0)
    dg = _running_sum(dg_cum, reverse=True)
    dgs = dg_ref[0]
    for j in range(h):
        dgs = jnp.where(lane == first + j, dg[j], dgs)
    dg_ref[0] = dgs
    _store(dq_ref, rows[:, c:]
           + jnp.where(sees, dq_in * p["decay_in"], 0.0), offset, group)
    _store(dk_ref, rows[:, :c] + cols
           + jnp.where(sees, dk_in * p["decay_in"], 0.0)
           + jnp.where(last, dk_out * p["decay_out"], 0.0), offset, group)


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


def _specs(heads: int, dk: int, dv: int, at, per_head: bool,
           group: int = 1):
    """(block specs of q, k, v, g, beta and the four marks; the spec of a
    [B, T, H·dv] array), for a grid (row, chunk, head slab) whose chunk
    ``at(c)`` is; g is a [B, T, H] array where ``per_head``; q and k hold
    a key head for every ``group`` value heads."""
    n = _slab(heads, dk, dv, group=group)
    keys = pl.BlockSpec((1, CHUNK, n // group * dk),
                        lambda b, c, h: (b, at(c), h))
    values = pl.BlockSpec((1, CHUNK, n * dv), lambda b, c, h: (b, at(c), h))
    column = pl.BlockSpec((1, CHUNK, 1), lambda b, c, h: (b, at(c), 0))
    by_head = pl.BlockSpec((1, CHUNK, heads), lambda b, c, h: (b, at(c), 0))
    return [
        keys, keys, values, by_head if per_head else keys, by_head,
        column,
        pl.BlockSpec((1, 1, 1, CHUNK), lambda b, c, h: (b, at(c), 0, 0)),
        column, column], keys, values


def _kept_specs(heads: int, dk: int, dv: int, at, group: int = 1):
    """Block specs of what the backward keeps: a state and an inverse a
    chunk and head."""
    n = _slab(heads, dk, dv, group=group)
    whole = lambda b, c, h: (b, at(c), h, 0, 0)
    return [pl.BlockSpec((1, 1, n, dv, dk), whole),
            pl.BlockSpec((1, 1, n, CHUNK, CHUNK), whole)]


_ORDER = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"))
# A block of every head (``_slab``) holds ten times a slab's operands and
# each head's state twice, in and kept: more than a kernel gets unasked.
_WHOLE = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=96 * 2 ** 20)


def _layout(q, v, g, heads: int, group: int):
    """(dk, dv, the slab, the heads taken at a time, whether g is one a
    head, the compiler's parameters) of ``heads`` value heads, ``group``
    of them a key head."""
    dk, dv = q.shape[-1] // (heads // group), v.shape[-1] // heads
    slab = _slab(heads, dk, dv, group=group)
    per_head = g.shape[-1] == heads
    if group > 1 and not per_head:
        raise ValueError("value heads share a key head with one decay a "
                         "head only")
    return (dk, dv, slab, _heads_a_step(slab, group), per_head,
            _ORDER if slab == _heads_a_step(heads, group) else _WHOLE)


def _forward(q, k, v, g, beta, seg, heads: int, keep: bool, group: int):
    b, t, _ = q.shape
    dk, dv, slab, taken, per_head, params = _layout(q, v, g, heads, group)
    n = t // CHUNK
    specs, _, values = _specs(heads, dk, dv, lambda c: c, per_head, group)
    out_shape = [pk._sds((b, t, heads * dv), jnp.float32, q)]
    out_specs = [values]
    if keep:
        out_shape += [pk._sds((b, n, heads, dv, dk), jnp.float32, q),
                      pk._sds((b, n, heads, CHUNK, CHUNK), jnp.float32, q)]
        out_specs += _kept_specs(heads, dk, dv, lambda c: c, group)
    return pl.pallas_call(
        functools.partial(_forward_kernel, slab=slab, heads=taken, dk=dk,
                          dv=dv, keep=keep, per_head=per_head, group=group),
        grid=(b, n, heads // slab),
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=params, interpret=pk._interpret(),
    )(q, k, v, g, beta, *_marks(seg))


def _backward(q, k, v, g, beta, seg, states, inverses, do, heads: int,
              group: int):
    b, t, _ = q.shape
    dk, dv, slab, taken, per_head, params = _layout(q, v, g, heads, group)
    n = t // CHUNK
    at = lambda c: n - 1 - c
    specs, keys, values = _specs(heads, dk, dv, at, per_head, group)
    return pl.pallas_call(
        functools.partial(_backward_kernel, slab=slab, heads=taken, dk=dk,
                          dv=dv, per_head=per_head, group=group),
        grid=(b, n, heads // slab),
        in_specs=specs + _kept_specs(heads, dk, dv, at, group) + [values],
        out_specs=[keys, keys, values, specs[3], specs[4]],
        out_shape=[pk._sds(q.shape, q.dtype, q), pk._sds(k.shape, k.dtype, q),
                   pk._sds(v.shape, v.dtype, q),
                   pk._sds(g.shape, jnp.float32, q),
                   pk._sds(beta.shape, jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=params, interpret=pk._interpret(),
    )(q, k, v, g, beta, *_marks(seg), states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _delta_rule(q, k, v, g, beta, seg, heads: int, group: int):
    return _forward(q, k, v, g, beta, seg, heads, False, group)[0]


def _delta_rule_fwd(q, k, v, g, beta, seg, heads: int, group: int):
    out, states, inverses = _forward(q, k, v, g, beta, seg, heads, True,
                                     group)
    return out, (q, k, v, g, beta, seg, states, inverses)


def _delta_rule_bwd(heads: int, group: int, kept, do):
    grads = _backward(*kept, do.astype(jnp.float32), heads, group)
    # integer segment ids carry a float0 (empty) cotangent
    return tuple(grads) + (np.zeros(kept[5].shape, jax.dtypes.float0),)


_delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)


def delta_rule(q, k, v, g, beta, segment_ids=None, group: int = 1):
    """The chunked delta rule by the kernels: q, k [B, T, H·dk] and v
    [B, T, H·dv] of one type (the matmuls' operands'), g as q (a decay a
    channel) or as beta (one a head) and beta [B, T, H] float32,
    ``segment_ids`` [B, T] or None -> o [B, T, H·dv] float32; with
    ``group`` (one decay a head), q and k hold a key head for every
    ``group`` value heads, [B, T, (H / group)·dk], value head h reading key
    head h // group.  T is padded to whole chunks with tokens that write
    nothing and decay nothing."""
    b, t, _ = q.shape
    pad = -t % CHUNK
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    out = _delta_rule(q, k, v, g.astype(jnp.float32),
                      beta.astype(jnp.float32), seg, beta.shape[-1], group)
    return out[:, :t]


# ---------------------------------------------------------------------------
# The output's per-head norm and gate, on [B, T, H·d] as well: a reduction
# over a head's d channels in XLA goes through [B, T, H, d], which under the
# (8, 128) tiling is another array (a 67 MB copy each way, each pass).
# ---------------------------------------------------------------------------

_NORM_ROWS = 256  # tokens of a norm kernel's block, at most
_NORM_BLOCK = _NORM_ROWS * 4 * pk._LANES  # lanes x rows of a block, at most


def _norm_rows(t: int, lanes: int, heads: int) -> int:
    """Tokens of a norm kernel's block: ``_NORM_ROWS``, or half as many
    until the block is no wider in all than four 128-lane heads' (a block
    of 256 x 5760 float32 arrays asked 28 MB of VMEM); a power of two, so
    that a row of 4096 tokens needs no padding."""
    slab = _slab(heads, lanes // heads) * (lanes // heads)
    rows = _NORM_ROWS
    while rows > 8 and rows * slab > _NORM_BLOCK:
        rows //= 2
    return min(t, rows)


def _norm_grid(x, heads: int):
    """(grid, the block spec of a [B, T, H·d] array, of a [B, T, H] one,
    heads a block, tokens a block) of the norm kernels: T padded to whole
    blocks by the caller."""
    b, t, lanes = x.shape
    step = _slab(heads, lanes // heads)
    rows = _norm_rows(t, lanes, heads)
    wide = pl.BlockSpec((1, rows, step * (lanes // heads)),
                        lambda b_, r, h: (b_, r, h))
    narrow = pl.BlockSpec((1, rows, heads), lambda b_, r, h: (b_, r, 0))
    return (b, t // rows, heads // step), wide, narrow, step


def _rows_padded(arrays, rows: int):
    pad = -arrays[0].shape[1] % rows
    if not pad:
        return arrays
    return tuple(jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in arrays)


def _gated(x_ref, weight_ref, gate_ref, heads: int, eps: float):
    """Per head of the block: (lanes, x / rms(x), 1 / rms(x), weight, the
    head's gate [rows, 1] or, a gate a channel, [rows, d], its lane mask
    over the [rows, H] block or None)."""
    width = x_ref.shape[-1] // heads
    per_channel = gate_ref.shape[-1] == x_ref.shape[-1]
    if not per_channel:
        first = pl.program_id(2) * heads
        gates = gate_ref[0]
        lane = lax.broadcasted_iota(jnp.int32, gates.shape, 1)
    for j in range(heads):
        lanes = slice(j * width, (j + 1) * width)
        x = x_ref[0, :, lanes]
        inverse = lax.rsqrt(jnp.mean(x * x, axis=1, keepdims=True) + eps)
        if per_channel:
            yield (lanes, x * inverse, inverse, weight_ref[...],
                   gate_ref[0, :, lanes], None)
        else:
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

    per_channel = gate_ref.shape[-1] == x_ref.shape[-1]
    if not per_channel:
        @pl.when(pl.program_id(2) == 0)
        def _():
            dgate_ref[0] = jnp.zeros(dgate_ref.shape[1:], jnp.float32)

        dgates = dgate_ref[0]
    dweight = dweight_ref[...]
    for lanes, normed, inverse, weight, gate, mask in _gated(
            x_ref, weight_ref, gate_ref, heads, eps):
        dy = dy_ref[0, :, lanes].astype(jnp.float32)
        through = dy * normed
        dweight = dweight + jnp.sum(through * gate, axis=0, keepdims=True)
        if per_channel:
            dgate_ref[0, :, lanes] = through * weight
        else:
            dgates = jnp.where(
                mask, jnp.sum(through * weight, axis=1, keepdims=True),
                dgates)
        dnormed = dy * weight * gate
        dx_ref[0, :, lanes] = inverse * (dnormed - normed * jnp.mean(
            dnormed * normed, axis=1, keepdims=True))
    if not per_channel:
        dgate_ref[0] = dgates
    dweight_ref[...] = dweight


def _weight_spec(weight):
    return pl.BlockSpec((1, weight.shape[0]), lambda b_, r, h: (0, 0))


def _gate_heads(x, weight, gate):
    """(heads, whether ``gate`` is one a channel) of head_rms_gate."""
    if gate.shape[-1] == x.shape[-1]:
        return x.shape[-1] // weight.shape[0], True
    return gate.shape[-1], False


def _head_rms_gate(x, weight, gate, eps: float, dtype):
    t = x.shape[1]
    heads, per_channel = _gate_heads(x, weight, gate)
    x, gate = _rows_padded((x, gate), _norm_rows(t, x.shape[-1], heads))
    grid, wide, narrow, step = _norm_grid(x, heads)
    return pl.pallas_call(
        functools.partial(_rms_gate_kernel, heads=step, eps=eps),
        grid=grid,
        in_specs=[wide, _weight_spec(weight), wide if per_channel else narrow],
        out_specs=wide, out_shape=pk._sds(x.shape, dtype, x),
        interpret=pk._interpret(),
    )(x, weight[None], gate)[:, :t]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def head_rms_gate(x, weight, gate, eps: float, dtype):
    """RMSNorm over each head's d channels, times ``weight`` [d] and the
    head's ``gate``: x [B, T, H·d] float32, gate [B, T, H] (one a head) or
    [B, T, H·d] (one a channel) float32 -> ``dtype`` [B, T, H·d]."""
    return _head_rms_gate(x, weight, gate, eps, dtype)


def _head_rms_gate_fwd(x, weight, gate, eps, dtype):
    return _head_rms_gate(x, weight, gate, eps, dtype), (x, weight, gate)


def _head_rms_gate_bwd(eps, dtype, kept, dy):
    x, weight, gate = kept
    t = x.shape[1]
    heads, per_channel = _gate_heads(x, weight, gate)
    x, gate, dy = _rows_padded((x, gate, dy),
                               _norm_rows(t, x.shape[-1], heads))
    grid, wide, narrow, step = _norm_grid(x, heads)
    gates = wide if per_channel else narrow
    dx, dweight, dgate = pl.pallas_call(
        functools.partial(_rms_gate_grad_kernel, heads=step, eps=eps),
        grid=grid, in_specs=[wide, _weight_spec(weight), gates, wide],
        out_specs=[wide, _weight_spec(weight), gates],
        out_shape=[pk._sds(x.shape, jnp.float32, x),
                   pk._sds((1,) + weight.shape, jnp.float32, x),
                   pk._sds(gate.shape, jnp.float32, x)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3),
        interpret=pk._interpret(),
    )(x, weight[None], gate, dy)
    return dx[:, :t], dweight[0], dgate[:, :t]


head_rms_gate.defvjp(_head_rms_gate_fwd, _head_rms_gate_bwd)


# ---------------------------------------------------------------------------
# The short convolution, SiLU and q's and k's L2 norm a head, one pass on the
# projection's [B, T, C] as it lies: a block of rows reads the rows before it
# (the backward also those after it) as a halo, a second block spec on the
# same array, and the convolution's float32 output never reaches HBM.
# ---------------------------------------------------------------------------

_CONV_ROWS = 256       # tokens of a block, at most
_CONV_BYTES = 1 << 21  # bytes of x's block, at most

_CONV_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel",) * 3)


def _conv_layout(x, taps, heads: int, unit: bool):
    """(tokens of a block, tokens of a halo, lanes of a block, the lane
    slices a kernel takes one at a time) of the convolution's kernels on x
    [B, T, C].  A halo is one tile of x's type (8 rows of float32, 16 of
    bfloat16) and has to reach back as far as the taps.  A block's lanes
    are whole heads where each head is normed (``_slab``), else up to eight
    128-lane columns that divide the width; its rows ``_CONV_ROWS``, halved
    while x's block is more than ``_CONV_BYTES``.  A kernel takes the
    fewest heads that fill whole 128-lane columns at a time where they are
    normed (four of 96 lanes; a slice at a column's edge costs Mosaic no
    turn of the lanes), else a 128-lane column."""
    t, c = x.shape[1:]
    width = c // heads
    halo = 32 // x.dtype.itemsize
    if taps.shape[0] - 1 > halo:
        raise ValueError(
            f"{taps.shape[0]} taps reach past a halo of {halo} rows")
    lanes = _slab(heads, width) * width
    if not unit:
        lanes = next((n * pk._LANES for n in range(8, 0, -1)
                      if c % (n * pk._LANES) == 0), lanes)
    rows = _CONV_ROWS
    while rows > halo and rows * lanes * x.dtype.itemsize > _CONV_BYTES:
        rows //= 2
    rows = min(rows, -(-t // halo) * halo)
    if not unit:
        step = pk._LANES if lanes % pk._LANES == 0 else width
    else:  # the fewest heads that fill whole 128-lane columns
        step = width * next((n for n in range(1, 9)
                             if n * width % pk._LANES == 0), lanes // width)
    return rows, halo, lanes, [slice(a, min(a + step, lanes))
                               for a in range(0, lanes, step)]


def _conv_specs(x, k: int, rows: int, halo: int, lanes: int):
    """Block specs on a grid (row, block of tokens, block of lanes), x's T
    whole blocks: of a [B, T, C] array ("block", and the halos "before" and
    "after" it, their index clamped to the row where none is), of the
    [B, T, 1] segment ids the same, and of the taps [K, C]."""
    per, last = rows // halo, x.shape[1] // halo - 1
    wide = {"block": pl.BlockSpec((1, rows, lanes), lambda b, r, h: (b, r, h))}
    ids = {"block": pl.BlockSpec((1, rows, 1), lambda b, r, h: (b, r, 0))}
    for name, at in (("before", lambda r: jnp.maximum(r * per - 1, 0)),
                     ("after", lambda r: jnp.minimum((r + 1) * per, last))):
        wide[name] = pl.BlockSpec(
            (1, halo, lanes), lambda b, r, h, at=at: (b, at(r), h))
        ids[name] = pl.BlockSpec(
            (1, halo, 1), lambda b, r, h, at=at: (b, at(r), 0))
    return wide, ids, pl.BlockSpec((k, lanes), lambda b, r, h: (0, h))


def _joined(refs, lanes, dropped):
    """The rows of ``refs`` (halo, block, halo) one after another as
    float32 [rows, lanes]; a halo that lies past the row's start or end
    (``dropped``) is zeros, as the convolution's padding is."""
    parts = [ref[0, :, lanes].astype(jnp.float32) for ref in refs]
    return jnp.concatenate([
        part if drop is None else jnp.where(drop, 0.0, part)
        for part, drop in zip(parts, dropped)])


def _ids(refs):
    """The segment ids of the halos and the block, [1, rows, 1], or None."""
    return jnp.concatenate([ref[0] for ref in refs])[None] if refs else None


def _activated(z, scale, width: int):
    """SiLU, then with ``scale`` x / |x| a head of ``width`` lanes times it
    (the heads side by side along the lanes)."""
    s = jax.nn.silu(z)
    if scale is None:
        return s
    squares = s * s
    if s.shape[-1] == width:
        return s * lax.rsqrt(
            jnp.sum(squares, axis=-1, keepdims=True) + 1e-6) * scale
    head = lax.broadcasted_iota(jnp.int32, s.shape, s.ndim - 1) // width
    inverse = jnp.zeros_like(s)
    for j in range(s.shape[-1] // width):
        mine = head == j
        inverse = jnp.where(mine, lax.rsqrt(jnp.sum(
            jnp.where(mine, squares, 0.0), axis=-1, keepdims=True) + 1e-6),
            inverse)
    return s * inverse * scale


def _conv_kernel(*refs, conv, groups, scale, width: int, halo: int):
    """refs: x's halo before its block and the block, the taps, with
    segment ids their halo and block, y."""
    x_refs, taps_ref, y_ref = refs[:2], refs[2], refs[-1]
    ids = _ids(refs[3:-1])
    first = pl.program_id(1) == 0
    for lanes in groups:
        x = _joined(x_refs, lanes, (first, None))
        taps = taps_ref[:, lanes].astype(jnp.float32)
        z = conv(x[None], taps, ids)[0, halo:]
        y_ref[0, :, lanes] = _activated(z, scale, width).astype(y_ref.dtype)


def _conv_grad_kernel(*refs, conv, groups, scale, width: int,
                      halo: int):
    """refs: x's halo before its block, the block and the halo after; the
    taps; dy's block and the halo after; with segment ids their three; dx
    and the block's share of the taps' gradient.  A token's dx takes the
    cotangents of the K - 1 tokens after it, which the halo after holds."""
    x_refs, taps_ref, dy_refs = refs[:3], refs[3], refs[4:6]
    dx_ref, dtaps_ref = refs[-2:]
    ids = _ids(refs[6:-2])
    block = pl.program_id(1)
    first, last = block == 0, block == pl.num_programs(1) - 1
    rows = dx_ref.shape[1]
    for lanes in groups:
        x = _joined(x_refs, lanes, (first, None, last))
        dy = _joined(dy_refs, lanes, (None, last))
        z, through_conv = jax.vjp(
            lambda x, taps: conv(x[None], taps, ids)[0], x,
            taps_ref[:, lanes].astype(jnp.float32))
        _, through_activation = jax.vjp(
            functools.partial(_activated, scale=scale, width=width), z[halo:])
        (dz,) = through_activation(dy)  # the block's tokens and the halo's
        edge = jnp.zeros((halo, dz.shape[1]), jnp.float32)
        dx = through_conv(jnp.concatenate([edge, dz]))[0]
        dx_ref[0, :, lanes] = dx[halo:halo + rows].astype(dx_ref.dtype)
        # of this block's tokens alone: the next block counts its own
        dtaps_ref[0, 0, :, lanes] = through_conv(
            jnp.concatenate([edge, dz[:rows], edge]))[1]


# Jitted: a model calls the pair three times a layer each way at a handful of
# shapes, and tracing the kernels' bodies again at every call made the step's
# tracing, and so every run's set-up, seconds longer.
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _conv_forward(x, taps, seg, conv, heads: int, scale, dtype):
    t = x.shape[1]
    rows, halo, lanes, groups = _conv_layout(x, taps, heads, scale is not None)
    (x,) = _rows_padded((x,), rows)
    b, padded, c = x.shape
    wide, ids, taps_spec = _conv_specs(x, taps.shape[0], rows, halo, lanes)
    operands, specs = [x, x, taps], [wide["before"], wide["block"], taps_spec]
    if seg is not None:
        (seg,) = _rows_padded((seg,), rows)
        operands += [seg, seg]
        specs += [ids["before"], ids["block"]]
    return pl.pallas_call(
        functools.partial(_conv_kernel, conv=conv, groups=groups, scale=scale,
                          width=c // heads, halo=halo),
        grid=(b, padded // rows, c // lanes), in_specs=specs,
        out_specs=wide["block"], out_shape=pk._sds(x.shape, dtype, x),
        compiler_params=_CONV_PARAMS, interpret=pk._interpret(),
    )(*operands)[:, :t]


@functools.partial(jax.jit, static_argnums=(4, 5, 6))
def _conv_backward(x, taps, seg, dy, conv, heads: int, scale):
    t = x.shape[1]
    rows, halo, lanes, groups = _conv_layout(x, taps, heads, scale is not None)
    x, dy = _rows_padded((x, dy), rows)
    b, padded, c = x.shape
    n, k = padded // rows, taps.shape[0]
    wide, ids, taps_spec = _conv_specs(x, k, rows, halo, lanes)
    operands = [x, x, x, taps, dy, dy]
    specs = [wide["before"], wide["block"], wide["after"], taps_spec,
             wide["block"], wide["after"]]
    if seg is not None:
        (seg,) = _rows_padded((seg,), rows)
        operands += [seg] * 3
        specs += [ids["before"], ids["block"], ids["after"]]
    dx, dtaps = pl.pallas_call(
        functools.partial(_conv_grad_kernel, conv=conv, groups=groups,
                          scale=scale, width=c // heads, halo=halo),
        grid=(b, n, c // lanes), in_specs=specs,
        out_specs=[wide["block"], pl.BlockSpec(
            (1, 1, k, lanes), lambda b_, r, h: (b_, r, 0, h))],
        out_shape=[pk._sds(x.shape, x.dtype, x),
                   pk._sds((b, n, k, c), jnp.float32, x)],
        compiler_params=_CONV_PARAMS, interpret=pk._interpret(),
    )(*operands)
    return dx[:, :t], jnp.sum(dtaps, axis=(0, 1)).astype(taps.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def short_conv_silu(x, taps, seg, conv, heads: int, scale, dtype):
    """SiLU(conv(x)) and, ``scale`` given, each head's L2 norm times it, in
    float32, as ``dtype``: x [B, T, C] as the projection left it, taps
    [K, C] in the parameters' type, seg [B, T, 1] int32 or None.
    ``conv(x, taps, seg)`` is the causal depthwise convolution the kernels
    run on a block of rows and its halo (float32 [1, rows, c] by [K, c] and
    the block's [1, rows, 1] ids): the model's own, so that the two cannot
    differ, and its VJP is the backward's.  What the backward keeps is x
    and the taps; it makes the convolution, SiLU and the norm again in
    VMEM, and gives dx in x's type and the taps' gradient as a float32
    share a block of rows, summed here."""
    return _conv_forward(x, taps, seg, conv, heads, scale, dtype)


def _short_conv_silu_fwd(x, taps, seg, conv, heads, scale, dtype):
    return (_conv_forward(x, taps, seg, conv, heads, scale, dtype),
            (x, taps, seg))


def _short_conv_silu_bwd(conv, heads, scale, dtype, kept, dy):
    x, taps, seg = kept
    dx, dtaps = _conv_backward(x, taps, seg, dy, conv, heads, scale)
    # integer segment ids carry a float0 (empty) cotangent
    return dx, dtaps, (None if seg is None
                       else np.zeros(seg.shape, jax.dtypes.float0))


short_conv_silu.defvjp(_short_conv_silu_fwd, _short_conv_silu_bwd)
