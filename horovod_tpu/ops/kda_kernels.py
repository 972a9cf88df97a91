"""The chunked delta rule of ``ops/kda.py`` as a pair of Pallas kernels,
forward and a hand-written backward under one ``jax.custom_vjp``, on the
projections' own layout: q, k, g ``[B, T, H·dk]``, v and o ``[B, T, H·dv]``,
beta ``[B, T, H]``.

The grid is (row, step of c chunks, slab of heads): a grid step owns c
consecutive chunks of ``CHUNK`` tokens, a block of c·CHUNK rows, by one
slab of heads' lanes (``_slab``: ``_heads_a_step`` heads where their keys
and values are whole 128-lane slabs, else every head, the block as wide as
the array, so that heads of 96 or 192 lanes are read where the projection
left them).  Inside a slab the heads are taken some at a time, each
group's lanes a static slice of the block, and c and the heads taken are
``step_plan``'s, from the shapes alone.  Of a group, the c chunks by its
heads are one batch axis of every array: all that does not touch the
state (the decays, the triangles, the inverse, U and W; in the backward
everything but dS's walk) is issued for the whole batch at once, and only
the state's short recurrence walks the step's chunks in order, two
matmuls a chunk (in reverse by the backward), with every head's state,
``[dv, dk]`` float32, in a VMEM scratch from step to step.  A chunk's
triangles, the inverse, U, W and what the tokens wrote never leave VMEM.
The state is kept transposed so that its decay, one factor a key channel,
runs along the lanes.

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
float32, ten full-precision matmuls to make again), written a block of c
chunks at a time; the triangles, U, W and what was written are made again
from q, k, v, g, beta.
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
    VMEM than a kernel gets unasked).  ``step_plan`` widens the batch with
    the step's chunks."""
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


# A grid step's batch: chunk-heads whose chains are issued together.  Past
# two chunks a step a chain's own work bounds the kernel, not its latency
# (PERF.md, PR 42: a layer's forward 1.63 -> 1.39 ms at two chunks of four
# heads, 1.39 at four, 1.42 at eight); Mosaic unrolls the batch, so the
# kernel's compile time grows with a step's chunk-heads (the pair at a
# block of 30 heads: 31 s at one chunk, 82 at two, 163 at four, compiled
# several at a time on the chip's host).
_CHAINS = 16      # chunk-heads a group of the body takes at once, at most
_STEP_CHAINS = 64  # chunk-heads of a grid step, at most
_VMEM_MOST = 96 * 2 ** 20


def _vmem_bytes(chunks: int, taken: int, slab: int, heads: int, dk: int,
                dv: int, group: int, per_head: bool, itemsize: int) -> int:
    """What the backward, the larger of the pair, holds in VMEM, from above
    (compiled for a v5e: 28 MiB needed at four chunks of four 128-lane
    heads, 20 with one decay a head, 37 at two chunks of three of Olmo's
    30 heads): its blocks twice over (the pipeline's two buffers), the
    running dS of every head, and 36 arrays of a chunk by a head's widest
    lanes for each of the ``chunks`` x ``taken`` chains of a group."""
    lanes = lambda n: -(-n // pk._LANES) * pk._LANES
    rows = chunks * CHUNK
    keys = rows * lanes(slab // group * dk) * itemsize
    values = rows * lanes(slab * dv)
    gates = rows * (lanes(heads) if per_head else lanes(slab * dk)) * 4
    blocks = (4 * keys + values * (2 * itemsize + 4) + 2 * gates
              + rows * (2 * lanes(heads) + 3 * pk._LANES) * 4
              + chunks * slab * (dv * lanes(dk) + CHUNK * pk._LANES) * 4)
    chains = chunks * taken * 36 * CHUNK * lanes(max(dk, dv)) * 4
    return 2 * blocks + heads * dv * lanes(dk) * 4 + chains


def _vmem_limit(*plan) -> int:
    """The VMEM a call of the pair asks: half as much again as
    ``_vmem_bytes`` gives, at least what a kernel gets unasked."""
    return min(_VMEM_MOST, max(16 * 2 ** 20, 3 * _vmem_bytes(*plan) // 2))


def step_plan(t: int, heads: int, dk: int, dv: int, group: int = 1,
              per_head: bool = False, itemsize: int = 2):
    """(chunks a grid step owns, heads its body takes at a time) of the
    pair on T = ``t`` tokens of ``heads`` value heads, keys ``dk`` and
    values ``dv`` wide, ``group`` value heads a key head, g one a head
    where ``per_head``, q ``itemsize`` bytes an element.  The chunks
    double while a group's batch stays within ``_CHAINS``, the step's
    within ``_STEP_CHAINS`` and its VMEM within ``_VMEM_MOST``; then a
    block of every head takes as many heads at a time as fit the same
    bounds.  A row's T is padded to whole steps, and the chunks are as
    few as give the same number of steps: no padding where whole steps
    fit."""
    slab = _slab(heads, dk, dv, group=group)
    taken = _heads_a_step(slab, group)
    fits = lambda c, h: c * h <= _CHAINS and c * slab <= _STEP_CHAINS and \
        _vmem_bytes(c, h, slab, heads, dk, dv, group, per_head,
                    itemsize) <= _VMEM_MOST
    chunks = 1
    while fits(2 * chunks, taken):
        chunks *= 2
    taken = max(h for h in range(taken, slab + 1)
                if slab % h == 0 and h % group == 0
                and (h == taken or fits(chunks, h)))
    n = -(-t // CHUNK)
    steps = -(-n // chunks)
    return -(-n // steps), taken


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
        # [h, 1, dk]
        carry=jnp.where(sees[:, c - 1:], decay_in[:, c - 1:], 0.0),
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
        # [h, 1, dk]
        carry=jnp.where(sees[:, c - 1:], decay_in[:, c - 1:], 0.0),
        rhs_plain=rhs_plain, rhs=(beta * rhs_plain).astype(dtype))


def _blocks_chunks(x, chunks: int):
    """The ``chunks`` chunks of a [c·C, ...] block's rows, each [C, ...]:
    slices at whole tiles of sublanes, so nothing is laid out again."""
    return [x[a * CHUNK:(a + 1) * CHUNK] for a in range(chunks)]


def _chunk_major(parts, heads: int):
    """A batch of a grid step's chunks by its heads, chunk-major, of one
    array a chunk that every head shares: chunk a's heads are the rows
    a·h to (a + 1)·h of the batch."""
    return jnp.stack([part for part in parts for _ in range(heads)])


def _column(block, lane, head):
    """Column ``head`` of a [rows, H] block as [rows, 1]; ``lane`` is the
    block's lane index."""
    return jnp.sum(jnp.where(lane == head, block, 0.0), axis=1, keepdims=True)


def _columns(block, lane, first, heads: int, chunks: int):
    """Columns ``first`` to ``first + heads`` of a [c·C, H] block as the
    chunk-major batch [c·h, C, 1]."""
    cols = [_blocks_chunks(_column(block, lane, first + j), chunks)
            for j in range(heads)]
    return jnp.stack([cols[j][a] for a in range(chunks)
                      for j in range(heads)])


def _put_columns(ref, value, lane, first, chunks: int):
    """The reverse of :func:`_columns`, into a [1, c·C, H] block whose
    other columns are kept."""
    heads = value.shape[0] // chunks
    block = ref[0]
    for j in range(heads):
        column = jnp.concatenate([value[a * heads + j] for a in range(chunks)])
        block = jnp.where(lane == first + j, column, block)
    ref[0] = block


def _load(ref, width: int, heads: int, chunks: int, offset: int = 0,
          group: int = 1):
    """The chunk-major batch [c·h, C, width] of heads ``offset`` to
    ``offset + h`` of the ``chunks`` chunks of a [1, c·C, slab·width]
    block; with ``group``, head j's is head ``j // group`` of the block (a
    key head shared by value heads)."""
    return jnp.stack([
        ref[0, a * CHUNK:(a + 1) * CHUNK,
            j // group * width:(j // group + 1) * width]
        for a in range(chunks) for j in range(offset, offset + heads)])


def _store(ref, value, chunks: int, offset: int = 0, group: int = 1):
    """The reverse of :func:`_load`, in the block's type: with ``group``
    the sum of each ``group`` consecutive heads' parts goes to their key
    head."""
    if group > 1:
        value = jnp.stack([sum(value[j + m] for m in range(group))
                           for j in range(0, value.shape[0], group)])
        offset //= group
    heads, width = value.shape[0] // chunks, value.shape[-1]
    for a in range(chunks):
        for j in range(heads):
            at = (offset + j) * width
            ref[0, a * CHUNK:(a + 1) * CHUNK, at:at + width] = value[
                a * heads + j].astype(ref.dtype)


def _put(ref, value, offset: int, chunk: int):
    """A group's [h, ...] of the step's chunk ``chunk`` into a [1, c,
    slab, ...] block."""
    if ref.shape[2] == value.shape[0]:
        ref[0, chunk] = value
    else:
        ref[0, chunk, offset:offset + value.shape[0]] = value


def _take(ref, heads: int, offset: int):
    """The chunk-major batch [c·h, ...] of a group's heads of a [1, c,
    slab, ...] block."""
    whole = ref.shape[2] == heads
    return jnp.concatenate([
        ref[0, a] if whole else ref[0, a, offset:offset + heads]
        for a in range(ref.shape[1])])


def _step(q_ref, k_ref, v_ref, g_ref, beta_ref, seg_col_ref, seg_row_ref,
          sees_ref, last_ref, heads: int, chunks: int, dk: int, dv: int,
          slab: int, offset: int, per_head: bool, group: int):
    """(the first head of the group of ``heads`` that starts ``offset``
    heads into this grid step's slab, the lane index of a [c·C, H] block,
    the heads' betas, the documents' masks and the chunks' parts, each a
    chunk-major batch of the step's ``chunks`` chunks by the group's
    heads); ``per_head``: g is a [c·C, H] block too, one decay a head;
    ``group`` value heads read each key head of q's and k's blocks."""
    first = pl.program_id(2) * slab
    if offset:
        first = first + offset
    row, col = _iotas(CHUNK)
    same = (_chunk_major(_blocks_chunks(seg_col_ref[0], chunks), heads)
            == _chunk_major([seg_row_ref[0, a] for a in range(chunks)],
                            heads))
    betas = beta_ref[0]
    lane = lax.broadcasted_iota(jnp.int32, betas.shape, 1)
    beta = _columns(betas, lane, first, heads, chunks)
    masks = (jnp.logical_and(same, row > col),
             jnp.logical_and(same, row >= col),
             _chunk_major(_blocks_chunks(sees_ref[0], chunks), heads) != 0,
             _chunk_major(_blocks_chunks(last_ref[0], chunks), heads) != 0)
    q, k, v = (_load(ref, width, heads, chunks, offset, n)
               for ref, width, n in ((q_ref, dk, group), (k_ref, dk, group),
                                     (v_ref, dv, 1)))
    if per_head:
        g = _columns(g_ref[0], lane, first, heads, chunks)
        parts = _chunk_per_head(q, k, v, g, beta, *masks)
    else:
        parts = _chunk(q, k, v, _load(g_ref, dk, heads, chunks, offset),
                       beta, *masks, SUB)
    return first, lane, beta, masks, parts


def _forward_kernel(*refs, slab: int, heads: int, chunks: int, dk: int,
                    dv: int, keep: bool, per_head: bool, group: int):
    """refs: the nine operands of ``_specs``, o, with ``keep`` the states
    and the inverses, the scratch of every head's running state.  The
    slab's heads are taken ``heads`` at a time; of a group, all that does
    not touch the state (triangles, inverse, U, W, B) is one batch of the
    step's chunks by its heads, and only the state's recurrence walks the
    chunks in order: a read of the state and a write to it a chunk."""
    o_ref, state_ref = refs[9], refs[-1]
    for offset in range(0, slab, heads):
        first, _, beta, _, parts = _step(
            *refs[:9], heads, chunks, dk, dv, slab, offset, per_head, group)
        mine = pl.ds(first, heads)

        @pl.when(pl.program_id(1) == 0)
        def _():
            state_ref[mine] = jnp.zeros((heads, dv, dk), jnp.float32)

        dtype = parts["rhs"].dtype
        inverse = _inverse_unit_lower(beta * parts["a_mat"], SUB)
        solved = _dot(inverse.astype(dtype), parts["rhs"])      # [U | W]
        w = solved[..., dv:].astype(dtype)
        state, states, wrote = state_ref[mine], [], []
        for a in range(chunks):
            at = slice(a * heads, (a + 1) * heads)
            states.append(state)
            wrote.append((solved[at, :, :dv] - _dot(
                w[at], state.astype(dtype), _NT)).astype(dtype))
            state = state * parts["carry"][at] + _dot(
                wrote[-1], parts["k_out"][at], _TN)
        state_ref[mine] = state
        entering = jnp.concatenate(states)                      # S0 a chunk
        _store(o_ref, _dot(parts["q_in"], entering.astype(dtype), _NT)
               + _dot(parts["b_mat"], jnp.concatenate(wrote)), chunks,
               offset)
        if keep:
            for a in range(chunks):
                at = slice(a * heads, (a + 1) * heads)
                _put(refs[10], states[a], offset, a)
                _put(refs[11], inverse[at], offset, a)


def _backward_kernel(*refs, slab: int, heads: int, chunks: int, dk: int,
                     dv: int, per_head: bool, group: int):
    """refs: the nine operands of ``_specs``, the states, the inverses and
    o's cotangent, the five gradients, the scratch of every head's dS."""
    for offset in range(0, slab, heads):
        _backward_group(refs, slab, heads, chunks, dk, dv, offset, per_head,
                        group)


def _backward_group(refs, slab: int, heads: int, chunks: int, dk: int,
                    dv: int, offset: int, per_head: bool, group: int):
    """A group's backward over the step's chunks: dS walks them in
    reverse, ``dwrote = B^T do + K_out dS1`` and ``dS0`` a chunk; every
    other part is one batch of the chunks by the heads."""
    states_ref, inverse_ref, do_ref = refs[9:12]
    dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dstate_ref = refs[12:]
    first, lane, beta, (strict, lower, sees, last), p = _step(
        *refs[:9], heads, chunks, dk, dv, slab, offset, per_head, group)
    mine = pl.ds(first, heads)
    c, sub = CHUNK, SUB
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
    solved = _dot(inverse.astype(dtype), p["rhs"])          # [U | W]
    w = solved[..., dv:].astype(dtype)
    state = state_in.astype(dtype)
    wrote = (solved[..., :dv] - _dot(w, state, _NT)).astype(dtype)
    do = _load(do_ref, dv, heads, chunks, offset).astype(dtype)
    through_b = _dot(p["b_mat"], do, _TN)
    reads = jnp.concatenate([p["q_in"], w], axis=1)
    dstate, dstates, dwrotes = dstate_ref[mine], [None] * chunks, [
        None] * chunks                                      # d S1, [h, dv, dk]
    for a in reversed(range(chunks)):
        at = slice(a * heads, (a + 1) * heads)
        dstates[a] = dstate
        dwrotes[a] = through_b[at] + _dot(
            p["k_out"][at], dstate.astype(dtype), _NT)      # [h, C, dv]
        dstate = dstate * p["carry"][at] + _dot(jnp.concatenate(
            [do[at], -dwrotes[a].astype(dtype)], axis=1), reads[at], _TN)
    dstate_ref[mine] = dstate
    dstate, dwrote = jnp.concatenate(dstates), jnp.concatenate(dwrotes)
    dstate_op = dstate.astype(dtype)

    db_mat = jnp.where(lower, _dot(do, wrote, _NT), 0.0)
    stacked = jnp.concatenate([do, -dwrote.astype(dtype)], axis=1)
    through_state = _dot(stacked, state)                    # [h, 2C, dk]
    dq_in, dw = through_state[:, :c], through_state[:, c:]
    dk_out = _dot(wrote, dstate_op)                         # [h, C, dk]
    dcarry = jnp.sum(dstate * state_in, axis=1, keepdims=True)

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
    _put_columns(dbeta_ref, dbeta, lane, first, chunks)
    drhs = beta * drhs
    _store(dv_ref, drhs[..., :dv], chunks, offset)
    dk_in = drhs[..., dv:]
    if per_head:
        _per_head_grads(p, da_mat, db_mat, dq_in, dk_in, dk_out, dcarry,
                        sees, last, dq_ref, dk_ref, dg_ref, lane, first,
                        chunks, offset, group)
        return

    # the triangles: rows of sub-chunk a are lefts[a] k_cols[a]^T
    dlefts, dstarts = [], []
    dg_cum, dk_total = jnp.zeros(kf.shape, jnp.float32), 0.0
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
    _store(dg_ref, _running_sum(dg_cum, reverse=True), chunks, offset)
    _store(dq_ref, dq_rows * p["row_decay"]
           + jnp.where(sees, dq_in * p["decay_in"], 0.0), chunks, offset)
    _store(dk_ref, dk_rows * p["row_decay"] + dk_total
           + jnp.where(sees, dk_in * p["decay_in"], 0.0)
           + jnp.where(last, dk_out * p["decay_out"], 0.0), chunks, offset)


def _per_head_grads(p, da_mat, db_mat, dq_in, dk_in, dk_out, dcarry, sees,
                    last, dq_ref, dk_ref, dg_ref, lane, first, chunks: int,
                    offset: int, group: int):
    """The backward's last part for one decay a head (the module's
    docstring): q's and k's gradients through A, B and the decays, summed
    over the ``group`` value heads of a key head, and g's, [C, 1] a head
    and chunk, into its column of the [c·C, H] block."""
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
    _put_columns(dg_ref, _running_sum(dg_cum, reverse=True), lane, first,
                 chunks)
    _store(dq_ref, rows[:, c:]
           + jnp.where(sees, dq_in * p["decay_in"], 0.0), chunks, offset,
           group)
    _store(dk_ref, rows[:, :c] + cols
           + jnp.where(sees, dk_in * p["decay_in"], 0.0)
           + jnp.where(last, dk_out * p["decay_out"], 0.0), chunks, offset,
           group)


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


def _specs(heads: int, dk: int, dv: int, chunks: int, at, per_head: bool,
           group: int = 1):
    """(block specs of q, k, v, g, beta and the four marks; the spec of a
    [B, T, H·dv] array), for a grid (row, step of ``chunks`` chunks, head
    slab) whose step ``at(s)`` is; g is a [B, T, H] array where
    ``per_head``; q and k hold a key head for every ``group`` value
    heads."""
    n, rows = _slab(heads, dk, dv, group=group), chunks * CHUNK
    keys = pl.BlockSpec((1, rows, n // group * dk),
                        lambda b, s, h: (b, at(s), h))
    values = pl.BlockSpec((1, rows, n * dv), lambda b, s, h: (b, at(s), h))
    column = pl.BlockSpec((1, rows, 1), lambda b, s, h: (b, at(s), 0))
    by_head = pl.BlockSpec((1, rows, heads), lambda b, s, h: (b, at(s), 0))
    return [
        keys, keys, values, by_head if per_head else keys, by_head,
        column,
        pl.BlockSpec((1, chunks, 1, CHUNK), lambda b, s, h: (b, at(s), 0, 0)),
        column, column], keys, values


def _kept_specs(heads: int, dk: int, dv: int, chunks: int, at,
                group: int = 1):
    """Block specs of what the backward keeps: a state and an inverse a
    chunk and head."""
    n = _slab(heads, dk, dv, group=group)
    whole = lambda b, s, h: (b, at(s), h, 0, 0)
    return [pl.BlockSpec((1, chunks, n, dv, dk), whole),
            pl.BlockSpec((1, chunks, n, CHUNK, CHUNK), whole)]


def _params(vmem: int):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        vmem_limit_bytes=vmem)


def _layout(q, v, g, heads: int, group: int):
    """(dk, dv, the slab, whether g is one a head) of ``heads`` value
    heads, ``group`` of them a key head."""
    dk, dv = q.shape[-1] // (heads // group), v.shape[-1] // heads
    slab = _slab(heads, dk, dv, group=group)
    per_head = g.shape[-1] == heads
    if group > 1 and not per_head:
        raise ValueError("value heads share a key head with one decay a "
                         "head only")
    return dk, dv, slab, per_head


@functools.lru_cache(maxsize=8)
def _jitted(body, avals, interpret: bool, static: tuple):
    """``body`` jitted with the arguments named ``static`` static, one a
    shape and mode (``avals`` and ``interpret`` are keys only).  A step's
    layers of one shape then trace and lower a kernel's body once, not
    once a layer (six layers' gradients at ``ling3flash``'s shape, traced
    and lowered for the chip on a CPU: 0.8 s, 3.9 without, 2.3 with one
    chunk a grid step); a process that calls many shapes keeps the
    executables of eight at most."""
    del avals, interpret
    return jax.jit(body, static_argnames=static)


def _call(body, *arrays, **static):
    """``body(*arrays, **static)`` as one jitted call (``_jitted``)."""
    avals = tuple(jax.typeof(a) for a in arrays)
    return _jitted(body, avals, pk._interpret(), tuple(sorted(static)))(
        *arrays, **static)


def _forward(q, k, v, g, beta, seg, heads: int, keep: bool, group: int,
             chunks: int, taken: int):
    b, t, _ = q.shape
    dk, dv, slab, per_head = _layout(q, v, g, heads, group)
    n = t // CHUNK
    specs, _, values = _specs(heads, dk, dv, chunks, lambda s: s, per_head,
                              group)
    out_shape = [pk._sds((b, t, heads * dv), jnp.float32, q)]
    out_specs = [values]
    if keep:
        out_shape += [pk._sds((b, n, heads, dv, dk), jnp.float32, q),
                      pk._sds((b, n, heads, CHUNK, CHUNK), jnp.float32, q)]
        out_specs += _kept_specs(heads, dk, dv, chunks, lambda s: s, group)
    return pl.pallas_call(
        functools.partial(_forward_kernel, slab=slab, heads=taken,
                          chunks=chunks, dk=dk, dv=dv, keep=keep,
                          per_head=per_head, group=group),
        grid=(b, n // chunks, heads // slab),
        in_specs=specs, out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_params(_vmem_limit(
            chunks, taken, slab, heads, dk, dv, group, per_head,
            q.dtype.itemsize)),
        interpret=pk._interpret(),
    )(q, k, v, g, beta, *_marks(seg))


def _backward(q, k, v, g, beta, seg, states, inverses, do, heads: int,
              group: int, chunks: int, taken: int):
    b, t, _ = q.shape
    dk, dv, slab, per_head = _layout(q, v, g, heads, group)
    steps = t // CHUNK // chunks
    at = lambda s: steps - 1 - s
    specs, keys, values = _specs(heads, dk, dv, chunks, at, per_head, group)
    return pl.pallas_call(
        functools.partial(_backward_kernel, slab=slab, heads=taken,
                          chunks=chunks, dk=dk, dv=dv, per_head=per_head,
                          group=group),
        grid=(b, steps, heads // slab),
        in_specs=specs + _kept_specs(heads, dk, dv, chunks, at, group)
        + [values],
        out_specs=[keys, keys, values, specs[3], specs[4]],
        out_shape=[pk._sds(q.shape, q.dtype, q), pk._sds(k.shape, k.dtype, q),
                   pk._sds(v.shape, v.dtype, q),
                   pk._sds(g.shape, jnp.float32, q),
                   pk._sds(beta.shape, jnp.float32, q)],
        scratch_shapes=[pltpu.VMEM((heads, dv, dk), jnp.float32)],
        compiler_params=_params(_vmem_limit(
            chunks, taken, slab, heads, dk, dv, group, per_head,
            q.dtype.itemsize)),
        interpret=pk._interpret(),
    )(q, k, v, g, beta, *_marks(seg), states, inverses, do)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _delta_rule(q, k, v, g, beta, seg, heads: int, group: int, chunks: int,
                taken: int):
    return _call(_forward, q, k, v, g, beta, seg, heads=heads, keep=False,
                 group=group, chunks=chunks, taken=taken)[0]


def _delta_rule_fwd(q, k, v, g, beta, seg, heads: int, group: int,
                    chunks: int, taken: int):
    out, states, inverses = _call(_forward, q, k, v, g, beta, seg,
                                  heads=heads, keep=True, group=group,
                                  chunks=chunks, taken=taken)
    return out, (q, k, v, g, beta, seg, states, inverses)


def _delta_rule_bwd(heads: int, group: int, chunks: int, taken: int, kept,
                    do):
    grads = _call(_backward, *kept, do.astype(jnp.float32), heads=heads,
                  group=group, chunks=chunks, taken=taken)
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
    head h // group.  T is padded to whole grid steps (``chunks_a_step``
    chunks each) with tokens that write nothing and decay nothing."""
    b, t, _ = q.shape
    heads = beta.shape[-1]
    chunks, taken = step_plan(t, heads, q.shape[-1] // (heads // group),
                              v.shape[-1] // heads, group,
                              g.shape[-1] == heads, q.dtype.itemsize)
    pad = -t % (chunks * CHUNK)
    seg = (jnp.ones((b, t), jnp.int32) if segment_ids is None
           else segment_ids.astype(jnp.int32))
    if pad:
        q, k, v, g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0)))
                            for a in (q, k, v, g, beta))
        seg = jnp.pad(seg, ((0, 0), (0, pad)), mode="edge")
    out = _delta_rule(q, k, v, g.astype(jnp.float32),
                      beta.astype(jnp.float32), seg, heads, group, chunks,
                      taken)
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
