"""Pallas TPU kernels for the hot ops.

TPU-native replacement for the reference's hand-written CUDA kernels
(``horovod/common/ops/cuda/cuda_kernels.cu``: ``ScaleBufferCudaImpl``,
``BatchedD2DMemcpyCudaImpl``, ``BatchedScaledD2DMemcpyCudaImpl``) plus a
flash-attention kernel for the long-context path that the reference
lacks entirely (SURVEY.md §5).  Where the reference fights the GPU
memory system with batched-copy kernels, on TPU the equivalents are
VMEM-tiled Pallas kernels that keep the score matrix / staging data
on-chip and feed the MXU directly.

All kernels transparently fall back to Pallas interpret mode off-TPU so
the same code paths are exercised by the CPU test mesh.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


def _interpret() -> bool:
    """Interpret Pallas kernels when not running on a real TPU."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Fused scale / cast (ScaleBufferCudaImpl / BatchedScaledD2DMemcpy analog)
# ---------------------------------------------------------------------------

_LANES = 128
_SUBLANES = 8
_SCALE_BLOCK_ROWS = 512


def _scale_cast_kernel(x_ref, s_ref, o_ref):
    o_ref[:] = (x_ref[:].astype(jnp.float32) * s_ref[0]).astype(o_ref.dtype)


def scale_buffer(
    x: jax.Array, scale, dtype: Optional[jnp.dtype] = None
) -> jax.Array:
    """``out = (x * scale).astype(dtype)`` as one VMEM-tiled kernel.

    Parity with the reference's pre/post-scale device kernels
    (``cuda_kernels.cu`` ``ScaleBufferCudaImpl``).  Inside jit/shard_map
    XLA already fuses scale+cast into neighboring ops, so the traced
    collective path uses plain arithmetic (``ops/traced.py:_scale``);
    this kernel is the single-pass alternative for eager/op-by-op use
    where there is no fusion context.  Differentiable (custom VJP:
    ``dx = g*scale``, ``dscale = Σ g·x``).  Accepts any shape; flattens
    and re-tiles to (rows, 128) lanes internally.
    """
    return _scale_buffer_vjp(x, jnp.asarray(scale, jnp.float32),
                             jnp.dtype(dtype or x.dtype).name)


def _scale_buffer_impl(x: jax.Array, scale, out_dtype_name: str) -> jax.Array:
    out_dtype = jnp.dtype(out_dtype_name)
    shape = x.shape
    n = int(np.prod(shape)) if shape else 1
    tile = _SCALE_BLOCK_ROWS * _LANES
    padded = -(-max(n, 1) // tile) * tile
    flat = jnp.pad(x.reshape(-1), (0, padded - n))
    rows = padded // _LANES
    flat = flat.reshape(rows, _LANES)
    scale_arr = jnp.asarray(scale, jnp.float32).reshape(1)

    grid = rows // _SCALE_BLOCK_ROWS
    out = pl.pallas_call(
        _scale_cast_kernel,
        out_shape=jax.ShapeDtypeStruct((rows, _LANES), out_dtype),
        grid=(grid,),
        in_specs=[
            pl.BlockSpec((_SCALE_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((_SCALE_BLOCK_ROWS, _LANES), lambda i: (i, 0)),
        interpret=_interpret(),
    )(flat, scale_arr)
    return out.reshape(-1)[:n].reshape(shape)


def cast_buffer(x: jax.Array, dtype) -> jax.Array:
    """``out = x.astype(dtype)`` as one VMEM-tiled kernel:
    :func:`scale_buffer` with scale 1 (the cast half of the reference's
    ``BatchedScaledD2DMemcpyCudaImpl``).  The bf16 cast wire routes its
    down/up casts through this (``sched/execute.bf16_wire``,
    ``xir/interp._bf16_around``) so the cast around a collective is a
    single fused pass rather than separate astype + multiply HLOs;
    values are identical to a plain ``astype`` (scale 1 is exact, and
    the f32 staging round-trips f16/bf16 inputs losslessly).
    Differentiable like :func:`scale_buffer`; identity when the dtype
    already matches."""
    if jnp.dtype(dtype) == jnp.dtype(x.dtype):
        return x
    return scale_buffer(x, 1.0, dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _scale_buffer_vjp(x, scale, out_dtype_name):
    return _scale_buffer_impl(x, scale, out_dtype_name)


def _scale_buffer_fwd(x, scale, out_dtype_name):
    return _scale_buffer_impl(x, scale, out_dtype_name), (x, scale)


def _scale_buffer_bwd(out_dtype_name, res, g):
    x, scale = res
    dx = _scale_buffer_impl(g, scale, jnp.dtype(x.dtype).name)
    dscale = jnp.sum(g.astype(jnp.float32) * x.astype(jnp.float32))
    return dx, dscale


_scale_buffer_vjp.defvjp(_scale_buffer_fwd, _scale_buffer_bwd)


# ---------------------------------------------------------------------------
# Flash attention (forward Pallas kernel + blockwise-recompute backward)
# ---------------------------------------------------------------------------


def _flash_fwd_kernel(
    *refs,
    scale: float,
    causal: bool,
    packed: bool,
    block_q: int,
    block_k: int,
    t_actual: int,
    nk: int,
):
    if packed:
        (q_ref, k_ref, v_ref, sq_ref, sk_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
    else:
        (q_ref, k_ref, v_ref,
         o_ref, lse_ref, acc_ref, m_ref, l_ref) = refs
        sq_ref = sk_ref = None
    qj = pl.program_id(2)
    kk = pl.program_id(3)

    @pl.when(kk == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # For causal attention, K blocks strictly above the diagonal band
    # contribute nothing: skip their matmuls entirely (the reference has
    # no analog — Horovod never sees attention — this is the TPU flash
    # schedule).
    run = True
    if causal:
        run = kk * block_k <= qj * block_q + block_q - 1

    @pl.when(run)
    def _compute():
        # Both matmuls run in the input dtype (bf16 fast path) with f32
        # accumulation; softmax state is f32 throughout.
        s = (
            jax.lax.dot_general(
                q_ref[:],
                k_ref[:],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )
            * scale
        )  # [block_q, block_k]

        k_pos = kk * block_k + lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = k_pos < t_actual
        if causal:
            q_pos = qj * block_q + lax.broadcasted_iota(jnp.int32, s.shape, 0)
            mask = jnp.logical_and(mask, q_pos >= k_pos)
        if packed:
            # Packed sequences: tokens attend only within their own
            # segment (sq_ref is [block_q, 1], sk_ref [1, block_k]).
            mask = jnp.logical_and(mask, sq_ref[:] == sk_ref[:])
        s = jnp.where(mask, s, _NEG_INF)

        m_prev = m_ref[:, :1]  # [block_q, 1]
        blk_max = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        p = jnp.where(mask, p, 0.0)
        corr = jnp.exp(jnp.where(m_prev <= _NEG_INF, _NEG_INF, m_prev) - m_safe)
        l_ref[:] = l_ref[:] * corr + jnp.sum(p, axis=-1, keepdims=True)
        # p @ v runs in the input dtype (bf16 on the fast path) with f32
        # accumulation — the standard flash trade; scores stay f32.
        acc_ref[:] = acc_ref[:] * corr + jax.lax.dot_general(
            p.astype(v_ref.dtype),
            v_ref[:],
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        m_ref[:] = jnp.broadcast_to(m_new, m_ref.shape)

    @pl.when(kk == nk - 1)
    def _finalize():
        l = l_ref[:, :1]
        o_ref[:] = (
            acc_ref[:] / jnp.where(l == 0.0, 1.0, l)
        ).astype(o_ref.dtype)
        m = m_ref[:, :1]
        lse = jnp.where(l == 0.0, _NEG_INF, m + jnp.log(jnp.maximum(l, 1e-37)))
        # lse is [block_q, 1]; the output carries 128 equal lanes (the
        # minimum TPU tile width) — lane 0 is read back by the wrapper.
        lse_ref[:] = jnp.broadcast_to(lse, lse_ref.shape)


def _sds(shape, dtype, like: jax.Array) -> jax.ShapeDtypeStruct:
    """ShapeDtypeStruct inheriting ``like``'s varying-mesh-axes (vma), so
    the kernel composes with ``shard_map`` (e.g. under Ulysses)."""
    vma = getattr(jax.typeof(like), "vma", None)
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def _pad_t(x: jax.Array, block: int) -> jax.Array:
    t = x.shape[1]
    pad = -(-t // block) * block - t
    if pad == 0:
        return x
    return jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))


def _pad_seg(seg: jax.Array, block: int) -> jax.Array:
    """Pad segment ids along T with -1 (matches nothing)."""
    t = seg.shape[1]
    pad = -(-t // block) * block - t
    if pad == 0:
        return seg
    return jnp.pad(seg, ((0, 0), (0, pad)), constant_values=-1)


def _flash_forward(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    scale: float,
    block_q: int,
    block_k: int,
    segments: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array]:
    b, t, h, d = q.shape
    block_q = min(block_q, max(t, 16))
    block_k = min(block_k, max(t, 16))
    # [B, T, H, D] → [B, H, T, D]: puts (seq, head_dim) in the minor two
    # dims so VMEM tiles are (block, d) — the layout the MXU wants.
    qp = _pad_t(q, block_q).transpose(0, 2, 1, 3)
    kp = _pad_t(k, block_k).transpose(0, 2, 1, 3)
    vp = _pad_t(v, block_k).transpose(0, 2, 1, 3)
    tq, tk = qp.shape[2], kp.shape[2]
    nq, nk = tq // block_q, tk // block_k

    kernel = functools.partial(
        _flash_fwd_kernel,
        scale=scale,
        causal=causal,
        packed=segments is not None,
        block_q=block_q,
        block_k=block_k,
        t_actual=t,
        nk=nk,
    )
    in_specs = [
        pl.BlockSpec((None, None, block_q, d), lambda b_, h_, j, kk: (b_, h_, j, 0)),
        pl.BlockSpec((None, None, block_k, d), lambda b_, h_, j, kk: (b_, h_, kk, 0)),
        pl.BlockSpec((None, None, block_k, d), lambda b_, h_, j, kk: (b_, h_, kk, 0)),
    ]
    inputs = [qp, kp, vp]
    if segments is not None:
        seg = jnp.asarray(segments, jnp.int32)
        # [B, Tq, 1] / [B, 1, Tk] so the blocks arrive pre-oriented for
        # the (block_q, block_k) mask broadcast.
        inputs.append(_pad_seg(seg, block_q)[:, :, None])
        inputs.append(_pad_seg(seg, block_k)[:, None, :])
        in_specs.append(pl.BlockSpec(
            (None, block_q, 1), lambda b_, h_, j, kk: (b_, j, 0)
        ))
        in_specs.append(pl.BlockSpec(
            (None, 1, block_k), lambda b_, h_, j, kk: (b_, 0, kk)
        ))
    out, lse = pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, None, block_q, d), lambda b_, h_, j, kk: (b_, h_, j, 0)),
            pl.BlockSpec(
                (None, None, block_q, _LANES),
                lambda b_, h_, j, kk: (b_, h_, j, 0),
            ),
        ],
        out_shape=[
            _sds((b, h, tq, d), q.dtype, qp),
            _sds((b, h, tq, _LANES), jnp.float32, qp),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
            pltpu.VMEM((block_q, _LANES), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=_interpret(),
    )(*inputs)
    return out.transpose(0, 2, 1, 3)[:, :t], lse[:, :, :t, 0]


def _flash_bwd_chunked(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    o: jax.Array,
    lse: jax.Array,
    do: jax.Array,
    causal: bool,
    scale: float,
    chunk: int,
    segments: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Blockwise-recompute flash backward (O(T·chunk) score memory).

    Standard flash-attention backward identities: with row logsumexp
    ``lse`` and ``delta = rowsum(do ⊙ o)``,
      p = exp(s − lse);  dv = pᵀ·do;  ds = p ⊙ (do·vᵀ − delta);
      dq = ds·k·scale;   dk = dsᵀ·q·scale.
    Expressed as a ``lax.scan`` over K/V chunks so XLA pipelines the
    chunk matmuls on the MXU without materialising the full [T,T] score.
    """
    b, t, h, d = q.shape
    in_dtype = q.dtype
    qf = q.astype(jnp.float32)
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    dof = do.astype(jnp.float32)
    delta = jnp.einsum("bthd,bthd->bht", dof, o.astype(jnp.float32))

    chunk = min(chunk, t)
    pad = -(-t // chunk) * chunk - t
    if pad:
        kf = jnp.pad(kf, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad), (0, 0), (0, 0)))
    nchunks = (t + pad) // chunk
    k_chunks = kf.reshape(b, nchunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    v_chunks = vf.reshape(b, nchunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    if segments is not None:
        segp = _pad_seg(jnp.asarray(segments, jnp.int32), chunk)
        seg_chunks = segp.reshape(b, nchunks, chunk).transpose(1, 0, 2)
    else:
        # dummy carry input keeps one scan structure for both modes
        seg_chunks = jnp.zeros((nchunks, b, 1), jnp.int32)

    q_pos = jnp.arange(t)

    def step(dq, inputs):
        j, kc, vc, segc = inputs
        k_pos = j * chunk + jnp.arange(chunk)
        s = jnp.einsum("bqhd,bkhd->bhqk", qf * scale, kc)
        mask = (k_pos < t)[None, :]
        if causal:
            mask = jnp.logical_and(mask, q_pos[:, None] >= k_pos[None, :])
        if segments is not None:
            # [b, 1, q, k] segment-match mask joins the [q, k] base
            mask = jnp.logical_and(
                mask[None, None],
                (segments[:, :, None] == segc[:, None, :])[:, None],
            )
        p = jnp.where(mask, jnp.exp(s - lse[..., None]), 0.0)
        dv_j = jnp.einsum("bhqk,bqhd->bkhd", p, dof)
        dp = jnp.einsum("bqhd,bkhd->bhqk", dof, vc)
        ds = p * (dp - delta[..., None])
        dq = dq + jnp.einsum("bhqk,bkhd->bqhd", ds, kc) * scale
        dk_j = jnp.einsum("bhqk,bqhd->bkhd", ds, qf) * scale
        return dq, (dk_j, dv_j)

    dq0 = jnp.zeros((b, t, h, d), jnp.float32)
    # Under shard_map the scan carry must match the (device-varying)
    # step outputs; mark the zero init varying over q's mesh axes.
    vma = getattr(jax.typeof(qf), "vma", None)
    if vma:
        dq0 = lax.pcast(dq0, tuple(vma), to="varying")
    dq, (dk_chunks, dv_chunks) = lax.scan(
        step, dq0, (jnp.arange(nchunks), k_chunks, v_chunks, seg_chunks)
    )
    dk = dk_chunks.transpose(1, 0, 2, 3, 4).reshape(b, t + pad, h, d)[:, :t]
    dv = dv_chunks.transpose(1, 0, 2, 3, 4).reshape(b, t + pad, h, d)[:, :t]
    return dq.astype(in_dtype), dk.astype(in_dtype), dv.astype(in_dtype)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7)
)
def _flash_attention_dense(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool,
    scale: Optional[float],
    block_q: int,
    block_k: int,
    bwd_chunk: int,
) -> jax.Array:
    out, _ = _flash_forward(
        q, k, v, causal, scale if scale is not None else q.shape[-1] ** -0.5,
        block_q, block_k,
    )
    return out


def _flash_fwd_rule(q, k, v, causal, scale, block_q, block_k, bwd_chunk):
    scale_val = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, causal, scale_val, block_q, block_k)
    return out, (q, k, v, out, lse)


def _flash_bwd_rule(causal, scale, block_q, block_k, bwd_chunk, res, do):
    q, k, v, out, lse = res
    scale_val = scale if scale is not None else q.shape[-1] ** -0.5
    dq, dk, dv = _flash_bwd_chunked(
        q, k, v, out, lse, do, causal, scale_val, bwd_chunk
    )
    return dq, dk, dv


_flash_attention_dense.defvjp(_flash_fwd_rule, _flash_bwd_rule)


@functools.partial(
    jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8)
)
def _flash_attention_packed(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    segment_ids: jax.Array,
    causal: bool,
    scale: Optional[float],
    block_q: int,
    block_k: int,
    bwd_chunk: int,
) -> jax.Array:
    out, _ = _flash_forward(
        q, k, v, causal, scale if scale is not None else q.shape[-1] ** -0.5,
        block_q, block_k, segments=segment_ids,
    )
    return out


def _flash_packed_fwd_rule(q, k, v, seg, causal, scale, block_q, block_k,
                           bwd_chunk):
    scale_val = scale if scale is not None else q.shape[-1] ** -0.5
    out, lse = _flash_forward(q, k, v, causal, scale_val, block_q, block_k,
                              segments=seg)
    return out, (q, k, v, seg, out, lse)


def _flash_packed_bwd_rule(causal, scale, block_q, block_k, bwd_chunk,
                           res, do):
    q, k, v, seg, out, lse = res
    scale_val = scale if scale is not None else q.shape[-1] ** -0.5
    dq, dk, dv = _flash_bwd_chunked(
        q, k, v, out, lse, do, causal, scale_val, bwd_chunk, segments=seg
    )
    # integer segment ids carry a float0 (empty) cotangent
    return dq, dk, dv, np.zeros(seg.shape, jax.dtypes.float0)


_flash_attention_packed.defvjp(_flash_packed_fwd_rule,
                               _flash_packed_bwd_rule)


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    causal: bool = False,
    scale: Optional[float] = None,
    block_q: int = 512,
    block_k: int = 512,
    bwd_chunk: int = 512,
    segment_ids: Optional[jax.Array] = None,
) -> jax.Array:
    """Fused flash attention: [B, T, H, D] → [B, T, H, D].

    Forward is a Pallas kernel: the [T,T] score matrix never leaves
    VMEM — each (q-block, k-block) tile is a pair of MXU matmuls with
    online softmax carried in VMEM scratch, causal upper blocks skipped.
    Backward recomputes blockwise from the saved logsumexp (flash
    identities), so memory stays O(T·chunk).  Numerics match
    ``parallel.ring_attention.full_attention`` to fp tolerance.

    ``segment_ids`` ([B, T] int32) enables packed-sequence attention:
    tokens attend only to keys in the same segment (the standard
    sequence-packing mask — multiple documents share one row with no
    cross-document attention).  The reference has no LM/attention story;
    this is the TPU-native throughput lever for LM pretraining.

    Requires ``q`` and ``k``/``v`` to share sequence length: the kernel's
    padding mask and causal diagonal are derived from ``q.shape[1]``.
    For cross-attention with differing lengths use ``full_attention``
    (which offsets the diagonal by ``tk - tq``).
    """
    if k.shape[1] != q.shape[1] or v.shape[1] != q.shape[1]:
        raise ValueError(
            f"flash_attention requires equal q/k/v sequence lengths, got "
            f"q T={q.shape[1]}, k T={k.shape[1]}, v T={v.shape[1]}; use "
            "full_attention for unequal lengths"
        )
    if segment_ids is None:
        return _flash_attention_dense(
            q, k, v, causal, scale, block_q, block_k, bwd_chunk
        )
    if segment_ids.shape != q.shape[:2]:
        raise ValueError(
            f"segment_ids must be [B, T] = {q.shape[:2]}, got "
            f"{segment_ids.shape}"
        )
    return _flash_attention_packed(
        q, k, v, jnp.asarray(segment_ids, jnp.int32), causal, scale,
        block_q, block_k, bwd_chunk,
    )
