"""Fused quantized collectives: EQuARX's transfer-loop fusion as Pallas
TPU kernels (``HVD_TPU_QUANT_BACKEND=fused``).

The phase backend (``ops/quantized.py``) is three separate HLOs per
bucket — blockwise quantize, ``all_to_all`` of wire chunks + fp32 block
scales, fp32 dequant-accumulate — and every intermediate round-trips
through HBM.  EQuARX (arXiv:2506.17615) shows the real win of a
quantized allreduce comes from fusing the quantize/dequant-accumulate
*into* the transfer loop itself.  This module is that lowering, behind
the same ``quantized_reduce_scatter``/``quantized_all_gather`` contract:

* **TPU** — one Pallas kernel per collective.  A ring schedule where
  each ICI hop quantizes the outgoing chunk in VMEM (double-buffered
  staging), ships wire payload + fp32 block scales together with
  ``pltpu.make_async_remote_copy``, and dequant-accumulates arrivals
  into an fp32 VMEM accumulator — partial sums never round-trip through
  HBM between hops.
* **off-TPU** — the identical hop math runs in Pallas interpret-mode
  kernels (every hop's quantize batched in one call, mirroring the TPU
  kernel's internal loop) with one ``lax.ppermute`` of the packed
  (wire chunk ‖ scales) payload standing in for each hop's remote DMA,
  so the CPU test mesh exercises the same
  quantize/dequant-accumulate code path and fused==phase parity is
  provable in tier-1 (tests/test_pallas_quant.py, the fused column in
  tests/test_collective_matrix.py).

Numerics contract — deliberately the *phase backend's*: every
contribution is quantized exactly once by its producer
(:func:`~horovod_tpu.ops.quantized._block_scale` is shared, so the
grids are bit-identical) and dequant-accumulated in fp32 at its
destination.  Per-hop *re*-quantization of partial sums — and its
O(hops) error compounding — is not done; the two backends are
interchangeable per bucket, differing only in fp32 summation order
(bitwise for exactly-representable sums, and the error-feedback
residual is bitwise identical).  ``quantized_all_gather`` is
order-free, so fused==phase is bitwise for every input there.

Dispatch (:func:`dispatch_mode`): off-TPU the interpret path serves any
axis + tiling-group combination (including the hierarchical DCN hop on
the CPU test mesh).  On a real TPU ``fused`` is refused with an error:
the ring kernels have never compiled there (:data:`TPU_RING_REFUSAL`).
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl

from .. import metrics
from ..exceptions import HorovodTpuError
from .pallas_kernels import _interpret, _sds, pltpu

# Why ``fused`` is refused on a TPU (first compile of the ring kernels,
# PR 21: Mosaic for v5e, jax 0.9.0 / libtpu 0.0.34).  Kept verbatim so
# the error names what has to change before the kernels can run.
TPU_RING_REFUSAL = (
    "HVD_TPU_QUANT_BACKEND=fused has no TPU lowering: Mosaic refuses "
    "_rs_ring_kernel/_ag_ring_kernel — 'Slice shape along dimension 2 "
    "must be aligned to tiling (128), but is 1' on the (slots, nb, 1) "
    "f32 block-scale buffers — and with lane-padded scale slots the "
    "whole-chunk VMEM staging exceeds the 16 MiB scoped VMEM limit from "
    "4M-element buckets on (22 MB asked; a default bucket is 16M "
    "elements). Use HVD_TPU_QUANT_BACKEND=phase."
)


def _wire_spec(wire: str):
    from .quantized import WIRE_FORMATS

    return WIRE_FORMATS[wire]


# ------------------------------------------------------------ hop math
#
# Shared between the interpret-mode hop kernels and the TPU ring
# kernels, and bit-identical to the phase backend's _quantize_blocks /
# _dequantize_blocks (the scale guard is the same _block_scale).

def _quant_math(x, wire: str):
    """Quantize one (nb, block) chunk: -> (q wire-dtype, scale (nb, 1)
    fp32, dequant fp32) with the phase backend's exact grid."""
    from .quantized import _block_scale

    qdtype, qmax = _wire_spec(wire)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    scale, safe = _block_scale(amax, qmax)
    scaled = xf / safe
    if wire == "int8":
        qv = jnp.clip(jnp.round(scaled), -qmax, qmax)
    else:
        # fp8 cast rounds to nearest representable; <= qmax by
        # construction so the cast never overflows to inf.
        qv = scaled
    qd = qv.astype(qdtype)
    return qd, scale, qd.astype(jnp.float32) * scale


def _accum_math(acc, rq, rs):
    """fp32 dequant-accumulate of one received (wire chunk, scales)."""
    return acc + rq.astype(jnp.float32) * rs


# ----------------------------------------------- interpret-path kernels
#
# The off-TPU lowering of the TPU ring kernel: the same hop math
# (_quant_math / _accum_math) runs in interpret-mode kernels — the
# quantize of every hop's outgoing chunk batched into one call,
# mirroring the TPU kernel's internal hop loop — and each hop's remote
# DMA is stood in for by ONE lax.ppermute of the packed
# (wire chunk ‖ fp32 block scales) payload: chunks and scales travel
# together, exactly as on the wire.

def _pack_math(q: jax.Array, s: jax.Array) -> jax.Array:
    """One wire payload per hop: the wire chunk with its fp32 block
    scales bitcast alongside — (..., nb, block) + (..., nb, 1) ->
    (..., nb, block + 4) int8.  Chunks and scales travel together."""
    qi = q if q.dtype == jnp.int8 else \
        lax.bitcast_convert_type(q, jnp.int8)
    si = lax.bitcast_convert_type(s, jnp.int8).reshape(
        s.shape[:-1] + (4,)
    )
    return jnp.concatenate([qi, si], axis=-1)


def _unpack_math(p: jax.Array, wire: str):
    """Inverse of :func:`_pack_math` on one (nb, block + 4) payload."""
    qdtype, _ = _wire_spec(wire)
    block = p.shape[-1] - 4
    qi = p[..., :block]
    q = qi if qdtype == jnp.int8 else \
        lax.bitcast_convert_type(qi, qdtype)
    s = lax.bitcast_convert_type(
        p[..., block:].reshape(p.shape[:-1] + (1, 4)), jnp.float32
    )
    return q, s


def _quant_packed_kernel(x_ref, p_ref, deq_ref, *, wire: str):
    q, s, deq = _quant_math(x_ref[:], wire)
    p_ref[:] = _pack_math(q, s)
    deq_ref[:] = deq


def _quant_packed_only_kernel(x_ref, p_ref, *, wire: str):
    q, s, _ = _quant_math(x_ref[:], wire)
    p_ref[:] = _pack_math(q, s)


def _quant_packed(x3: jax.Array, wire: str, want_deq: bool = True):
    """Quantize every hop's outgoing chunk in one kernel call —
    directly into the packed wire layout, plus (when the caller needs
    the EF residual or a local gather row) the fp32 dequant.  Skipping
    the dequant output drops a full fp32 payload write — the wire
    itself is 4x smaller."""
    m, nb, block = x3.shape
    if not want_deq:
        out = pl.pallas_call(
            functools.partial(_quant_packed_only_kernel, wire=wire),
            out_shape=_sds((m, nb, block + 4), jnp.int8, x3),
            interpret=_interpret(),
        )(x3)
        return out, None
    return pl.pallas_call(
        functools.partial(_quant_packed_kernel, wire=wire),
        out_shape=[
            _sds((m, nb, block + 4), jnp.int8, x3),
            _sds((m, nb, block), jnp.float32, x3),
        ],
        interpret=_interpret(),
    )(x3)


def _rs_accum(payloads, wire: str):
    """fp32 dequant-accumulate of the packed arrivals (one ref per
    hop, unpacked inside the kernel — no intermediate copies), in
    fixed payload order."""
    nb = payloads[0].shape[0]
    block = payloads[0].shape[1] - 4

    def kernel(*refs):
        out_ref = refs[-1]
        acc = None
        for r in refs[:-1]:
            q, s = _unpack_math(r[:], wire)
            acc = _accum_math(acc, q, s) if acc is not None \
                else q.astype(jnp.float32) * s
        out_ref[:] = acc

    return pl.pallas_call(
        kernel,
        out_shape=_sds((nb, block), jnp.float32, payloads[0]),
        interpret=_interpret(),
    )(*payloads)


def _dequant_rows_kernel(p_ref, out_ref, *, wire: str):
    q, s = _unpack_math(p_ref[:], wire)
    out_ref[:] = q.astype(jnp.float32) * s


# ------------------------------------------------------ ring addressing

def _position(axis: str, groups):
    """This rank's position within its ring (= its replica group, or
    the whole axis)."""
    idx = lax.axis_index(axis)
    if groups is None:
        return idx
    table = np.zeros(sum(len(g) for g in groups), np.int32)
    for g in groups:
        for i, r in enumerate(g):
            table[r] = i
    return jnp.asarray(table)[idx]


def _perm(groups, n: int, t: int) -> List[Tuple[int, int]]:
    """ppermute pairs shifting every ring position forward by ``t``."""
    if groups is None:
        return [(i, (i + t) % n) for i in range(n)]
    return [
        (g[i], g[(i + t) % len(g)])
        for g in groups for i in range(len(g))
    ]


# ------------------------------------------------------------ dispatch

def dispatch_mode(groups, n: int, wire_nbytes: int = 0) -> Optional[str]:
    """How the fused backend serves this collective: ``"interp"``
    off-TPU (any axis/groups — ppermute transport), ``None`` for a
    size-1 axis.  On a TPU it raises: the single-kernel RDMA ring
    below does not compile (see :data:`TPU_RING_REFUSAL`), and a
    requested ``fused`` backend must not turn into a quiet ``phase``."""
    del groups, wire_nbytes
    if n <= 1:
        return None
    if _interpret():
        return "interp"
    raise HorovodTpuError(TPU_RING_REFUSAL)


def _account(n: int, c: int, block: int, wire: str) -> None:
    from .quantized import wire_itemsize

    metrics.inc_counter("quant.fused_collectives")
    metrics.inc_counter(
        "quant.fused_bytes",
        n * (c * wire_itemsize(wire) + 4 * (c // block)),
    )


# ------------------------------------------------- fused reduce-scatter

def fused_reduce_scatter(
    chunks: jax.Array,
    axis: str,
    *,
    groups,
    n: int,
    wire: str,
    block: int,
    want_deq: bool = False,
    mode: str = "interp",
) -> Tuple[jax.Array, Optional[jax.Array]]:
    """Fused-backend reduce-scatter core: ``chunks`` is the (n, c)
    block-aligned chunk layout ``quantized_reduce_scatter`` built (c a
    multiple of ``block``).  Returns ``(mine, deq)``: the fp32
    exact-sum (c,) of this position's chunk over all ring members, and
    — when ``want_deq`` (error feedback) — the fp32 (n, c)
    dequantization of every chunk this rank quantized, in chunk order
    (the phase backend's ``_dequantize_blocks(q, s)`` layout)."""
    c = int(chunks.shape[1])
    nb = c // block
    _account(n, c, block, wire)
    if mode == "tpu":
        return _rs_ring_tpu(chunks, axis, n=n, wire=wire, block=block,
                            want_deq=want_deq)
    pos = _position(axis, groups)
    # Every hop's outgoing chunk quantizes in one kernel call (the TPU
    # kernel's internal hop loop, batched) straight into the packed
    # wire layout, then hop t ships the (wire chunk ‖ scales) payload
    # for ring position (pos + t) with a single ppermute — one
    # quantization per contribution, never a requantized partial — and
    # the arrivals dequant-accumulate in fp32 in one kernel, unpacked
    # in place.
    packed, deq = _quant_packed(chunks.reshape(n, nb, block), wire,
                                want_deq=want_deq)
    arrivals = [
        lax.dynamic_index_in_dim(packed, pos, axis=0, keepdims=False)
    ]  # the local chunk delivers without a hop
    for t in range(1, n):
        d = lax.rem(pos + t, n)
        payload = lax.dynamic_index_in_dim(packed, d, axis=0,
                                           keepdims=False)
        arrivals.append(lax.ppermute(payload, axis, _perm(groups, n, t)))
    acc = _rs_accum(arrivals, wire)
    deq_rows = deq.reshape(n, c) if want_deq else None
    return acc.reshape(c), deq_rows


# ---------------------------------------------------- fused all-gather

def fused_all_gather(
    shard: jax.Array,
    axis: str,
    *,
    groups,
    n: int,
    wire: str,
    block: int,
    mode: str = "interp",
) -> jax.Array:
    """Fused-backend all-gather core: quantize this rank's (c,) shard
    once, forward (wire payload, scales) around the ring, dequantize
    each arrival into its source slot.  Returns the fp32 (n*c,)
    concatenation in ring-position order — elementwise bitwise equal to
    the phase backend (same grid, no accumulation)."""
    c = int(shard.shape[0])
    nb = c // block
    _account(n, c, block, wire)
    if mode == "tpu":
        return _ag_ring_tpu(shard, axis, n=n, wire=wire, block=block)
    pos = _position(axis, groups)
    packed, _ = _quant_packed(shard.reshape(1, nb, block), wire,
                              want_deq=False)
    # Ring forwarding of a quantized-once payload: because the payload
    # is immutable in flight, hop t's forwarded copy equals a direct
    # shift-by-t of the original — the stand-in issues the shifts as
    # independent ppermutes (no hop-to-hop data dependency) so the
    # scheduler can overlap them, exactly like the TPU kernel's
    # in-flight RDMAs.
    payload = packed[0]
    arrivals = [
        lax.ppermute(payload, axis, _perm(groups, n, t))
        for t in range(1, n)
    ]
    # Row i of the arrival stack holds source (pos - i) mod n; one
    # gather reorders to source order while the payload is still
    # 1-byte wire data, so the fp32 gathered buffer is written exactly
    # once, by the dequant kernel.
    stacked = jnp.stack([payload] + arrivals)
    by_src = jnp.take(stacked, lax.rem(pos - jnp.arange(n) + n, n),
                      axis=0)
    out = pl.pallas_call(
        functools.partial(_dequant_rows_kernel, wire=wire),
        out_shape=_sds((n, nb, block), jnp.float32, by_src),
        interpret=_interpret(),
    )(by_src)
    return out.reshape(-1)


# --------------------------------------------------- TPU ring kernels
#
# The hardware lowering: ONE pallas_call per collective, hop loop
# inside the kernel, quantize + RDMA + dequant-accumulate per ICI hop
# with double-buffered VMEM staging.  NOT reachable today: Mosaic
# refuses these kernels (TPU_RING_REFUSAL), so dispatch_mode never
# returns "tpu" and they have never executed anywhere — the CPU tier
# runs the interpret path above.  ROADMAP C2 repairs or deletes them.

def _rs_ring_kernel(x_ref, acc_ref, deq_ref,
                    xst, sq, ss, dst, rq, rs,
                    load_sem, deq_sem, sendq_sem, sends_sem,
                    recvq_sem, recvs_sem,
                    *, axis: str, n: int, wire: str, want_deq: bool):
    my = lax.axis_index(axis)
    # All-pairs barrier: every peer must have entered the kernel (recv
    # slots live) before any remote write can land.
    bar = pltpu.get_barrier_semaphore()
    for t in range(1, n):
        pltpu.semaphore_signal(
            bar, inc=1, device_id=lax.rem(my + t, n),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(bar, n - 1)

    def stage(slot, d):
        cp = pltpu.make_async_copy(x_ref.at[d], xst.at[slot],
                                   load_sem.at[slot])
        cp.start()
        return cp

    def drain(sem_slot):
        # Wait a previously-started DMA on this (ref, sem) pair so its
        # staging buffer can be reused.  The hop loop is unrolled (n is
        # static), so which slots have a pending transfer is tracked
        # python-side — a wait on a never-signaled semaphore would hang.
        ref, sem = sem_slot
        pltpu.make_async_copy(ref, ref, sem).wait()

    send_pending = [None, None]  # per send slot: [(ref, sem), ...]
    deq_pending = [None, None]

    # Hop 0: the local chunk seeds the fp32 accumulator (the own
    # contribution is quantized too — one quantization per
    # contribution, exactly like the phase backend).
    stage(0, my).wait()
    _, _, deq0 = _quant_math(xst[0], wire)
    acc = deq0
    if want_deq:
        dst[0] = deq0
        pltpu.make_async_copy(dst.at[0], deq_ref.at[my],
                              deq_sem.at[0]).start()
        deq_pending[0] = [(dst.at[0], deq_sem.at[0])]
    next_cp = stage(1, lax.rem(my + 1, n)) if n > 1 else None
    for t in range(1, n):
        dest = lax.rem(my + t, n)
        slot = t % 2
        next_cp.wait()
        if t + 1 < n:
            # double buffering: the next chunk streams in from HBM
            # while this one quantizes and ships.
            next_cp = stage((t + 1) % 2, lax.rem(my + t + 1, n))
        if send_pending[slot] is not None:
            # this staging slot's previous RDMA must have drained
            # before we overwrite its send buffers.
            for p in send_pending[slot]:
                drain(p)
        q_t, s_t, deq_t = _quant_math(xst[slot], wire)
        sq[slot] = q_t
        ss[slot] = s_t
        if want_deq:
            if deq_pending[slot] is not None:
                for p in deq_pending[slot]:
                    drain(p)
            dst[slot] = deq_t
            pltpu.make_async_copy(dst.at[slot], deq_ref.at[dest],
                                  deq_sem.at[slot]).start()
            deq_pending[slot] = [(dst.at[slot], deq_sem.at[slot])]
        # Wire chunk and block scales travel together: two RDMAs into
        # the destination's per-hop recv slots (distinct per t, so no
        # cross-device credit protocol is needed; the send side is the
        # double-buffered resource).
        pltpu.make_async_remote_copy(
            src_ref=sq.at[slot], dst_ref=rq.at[t - 1],
            send_sem=sendq_sem.at[slot], recv_sem=recvq_sem.at[t - 1],
            device_id=dest, device_id_type=pltpu.DeviceIdType.LOGICAL,
        ).start()
        pltpu.make_async_remote_copy(
            src_ref=ss.at[slot], dst_ref=rs.at[t - 1],
            send_sem=sends_sem.at[slot], recv_sem=recvs_sem.at[t - 1],
            device_id=dest, device_id_type=pltpu.DeviceIdType.LOGICAL,
        ).start()
        send_pending[slot] = [
            (sq.at[slot], sendq_sem.at[slot]),
            (ss.at[slot], sends_sem.at[slot]),
        ]
    # Consume arrivals in hop order (sources my-1, my-2, ...): the fp32
    # partial sum lives in VMEM/vregs for the whole loop — it never
    # round-trips through HBM between hops.
    for t in range(1, n):
        pltpu.make_async_copy(rq.at[t - 1], rq.at[t - 1],
                              recvq_sem.at[t - 1]).wait()
        pltpu.make_async_copy(rs.at[t - 1], rs.at[t - 1],
                              recvs_sem.at[t - 1]).wait()
        acc = _accum_math(acc, rq[t - 1], rs[t - 1])
    acc_ref[:] = acc
    for slot in range(2):
        if send_pending[slot] is not None:
            for p in send_pending[slot]:
                drain(p)
        if deq_pending[slot] is not None:
            for p in deq_pending[slot]:
                drain(p)


def _rs_ring_tpu(chunks, axis, *, n, wire, block, want_deq):
    c = int(chunks.shape[1])
    nb = c // block
    qdtype, _ = _wire_spec(wire)
    x3 = chunks.reshape(n, nb, block)
    acc, deq = pl.pallas_call(
        functools.partial(_rs_ring_kernel, axis=axis, n=n, wire=wire,
                          want_deq=want_deq),
        out_shape=[
            _sds((nb, block), jnp.float32, chunks),
            _sds((n, nb, block), jnp.float32, chunks),
        ],
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, nb, block), chunks.dtype),   # chunk staging
            pltpu.VMEM((2, nb, block), qdtype),          # send q slots
            pltpu.VMEM((2, nb, 1), jnp.float32),         # send scale slots
            pltpu.VMEM((2, nb, block), jnp.float32),     # deq staging
            pltpu.VMEM((n - 1, nb, block), qdtype),      # recv q slots
            pltpu.VMEM((n - 1, nb, 1), jnp.float32),     # recv scale slots
            pltpu.SemaphoreType.DMA((2,)),               # load
            pltpu.SemaphoreType.DMA((2,)),               # deq writeback
            pltpu.SemaphoreType.DMA((2,)),               # send q
            pltpu.SemaphoreType.DMA((2,)),               # send s
            pltpu.SemaphoreType.DMA((n - 1,)),           # recv q
            pltpu.SemaphoreType.DMA((n - 1,)),           # recv s
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=13,
        ),
    )(x3)
    return acc.reshape(c), (deq.reshape(n, c) if want_deq else None)


def _ag_ring_kernel(x_ref, out_ref,
                    sq, ss, dst, rq, rs,
                    deq_sem, sendq_sem, sends_sem, recvq_sem, recvs_sem,
                    *, axis: str, n: int, wire: str):
    my = lax.axis_index(axis)
    bar = pltpu.get_barrier_semaphore()
    for t in range(1, n):
        pltpu.semaphore_signal(
            bar, inc=1, device_id=lax.rem(my + t, n),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
    pltpu.semaphore_wait(bar, n - 1)
    q, s, deq = _quant_math(x_ref[:], wire)
    sq[:] = q
    ss[:] = s
    dst[:] = deq
    pltpu.make_async_copy(dst, out_ref.at[my], deq_sem).start()
    # The shard is quantized exactly once; the same send buffer ships to
    # every peer's per-source slot (ICI routes non-neighbor hops).
    for t in range(1, n):
        dest = lax.rem(my + t, n)
        pltpu.make_async_remote_copy(
            src_ref=sq, dst_ref=rq.at[t - 1],
            send_sem=sendq_sem.at[t - 1], recv_sem=recvq_sem.at[t - 1],
            device_id=dest, device_id_type=pltpu.DeviceIdType.LOGICAL,
        ).start()
        pltpu.make_async_remote_copy(
            src_ref=ss, dst_ref=rs.at[t - 1],
            send_sem=sends_sem.at[t - 1], recv_sem=recvs_sem.at[t - 1],
            device_id=dest, device_id_type=pltpu.DeviceIdType.LOGICAL,
        ).start()
    for t in range(1, n):
        src = lax.rem(my - t + n, n)
        pltpu.make_async_copy(rq.at[t - 1], rq.at[t - 1],
                              recvq_sem.at[t - 1]).wait()
        pltpu.make_async_copy(rs.at[t - 1], rs.at[t - 1],
                              recvs_sem.at[t - 1]).wait()
        # the previous hop's writeback must drain before the deq
        # staging buffer is overwritten
        pltpu.make_async_copy(dst, dst, deq_sem).wait()
        dst[:] = rq[t - 1].astype(jnp.float32) * rs[t - 1]
        pltpu.make_async_copy(dst, out_ref.at[src], deq_sem).start()
    pltpu.make_async_copy(dst, dst, deq_sem).wait()
    for t in range(1, n):
        pltpu.make_async_copy(sq, sq, sendq_sem.at[t - 1]).wait()
        pltpu.make_async_copy(ss, ss, sends_sem.at[t - 1]).wait()


def _ag_ring_tpu(shard, axis, *, n, wire, block):
    c = int(shard.shape[0])
    nb = c // block
    qdtype, _ = _wire_spec(wire)
    out = pl.pallas_call(
        functools.partial(_ag_ring_kernel, axis=axis, n=n, wire=wire),
        out_shape=_sds((n, nb, block), jnp.float32, shard),
        in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec(memory_space=pl.ANY),
        scratch_shapes=[
            pltpu.VMEM((nb, block), qdtype),             # send q
            pltpu.VMEM((nb, 1), jnp.float32),            # send scales
            pltpu.VMEM((nb, block), jnp.float32),        # deq staging
            pltpu.VMEM((n - 1, nb, block), qdtype),      # recv q slots
            pltpu.VMEM((n - 1, nb, 1), jnp.float32),     # recv scales
            pltpu.SemaphoreType.DMA(()),                 # deq writeback
            pltpu.SemaphoreType.DMA((n - 1,)),           # send q
            pltpu.SemaphoreType.DMA((n - 1,)),           # send s
            pltpu.SemaphoreType.DMA((n - 1,)),           # recv q
            pltpu.SemaphoreType.DMA((n - 1,)),           # recv s
        ],
        compiler_params=pltpu.CompilerParams(
            has_side_effects=True, collective_id=14,
        ),
    )(shard.reshape(nb, block))
    return out.reshape(-1)
