"""Fused quantized collectives, GPU lowering (backend family ``gpu``).

``ops/pallas_quant.py`` carries the fused quantized ring's two-lowering
pattern for TPU: hardware kernels on-device, the identical hop math in
interpret mode off-device so the CPU tier proves fused==phase parity.
This module is the same pattern for the gpu family, selected through
the backend registry's kernel-lowering table
(``backend/registry.py``: ``quant_ring -> mosaic_quant``):

* **GPU** — a Mosaic-GPU/Triton transfer loop.  GPUs have no Pallas
  remote-DMA primitive (the NIC/NVLink transport belongs to NCCL), so
  the lowering is the EQuARX shape adapted to the NCCL transport model:
  one Triton-lowered Pallas kernel quantizes every hop's outgoing chunk
  straight into the packed (wire chunk ‖ fp32 block scales) payload,
  each hop ships the 1-byte payload with ``lax.ppermute`` (XLA lowers
  it to NCCL send/recv over NVLink inside a domain, IB across), and one
  Triton kernel dequant-accumulates the arrivals in fp32 — the fp32
  buffers never hit the wire, which is the whole point.
* **off-GPU** — the SAME hop math runs through ``pallas_quant``'s
  interpret-mode kernels (this module imports them; they are not
  copies), so the CPU sim mesh under ``HVD_TPU_BACKEND=gpu`` executes
  bit-identical quantize/pack/dequant-accumulate grids and
  gpu==phase==dense parity is provable in tier-1
  (``TestBackendColumn`` in tests/test_collective_matrix.py,
  tools/tier1_backend_smoke.sh).

Numerics contract: identical to ``pallas_quant`` (and therefore to the
phase backend) — every contribution quantized exactly once by its
producer on the shared :func:`~horovod_tpu.ops.quantized._block_scale`
grid, fp32 dequant-accumulate at the destination, no per-hop
requantization.  The backends differ only in fp32 summation order.

Dispatch (:func:`dispatch_mode`): off-GPU the interpret path serves any
axis + tiling-group combination.  On real GPUs the ring serves
single-domain worlds and explicit groups fall back to the phase
backend, mirroring the TPU rule (only the NVLink-resident ring is
fused; the cross-domain IB hop of a hierarchical lowering quantizes
through phase).  Fallbacks count ``quant.fused_fallback`` exactly like
the TPU path; served collectives additionally count the
``backend.gpu.*`` series so a GPU mesh's fused traffic is attributable
per family.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .. import metrics
from .pallas_kernels import _sds
from .pallas_quant import (
    _dequant_rows_kernel,
    _perm,
    _position,
    _quant_packed,
    _quant_packed_kernel,
    _quant_packed_only_kernel,
    _rs_accum,
)

try:  # Triton lowering params; absent on CPU/TPU-only jax builds.
    from jax.experimental.pallas import triton as plgpu

    _HAS_PLGPU = True
except Exception:  # pragma: no cover - environment-dependent
    plgpu = None
    _HAS_PLGPU = False

#: jax platform strings the hardware path serves.
_GPU_PLATFORMS = ("gpu", "cuda", "rocm")

# Per-rank packed-payload cap for the single-shot GPU ring (HBM staging
# is roomy, but the all-hops-resident layout still bounds it).
_GPU_STAGING_CAP = 8 * 1024 * 1024


def _on_gpu() -> bool:
    return jax.default_backend() in _GPU_PLATFORMS


def _gpu_compiler_params(num_warps: int = 4):
    """Triton compiler params when this jax build exposes them (the
    kernels are bandwidth-bound memcpy-shaped, so defaults are near
    enough when it does not)."""
    if not _HAS_PLGPU:  # pragma: no cover - environment-dependent
        return None
    cls = getattr(plgpu, "CompilerParams", None) or getattr(
        plgpu, "TritonCompilerParams", None
    )
    try:
        return cls(num_warps=num_warps) if cls is not None else None
    except Exception:  # pragma: no cover - defensive
        return None


# ------------------------------------------------------------ dispatch

def dispatch_mode(groups, n: int, wire_nbytes: int = 0) -> Optional[str]:
    """How (whether) the gpu fused backend serves this collective:
    ``"interp"`` off-GPU (any axis/groups — the pallas_quant interpret
    machinery, ppermute transport), ``"gpu"`` for the Triton transfer
    loop on hardware, ``None`` when the caller must fall back to the
    phase backend (explicit groups or a multi-domain world on real
    GPUs — the fused ring rides one NVLink domain; cross-domain hops
    quantize through phase, the hierarchical lowering's contract — or
    a payload past the staging cap)."""
    if n <= 1:
        return None
    if not _on_gpu():
        return "interp"
    if not _HAS_PLGPU:
        return None
    if groups is not None:
        return None
    from ..topo import model as topo_model

    if topo_model.current().num_slices != 1:
        return None
    if wire_nbytes > _GPU_STAGING_CAP:
        return None
    return "gpu"


def _account(n: int, c: int, block: int, wire: str) -> None:
    """Count the fused dispatch under both series: the shared
    ``quant.fused_*`` counters every existing consumer reads, plus the
    family-tagged ``backend.gpu.*`` pair (the acceptance gauge for
    "quantized reduce ops actually routed through the mosaic
    lowering")."""
    from .quantized import wire_itemsize

    nbytes = n * (c * wire_itemsize(wire) + 4 * (c // block))
    metrics.inc_counter("quant.fused_collectives")
    metrics.inc_counter("quant.fused_bytes", nbytes)
    metrics.inc_counter("backend.gpu.quant_collectives")
    metrics.inc_counter("backend.gpu.quant_bytes", nbytes)


# --------------------------------------------------- GPU kernel wrappers
#
# The same kernel bodies as the interpret path (imported from
# pallas_quant — shared code, not copies), launched with Triton
# compiler params and interpret=False.  Exercised on real GPUs only.

def _quant_packed_gpu(x3: jax.Array, wire: str, want_deq: bool):
    m, nb, block = x3.shape
    params = _gpu_compiler_params()
    kwargs = {"compiler_params": params} if params is not None else {}
    if not want_deq:
        out = pl.pallas_call(
            functools.partial(_quant_packed_only_kernel, wire=wire),
            out_shape=_sds((m, nb, block + 4), jnp.int8, x3),
            **kwargs,
        )(x3)
        return out, None
    return pl.pallas_call(
        functools.partial(_quant_packed_kernel, wire=wire),
        out_shape=[
            _sds((m, nb, block + 4), jnp.int8, x3),
            _sds((m, nb, block), jnp.float32, x3),
        ],
        **kwargs,
    )(x3)


def _rs_accum_gpu(payloads, wire: str):
    from .pallas_quant import _accum_math, _unpack_math

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

    params = _gpu_compiler_params()
    kwargs = {"compiler_params": params} if params is not None else {}
    return pl.pallas_call(
        kernel,
        out_shape=_sds((nb, block), jnp.float32, payloads[0]),
        **kwargs,
    )(*payloads)


def _dequant_rows_gpu(by_src: jax.Array, wire: str):
    n, nb, blk4 = by_src.shape
    params = _gpu_compiler_params()
    kwargs = {"compiler_params": params} if params is not None else {}
    return pl.pallas_call(
        functools.partial(_dequant_rows_kernel, wire=wire),
        out_shape=_sds((n, nb, blk4 - 4), jnp.float32, by_src),
        **kwargs,
    )(by_src)


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
    """GPU-family fused reduce-scatter: same contract as
    ``pallas_quant.fused_reduce_scatter`` (the (n, c) block-aligned
    chunk layout in, ``(mine, deq)`` out).  The transfer loop is the
    ppermute ring either way — in ``"gpu"`` mode the quantize and
    dequant-accumulate stages are Triton-compiled, in ``"interp"`` mode
    they run through the shared interpret kernels."""
    c = int(chunks.shape[1])
    nb = c // block
    _account(n, c, block, wire)
    quant = _quant_packed_gpu if mode == "gpu" else _quant_packed
    accum = _rs_accum_gpu if mode == "gpu" else _rs_accum
    pos = _position(axis, groups)
    # One quantization per contribution, batched into one kernel call,
    # straight into the packed (wire chunk ‖ scales) layout; hop t
    # ships ring position (pos + t)'s payload with a single ppermute
    # (NCCL send/recv on hardware); arrivals dequant-accumulate in fp32
    # in one kernel, unpacked in place.
    packed, deq = quant(chunks.reshape(n, nb, block), wire,
                        want_deq=want_deq)
    arrivals = [
        lax.dynamic_index_in_dim(packed, pos, axis=0, keepdims=False)
    ]  # the local chunk delivers without a hop
    for t in range(1, n):
        d = lax.rem(pos + t, n)
        payload = lax.dynamic_index_in_dim(packed, d, axis=0,
                                           keepdims=False)
        arrivals.append(lax.ppermute(payload, axis, _perm(groups, n, t)))
    acc = accum(arrivals, wire)
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
    """GPU-family fused all-gather: quantize the (c,) shard once,
    forward the packed payload around the ring, dequantize each arrival
    into its source slot.  Order-free, so gpu==phase is bitwise for
    every input (same grid, no accumulation)."""
    c = int(shard.shape[0])
    nb = c // block
    _account(n, c, block, wire)
    quant = _quant_packed_gpu if mode == "gpu" else _quant_packed
    pos = _position(axis, groups)
    packed, _ = quant(shard.reshape(1, nb, block), wire, want_deq=False)
    # The payload is immutable in flight: hop t's forwarded copy equals
    # a direct shift-by-t of the original, so the shifts issue as
    # independent ppermutes (NCCL can overlap them).
    payload = packed[0]
    arrivals = [
        lax.ppermute(payload, axis, _perm(groups, n, t))
        for t in range(1, n)
    ]
    # Reorder to source order while the payload is still 1-byte wire
    # data; the fp32 gathered buffer is written exactly once, by the
    # dequant kernel.
    stacked = jnp.stack([payload] + arrivals)
    by_src = jnp.take(stacked, lax.rem(pos - jnp.arange(n) + n, n),
                      axis=0)
    if mode == "gpu":
        out = _dequant_rows_gpu(by_src, wire)
    else:
        from .pallas_kernels import _interpret

        out = pl.pallas_call(
            functools.partial(_dequant_rows_kernel, wire=wire),
            out_shape=_sds((n, nb, block), jnp.float32, by_src),
            interpret=_interpret(),
        )(by_src)
    return out.reshape(-1)
