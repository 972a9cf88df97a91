"""Operations and bytes one call of the flash-attention forward kernel
requires (``horovod_tpu/ops/pallas_kernels.py:_flash_fwd_kernel``)."""

from __future__ import annotations

from typing import Tuple

from .gpt import attention_pairs


def ops_and_bytes(rows: int, seq_len: int, heads: int, head_dim: int,
                  units: float, sum_sq: float, causal: bool = True,
                  itemsize: int = 2) -> Tuple[float, float]:
    """One layer's call on a batch of ``rows`` x ``seq_len`` positions
    whose mask allows the pairs of (``units``, ``sum_sq``) (see
    ``ops/gpt.py``).  Operations: QK^T and AV, 2*head_dim each per allowed
    pair and head.  Bytes: q, k and v read and the output written once in
    the activation type, and one float32 logsumexp per position and head
    (what the kernel writes since PR 28: a row of positions, not 128 lanes
    a position)."""
    ops = 4.0 * heads * head_dim * attention_pairs(units, sum_sq, causal)
    positions = rows * seq_len * heads
    return ops, positions * (4.0 * head_dim * itemsize + 4.0)
