"""Device peak-FLOPs model shared by the benches and the online MFU
gauge.

One peak table, three consumers: ``bench.py`` and ``chip_smoke.py``
(full-workload records) and ``prof/mfu.py`` (the per-step online
gauge), so a new device generation is added exactly once.

Datasheet peaks are keyed by ``device_kind`` substring.  An accelerator
whose kind is not in the table is an error, not a default; on a CPU
backend there is no peak and MFU stays absent — it is never estimated.
"""

from __future__ import annotations

from typing import Optional, Tuple

from ..exceptions import HorovodTpuError

# Peak dense bf16 TFLOP/s per chip by device_kind substring (public
# cloud.google.com/tpu/docs system-architecture figures).
PEAK_BF16_TFLOPS = [
    ("v6", 918.0),       # Trillium / v6e
    ("v5p", 459.0),
    ("v5 lite", 197.0),  # v5e reports device_kind "TPU v5 lite"
    ("v5e", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
]

# The gpu-family table (backend/registry.py peak-table hook): dense
# bf16 tensor-core peaks from the public NVIDIA/AMD datasheets, keyed
# by device_kind substring exactly like the TPU table.  Ordered
# longest-match-first where one name contains another.
PEAK_BF16_TFLOPS_GPU = [
    ("h200", 989.0),
    ("h100", 989.0),     # SXM; PCIe parts report the same kind string
    ("a100", 312.0),
    ("a10g", 70.0),
    ("l40", 181.0),
    ("l4", 121.0),
    ("v100", 125.0),     # no bf16 — fp16 tensor-core figure
    ("mi300", 1307.0),
    ("mi250", 383.0),
]

# ResNet-50 v1.5 @224: ~4.1 GFLOPs forward per image; training
# (fwd + bwd) ~3x forward.
RESNET50_TRAIN_GFLOPS_PER_IMAGE = 4.1 * 3

_DEFAULT_PEAK: Optional[Tuple[float, str]] = None
_resolved = False
_override: Optional[float] = None


def chip_peak_tflops(device) -> Optional[float]:
    """Datasheet peak for a jax device, or None when its kind is not
    in the resolved backend family's table (the registry peak-table
    hook picks TPU vs GPU figures; registry failure falls back to the
    TPU table — the pre-registry behavior)."""
    kind = (getattr(device, "device_kind", "") or "").lower()
    try:
        from ..backend import registry

        table = registry.get().peak_table()
    except Exception:
        table = PEAK_BF16_TFLOPS
    for key, peak in table:
        if key in kind:
            return peak
    return None


def peak_tflops(device) -> Optional[Tuple[float, str]]:
    """(peak TFLOP/s, source) for a jax device: ``"table"`` when its
    kind is in the datasheet table, ``"override"`` when a test pinned
    one.  None on a CPU device (no peak, no MFU).  Any other device
    missing from the table raises — add its datasheet figure."""
    if _override is not None:
        return _override, "override"
    peak = chip_peak_tflops(device)
    if peak is not None:
        return peak, "table"
    if device.platform == "cpu":
        return None
    raise HorovodTpuError(
        f"no bf16 peak for device_kind {device.device_kind!r} "
        f"(platform {device.platform!r}): add its datasheet figure to "
        "horovod_tpu/prof/peak.py"
    )


def default_peak_tflops() -> Optional[Tuple[float, str]]:
    """:func:`peak_tflops` of this process's first jax device, resolved
    once — the denominator ``prof/mfu.py`` prices every step against.
    (The lookup is idempotent, so two threads racing here only resolve
    the same value twice.)"""
    global _DEFAULT_PEAK, _resolved
    if _override is not None:
        return _override, "override"
    if not _resolved:
        import jax

        _DEFAULT_PEAK = peak_tflops(jax.devices()[0])
        _resolved = True
    return _DEFAULT_PEAK


def cached_peak() -> Optional[Tuple[float, str]]:
    """The already-resolved default peak, or None — what a telemetry
    scrape reads, so it never opens a jax backend itself."""
    if _override is not None:
        return _override, "override"
    return _DEFAULT_PEAK


def set_peak_override(value: Optional[float]) -> None:
    """Pin the peak (tests assert exact MFU values through this); None
    restores table resolution."""
    global _override
    _override = None if value is None else float(value)


def reset() -> None:
    """Forget the resolved peak and any override (test isolation)."""
    global _DEFAULT_PEAK, _resolved, _override
    _DEFAULT_PEAK, _resolved, _override = None, False, None
