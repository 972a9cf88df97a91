"""Backend registry: one descriptor per accelerator family.

The reference serves four wire backends (MPI/NCCL/Gloo/oneCCL) behind
one plan pipeline by keeping the backend-specific pieces — which links
exist, how fast they are, which kernels lower a fused collective — in
per-backend operation tables (``horovod/common/ops/``).  This module is
that seam for the JAX stack: a :class:`Backend` descriptor bundles the
four things that actually differ between a TPU pod and a GPU cluster,
and everything above it (the XIR lowering pass, the two-rail pipeliner,
DRR rail pricing, fusion buffers, the exchange service, the arbiter,
the serve plane) keys off the *canonical* two-rail model and never
notices which family is underneath:

* **rail names** — the canonical fast/slow rails (``ici``/``dcn``)
  mapped to the family's physical spelling (NVLink ≈ ICI, IB ≈ DCN on
  gpu; identity on tpu).  ``topo.model.rail_labels`` serves them to
  ``/tenants`` and ``/prof``.
* **peak table hook** — the datasheet bf16 peak list ``prof/peak.py``
  resolves MFU denominators against (TPU v2–v6 vs A100/H100/...).
* **kernel-lowering table** — op class → kernel module: the fused
  quantized ring lowers through ``ops/pallas_quant.py`` on tpu and
  ``ops/mosaic_quant.py`` on gpu (``quantized.fused_kernel_module``).
* **discovery fn** — device list → :class:`~horovod_tpu.topo.model.Topology`:
  slice_index/coords grouping on tpu, NVLink-domain/IB grouping on gpu
  (``backend/gpu_topo.py``).  The ``HVD_TPU_TOPO`` override bypasses
  both, unchanged.

Resolution (:func:`family`): ``HVD_TPU_BACKEND=auto|tpu|gpu`` — the env
override first (CPU test meshes force either family without hardware),
else ``jax.devices()[0].platform`` (``gpu``/``cuda``/``rocm`` → gpu,
anything else → tpu, the safe pre-PR-20 default).  The gpu family's
``default_quant_backend`` is ``fused``: on a GPU mesh quantized reduce
ops route through the mosaic ring by default, exactly as
``HVD_TPU_QUANT_BACKEND=fused`` does on TPU.
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Callable, Dict, Optional, Sequence, Tuple

from ..exceptions import HorovodTpuError
from ..utils import env

#: Canonical rail tags every pricing/pipelining consumer keys on.
RAILS = ("ici", "dcn")

#: jax platform strings that resolve to the gpu family under "auto".
_GPU_PLATFORMS = ("gpu", "cuda", "rocm")

#: Spellings accepted by the HVD_TPU_BACKEND knob, canonicalized.
_FAMILY_ALIASES = {
    "tpu": "tpu",
    "gpu": "gpu", "cuda": "gpu", "rocm": "gpu", "nvidia": "gpu",
}


@dataclasses.dataclass(frozen=True)
class Backend:
    """One accelerator family's lowering plane.

    ``rails`` maps the canonical tags to the family's physical labels;
    ``peak_table`` lazily returns the ``(device_kind substring, bf16
    TFLOP/s)`` list (a hook, so the tables stay in ``prof/peak.py``);
    ``kernels`` maps op classes to kernel module names; ``discover``
    builds a Topology from a device list (late-bound — topology and
    registry import each other lazily)."""

    name: str
    platforms: Tuple[str, ...]
    rails: Dict[str, str]
    peak_table: Callable[[], list]
    kernels: Dict[str, str]
    discover: Callable[[Sequence], "object"]
    default_quant_backend: str = "phase"

    def rail_label(self, rail: str) -> str:
        """Physical spelling of one canonical rail tag (identity for
        unknown tags — never a KeyError)."""
        return self.rails.get(rail, rail)


def _tpu_peak_table() -> list:
    from ..prof import peak

    return peak.PEAK_BF16_TFLOPS


def _gpu_peak_table() -> list:
    from ..prof import peak

    return peak.PEAK_BF16_TFLOPS_GPU


def _tpu_discover(devices):
    from ..topo import model as topo_model

    return topo_model._from_devices(devices)


def _gpu_discover(devices):
    from . import gpu_topo

    return gpu_topo.discover(devices)


BACKENDS: Dict[str, Backend] = {
    "tpu": Backend(
        name="tpu",
        platforms=("tpu",),
        rails={"ici": "ici", "dcn": "dcn"},
        peak_table=_tpu_peak_table,
        kernels={"quant_ring": "pallas_quant"},
        discover=_tpu_discover,
        default_quant_backend="phase",
    ),
    "gpu": Backend(
        name="gpu",
        platforms=_GPU_PLATFORMS,
        rails={"ici": "nvlink", "dcn": "ib"},
        peak_table=_gpu_peak_table,
        kernels={"quant_ring": "mosaic_quant"},
        discover=_gpu_discover,
        # EQuARX-style fused rings are the GPU default: there is no
        # legacy phase-tuned GPU fleet to stay bitwise with, and the
        # mosaic interpret path proves gpu==phase parity in tier-1.
        default_quant_backend="fused",
    ),
}

_lock = threading.Lock()
_platform_cache: Optional[str] = None


def _device_platform() -> str:
    """``jax.devices()[0].platform``, probed once per process.  Any
    failure (no runtime yet, headless tools) resolves to ``cpu`` — the
    tpu family's safe degenerate."""
    global _platform_cache
    with _lock:
        if _platform_cache is not None:
            return _platform_cache
    try:
        import jax

        from ..runtime import get_runtime_or_none

        rt = get_runtime_or_none()
        devices = rt.devices if rt is not None else jax.devices()
        platform = (devices[0].platform or "cpu").lower()
    except Exception:
        platform = "cpu"
    with _lock:
        if _platform_cache is None:
            _platform_cache = platform
        return _platform_cache


def family(raw: Optional[str] = None) -> str:
    """Resolve the backend family: the ``HVD_TPU_BACKEND`` env override
    (or an explicit ``raw`` spelling) when set, else the first jax
    device's platform.  Unknown spellings raise — a typo'd backend must
    never silently train on the wrong lowering tables."""
    if raw is None:
        raw = env.get_env(env.BACKEND, "auto")
    r = (raw or "auto").strip().lower()
    if r in ("", "auto"):
        return "gpu" if _device_platform() in _GPU_PLATFORMS else "tpu"
    fam = _FAMILY_ALIASES.get(r)
    if fam is None:
        raise HorovodTpuError(
            f"HVD_TPU_BACKEND must be auto|tpu|gpu (got {raw!r})"
        )
    return fam


def get(name: Optional[str] = None) -> Backend:
    """The resolved :class:`Backend` descriptor (or a named one)."""
    return BACKENDS[family(raw=name) if name is not None else family()]


def rail_labels() -> Dict[str, str]:
    """Canonical rail tag → the resolved family's physical label."""
    return dict(get().rails)


def kernel_module_name(op_class: str) -> Optional[str]:
    """Kernel-lowering table lookup for the resolved family (``None``
    for op classes the family has no fused lowering for)."""
    return get().kernels.get(op_class)


def reset() -> None:
    """Drop the platform probe cache (tests flip the env override and
    simulated platforms between cases)."""
    global _platform_cache
    with _lock:
        _platform_cache = None
