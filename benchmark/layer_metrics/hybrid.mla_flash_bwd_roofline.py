"""The flash-attention backward kernel's share of its roofline under latent
attention, in percent: as ``hybrid.mla_flash_fwd_roofline`` with twice the
operations a call, over the device time of the calls under ``flash_bwd``."""

from benchmark.ops import hybridmoe


def read(run):
    return hybridmoe.mla_flash_roofline(
        run, "flash_bwd", "hybrid.mla_flash_bwd_roofline", 2.0)
