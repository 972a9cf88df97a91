"""The flash-attention backward kernel's share of its roofline in the
sliding-window layers, in percent: one call's bound by ops/swamoe.py
(the pairs the layer type requires at its query heads; q and o at the
query heads, k and v once at the key/value heads) times the calls a step
the trace shows, over those calls' device time (trace/calls.py)."""

from benchmark.ops import swamoe


def read(run):
    return swamoe.flash_roofline(
        run, "window", True, "swa.window_flash_bwd_roofline")
