"""The flash-attention forward kernel's share of its roofline in a
looped-decoder cell, in percent: the least time the chip could take for
one call (``ops/flash_fwd.py``: the larger of operations over the bf16
peak and bytes over the HBM peak) times the calls a step the trace shows
— a layer calls it once every pass of the loop, and once more in the
backward where the model recomputes it — over those calls' device time
(``trace/calls.py``)."""

from benchmark.trace import calls


def read(run):
    # the program gives its pallas_call no name: the scope path is the anchor
    return calls.flash_roofline(
        run, "attn/pallas_call", "loop.flash_fwd_roofline", 1.0)
