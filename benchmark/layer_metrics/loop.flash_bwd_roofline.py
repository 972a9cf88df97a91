"""The flash-attention backward kernel's share of its roofline in a
looped-decoder cell, in percent: as ``loop.flash_fwd_roofline`` with twice
``ops/flash_fwd.py``'s operations a call (dq, dk and dv are four matmuls
over the allowed pairs where the forward has two; the scores it computes
again are not required) and the same bytes, over the device time of the
calls under ``flash_bwd``."""

from benchmark.trace import calls


def read(run):
    return calls.flash_roofline(
        run, "flash_bwd", "loop.flash_bwd_roofline", 2.0)
