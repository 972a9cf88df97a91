"""The flash-attention forward kernel's share of its roofline in the gated
attention layers of a Qwen3-Next cell, in percent: one call's bound by
``ops/qwen3next.py:flash_step`` (16 query heads and 2 K/V heads of 256,
the causal pairs) times the calls a step the trace shows under
``attn/pallas_call``, over those calls' device time (``trace/calls.py``)."""

from benchmark.ops import qwen3next


def read(run):
    return qwen3next.flash_roofline(run, False, "qnext.flash_fwd_roofline")
