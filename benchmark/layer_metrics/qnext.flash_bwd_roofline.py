"""The flash-attention backward kernel's share of its roofline in the gated
attention layers of a Qwen3-Next cell, in percent: one call's bound by
``ops/qwen3next.py:flash_step`` (twice the forward's operations, and the
gradients' bytes beside its reads) times the calls a step the trace shows
under ``attn/flash_bwd``, over those calls' device time
(``trace/calls.py``)."""

from benchmark.ops import qwen3next


def read(run):
    return qwen3next.flash_roofline(run, True, "qnext.flash_bwd_roofline")
