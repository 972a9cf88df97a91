"""The flash-attention forward kernel's share of its roofline under latent
attention, in percent: one call's bound by ``ops/hybridmoe.py`` (scores
over qk_head_dim and values of v_head_dim a pair, q, k, v and o at those
widths; the kernel itself runs heads padded to 256 lanes) times the calls a
step the trace shows, over those calls' device time (``trace/calls.py``)."""

from benchmark.ops import hybridmoe


def read(run):
    return hybridmoe.mla_flash_roofline(
        run, "attn/pallas_call", "hybrid.mla_flash_fwd_roofline", 1.0)
