"""The flash-attention forward kernel's share of its roofline: the least
time the chip could take for the calls of one step — the larger of
operations over the bf16 peak and bytes over the HBM peak, both from
``ops/flash_fwd.py`` and the batches' real document lengths — over the
device time of the kernel's events.  In percent; an earlier line of the
run says which of the two bounds it."""

from benchmark import peaks
from benchmark.harness import say
from benchmark.ops import flash_fwd, gpt


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    # the program gives its pallas_call no name: the scope path is the anchor
    seconds = reduced.kernel_seconds_per_step("attn/pallas_call")
    if not seconds:
        return None
    model, work = run.model, run.work()
    steps = len(run.completions)
    rows = run.cell.traffic["rows"] // run.chips
    ops, nbytes = flash_fwd.ops_and_bytes(
        rows, run.cell.traffic["seq_len"], model["n_head"],
        gpt.head_dim(model), work.units / steps / run.chips,
        work.sum_sq / steps / run.chips)
    peak = peaks.for_kind(run.device_kind)
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say("flash_fwd_roofline.bound_by",
        "operations" if by_ops >= by_bytes else "bytes")
    return 100.0 * model["n_layer"] * max(by_ops, by_bytes) / seconds
