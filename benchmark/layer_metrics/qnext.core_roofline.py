"""The Gated DeltaNet core's share of its roofline, in percent, in a
Qwen3-Next cell: the least time the chip could take for a step's cores —
the larger of operations over the bf16 peak and bytes over the HBM peak,
both by ``ops/qwen3next.py``'s ``gdn_core_step`` (the recurrence's 7 d_k d_v
a value head and token; q and k at the key heads, v and o at the value
heads, once in bf16, g and beta a value head in float32; forward, and three
times that a step; nothing recomputed) — over the device time under
``gdn/core``.  An earlier line says which bound holds.  None where the
program has no such scope or the family no such count."""

from benchmark import peaks
from benchmark.harness import say


def read(run):
    reduced = run.reduced()
    count = getattr(run.ops, "gdn_core_step", None)
    if reduced is None or count is None:
        return None
    ms = reduced.scope_ms_per_step("hvd_compute_grads", "/gdn/core")
    if not ms:
        return None
    work, steps = run.work(), len(run.completions)
    ops, nbytes = count(run.model, work.units / steps / run.chips,
                        work.positions / steps / run.chips)
    peak = peaks.for_kind(run.device_kind)
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say("qnext.core_roofline.bound_by",
        "operations" if by_ops >= by_bytes else "bytes")
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
