"""The delta rule's share of its roofline, in percent: the least time the
chip could take for a step's KDA cores — the larger of operations over the
bf16 peak and bytes over the HBM peak, both by ``ops/hybridmoe.py`` from the
recurrence's count (forward, and twice that with the gradients' bytes for
the backward; nothing recomputed), whatever computes it — over the device
time under ``kda/core``.  An earlier line says which bound holds."""

from benchmark import peaks
from benchmark.harness import say


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    ms = reduced.scope_ms_per_step("hvd_compute_grads", "/kda/core")
    if not ms:
        return None
    work, steps = run.work(), len(run.completions)
    ops, nbytes = run.ops.kda_core_step(
        run.model, work.units / steps / run.chips,
        work.positions / steps / run.chips)
    peak = peaks.for_kind(run.device_kind)
    by_ops = ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say("hybrid.kda_core_roofline.bound_by",
        "operations" if by_ops >= by_bytes else "bytes")
    return 100.0 * max(by_ops, by_bytes) / (ms / 1e3)
