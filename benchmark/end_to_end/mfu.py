"""Model FLOP/s utilization: the operations the forward and backward
passes of the completed steps require (``ops/<family>.py``: from shapes and
the batches' real document lengths, no recomputation) per second and chip,
over the bf16 peak of the device kind (``peaks.json``).  In percent."""

from benchmark import peaks


def read(run):
    work = run.work()
    flops = run.ops.train_flops(run.model, work.units, work.sum_sq)
    peak = peaks.for_kind(run.device_kind)["bf16_flops_per_s"]
    return 100.0 * flops / run.window_seconds / run.chips / peak
