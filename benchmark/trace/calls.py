"""A Mosaic kernel's calls in a reduced trace: how many a step, and their
device time.  A step that recomputes part of its forward pass calls a
forward kernel more often than the model has layers; a roofline share that
divided the layers' work by the time of all calls would read the
recomputed ones as a slow kernel, so the share takes its number of calls
from the trace (``layer_metrics/loop.flash_*_roofline.py``)."""

from __future__ import annotations

from typing import Optional, Tuple


def per_step(reduced, kernel: str) -> Optional[Tuple[float, float]]:
    """(calls, device seconds) per step and chip of the Mosaic kernel
    whose scope path holds ``kernel``, averaged over the chips; None
    without the step's HLO or where a chip shows no such event."""
    if reduced.module is None:
        return None
    calls, seconds = [], []
    for chip in reduced.chips:
        own = [o.own for o in chip.ops if kernel in o.scope
               and reduced.module.is_mosaic(o.event.name)]
        if not own:
            return None
        calls.append(len(own) / chip.steps)
        seconds.append(sum(own) / chip.steps / 1e9)
    if not calls:
        return None
    return sum(calls) / len(calls), sum(seconds) / len(seconds)


def flash_roofline(run, kernel: str, what: str, ops_factor: float
                   ) -> Optional[float]:
    """Share of its roofline, in percent, of the attention kernel under
    ``kernel`` in a looped-decoder cell: ``ops/flash_fwd.py``'s operations
    (times ``ops_factor``) and bytes of one call, times the calls a step
    the trace shows, over those calls' device time.  Which bound holds and
    the number of calls go to earlier lines as ``<what>.bound_by`` and
    ``<what>.calls_per_step``."""
    from benchmark import peaks
    from benchmark.harness import say
    from benchmark.ops import flash_fwd

    reduced = run.reduced()
    found = None if reduced is None else per_step(reduced, kernel)
    if found is None:
        return None
    calls, seconds = found
    model, work = run.model, run.work()
    steps = len(run.completions)
    ops, nbytes = flash_fwd.ops_and_bytes(
        run.cell.traffic["rows"] // run.chips, run.cell.traffic["seq_len"],
        model["num_attention_heads"], model["head_dim"],
        work.units / steps / run.chips, work.sum_sq / steps / run.chips)
    peak = peaks.for_kind(run.device_kind)
    by_ops = ops_factor * ops / peak["bf16_flops_per_s"]
    by_bytes = nbytes / peak["hbm_bytes_per_s"]
    say(f"{what}.bound_by", "operations" if by_ops >= by_bytes else "bytes")
    say(f"{what}.calls_per_step", calls)
    return 100.0 * calls * max(by_ops, by_bytes) / seconds
