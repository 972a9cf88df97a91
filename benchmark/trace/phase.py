"""The step's second phase, ``hvd_reduce_and_update``, cut by the scopes
the program puts inside it (``optim/distributed_optimizer.py``):

    hvd_exchange > wire_out | hvd_sched_bucket<i>_... | wire_in
    hvd_update
    hvd_accumulate        (only where gradients are accumulated)

Times come from the reduced device trace, counts from the compiled step's
HLO text, where a fused computation's instructions keep the ``op_name``
they were traced under even when the fusion that holds them is named by
another.  A program from before the scopes has no instruction named under
``hvd_exchange``; every reader here then gives None, so the line leaves
the metric out.  Nothing is imported from the program.
"""

from __future__ import annotations

import re
from collections import Counter
from typing import Dict, Optional, Tuple

from . import hlo, reduce

EXCHANGE = "hvd_exchange"
UPDATE = "hvd_update"
BUCKET = "hvd_sched_bucket"
WIRE = ("/wire_out", "/wire_in")
# most specific first: the part of the step an ``op_name`` lies in
_PARTS = ("wire_out", "wire_in", BUCKET, EXCHANGE, UPDATE, "hvd_accumulate",
          "hvd_reduce_and_update", "hvd_compute_grads")
# opcodes whose result is no write of their own: plumbing, aliases, and
# the first half of an asynchronous pair (its ``-done`` is counted)
_NO_WRITE = frozenset((
    "parameter", "constant", "tuple", "get-tuple-element", "bitcast",
    "opt-barrier", "after-all", "partition-id", "replica-id"))
_ENTRY = re.compile(r"^ENTRY %?([\w.\-]+)", re.MULTILINE)
_CALLS = re.compile(
    r"^\s*(?:ROOT )?%?([\w.\-]+) = .*?\bcalls=%?([\w.\-]+)", re.MULTILINE)


def part_of(op_name: str) -> str:
    """Which part of the step a scope path lies in, ``none`` without one."""
    for part in _PARTS:
        if part in op_name:
            return part
    return "none"


def scoped_module(run) -> Optional[hlo.Module]:
    """The run's parsed step if the program cut the phase, else None."""
    module = run.module()
    if module is None or not any(
            EXCHANGE in ins.op_name for ins in module.instructions.values()):
        return None
    return module


def scoped_reduced(run) -> Optional[reduce.Reduced]:
    """The run's reduced trace if there is one and the program cut the
    phase, else None."""
    reduced = run.reduced()
    return reduced if reduced is not None and scoped_module(run) else None


# ------------------------------------------------------------------ times
def scope_ms(run, *needles: str) -> Optional[float]:
    """Self time per step and chip of the operations, collectives between
    chips left out, whose scope path holds every needle."""
    reduced = scoped_reduced(run)
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(*needles, collectives=False)


def wire_cast_ms(run) -> Optional[float]:
    """The same for what stands under ``wire_out`` or ``wire_in``."""
    parts = [scope_ms(run, EXCHANGE, wire) for wire in WIRE]
    return None if None in parts else sum(parts)


def collective_ms(run) -> Optional[float]:
    """Time per step and chip during which a collective between chips is
    in progress, whatever else runs meanwhile: the union of the intervals
    ``Reduced.exposed_collective_ms_per_step`` starts from.  None where no
    chip shows a collective."""
    reduced = scoped_reduced(run)
    if reduced is None:
        return None
    per_chip = []
    for chip in reduced.chips:
        spans = reduced._collective_spans(chip)
        if not spans:
            return None
        per_chip.append(reduce.total(reduce.clip(
            reduce.union(spans), chip.window)) / chip.steps / 1e6)
    return sum(per_chip) / len(per_chip)


# ----------------------------------------------------------------- counts
def _entry_name(run) -> Optional[str]:
    found = _ENTRY.search(run.step_hlo or "")
    return found.group(1) if found else None


def wire_converts(run) -> Optional[Tuple[float, Dict[str, object]]]:
    """(result bytes, where they stand) of the ``convert`` instructions
    traced under ``wire_out`` / ``wire_in``, in the entry computation and
    in every other computation alike.  ``where``: how many stand as
    instructions of their own, how many inside a fused computation, and
    for those the part of the step their fusion is named under."""
    module = scoped_module(run)
    if module is None:
        return None
    caller = {computation: module.instructions.get(name)
              for name, computation in _CALLS.findall(run.step_hlo or "")}
    nbytes, alone, fused = 0.0, 0, 0
    by_part: Counter = Counter()
    for ins in module.instructions.values():
        if ins.opcode != "convert" or EXCHANGE not in ins.op_name or not any(
                wire in ins.op_name for wire in WIRE):
            continue
        nbytes += ins.result_bytes
        fusion = caller.get(ins.computation)
        if fusion is None or fusion.opcode != "fusion":
            alone += 1
        else:
            fused += 1
            by_part[part_of(fusion.op_name)] += 1
    return nbytes, {"alone": alone, "fused": fused,
                    "fusions_named_under": dict(by_part)}


def update_writes(run) -> Optional[Tuple[float, int]]:
    """(result bytes, instructions) of the entry computation's
    instructions named under ``hvd_update`` that write a result."""
    module = scoped_module(run)
    entry = _entry_name(run)
    if module is None or entry is None:
        return None
    written = [
        ins.result_bytes for ins in module.instructions.values()
        if ins.computation == entry and UPDATE in ins.op_name
        and ins.opcode not in _NO_WRITE
        and not ins.opcode.endswith("-start")]
    return sum(written), len(written)


# ------------------------------------------------------------------ spans
def span_ms(run, name: str) -> Optional[Tuple[float, float, int]]:
    """(summed duration in ms of the host spans ``name`` that start inside
    the first chip's steady window, the window's length in ms, its steps);
    None without a trace or where the program made no such span."""
    from . import spans

    reduced = run.reduced()
    if reduced is None:
        return None
    found = spans.events(reduced, name)
    if not found:
        return None
    first = reduced.chips[0]
    return (sum(e.end - e.start for e in found) / 1e6,
            (first.window[1] - first.window[0]) / 1e6, first.steps)


def main(argv) -> int:
    """``python3 -m benchmark.trace.phase <file.xplane.pb> <step.hlo.txt>``:
    the phase's split of one traced run, for reading by hand."""
    import json
    from types import SimpleNamespace

    from . import xplane

    text = open(argv[2]).read()
    module = hlo.Module(text)
    reduced = reduce.Reduced(xplane.load(argv[1]), module)
    if not reduced.usable:
        print("no chip's plane holds four executions of one program")
        return 1
    run = SimpleNamespace(
        step_hlo=text, module=lambda: module, reduced=lambda: reduced)
    converts, writes = wire_converts(run), update_writes(run)
    print(json.dumps({
        "hvd_reduce_and_update_ms": reduced.scope_ms_per_step(
            "hvd_reduce_and_update", collectives=False),
        "wire_out_ms": scope_ms(run, EXCHANGE, WIRE[0]),
        "buckets_ms": scope_ms(run, EXCHANGE, BUCKET),
        "wire_in_ms": scope_ms(run, EXCHANGE, WIRE[1]),
        "hvd_update_ms": scope_ms(run, UPDATE),
        "hvd_accumulate_ms": scope_ms(run, "hvd_accumulate"),
        "collective_ms": collective_ms(run),
        "wire_cast_gb": converts and converts[0] / 1e9,
        "wire_cast_converts": converts and converts[1],
        "written_gb_under_hvd_update": writes and writes[0] / 1e9,
    }, indent=1))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
