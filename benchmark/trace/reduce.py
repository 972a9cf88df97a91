"""The arithmetic from a device trace to numbers.

Intervals are (start, end) pairs in nanoseconds.  Everything is taken
inside a *steady window* of the trace: from the start of the second
execution of the step's program to the end of the last-but-one, on each
chip, so that the profiler's own start and stop (during which the host
does not enqueue) are left out.  Per-step numbers divide by the executions
inside that window; per-chip numbers are averaged over the chips.
"""

from __future__ import annotations

import dataclasses
import re
from collections import Counter
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from . import hlo, xplane

Interval = Tuple[float, float]
_COLLECTIVE_NAME = re.compile(
    r"%?(" + "|".join(hlo.COLLECTIVES) + r")(-start|-done)?(\.|\Z)")
HOST_SPANS = ("bench_input_wait", "bench_dispatch", "bench_block")
_NUMBER = re.compile(r"\.\d+\Z")
_LAYER_NUMBER = re.compile(r"(_)\d+(?=/|\Z)")
_SCOPE_PREFIXES = ("jit(step_body)/", "shard_map/")
MIN_STEPS = 4  # executions of the step a usable trace holds


# ---------------------------------------------------------------- intervals
def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Disjoint sorted intervals covering the same points."""
    out: List[List[float]] = []
    for start, end in sorted(i for i in intervals if i[1] > i[0]):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return [(a, b) for a, b in out]


def total(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], window: Interval) -> List[Interval]:
    lo, hi = window
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: Sequence[Interval], b: Sequence[Interval]) -> List[Interval]:
    """The points of ``a`` (disjoint, sorted) not covered by ``b``
    (disjoint, sorted)."""
    out = []
    j = 0
    for start, end in a:
        cursor = start
        while j < len(b) and b[j][1] <= cursor:
            j += 1
        k = j
        while k < len(b) and b[k][0] < end:
            if b[k][0] > cursor:
                out.append((cursor, b[k][0]))
            cursor = max(cursor, b[k][1])
            k += 1
        if cursor < end:
            out.append((cursor, end))
    return out


def overlap(a: Interval, b: Interval) -> float:
    return max(0.0, min(a[1], b[1]) - max(a[0], b[0]))


def self_times(events: Sequence[xplane.Event]) -> List[float]:
    """For events sorted by (start, -end) on one line: each event's
    duration minus what the events nested in it cover (an XLA ``while`` or
    ``call`` holds its body's operations)."""
    own = [e.end - e.start for e in events]
    stack: List[int] = []
    for i, e in enumerate(events):
        while stack and events[stack[-1]].end <= e.start:
            stack.pop()
        if stack:
            parent = events[stack[-1]]
            own[stack[-1]] -= max(0.0, min(e.end, parent.end) - e.start)
        stack.append(i)
    return [max(0.0, x) for x in own]


# ------------------------------------------------------------------ reduced
@dataclasses.dataclass
class Op:
    """A device event inside the steady window with what the HLO says of
    its instruction."""

    event: xplane.Event
    own: float                  # self time, ns
    scope: str                  # jax's scope path, "" where there is none
    collective: Optional[str]   # "all-reduce", ... between chips, or None


@dataclasses.dataclass
class Chip:
    ops: List[Op]               # sorted by (start, -end)
    window: Interval
    steps: int

    def busy(self) -> List[Interval]:
        return clip(union((o.event.start, o.event.end) for o in self.ops),
                    self.window)


class Reduced:
    """One trace and, where there is one, the compiled step it ran."""

    def __init__(self, trace: xplane.Trace,
                 module: Optional[hlo.Module] = None):
        self.trace = trace
        self.module = module
        self.chips: List[Chip] = []
        for plane in trace.devices():
            events = plane.lines.get(xplane.OPS_LINE, [])
            window, steps = _steady_window(
                plane.lines.get(xplane.MODULES_LINE, []))
            if window is None or not events:
                continue
            ops = [
                Op(e, own, self.scope_of(e), self.collective_of(e))
                for e, own in zip(events, self_times(events))
                if e.end > window[0] and e.start < window[1]
            ]
            self.chips.append(Chip(ops, window, steps))

    @property
    def usable(self) -> bool:
        return bool(self.chips)

    # -- naming ---------------------------------------------------------
    def scope_of(self, event: xplane.Event) -> str:
        """The scope path jax recorded for the event's instruction, from
        the HLO (the device plane carries none)."""
        return self.module.scope_of(event.name) if self.module else ""

    def collective_of(self, event: xplane.Event) -> Optional[str]:
        """The collective between chips the event is, by the HLO, or by
        its name where there is no HLO."""
        if self.module is not None and self.module.get(event.name):
            return self.module.collective_kind(event.name)
        m = _COLLECTIVE_NAME.match(event.name)
        return m.group(1) if m else None

    def label(self, op: Op) -> str:
        """A name for the breakdown: the scope path with the numbers of
        repeated layers taken out, so that the same operation of every
        layer adds up; the opcode where there is no scope."""
        if not op.scope:
            ins = self.module.get(op.event.name) if self.module else None
            what = ins.opcode if ins else hlo.instruction_name(op.event.name)
            return f"{_NUMBER.sub('', what)} (no scope)"
        scope = _LAYER_NUMBER.sub(r"\1*", op.scope)
        for prefix in _SCOPE_PREFIXES:
            scope = scope.replace(prefix, "")
        return scope[-110:]

    # -- numbers --------------------------------------------------------
    def _mean_over_chips(self, per_chip: List[float]) -> float:
        return sum(per_chip) / len(per_chip)

    def window_seconds(self) -> float:
        return self._mean_over_chips(
            [(c.window[1] - c.window[0]) / 1e9 for c in self.chips])

    def steps(self) -> int:
        return min(c.steps for c in self.chips)

    def busy_seconds(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        return self._mean_over_chips(
            [total(c.busy()) / 1e9 for c in self.chips])

    def scope_ms_per_step(self, *needles: str, without: Sequence[str] = (),
                          collectives: Optional[bool] = None) -> float:
        """Self time of the operations whose scope path holds every one of
        ``needles`` and none of ``without``, per step and chip.  No needle
        at all asks for the operations that have no ``hvd_`` scope.
        ``collectives``: None counts all, False leaves collectives between
        chips out, True counts only them."""
        def wanted(op: Op) -> bool:
            if not needles and "hvd_" in op.scope:
                return False
            if collectives is not None and (
                    (op.collective is not None) != collectives):
                return False
            return all(n in op.scope for n in needles) and not any(
                w in op.scope for w in without)

        return self._mean_over_chips([
            sum(o.own for o in c.ops if wanted(o)) / c.steps / 1e6
            for c in self.chips])

    def kernel_seconds_per_step(self, kernel: str) -> Optional[float]:
        """Device seconds per step and chip of the Mosaic kernel whose
        scope path holds ``kernel``; None when a chip shows no such
        event."""
        per_chip = []
        for c in self.chips if self.module else ():
            own = [o.own for o in c.ops if kernel in o.scope
                   and self.module.is_mosaic(o.event.name)]
            if not own:
                return None
            per_chip.append(sum(own) / c.steps / 1e9)
        return self._mean_over_chips(per_chip) if per_chip else None

    def _collective_spans(self, chip: Chip) -> List[Interval]:
        """Intervals in which a collective between chips is in progress: a
        synchronous one for its event, an asynchronous one from the start
        of its ``-start`` to the end of its ``-done``."""
        spans: List[Interval] = []
        pending: Dict[str, List[float]] = {}
        for op in chip.ops:
            if op.collective is None:
                continue
            e = op.event
            ins = self.module.get(e.name) if self.module else None
            name = hlo.instruction_name(e.name)
            opcode = ins.opcode if ins else name
            started = None
            if "-start" in opcode:
                pending.setdefault(name, []).append(e.start)
            elif "-done" in opcode and ins is not None and ins.operands:
                queue = pending.get(ins.operands[0])
                if queue:
                    started = queue.pop(0)
            spans.append((e.start if started is None else started, e.end))
        return spans

    def exposed_collective_ms_per_step(self) -> float:
        """Per step and chip: time inside a collective's span during which
        no other operation runs on that chip."""
        per_chip = []
        for c in self.chips:
            compute = union(
                (o.event.start, o.event.end) for o in c.ops
                if o.own > 0.0 and o.collective is None)
            spans = clip(union(self._collective_spans(c)), c.window)
            per_chip.append(total(subtract(spans, compute)) / c.steps / 1e6)
        return self._mean_over_chips(per_chip)

    # -- breakdown ------------------------------------------------------
    def breakdown(self, entries: int = 10) -> Dict[str, list]:
        """The device operations that took most time (self time per step,
        averaged over the chips, seconds) and the device's idle time per
        step by what the host's main loop was doing meanwhile (first
        chip)."""
        by_label: Counter = Counter()
        for c in self.chips:
            for op in c.ops:
                if op.own > 0.0:
                    by_label[self.label(op)] += (
                        op.own / c.steps / len(self.chips))
        first = self.chips[0]
        gaps = subtract([first.window], first.busy())
        host = self.trace.host()
        spans = sorted(
            (e for events in (host.lines.values() if host else ())
             for e in events if e.name in HOST_SPANS),
            key=lambda e: e.start)
        by_host: Counter = Counter()
        for gap in gaps:
            covered = 0.0
            for e in spans:
                if e.start >= gap[1]:
                    break
                got = overlap(gap, (e.start, e.end))
                if got:
                    by_host[e.name] += got / first.steps
                    covered += got
            rest = (gap[1] - gap[0]) - covered
            if rest > 0:
                by_host["no_bench_span"] += rest / first.steps
        return {
            "device_ops": [[k, v / 1e9]
                           for k, v in by_label.most_common(entries)],
            "idle_gaps": [[k, v / 1e9]
                          for k, v in by_host.most_common(entries)],
        }


def _steady_window(modules: Sequence[xplane.Event]
                   ) -> Tuple[Optional[Interval], int]:
    """(window, executions inside it) from the chip's program executions:
    the program with the most device time is the step."""
    by_name: Counter = Counter()
    for e in modules:
        by_name[e.name] += e.end - e.start
    if not by_name:
        return None, 0
    step_name = by_name.most_common(1)[0][0]
    steps = [e for e in modules if e.name == step_name]
    if len(steps) < MIN_STEPS:
        return None, 0
    inner = steps[1:-1]
    return (inner[0].start, inner[-1].end), len(inner)


def main(argv) -> int:
    """``python3 -m benchmark.trace.reduce <file.xplane.pb> [step.hlo.txt]``:
    the numbers of one trace, for reading by hand."""
    import json

    module = hlo.Module(open(argv[2]).read()) if len(argv) > 2 else None
    reduced = Reduced(xplane.load(argv[1]), module)
    if not reduced.usable:
        print("no chip's plane holds four executions of one program")
        return 1
    print(json.dumps({
        "chips": len(reduced.chips),
        "steps": reduced.steps(),
        "window_s": reduced.window_seconds(),
        "busy_s": reduced.busy_seconds(),
        "hvd_compute_grads_ms": reduced.scope_ms_per_step(
            "hvd_compute_grads"),
        "hvd_reduce_and_update_ms": reduced.scope_ms_per_step(
            "hvd_reduce_and_update"),
        "unscoped_ms": reduced.scope_ms_per_step(),
        "collectives_ms": reduced.scope_ms_per_step("", collectives=True),
        "exposed_collective_ms": reduced.exposed_collective_ms_per_step(),
        "breakdown": reduced.breakdown(),
    }, indent=1))
    return 0


if __name__ == "__main__":
    import sys

    sys.exit(main(sys.argv))
