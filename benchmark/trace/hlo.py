"""What the reduction needs from the compiled step's HLO text
(``Compiled.as_text()``): for every instruction its opcode, the scope path
jax recorded for it (``op_name``), the bytes of its operands, and whether
it is a collective between chips or a Mosaic kernel.  Device-trace events
carry the instruction's name, so this is the join from an event to a
``jax.named_scope`` where the trace itself has no scope path."""

from __future__ import annotations

import dataclasses
import math
import re
from typing import Dict, List, Optional, Tuple

COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "collective-permute",
    "all-to-all", "collective-broadcast", "ragged-all-to-all",
)
_ITEMSIZE = {
    "pred": 1, "s4": 0.5, "u4": 0.5, "s8": 1, "u8": 1, "s16": 2, "u16": 2,
    "f16": 2, "bf16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
    "f64": 8, "c64": 8, "c128": 16,
}
_ARRAY = re.compile(r"\b([a-z]+[0-9]*(?:e[0-9]m[0-9]\w*)?)\[([0-9, ]*)\]")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_TARGET = re.compile(r'custom_call_target="([^"]*)"')
_GROUPS = re.compile(r"replica_groups=(\{\{[^}]*\}|\[[0-9, ]+\])")
_IDENT = re.compile(r"%?([\w.\-]+)")


@dataclasses.dataclass
class Instruction:
    name: str
    opcode: str
    computation: str
    op_name: str
    result_bytes: float
    operands: List[str]
    custom_call_target: Optional[str]
    group_size: Optional[int]

    @property
    def base_collective(self) -> Optional[str]:
        """``all-reduce`` for ``all-reduce``, ``all-reduce-start`` and
        ``all-reduce-done``; None for anything else."""
        for c in COLLECTIVES:
            if self.opcode in (c, c + "-start", c + "-done"):
                return c
        return None


def shape_bytes(shape: str) -> float:
    """Bytes of every array in a shape string (a tuple's arrays added)."""
    total = 0.0
    for dtype, dims in _ARRAY.findall(shape):
        size = _ITEMSIZE.get(dtype, 1 if dtype.startswith("f8") else None)
        if size is None:
            continue
        dims = [int(d) for d in dims.replace(" ", "").split(",") if d]
        total += size * math.prod(dims)
    return total


def _split_top(text: str, start: int, stop_at: str) -> int:
    """Index of the first char of ``stop_at`` at bracket depth 0 from
    ``start`` on (or len(text))."""
    depth = 0
    for i in range(start, len(text)):
        ch = text[i]
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            if depth == 0 and ch in stop_at:
                return i
            depth -= 1
        elif depth == 0 and ch in stop_at:
            return i
    return len(text)


def _group_size(attrs: str) -> Optional[int]:
    m = _GROUPS.search(attrs)
    if not m:
        return None
    text = m.group(1)
    if text.startswith("{{"):
        first = text[2:].split("}")[0]
        return len([x for x in first.split(",") if x.strip()])
    dims = [int(x) for x in text.strip("[]").split(",") if x.strip()]
    return dims[-1] if dims else None


def parse(text: str) -> Dict[str, Instruction]:
    """Every instruction of every computation of an HLO module, by name."""
    out: Dict[str, Instruction] = {}
    computation = ""
    for raw in text.splitlines():
        line = raw.strip()
        if line.endswith("{") and " = " not in line.split("(", 1)[0]:
            head = line[len("ENTRY "):] if line.startswith("ENTRY ") else line
            m = _IDENT.match(head)
            if m:
                computation = m.group(1)
            continue
        if " = " not in line:
            continue
        if line.startswith("ROOT "):
            line = line[len("ROOT "):]
        m = _IDENT.match(line)
        if not m or line[m.end():m.end() + 3] != " = ":
            continue
        name = m.group(1)
        rest = line[m.end() + 3:]
        shape_end = _split_top(rest, 0, " ")
        shape = rest[:shape_end]
        after = rest[shape_end + 1:]
        paren = after.find("(")
        if paren < 0:
            continue
        opcode = after[:paren].strip()
        close = _split_top(after, paren + 1, ")")
        operand_text = after[paren + 1:close]
        attrs = after[close + 1:]
        operands = []
        pos = 0
        while pos < len(operand_text):
            end = _split_top(operand_text, pos, ",")
            token = operand_text[pos:end].strip()
            # an operand is "%name" or "shape %name"
            ident = token.split(" ")[-1] if token else ""
            im = _IDENT.fullmatch(ident)
            if im:
                operands.append(im.group(1))
            pos = end + 1
        name_m = _OP_NAME.search(attrs)
        target_m = _TARGET.search(attrs)
        out[name] = Instruction(
            name=name, opcode=opcode, computation=computation,
            op_name=name_m.group(1) if name_m else "",
            result_bytes=shape_bytes(shape), operands=operands,
            custom_call_target=target_m.group(1) if target_m else None,
            group_size=_group_size(attrs),
        )
    return out


class Module:
    """The parsed step with the questions the reduction asks of it."""

    def __init__(self, text: str):
        self.instructions = parse(text)

    def get(self, event_name: str) -> Optional[Instruction]:
        return self.instructions.get(instruction_name(event_name))

    def collective_kind(self, event_name: str) -> Optional[str]:
        """The collective between chips an event is.  One inside a group of
        one chip moves nothing and does not count."""
        ins = self.get(event_name)
        if ins is None or not _between_chips(ins):
            return None
        return ins.base_collective

    def scope_of(self, event_name: str) -> str:
        ins = self.get(event_name)
        return ins.op_name if ins is not None else ""

    def is_mosaic(self, event_name: str) -> bool:
        ins = self.get(event_name)
        return (ins is not None and ins.opcode == "custom-call"
                and ins.custom_call_target == "tpu_custom_call")

    def exchange_per_step(self) -> Tuple[int, float]:
        """(collective calls, operand bytes) one execution of the module
        issues between chips, per chip.  A ``-done`` is its ``-start``'s
        other half and is not counted again; a collective inside a loop
        body is counted once (the step's exchange has no loop)."""
        calls, nbytes = 0, 0.0
        for ins in self.instructions.values():
            if not ins.base_collective or ins.opcode.endswith("-done"):
                continue
            if not _between_chips(ins):
                continue
            calls += 1
            for name in ins.operands:
                operand = self.instructions.get(name)
                if operand is not None:
                    nbytes += operand.result_bytes
        return calls, nbytes


def instruction_name(event_name: str) -> str:
    """A device-trace event is named by its instruction's whole text,
    ``%fusion.12 = bf16[...] fusion(...)``; the instruction's name is its
    first word without the ``%``."""
    return event_name.lstrip("%").split(" ", 1)[0]


def _between_chips(ins: Instruction) -> bool:
    return ins.group_size is None or ins.group_size > 1
