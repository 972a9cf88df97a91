"""GB the compiled step's layout copies write in one step, per chip: the
result bytes of the ``copy`` instructions of the entry computation of the
step's HLO.  Each is an array XLA reads once and writes once in another
layout between two operations that do not agree on one; a copy inside a
fusion does its work on the way and is not counted, nor is the
``copy-start`` / ``copy-done`` pair of a prefetch.  A count of the
program, exact on any chip."""

import re

_ENTRY = re.compile(r"^ENTRY %?([\w.\-]+)", re.MULTILINE)


def read(run):
    module = run.module()
    entry = _ENTRY.search(run.step_hlo or "")
    if module is None or entry is None:
        return None
    return sum(
        ins.result_bytes for ins in module.instructions.values()
        if ins.opcode == "copy" and ins.computation == entry.group(1)) / 1e9
