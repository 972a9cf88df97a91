"""GB the wire's casts produce in one step, per chip: the result bytes of
the ``convert`` instructions of the compiled step traced under ``wire_out``
or ``wire_in``, in the entry computation and in fused computations alike.
An earlier line (``update.wire_cast_converts``) says how many stand alone,
how many are fused, and under which part of the step the fusions that hold
them are named.  A count of the program, exact on any chip."""

from benchmark.harness import say
from benchmark.trace import phase


def read(run):
    found = phase.wire_converts(run)
    if found is None:
        return None
    nbytes, where = found
    say("update.wire_cast_converts", where)
    return nbytes / 1e9
