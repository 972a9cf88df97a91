"""GB the compiled step's entry computation writes under the program's
``hvd_update`` scope in one step, per chip: the result bytes of its
instructions named under that scope (plumbing that writes nothing left
out; ``update.written_instructions`` on an earlier line).  Beside the size
of the optimizer's state and the parameters it says how much of the new
state is written by operations that carry another scope's name.  A count
of the program, exact on any chip."""

from benchmark.harness import say
from benchmark.trace import phase


def read(run):
    found = phase.update_writes(run)
    if found is None:
        return None
    nbytes, instructions = found
    say("update.written_instructions", instructions)
    return nbytes / 1e9
