"""Bytes entering collectives between chips in one step, per chip: the
operands of the collective instructions in the compiled step's HLO."""

def read(run):
    module = run.module()
    return None if module is None else module.exchange_per_step()[1]
