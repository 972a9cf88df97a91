"""What XLA's memory analysis says the compiled step holds on each chip:
arguments + outputs + temporaries - aliased (``prof.peak_hbm_bytes``).
Only of a step compiled for the chip: the analysis of a CPU program says
nothing about HBM."""


def read(run):
    if run.platform != "tpu":
        return None
    nbytes = run.step_record.get("peak_hbm_bytes")
    return None if nbytes is None else nbytes / 1e9
