"""The packer's own work a batch, in ms: the summed duration of the
program's ``hvd_pack_window`` spans that start inside the steady window
over the steps of that window (a step consumes a batch)."""

from benchmark.trace import phase


def read(run):
    found = phase.span_ms(run, "hvd_pack_window")
    if found is None:
        return None
    busy_ms, _, steps = found
    return busy_ms / steps
