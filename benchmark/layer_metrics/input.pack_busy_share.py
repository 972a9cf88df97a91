"""Share of the steady window, in percent, that the packer's thread spent
in the program's ``hvd_pack_window`` spans (``data/packing.py``: packing a
window of documents and stacking its batches): how near the input layer
is to making the loop wait."""

from benchmark.trace import phase


def read(run):
    found = phase.span_ms(run, "hvd_pack_window")
    if found is None:
        return None
    busy_ms, window_ms, _ = found
    return 100.0 * busy_ms / window_ms
