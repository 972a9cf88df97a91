"""1 - (union of the device's operation intervals) / (traced steady
window), averaged over the chips.  In percent."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return 100.0 * (1.0 - reduced.busy_seconds() / reduced.window_seconds())
