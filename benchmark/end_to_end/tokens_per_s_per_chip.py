"""Non-padding tokens of the steps completed inside the window, over the
window's wall time, over the chips."""


def read(run):
    if "seq_len" not in run.cell.traffic:
        return None
    return run.work().units / run.window_seconds / run.chips
