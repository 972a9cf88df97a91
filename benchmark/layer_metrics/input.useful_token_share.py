"""Non-padding positions over all positions of the batches served inside
the window: a count, which repeats exactly per seed.  In percent."""


def read(run):
    work = run.work()
    if "seq_len" not in run.cell.traffic or not work.positions:
        return None
    return 100.0 * work.units / work.positions
