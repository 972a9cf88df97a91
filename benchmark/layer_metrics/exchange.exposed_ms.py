"""Device time per step and chip during which a collective between chips
is in progress and no other operation runs on that chip."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.exposed_collective_ms_per_step()
