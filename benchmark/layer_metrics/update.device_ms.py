"""Device time per step and chip of the operations under the program's
``hvd_reduce_and_update`` scope that are not collectives between chips:
casts, bucket copies and the optimizer's update."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_reduce_and_update", collectives=False)
