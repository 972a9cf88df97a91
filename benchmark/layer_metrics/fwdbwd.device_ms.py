"""Device time per step and chip of the operations under the program's
``hvd_compute_grads`` scope (forward, loss and backward)."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads")
