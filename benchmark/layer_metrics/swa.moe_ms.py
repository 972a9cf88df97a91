"""Device time per step and chip of the expert layers' FFNs: the operations
under ``hvd_compute_grads`` inside a ``moe`` module's scope: router, sorted
dispatch, the grouped product over the held experts, combine and the
shared expert, forward, backward and recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/moe/")
