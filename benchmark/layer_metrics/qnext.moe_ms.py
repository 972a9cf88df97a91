"""Device time per step and chip of the expert layers of a Qwen3-Next
cell: the operations under ``hvd_compute_grads`` inside a ``moe`` module's
scope: the softmax router, sorted dispatch, the grouped product over the
held experts, combine and the gated shared expert, forward, backward and
recomputation.  None where the program has no such scope."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/moe/")
