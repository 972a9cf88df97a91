"""The part of ``qnext.moe_ms`` that is no expert's matmul: the router, the
pairs' sort and gather (``dispatch``) and the weighted scatter back
(``combine``): everything under ``moe`` but ``experts`` and ``shared``
(the shared expert and its gate)."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_compute_grads", "/moe/", without=("/experts", "/shared"))
