"""The part of ``hybrid.kda_ms`` under the ``kda/core`` scope: the delta
rule itself (``ops/kda.py``), forward, backward and recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/kda/core")
