"""The part of ``qnext.gdn_ms`` under the ``gdn/core`` scope: the delta
rule whose key heads each serve two value heads (``ops/kda.py``: the kernel
pair of ``ops/kda_kernels.py`` where it takes the widths), forward,
backward and recomputation.  None where the program has no such scope."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/gdn/core")
