"""The part of ``gdn.mixer_ms`` under the ``gdn/core`` scope: the delta
rule with one decay a head itself (``ops/kda.py``: the kernel pair of
``ops/kda_kernels.py`` where it takes the widths), forward, backward and
recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/gdn/core")
