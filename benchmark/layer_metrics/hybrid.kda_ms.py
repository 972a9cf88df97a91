"""Device time per step and chip of the delta-rule mixers (KDA): the
operations under ``hvd_compute_grads`` inside a ``kda`` module's scope:
projections, convolutions, gates, the chunked core, the output norm and
gate, forward, backward and recomputation."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step("hvd_compute_grads", "/kda/")
