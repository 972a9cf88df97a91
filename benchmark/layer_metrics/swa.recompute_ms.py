"""Device time per step and chip the rematerialised layers spend on their
forward pass a second time (jax's ``rematted_computation`` scope inside a
``block_<i>``): 0 where the model keeps everything."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_compute_grads", "/block_", "rematted_computation")
