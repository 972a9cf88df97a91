"""The part of ``loop.blocks_ms`` that runs the layers' forward pass again
inside the backward (jax's ``rematted_computation`` scope of a
``jax.checkpoint``): 0 where the model keeps everything."""


def read(run):
    reduced = run.reduced()
    if reduced is None:
        return None
    return reduced.scope_ms_per_step(
        "hvd_compute_grads", "/block_", "rematted_computation")
