"""XLA backend compiles and persistent-cache reads that ended inside the
window (jax's monitoring events): expected 0."""


def read(run):
    return run.builds_in_window
