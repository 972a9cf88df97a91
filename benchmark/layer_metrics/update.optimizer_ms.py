"""Device time per step and chip of the operations under the program's
``hvd_update`` scope: the inner optimizer's update of its state and the
parameters' (``optax.apply_updates``), as far as XLA named their fusions
by them.  None for a program that does not cut ``hvd_reduce_and_update``."""

from benchmark.trace import phase


def read(run):
    return phase.scope_ms(run, phase.UPDATE)
