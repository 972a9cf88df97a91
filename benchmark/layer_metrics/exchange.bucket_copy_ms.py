"""Device time per step and chip of the operations under the per-bucket
scopes ``hvd_sched_bucket<i>_...`` of ``hvd_exchange``, collectives between
chips left out: packing the leaves into a bucket's buffer and cutting them
out again.  None for a program that does not cut
``hvd_reduce_and_update``."""

from benchmark.trace import phase


def read(run):
    return phase.scope_ms(run, phase.EXCHANGE, phase.BUCKET)
