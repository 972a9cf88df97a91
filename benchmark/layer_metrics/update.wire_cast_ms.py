"""Device time per step and chip of the operations under ``hvd_exchange``
and ``wire_out`` or ``wire_in``, collectives left out: the casts of the
gradients to the wire's type and back (and the barrier between the last
bucket and the update) that stand as operations of their own.  A cast XLA
fused into a neighbour is in that neighbour's time;
``update.wire_cast_gb_per_step`` counts those too."""

from benchmark.trace import phase


def read(run):
    return phase.wire_cast_ms(run)
