"""(token, expert) pairs a held expert served in a step of a Qwen3-Next
cell, on average over the held experts of every expert layer: the
program's gauge ``model.moe.pairs_per_step`` (counted by the step itself
from the router's choices) over ``model.moe.experts_held`` and the expert
layers.  None from a program without the gauges."""


def read(run):
    from horovod_tpu import metrics

    pairs = metrics.get_gauge("model.moe.pairs_per_step")
    held = metrics.get_gauge("model.moe.experts_held")
    layers = metrics.get_gauge("model.layer_kinds", {"kind": "experts"})
    if pairs is None or not held or not layers:
        return None
    return pairs / held / layers
