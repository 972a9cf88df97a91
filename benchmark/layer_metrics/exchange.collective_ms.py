"""Device time per step and chip during which a collective between chips
is in progress, whatever else the chip does meanwhile: what
``exchange.exposed_ms`` starts from before other operations are
subtracted, so exposed / collective is the share that is not hidden."""

from benchmark.trace import phase


def read(run):
    return phase.collective_ms(run)
