"""One module per family of configurations: how its model, loss and
optimizer are built from a configuration file, through the entry points a
user of ``horovod_tpu`` calls.  ``build(config, traffic)`` returns a
:class:`System`."""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional


@dataclasses.dataclass(frozen=True)
class System:
    """What the harness needs of a configuration under one traffic mix.

    ``init(key)`` makes the weights (jittable) and returns ``(params,
    model_state)`` with ``model_state`` None where the model has none.
    ``loss_fn`` has the signature ``distributed_train_step`` wants:
    ``(params, batch)`` or, with a model state, ``(params, model_state,
    batch) -> (loss, new_model_state)``.  ``optimizer`` is the plain optax
    transformation: the harness wraps it in ``hvd.DistributedOptimizer``
    with the ``compression`` named, the reference uses it as it is.
    ``element`` says what a row of a batch is, for the traffic generator.
    The plain reference reads ``params`` as they are, by name."""

    init: Callable[[Any], Any]
    loss_fn: Callable
    optimizer: Any
    compression: str
    stateful: bool
    element: Dict[str, Any]


def optimizer_from(spec: Dict[str, Any]):
    """The plain optax transformation a configuration file names; the
    system wraps it in ``hvd.DistributedOptimizer``, the reference uses it
    as it is."""
    import optax

    kind = spec["name"]
    if kind == "adamw":
        return optax.adamw(spec["learning_rate"])
    if kind == "sgd":
        return optax.sgd(spec["learning_rate"],
                         momentum=spec.get("momentum"))
    raise ValueError(f"unknown optimizer {kind!r}")


def dtype_from(name: Optional[str]):
    import jax.numpy as jnp

    return jnp.dtype(name or "float32")
