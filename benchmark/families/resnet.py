"""Bottleneck ResNets through ``models/resnet.py``, trained the way
``utils/benchmarks.build_dp_step`` trains them (stateful step: BatchNorm's
running statistics are the model state)."""

from __future__ import annotations

from typing import Any, Dict

from . import System, dtype_from, optimizer_from


def build(config: Dict[str, Any], traffic: Dict[str, Any]) -> System:
    import jax.numpy as jnp
    import optax

    from horovod_tpu.models.resnet import ResNet

    del traffic
    m = config["model"]
    if (m["batch_norm_epsilon"], m["batch_norm_momentum"]) != (1e-5, 0.9):
        raise ValueError("models/resnet.py fixes BatchNorm at epsilon 1e-5 "
                         "and momentum 0.9")
    if m["bottleneck_expansion"] != 4 or not m["stride_in_3x3"]:
        raise ValueError("models/resnet.py is the v1.5 bottleneck with "
                         "expansion 4")
    model = ResNet(
        stage_sizes=list(m["stage_sizes"]), num_classes=m["num_classes"],
        num_filters=m["num_filters"], stem=m["stem"],
        dtype=dtype_from(config["activation_dtype"]),
    )
    size = m["image_size"]

    def init(key):
        variables = model.init(
            key, jnp.zeros((1, size, size, 3), jnp.float32), train=True)
        return variables["params"], variables["batch_stats"]

    def loss_fn(params, batch_stats, batch):
        images, labels = batch
        logits, updated = model.apply(
            {"params": params, "batch_stats": batch_stats}, images,
            train=True, mutable=["batch_stats"])
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits, labels).mean()
        return loss, updated["batch_stats"]

    return System(
        init=init, loss_fn=loss_fn,
        optimizer=optimizer_from(config["optimizer"]),
        compression=config["compression"], stateful=True,
        element={"kind": "images", "image_size": size,
                 "num_classes": m["num_classes"]},
    )
