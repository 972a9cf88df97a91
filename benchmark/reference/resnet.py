"""Bottleneck ResNet (He et al., arXiv:1512.03385, table 1) in its v1.5
form: the stride of a down-sampling block sits in its 3x3 convolution, as
in torchvision.  NHWC, training mode: BatchNorm normalises with the
batch's own moments (biased variance).

Departures from the publication, each because the system under test is so
(``horovod_tpu/models/resnet.py``): "SAME" padding as XLA defines it (a
stride-2 3x3 convolution or pooling pads one row at the far edge only,
where torchvision pads both), and the epsilon of BatchNorm comes from the
configuration file.  (The zero-initialised last BatchNorm scale of each
block is a matter of the weights, which the system makes.)
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from jax import lax

_DIMS = ("NHWC", "HWIO", "NHWC")


def _conv(x, kernel, stride=1, padding="SAME"):
    return lax.conv_general_dilated(
        x, kernel, (stride, stride), padding, dimension_numbers=_DIMS)


def _batch_norm(x, p, eps):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x - mean), axis=(0, 1, 2))
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def logits(params, model: Dict[str, Any], images):
    """[N, H, W, 3] images -> [N, classes] float32 logits."""
    eps = model["batch_norm_epsilon"]
    x = _conv(images.astype(jnp.float32), params["conv_init"]["kernel"],
              stride=2, padding=[(3, 3), (3, 3)])
    x = jax.nn.relu(_batch_norm(x, params["bn_init"], eps))
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 3, 3, 1), (1, 2, 2, 1),
                          "SAME")
    block = 0
    for stage, count in enumerate(model["stage_sizes"]):
        for j in range(count):
            p = params[f"BottleneckBlock_{block}"]
            stride = 2 if stage > 0 and j == 0 else 1
            y = _conv(x, p["Conv_0"]["kernel"])
            y = jax.nn.relu(_batch_norm(y, p["BatchNorm_0"], eps))
            y = _conv(y, p["Conv_1"]["kernel"], stride)
            y = jax.nn.relu(_batch_norm(y, p["BatchNorm_1"], eps))
            y = _conv(y, p["Conv_2"]["kernel"])
            y = _batch_norm(y, p["BatchNorm_2"], eps)
            if "conv_proj" in p:
                x = _conv(x, p["conv_proj"]["kernel"], stride)
                x = _batch_norm(x, p["norm_proj"], eps)
            x = jax.nn.relu(x + y)
            block += 1
    x = jnp.mean(x, axis=(1, 2))
    return x @ params["Dense_0"]["kernel"] + params["Dense_0"]["bias"]


def loss(params, model: Dict[str, Any], batch) -> jax.Array:
    """Mean cross-entropy of one batch ``(images, labels)``."""
    images, labels = batch
    lg = logits(params, model, images)
    logz = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(logz - picked)
