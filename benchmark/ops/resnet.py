"""Operations a bottleneck ResNet (v1.5: the stride sits in the 3x3)
requires, convolution by convolution, from the sizes in its configuration
file (``model``).  Two operations per multiply-add: the 4.1 G usually
quoted for ResNet-50 are multiply-adds, so its forward pass is 8.2 GFLOP
per image and not 4.1.
"""

from __future__ import annotations

from typing import Any, Dict, List, Tuple

# (name, output height = width, kernel height = width, in, out channels)
Conv = Tuple[str, int, int, int, int]


def convolutions(model: Dict[str, Any]) -> List[Conv]:
    """Every convolution of the forward pass, in order."""
    if model["stem"] != "conv7":
        raise ValueError(f"no operation count for stem {model['stem']!r}")
    size = model["image_size"] // 2  # 7x7, stride 2
    width = model["num_filters"]
    convs: List[Conv] = [("conv_init", size, 7, 3, width)]
    size //= 2  # 3x3 max pool, stride 2
    channels = width
    expansion = model["bottleneck_expansion"]
    block = 0
    for stage, count in enumerate(model["stage_sizes"]):
        filters = width * 2 ** stage
        for j in range(count):
            stride = 2 if stage > 0 and j == 0 else 1
            out = size // stride
            name = f"BottleneckBlock_{block}"
            convs.append((f"{name}/Conv_0", size, 1, channels, filters))
            convs.append((f"{name}/Conv_1", out, 3, filters, filters))
            convs.append(
                (f"{name}/Conv_2", out, 1, filters, filters * expansion))
            if stride != 1 or channels != filters * expansion:
                convs.append((f"{name}/conv_proj", out, 1, channels,
                              filters * expansion))
            channels, size, block = filters * expansion, out, block + 1
    return convs


def forward_flops_per_image(model: Dict[str, Any]) -> float:
    total = 0.0
    for _, out, k, c_in, c_out in convolutions(model):
        total += 2.0 * out * out * k * k * c_in * c_out
    channels = (model["num_filters"] * 2 ** (len(model["stage_sizes"]) - 1)
                * model["bottleneck_expansion"])
    return total + 2.0 * channels * model["num_classes"]  # the classifier


def train_flops(model: Dict[str, Any], units: float, sum_sq: float = 0.0,
                causal: bool = True) -> float:
    """Forward and backward over ``units`` images (``sum_sq`` and
    ``causal`` belong to token batches and are not used)."""
    del sum_sq, causal
    return 3.0 * forward_flops_per_image(model) * units
