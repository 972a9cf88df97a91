"""``hybrid.conv_kernel_layers`` and ``gdn.conv_kernel_layers``: the gauge
``model.conv.kernel_layers`` (delta-rule layers whose short convolutions,
SiLU and norms ran as ``ops/kda_kernels.py``'s convolution pair) as the
benchmark reads it, in the one cell each that the manifest lists, and
nothing from a program that sets no such gauge (the parent's)."""

import importlib.util
import json

import pytest

from benchmark import manifest
from horovod_tpu import metrics

from tiny_cells import CHECKOUT

CELLS = {"hybrid.conv_kernel_layers": "ling3flash.ring1x4096",
         "gdn.conv_kernel_layers": "olmo_hybrid7b.ring1x4096"}


def _reader(name):
    path = manifest.PACKAGE_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


@pytest.mark.parametrize("name", sorted(CELLS))
def test_reader_gives_the_gauge_or_nothing(name):
    read = _reader(name)
    metrics.clear_gauge("model.conv.kernel_layers")
    assert read(None) is None
    metrics.set_gauge("model.conv.kernel_layers", 3)
    try:
        assert read(None) == 3
    finally:
        metrics.clear_gauge("model.conv.kernel_layers")


@pytest.mark.parametrize("name", sorted(CELLS))
def test_manifest_lists_the_metric_in_its_one_cell(name):
    entries = json.loads((CHECKOUT / "BENCHMARK.json").read_text())[
        "per_layer"]
    (entry,) = [e for e in entries if e["name"] == name]
    assert entry == {
        "name": name, "unit": "count", "better": "higher",
        "source": "program_counter", "layer": "kernels",
        "moves": "tokens_per_s_per_chip", "workloads": [CELLS[name]]}
    cell = manifest.load_cell(CELLS[name])
    assert name in {m.name for m in cell.per_layer}
    other = manifest.load_cell("gpt2s.dense")
    assert name not in {m.name for m in other.per_layer}
