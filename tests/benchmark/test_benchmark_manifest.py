"""``BENCHMARK.json`` and every data file of the benchmark load, name each
other consistently and keep to the contract's limits."""

import importlib
import importlib.util
import json
import re

import pytest

from benchmark import manifest, peaks

from tiny_cells import CHECKOUT

PACKAGE = manifest.PACKAGE_DIR
NAME = re.compile(r"[A-Za-z0-9][A-Za-z0-9_.-]{0,63}\Z")
PLAIN_PATH = re.compile(r"[A-Za-z0-9_./-]+\Z")
MANIFEST = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _data_files():
    for sub in ("configs", "traffic"):
        yield from sorted((PACKAGE / sub).glob("*.json"))


def _reader_files():
    for sub in ("end_to_end", "layer_metrics"):
        yield from sorted((PACKAGE / sub).glob("*.py"))


def test_manifest_has_exactly_the_contracts_keys():
    assert set(MANIFEST) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}
    assert MANIFEST["command"][:3] == ["python3", "-m", "benchmark.run"]
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert len((CHECKOUT / "BENCHMARK.json").read_bytes()) <= 64 * 1024
    for path in MANIFEST["paths"]:
        assert (CHECKOUT / path).is_dir()


@pytest.mark.parametrize("path", list(_data_files()) + list(_reader_files()),
                         ids=lambda p: f"{p.parent.name}/{p.name}")
def test_file_loads_and_has_a_plain_name(path):
    assert NAME.match(path.stem), path
    assert PLAIN_PATH.match(str(path.relative_to(CHECKOUT)))
    if path.suffix == ".json":
        assert isinstance(manifest.load_json(path), dict)
    else:
        spec = importlib.util.spec_from_file_location("reader", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        assert callable(module.read) and module.__doc__


@pytest.mark.parametrize(
    "cell", [w["name"] for w in MANIFEST["workloads"]])
def test_cell_loads_with_its_files_and_metrics(cell):
    loaded = manifest.load_cell(cell)
    family = loaded.config["family"]
    for package in ("families", "reference", "ops"):
        importlib.import_module(f"benchmark.{package}.{family}")
    assert loaded.traffic["rows"] % loaded.chips == 0
    names = [m.name for m in loaded.end_to_end]
    assert "setup_s" in names and len(names) >= 2
    assert loaded.per_layer
    for metric in loaded.end_to_end + loaded.per_layer:
        sub = "end_to_end" if metric.end_to_end else "layer_metrics"
        assert (PACKAGE / sub / f"{metric.name}.py").is_file(), metric.name


def test_metrics_keep_to_the_contract():
    end_to_end = {m["name"]: m for m in MANIFEST["end_to_end"]}
    cells = {w["name"] for w in MANIFEST["workloads"]}
    for m in MANIFEST["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert end_to_end["setup_s"]["bound"] == 0.1
    for m in MANIFEST["per_layer"]:
        assert m["source"] in SOURCES and "bound" not in m
        moved = end_to_end[m["moves"]]
        # reported only where the metric it moves is
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
    for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("higher", "lower")
        assert set(m.get("workloads", [])) <= cells
    names = [m["name"] for m in MANIFEST["end_to_end"] + MANIFEST["per_layer"]]
    assert len(names) == len(set(names))
    assert any(m["name"].endswith("_roofline") and m["unit"] == "%"
               for m in MANIFEST["per_layer"])


def test_cells_keep_to_the_contract():
    cells = MANIFEST["workloads"]
    assert 2 <= len(cells) <= 24
    pairs = [(w["config"], w["traffic"]) for w in cells]
    assert len(pairs) == len(set(pairs))
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    used = {w["config"] for w in cells}
    files = [c["file"] for c in MANIFEST["configs"]]
    assert len(files) == len(set(files))
    for c in MANIFEST["configs"]:
        assert c["name"] in used
        assert c["file"].startswith(tuple(MANIFEST["paths"]))
        assert manifest.load_json(CHECKOUT / c["file"])["reduced"] == \
            c["reduced"]
    for entry in cells + MANIFEST["configs"]:
        assert NAME.match(entry["name"]) and len(entry["why"]) <= 200


def test_unknown_names_are_errors():
    with pytest.raises(manifest.ManifestError, match="no workload"):
        manifest.load_cell("gpt2s.nothing")
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("cpu")
    assert peaks.for_kind("TPU v5 lite")["bf16_flops_per_s"] == 197e12
