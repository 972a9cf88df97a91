"""Fixtures of the benchmark's tests: the real manifest with tiny
configurations and mixes in place of the real ones, so that tier-1 runs
the harness's own body on the virtual CPU mesh."""

import json
import shutil

import pytest

from tiny_cells import CHECKOUT, HERE, TINY


@pytest.fixture()
def tiny_root(tmp_path):
    """A root directory whose ``BENCHMARK.json`` is the real one with every
    cell replaced by its tiny stand-in (same metrics, same readers)."""
    root = tmp_path / "root"
    shutil.copytree(HERE / "fixture", root)
    manifest = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    manifest["configs"] = [
        {"name": name, "file": f"configs/{name}.json"}
        for name in sorted({config for _, config, _, _ in TINY.values()})
    ]
    manifest["workloads"] = [
        {"name": cell, "config": config, "traffic": traffic, "chips": chips}
        for cell, config, traffic, chips in TINY.values()
    ]
    for key in ("end_to_end", "per_layer"):
        for metric in manifest[key]:
            if "workloads" in metric:
                metric["workloads"] = [
                    TINY[w][0] for w in metric["workloads"]]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    return root


@pytest.fixture()
def quiet_runtime(monkeypatch):
    """No runtime left over from another test or for the next one, and no
    persistent compilation cache switched on for the rest of the worker's
    tests (``harness.run_cell`` would enable the checkout's)."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu.utils import compile_cache

    monkeypatch.setattr(compile_cache, "enable", lambda: "off in the tests")
    keep = {
        name: getattr(jax.config, name) for name in (
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    }
    hvd.shutdown()
    yield
    hvd.shutdown()
    for name, value in keep.items():
        jax.config.update(name, value)
