"""The entry scripts measure on a TPU and nowhere else: on a CPU backend
``chip_smoke.main()`` and ``bench.py`` exit non-zero before they train
anything, and write no number."""

import os

import pytest

import bench
import chip_smoke


def _no_training(monkeypatch, module, names):
    def boom(*args, **kwargs):
        raise AssertionError("trained on a CPU backend")

    for name in names:
        monkeypatch.setattr(module, name, boom)


def test_chip_smoke_main_refuses_cpu(monkeypatch, capsys):
    _no_training(monkeypatch, chip_smoke, ("train", "parity", "kernels"))
    assert chip_smoke.main() == 2
    out, err = capsys.readouterr()
    assert out == ""  # no result line
    assert "no TPU" in err


def test_bench_main_refuses_cpu(monkeypatch, capsys):
    _no_training(monkeypatch, bench, ("bench_resnet", "bench_gpt",
                                      "_device_free_records"))
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert capsys.readouterr().out == ""  # no JSON, no device metric


def test_bench_has_no_success_exit_on_failure():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    with open(path) as f:
        source = f.read()
    assert "sys.exit(0)" not in source
    for gone in ("run_device_probe", "_cpu_resnet_fallback",
                 "emit_structured_abort", "cpu_sim"):
        assert gone not in source
