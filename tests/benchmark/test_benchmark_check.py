"""The check that decides ``correct`` fails for what it has to catch: bf16
parameters, a skipped gradient exchange, a zeroed dq — on the tiny
fixtures, with the tolerances the real configurations use."""

import ast
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, manifest
from benchmark.families import gpt as gpt_family, resnet as resnet_family

from tiny_cells import CHECKOUT, HERE, run_tiny


def _losses(capsys):
    out = capsys.readouterr().out
    found = {}
    for line in out.splitlines():
        key, _, value = line.partition(": ")
        if key in ("check.system_losses", "check.reference_losses",
                   "check.replicas_identical"):
            found[key] = ast.literal_eval(value)
    return found


@pytest.mark.parametrize("real, tiny", [
    ("gpt2s", "gpt_tiny"), ("resnet50", "resnet_cut")])
def test_fixture_uses_the_real_tolerance(real, tiny):
    a = manifest.load_json(CHECKOUT / "benchmark/configs" / f"{real}.json")
    b = manifest.load_json(HERE / "fixture/configs" / f"{tiny}.json")
    assert a["check"]["loss_rtol"] == b["check"]["loss_rtol"]
    assert a["check"]["steps"] == b["check"]["steps"] == 4
    assert a["check"]["why"]


@pytest.mark.parametrize("family, cell_name", [
    (gpt_family, "gpt_tiny.dense"), (gpt_family, "gpt_tiny.packed"),
    (resnet_family, "resnet_cut.b8")])
def test_bf16_parameters_fail_the_check(
        family, cell_name, tiny_root, quiet_runtime, monkeypatch, capsys):
    real = family.build

    def build(config, traffic):
        system = real(config, traffic)

        def init(key):
            return jax.tree.map(
                lambda x: x.astype(jnp.bfloat16)
                if jnp.issubdtype(x.dtype, jnp.floating) else x,
                system.init(key))

        return dataclasses.replace(system, init=init)

    monkeypatch.setattr(family, "build", build)
    _, run, correct = run_tiny(tiny_root, cell_name, seconds=0.1)
    seen = _losses(capsys)
    assert not correct
    # the weights are the same, so the first loss still agrees
    assert seen["check.system_losses"][0] == pytest.approx(
        seen["check.reference_losses"][0], rel=4e-4)
    assert run.failed == 0


def test_skipped_exchange_fails_the_check(
        tiny_root, quiet_runtime, monkeypatch, capsys):
    def make_step(hvd, system):  # no DistributedOptimizer: no exchange
        return hvd.distributed_train_step(
            system.loss_fn, system.optimizer, stateful=system.stateful)

    monkeypatch.setattr(harness, "make_step", make_step)
    _, _, correct = run_tiny(tiny_root, "gpt_tiny.dp4", seconds=0.1)
    seen = _losses(capsys)
    assert not correct
    assert seen["check.replicas_identical"] == (False, False)
    assert not check.losses_agree(seen["check.system_losses"],
                                  seen["check.reference_losses"], 4e-4)


@pytest.mark.parametrize("cell_name", ["gpt_tiny.dense", "gpt_tiny.packed"])
def test_zeroed_dq_fails_the_check(
        cell_name, tiny_root, quiet_runtime, monkeypatch, capsys):
    from horovod_tpu.ops import pallas_kernels

    real = pallas_kernels._flash_bwd_chunked

    def zero_dq(*args, **kwargs):
        dq, dk, dv = real(*args, **kwargs)
        return jnp.zeros_like(dq), dk, dv

    monkeypatch.setattr(pallas_kernels, "_flash_bwd_chunked", zero_dq)
    _, _, correct = run_tiny(tiny_root, cell_name, seconds=0.1)
    seen = _losses(capsys)
    assert not correct
    assert seen["check.replicas_identical"] == (True, True)


def test_tiling_keeps_each_chips_sample_on_its_chip():
    sample = (np.arange(8 * 3).reshape(8, 3), np.arange(8))
    tiled = check.tile_for_chips(sample, chips=4, rows_per_chip=6)
    assert tiled[0].shape == (24, 3) and tiled[1].shape == (24,)
    for chip, chunk in enumerate(check.chunks_for_chips(sample, 4)):
        block = tiled[1][chip * 6:(chip + 1) * 6]
        assert sorted(set(block)) == list(chunk[1]) == [2 * chip,
                                                        2 * chip + 1]
        assert list(block) == list(chunk[1]) * 3
    with pytest.raises(ValueError, match="cannot be tiled"):
        check.tile_for_chips(sample, chips=4, rows_per_chip=5)


@pytest.mark.parametrize("system, reference, agree", [
    ([1.0, 0.9], [1.0002, 0.9002], True),
    ([1.0, 0.9], [1.0, 0.902], False),
    ([1.0, float("nan")], [1.0, 0.9], False),
    ([1.0], [1.0, 0.9], False),
])
def test_losses_agree(system, reference, agree):
    assert check.losses_agree(system, reference, 4e-4) is agree
