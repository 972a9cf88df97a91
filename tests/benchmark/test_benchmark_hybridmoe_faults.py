"""The check of the hybrid decoder's cell (``ling3flash``) failing for what
it has to catch, on the tiny stand-in of the cell through the harness.

Four losses on 64 random tokens hardly see a fault that leaves the layers'
outputs as random as they were: at the cell's own settings (bfloat16
activations, four steps at 3e-4) a held expert left out, the rope left off
the shared key or the group limit ignored move a loss by 1e-4 to 4e-4, which
is what bfloat16 activations move it by themselves (PERF.md, PR 32).  So the
faults are planted in the fixture *sharpened*: float32 activations, under
which a sound run agrees to 5e-7, and a rate of 1e-2, at which four steps
lean on what the layers compute.  What holds each layer to its equations is
``test_benchmark_hybridmoe.py``: logits, loss and every gradient against
the reference."""

import ast
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import check
from benchmark.families import hybridmoe as family
from horovod_tpu.models import transformer
from horovod_tpu.parallel import moe

from tiny_cells import TINY, run_tiny

TINY_CELL = TINY["ling3flash.ring1x4096"][0]


def _earlier_lines(capsys):
    found = {}
    for line in capsys.readouterr().out.splitlines():
        key, _, value = line.partition(": ")
        if key.startswith("check."):
            found[key] = ast.literal_eval(value)
    return found


def _sharpened(root):
    path = root / "configs" / "hybridmoe_tiny.json"
    config = json.loads(path.read_text())
    config["activation_dtype"] = "float32"
    config["optimizer"].update(learning_rate=1e-2, warmup_from=1e-2)
    path.write_text(json.dumps(config))
    return root


def _bf16_parameters(monkeypatch):
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


def _a_held_experts_part_left_out(monkeypatch):
    real = moe.route_group_limited

    def route(scores, *args, **kwargs):
        ids, weights = real(scores, *args, **kwargs)
        # the fixture holds experts 2-5: expert 3's pairs go nowhere
        return jnp.where(ids == 3, scores.shape[-1] - 1, ids), weights

    monkeypatch.setattr(moe, "route_group_limited", route)


def _decay_dropped(monkeypatch):
    real = transformer.kda_chunk_major
    monkeypatch.setattr(
        transformer, "kda_chunk_major",
        lambda q, k, v, g, beta, seg: real(q, k, v, 0.0 * g, beta, seg))


def _beta_one(monkeypatch):
    real = transformer.kda_chunk_major
    monkeypatch.setattr(
        transformer, "kda_chunk_major",
        lambda q, k, v, g, beta, seg: real(
            q, k, v, g, jnp.ones_like(beta), seg))


def _rope_left_off_the_shared_key(monkeypatch):
    real = transformer.apply_rope
    monkeypatch.setattr(
        transformer, "apply_rope",
        lambda x, rope: x if x.shape[2] == 1 else real(x, rope))


def _group_limit_ignored(monkeypatch):
    real = moe.route_group_limited
    monkeypatch.setattr(
        moe, "route_group_limited",
        lambda scores, bias, k, n_group, topk_group, scale: real(
            scores, bias, k, 1, 1, scale))


def _shared_expert_left_out(monkeypatch):
    real = moe.TensorParallelMLP
    monkeypatch.setattr(
        moe, "TensorParallelMLP", lambda **kwargs: _Zeroed(real(**kwargs)))


class _Zeroed:
    """A module called as it is, its output multiplied by nothing."""

    def __init__(self, module):
        self.module = module

    def __call__(self, x):
        return 0.0 * self.module(x)


FAULTS = {
    "bf16_parameters": _bf16_parameters,
    "a_held_experts_part_left_out": _a_held_experts_part_left_out,
    "decay_dropped": _decay_dropped,
    "beta_one": _beta_one,
    "rope_left_off_the_shared_key": _rope_left_off_the_shared_key,
    "group_limit_ignored": _group_limit_ignored,
    "shared_expert_left_out": _shared_expert_left_out,
}


@pytest.mark.parametrize("fault", list(FAULTS))
def test_fault_fails_the_check(fault, tiny_root, quiet_runtime, monkeypatch,
                               capsys):
    FAULTS[fault](monkeypatch)
    cell, run, correct = run_tiny(
        _sharpened(tiny_root), TINY_CELL, seed=7, seconds=0.1)
    seen = _earlier_lines(capsys)
    assert not correct
    assert run.failed == 0  # every loss finite: the comparison caught it
    assert seen["check.replicas_identical"] == (True, True)
    rtol = cell.config["check"]["loss_rtol"]
    system, ref = seen["check.system_losses"], seen["check.reference_losses"]
    gaps = check.loss_gaps(system, ref)
    assert max(gaps) > 3 * rtol, gaps  # and by no hair's breadth
    # before any update the weights are the same: the loss of random
    # features is log(vocabulary) whatever a layer computes
    assert gaps[0] <= rtol or fault in ("decay_dropped", "beta_one")


def test_the_sharpened_fixture_is_correct_without_a_fault(
        tiny_root, quiet_runtime, capsys):
    cell, run, correct = run_tiny(
        _sharpened(tiny_root), TINY_CELL, seed=7, seconds=0.1)
    seen = _earlier_lines(capsys)
    assert correct and run.failed == 0
    gaps = check.loss_gaps(
        seen["check.system_losses"], seen["check.reference_losses"])
    assert max(gaps) <= 1e-5, gaps  # float32 against float32


def test_bf16_parameters_fail_the_check_at_the_cells_own_settings(
        tiny_root, quiet_runtime, monkeypatch, capsys):
    """The control the cell's limit is set by (PERF.md section 2)."""
    _bf16_parameters(monkeypatch)
    cell, run, correct = run_tiny(tiny_root, TINY_CELL, seed=7, seconds=0.1)
    seen = _earlier_lines(capsys)
    assert not correct and run.failed == 0
    rtol = cell.config["check"]["loss_rtol"]
    system, ref = seen["check.system_losses"], seen["check.reference_losses"]
    assert check.losses_agree(system[:1], ref[:1], rtol)  # the same weights
    assert not check.losses_agree(system, ref, rtol)


