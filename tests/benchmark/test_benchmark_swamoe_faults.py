"""The check of the window/full-attention decoder's cell (``laguna_xs2``)
failing for what it has to catch, on the tiny stand-in of the cell through
the harness.

Four losses on 64 random tokens hardly see a fault that leaves the layers'
outputs as random as they were (PERF.md, PR 32), so the layers' own faults
are planted in the fixture *sharpened* as ``test_benchmark_hybridmoe_
faults.py`` sharpens its own: float32 activations, under which a sound run
agrees to 1e-5, and a rate of 1e-2, at which four steps lean on what the
layers compute.  What holds each layer to its equations is
``test_benchmark_swamoe.py``: logits, loss and every gradient against the
reference."""

import ast
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import check
from benchmark.families import swamoe as family
from horovod_tpu.models import transformer
from horovod_tpu.parallel import moe

from tiny_cells import TINY, run_tiny

TINY_CELL = TINY["laguna_xs2.ring1x8192"][0]


def _earlier_lines(capsys):
    found = {}
    for line in capsys.readouterr().out.splitlines():
        key, _, value = line.partition(": ")
        if key.startswith("check."):
            found[key] = ast.literal_eval(value)
    return found


def _sharpened(root):
    path = root / "configs" / "swamoe_tiny.json"
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


def _window_ignored(monkeypatch):
    real = transformer.flash_attention
    monkeypatch.setattr(
        transformer, "flash_attention",
        lambda *args, window=None, **kwargs: real(*args, **kwargs))


def _query_heads_read_modulo(monkeypatch):
    """Query head h reads key/value head h % G, not h // (H / G)."""
    real = transformer.flash_attention

    def modulo(q, k, v, *args, **kwargs):
        times = q.shape[2] // k.shape[2]
        return real(q, jnp.tile(k, (1, 1, times, 1)),
                    jnp.tile(v, (1, 1, times, 1)), *args, **kwargs)

    monkeypatch.setattr(transformer, "flash_attention", modulo)


def _rule_changed(monkeypatch, **change):
    real = family.rope_rule
    monkeypatch.setattr(
        family, "rope_rule", lambda m, layer_type: dataclasses.replace(
            real(m, layer_type), **change))


def _rotated_share_ignored(monkeypatch):
    _rule_changed(monkeypatch, dim=0)  # the whole head turns


def _yarn_factor_left_off(monkeypatch):
    _rule_changed(monkeypatch, attention_factor=1.0)


def _gate_left_out(monkeypatch):
    real = transformer._gate_heads
    # the gate's matrix is still made (the reference reads it by name)
    monkeypatch.setattr(
        transformer, "_gate_heads",
        lambda x, out: out + 0.0 * real(x, out))


def _a_held_experts_part_left_out(monkeypatch):
    real = moe.route_group_limited

    def route(scores, *args, **kwargs):
        ids, weights = real(scores, *args, **kwargs)
        # the fixture holds experts 4-7: expert 4's pairs go nowhere
        return jnp.where(ids == 4, scores.shape[-1] - 1, ids), weights

    monkeypatch.setattr(moe, "route_group_limited", route)


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


# (bfloat16 parameters are the control at the cell's own settings, below:
# at this fixture's rate of 1e-2 they move with every step and agree)
FAULTS = {
    "window_ignored": _window_ignored,
    "query_heads_read_modulo": _query_heads_read_modulo,
    "rotated_share_ignored": _rotated_share_ignored,
    "yarn_factor_left_off": _yarn_factor_left_off,
    "gate_left_out": _gate_left_out,
    "a_held_experts_part_left_out": _a_held_experts_part_left_out,
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
