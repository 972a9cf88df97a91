"""The check of the Gated DeltaNet / full-attention decoder's cell
(``olmo_hybrid7b``) failing for what it has to catch, on the tiny stand-in
of the cell through the harness.

Four losses on 64 random tokens hardly see a fault that leaves the layers'
outputs as random as they were (PERF.md, PR 32), so the layers' own faults
are planted in the fixture *sharpened* as ``test_benchmark_hybridmoe_
faults.py`` sharpens its own: float32 activations, under which a sound run
agrees to 1e-5, and a rate of 1e-2, at which four steps lean on what the
layers compute.  A fault that changes which weights the system has (the
QK-norm left out, the norms on the sublayers' inputs) hands the reference
the weights under its own names, the missing scales at their initial ones,
so that the check compares programs and not parameter trees.  What holds
each layer to its equations is ``test_benchmark_olmohybrid.py``: logits,
loss and every gradient against the reference."""

import ast
import dataclasses
import json

import jax
import jax.numpy as jnp
import pytest

from benchmark import check
from benchmark.families import olmohybrid as family
from benchmark.reference import olmohybrid as reference
from horovod_tpu.models import transformer

from tiny_cells import TINY, run_tiny

TINY_CELL = TINY["olmo_hybrid7b.ring1x4096"][0]


def _earlier_lines(capsys):
    found = {}
    for line in capsys.readouterr().out.splitlines():
        key, _, value = line.partition(": ")
        if key.startswith("check."):
            found[key] = ast.literal_eval(value)
    return found


def _sharpened(root):
    path = root / "configs" / "olmohybrid_tiny.json"
    config = json.loads(path.read_text())
    config["activation_dtype"] = "float32"
    config["optimizer"].update(learning_rate=1e-2, warmup_from=1e-2)
    path.write_text(json.dumps(config))
    return root


def _configured(monkeypatch, **change):
    """The system built with ``change`` to its TransformerConfig."""
    real = family.transformer_config
    monkeypatch.setattr(
        family, "transformer_config",
        lambda config, traffic: dataclasses.replace(
            real(config, traffic), **change))


def _reference_given(monkeypatch, rename):
    """The reference handed the system's weights under its own names:
    ``rename(block) -> block``."""
    real = reference.loss

    def loss(params, model, batch):
        inner = {name: rename(dict(blk)) if name.startswith("block_")
                 else blk for name, blk in params["params"].items()}
        return real({"params": inner}, model, batch)

    monkeypatch.setattr(reference, "loss", loss)


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


def _decay_dropped(monkeypatch):
    real = transformer.kda
    monkeypatch.setattr(
        transformer, "kda",
        lambda q, k, v, g, beta, seg: real(q, k, v, 0.0 * g, beta, seg))


def _beta_without_its_factor_2(monkeypatch):
    real = transformer.kda
    monkeypatch.setattr(
        transformer, "kda",
        lambda q, k, v, g, beta, seg: real(q, k, v, g, beta / 2.0, seg))


def _gate_a_head_not_a_channel(monkeypatch):
    """The output gate as one value a head (its channels' mean)."""
    real = transformer.rms_gate_heads

    def gated(x, weight, gate, eps, dtype):
        b, t, lanes = gate.shape
        heads = lanes // weight.shape[0]
        return real(x, weight, jnp.mean(gate.reshape(b, t, heads, -1), -1),
                    eps, dtype)

    monkeypatch.setattr(transformer, "rms_gate_heads", gated)


def _qk_norm_left_out(monkeypatch):
    _configured(monkeypatch, qk_norm=False)

    def rename(blk):
        if "attn" in blk:
            attn = dict(blk["attn"])
            for name in ("q", "k"):
                width = attn[name]["Dense_0"]["kernel"].shape[1]
                attn[f"{name}_norm"] = {"scale": jnp.ones((width,))}
            blk["attn"] = attn
        return blk

    _reference_given(monkeypatch, rename)


def _norms_on_the_sublayers_inputs(monkeypatch):
    """Pre-norm blocks: h = x + Mixer(Norm(x)), the scales the same."""
    _configured(monkeypatch, pre_norm=True, post_norm=False)

    def rename(blk):
        blk["ln_attn_post"] = blk.pop("ln_attn")
        blk["ln_mlp_post"] = blk.pop("ln_mlp")
        return blk

    _reference_given(monkeypatch, rename)


def _convolution_left_out(monkeypatch):
    real = transformer._short_conv
    # the taps are still made (the reference reads them by name)
    monkeypatch.setattr(
        transformer, "_short_conv",
        lambda x, taps, seg: x.astype(jnp.float32) + 0.0 * real(x, taps, seg))


# (bfloat16 parameters are the control at the cell's own settings, below:
# at this fixture's rate of 1e-2 they move with every step and agree)
FAULTS = {
    "decay_dropped": _decay_dropped,
    "beta_without_its_factor_2": _beta_without_its_factor_2,
    "gate_a_head_not_a_channel": _gate_a_head_not_a_channel,
    "qk_norm_left_out": _qk_norm_left_out,
    "norms_on_the_sublayers_inputs": _norms_on_the_sublayers_inputs,
    "convolution_left_out": _convolution_left_out,
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
