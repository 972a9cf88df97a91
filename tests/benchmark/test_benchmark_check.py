"""The check that decides ``correct`` fails for what it has to catch: bf16
parameters, a skipped gradient exchange, a zeroed dq, a step that leaves
its state as it was, other weights under the reference — on the tiny
fixtures, with the tolerances the real configurations use — and the
reference trains alone on the device, donating what it overwrites."""

import ast
import dataclasses
import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, manifest
from benchmark.families import gpt as gpt_family, resnet as resnet_family

from benchmark.traffic import Traffic

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


def test_unchanged_state_fails_the_check(
        tiny_root, quiet_runtime, monkeypatch, capsys):
    import optax

    def make_step(hvd, system):  # the step runs and moves nothing
        return hvd.distributed_train_step(
            system.loss_fn, hvd.DistributedOptimizer(optax.set_to_zero()),
            stateful=system.stateful)

    monkeypatch.setattr(harness, "make_step", make_step)
    _, run, correct = run_tiny(tiny_root, "gpt_tiny.dense", seconds=0.1)
    seen = _losses(capsys)
    assert not correct and run.failed == 0
    assert len(set(seen["check.system_losses"])) == 1
    value, limit = run.compared["loss_gap_step_0"]
    assert value <= limit
    value, limit = run.compared["loss_gap_step_3"]
    assert value > 10 * limit


def test_other_weights_under_the_reference_fail_the_check(
        tiny_root, quiet_runtime, monkeypatch):
    made = []

    def fingerprint(tree):
        made.append(len(made))
        return made[-1]

    monkeypatch.setattr(check, "fingerprint", fingerprint)
    _, run, correct = run_tiny(tiny_root, "gpt_tiny.dense", seconds=0.1)
    assert made == [0, 1] and not correct
    assert run.compared["weights_differ"] == (1.0, 0.0)
    assert all(value <= limit for name, (value, limit)
               in run.compared.items() if name != "weights_differ")


# ------------------------------------------- the reference alone on the chip
def _reference_job(config_name, traffic_name, chips):
    """(loss, model, optimizer, params, chunks) of a tiny configuration, as
    the harness hands them to ``check.train_reference``."""
    config = manifest.load_json(
        HERE / "fixture/configs" / f"{config_name}.json")
    mix = manifest.load_json(HERE / "fixture/traffic" / f"{traffic_name}.json")
    family = importlib.import_module(f"benchmark.families.{config['family']}")
    reference = importlib.import_module(
        f"benchmark.reference.{config['family']}")
    system = family.build(config, mix)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("world",))
    sample = Traffic(mix, system.element, mesh, "world", seed=3).sample(
        chips * config["check"]["sample_rows_per_chip"])
    params, _ = jax.jit(system.init)(jax.random.PRNGKey(3))
    return (reference.loss, config["model"], system.optimizer, params,
            check.chunks_for_chips(sample, chips))


def _loop_without_donation(loss, model, optimizer, params, chunks, steps):
    """``check.train_reference`` as it was before anything was donated."""
    import optax

    value_and_grad = jax.jit(
        lambda p, batch: jax.value_and_grad(
            lambda q: loss(q, model, batch))(p))

    @jax.jit
    def update(p, state, grads):
        updates, state = optimizer.update(grads, state, p)
        return optax.apply_updates(p, updates), state

    chunks = [jax.device_put(c) for c in chunks]
    n, out = float(len(chunks)), []
    with jax.default_matmul_precision("highest"):
        state = optimizer.init(params)
        for k in range(steps):
            total, grads = value_and_grad(params, chunks[0])
            for chunk in chunks[1:]:
                value, g = value_and_grad(params, chunk)
                total = total + value
                grads = jax.tree.map(lambda a, b: a + b, grads, g)
            out.append(float(total) / n)
            if k + 1 < steps:
                grads = jax.tree.map(lambda a: a / n, grads)
                params, state = update(params, state, grads)
    return out


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("config_name, traffic_name", [
    ("gpt_tiny", "ring-8x64"), ("looplm_tiny", "ring-2x64")])
def test_donating_reference_gives_the_same_floats(
        config_name, traffic_name, chips):
    loss, model, optimizer, params, chunks = _reference_job(
        config_name, traffic_name, chips)
    want = _loop_without_donation(loss, model, optimizer, params, chunks, 4)
    assert all(not x.is_deleted() for x in jax.tree.leaves(params))
    trained = check.train_reference(
        loss, model, optimizer, params, chunks, 4, jax.devices()[0])
    assert trained.losses == want and len(set(want)) == 4
    assert set(trained.program_bytes) == (
        {"value_and_grad", "update"} | ({"add"} if chips > 1 else set()))
    # what it overwrote is gone: the caller hands it weights of its own
    assert all(x.is_deleted() for x in jax.tree.leaves(params))


def test_float32_on_copies_only_what_it_must():
    device = jax.devices()[0]
    tree = {"w": jnp.ones((4, 4), jnp.bfloat16), "b": jnp.ones((4,)),
            "n": jnp.arange(3)}
    got = check.float32_on(tree, device)
    assert got["w"].dtype == got["b"].dtype == jnp.float32
    assert got["n"].dtype == tree["n"].dtype
    assert got["b"].unsafe_buffer_pointer() == tree["b"].unsafe_buffer_pointer()
    assert check.fingerprint(tree) == check.fingerprint(
        jax.tree.map(jnp.array, tree))
    assert check.fingerprint(tree) != check.fingerprint(
        dict(tree, b=tree["b"].at[2].set(1.0000001)))


@pytest.mark.parametrize("cell_name", [
    "gpt_tiny.dense", "gpt_tiny.dp4", "resnet_cut.b8",
    "looplm_tiny.ring2x64"])
def test_nothing_of_the_system_is_alive_when_the_reference_trains(
        cell_name, tiny_root, quiet_runtime, monkeypatch):
    real = check.train_reference
    seen = {}

    def train_reference(loss, model, optimizer, params, *args):
        seen["alive"] = sum(x.nbytes for x in jax.live_arrays())
        seen["weights"] = sum(x.nbytes for x in jax.tree.leaves(params))
        seen["float32"] = all(
            x.dtype == jnp.float32 for x in jax.tree.leaves(params)
            if jnp.issubdtype(x.dtype, jnp.floating))
        return real(loss, model, optimizer, params, *args)

    monkeypatch.setattr(check, "train_reference", train_reference)
    _, run, correct = run_tiny(tiny_root, cell_name, seconds=0.1)
    assert correct and seen["float32"]
    # the reference's weights and the seed's key; not the system's
    # parameters, moments, model state, batches or ring (3 x and more)
    assert seen["weights"] <= seen["alive"] <= seen["weights"] + 4096
    assert run.compared["weights_differ"] == (0.0, 0.0)


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
