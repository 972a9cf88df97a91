"""Each plain reference against the system's own model and loss, loss and
gradients leaf by leaf, at a tiny size in float32 on the CPU (the chip run
compares losses at the published widths; ``chip_smoke.kernels()`` compares
the kernels there)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import manifest
from benchmark.traffic import Traffic

from tiny_cells import HERE

FIXTURE = HERE / "fixture"


def _system_and_batch(config_name, traffic_name, rows):
    config = manifest.load_json(FIXTURE / "configs" / f"{config_name}.json")
    config["activation_dtype"] = "float32"
    mix = manifest.load_json(FIXTURE / "traffic" / f"{traffic_name}.json")
    family = importlib.import_module(
        f"benchmark.families.{config['family']}")
    system = family.build(config, mix)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("world",))
    batch = Traffic(mix, system.element, mesh, "world", seed=5).sample(rows)
    reference = importlib.import_module(
        f"benchmark.reference.{config['family']}")
    return config, system, jax.tree.map(jnp.asarray, batch), reference


def _assert_trees_close(got, want, tol):
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        scale = max(float(jnp.max(jnp.abs(w))), 1e-6)
        err = float(jnp.max(jnp.abs(g - w))) / scale
        assert err <= tol, (jax.tree_util.keystr(path), err)


@pytest.mark.parametrize("traffic_name", ["ring-8x64", "packed-docs-8x64"])
def test_gpt_reference_matches_the_system(traffic_name):
    config, system, batch, reference = _system_and_batch(
        "gpt_tiny", traffic_name, rows=4)
    params, _ = system.init(jax.random.PRNGKey(3))
    # biases start at zero; move them so that their gradients are tested
    params = jax.tree.map(
        lambda x, k: x + 0.02 * jax.random.normal(k, x.shape, x.dtype),
        params, _keys_like(params))
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(system.loss_fn)(params, batch)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.loss(p, config["model"], batch))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    # float32 sums in another order: 1e-4 of each leaf's largest gradient
    _assert_trees_close(grads, ref_grads, 1e-4)
    if isinstance(batch, tuple):
        tokens, segments = batch
        assert int(segments.max()) > 1  # several documents a row


def test_resnet_reference_matches_the_system():
    config, system, batch, reference = _system_and_batch(
        "resnet_cut", "ring-8-images", rows=8)
    params, stats = system.init(jax.random.PRNGKey(3))
    # the last BatchNorm scale of a block starts at zero, which would hide
    # the block's branch: perturb every leaf
    params = jax.tree.map(
        lambda x, k: x + 0.1 * jax.random.normal(k, x.shape, x.dtype),
        params, _keys_like(params))
    with jax.default_matmul_precision("highest"):
        (loss, new_stats), grads = jax.value_and_grad(
            system.loss_fn, has_aux=True)(params, stats, batch)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.loss(p, config["model"], batch))(params)
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    _assert_trees_close(grads, ref_grads, 2e-4)
    assert jax.tree.structure(new_stats) == jax.tree.structure(stats)


def _keys_like(tree):
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    return jax.tree.unflatten(treedef, list(keys))
