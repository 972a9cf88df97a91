"""The hybrid decoder of Gated DeltaNet and full attention under OLMo 2's
norms (Olmo-Hybrid-7B, ``olmo_hybrid7b``): the system against its plain
reference at a tiny size, the tiny stand-in of its cell through the
harness, the operation count, the configuration file, and the readers of
its per-layer metrics."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, manifest
from benchmark.families import olmohybrid as family
from benchmark.ops import olmohybrid as ops
from benchmark.reference import olmohybrid as reference
from benchmark.traffic import Traffic
from horovod_tpu import metrics
from horovod_tpu.models import transformer

from tiny_cells import CHECKOUT, HERE, TINY, run_tiny

# the stand-in of the real cell: fixture/cells/olmohybrid_tiny.ring1x64.json
REAL_CELL = "olmo_hybrid7b.ring1x4096"
TINY_CELL = TINY[REAL_CELL][0]

FIXTURE = HERE / "fixture"
TIMED_METRICS = ("gdn.mixer_ms", "gdn.core_ms", "gdn.core_roofline",
                 "gdn.full_attn_ms")
COUNTED_METRICS = ("gdn.kernel_layers",)


def _tiny_config(**model):
    config = manifest.load_json(FIXTURE / "configs" / "olmohybrid_tiny.json")
    config["activation_dtype"] = "float32"
    config["model"].update(model)
    return config


def _real_config():
    return manifest.load_json(
        CHECKOUT / "benchmark/configs/olmo_hybrid7b.json")


def _system_and_batch(config, traffic_name, rows=4):
    mix = manifest.load_json(FIXTURE / "traffic" / f"{traffic_name}.json")
    system = family.build(config, mix)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("world",))
    batch = Traffic(mix, system.element, mesh, "world", seed=5).sample(rows)
    return system, mix, jax.tree.map(jnp.asarray, batch)


def _perturbed(params, scale=0.05):
    """Norm scales start at one: move every leaf, so that no gradient is
    tested at a special point."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    return jax.tree.unflatten(treedef, [
        x + scale * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _max_rel(got, want):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    return float(jnp.max(jnp.abs(got - want))) / scale


# ------------------------------------------- the system against the reference
@pytest.mark.parametrize("traffic_name", ["ring-1x64", "packed-docs-8x64"])
def test_logits_loss_and_every_gradient_match_the_reference(traffic_name):
    config = _tiny_config()
    model = config["model"]
    assert family.layer_kinds(model) == ["gdn", "full"]
    system, mix, batch = _system_and_batch(config, traffic_name)
    packed = isinstance(batch, tuple)
    tokens, segments = batch if packed else (batch, None)
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    net = transformer.Transformer(family.transformer_config(config, mix))
    with jax.default_matmul_precision("highest"):
        logits, _ = net.apply(params, tokens, segments)
        ref_logits = reference.logits(params, model, tokens, segments)
        loss, grads = jax.value_and_grad(system.loss_fn)(params, batch)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.loss(p, model, batch))(params)
    assert logits.shape == tokens.shape + (model["vocab_size"],)
    assert _max_rel(logits, ref_logits) <= 1e-4
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(grads)[0]
    assert len(flat) == len(jax.tree.leaves(ref_grads))
    for (path, g), w in zip(flat, jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        assert _max_rel(g, w) <= 1e-4, name
        assert float(jnp.max(jnp.abs(w))) > 0, name  # no idle leaf
    if packed:
        assert int(segments.max()) > 1  # several documents a row


def test_reference_in_blocks_is_the_reference(monkeypatch):
    config = _tiny_config()
    system, _, batch = _system_and_batch(config, "packed-docs-8x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])

    def value_and_grad():
        return jax.value_and_grad(
            lambda p: reference.loss(p, config["model"], batch))(params)

    for name in ("QUERY_BLOCK", "RECURRENCE_BLOCK", "ROW_BLOCK"):
        monkeypatch.setattr(reference, name, 64)             # one block
    whole = value_and_grad()
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)        # four a row
    monkeypatch.setattr(reference, "RECURRENCE_BLOCK", 8)    # eight
    monkeypatch.setattr(reference, "ROW_BLOCK", 16)          # four
    blocked = value_and_grad()
    assert float(blocked[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    for got, want in zip(jax.tree.leaves(blocked[1]),
                         jax.tree.leaves(whole[1])):
        assert _max_rel(got, want) <= 1e-5


def test_reference_imports_nothing_of_the_system():
    text = (CHECKOUT / "benchmark/reference/olmohybrid.py").read_text()
    code = text.split('"""', 2)[2]
    for name in ("horovod_tpu", "kda", "pallas", "remat"):
        assert name not in code, name
    assert "lax.scan(" in code and "-jnp.inf" in code


def test_no_rotary_positions_unless_the_file_sets_a_theta():
    """rope_theta null (the published file) gives no positions at all; a
    theta set turns the full layers' heads in the system and the reference
    alike."""
    config = _tiny_config()
    assert family.positions(config["model"]) == "none"
    system, mix, batch = _system_and_batch(config, "ring-1x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    turned = _tiny_config(rope_parameters={"rope_theta": 10000})
    assert family.positions(turned["model"]) == "rope"
    turned_system = family.build(turned, mix)
    with jax.default_matmul_precision("highest"):
        plain = system.loss_fn(params, batch)
        got = turned_system.loss_fn(params, batch)
        want = reference.loss(params, turned["model"], batch)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    assert abs(float(got) - float(plain)) > 1e-5


# ---------------------------------------------------------- the tiny cell
def test_tiny_cell_runs_through_the_harness_and_every_count_is_read(
        tiny_root, quiet_runtime):
    from benchmark import run as bench_run

    cell, run, correct = run_tiny(tiny_root, TINY_CELL, trace=True)
    assert correct and run.failed == 0 and run.builds_in_window == 0
    assert {m.name for m in cell.per_layer} >= set(
        TIMED_METRICS + COUNTED_METRICS)
    read = harness.metrics_of(run, cell.per_layer, on_chip=False)
    # off the chip only the counts are given, and they are the gauges'
    assert set(read) & set(TIMED_METRICS + COUNTED_METRICS) == set(
        COUNTED_METRICS)
    # heads of 8 and 16 are interpreted here: the core took the kernels
    assert read["gdn.kernel_layers"]["value"] == 1
    for kind, count in (("gdn", 1), ("full", 1), ("kda", 0), ("dense", 2)):
        assert metrics.get_gauge("model.layer_kinds", {"kind": kind}) == count
    assert metrics.get_gauge("model.kda.kernel_layers") == 0
    line = bench_run.result_line(run, correct, True, jax.devices()[:1])
    assert json.loads(json.dumps(line))["correct"] is True
    timed = harness.metrics_of(run, cell.per_layer, on_chip=True)
    assert not set(timed) & set(TIMED_METRICS)
    # the scopes the readers anchor on are in the compiled step
    text = run.step_hlo
    for scope in ("block_0/gdn/", "/gdn/conv/", "/gdn/gate/", "/gdn/core/",
                  "/gdn/norm/", "block_1/attn/", "/attn/flash_bwd",
                  "/head/", "(loss)", "rematted_computation/block_",
                  "hvd_compute_grads"):
        assert scope in text, scope
    assert "block_0/attn/" not in text and "block_1/gdn/" not in text


def test_fixture_uses_the_real_tolerance_and_settings():
    real = _real_config()
    tiny = manifest.load_json(FIXTURE / "configs/olmohybrid_tiny.json")
    assert real["check"]["loss_rtol"] == tiny["check"]["loss_rtol"]
    assert real["check"]["steps"] == tiny["check"]["steps"] == 4
    assert real["check"]["sample_rows_per_chip"] == 1
    assert real["check"]["why"]
    for key in ("remat", "remat_save", "compression", "activation_dtype",
                "attn_impl"):
        assert real[key] == tiny[key], key
    # the same AdamW; the fixture starts at the peak rate (its note says why)
    assert dict(real["optimizer"], warmup_from=3e-4) == tiny["optimizer"]
    # every key the family reads is the published one in both, but sizes
    for key, value in real["model"].items():
        if isinstance(value, (bool, str)) or value is None:
            assert tiny["model"][key] == value, key
    assert tiny["model"]["rope_parameters"] == real["model"][
        "rope_parameters"]


# -------------------------------------------------------- operation count
def _xla_flops(fn, *shapes) -> float:
    cost = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def test_the_matmuls_match_xla_within_3_percent(monkeypatch):
    """The fixture's two layers at a width of 256, compiled on the CPU and
    never run.  XLA counts the whole score matrix of the full layer and a
    scan's body once, so the count is compared with neither mask and
    without the recurrence (the reference's token loop, which XLA counts
    one token of), and the masks take off the pairs counted by hand."""
    seq, rows = 256, 2
    for name in ("QUERY_BLOCK", "ROW_BLOCK"):
        monkeypatch.setattr(reference, name, seq)
    config = _tiny_config(
        hidden_size=256, intermediate_size=704, vocab_size=768,
        linear_key_head_dim=32, linear_value_head_dim=64,
        max_position_embeddings=1024)
    model = config["model"]
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": seq}).init(k)[0],
        jax.random.PRNGKey(0))
    counted = _xla_flops(
        lambda p, t: reference.logits(p, model, t), params,
        jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    units, sum_sq = rows * seq, rows * seq * seq
    required = ops.forward_flops(model, units, sum_sq)
    head = 256 // 2
    triangle = seq * (seq + 1) // 2
    attention = 4 * 2 * head * rows * triangle
    matmuls = 2.0 * ops.matmul_params(model) * units
    core = ops.gdn_core_ops(model, units)
    assert core == 7 * 2 * 32 * 64 * units
    assert required == matmuls + attention + core
    unmasked = matmuls + 4 * 2 * head * rows * seq * seq
    assert counted == pytest.approx(unmasked, rel=0.03)


def test_the_count_at_the_published_widths():
    model = _real_config()["model"]
    d = 3840
    assert ops.gdn_matmul_params(model) == (
        2 * d * 30 * 96 + 3 * d * 30 * 192 + 2 * d * 30)
    assert ops.gdn_matmul_params(model) == pytest.approx(88.7e6, rel=1e-3)
    assert ops.full_matmul_params(model) == 4 * d * d
    assert family.layer_kinds(model) == ["gdn", "gdn", "gdn", "full"]
    per_token = (3 * ops.gdn_matmul_params(model)
                 + ops.full_matmul_params(model) + 4 * 3 * d * 11008
                 + 12544 * d)
    assert ops.matmul_params(model) == per_token
    assert per_token == pytest.approx(879.8e6, rel=1e-3)
    units = 4096
    want = 3 * (2 * units * per_token
                + 4 * 30 * 128 * units * (units + 1) / 2
                + 3 * 7 * 30 * 96 * 192 * units)
    assert ops.train_flops(model, units, units * units) == want
    assert want == pytest.approx(22.1e12, rel=0.01)  # ~22.1 TFLOP a step
    # the share of the GDN mixers in the required matmuls: 30%
    assert 3 * ops.gdn_matmul_params(model) / per_token == pytest.approx(
        0.30, abs=0.01)
    # the core's step: bound by bytes, 1.28 GB for 0.045 TFLOP
    o, b = ops.gdn_core_step(model, units, units)
    assert o == 3 * 3 * 7 * 30 * 96 * 192 * units
    assert b == 3 * 3 * units * 30 * ((2 * 96 + 2 * 192) * 2 + 8)
    assert b / 819e9 > o / 197e12


# --------------------------------------------------- the configuration file
def test_configuration_keeps_the_published_sizes():
    config = _real_config()
    model = config["model"]
    catalog = {
        "model_type": "olmo_hybrid", "hidden_size": 3840,
        "intermediate_size": 11008, "num_attention_heads": 30,
        "num_key_value_heads": 30, "hidden_act": "silu",
        "max_position_embeddings": 65536, "attention_bias": False,
        "rms_norm_eps": 1e-06, "tie_word_embeddings": False,
        "linear_num_key_heads": 30, "linear_num_value_heads": 30,
        "linear_key_head_dim": 96, "linear_value_head_dim": 192,
        "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
        "rope_parameters": {"rope_theta": None},
    }
    for key, value in catalog.items():
        assert model[key] == config[key] == value, key
    # the layer pattern is copied whole and read at the held indices
    assert len(model["layer_types"]) == len(config["layer_types"]) == 32
    assert model["layer_types"] == (
        ["linear_attention"] * 3 + ["full_attention"]) * 8
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (model["num_hidden_layers"], model["vocab_size"]) == (4, 12544)
    assert config["published"]["num_hidden_layers"] == 32
    assert config["published"]["vocab_size"] == 100352 == 8 * 12544
    assert model["layers_held"] == [0, 1, 2, 3]
    assert set(config["changed"]) == set(config["reduced"])
    for key in ("norm_placement", "qk_norm", "rope_parameters", "head_dim",
                "gdn", "optimizer"):
        assert config["assumed"][key], key
    assert "8 chips" in config["deployment"]


def test_the_family_builds_the_published_shapes():
    config = _real_config()
    mix = manifest.load_json(CHECKOUT / "benchmark/traffic/ring-1x4096.json")
    assert (mix["rows"], mix["seq_len"], mix["ring"]) == (1, 4096, 8)
    cfg = family.transformer_config(config, mix)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "full")
    assert (cfg.num_heads, cfg.head_dim, cfg.gdn_key_dim, cfg.gdn_value_dim,
            cfg.kda_conv) == (30, 128, 96, 192, 4)
    assert (cfg.positions, cfg.pre_norm, cfg.post_norm, cfg.qk_norm) == (
        "none", False, True, True)
    params = jax.eval_shape(
        family.build(config, mix).init, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(params)) == 928_862_196


@pytest.mark.parametrize("change, what", [
    ({"attention_bias": True}, "biases"),
    ({"tie_word_embeddings": True}, "tied head"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"num_key_value_heads": 1}, "grouped"),
    ({"linear_num_value_heads": 4}, "linear-attention heads"),
    ({"linear_allow_neg_eigval": False}, "linear_allow_neg_eigval"),
    ({"num_hidden_layers": 3}, "layers_held"),
])
def test_the_family_refuses_by_name_what_it_does_not_build(change, what):
    config = _tiny_config(**change)
    with pytest.raises(ValueError, match=what):
        family.transformer_config(config, {"seq_len": 64})
