"""The decoder of mixed full and sliding-window attention with routed
experts (Laguna-XS.2, ``laguna_xs2``): the system against its plain
reference at a tiny size, the tiny stand-in of its cell through the
harness, the operation count, the configuration file, and the readers of
its per-layer metrics."""

import ast
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, manifest
from benchmark.families import swamoe as family
from benchmark.ops import swamoe as ops
from benchmark.reference import swamoe as reference
from benchmark.traffic import Traffic
from horovod_tpu import metrics
from horovod_tpu.models import transformer

from tiny_cells import CHECKOUT, HERE, TINY, run_tiny

# the stand-in of the real cell: fixture/cells/swamoe_tiny.ring1x64.json
REAL_CELL = "laguna_xs2.ring1x8192"
TINY_CELL = TINY[REAL_CELL][0]

FIXTURE = HERE / "fixture"
TIMED_METRICS = (
    "swa.window_attn_ms", "swa.full_attn_ms", "swa.moe_ms",
    "swa.moe_dispatch_ms", "swa.recompute_ms",
    "swa.window_flash_fwd_roofline", "swa.window_flash_bwd_roofline",
    "swa.full_flash_fwd_roofline", "swa.full_flash_bwd_roofline")
COUNTED_METRICS = ("swa.pairs_per_expert", "swa.load_max_over_mean",
                   "swa.window_tiles_per_head")


def _tiny_config(**model):
    config = manifest.load_json(FIXTURE / "configs" / "swamoe_tiny.json")
    config["activation_dtype"] = "float32"
    config["model"].update(model)
    return config


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
    assert family.layer_kinds(model) == [
        ("full", "dense", 4), ("window", "experts", 6),
        ("full", "experts", 4)]
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
        if name.endswith("['router_bias']"):
            assert not np.any(g) and not np.any(w), name  # a buffer
        else:
            assert _max_rel(g, w) <= 1e-4, name
            assert float(jnp.max(jnp.abs(w))) > 0, name  # no idle leaf
    if packed:
        assert int(segments.max()) > 1  # several documents a row


def test_reference_in_blocks_is_the_reference(monkeypatch):
    config = _tiny_config()
    system, _, batch = _system_and_batch(config, "packed-docs-8x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    monkeypatch.setattr(reference, "QUERY_BLOCK", 64)       # one block
    monkeypatch.setattr(reference, "LOSS_BLOCK", 64)
    whole = jax.value_and_grad(
        lambda p: reference.loss(p, config["model"], batch))(params)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)       # four a row
    monkeypatch.setattr(reference, "LOSS_BLOCK", 32)        # two a row
    blocked = jax.value_and_grad(
        lambda p: reference.loss(p, config["model"], batch))(params)
    assert float(blocked[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    for got, want in zip(jax.tree.leaves(blocked[1]),
                         jax.tree.leaves(whole[1])):
        assert _max_rel(got, want) <= 1e-5


def test_reference_makes_its_own_rotary_tables():
    """The reference's tables from its own formulas against the values of
    Laguna-XS.2's full-attention rule worked out by hand (low 5, high 16,
    the factor on cos and sin), and the unscaled sliding rule."""
    model = manifest.load_json(
        CHECKOUT / "benchmark/configs/laguna_xs2.json")["model"]
    pos = jnp.asarray([[0, 1, 7, 300]])
    rule = model["rope_parameters"]["full_attention"]
    cos, sin = reference.rotary_tables(rule, 128, pos)
    assert cos.shape == (1, 4, 32)
    ext = np.asarray([500000.0 ** (-2 * j / 64) for j in range(32)])
    ramp = np.clip((np.arange(32) - 5) / 11, 0, 1)
    inv = ext / 64 * ramp + ext * (1 - ramp)
    factor = 0.1 * math.log(64) + 1
    assert rule["attention_factor"] == pytest.approx(factor)
    angles = np.asarray(pos[0], np.float64)[:, None] * inv
    np.testing.assert_allclose(cos[0], factor * np.cos(angles), atol=1e-4)
    np.testing.assert_allclose(sin[0], factor * np.sin(angles), atol=1e-4)
    cos, sin = reference.rotary_tables(
        model["rope_parameters"]["sliding_attention"], 128, pos)
    assert cos.shape == (1, 4, 64)
    np.testing.assert_allclose(
        cos[0, 1], np.cos(10000.0 ** (-np.arange(64) / 64)), atol=1e-6)


def test_reference_imports_nothing_of_the_system():
    text = (CHECKOUT / "benchmark/reference/swamoe.py").read_text()
    assert "horovod_tpu" not in text.split('"""', 2)[2]
    assert "repeat(" in text and "-jnp.inf" in text


def test_the_routers_bias_stays_at_its_seeded_value():
    config = _tiny_config()
    system, _, batch = _system_and_batch(config, "ring-1x64")
    params = system.init(jax.random.PRNGKey(3))[0]
    state = system.optimizer.init(params)
    grads = jax.grad(system.loss_fn)(params, batch)
    updates, _ = system.optimizer.update(grads, state, params)
    for path, u in jax.tree_util.tree_flatten_with_path(updates)[0]:
        name = jax.tree_util.keystr(path)
        assert np.any(u) != name.endswith("['router_bias']"), name


# ---------------------------------------------------------- the tiny cell
def _earlier_lines(capsys):
    found = {}
    for line in capsys.readouterr().out.splitlines():
        key, _, value = line.partition(": ")
        if key.startswith("check."):
            found[key] = ast.literal_eval(value)
    return found


def test_tiny_cell_runs_through_the_harness_and_every_count_is_read(
        tiny_root, quiet_runtime, capsys):
    from benchmark import run as bench_run

    cell, run, correct = run_tiny(tiny_root, TINY_CELL, trace=True)
    assert correct and run.failed == 0 and run.builds_in_window == 0
    model = cell.config["model"]
    assert {m.name for m in cell.per_layer} >= set(
        TIMED_METRICS + COUNTED_METRICS)
    read = harness.metrics_of(run, cell.per_layer, on_chip=False)
    # off the chip only the counts are given, and they are the gauges'
    assert set(read) & set(TIMED_METRICS + COUNTED_METRICS) == set(
        COUNTED_METRICS)
    pairs = metrics.get_gauge("model.moe.pairs_per_step")
    held = model["experts_held"][1] - model["experts_held"][0]
    tokens = cell.traffic["rows"] * cell.traffic["seq_len"]
    assert metrics.get_gauge("model.moe.experts_held") == held == 4
    assert 0 < pairs <= 2 * tokens * min(held, model["num_experts_per_tok"])
    assert read["swa.pairs_per_expert"]["value"] == pairs / held / 2
    assert read["swa.load_max_over_mean"]["value"] >= 1.0
    # 64 tokens are one tile, whatever the window
    assert read["swa.window_tiles_per_head"]["value"] == 1
    for kind, count in (("full", 2), ("window", 1), ("dense", 1),
                        ("experts", 2)):
        assert metrics.get_gauge("model.layer_kinds", {"kind": kind}) == count
    assert metrics.get_gauge("model.attn.kv_groups", {"kind": "full"}) == 2
    assert metrics.get_gauge("model.attn.kv_groups", {"kind": "window"}) == 3
    line = bench_run.result_line(run, correct, True, jax.devices()[:1])
    assert json.loads(json.dumps(line))["correct"] is True
    # every timed reader runs on this trace without the chip's planes
    timed = harness.metrics_of(run, cell.per_layer, on_chip=True)
    assert not set(timed) & set(TIMED_METRICS)
    # the scopes the readers anchor on are in the compiled step
    text = run.step_hlo
    for scope in ("block_0/attn/", "block_1/attn/window/", "block_2/attn/",
                  "/attn/window/flash_bwd", "/attn/flash_bwd",
                  "block_1/moe/", "/moe/router/", "/dispatch/", "/experts/",
                  "/combine/", "/moe/shared/", "/head/", "/embed/", "(loss)",
                  "rematted_computation/block_", "hvd_compute_grads"):
        assert scope in text, scope
    assert "block_0/attn/window" not in text
    assert "block_2/attn/window" not in text
    seen = _earlier_lines(capsys)
    assert len(seen["check.system_losses"]) == 4


def test_fixture_uses_the_real_tolerance_and_settings():
    real = manifest.load_json(CHECKOUT / "benchmark/configs/laguna_xs2.json")
    tiny = manifest.load_json(FIXTURE / "configs/swamoe_tiny.json")
    assert real["check"]["loss_rtol"] == tiny["check"]["loss_rtol"]
    assert real["check"]["steps"] == tiny["check"]["steps"] == 4
    assert real["check"]["sample_rows_per_chip"] == 1
    assert real["check"]["why"]
    for key in ("remat", "compression", "activation_dtype", "attn_impl"):
        assert real[key] == tiny[key], key
    assert "moe_route" in real["remat_save"]
    # the same AdamW; the fixture starts at the peak rate (its note says why)
    assert dict(real["optimizer"], warmup_from=3e-4) == tiny["optimizer"]
    # every key the family reads is the published one in both, but sizes
    for key, value in real["model"].items():
        if isinstance(value, (bool, str)) or value is None:
            assert tiny["model"][key] == value, key


# -------------------------------------------------------- operation count
def _xla_flops(fn, *shapes) -> float:
    cost = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def test_count_matches_xla_within_3_percent(monkeypatch):
    """The fixture's three layers at a width of 256, compiled on the CPU
    and never run, with every expert held (XLA counts the reference's dense
    loop: every token through every held expert, which is the count's at
    ``pairs_per_token`` = held experts; the loop is a scan, whose body XLA
    counts once, so one expert is held).  XLA counts the whole score
    matrix of every layer: the count with neither mask agrees with it, and
    the masks take off exactly the pairs counted by hand."""
    seq, rows, window = 256, 2, 64
    monkeypatch.setattr(reference, "QUERY_BLOCK", seq)
    config = _tiny_config(
        hidden_size=256, head_dim=64, intermediate_size=704,
        moe_intermediate_size=96, shared_expert_intermediate_size=96,
        vocab_size=768, max_position_embeddings=1024, sliding_window=window,
        num_experts=1, experts_held=[4, 5])
    model = config["model"]
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": seq}).init(k)[0],
        jax.random.PRNGKey(0))
    counted = _xla_flops(
        lambda p, t: reference.logits(p, model, t), params,
        jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    dense_loop = dict(model, num_experts_per_tok=model["router_width"])
    assert ops.pairs_per_token(dense_loop) == 1
    units, sum_sq = rows * seq, rows * seq * seq
    required = ops.forward_flops(dense_loop, units, sum_sq)
    # by hand: heads x 4 x head_dim a pair; a full layer's row has
    # T(T+1)/2 pairs, a sliding layer's W(W+1)/2 + (T - W) W
    triangle = seq * (seq + 1) // 2
    band = window * (window + 1) // 2 + (seq - window) * window
    assert ops.window_pairs(units, sum_sq, window) == rows * band
    assert ops.window_pairs(rows * 40, rows * 40 * 40, window) == \
        rows * 40 * 41 / 2  # a row shorter than the window
    attention = 4 * 64 * rows * (4 * triangle + 6 * band + 4 * triangle)
    matmuls = 2.0 * ops.matmul_params(dense_loop) * units
    assert required == matmuls + attention
    unmasked = matmuls + 4 * 64 * rows * seq * seq * (4 + 6 + 4)
    assert counted == pytest.approx(unmasked, rel=0.03)


def test_the_count_at_the_published_widths():
    model = manifest.load_json(
        CHECKOUT / "benchmark/configs/laguna_xs2.json")["model"]
    assert ops.mixer_matmul_params(model, 48) == (
        2 * 2048 * 6144 + 2 * 2048 * 1024 + 2048 * 48)
    assert ops.mixer_matmul_params(model, 64) == (
        2 * 2048 * 8192 + 2 * 2048 * 1024 + 2048 * 64)
    assert ops.pairs_per_token(model) == 8 * 32 / 256 == 1
    expert = 3 * 2048 * 512
    assert ops.ffn_matmul_params(model, "experts") == (
        2048 * 256 + expert + expert)
    assert ops.ffn_matmul_params(model, "dense") == 3 * 2048 * 8192
    assert ops.kind_heads(model, "full") == 48
    assert ops.kind_heads(model, "window") == 64
    units = seq = 8192
    triangle = seq * (seq + 1) / 2
    band = 512 * 513 / 2 + (seq - 512) * 512
    per_token = (2 * ops.mixer_matmul_params(model, 48)
                 + 3 * ops.mixer_matmul_params(model, 64)
                 + 3 * 2048 * 8192 + 4 * (2048 * 256 + 2 * expert)
                 + 12544 * 2048)
    want = 3 * (2 * units * per_token + 4 * 128 * (
        2 * 48 * triangle + 3 * 64 * band))
    assert ops.train_flops(model, units, seq * seq) == want
    assert want == pytest.approx(19.7e12, rel=0.01)  # ~19.7 TFLOP a step
    # run unwindowed the three sliding layers would be 8.7 TFLOP more
    assert 3 * 4 * 128 * 3 * 64 * (triangle - band) == pytest.approx(
        8.7e12, rel=0.01)
    # a call's bound: operations on full layers and on sliding layers
    for mixer in ("full", "window"):
        for backward in (False, True):
            o, b = ops.flash_ops_and_bytes(
                model, mixer, units, units, seq * seq, backward)
            assert o / 197e12 > b / 819e9, (mixer, backward)
    o, b = ops.flash_ops_and_bytes(model, "window", units, units,
                                   seq * seq, False)
    assert o == 4 * 64 * 128 * band
    assert b == units * (2 * (64 + 8) * 128 * 2 + 4 * 64)


# --------------------------------------------------- the configuration file
def test_configuration_keeps_the_published_sizes():
    config = manifest.load_json(
        CHECKOUT / "benchmark/configs/laguna_xs2.json")
    model = config["model"]
    catalog = {
        "model_type": "laguna", "hidden_size": 2048,
        "intermediate_size": 8192, "num_attention_heads": 48,
        "num_key_value_heads": 8, "head_dim": 128,
        "max_position_embeddings": 262144, "attention_bias": False,
        "rms_norm_eps": 1e-06, "num_experts_per_tok": 8,
        "moe_intermediate_size": 512, "shared_expert_intermediate_size": 512,
        "tie_word_embeddings": False, "gating": True, "sliding_window": 512,
        "moe_apply_router_weight_on_input": False,
        "partial_rotary_factor": 0.5, "moe_routed_scaling_factor": 2.5,
    }
    for key, value in catalog.items():
        assert model[key] == config[key] == value, key
    assert model["rope_parameters"] == config["rope_parameters"] == {
        "full_attention": {
            "rope_theta": 500000, "rope_type": "yarn", "factor": 64,
            "original_max_position_embeddings": 4096, "beta_slow": 1,
            "beta_fast": 64, "attention_factor": 1.4158883083359672,
            "partial_rotary_factor": 0.5},
        "sliding_attention": {
            "rope_type": "default", "rope_theta": 10000,
            "partial_rotary_factor": 1},
        "original_max_position_embeddings": 4096}
    # the per-layer lists are copied whole and read at the held indices
    for key in ("layer_types", "mlp_layer_types",
                "num_attention_heads_per_layer"):
        assert len(model[key]) == len(config[key]) == 40, key
    assert model["layer_types"][:5] == [
        "full_attention", "sliding_attention", "sliding_attention",
        "sliding_attention", "full_attention"]
    assert model["mlp_layer_types"][:5] == ["dense"] + ["sparse"] * 4
    assert model["num_attention_heads_per_layer"][:5] == [48, 64, 64, 64, 48]
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert (model["num_hidden_layers"], model["num_experts"],
            model["vocab_size"]) == (5, 32, 12544)
    assert config["published"]["num_hidden_layers"] == 40
    assert config["published"]["num_experts"] == 256
    assert config["published"]["vocab_size"] == 100352 == 8 * 12544
    assert model["layers_held"] == [0, 1, 2, 3, 4]
    assert model["experts_held"] == [0, 32] and model["router_width"] == 256
    assert set(config["changed"]) == set(config["reduced"])
    for key in ("gating", "router", "sliding_window", "rope_parameters",
                "qk_norm", "optimizer"):
        assert config["assumed"][key], key
    assert "8 chips" in config["deployment"]
    assert family.layer_kinds(model) == [
        ("full", "dense", 48), ("window", "experts", 64),
        ("window", "experts", 64), ("window", "experts", 64),
        ("full", "experts", 48)]


def test_the_family_builds_the_published_shapes():
    config = manifest.load_json(
        CHECKOUT / "benchmark/configs/laguna_xs2.json")
    mix = manifest.load_json(
        CHECKOUT / "benchmark/traffic/ring-1x8192.json")
    assert (mix["rows"], mix["seq_len"], mix["ring"]) == (1, 8192, 8)
    cfg = family.transformer_config(config, mix)
    assert (cfg.num_kv_heads, cfg.window, cfg.layer_heads) == (
        8, 512, (48, 64, 64, 64, 48))
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.n_group, cfg.topk_group, cfg.routed_scaling) == (
        256, (0, 32), 8, 1, 1, 2.5)
    rules = dict(cfg.rope_rules)
    assert (rules["full"].dim, rules["full"].factor,
            rules["full"].original_max_len) == (64, 64.0, 4096)
    assert (rules["window"].dim, rules["window"].factor,
            rules["window"].theta) == (128, 1.0, 10000.0)
    params = jax.eval_shape(
        family.build(config, mix).init, jax.random.PRNGKey(0))[0]
    assert sum(x.size for x in jax.tree.leaves(params)) == 691_624_960


@pytest.mark.parametrize("change, what", [
    ({"attention_bias": True}, "biases"),
    ({"tie_word_embeddings": True}, "tied head"),
    ({"gating": "per-channel"}, "gate"),
    ({"shared_expert_intermediate_size": 48}, "shared expert"),
    ({"moe_apply_router_weight_on_input": True}, "input"),
    ({"num_hidden_layers": 4}, "layers_held"),
    ({"num_experts": 8}, "experts_held"),
])
def test_the_family_refuses_by_name_what_it_does_not_build(change, what):
    config = _tiny_config(**change)
    with pytest.raises(ValueError, match=what):
        family.transformer_config(config, {"seq_len": 64})
