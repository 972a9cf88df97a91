"""The hybrid decoder of Gated DeltaNet (key heads shared by value heads)
and gated attention with routed experts (Qwen3-Next, ``qwen3next``): the
system against its plain reference at a tiny size, the tiny stand-in of its
cell through the harness, the operation count, the configuration file, the
family's refusals and the readers of its per-layer metrics."""

import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, manifest
from benchmark.families import qwen3next as family
from benchmark.ops import qwen3next as ops
from benchmark.reference import qwen3next as reference
from benchmark.trace import hlo, reduce, xplane
from benchmark.traffic import Traffic, Work
from horovod_tpu import metrics
from horovod_tpu.models import transformer

from tiny_cells import CHECKOUT, HERE, TINY, run_tiny

# the stand-in of the real cell: fixture/cells/qwen3next_tiny.ring1x64.json
REAL_CELL = "qwen3next.ring1x8192"
TINY_CELL = TINY[REAL_CELL][0]

FIXTURE = HERE / "fixture"
TIMED_METRICS = ("qnext.gdn_ms", "qnext.core_ms", "qnext.core_roofline",
                 "qnext.attn_ms", "qnext.flash_fwd_roofline",
                 "qnext.flash_bwd_roofline", "qnext.moe_ms",
                 "qnext.moe_dispatch_ms")
COUNTED_METRICS = ("qnext.kernel_layers", "qnext.moe_kernel_layers",
                   "qnext.pairs_per_expert", "qnext.load_max_over_mean")
CATALOG = {
    "decoder_sparse_step": 1, "full_attention_interval": 4, "head_dim": 256,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 5120,
    "linear_conv_kernel_dim": 4, "linear_key_head_dim": 128,
    "linear_num_key_heads": 16, "linear_num_value_heads": 32,
    "linear_value_head_dim": 128, "max_position_embeddings": 262144,
    "mlp_only_layers": [], "model_type": "qwen3_next",
    "moe_intermediate_size": 512, "norm_topk_prob": True,
    "num_attention_heads": 16, "num_experts": 512, "num_experts_per_tok": 10,
    "num_hidden_layers": 48, "num_key_value_heads": 2,
    "partial_rotary_factor": 0.25, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 10000000,
    "shared_expert_intermediate_size": 512, "tie_word_embeddings": False,
    "use_sliding_window": False, "vocab_size": 151936,
}


def _tiny_config(**model):
    config = manifest.load_json(FIXTURE / "configs" / "qwen3next_tiny.json")
    config["activation_dtype"] = "float32"
    config["model"].update(model)
    return config


def _real_config():
    return manifest.load_json(CHECKOUT / "benchmark/configs/qwen3next.json")


def _system_and_batch(config, traffic_name, rows=4):
    mix = manifest.load_json(FIXTURE / "traffic" / f"{traffic_name}.json")
    system = family.build(config, mix)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("world",))
    batch = Traffic(mix, system.element, mesh, "world", seed=5).sample(rows)
    return system, mix, jax.tree.map(jnp.asarray, batch)


def _perturbed(params, scale=0.05):
    """Norm weights start at zero or one: move every leaf, so that no
    gradient is tested at a special point."""
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
    text = (CHECKOUT / "benchmark/reference/qwen3next.py").read_text()
    code = text.split('"""', 2)[2]
    for name in ("horovod_tpu", "kda", "pallas", "remat", "flax"):
        assert name not in code, name
    assert "lax.scan(" in code and "-jnp.inf" in code
    assert "jax.nn.softmax(logits" in code  # the router's own softmax


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
    # heads of 8 are interpreted here: the core took the kernels
    assert read["qnext.kernel_layers"]["value"] == 1
    assert read["qnext.moe_kernel_layers"]["value"] == 2
    # 64 tokens x 4 experts a token x 4 held of 16: 64 pairs a layer
    assert 0 < read["qnext.pairs_per_expert"]["value"] <= 64
    assert read["qnext.load_max_over_mean"]["value"] >= 1
    for kind, count in (("gdn", 1), ("full", 1), ("experts", 2),
                        ("dense", 0)):
        assert metrics.get_gauge("model.layer_kinds", {"kind": kind}) == count
    assert metrics.get_gauge("model.gdn.value_heads_per_key") == 2
    assert metrics.get_gauge("model.attn.kv_groups", {"kind": "full"}) == 2
    line = bench_run.result_line(run, correct, True, jax.devices()[:1])
    assert json.loads(json.dumps(line))["correct"] is True
    timed = harness.metrics_of(run, cell.per_layer, on_chip=True)
    assert not set(timed) & set(TIMED_METRICS)
    # the scopes the readers anchor on are in the compiled step
    text = run.step_hlo
    for scope in ("block_0/gdn/", "/gdn/conv/", "/gdn/gate/", "/gdn/core/",
                  "/gdn/norm/", "block_1/attn/", "/attn/qk_norm/",
                  "/attn/gate/", "/attn/flash_bwd", "/moe/router/",
                  "/moe/shared/", "/experts/", "/head/", "(loss)",
                  "rematted_computation/block_", "hvd_compute_grads"):
        assert scope in text, scope
    assert "block_0/attn/" not in text and "block_1/gdn/" not in text
    assert "router_bias" not in json.dumps(
        jax.tree_util.tree_structure(run.cell.config).__repr__())


def test_fixture_uses_the_real_tolerance_and_settings():
    real = _real_config()
    tiny = manifest.load_json(FIXTURE / "configs/qwen3next_tiny.json")
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
        if isinstance(value, (bool, str, list)) or value is None:
            if key not in ("layers_held", "experts_held"):
                assert tiny["model"][key] == value, key
    for key in ("rope_theta", "partial_rotary_factor", "rms_norm_eps",
                "full_attention_interval", "decoder_sparse_step",
                "linear_conv_kernel_dim"):
        assert tiny["model"][key] == real["model"][key], key


# -------------------------------------------------------- operation count
def _xla_flops(fn, *shapes) -> float:
    cost = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def test_the_matmuls_match_xla_within_3_percent(monkeypatch):
    """The fixture's two layers at a width of 256 with every expert held
    (so that every token goes through every expert in the reference's
    dense loop, as the count's expectation of pairs then says), compiled
    on the CPU and never run.  XLA counts the whole score matrix of the
    full layer and a scan's body once, so the count is compared with
    neither mask and without the recurrence; the router's and the gates'
    matmuls are counted as they are."""
    seq, rows = 256, 2
    for name in ("QUERY_BLOCK", "ROW_BLOCK"):
        monkeypatch.setattr(reference, name, seq)
    config = _tiny_config(
        hidden_size=256, vocab_size=768, linear_key_head_dim=32,
        linear_value_head_dim=32, head_dim=64, moe_intermediate_size=128,
        shared_expert_intermediate_size=128, max_position_embeddings=1024,
        num_experts=16, num_experts_per_tok=16, experts_held=[0, 16])
    model = config["model"]
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": seq}).init(k)[0],
        jax.random.PRNGKey(0))
    counted = _xla_flops(
        lambda p, t: reference.logits(p, model, t), params,
        jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    units, sum_sq = rows * seq, rows * seq * seq
    required = ops.forward_flops(model, units, sum_sq)
    triangle = seq * (seq + 1) // 2
    attention = 4 * 4 * 64 * rows * triangle
    matmuls = 2.0 * ops.matmul_params(model) * units
    core = ops.gdn_core_ops(model, units)
    assert ops.pairs_per_token(model) == 16
    assert core == 7 * 4 * 32 * 32 * units
    assert required == matmuls + attention + core
    unmasked = matmuls + 4 * 4 * 64 * rows * seq * seq
    # the scan over the held experts is counted for one expert by XLA, in
    # each of the two layers
    loop = 2.0 * units * 15 * 3 * 256 * 128 * 2
    assert counted == pytest.approx(unmasked - loop, rel=0.03)


def test_the_count_at_the_published_widths():
    model = _real_config()["model"]
    d = 2048
    assert ops.gdn_matmul_params(model) == (
        2 * d * 16 * 128 + 3 * d * 32 * 128 + 2 * d * 32)
    assert ops.attn_matmul_params(model) == (
        3 * d * 16 * 256 + 2 * d * 2 * 256)
    assert ops.pairs_per_token(model) == 10 * 32 / 512 == 0.625
    expert = 3 * d * 512
    assert ops.ffn_matmul_params(model) == (
        d * 512 + expert + d + 0.625 * expert)
    assert family.layer_kinds(model) == ["gdn", "gdn", "gdn", "full"]
    per_token = (3 * ops.gdn_matmul_params(model)
                 + ops.attn_matmul_params(model)
                 + 4 * ops.ffn_matmul_params(model) + 18992 * d)
    assert ops.matmul_params(model) == per_token
    units = 8192
    triangle = units * (units + 1) / 2
    forward = (2 * units * per_token + 4 * 16 * 256 * triangle
               + 3 * 7 * 32 * 128 * 128 * units)
    assert ops.forward_flops(model, units, units * units) == forward
    assert forward / units == pytest.approx(462e6, rel=0.01)
    assert ops.train_flops(model, units, units * units) == 3 * forward
    assert 3 * forward == pytest.approx(11.35e12, rel=0.01)
    # the GDN layers' share of the step's work, and the attention layer's
    gdn = 3 * (2 * units * ops.gdn_matmul_params(model)
               + 7 * 32 * 128 * 128 * units)
    attn = (2 * units * ops.attn_matmul_params(model)
            + 4 * 16 * 256 * triangle)
    assert gdn / forward == pytest.approx(0.46, abs=0.01)
    assert attn / forward == pytest.approx(0.26, abs=0.01)
    # the core's step: bound by bytes
    o, b = ops.gdn_core_step(model, units, units)
    assert o == 3 * 3 * 7 * 32 * 128 * 128 * units
    assert b == 3 * 3 * units * ((2 * 16 * 128 + 2 * 32 * 128) * 2 + 8 * 32)
    assert b / 819e9 > o / 197e12
    # a flash call: bound by operations both ways
    for backward in (False, True):
        o, b = ops.flash_step(model, units, units, units * units, backward)
        assert o == (2 if backward else 1) * 4 * 16 * 256 * triangle
        assert o / 197e12 > b / 819e9


# --------------------------------------------------- the configuration file
def test_configuration_keeps_the_published_sizes():
    config = _real_config()
    model = config["model"]
    reduced = {"num_hidden_layers": 4, "num_experts": 32,
               "vocab_size": 18992}
    for key, value in CATALOG.items():
        assert model[key] == config[key] == reduced.get(key, value), key
    assert config["reduced"] == list(reduced)
    assert set(config["changed"]) == set(config["reduced"])
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["num_experts"] == 512 == 16 * 32
    assert config["published"]["vocab_size"] == 151936 == 8 * 18992
    assert model["layers_held"] == [0, 1, 2, 3]
    assert model["experts_held"] == [0, 32] and model["router_width"] == 512
    for key in ("norms", "gdn", "attention", "router", "mtp", "aux_loss",
                "optimizer", "initializers"):
        assert config["assumed"][key], key
    assert "16 chips" in config["deployment"]


def test_the_family_builds_the_published_shapes():
    config = _real_config()
    mix = manifest.load_json(CHECKOUT / "benchmark/traffic/ring-1x8192.json")
    assert (mix["rows"], mix["seq_len"], mix["ring"]) == (1, 8192, 8)
    cfg = family.transformer_config(config, mix)
    assert cfg.layer_kinds == ("gdn", "gdn", "gdn", "full")
    assert cfg.ffn_kinds == ("experts",) * 4
    assert (cfg.num_heads, cfg.gdn_key_heads, cfg.gdn_key_dim,
            cfg.gdn_value_dim, cfg.gdn_allow_neg_eigval) == (
        32, 16, 128, 128, False)
    assert (cfg.layer_heads[3], cfg.num_kv_heads, cfg.head_dim) == (
        16, 2, 256)
    rule = dict(cfg.rope_rules)["full"]
    assert (rule.theta, rule.dim, rule.factor) == (1e7, 64, 1.0)
    assert (cfg.norm, cfg.qk_head_norm, cfg.attn_channel_gate,
            cfg.attn_gate, cfg.qk_norm) == (
        "zero_centred_rmsnorm", True, True, False, False)
    assert (cfg.num_experts, cfg.experts_held, cfg.experts_per_token,
            cfg.n_group, cfg.routed_scaling, cfg.router_scoring,
            cfg.shared_expert_gate) == (
        512, (0, 32), 10, 1, 1.0, "softmax", True)
    params = jax.eval_shape(
        family.build(config, mix).init, jax.random.PRNGKey(0))[0]["params"]
    assert sum(x.size for x in jax.tree.leaves(params)) == 625_667_136
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    assert size(params["block_0"]["gdn"]) == 33_718_464
    assert size(params["block_3"]["attn"]) == 27_263_488
    moe = params["block_0"]["moe"]
    assert size(moe) - size({k: moe[k] for k in ("wg", "wi", "wo")}) == \
        4_196_352
    assert size({k: moe[k] for k in ("wg", "wi", "wo")}) == 32 * 3_145_728
    assert "router_bias" not in moe


@pytest.mark.parametrize("change, what", [
    ({"attention_bias": True}, "biases"),
    ({"tie_word_embeddings": True}, "tied head"),
    ({"hidden_act": "gelu"}, "hidden_act"),
    ({"use_sliding_window": True}, "sliding window"),
    ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    ({"norm_topk_prob": False}, "renormalised"),
    ({"shared_expert_intermediate_size": 16}, "shared expert"),
    ({"linear_num_key_heads": 3}, "key heads"),
    ({"mlp_only_layers": [3]}, "dense MLP"),
    ({"num_hidden_layers": 3}, "layers_held"),
    ({"num_experts": 8}, "experts_held"),
])
def test_the_family_refuses_by_name_what_it_does_not_build(change, what):
    config = _tiny_config(**change)
    with pytest.raises(ValueError, match=what):
        family.transformer_config(config, {"seq_len": 64})


# ------------------------------------------------------------- the readers
def _reader(name):
    path = manifest.PACKAGE_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


MS = 1e6  # the traces count nanoseconds
_SCOPES = {  # instruction: (scope, Mosaic, start, end) in ms of a step
    "gdn_proj": ("block_0/gdn/q/dot_general", False, 0, 4),
    "gdn_core": ("block_0/gdn/core/pallas_call", True, 4, 6),
    "gdn_core_bwd": ("transpose(jvp(block_0))/gdn/core/pallas_call", True,
                     6, 9),
    "attn_fwd": ("block_3/attn/pallas_call", True, 9, 12),
    "attn_norm": ("block_3/attn/qk_norm/pallas_call", True, 12, 13),
    "attn_bwd": ("transpose(jvp(block_3))/attn/flash_bwd/pallas_call", True,
                 13, 19),
    "router": ("block_0/moe/router/dot_general", False, 19, 21),
    "experts": ("block_0/moe/while/body/experts/pallas_call", True, 21, 24),
    "shared": ("block_0/moe/shared/dot_general", False, 24, 25),
    "combine": ("block_0/moe/while/body/combine/scatter", False, 25, 26),
}


def _hand_made_trace(steps=4):
    lines = ["HloModule jit_step_body, is_scheduled=true", "",
             "ENTRY %main (a: f32[8]) -> f32[8] {",
             "  %a = f32[8]{0} parameter(0)"]
    for name, (scope, mosaic, _, _) in _SCOPES.items():
        target = (', custom_call_target="tpu_custom_call"'
                  if mosaic else "")
        opcode = "custom-call(%a)" if mosaic else "fusion(%a), kind=kLoop"
        lines.append(
            f"  %{name} = f32[8]{{0}} {opcode}{target}, metadata={{op_name="
            f'"jit(step_body)/hvd_compute_grads/{scope}"}}')
    lines += ["  ROOT %r = f32[8]{0} copy(%a)", "}", ""]
    ops, modules = [], []
    for k in range(steps):
        modules.append(xplane.Event("jit_step_body(1)", 100 * k * MS,
                                    (100 * k + 100) * MS))
        for name, (_, _, a, b) in _SCOPES.items():
            ops.append(xplane.Event(
                f"%{name} = f32[8]{{0}} x(%a)", (100 * k + a) * MS,
                (100 * k + b) * MS))
    trace = xplane.Trace([xplane.Plane(
        "/device:TPU:0", {xplane.OPS_LINE: ops,
                          xplane.MODULES_LINE: modules})])
    return reduce.Reduced(trace, hlo.Module("\n".join(lines)))


class _Run:
    """What the readers look at of a run: the reduced trace, the model,
    the window's work."""

    def __init__(self, reduced, model, units=8192.0, steps=4):
        self._reduced = reduced
        self.model = model
        self.chips = 1
        self.device_kind = "TPU v5 lite"
        self.ops = ops
        self.completions = [None] * steps
        self._work = Work(units * steps, units * units * steps,
                          int(units) * steps)

    def reduced(self):  # as ``harness.Run.reduced``
        if self._reduced is not None and self._reduced.usable:
            return self._reduced
        return None

    def work(self):
        return self._work


def test_readers_on_a_hand_made_trace(capsys):
    run = _Run(_hand_made_trace(), _real_config()["model"])
    assert _reader("qnext.gdn_ms")(run) == pytest.approx(9.0)
    assert _reader("qnext.core_ms")(run) == pytest.approx(5.0)
    assert _reader("qnext.attn_ms")(run) == pytest.approx(10.0)
    assert _reader("qnext.moe_ms")(run) == pytest.approx(7.0)
    # router and combine, not the experts' kernel nor the shared expert
    assert _reader("qnext.moe_dispatch_ms")(run) == pytest.approx(3.0)
    o, b = ops.gdn_core_step(run.model, 8192, 8192)
    assert _reader("qnext.core_roofline")(run) == pytest.approx(
        100 * max(o / 197e12, b / 819e9) / 5e-3)
    # the forward kernel at attn/pallas_call only (not the q/k norm's)
    o, b = ops.flash_step(run.model, 8192, 8192, 8192 ** 2, False)
    assert _reader("qnext.flash_fwd_roofline")(run) == pytest.approx(
        100 * max(o / 197e12, b / 819e9) / 3e-3)
    o, b = ops.flash_step(run.model, 8192, 8192, 8192 ** 2, True)
    assert _reader("qnext.flash_bwd_roofline")(run) == pytest.approx(
        100 * max(o / 197e12, b / 819e9) / 6e-3)
    seen = capsys.readouterr().out
    assert "qnext.core_roofline.bound_by: bytes" in seen
    assert "qnext.flash_fwd_roofline.calls_per_step: 1.0" in seen


@pytest.mark.parametrize("name", sorted(TIMED_METRICS + COUNTED_METRICS))
def test_a_program_without_the_scopes_or_gauges_reads_nothing(name):
    """The parent's program (no such scope, no such gauge) and a run that
    was not traced: every reader gives None and raises nothing."""
    for gauge in ("model.gdn.kernel_layers", "model.moe.kernel_layers",
                  "model.moe.pairs_per_step", "model.moe.experts_held",
                  "model.moe.load_max_over_mean"):
        metrics.clear_gauge(gauge)
    lines = ["HloModule m", "", "ENTRY %main (a: f32[8]) -> f32[8] {",
             "  %a = f32[8]{0} parameter(0)",
             "  ROOT %mlp = f32[8]{0} fusion(%a), kind=kLoop, metadata={"
             'op_name="jit(step_body)/hvd_compute_grads/block_0/mlp/dot"}',
             "}", ""]
    other = reduce.Reduced(xplane.Trace([xplane.Plane(
        "/device:TPU:0", {
            xplane.OPS_LINE: [
                xplane.Event("%mlp = f32[8]{0} x(%a)", k * 100 * MS,
                             (k * 100 + 50) * MS) for k in range(4)],
            xplane.MODULES_LINE: [
                xplane.Event("jit_step_body(1)", k * 100 * MS,
                             (k + 1) * 100 * MS) for k in range(4)]})]),
        hlo.Module("\n".join(lines)))
    assert other.usable
    for reduced in (other, None):
        value = _reader(name)(_Run(reduced, _real_config()["model"]))
        assert value in (None, 0.0), (name, value)


def test_manifest_lists_the_twelve_metrics_in_the_one_cell():
    entries = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    mine = [e for e in entries["per_layer"] if e["name"].startswith("qnext.")]
    assert sorted(e["name"] for e in mine) == sorted(
        TIMED_METRICS + COUNTED_METRICS)
    for e in mine:
        assert e["workloads"] == [REAL_CELL]
        assert e["moves"] == "tokens_per_s_per_chip"
    (tokens,) = [e for e in entries["end_to_end"]
                 if e["name"] == "tokens_per_s_per_chip"]
    assert tokens["workloads"][-1] == REAL_CELL
    (cell,) = [w for w in entries["workloads"] if w["name"] == REAL_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "qwen3next", "ring-1x8192", 1)
    loaded = manifest.load_cell(REAL_CELL)
    assert set(TIMED_METRICS + COUNTED_METRICS) <= {
        m.name for m in loaded.per_layer}
