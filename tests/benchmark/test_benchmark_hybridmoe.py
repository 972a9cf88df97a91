"""The hybrid decoder with routed experts (Ling-3.0-flash, ``ling3flash``):
the system against its plain reference at a tiny size, the tiny stand-in of
its cell through the harness, the check failing for what it has to catch,
the operation count, the configuration file, and the readers of its
per-layer metrics."""

import ast
import dataclasses
import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness, manifest
from benchmark.families import hybridmoe as family
from benchmark.ops import hybridmoe as ops
from benchmark.reference import hybridmoe as reference
from benchmark.trace import hlo, reduce, xplane
from benchmark.traffic import Traffic
from horovod_tpu import metrics
from horovod_tpu.models import transformer

from tiny_cells import CHECKOUT, HERE, TINY, run_tiny

# the stand-in of the real cell: fixture/cells/hybridmoe_tiny.ring1x64.json
REAL_CELL = "ling3flash.ring1x4096"
TINY_CELL = TINY[REAL_CELL][0]

FIXTURE = HERE / "fixture"
TIMED_METRICS = (
    "hybrid.kda_ms", "hybrid.kda_core_ms", "hybrid.mla_ms", "hybrid.moe_ms",
    "hybrid.moe_dispatch_ms", "hybrid.recompute_ms",
    "hybrid.kda_core_roofline", "hybrid.mla_flash_fwd_roofline",
    "hybrid.mla_flash_bwd_roofline")
COUNTED_METRICS = ("hybrid.pairs_per_expert", "hybrid.load_max_over_mean")


def _tiny_config(**model):
    config = manifest.load_json(FIXTURE / "configs" / "hybridmoe_tiny.json")
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
    assert family.layer_kinds(model) == reference.layer_kinds(model) == [
        ("kda", "dense"), ("kda", "experts"), ("mla", "experts")]
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
    monkeypatch.setattr(reference, "RECURRENCE_BLOCK", 64)
    whole = jax.value_and_grad(
        lambda p: reference.loss(p, config["model"], batch))(params)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)       # four a row
    monkeypatch.setattr(reference, "RECURRENCE_BLOCK", 8)   # eight a row
    blocked = jax.value_and_grad(
        lambda p: reference.loss(p, config["model"], batch))(params)
    assert float(blocked[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    for got, want in zip(jax.tree.leaves(blocked[1]),
                         jax.tree.leaves(whole[1])):
        assert _max_rel(got, want) <= 1e-5


def test_the_routers_bias_stays_at_its_seeded_value():
    """No gradient reaches it and the weight decay is masked off it, so
    AdamW leaves its bits alone; every other leaf moves."""
    config = _tiny_config()
    system, _, batch = _system_and_batch(config, "ring-1x64")
    params = system.init(jax.random.PRNGKey(3))[0]
    state = system.optimizer.init(params)
    grads = jax.grad(system.loss_fn)(params, batch)
    updates, _ = system.optimizer.update(grads, state, params)
    flat = jax.tree_util.tree_flatten_with_path(updates)[0]
    for path, u in flat:
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
    assert read["hybrid.pairs_per_expert"]["value"] == pairs / held / 2
    assert read["hybrid.load_max_over_mean"]["value"] >= 1.0
    for kind, count in (("kda", 2), ("mla", 1), ("dense", 1),
                        ("experts", 2)):
        assert metrics.get_gauge("model.layer_kinds", {"kind": kind}) == count
    assert metrics.get_gauge("model.layer_applications") == 3
    line = bench_run.result_line(run, correct, True, jax.devices()[:1])
    assert json.loads(json.dumps(line))["correct"] is True
    # every timed reader runs on this trace without the chip's planes
    timed = harness.metrics_of(run, cell.per_layer, on_chip=True)
    assert not set(timed) & set(TIMED_METRICS)
    # the scopes the readers anchor on are in the compiled step
    text = run.step_hlo
    for scope in ("block_0/kda/", "/kda/conv/", "/kda/gate/", "/kda/core/",
                  "block_2/attn/", "/mla/", "block_1/moe/", "/moe/router/",
                  "/dispatch/", "/experts/", "/combine/", "/moe/shared/",
                  "/head/", "/embed/", "(loss)",
                  "rematted_computation/block_", "hvd_compute_grads"):
        assert scope in text, scope
    assert "block_3/" not in text
    seen = _earlier_lines(capsys)
    assert len(seen["check.system_losses"]) == 4


def test_fixture_uses_the_real_tolerance_and_settings():
    real = manifest.load_json(CHECKOUT / "benchmark/configs/ling3flash.json")
    tiny = manifest.load_json(FIXTURE / "configs/hybridmoe_tiny.json")
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
    ``pairs_per_token`` = held experts).  The delta rule is left out of both
    sides: XLA counts a loop's body once, so the recurrence reads as one
    token's."""
    seq, rows = 256, 2
    monkeypatch.setattr(reference, "QUERY_BLOCK", seq)
    config = _tiny_config(
        hidden_size=256, head_dim=64, v_head_dim=64, num_attention_heads=4,
        num_key_value_heads=4, intermediate_size=704, kv_lora_rank=128,
        qk_nope_head_dim=64, qk_rope_head_dim=32, qk_head_dim=96,
        moe_intermediate_size=96, moe_shared_expert_intermediate_size=96,
        vocab_size=768, max_position_embeddings=1024)
    model = config["model"]
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": seq}).init(k)[0],
        jax.random.PRNGKey(0))
    counted = _xla_flops(
        lambda p, t: reference.logits(p, model, t), params,
        jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    held = model["experts_held"][1] - model["experts_held"][0]
    dense_loop = dict(model, num_experts_per_tok=model["router_width"])
    assert ops.pairs_per_token(dense_loop) == held
    units, sum_sq = rows * seq, rows * seq * seq
    full = ops.forward_flops(dense_loop, units, sum_sq, causal=False) \
        - 2 * ops.kda_core_ops(model, units)
    assert counted == pytest.approx(full, rel=0.03)
    # causal: exactly the masked share of the one softmax layer less
    causal = ops.forward_flops(dense_loop, units, sum_sq)
    per_pair = 2 * model["num_attention_heads"] * (
        model["qk_head_dim"] + model["v_head_dim"])
    assert full + 2 * ops.kda_core_ops(model, units) - causal == \
        per_pair * rows * (seq * seq - seq) / 2


def test_the_count_at_the_published_widths():
    model = manifest.load_json(
        CHECKOUT / "benchmark/configs/ling3flash.json")["model"]
    assert ops.kda_matmul_params(model) == 5 * 2560 * 4096 + 2 * 2560 * 32
    assert ops.mla_matmul_params(model) == (
        2560 * 6144 + 2560 * 576 + 512 * 8192 + 4096 * 2560 + 2560 * 32)
    assert ops.pairs_per_token(model) == 8 * 8 / 512
    expert = 3 * 2560 * 768
    assert ops.ffn_matmul_params(model, "experts") == (
        2560 * 512 + expert + 0.125 * expert)
    assert ops.ffn_matmul_params(model, "dense") == 3 * 2560 * 6144
    units, seq = 4096, 4096
    pairs = seq * (seq + 1) / 2
    per_token = (6 * ops.kda_matmul_params(model)
                 + ops.mla_matmul_params(model) + 3 * 2560 * 6144
                 + 6 * ops.ffn_matmul_params(model, "experts")
                 + 19648 * 2560)
    want = 3 * (2 * units * per_token + 2 * 32 * (192 + 128) * pairs
                + 6 * 7 * 32 * 128 * 128 * units)
    assert ops.train_flops(model, units, seq * seq) == want
    assert want == pytest.approx(12.9e12, rel=0.01)  # ~13 TFLOP a step
    # the delta rule's roofline: bytes bind, not operations
    core_ops, core_bytes = ops.kda_core_step(model, units, units)
    assert core_ops == 6 * 3 * 7 * 32 * 128 * 128 * units
    assert core_bytes / 819e9 > core_ops / 197e12


# --------------------------------------------------- the configuration file
def test_configuration_keeps_the_published_sizes():
    config = manifest.load_json(
        CHECKOUT / "benchmark/configs/ling3flash.json")
    model = config["model"]
    published = {
        "hidden_size": 2560, "num_attention_heads": 32, "head_dim": 128,
        "num_key_value_heads": 32, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_per_tok": 8,
        "n_group": 8, "topk_group": 4, "routed_scaling_factor": 2.5,
        "kv_lora_rank": 512, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
        "qk_head_dim": 192, "v_head_dim": 128, "q_lora_rank": None,
        "rope_theta": 6000000, "rms_norm_eps": 1e-06, "layer_group_size": 6,
        "first_k_dense_replace": 2, "short_conv_kernel_size": 4,
        "kda_lower_bound": -5, "num_shared_experts": 1,
        "max_position_embeddings": 262144, "model_type": "bailing_hybrid",
    }
    for key, value in published.items():
        assert model[key] == config[key] == value, key
    assert len(model["expert_swiglu_limit_list"]) == 42  # copied whole
    assert config["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size",
        "num_nextn_predict_layers"]
    assert [model[k] for k in config["reduced"]] == [7, 8, 19648, 0]
    assert config["published"]["num_hidden_layers"] == 42
    assert config["published"]["num_experts"] == 512
    assert config["published"]["vocab_size"] == 157184 == 8 * 19648
    assert set(config["changed"]) == set(config["reduced"])
    assert model["layers_held"] == [1, 6, 7, 8, 9, 10, 11]
    assert model["experts_held"] == [0, 8] and model["router_width"] == 512
    # one leading dense layer and one whole period, five KDA then one MLA
    assert family.layer_kinds(model) == [("kda", "dense")] + [
        ("kda", "experts")] * 5 + [("mla", "experts")]
    cfg = family.transformer_config(config, manifest.load_json(
        CHECKOUT / "benchmark/traffic/ring-1x4096.json"))
    assert (cfg.model_dim, cfg.num_heads, cfg.head_dim, cfg.ff_dim) == (
        2560, 32, 128, 6144)
    assert (cfg.num_experts, cfg.experts_held, cfg.expert_ff_dim) == (
        512, (0, 8), 768)
    assert cfg.remat and not cfg.tie_head
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": 4096}).init(k)[0],
        jax.random.PRNGKey(0))["params"]
    size = lambda tree: sum(x.size for x in jax.tree.leaves(tree))
    assert size(params) == 822_036_416
    assert size(params["block_0"]["kda"]) == 52_646_048     # ISSUE: 52.6 M
    assert size(params["block_6"]["attn"]) == 31_965_696    # 31.9 M
    assert size(params["block_1"]["moe"]) == 9 * 5_898_240 + 2560 * 512 + 512
    assert params["head"].shape == (19648, 2560)
    # a configuration the family cannot build is refused, not run
    for key, value in (("q_lora_rank", 1536), ("use_nGPT", True),
                       ("num_nextn_predict_layers", 1),
                       ("layers_held", [1, 36, 37, 38, 39, 40, 41])):
        broken = dict(config, model=dict(model, **{key: value}))
        with pytest.raises(ValueError, match="does not build"):
            family.transformer_config(broken, {"seq_len": 4096})


# ------------------------------------------------------------ the readers
def _reader(name):
    path = manifest.PACKAGE_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


_PRE = "jit(step_body)/hvd_compute_grads/jvp(T)"
_BWD = "jit(step_body)/hvd_compute_grads/transpose(jvp(T))/jvp(T)/checkpoint"
HLO = f'''HloModule jit_step_body, is_scheduled=true

ENTRY %main.9 (q: bf16[1,4096,4096]) -> f32[8] {{
  %q = bf16[1,4096,4096]{{2,1,0}} parameter(0)
  %proj.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_PRE}/block_0/kda/q/dot_general"}}
  %core.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_PRE}/block_0/kda/core/while/body/dot_general"}}
  %fwd.1 = bf16[1,4096,4096]{{2,1,0}} custom-call(%q, %q, %q), custom_call_target="tpu_custom_call", metadata={{op_name="{_PRE}/block_6/attn/pallas_call"}}
  %rout.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_PRE}/block_6/moe/router/dot_general"}}
  %exp.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_PRE}/block_6/moe/while/body/experts/dot_general"}}
  %shar.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_PRE}/block_6/moe/shared/shared/wi/dot_general"}}
  %head.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_PRE}/head/dot_general"}}
  %core.2 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_BWD}/rematted_computation/block_0/kda/core/while/body/dot_general"}}
  %core.3 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_BWD}/block_0/kda/core/while/body/transpose"}}
  %bwd.1 = bf16[1,4096,4096]{{2,1,0}} custom-call(%q, %q, %q), custom_call_target="tpu_custom_call", metadata={{op_name="{_BWD}/block_6/attn/flash_bwd/flash_bwd_dq_dkv/pallas_call"}}
  %comb.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="{_BWD}/block_6/moe/while/body/combine/scatter-add"}}
  ROOT %upd.1 = f32[8]{{0}} fusion(%q), kind=kLoop, calls=%f, metadata={{op_name="jit(step_body)/hvd_reduce_and_update/adamw/mul"}}
}}
'''
# one step of 100 ms, as (instruction, start, end) in ms from its start
STEP = [("%proj.1 = fusion()", 0, 5), ("%core.1 = fusion()", 5, 15),
        ("%fwd.1 = custom-call(...)", 15, 17), ("%rout.1 = fusion()", 17, 18),
        ("%exp.1 = fusion()", 18, 21), ("%shar.1 = fusion()", 21, 23),
        ("%head.1 = fusion()", 23, 30), ("%core.2 = fusion()", 30, 40),
        ("%core.3 = fusion()", 40, 60), ("%bwd.1 = custom-call(...)", 60, 66),
        ("%comb.1 = fusion()", 66, 70), ("%upd.1 = fusion()", 70, 95)]


@dataclasses.dataclass
class _TracedRun:
    """What a reader looks at, from a trace made by hand."""

    cell: manifest.Cell
    completions: list
    _reduced: reduce.Reduced
    chips: int = 1
    device_kind: str = "TPU v5 lite"

    model = property(lambda self: self.cell.config["model"])
    ops = ops

    def reduced(self):
        return self._reduced

    def work(self):
        from benchmark.traffic import Work

        n = len(self.completions)
        return Work(n * 4096, n * 4096 * 4096, n * 4096)


def _hand_made_run():
    events = [xplane.Event(n, (a + 100 * k) * 1e6, (b + 100 * k) * 1e6)
              for k in range(4) for n, a, b in STEP]
    modules = [xplane.Event("jit_step_body(1)", 100e6 * k, 100e6 * (k + 1))
               for k in range(4)]
    trace = xplane.Trace([xplane.Plane("/device:TPU:0", {
        xplane.OPS_LINE: events, xplane.MODULES_LINE: modules})])
    return _TracedRun(manifest.load_cell(REAL_CELL), [None] * 3,
                      reduce.Reduced(trace, hlo.Module(HLO)))


def test_readers_on_a_trace_made_by_hand(capsys):
    run = _hand_made_run()
    assert _reader("hybrid.kda_ms")(run) == pytest.approx(5 + 10 + 10 + 20)
    assert _reader("hybrid.kda_core_ms")(run) == pytest.approx(40)
    assert _reader("hybrid.mla_ms")(run) == pytest.approx(2 + 6)
    assert _reader("hybrid.moe_ms")(run) == pytest.approx(1 + 3 + 2 + 4)
    assert _reader("hybrid.moe_dispatch_ms")(run) == pytest.approx(1 + 4)
    assert _reader("hybrid.recompute_ms")(run) == pytest.approx(10)
    model = run.model
    core_ops, core_bytes = ops.kda_core_step(model, 4096, 4096)
    assert _reader("hybrid.kda_core_roofline")(run) == pytest.approx(
        100 * (core_bytes / 819e9) / 0.040)
    pairs = 4096 * 4097 / 2
    flash_ops = 2 * 32 * (192 + 128) * pairs
    assert _reader("hybrid.mla_flash_fwd_roofline")(run) == pytest.approx(
        100 * (flash_ops / 197e12) / 0.002)
    assert _reader("hybrid.mla_flash_bwd_roofline")(run) == pytest.approx(
        100 * (2 * flash_ops / 197e12) / 0.006)
    out = capsys.readouterr().out
    assert "hybrid.kda_core_roofline.bound_by: bytes" in out
    assert "hybrid.mla_flash_fwd_roofline.calls_per_step: 1.0" in out
    assert "hybrid.mla_flash_bwd_roofline.bound_by: operations" in out


def test_readers_find_nothing_in_a_program_without_the_scopes():
    """The parent's program, or another family's: no such span or gauge,
    and every reader says None or 0 and raises nothing."""
    only_update = "\n".join(
        line for line in HLO.splitlines()
        if "op_name" not in line or "hvd_reduce_and_update" in line)
    events = [xplane.Event("%upd.1 = fusion()", (70 + 100 * k) * 1e6,
                           (95 + 100 * k) * 1e6) for k in range(4)]
    modules = [xplane.Event("jit_step_body(1)", 100e6 * k, 100e6 * (k + 1))
               for k in range(4)]
    trace = xplane.Trace([xplane.Plane("/device:TPU:0", {
        xplane.OPS_LINE: events, xplane.MODULES_LINE: modules})])
    run = _TracedRun(manifest.load_cell(REAL_CELL), [None] * 3,
                     reduce.Reduced(trace, hlo.Module(only_update)))
    for name in TIMED_METRICS:
        assert not _reader(name)(run), name
    for gauge in ("model.moe.pairs_per_step", "model.moe.experts_held",
                  "model.moe.load_max_over_mean", "model.layer_kinds"):
        metrics.clear_gauge(gauge)
    for name in COUNTED_METRICS:
        assert _reader(name)(run) is None, name
    # and without a trace at all
    run._reduced = None
    for name in TIMED_METRICS:
        assert _reader(name)(run) is None, name
