"""The looped decoder (Ouro / LoopLM, ``ouro26b``): the system against its
plain reference at a tiny size with three passes, the tiny stand-in of its
cell through the harness, the check failing for what it has to catch, the
operation count, the vocabulary share, and the readers of its per-layer
metrics."""

import ast
import dataclasses
import gzip
import importlib.util
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check, harness, manifest
from benchmark.families import looplm as family
from benchmark.ops import looplm as ops
from benchmark.reference import looplm as reference
from benchmark.trace import calls, hlo, reduce, xplane
from benchmark.traffic import Traffic
from horovod_tpu import metrics
from horovod_tpu.models import transformer

from tiny_cells import CHECKOUT, HERE, TINY, run_tiny

# the stand-in of the real cell: fixture/cells/looplm_tiny.ring2x64.json
REAL_CELL = "ouro26b.ring2x4096"
TINY_CELL = TINY[REAL_CELL][0]

FIXTURE = HERE / "fixture"
LOOP_METRICS = (
    "loop.blocks_ms", "loop.recompute_ms", "loop.head_gate_loss_ms",
    "loop.flash_fwd_roofline", "loop.flash_bwd_roofline",
    "loop.layer_applications")


def _tiny_config(**model):
    config = manifest.load_json(FIXTURE / "configs" / "looplm_tiny.json")
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
    """Norm scales start at one and the gate's bias at zero: move every
    leaf, so that no gradient is tested at a special point."""
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(11), len(leaves))
    return jax.tree.unflatten(treedef, [
        x + scale * jax.random.normal(k, x.shape, x.dtype)
        for x, k in zip(leaves, keys)])


def _max_rel(got, want):
    scale = max(float(jnp.max(jnp.abs(want))), 1e-6)
    return float(jnp.max(jnp.abs(got - want))) / scale


def _assert_trees_close(got, want, tol):
    flat_got = jax.tree_util.tree_flatten_with_path(got)[0]
    flat_want = jax.tree.leaves(want)
    assert len(flat_got) == len(flat_want)
    for (path, g), w in zip(flat_got, flat_want):
        assert _max_rel(g, w) <= tol, jax.tree_util.keystr(path)


# ------------------------------------------- the system against the reference
@pytest.mark.parametrize("traffic_name", ["ring-2x64", "packed-docs-8x64"])
def test_every_pass_the_loss_and_every_gradient_match_the_reference(
        traffic_name):
    config = _tiny_config()
    model = config["model"]
    assert model["total_ut_steps"] == 3 and model["num_hidden_layers"] == 2
    system, mix, batch = _system_and_batch(config, traffic_name)
    packed = isinstance(batch, tuple)
    tokens, segments = batch if packed else (batch, None)
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    net = transformer.Transformer(family.transformer_config(config, mix))
    with jax.default_matmul_precision("highest"):
        logits, exits, _ = net.apply(params, tokens, segments)
        ref_logits, ref_gates = reference.logits_and_gates(
            params, model, tokens, segments)
        loss, grads = jax.value_and_grad(system.loss_fn)(params, batch)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference.loss(p, model, batch))(params)
    assert logits.shape == (3,) + tokens.shape + (model["vocab_size"],)
    for step in range(3):  # each pass, so that none hides behind another
        assert _max_rel(logits[step], ref_logits[step]) <= 1e-4, step
        np.testing.assert_allclose(
            jax.nn.sigmoid(exits[step]), ref_gates[step], atol=1e-5)
    # the passes differ: a loop that ran one pass three times would not
    assert _max_rel(logits[1], logits[2]) > 1e-2
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    # float32 sums in another order: 1e-4 of each leaf's largest gradient
    _assert_trees_close(grads, ref_grads, 1e-4)
    if packed:
        assert int(segments.max()) > 1  # several documents a row


def test_reference_in_blocks_of_queries_is_the_reference(monkeypatch):
    config = _tiny_config()
    system, _, batch = _system_and_batch(config, "packed-docs-8x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    whole = jax.value_and_grad(
        lambda p: reference.loss(p, config["model"], batch))(params)
    monkeypatch.setattr(reference, "QUERY_BLOCK", 16)  # four blocks a row
    blocked = jax.value_and_grad(
        lambda p: reference.loss(p, config["model"], batch))(params)
    assert float(blocked[0]) == pytest.approx(float(whole[0]), rel=1e-6)
    _assert_trees_close(blocked[1], whole[1], 1e-5)


def test_one_pass_is_the_plain_cross_entropy():
    """S = 1: p_1 = 1 and H = 0 whatever the gate says."""
    config = _tiny_config(total_ut_steps=1)
    system, mix, batch = _system_and_batch(config, "ring-2x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    plain = transformer.Transformer(dataclasses.replace(
        family.transformer_config(config, mix), exit_gate=False))
    logits, _ = plain.apply({"params": {
        k: v for k, v in params["params"].items() if k != "exit_gate"}},
        batch)
    want = transformer.token_cross_entropy(
        logits, jnp.roll(batch, -1, axis=-1))
    loss, grads = jax.value_and_grad(system.loss_fn)(params, batch)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    assert float(loss) == pytest.approx(
        float(reference.loss(params, config["model"], batch)), rel=1e-5)
    gate = grads["params"]["exit_gate"]
    assert not np.any(gate["kernel"]) and not np.any(gate["bias"])


def test_a_parameters_gradient_is_the_sum_over_its_uses():
    """The reference with a tree of its own for every pass: the shared
    parameter's gradient is the sum of the per-pass ones, and the system's
    equals it."""
    config = _tiny_config()
    model = config["model"]
    system, _, batch = _system_and_batch(config, "ring-2x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])
    targets = jnp.roll(batch, -1, axis=-1)

    def untied(trees):
        lg, lam = reference.logits_and_gates(trees, model, batch)
        return reference.expected_loss(lg, lam, targets,
                                       model["entropy_beta"])

    with jax.default_matmul_precision("highest"):
        per_use = jax.grad(untied)([params] * 3)
        grads = jax.grad(system.loss_fn)(params, batch)
    summed = jax.tree.map(lambda *g: sum(g), *per_use)
    _assert_trees_close(grads, summed, 1e-4)
    # no use is idle: every pass contributes to a layer's gradient
    for g in per_use:
        assert float(jnp.max(jnp.abs(
            g["params"]["block_1"]["mlp"]["wo"]["Dense_0"]["kernel"]))) > 0


def test_vocabulary_slices_add_up_to_the_uncut_model():
    """The deployment's share: a chip holds rows of the head (and of the
    embedding) and nothing else differs.  The eight slices' logits of a
    pass, side by side, are the uncut reference's, and the uncut loss
    follows from them by one log-sum-exp."""
    config = _tiny_config()
    model = config["model"]
    system, _, batch = _system_and_batch(config, "ring-2x64")
    params = _perturbed(system.init(jax.random.PRNGKey(3))[0])["params"]
    vocab, shares = model["vocab_size"], 8
    rows = vocab // shares
    uncut, _ = reference.logits_and_gates({"params": params}, model, batch)
    parts = []
    for s in range(shares):
        held = slice(s * rows, (s + 1) * rows)
        share = dict(params, head=params["head"][held])
        # every chip embeds the same tokens: a token's row lives on one
        # chip, which is the lookup's exchange and not the slice's result
        lg, _ = reference.logits_and_gates(
            {"params": share}, dict(model, vocab_size=rows), batch)
        assert lg.shape[-1] == rows
        parts.append(lg)
    together = jnp.concatenate(parts, axis=-1)
    np.testing.assert_allclose(together, uncut, rtol=1e-5, atol=1e-5)
    # the embedding's slice: the rows a chip holds are the uncut table's
    table = params["wte"]["embedding"]
    np.testing.assert_array_equal(
        jnp.concatenate([table[s * rows:(s + 1) * rows]
                         for s in range(shares)]), table)
    # the uncut cross-entropy from the slices' own log-sum-exps
    targets = jnp.roll(batch, -1, axis=-1)
    logz = jax.nn.logsumexp(jnp.stack(
        [jax.nn.logsumexp(p, axis=-1) for p in parts]), axis=0)
    picked = jnp.take_along_axis(
        together, jnp.broadcast_to(targets, logz.shape)[..., None],
        axis=-1)[..., 0]
    np.testing.assert_allclose(
        logz - picked,
        reference._cross_entropy(uncut, jnp.broadcast_to(targets,
                                                         logz.shape)),
        rtol=1e-5, atol=1e-5)


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
    applications = model["num_hidden_layers"] * model["total_ut_steps"]
    assert {m.name for m in cell.per_layer} >= set(LOOP_METRICS)
    read = harness.metrics_of(run, cell.per_layer, on_chip=False)
    # off the chip only the count is given, and it is the gauge's
    assert set(read) & set(LOOP_METRICS) == {"loop.layer_applications"}
    assert read["loop.layer_applications"]["value"] == applications == 6
    assert metrics.get_gauge("model.layer_applications") == 6
    assert metrics.get_gauge("model.ut_steps") == 3
    line = bench_run.result_line(run, correct, True, jax.devices()[:1])
    assert json.loads(json.dumps(line))["correct"] is True
    # every timed reader runs on this trace without the chip's planes
    timed = harness.metrics_of(run, cell.per_layer, on_chip=True)
    assert not set(timed) & (set(LOOP_METRICS) - {"loop.layer_applications"})
    # the scopes the readers anchor on are in the compiled step
    text = run.step_hlo
    for scope in ("ut_0/block_0/", "ut_2/block_1/", "/head/", "/exit_gate/",
                  "/embed/", "(loss)", "rematted_computation/block_",
                  "hvd_compute_grads"):
        assert scope in text, scope
    assert "ut_3/" not in text
    seen = _earlier_lines(capsys)
    assert len(seen["check.system_losses"]) == 4


def test_fixture_uses_the_real_tolerance_and_settings():
    real = manifest.load_json(CHECKOUT / "benchmark/configs/ouro26b.json")
    tiny = manifest.load_json(FIXTURE / "configs/looplm_tiny.json")
    assert real["check"]["loss_rtol"] == tiny["check"]["loss_rtol"]
    assert real["check"]["steps"] == tiny["check"]["steps"] == 4
    assert real["check"]["sample_rows_per_chip"] == 1
    assert real["check"]["why"]
    for key in ("remat", "remat_save", "compression", "activation_dtype",
                "attn_impl"):
        assert real[key] == tiny[key], key
    # the same AdamW; the fixture starts at the peak rate (its note says why)
    assert dict(real["optimizer"], warmup_from=3e-4) == tiny["optimizer"]
    assert real["model"]["entropy_beta"] == tiny["model"]["entropy_beta"]


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


def _skipped_pass(monkeypatch):
    real = family.transformer_config

    def one_pass_less(config, traffic):
        cfg = real(config, traffic)
        return dataclasses.replace(cfg, ut_steps=cfg.ut_steps - 1)

    monkeypatch.setattr(family, "transformer_config", one_pass_less)


def _gate_of_the_wrong_pass(monkeypatch):
    real = transformer.looped_token_cross_entropy

    def shifted(logits, exit_logits, *args, **kwargs):
        return real(logits, jnp.roll(exit_logits, 1, axis=0), *args,
                    **kwargs)

    monkeypatch.setattr(transformer, "looped_token_cross_entropy", shifted)


def _zeroed_dq(monkeypatch):
    from horovod_tpu.ops import pallas_kernels

    real = pallas_kernels._flash_bwd_chunked

    def zero_dq(*args, **kwargs):
        dq, dk, dv = real(*args, **kwargs)
        return jnp.zeros_like(dq), dk, dv

    monkeypatch.setattr(pallas_kernels, "_flash_bwd_chunked", zero_dq)


@pytest.mark.parametrize("fault, first_loss_agrees", [
    (_bf16_parameters, True), (_skipped_pass, False),
    (_gate_of_the_wrong_pass, False), (_zeroed_dq, True)],
    ids=["bf16_parameters", "skipped_pass", "gate_of_the_wrong_pass",
         "zeroed_dq"])
def test_fault_fails_the_check(fault, first_loss_agrees, tiny_root,
                               quiet_runtime, monkeypatch, capsys):
    fault(monkeypatch)
    cell, run, correct = run_tiny(tiny_root, TINY_CELL, seed=7, seconds=0.1)
    seen = _earlier_lines(capsys)
    assert not correct
    assert run.failed == 0  # every loss finite: the comparison caught it
    assert seen["check.replicas_identical"] == (True, True)
    rtol = cell.config["check"]["loss_rtol"]
    system, ref = seen["check.system_losses"], seen["check.reference_losses"]
    assert not check.losses_agree(system, ref, rtol)
    # a fault of the backward or of the parameters' type shows only after
    # the first update; one of the forward pass at once
    assert check.losses_agree(system[:1], ref[:1], rtol) is first_loss_agrees


# -------------------------------------------------------- operation count
def _xla_flops(fn, *shapes) -> float:
    cost = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


SMALL = dict(hidden_size=256, head_dim=128, intermediate_size=704,
             vocab_size=768, max_position_embeddings=1024)
WIDTHS = dict(hidden_size=2048, head_dim=128, num_attention_heads=16,
              num_key_value_heads=16, intermediate_size=5632,
              vocab_size=6144, max_position_embeddings=1024)


@pytest.mark.parametrize("sizes, seq", [(SMALL, 256), (WIDTHS, 1024)],
                         ids=["small", "published_widths"])
def test_count_matches_xla_within_2_percent(sizes, seq, monkeypatch):
    """Two layers, compiled on the CPU and never run.  At the
    fixture's width of 32 the norms' and the rotation's elementwise
    operations, which the count leaves out, are 7% of XLA's; from a width
    of 256 on they are under 1%."""
    # XLA counts a loop's body once: one block of queries, and one pass
    # of the reference's scan over the passes
    monkeypatch.setattr(reference, "QUERY_BLOCK", seq)
    config = _tiny_config(**sizes)
    looped = config["model"]
    model = dict(looped, total_ut_steps=1)
    rows = 2
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": seq}).init(k)[0],
        jax.random.PRNGKey(0))
    counted = _xla_flops(
        lambda p, t: reference.logits_and_gates(p, model, t), params,
        jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    for causal in (False, True):  # a layer and the head once every pass
        assert ops.forward_flops(looped, rows * seq, rows * seq * seq,
                                 causal) == 3 * ops.forward_flops(
            model, rows * seq, rows * seq * seq, causal)
    # XLA counts the whole T x T score matrix: compare with the unmasked
    # count, then hold the causal one to exactly the masked share of it.
    full = ops.forward_flops(model, rows * seq, rows * seq * seq,
                             causal=False)
    assert counted == pytest.approx(full, rel=0.02)
    causal = ops.forward_flops(model, rows * seq, rows * seq * seq)
    per_pair = (4 * model["total_ut_steps"] * model["num_hidden_layers"]
                * model["num_attention_heads"] * model["head_dim"])
    assert full - causal == per_pair * rows * (seq * seq - seq) / 2


def test_a_layer_and_the_head_count_once_a_pass():
    model = manifest.load_json(
        CHECKOUT / "benchmark/configs/ouro26b.json")["model"]
    assert ops.layer_matmul_params(model) == 51_380_224
    s, layers, vocab = 4, 6, 6144
    units, seq = 2 * 4096, 4096
    pairs = 2 * seq * (seq + 1) / 2
    issue = 3 * (2 * units * (s * layers * 51_380_224 + s * vocab * 2048
                              + s * 2048)
                 + 4 * s * layers * 16 * 128 * pairs)
    assert ops.train_flops(model, units, 2 * seq * seq) == issue
    assert issue / units == pytest.approx(8.909e9, rel=1e-4)  # a token
    one = dict(model, total_ut_steps=1)
    assert ops.train_flops(model, units, 2 * seq * seq) == \
        4 * ops.train_flops(one, units, 2 * seq * seq)
    # attention's share of a layer's required work at 4096 tokens
    attention = 4 * 16 * 128 * pairs
    layer = 2 * units * 51_380_224 + attention
    assert attention / layer == pytest.approx(0.14, abs=0.005)


# --------------------------------------------------- the configuration file
def test_configuration_keeps_the_published_sizes():
    config = manifest.load_json(CHECKOUT / "benchmark/configs/ouro26b.json")
    model = config["model"]
    published = {
        "head_dim": 128, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 5632, "max_position_embeddings": 65536,
        "max_window_layers": 48, "model_type": "ouro",
        "num_attention_heads": 16, "num_key_value_heads": 16,
        "rms_norm_eps": 1e-06, "rope_scaling": None, "rope_theta": 1000000,
        "sliding_window": None, "tie_word_embeddings": False,
        "total_ut_steps": 4, "early_exit_threshold": 1,
        "use_sliding_window": False,
    }
    for key, value in published.items():
        assert model[key] == value, key
    assert model["layer_types"] == ["full_attention"] * 48
    assert config["reduced"] == ["num_hidden_layers", "vocab_size"]
    assert (model["num_hidden_layers"], model["vocab_size"]) == (6, 6144)
    assert config["published"]["num_hidden_layers"] == 48
    assert config["published"]["vocab_size"] == 49152
    # the keys as run stand at the file's top level too, under the
    # source's names, and say the same as ``model``
    for key in list(published) + ["layer_types", "num_hidden_layers",
                                  "vocab_size"]:
        assert config[key] == model[key], key
    cfg = family.transformer_config(config, manifest.load_json(
        CHECKOUT / "benchmark/traffic/ring-2x4096.json"))
    assert (cfg.model_dim, cfg.num_heads, cfg.head_dim, cfg.ff_dim) == (
        2048, 16, 128, 5632)
    assert cfg.ut_steps == 4 and cfg.exit_gate and not cfg.tie_head
    params = jax.eval_shape(
        lambda k: family.build(config, {"seq_len": 4096}).init(k)[0],
        jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(params)) == 333_500_417
    layer = params["params"]["block_0"]
    assert sum(x.size for x in jax.tree.leaves(layer)) == 51_388_416


# ------------------------------------------------------------ the readers
def _reader(name):
    path = manifest.PACKAGE_DIR / "layer_metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("reader", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read


HLO = '''HloModule jit_step_body, is_scheduled=true

ENTRY %main.7 (q: bf16[2,16,4096,128]) -> f32[8] {
  %q = bf16[2,16,4096,128]{3,2,1,0} parameter(0)
  %fwd.1 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q, %q, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_body)/hvd_compute_grads/jvp(T)/ut_0/block_0/attn/pallas_call"}
  %head.1 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f, metadata={op_name="jit(step_body)/hvd_compute_grads/jvp(T)/ut_0/head/dot_general"}
  %fwd.2 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q, %q, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_body)/hvd_compute_grads/transpose(jvp(T))/ut_0/jvp(T)/ut_0/checkpoint/rematted_computation/block_0/attn/pallas_call"}
  %mlp.2 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f, metadata={op_name="jit(step_body)/hvd_compute_grads/transpose(jvp(T))/ut_0/jvp(T)/ut_0/checkpoint/rematted_computation/block_0/mlp/wi/dot_general"}
  %bwd.1 = bf16[2,16,4096,128]{3,2,1,0} custom-call(%q, %q, %q), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_body)/hvd_compute_grads/transpose(jvp(T))/ut_0/jvp(T)/ut_0/checkpoint/block_0/attn/flash_bwd/flash_bwd_dq_dkv/pallas_call"}
  ROOT %upd.1 = f32[8]{0} fusion(%q), kind=kLoop, calls=%f, metadata={op_name="jit(step_body)/hvd_reduce_and_update/adamw/mul"}
}
'''
# one step of 100 ms, as (instruction, start, end) in ms from its start
STEP = [("%fwd.1 = custom-call(...)", 0, 10), ("%head.1 = fusion()", 10, 14),
        ("%fwd.2 = custom-call(...)", 20, 32), ("%mlp.2 = fusion()", 32, 40),
        ("%bwd.1 = custom-call(...)", 40, 80), ("%upd.1 = fusion()", 80, 90)]


@dataclasses.dataclass
class _TracedRun:
    """What a reader looks at, from a trace made by hand."""

    cell: manifest.Cell
    completions: list
    _reduced: reduce.Reduced
    chips: int = 1
    device_kind: str = "TPU v5 lite"

    model = property(lambda self: self.cell.config["model"])

    def reduced(self):
        return self._reduced

    def work(self):
        from benchmark.traffic import Work

        rows, seq = 2, 4096
        n = len(self.completions)
        return Work(n * rows * seq, n * rows * seq * seq, n * rows * seq)


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
    assert _reader("loop.blocks_ms")(run) == pytest.approx(10 + 12 + 8 + 40)
    assert _reader("loop.recompute_ms")(run) == pytest.approx(12 + 8)
    assert _reader("loop.head_gate_loss_ms")(run) == pytest.approx(4)
    # two forward calls a step (one recomputed) in 22 ms, one backward in 40
    assert calls.per_step(run.reduced(), "attn/pallas_call") == (
        2, pytest.approx(0.022))
    assert calls.per_step(run.reduced(), "flash_bwd") == (
        1, pytest.approx(0.040))
    assert calls.per_step(run.reduced(), "no_such_kernel") is None
    pairs = 2 * 4096 * 4097 / 2
    one_call = 4 * 16 * 128 * pairs / 197e12  # bound by operations
    assert _reader("loop.flash_fwd_roofline")(run) == pytest.approx(
        100 * 2 * one_call / 0.022)
    assert _reader("loop.flash_bwd_roofline")(run) == pytest.approx(
        100 * 2 * one_call / 0.040)
    out = capsys.readouterr().out
    assert "loop.flash_fwd_roofline.calls_per_step: 2.0" in out
    assert "loop.flash_bwd_roofline.bound_by: operations" in out


def test_calls_on_the_trace_recorded_on_four_chips():
    data = HERE / "data"
    module = hlo.Module(gzip.decompress(
        (data / "rec_dp4.step.hlo.txt.gz").read_bytes()).decode())
    reduced = reduce.Reduced(xplane.load(data / "rec_dp4.xplane.pb"), module)
    per_step, seconds = calls.per_step(reduced, "attn/pallas_call")
    assert per_step == 2  # gpt_rec's two layers, nothing recomputed
    assert seconds == pytest.approx(
        reduced.kernel_seconds_per_step("attn/pallas_call"))
    # a program from before the backward kernel has no such call
    assert calls.per_step(reduced, "flash_bwd") is None
    assert calls.per_step(reduce.Reduced(xplane.load(
        data / "rec_dp4.xplane.pb")), "attn/pallas_call") is None
