"""The operation counts of ``benchmark/ops/`` against XLA's own count of
the plain reference's forward pass, at the published widths (compiled on
the CPU, never run)."""

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest
from benchmark.ops import flash_fwd, gpt as gpt_ops, resnet as resnet_ops
from benchmark.reference import gpt as gpt_ref, resnet as resnet_ref

from tiny_cells import CHECKOUT


def _xla_flops(fn, *shapes) -> float:
    cost = jax.jit(fn).lower(*shapes).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return float(cost["flops"])


def _model(name):
    return manifest.load_json(
        CHECKOUT / "benchmark" / "configs" / f"{name}.json")["model"]


def test_gpt2s_forward_matches_xla_within_3_percent():
    model = _model("gpt2s")
    rows, seq = 1, 1024
    params = jax.eval_shape(
        lambda: _gpt_params(model))
    counted = _xla_flops(
        lambda p, t: gpt_ref.logits(p, model, t), params,
        jax.ShapeDtypeStruct((rows, seq), jnp.int32))
    # XLA counts the whole T x T score matrix: compare with the unmasked
    # count, then hold the causal one to exactly the masked share of it.
    full = gpt_ops.forward_flops(model, rows * seq, rows * seq * seq,
                                 causal=False)
    assert counted == pytest.approx(full, rel=0.03)
    causal = gpt_ops.forward_flops(model, rows * seq, rows * seq * seq)
    per_pair = 4 * model["n_layer"] * model["n_embd"]
    assert full - causal == per_pair * rows * (seq * seq - seq) / 2


def _gpt_params(model):
    d, f, v = model["n_embd"], gpt_ops.ff_dim(model), model["vocab_size"]
    z = jnp.zeros
    dense = lambda i, o: {"kernel": z((i, o)), "bias": z((o,))}  # noqa: E731
    norm = {"scale": z((d,)), "bias": z((d,))}
    block = {
        "ln_attn": norm, "ln_mlp": norm,
        "attn": {"qkv": {"Dense_0": dense(d, 3 * d)},
                 "proj": {"Dense_0": {"kernel": z((d, d))}, "bias": z((d,))}},
        "mlp": {"wi": {"Dense_0": dense(d, f)},
                "wo": {"Dense_0": {"kernel": z((f, d))}, "bias": z((d,))}},
    }
    tree = {f"block_{i}": block for i in range(model["n_layer"])}
    tree.update(wte={"embedding": z((v, d))},
                wpe=z((model["n_positions"], d)), ln_f=norm)
    return {"params": tree}


def test_gpt2s_train_count_is_the_issues_798_mflop_per_token():
    model = _model("gpt2s")
    assert gpt_ops.matmul_params(model) == 123_568_128  # no wpe, no bias
    per_token = gpt_ops.train_flops(model, 1024, 1024 * 1024) / 1024
    assert per_token == pytest.approx(798.09e6, rel=1e-4)
    # two documents of 512 need half the attention of one of 1024
    packed = gpt_ops.train_flops(model, 1024, 2 * 512 * 512)
    dense = gpt_ops.train_flops(model, 1024, 1024 * 1024)
    matmuls = 6 * gpt_ops.matmul_params(model) * 1024
    assert (packed - matmuls) == pytest.approx(
        (dense - matmuls) * 513 / 1025)


def test_resnet50_forward_matches_xla_within_3_percent():
    model = _model("resnet50")
    size = model["image_size"]

    def params():
        tree = {"conv_init": {"kernel": jnp.zeros((7, 7, 3, 64))},
                "bn_init": _bn(64),
                "Dense_0": {"kernel": jnp.zeros((2048, 1000)),
                            "bias": jnp.zeros((1000,))}}
        for name, _, k, c_in, c_out in resnet_ops.convolutions(model)[1:]:
            block, conv = name.split("/")
            tree.setdefault(block, {})[conv] = {
                "kernel": jnp.zeros((k, k, c_in, c_out))}
            norm = ("norm_proj" if conv == "conv_proj"
                    else conv.replace("Conv", "BatchNorm"))
            tree[block][norm] = _bn(c_out)
        return tree

    counted = _xla_flops(
        lambda p, x: resnet_ref.logits(p, model, x),
        jax.eval_shape(params),
        jax.ShapeDtypeStruct((1, size, size, 3), jnp.float32))
    ours = resnet_ops.forward_flops_per_image(model)
    assert counted == pytest.approx(ours, rel=0.03)
    # 4.09 G multiply-adds are 8.18 GFLOP; training is three forwards:
    # 24.5 GFLOP an image, not the 12.3 of prof/peak.py
    assert ours == pytest.approx(8.18e9, rel=2e-3)
    assert resnet_ops.train_flops(model, 1) == pytest.approx(24.5e9,
                                                             rel=2e-3)
    assert len(resnet_ops.convolutions(model)) == 53


def _bn(c):
    return {"scale": jnp.zeros((c,)), "bias": jnp.zeros((c,))}


def test_flash_forward_ops_and_bytes():
    ops, nbytes = flash_fwd.ops_and_bytes(
        rows=16, seq_len=1024, heads=12, head_dim=64,
        units=16 * 1024, sum_sq=16 * 1024 * 1024)
    assert ops == 4 * 12 * 64 * 16 * (1024 * 1025 // 2)
    assert nbytes == 16 * 1024 * 12 * (4 * 64 * 2 + 4)
    full, _ = flash_fwd.ops_and_bytes(16, 1024, 12, 64, 16 * 1024,
                                      16 * 1024 * 1024, causal=False)
    assert full == pytest.approx(2 * ops, rel=2e-3)
