"""sync_gradients: the per-parameter pmean/psum rule for hybrid
parallelism, validated by multi-step training equivalence — a dp×tp
sharded model trained with sync_gradients must track single-device
training on the same global weights step for step."""

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import PartitionSpec as P

from horovod_tpu.models import gpt_tiny
from horovod_tpu.models.transformer import param_shard_axes
from horovod_tpu.parallel import make_mesh, sync_gradients


def test_param_shard_axes_classification():
    model = gpt_tiny(moe_every=2, num_experts_local=2)
    toks = jnp.zeros((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), toks)["params"]
    axes = param_shard_axes(params, model.cfg)

    assert axes["block_0"]["attn"]["qkv"]["Dense_0"]["kernel"] == "tp"
    assert axes["block_0"]["attn"]["qkv"]["Dense_0"]["bias"] == "tp"
    assert axes["block_0"]["attn"]["proj"]["Dense_0"]["kernel"] == "tp"
    assert axes["block_0"]["attn"]["proj"]["bias"] == ""
    assert axes["block_0"]["mlp"]["wi"]["Dense_0"]["kernel"] == "tp"
    assert axes["block_0"]["mlp"]["wo"]["Dense_0"]["kernel"] == "tp"
    assert axes["block_0"]["mlp"]["wo"]["bias"] == ""
    # block_1 is the MoE block (moe_every=2)
    assert axes["block_1"]["moe"]["wi"] == "ep"
    assert axes["block_1"]["moe"]["wo"] == "ep"
    assert axes["block_1"]["moe"]["router"]["kernel"] == ""
    assert axes["wte"]["embedding"] == ""
    assert axes["wpe"] == ""
    assert axes["ln_f"]["scale"] == ""


def test_hybrid_dp_tp_training_matches_single_device():
    """3 SGD steps on a dp=2 × tp=4 mesh == 3 steps on one device.

    The model mixes a replicated input projection (grad must be
    psum'd over tp, pmean'd over dp) with a column/row TP MLP (grad
    local over tp, pmean'd over dp)."""
    d, hidden, n_tp, n_dp = 8, 16, 4, 2
    hloc = hidden // n_tp
    key = jax.random.PRNGKey(7)
    k0, k1, k2, kx, kt = jax.random.split(key, 5)
    w_rep = jax.random.normal(k0, (d, d)) * 0.3
    wi = jax.random.normal(k1, (d, hidden)) * 0.3
    wo = jax.random.normal(k2, (hidden, d)) * 0.3
    bo = jnp.zeros((d,))
    x = jax.random.normal(kx, (8, d))
    tgt = jax.random.normal(kt, (8, d))
    lr = 0.1

    def forward(w_rep, wi, wo, bo, x):
        h = nn.gelu(x @ w_rep)
        return nn.gelu(h @ wi) @ wo + bo

    # ---- single-device reference: 3 SGD steps on global weights ----
    ref = {"w_rep": w_rep, "wi": wi, "wo": wo, "bo": bo}

    def ref_loss(p):
        y = forward(p["w_rep"], p["wi"], p["wo"], p["bo"], x)
        return jnp.mean((y - tgt) ** 2)

    for _ in range(3):
        g = jax.grad(ref_loss)(ref)
        ref = jax.tree.map(lambda p, g: p - lr * g, ref, g)

    # ---- sharded: stacked tp shards, batch sharded over dp ----
    params = {
        "w_rep": w_rep,
        "wi": wi.reshape(d, n_tp, hloc).transpose(1, 0, 2),   # [tp, d, hloc]
        "wo": wo.reshape(n_tp, hloc, d),                      # [tp, hloc, d]
        "bo": bo,
    }
    shard_axes = {"w_rep": "", "wi": "tp", "wo": "tp", "bo": ""}
    specs = {"w_rep": P(), "wi": P("tp"), "wo": P("tp"), "bo": P()}
    mesh = make_mesh(dp=n_dp, tp=n_tp)

    def step(p, x, tgt):
        def loss_fn(p):
            y = nn.gelu(x @ p["w_rep"])
            y = nn.gelu(y @ p["wi"][0]) @ p["wo"][0]
            y = jax.lax.psum(y, "tp") + p["bo"]
            return jnp.mean((y - tgt) ** 2)

        loss, g = jax.value_and_grad(loss_fn)(p)
        g = sync_gradients(g, shard_axes, axes=("dp", "tp"))
        return jax.tree.map(lambda p, g: p - lr * g, p, g), loss

    f = jax.jit(shard_map(
        step, mesh=mesh,
        in_specs=(specs, P("dp"), P("dp")),
        out_specs=(specs, P()),
        check_vma=False,
    ))
    for _ in range(3):
        params, loss = f(params, x, tgt)

    np.testing.assert_allclose(
        np.asarray(params["w_rep"]), np.asarray(ref["w_rep"]), atol=1e-5
    )
    got_wi = np.asarray(params["wi"]).transpose(1, 0, 2).reshape(d, hidden)
    np.testing.assert_allclose(got_wi, np.asarray(ref["wi"]), atol=1e-5)
    got_wo = np.asarray(params["wo"]).reshape(hidden, d)
    np.testing.assert_allclose(got_wo, np.asarray(ref["wo"]), atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(params["bo"]), np.asarray(ref["bo"]), atol=1e-5
    )


class TestRule2x2Mesh:
    """pmean-vs-divide rule pinned on a 2×2 dp×tp mesh: replicated,
    tp-sharded, and mixed ``param_shard_axes`` pytrees — and the
    bucketed exchange must match the per-leaf rule written out in
    plain ``lax`` bit-for-bit in f32."""

    def _mesh(self):
        return make_mesh(dp=2, tp=2, devices=jax.devices()[:4])

    @staticmethod
    def _per_leaf_rule(grads, shard_axes, axes):
        """The rule of grad_sync's docstring, a leaf at a time: pmean
        over the axes the parameter is not sharded over, divide by the
        size of those it is."""
        def sync(g, sharded_str):
            sharded = sharded_str.split()
            mean_over = tuple(a for a in axes if a not in sharded)
            if mean_over:
                g = lax.pmean(g, mean_over)
            scale = 1
            for a in axes:
                if a in sharded:
                    scale *= lax.axis_size(a)
            return g / scale if scale != 1 else g

        return jax.tree.map(sync, grads, shard_axes)

    def _run(self, sync):
        mesh = self._mesh()
        # distinct per-device blocks: x is sharded over (dp, tp)
        x = jnp.arange(16.0, dtype=jnp.float32).reshape(4, 4)
        axes_tree = {"rep": "", "tp": "tp", "mix": ""}

        def fn(x):
            g = {"rep": x, "tp": x * 2.0, "mix": x + 1.0}
            return sync(g, axes_tree, axes=("dp", "tp"))

        spec = {"rep": P("dp", "tp"), "tp": P("dp", "tp"),
                "mix": P("dp", "tp")}
        f = jax.jit(shard_map(
            fn, mesh=mesh, in_specs=(P("dp", "tp"),), out_specs=spec,
            check_vma=False,
        ))
        return jax.tree.map(np.asarray, f(x))

    @staticmethod
    def _blocks(arr):
        """(dp, tp) -> 2x2 block of the 4x4 array."""
        return {
            (d, t): arr[2 * d:2 * d + 2, 2 * t:2 * t + 2]
            for d in range(2) for t in range(2)
        }

    def _expected(self):
        x = np.arange(16.0, dtype=np.float32).reshape(4, 4)
        xb = self._blocks(x)
        out = {"rep": np.zeros_like(x), "tp": np.zeros_like(x),
               "mix": np.zeros_like(x)}
        for d in range(2):
            for t in range(2):
                # replicated: pmean over dp AND tp
                out["rep"][2 * d:2 * d + 2, 2 * t:2 * t + 2] = np.mean(
                    [xb[(dd, tt)] for dd in range(2) for tt in range(2)],
                    axis=0,
                )
                # tp-sharded: pmean over dp only, then divide by |tp|
                out["tp"][2 * d:2 * d + 2, 2 * t:2 * t + 2] = (
                    (xb[(0, t)] * 2 + xb[(1, t)] * 2) / 2 / 2
                )
                # replicated again, shifted input
                out["mix"][2 * d:2 * d + 2, 2 * t:2 * t + 2] = np.mean(
                    [xb[(dd, tt)] + 1 for dd in range(2)
                     for tt in range(2)], axis=0,
                )
        return out

    def test_rule_replicated_tp_sharded_mixed(self):
        got = self._run(self._per_leaf_rule)
        want = self._expected()
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6)

    def test_scheduler_mode_bit_for_bit(self):
        """Bucketed exchange == the per-leaf rule in plain lax, exact
        f32 equality (pmean is elementwise; bucketing moves no value)."""
        ref = self._run(self._per_leaf_rule)
        got = self._run(sync_gradients)
        for k in ref:
            np.testing.assert_array_equal(got[k], ref[k])

    def test_scheduler_mode_matches_rule(self):
        got = self._run(sync_gradients)
        want = self._expected()
        for k in want:
            np.testing.assert_allclose(got[k], want[k], rtol=1e-6)


def test_sync_gradients_default_replicated():
    """With no shard-axes tree every grad is pmean'd over the data axes
    (pure-DP semantics, matching DistributedOptimizer)."""
    mesh = make_mesh(dp=8)
    g = jnp.arange(8.0)

    def fn(g):
        out = sync_gradients({"w": g}, axes=("dp",))
        return out["w"]

    f = jax.jit(shard_map(
        fn, mesh=mesh, in_specs=(P("dp"),), out_specs=P("dp"),
        check_vma=False,
    ))
    out = np.asarray(f(g))
    np.testing.assert_allclose(out, np.full(8, np.mean(np.arange(8.0))))
