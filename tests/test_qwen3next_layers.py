"""The layers Qwen3-Next adds, each against a plain form at a small size:
the delta rule whose key heads serve several value heads (recurrence,
chunked form and kernel pair), gated attention with per-head zero-centred
q/k norms, partial rope and a gate a channel, the softmax router beside a
gated shared expert, and the shares of an expert layer against the whole
layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu import metrics
from horovod_tpu.models import transformer
from horovod_tpu.ops import kda, kda_kernels
from horovod_tpu.parallel import moe


def _max_rel(got, want, floor=1e-6):
    scale = max(float(jnp.max(jnp.abs(want))), floor)
    return float(jnp.max(jnp.abs(got - want))) / scale


# ------------------------------------------- key heads shared by value heads
def _shared_inputs(seed, group, b=2, t=150, h=4, dk=16, dv=8):
    """q, k of h / group key heads; v, g [B, T, H, 1] (one decay a value
    head, down to -20), beta [B, T, H] in (0, 1) with some of it near 0 and
    some near 1."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    hk = h // group
    q = unit(jax.random.normal(keys[0], (b, t, hk, dk)))
    k = unit(jax.random.normal(keys[1], (b, t, hk, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    g = -20.0 * jax.random.uniform(keys[3], (b, t, h, 1)) ** 2
    beta = jax.nn.sigmoid(6.0 * jax.random.normal(keys[4], (b, t, h)))
    return q, k, v, g, beta


def _packed_rows(t):
    # a boundary inside a chunk, one on a chunk's edge (64), padding last
    first = np.repeat([1, 2, 3], [40, 24, t - 64])
    second = np.repeat([1, 2, 0], [100, 30, t - 130])
    return jnp.asarray(np.stack([first, second]), jnp.int32)


def _head_by_head(q, k, v, g, beta, segment_ids=None):
    """Each value head alone through the recurrence with its key head
    h // group: the definition of sharing, written without the repeat."""
    group = v.shape[2] // q.shape[2]
    return jnp.concatenate([
        kda.kda_recurrent(q[:, :, j // group:j // group + 1],
                          k[:, :, j // group:j // group + 1],
                          v[:, :, j:j + 1], g[:, :, j:j + 1],
                          beta[:, :, j:j + 1], segment_ids)
        for j in range(v.shape[2])], axis=2)


def _by_the_kernels(q, k, v, g, beta, segment_ids=None):
    """The kernel pair (interpreted here) on the projections' layout, q
    and k at the key heads."""
    b, t, h, _ = v.shape
    out = kda.kda(*(a.reshape(b, t, -1) for a in (q, k, v, g)), beta,
                  segment_ids, group=h // q.shape[2])
    return out.reshape(b, t, h, -1)


IMPLS = {"recurrence": kda.kda_recurrent, "chunked": kda.kda_chunked,
         "kernel": _by_the_kernels}


@pytest.mark.parametrize("impl", sorted(IMPLS))
@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("group", [2, 1], ids=["two_a_key", "one_a_key"])
def test_shared_key_heads_are_each_value_head_alone(group, packed, impl):
    """T = 150 (two chunks and a ragged third), a document starting inside
    a chunk and one on its edge, beta near 0 and near 1: outputs and every
    gradient against each value head run alone with key head h // group
    (for q and k, the sum over the value heads that read them)."""
    args = _shared_inputs(3, group)
    assert float(jnp.min(args[4])) < 1e-3 and float(jnp.max(args[4])) > 0.999
    seg = _packed_rows(150) if packed else None
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 150, 4, 8))
    fn = IMPLS[impl]

    def scalar(f):
        return lambda *a: jnp.sum(f(*a, segment_ids=seg) * weight)

    with jax.default_matmul_precision("highest"):
        got = fn(*args, segment_ids=seg)
        want = _head_by_head(*args, segment_ids=seg)
        g_got = jax.grad(scalar(fn), argnums=range(5))(*args)
        g_want = jax.grad(scalar(_head_by_head), argnums=range(5))(*args)
    assert got.shape == want.shape == (2, 150, 4, 8)
    # beta near 1 and decays to -20: the chunk's inverse rounds to 1e-5
    assert _max_rel(got, want) <= 5e-5
    scale = max(float(jnp.max(jnp.abs(w))) for w in g_want)
    for name, a, w in zip("q k v g beta".split(), g_got, g_want):
        assert a.shape == w.shape, name
        assert float(jnp.max(jnp.abs(a - w))) <= 1e-4 * scale, name
        assert float(jnp.max(jnp.abs(w))) > 0, name


def test_key_head_h_over_group_is_not_h_modulo_the_key_heads():
    """Reading key head h mod H_k gives another answer: the test above
    would see it."""
    q, k, v, g, beta = _shared_inputs(4, 2, b=1, t=70)
    right = _head_by_head(q, k, v, g, beta)
    wrong = kda.kda_recurrent(jnp.tile(q, (1, 1, 2, 1)),
                              jnp.tile(k, (1, 1, 2, 1)), v, g, beta)
    assert _max_rel(wrong, right) > 1e-2


def test_shared_keys_at_the_chips_block_shape_are_the_chunked_form():
    """Heads of 128 (a key head one 128-lane slab), 8 value heads over 4
    key heads: two grid steps of four value heads, each reading the two
    key heads of its block; bfloat16 operands, T = 192 with a boundary
    inside the second chunk.  The pair against the plain chunked form at
    the same types, output and every gradient."""
    b, t, h, d = 1, 192, 8, 128
    assert kda_kernels._slab(h, d, d, group=2) == 4
    q, k, v, g, beta = _shared_inputs(5, 2, b=b, t=t, h=h, dk=d, dv=d)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q * d ** -0.5, k, v))
    seg = jnp.asarray(np.repeat([1, 2], [100, 92])[None], jnp.int32)
    weight = jax.random.normal(jax.random.PRNGKey(9), (b, t, h, d))

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a, segment_ids=seg) * weight)

    got = _by_the_kernels(q, k, v, g, beta, seg)
    want = kda.kda_chunked(q, k, v, g, beta, seg)
    assert _max_rel(got, want) <= 2e-3
    g_got = jax.grad(scalar(_by_the_kernels), argnums=range(5))(
        q, k, v, g, beta)
    g_want = jax.grad(scalar(kda.kda_chunked), argnums=range(5))(
        q, k, v, g, beta)
    for name, a, w in zip("q k v g beta".split(), g_got, g_want):
        assert a.dtype == w.dtype and a.shape == w.shape, name
        assert _max_rel(a.astype(jnp.float32), w.astype(jnp.float32)
                        ) <= 2e-2, name


def test_slabs_hold_whole_groups_of_value_heads():
    """A grid step owns whole groups: four value heads and their two key
    heads at 128 lanes; a group of one is the grid it was."""
    assert kda_kernels._heads_a_step(32, 2) == 4
    assert kda_kernels._heads_a_step(6, 2) == 2
    assert kda_kernels._heads_a_step(6, 3) == 3
    assert kda_kernels._slab(32, 128, 128, group=2) == 4
    assert kda_kernels._slab(32, 128, 128) == 4
    # two value heads of 64 over one key head of 64 fill no 128-lane slab
    assert kda_kernels._slab(4, 64, 64, group=2) == 4
    with pytest.raises(ValueError, match="one decay a head"):
        q, k, v, _, beta = _shared_inputs(6, 2, b=1, t=64)
        kda.kda(q.reshape(1, 64, -1), k.reshape(1, 64, -1),
                v.reshape(1, 64, -1), jnp.zeros((1, 64, 2 * 16)), beta,
                group=2)


# -------------------------------------------------------- gated attention
def _attn_config(**over):
    return dataclasses.replace(transformer.TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=48, num_heads=16,
        num_kv_heads=2, head_dim=32, max_len=128, dtype=jnp.float32,
        norm="zero_centred_rmsnorm", positions="rope", use_bias=False,
        fused_qkv=False, qk_head_norm=True, attn_channel_gate=True,
        rope_rules=(("full", transformer.RopeRule(theta=1e7, dim=8)),)),
        **over)


def _plain_gated_attention(p, x, cfg, pos, seg):
    """Materialised scores in float32: q, k normed a head with (1 + w),
    the first 8 of 32 channels turned (pairs i, i + 4), query head h
    reading K/V head h // 8, a gate a channel."""
    b, t, _ = x.shape
    d, h, g = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads

    def norm(a, w):
        return a / jnp.sqrt(jnp.mean(a * a, -1, keepdims=True)
                            + cfg.norm_eps) * (1.0 + w)

    def turn(a):
        r = 8
        inv = 1e7 ** (-jnp.arange(0, r, 2) / r)
        ang = pos[..., None, None] * inv
        a1, a2, rest = a[..., :r // 2], a[..., r // 2:r], a[..., r:]
        return jnp.concatenate([a1 * jnp.cos(ang) - a2 * jnp.sin(ang),
                                a2 * jnp.cos(ang) + a1 * jnp.sin(ang),
                                rest], -1)

    dense = lambda name: p[name]["Dense_0"]["kernel"]
    q = turn(norm((x @ dense("q")).reshape(b, t, h, d), p["q_norm"]["scale"]))
    k = turn(norm((x @ dense("k")).reshape(b, t, g, d), p["k_norm"]["scale"]))
    v = (x @ dense("v")).reshape(b, t, g, d)
    k, v = (jnp.repeat(a, h // g, axis=2) for a in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    i = jnp.arange(t)
    allowed = (i[:, None] >= i[None, :])[None, None]
    if seg is not None:
        allowed = allowed & (seg[:, :, None] == seg[:, None, :])[:, None]
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(allowed, scores, -jnp.inf), -1), v).reshape(b, t, -1)
    o = o * jax.nn.sigmoid(x @ p["gate"]["kernel"])
    return o @ dense("proj")


@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
def test_gated_attention_is_the_dense_float32_form(packed):
    """Per-head zero-centred q/k norms, rope on 8 of 32 channels, a gate a
    channel, groups of 8 query heads: output and every gradient."""
    cfg = _attn_config()
    t = 40
    x = jax.random.normal(jax.random.PRNGKey(0), (2, t, 48))
    seg = (jnp.asarray(np.repeat([1, 2], [15, 25])[None].repeat(2, 0))
           if packed else None)
    pos = transformer._positions(cfg, 2, t, seg)
    rope = transformer._rope_by_kind(cfg, pos)["full"]
    layer = transformer.Attention(cfg)
    params = layer.init(jax.random.PRNGKey(1), x, seg, rope)["params"]
    assert params["q_norm"]["scale"].shape == (32,)
    assert params["gate"]["kernel"].shape == (48, 16 * 32)
    assert not float(jnp.max(jnp.abs(params["q_norm"]["scale"])))  # zeros
    leaves, tree = jax.tree.flatten(params)
    params = jax.tree.unflatten(tree, [
        a + 0.1 * jax.random.normal(jax.random.PRNGKey(i), a.shape)
        for i, a in enumerate(leaves)])
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(lambda p: jnp.sum(
            layer.apply({"params": p}, x, seg, rope) ** 2))(params)
        want, g_want = jax.value_and_grad(lambda p: jnp.sum(
            _plain_gated_attention(p, x, cfg, pos, seg) ** 2))(params)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    flat = jax.tree_util.tree_flatten_with_path(g_got)[0]
    for (path, a), w in zip(flat, jax.tree.leaves(g_want)):
        assert _max_rel(a, w) <= 1e-4, jax.tree_util.keystr(path)
        assert float(jnp.max(jnp.abs(w))) > 0, jax.tree_util.keystr(path)


def test_every_norm_of_the_model_is_zero_centred():
    """A whole model's norms start at w = 0 and multiply by 1 + w; the
    gated norm of the delta rule's output stays a plain weight of ones."""
    cfg = _attn_config(
        num_layers=2, layer_kinds=("gdn", "full"), num_heads=4,
        layer_heads=(4, 16), gdn_key_dim=16, gdn_value_dim=16,
        gdn_key_heads=2, gdn_allow_neg_eigval=False)
    model = transformer.Transformer(cfg)
    tokens = jnp.zeros((1, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), tokens)["params"]
    for path in (("ln_f",), ("block_0", "ln_attn"), ("block_1", "ln_mlp"),
                 ("block_1", "attn", "q_norm"), ("block_1", "attn", "k_norm")):
        node = params
        for key in path:
            node = node[key]
        assert not float(jnp.max(jnp.abs(node["scale"]))), path
    assert float(jnp.min(params["block_0"]["gdn"]["o_norm"]["scale"])) == 1.0
    assert params["block_0"]["gdn"]["q"]["kernel"].shape == (48, 2 * 16)
    assert params["block_0"]["gdn"]["v"]["kernel"].shape == (48, 4 * 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (3, 48))
    w = jax.random.normal(jax.random.PRNGKey(2), (48,))
    got = transformer.ZeroCentredRMSNorm(epsilon=1e-6).apply(
        {"params": {"scale": w}}, x)
    want = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * (1 + w)
    assert _max_rel(got, want) <= 1e-6
    with metrics.traced_gauges():
        model.apply({"params": params}, tokens)
    assert metrics.get_gauge("model.gdn.value_heads_per_key") == 2
    assert metrics.get_gauge("model.attn.kv_groups", {"kind": "full"}) == 8


def test_beta_in_zero_one_without_negative_eigenvalues(monkeypatch):
    """``gdn_allow_neg_eigval`` False hands the core sigmoid(x W_b), True
    twice that: the only difference between the two."""
    seen = {}
    real = transformer.kda

    def spy(q, k, v, g, beta, seg, **kw):
        seen.setdefault("beta", []).append(beta)
        return real(q, k, v, g, beta, seg, **kw)

    monkeypatch.setattr(transformer, "kda", spy)
    cfg = _attn_config(
        num_layers=1, layer_kinds=("gdn",), num_heads=4, gdn_key_dim=16,
        gdn_value_dim=16, gdn_key_heads=2)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 16), 0, 64)
    handed = {}
    for allow in (False, True):
        model = transformer.Transformer(
            dataclasses.replace(cfg, gdn_allow_neg_eigval=allow))
        params = model.init(jax.random.PRNGKey(1), tokens)
        seen.clear()
        model.apply(params, tokens)
        handed[allow] = seen["beta"][0]
    assert float(jnp.max(handed[False])) < 1.0
    assert _max_rel(handed[True], 2.0 * handed[False]) <= 1e-6


# ------------------------------------------------ softmax router, gated shared
def _qwen_expert_layer(held, total=512, k=10):
    return moe.ExpertFFN(
        num_experts=total, experts_held=held, hidden=12, k=k,
        dtype=jnp.float32, scoring="softmax", shared_gate=True)


def test_softmax_router_and_gated_shared_expert_are_a_dense_loop():
    """p = softmax(x W_r) over all experts, the 10 largest, weights p_i /
    sum of the chosen; the shared expert times sigmoid(x w_sg); no
    selection bias is made."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 50, 16))
    layer = _qwen_expert_layer((0, 64), total=64)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    assert "router_bias" not in params
    assert params["shared_gate"].shape == (16, 1)
    xf = x.reshape(-1, 16)
    dense = lambda p: p["Dense_0"]["kernel"]
    with jax.default_matmul_precision("highest"):
        y, load = layer.apply({"params": params}, x)
        p = jax.nn.softmax(xf @ params["router"], -1)
        chosen, ids = jax.lax.top_k(p, 10)
        weights = chosen / chosen.sum(-1, keepdims=True)
        sh = params["shared"]
        shared = jax.nn.sigmoid(xf @ params["shared_gate"]) * (
            (jax.nn.silu(xf @ dense(sh["wg"])) * (xf @ dense(sh["wi"])))
            @ dense(sh["wo"]))
        routed = 0.0
        for e in range(64):
            w = jnp.sum(jnp.where(ids == e, weights, 0.0), -1)[:, None]
            routed = routed + w * ((jax.nn.silu(xf @ params["wg"][e])
                                    * (xf @ params["wi"][e]))
                                   @ params["wo"][e])
        want = shared + routed
    assert _max_rel(y.reshape(-1, 16), want) <= 1e-5
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(ids).ravel(), minlength=64))
    # sigmoid scores renormalised weigh the same experts otherwise
    sig = jax.nn.sigmoid(xf @ params["router"])
    sig_w = jnp.take_along_axis(sig, ids, -1)
    assert _max_rel(sig_w / sig_w.sum(-1, keepdims=True), weights) > 1e-3


def test_sixteen_shares_add_up_to_the_uncut_512_expert_layer():
    """16 shares of 32 experts of a 512-expert layer, 10 a token: the
    routed parts summed and the gated shared expert counted once are the
    uncut layer, which is the plain reference's."""
    from benchmark.reference import qwen3next as reference

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 16))
    whole = _qwen_expert_layer((0, 512))
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    model = dict(num_experts_per_tok=10, experts_held=[0, 512])
    with jax.default_matmul_precision("highest"):
        uncut = reference._experts(params, x, model)
        y_whole, load_whole = whole.apply({"params": params}, x)
        xf = x.reshape(-1, 16)
        sh = params["shared"]
        shared = (jax.nn.sigmoid(xf @ params["shared_gate"]) * reference._swiglu(
            xf, *(sh[n]["Dense_0"]["kernel"] for n in ("wg", "wi", "wo")))
        ).reshape(x.shape)
        total, loads = jnp.zeros_like(uncut), []
        for s in range(16):
            held = slice(32 * s, 32 * s + 32)
            share = dict(params, **{
                n: params[n][held] for n in ("wg", "wi", "wo")})
            y, load = _qwen_expert_layer((32 * s, 32 * s + 32)).apply(
                {"params": share}, x)
            part = reference._experts(
                share, x, dict(model, experts_held=[32 * s, 32 * s + 32]))
            assert _max_rel(y, part) <= 1e-5, s
            total = total + (y - shared)
            loads.append(load)
        total = total + shared
    assert _max_rel(y_whole, uncut) <= 1e-5
    assert _max_rel(total, uncut) <= 1e-5
    assert float(sum(l.sum() for l in loads)) == 2 * 40 * 10
    np.testing.assert_array_equal(jnp.concatenate(loads), load_whole)
    # the shared expert counted in every share would be fifteen too many
    assert _max_rel(total + 15 * shared, uncut) > 100 * _max_rel(total, uncut)


def test_an_unknown_scoring_is_refused():
    x = jnp.zeros((1, 4, 16))
    with pytest.raises(ValueError, match="scoring"):
        moe.ExpertFFN(num_experts=8, experts_held=(0, 8), hidden=12, k=2,
                      scoring="relu").init(jax.random.PRNGKey(0), x)
