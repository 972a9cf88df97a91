"""The layers of a hybrid linear-attention / latent-attention decoder with
routed experts, each against a plain form at a small size: the chunked
delta rule (``ops/kda.py``) against its recurrence, latent attention
against materialised scores, the group-limited router against ``top_k`` on
hand-made cases, the dropless grouped product against a dense loop, and the
shares of an expert layer against the whole layer."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.models import transformer
from horovod_tpu.ops import kda, kda_kernels
from horovod_tpu.parallel import moe


def _max_rel(got, want, floor=1e-6):
    scale = max(float(jnp.max(jnp.abs(want))), floor)
    return float(jnp.max(jnp.abs(got - want))) / scale


# ------------------------------------------------------------- the delta rule
def _kda_inputs(seed, decay, b=2, t=150, h=2, dk=16, dv=8):
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda a: a / jnp.linalg.norm(a, axis=-1, keepdims=True)
    q = unit(jax.random.normal(keys[0], (b, t, h, dk)))
    k = unit(jax.random.normal(keys[1], (b, t, h, dk)))
    v = jax.random.normal(keys[2], (b, t, h, dv))
    u = jax.random.uniform(keys[3], (b, t, h, dk))
    g = {
        "across": -5.0 * u,
        "strong_end": -4.9 - 0.0999 * u,             # alpha ~ e^-5
        "weak_end": -1e-3 * u,                       # alpha ~ 1
        # half of a head's channels at each end of (-5, 0)
        "both_ends": jnp.where(jnp.arange(dk) % 2 == 0, -4.99, -0.01) + 0 * u,
    }[decay]
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (b, t, h)))
    return q, k, v, g, beta


def _packed_rows(t):
    # a boundary inside a chunk, one on a chunk's edge (64), padding last
    first = np.repeat([1, 2, 3], [40, 24, t - 64])
    second = np.repeat([1, 2, 0], [100, 30, t - 130])
    return jnp.asarray(np.stack([first, second]), jnp.int32)


def _by_the_kernels(q, k, v, g, beta, segment_ids=None):
    """The kernel pair (interpreted here) on operands laid out as the
    recurrence takes them: [B, T, H, d] is [B, T, H·d] for nothing."""
    b, t, h, _ = q.shape
    assert kda_kernels.takes(q.shape[-1]) and kda_kernels.takes(v.shape[-1])
    out = kda.kda(*(a.reshape(b, t, -1) for a in (q, k, v, g)), beta,
                  segment_ids)
    return out.reshape(b, t, h, -1)


IMPLS = {"chunked": kda.kda_chunked, "kernel": _by_the_kernels}
impls = pytest.mark.parametrize("impl", sorted(IMPLS))


@impls
@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize(
    "decay", ["across", "strong_end", "weak_end", "both_ends"])
def test_chunked_delta_rule_is_the_recurrence(decay, packed, impl):
    """T = 150 is two chunks of 64 and a ragged third.  Outputs and every
    gradient; a gradient is held to 1e-4 of the largest gradient of the
    five (at the strong end the decays' own gradient is 1e-3 of k's, and
    float32 cancels in the chunk's running sums).  ``kernel`` is the
    Pallas pair with its hand-written backward, two heads a grid step."""
    args = _kda_inputs(0, decay)
    seg = _packed_rows(150) if packed else None
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 150, 2, 8))
    chunked = IMPLS[impl]

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a, segment_ids=seg) * weight)

    with jax.default_matmul_precision("highest"):
        got = chunked(*args, segment_ids=seg)
        want = kda.kda_recurrent(*args, segment_ids=seg)
        g_got = jax.grad(scalar(chunked), argnums=range(5))(*args)
        g_want = jax.grad(scalar(kda.kda_recurrent), argnums=range(5))(*args)
    assert got.shape == want.shape == (2, 150, 2, 8)
    assert _max_rel(got, want) <= 1e-5
    scale = max(float(jnp.max(jnp.abs(w))) for w in g_want)
    for name, a, w in zip("q k v g beta".split(), g_got, g_want):
        assert float(jnp.max(jnp.abs(a - w))) <= 1e-4 * scale, name
        assert float(jnp.max(jnp.abs(w))) > 0, name


@impls
def test_a_document_starts_from_an_empty_state(impl):
    """The second document of a packed row alone gives what it gives in
    the row: nothing crosses the boundary."""
    chunked = IMPLS[impl]
    q, k, v, g, beta = _kda_inputs(1, "weak_end", b=1, t=100)
    seg = jnp.asarray(np.repeat([1, 2], [37, 63])[None], jnp.int32)
    with jax.default_matmul_precision("highest"):
        row = chunked(q, k, v, g, beta, segment_ids=seg)
        alone = chunked(*(a[:, 37:] for a in (q, k, v, g, beta)))
        unpacked = chunked(q, k, v, g, beta)
    assert _max_rel(row[:, 37:], alone) <= 1e-5
    assert _max_rel(unpacked[:, 37:], alone) > 1e-2  # the state matters


@impls
def test_no_exponent_passes_the_bound_at_the_strongest_decay(impl):
    q, k, v, _, beta = _kda_inputs(2, "across", t=128)
    g = jnp.full(q.shape, -4.999)
    out, grads = jax.value_and_grad(
        lambda g: jnp.sum(IMPLS[impl](q, k, v, g, beta)))(g)
    assert np.isfinite(float(out)) and bool(jnp.all(jnp.isfinite(grads)))
    with pytest.raises(ValueError, match="sub-chunks"):
        kda.kda_chunked(q, k, v, g, beta, chunk=64, sub=24)


def test_kernels_at_the_chips_block_shape_are_the_chunked_form():
    """Heads of 128 (a head is one 128-lane slab), bfloat16 operands,
    T = 192 with a document's boundary inside the second chunk: the pair
    against the plain chunked form at the same types, output and every
    gradient.  The two round in the same places, so they differ by the
    order of float32 sums and by where a bfloat16 rounding falls."""
    b, t, h, d = 1, 192, 2, 128
    q, k, v, g, beta = _kda_inputs(3, "across", b=b, t=t, h=h, dk=d, dv=d)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q * d ** -0.5, k, v))
    seg = jnp.asarray(np.repeat([1, 2], [100, 92])[None], jnp.int32)
    weight = jax.random.normal(jax.random.PRNGKey(9), (b, t, h, d))

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a, segment_ids=seg) * weight)

    got = _by_the_kernels(q, k, v, g, beta, seg)
    want = kda.kda_chunked(q, k, v, g, beta, seg)
    assert got.dtype == want.dtype == jnp.float32
    assert _max_rel(got, want) <= 2e-3
    g_got = jax.grad(scalar(_by_the_kernels), argnums=range(5))(
        q, k, v, g, beta)
    g_want = jax.grad(scalar(kda.kda_chunked), argnums=range(5))(
        q, k, v, g, beta)
    for name, a, w in zip("q k v g beta".split(), g_got, g_want):
        assert a.dtype == w.dtype and a.shape == w.shape, name
        assert _max_rel(a.astype(jnp.float32), w.astype(jnp.float32)
                        ) <= 2e-2, name


def test_a_width_the_chips_kernels_do_not_take_goes_to_the_chunked_form(
        monkeypatch):
    """On the chip (Pallas not interpreted) heads of 16 are no whole slab:
    ``kda`` re-lays them for ``kda_chunk_major`` and gives the same."""
    from horovod_tpu.ops import pallas_kernels

    args = _kda_inputs(4, "across", b=1, t=70)
    want = _by_the_kernels(*args)
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    assert not kda_kernels.takes(16) and kda_kernels.takes(256)
    called = []
    real = kda.kda_chunk_major
    monkeypatch.setattr(kda, "kda_chunk_major",
                        lambda *a: called.append(1) or real(*a))
    b, t, h, _ = args[0].shape
    got = kda.kda(*(a.reshape(b, t, -1) for a in args[:4]), args[4])
    assert called and _max_rel(got.reshape(b, t, h, -1), want) <= 1e-5


# --------------------------------------------- one decay a head (Gated DeltaNet)
def _gdn_inputs(seed, b=2, t=150, h=2, dk=96, dv=192):
    """q, k, v as for a channel's decay; g [B, T, H, 1] down to -20 (most
    of a head's tokens below the per-channel form's bound of -5 and some
    near 0), beta in (0, 2)."""
    q, k, v, _, _ = _kda_inputs(seed, "across", b=b, t=t, h=h, dk=dk, dv=dv)
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), 2)
    g = -20.0 * jax.random.uniform(keys[0], (b, t, h, 1)) ** 2
    beta = 2.0 * jax.nn.sigmoid(2.0 * jax.random.normal(keys[1], (b, t, h)))
    return q, k, v, g, beta


@impls
@pytest.mark.parametrize("packed", [False, True], ids=["dense", "packed"])
@pytest.mark.parametrize("h, dk, dv", [(2, 96, 192), (6, 32, 48)],
                         ids=["olmo_widths", "two_groups_a_slab"])
def test_one_decay_a_head_is_the_recurrence(h, dk, dv, packed, impl):
    """Gated DeltaNet's case: keys of 96 and values of 192 (no whole
    128-lane slab: a block holds every head), or six heads in two groups
    of three a block; beta up to 2 (eigenvalues down to -1) and decays
    far below -5, where exp(G_r - G_i) is one exact [C, C] matrix a head
    and no sub-chunk bound applies; T = 150 with a document's boundary
    inside a chunk.  Outputs and every gradient against the recurrence,
    which takes g [B, T, H, 1] as it is."""
    args = _gdn_inputs(7, h=h, dk=dk, dv=dv)
    assert float(jnp.min(args[3])) < -15 and float(jnp.max(args[4])) > 1.5
    seg = _packed_rows(150) if packed else None
    weight = jax.random.normal(jax.random.PRNGKey(9), (2, 150, h, dv))
    chunked = IMPLS[impl]

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a, segment_ids=seg) * weight)

    with jax.default_matmul_precision("highest"):
        got = chunked(*args, segment_ids=seg)
        want = kda.kda_recurrent(*args, segment_ids=seg)
        g_got = jax.grad(scalar(chunked), argnums=range(5))(*args)
        g_want = jax.grad(scalar(kda.kda_recurrent), argnums=range(5))(*args)
    assert got.shape == want.shape == (2, 150, h, dv)
    assert _max_rel(got, want) <= 1e-5
    scale = max(float(jnp.max(jnp.abs(w))) for w in g_want)
    for name, a, w in zip("q k v g beta".split(), g_got, g_want):
        assert a.shape == w.shape, name
        assert float(jnp.max(jnp.abs(a - w))) <= 1e-4 * scale, name
        assert float(jnp.max(jnp.abs(w))) > 0, name


def test_one_decay_a_head_by_the_kernels_is_the_chunked_form_in_bf16():
    """bfloat16 operands at Olmo-Hybrid's widths: the pair against the
    plain chunked form at the same types, output and every gradient (as
    ``test_kernels_at_the_chips_block_shape_are_the_chunked_form``)."""
    b, t, h = 1, 192, 2
    q, k, v, g, beta = _gdn_inputs(8, b=b, t=t, h=h)
    q, k, v = (a.astype(jnp.bfloat16) for a in (q * 96 ** -0.5, k, v))
    seg = jnp.asarray(np.repeat([1, 2], [100, 92])[None], jnp.int32)
    weight = jax.random.normal(jax.random.PRNGKey(9), (b, t, h, 192))

    def scalar(fn):
        return lambda *a: jnp.sum(fn(*a, segment_ids=seg) * weight)

    got = _by_the_kernels(q, k, v, g, beta, seg)
    want = kda.kda_chunked(q, k, v, g, beta, seg)
    assert got.dtype == want.dtype == jnp.float32
    assert _max_rel(got, want) <= 2e-3
    g_got = jax.grad(scalar(_by_the_kernels), argnums=range(5))(
        q, k, v, g, beta)
    g_want = jax.grad(scalar(kda.kda_chunked), argnums=range(5))(
        q, k, v, g, beta)
    for name, a, w in zip("q k v g beta".split(), g_got, g_want):
        assert a.dtype == w.dtype and a.shape == w.shape, name
        assert _max_rel(a.astype(jnp.float32), w.astype(jnp.float32)
                        ) <= 2e-2, name


def test_slabs_are_lane_slabs_or_every_head():
    """Heads of whole 128-lane slabs keep their grid of four heads a
    step; keys of 96 and values of 192 take all 30 heads a block, in
    groups of three; the norms' blocks shrink their rows as they widen."""
    assert kda_kernels._slab(32, 128, 128) == 4
    assert kda_kernels._slab(30, 96, 192) == 30
    assert kda_kernels._heads_a_step(30) == 3
    assert kda_kernels._slab(30, 192) == 30 and kda_kernels._slab(32, 64) == 4
    assert kda_kernels._norm_rows(4096, 32 * 128, 32) == 256
    assert kda_kernels._norm_rows(4096, 30 * 192, 30) == 16
    assert kda_kernels._norm_rows(4096, 30 * 96, 30) == 32
    assert kda_kernels._norm_rows(20, 30 * 96, 30) == 20


@pytest.mark.parametrize("h, d", [(2, 16), (5, 96)])
def test_a_gate_a_channel_is_the_plain_norm(h, d, monkeypatch):
    """The output's RMSNorm a head times a gate a channel (Gated
    DeltaNet's SiLU(x W_z)) by the kernel on [B, T, H·d], against the
    plain formula: values and every gradient; heads of 96 in a block of
    every head."""
    b, t = 2, 70
    keys = jax.random.split(jax.random.PRNGKey(6), 4)
    x = jax.random.normal(keys[0], (b, t, h * d))
    weight = 1.0 + 0.1 * jax.random.normal(keys[1], (d,))
    gate = jax.nn.silu(jax.random.normal(keys[2], (b, t, h * d)))
    cotangent = jax.random.normal(keys[3], (b, t, h * d))

    def plain(x, weight, gate):
        y = x.reshape(b, t, h, d)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True) + 1e-6)
        return (y * weight).reshape(b, t, h * d) * gate

    def both(fn):
        return (fn(x, weight, gate),) + jax.grad(
            lambda *a: jnp.sum(fn(*a) * cotangent), argnums=range(3))(
                x, weight, gate)

    got = both(lambda *a: kda.rms_gate_heads(*a, 1e-6, jnp.float32))
    want = both(plain)
    for a, w in zip(got, want):
        assert a.shape == w.shape and _max_rel(a, w) <= 1e-5
    unit = kda.unit_heads(x, h, 0.5, jnp.float32).reshape(b, t, h, d)
    np.testing.assert_allclose(jnp.sum(unit * unit, -1), 0.25, rtol=1e-5)


@pytest.mark.parametrize("t", [70, 300])
def test_head_norm_kernels_are_the_plain_norms(t, monkeypatch):
    """The output's RMSNorm and gate as a kernel on [B, T, H·d]
    (interpreted here) against the same through [B, T, H, d], which a
    width the chip's kernels do not take falls to: values and every
    gradient, T under and over a block of rows."""
    from horovod_tpu.ops import pallas_kernels

    b, h, d = 2, 4, 16
    keys = jax.random.split(jax.random.PRNGKey(5), 4)
    x = jax.random.normal(keys[0], (b, t, h * d))
    weight = 1.0 + 0.1 * jax.random.normal(keys[1], (d,))
    gate = jax.nn.sigmoid(jax.random.normal(keys[2], (b, t, h)))
    cotangent = jax.random.normal(keys[3], (b, t, h * d))

    def both(fn, *args):
        return (fn(*args),) + jax.grad(
            lambda *a: jnp.sum(fn(*a) * cotangent),
            argnums=range(len(args)))(*args)

    normed = lambda *a: kda.rms_gate_heads(*a, 1e-6, jnp.float32)
    got = both(normed, x, weight, gate)
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    want = both(normed, x, weight, gate)
    assert len(got) == 4
    for a, w in zip(got, want):
        assert a.shape == w.shape and _max_rel(a, w) <= 1e-5
    assert kda.unit_heads(x, h, 1.0, jnp.bfloat16).dtype == jnp.bfloat16


def test_short_convolution_keeps_to_its_document():
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 10, 3))
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    seg = jnp.asarray([[1, 1, 1, 1, 2, 2, 2, 2, 2, 2]])
    y = transformer._short_conv(x, taps, seg[..., None])
    want = np.zeros((10, 3), np.float32)
    for t in range(10):
        for j in range(4):
            if t - j >= 0 and seg[0, t - j] == seg[0, t]:
                want[t] += np.asarray(taps[j] * x[0, t - j])
    np.testing.assert_allclose(y[0], want, rtol=1e-5, atol=1e-6)
    # without segments the second document sees the first one's last tokens
    assert not np.allclose(transformer._short_conv(x, taps, None)[0, 4],
                           want[4])
    # a row shorter than the taps' reach
    short = transformer._short_conv(x[:, :2], taps, None)
    np.testing.assert_allclose(short[0], want[:2], rtol=1e-5, atol=1e-6)


# x's type, heads, a head's width, the norm's scale (None: none, v's), T,
# the kernels' block of rows at most, where documents start (None: a row
# of one document, no segment ids)
CONV_CASES = {
    # KDA's heads of 128: a block of rows is 16 tokens, T = 40 is two and
    # a half, so every block but the first reads a halo before it
    "kda_heads_normed": (jnp.float32, 2, 128, 128 ** -0.5, 40, 16, None),
    "kda_heads_plain": (jnp.float32, 2, 128, None, 40, 16, None),
    # GDN's keys of 96: a block as wide as the array, four heads filling
    # three 128-lane columns and one head beside them; documents starting
    # inside the first block (10) and on the edge of the third (32)
    "gdn_keys_normed_packed": (jnp.float32, 5, 96, 0.25, 40, 16, (10, 32)),
    # GDN's values of 192, no norm: taken a 128-lane column at a time
    "gdn_values_plain_packed": (jnp.float32, 2, 192, None, 40, 16, (10, 32)),
    # bfloat16 as projected: a halo is 16 rows, a block 32
    "bf16_normed_packed": (jnp.bfloat16, 2, 128, 0.5, 72, 32, (48, 64)),
    # a row shorter than a halo: one block, both halos past its ends
    "one_short_block": (jnp.float32, 2, 8, 1.0, 5, 256, None),
}


@pytest.mark.parametrize("case", list(CONV_CASES))
def test_convolution_kernel_pair_is_the_plain_path(case, monkeypatch):
    """``kda_kernels.short_conv_silu`` (interpreted here) against the
    model's convolution, SiLU and the norm through [B, T, H, d] in XLA:
    the output and the gradients of x and of the taps.  With blocks of
    rows shorter than T a halo dropped, or read from the wrong rows or
    documents, changes both."""
    dtype, heads, width, scale, t, rows, starts = CONV_CASES[case]
    monkeypatch.setattr(kda_kernels, "_CONV_ROWS", rows)
    b, c = 2, heads * width
    keys = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(keys[0], (b, t, c)).astype(dtype)
    taps = 0.5 * jax.random.normal(keys[1], (4, c))
    cotangent = jax.random.normal(keys[2], (b, t, c))
    seg = None
    if starts is not None:
        seg = jnp.asarray(np.searchsorted(starts, np.arange(t), "right"),
                          jnp.int32)[None, :, None].repeat(b, 0)
        seg = seg.at[1].set(7)  # the second row one document

    def kernels(x, taps):
        return kda_kernels.short_conv_silu(
            x, taps, seg, transformer._short_conv, heads, scale, jnp.float32)

    def plain(x, taps):
        y = jax.nn.silu(transformer._short_conv(x, taps, seg))
        return y if scale is None else kda.unit_heads(y, heads, scale,
                                                      jnp.float32)

    def both(fn):
        return (fn(x, taps),) + jax.grad(
            lambda *a: jnp.sum(fn(*a) * cotangent), argnums=(0, 1))(x, taps)

    tolerance = 1e-5 if dtype == jnp.float32 else 2.0 ** -7  # dx's rounding
    for got, want in zip(both(kernels), both(plain)):
        assert got.shape == want.shape and got.dtype == want.dtype
        assert _max_rel(got.astype(jnp.float32),
                        want.astype(jnp.float32)) <= tolerance
    if scale is not None:
        unit = kernels(x, taps).reshape(b, t, heads, width)
        np.testing.assert_allclose(jnp.sum(unit * unit, -1), scale ** 2,
                                   rtol=1e-4)


# -------------------------------------------------------- latent attention
def _mla_config(**over):
    cfg = transformer.TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=48, num_heads=3, head_dim=16,
        ff_dim=64, max_len=256, dtype=jnp.float32, attn_impl="flash",
        norm="rmsnorm", positions="rope", rope_theta=6e6, use_bias=False,
        fused_qkv=False, mlp="gated_silu", tie_head=False,
        layer_kinds=("mla",), kv_lora_rank=24,
        qk_nope_dim=16, rope_dim=8, rope_interleave=True)
    return dataclasses.replace(cfg, **over)


def _plain_mla(p, x, cfg, pos, seg):
    """Materialised scores, interleaved rope written out pair by pair."""
    b, t, _ = x.shape
    h, nope, turn = cfg.num_heads, cfg.qk_nope_dim, cfg.rope_dim

    def rope(a):  # [B, T, H, turn]
        inv = 1.0 / cfg.rope_theta ** (np.arange(0, turn, 2) / turn)
        ang = pos[..., None, None] * inv
        if cfg.rope_interleave:
            even, odd = a[..., 0::2], a[..., 1::2]
        else:
            even, odd = a[..., :turn // 2], a[..., turn // 2:]
        out = (even * jnp.cos(ang) - odd * jnp.sin(ang),
               odd * jnp.cos(ang) + even * jnp.sin(ang))
        if cfg.rope_interleave:
            return jnp.stack(out, axis=-1).reshape(a.shape)
        return jnp.concatenate(out, axis=-1)

    q = (x @ p["q"]["Dense_0"]["kernel"]).reshape(b, t, h, nope + turn)
    down = x @ p["kv_down"]["kernel"]
    c = down[..., :cfg.kv_lora_rank]
    c = c / jnp.sqrt(jnp.mean(c * c, -1, keepdims=True) + cfg.norm_eps) \
        * p["kv_norm"]["scale"]
    up = (c @ p["kv_up"]["Dense_0"]["kernel"]).reshape(b, t, h, -1)
    k_r = rope(down[..., None, cfg.kv_lora_rank:])
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q[..., :nope], up[..., :nope])
              + jnp.einsum("bqhd,bkd->bhqk", rope(q[..., nope:]),
                           k_r[:, :, 0])) / np.sqrt(nope + turn)
    idx = jnp.arange(t)
    allowed = (idx[:, None] >= idx[None]) & (seg[:, :, None] == seg[:, None])
    w = jax.nn.softmax(jnp.where(allowed[:, None], scores, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, up[..., nope:])
    o = o * jax.nn.sigmoid(x @ p["gate"]["kernel"])[..., None]
    return o.reshape(b, t, -1) @ p["proj"]["Dense_0"]["kernel"]


@pytest.mark.parametrize("impl, interleave, packed", [
    ("flash", True, False), ("flash", True, True), ("flash", False, False),
    ("full", True, True)])
def test_latent_attention_is_the_plain_form(impl, interleave, packed):
    cfg = _mla_config(attn_impl=impl, rope_interleave=interleave)
    b, t = 2, 70
    x = jax.random.normal(jax.random.PRNGKey(0), (b, t, cfg.model_dim))
    seg = jnp.asarray(np.stack([np.repeat([1, 2], [30, 40]),
                                np.repeat([1, 2, 3], [10, 50, 10])]))
    if not packed:
        seg = jnp.ones((b, t), jnp.int32)
    idx = jnp.broadcast_to(jnp.arange(t), (b, t))
    starts = jnp.concatenate(
        [jnp.ones((b, 1), bool), seg[:, 1:] != seg[:, :-1]], axis=1)
    pos = idx - jax.lax.cummax(jnp.where(starts, idx, 0), axis=1)
    tables = transformer.rope_tables(pos, cfg.rope_dim, cfg.rope_theta)
    attn = transformer.Attention(cfg, latent=True)
    params = attn.init(jax.random.PRNGKey(1), x, seg, tables)
    assert params["params"]["kv_up"]["Dense_0"]["kernel"].shape == (
        24, 3 * 32)
    assert params["params"]["q"]["Dense_0"]["kernel"].shape == (48, 3 * 24)

    def system(p, x):
        return attn.apply(p, x, seg if packed else None, tables)

    def plain(p, x):
        return _plain_mla(p["params"], x, cfg, pos.astype(jnp.float32), seg)

    weight = jax.random.normal(jax.random.PRNGKey(2), (b, t, cfg.model_dim))
    with jax.default_matmul_precision("highest"):
        got, want = system(params, x), plain(params, x)
        g_got = jax.grad(lambda p, x: jnp.sum(system(p, x) * weight),
                         argnums=(0, 1))(params, x)
        g_want = jax.grad(lambda p, x: jnp.sum(plain(p, x) * weight),
                          argnums=(0, 1))(params, x)
    assert _max_rel(got, want) <= 2e-5
    flat = jax.tree_util.tree_flatten_with_path(g_got)[0]
    for (path, a), w in zip(flat, jax.tree.leaves(g_want)):
        assert _max_rel(a, w) <= 1e-4, jax.tree_util.keystr(path)


# ------------------------------------------------------------------ router
def _route(scores, bias, **kw):
    args = dict(k=2, n_group=2, topk_group=1, scale=2.5)
    args.update(kw)
    ids, w = moe.route_group_limited(
        jnp.asarray([scores], jnp.float32), jnp.asarray(bias, jnp.float32),
        **args)
    return sorted(np.asarray(ids[0]).tolist()), np.asarray(w[0])


def test_the_group_limit_changes_the_choice():
    """Two groups of three, one kept.  The largest score of all lies in
    group 0; group 1's two best sum higher, so both choices come from
    group 1: plain top-2 would take experts 0 and 3."""
    scores = [0.9, 0.1, 0.1, 0.6, 0.5, 0.1]
    zero = [0.0] * 6
    assert _route(scores, zero)[0] == [3, 4]
    assert _route(scores, zero, n_group=1)[0] == [0, 3]  # = lax.top_k
    assert sorted(np.asarray(jax.lax.top_k(
        jnp.asarray(scores), 2)[1]).tolist()) == [0, 3]
    ids, w = _route(scores, zero)
    np.testing.assert_allclose(sorted(w), [2.5 * 0.5 / 1.1, 2.5 * 0.6 / 1.1],
                               rtol=1e-6)


def test_the_bias_changes_the_choice_and_not_the_weight():
    scores = [0.2, 0.1, 0.1, 0.6, 0.5, 0.4]
    ids, w = _route(scores, [0.0] * 6)
    assert ids == [3, 4]
    # expert 5's bias lifts it over expert 4; its weight is from its score
    ids_b, w_b = _route(scores, [0, 0, 0, 0, 0, 0.2])
    assert ids_b == [3, 5]
    np.testing.assert_allclose(sorted(w_b), [2.5 * 0.4 / 1.0, 2.5 * 0.6 / 1.0],
                               rtol=1e-6)
    # a bias can move the kept group too
    assert _route(scores, [0.9, 0.9, 0, 0, 0, 0])[0] == [0, 1]
    # and takes no gradient, the scores do
    g_s, g_b = jax.grad(
        lambda s, b: jnp.sum(moe.route_group_limited(
            s, b, 2, 2, 1, 2.5)[1] * jnp.asarray([1.0, 2.0])),
        argnums=(0, 1))(jnp.asarray([scores]), jnp.zeros(6))
    assert not np.any(g_b) and np.any(g_s)


def test_router_is_top_k_where_nothing_limits_it():
    scores = jax.nn.sigmoid(
        jax.random.normal(jax.random.PRNGKey(0), (50, 32)))
    ids, w = moe.route_group_limited(scores, jnp.zeros(32), 4, 8, 8, 1.0)
    vals, want = jax.lax.top_k(scores, 4)
    np.testing.assert_array_equal(np.sort(ids, -1), np.sort(want, -1))
    np.testing.assert_allclose(np.sort(w, -1), np.sort(
        vals / vals.sum(-1, keepdims=True), -1), rtol=1e-6)


# ---------------------------------------------------------------- dispatch
def _dense_loop(x, weights, wg, wu, wd, local):
    out = jnp.zeros(x.shape, jnp.float32)
    for e in range(wg.shape[0]):
        w = jnp.sum(jnp.where(local == e, weights, 0.0), axis=-1)
        out = out + w[:, None] * (
            (jax.nn.silu(x @ wg[e]) * (x @ wu[e])) @ wd[e])
    return out


def _dispatch_case(load, s=600, k=3, d=16, f=24, n=4, dtype=jnp.float32):
    """(x, weights, wg, wu, wd, local, cotangent): ``s`` tokens x 3 choices
    over 4 held experts (index 4: another device's).  Skewed: expert 1
    takes most pairs, more than two tiles, expert 2 takes none."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    if load == "skewed":
        local = jnp.stack([
            jnp.where(jnp.arange(s) % 10 < 9, 1, 0),     # 540 on expert 1
            jnp.where(jnp.arange(s) % 7 == 0, 3, 4),
            jnp.where(jnp.arange(s) % 5 == 0, 0, 4)], axis=1)
        assert int(jnp.sum(local == 1)) > 2 * moe._TILE
        assert int(jnp.sum(local == 2)) == 0
    elif load == "even":
        local = jnp.stack([jax.random.permutation(k_, 5)[:3]
                           for k_ in jax.random.split(keys[5], s)])
    else:
        local = jnp.full((s, k), 4)
    x = jax.random.normal(keys[0], (s, d)).astype(dtype)
    weights = jax.random.uniform(keys[1], (s, k), minval=0.1)
    wg, wu = (jax.random.normal(kk, (n, d, f)) / d ** 0.5 for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (n, f, d)) / f ** 0.5
    cot = jax.random.normal(jax.random.PRNGKey(7), (s, d))
    return x, weights, wg, wu, wd, local.astype(jnp.int32), cot


@pytest.mark.parametrize("load, d, f", [
    ("skewed", 16, 24), ("even", 16, 24), ("none_held", 16, 24),
    # a block shape the chip uses: d and f whole 128-lane slabs, three
    # blocks of f a tile
    ("skewed", 128, 384), ("none_held", 128, 384)])
def test_dropless_dispatch_is_the_dense_loop(load, d, f):
    """The kernel pair (interpreted here), a call a tile, against every
    token through every expert: the output and all five gradients, those
    of the float32 matrices float32; no pair is lost."""
    from horovod_tpu.ops import expert_kernels

    assert f // expert_kernels._f_block(f) == (3 if f == 384 else 1)
    *args, local, cot = _dispatch_case(load, d=d, f=f)
    with jax.default_matmul_precision("highest"):
        got = moe.grouped_experts(*args, local)
        want = _dense_loop(*args, local)
        g_got = jax.grad(lambda *a: jnp.sum(
            moe.grouped_experts(*a, local) * cot), argnums=range(5))(*args)
        g_want = jax.grad(lambda *a: jnp.sum(
            _dense_loop(*a, local) * cot), argnums=range(5))(*args)
    assert _max_rel(got, want, 1.0) <= 1e-5
    for name, a, w in zip("x weights wg wu wd".split(), g_got, g_want):
        assert a.dtype == jnp.float32, name
        assert _max_rel(a, w, 1.0) <= 1e-5, name
    if load == "none_held":
        assert not np.any(got)
        assert not any(np.any(g) for g in g_got)


def test_no_bfloat16_between_the_tiles_and_a_float32_parameter():
    """Rows in bfloat16, the experts' matrices float32 as the parameters
    are: their gradients come back float32 with more than bfloat16's bits
    (the float32 sums of a tile's products, never rounded on the way), for
    every expert a pair reached, and zeros for the one none did."""
    *args, local, cot = _dispatch_case("skewed", dtype=jnp.bfloat16)
    grads = jax.grad(lambda *a: jnp.sum(
        moe.grouped_experts(*a, local) * cot), argnums=(2, 3, 4))(*args)
    for g in grads:
        assert g.dtype == jnp.float32
        rounded = g.astype(jnp.bfloat16).astype(jnp.float32)
        for e in (0, 1, 3):
            assert float(jnp.mean(g[e] != rounded[e])) > 0.9, e
        assert not np.any(g[2])
    # and they are the float32 program's to bfloat16's rounding of the rows
    want = jax.grad(lambda *a: jnp.sum(
        _dense_loop(*a, local) * cot), argnums=(2, 3, 4))(
            args[0].astype(jnp.float32), *args[1:])
    for g, w in zip(grads, want):
        assert _max_rel(g, w, 1.0) <= 3e-2


def test_padding_rows_are_not_scattered(monkeypatch):
    """A tile's rows past its expert's pairs are gathered as zeros and
    dropped on the way back: a NaN planted in every such row of the
    kernels' results (the rows whose routing weight is 0; the case's real
    weights are 0.1 and more) reaches neither the output nor a gradient
    of x or of the routing weights."""
    from horovod_tpu.ops import expert_kernels

    forward, backward = (
        expert_kernels.tile_forward, expert_kernels.tile_backward)
    planted = []

    def nan_rows(w, rows):
        return jnp.where(w == 0.0, jnp.nan, rows)

    def tile_forward(e, x, w, *matrices):
        planted.append("forward")
        return nan_rows(w, forward(e, x, w, *matrices))

    def tile_backward(e, first, x, dy, w, *matrices):
        planted.append("backward")
        dx, dw, *grads = backward(e, first, x, dy, w, *matrices)
        return (nan_rows(w, dx), nan_rows(w, dw), *grads)

    monkeypatch.setattr(expert_kernels, "tile_forward", tile_forward)
    monkeypatch.setattr(expert_kernels, "tile_backward", tile_backward)
    *args, local, cot = _dispatch_case("skewed")
    with jax.default_matmul_precision("highest"):
        got, grads = jax.value_and_grad(lambda *a: jnp.sum(
            moe.grouped_experts(*a, local) * cot), argnums=range(5))(*args)
        want = jnp.sum(_dense_loop(*args, local) * cot)
    assert set(planted) == {"forward", "backward"}
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for g in grads:
        assert np.isfinite(np.asarray(g)).all()


def test_an_experts_first_tile_writes_its_sums_and_a_later_one_adds():
    """The backward kernel on one tile: where the tile is its expert's
    first, the expert's blocks of the accumulators are written whatever
    they held (NaN here); a later tile fetches them and adds; no other
    expert's block is touched either way."""
    from horovod_tpu.ops import expert_kernels

    tile, d, f, n, e = 256, 128, 256, 3, 1
    keys = jax.random.split(jax.random.PRNGKey(5), 6)
    x, dy = (jax.random.normal(k_, (tile, d)) for k_ in keys[:2])
    w = jax.random.uniform(keys[2], (tile, 1), minval=0.1)
    wg, wu = (jax.random.normal(k_, (n, d, f)) / d ** 0.5 for k_ in keys[3:5])
    wd = jax.random.normal(keys[5], (n, f, d)) / f ** 0.5
    assert f // expert_kernels._f_block(f) == 2

    def tile_sums(first, start):
        held = [jnp.full(m.shape, start) for m in (wg, wu, wd)]
        with jax.default_matmul_precision("highest"):
            return expert_kernels.tile_backward(
                jnp.int32(e), jnp.asarray(first), x, dy, w, wg, wu, wd,
                *held)[2:]

    def plain(wg_e, wu_e, wd_e):
        return jnp.sum(w * ((jax.nn.silu(x @ wg_e) * (x @ wu_e)) @ wd_e) * dy)

    with jax.default_matmul_precision("highest"):
        want = jax.grad(plain, argnums=(0, 1, 2))(wg[e], wu[e], wd[e])
    for got, later, exact in zip(
            tile_sums(True, jnp.nan), tile_sums(False, 1.0), want):
        assert _max_rel(got[e], exact, 1.0) <= 1e-5
        assert _max_rel(later[e], exact + 1.0, 1.0) <= 1e-5
        for other in (0, 2):
            assert np.isnan(np.asarray(got[other])).all()
            assert np.all(np.asarray(later[other]) == 1.0)


def test_a_token_may_name_one_expert_twice():
    """``lax.top_k`` gives a token distinct experts, a direct caller's
    ``local`` need not: two choices of one token on one expert are two
    rows of its tile, both summed (the scatter-add is given no hint about
    its indices)."""
    *args, local, cot = _dispatch_case("even")
    local = local.at[:, 1].set(local[:, 0])
    assert int(jnp.sum(local[:, 0] < 4)) > 300
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(lambda *a: jnp.sum(
            moe.grouped_experts(*a, local) * cot), argnums=range(5))(*args)
        want, g_want = jax.value_and_grad(lambda *a: jnp.sum(
            _dense_loop(*a, local) * cot), argnums=range(5))(*args)
    assert float(got) == pytest.approx(float(want), rel=1e-5)
    for a, w in zip(g_got, g_want):
        assert _max_rel(a, w, 1.0) <= 1e-5


def test_a_width_the_chips_expert_kernels_do_not_take_is_refused(
        monkeypatch):
    from horovod_tpu.ops import expert_kernels, pallas_kernels

    *args, local, _ = _dispatch_case("even", s=8)
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    assert expert_kernels.takes(2048, 512) and expert_kernels.takes(2560, 768)
    with pytest.raises(ValueError, match="whole 128-lane slabs"):
        moe.grouped_experts(*args, local)


def _expert_layer(experts_held, total=64):
    return moe.ExpertFFN(
        num_experts=total, experts_held=experts_held, hidden=12, k=8,
        n_group=8, topk_group=4, routed_scaling=2.5, dtype=jnp.float32)


def test_the_shares_add_up_to_the_uncut_layer():
    """8 shares of 8 experts of a 64-expert layer: the routed parts summed
    and the shared expert counted once are the uncut layer, which is the
    plain reference's (every token through every expert)."""
    from benchmark.reference import hybridmoe as reference

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 16))
    whole = _expert_layer((0, 64))
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (64,))
    model = dict(n_group=8, topk_group=4, num_experts_per_tok=8,
                 routed_scaling_factor=2.5, experts_held=[0, 64])
    with jax.default_matmul_precision("highest"):
        uncut = reference._experts(params, x, model)
        y_whole, load_whole = whole.apply({"params": params}, x)
        shared = reference._swiglu(
            x, *(params["shared"][n]["Dense_0"]["kernel"]
                 for n in ("wg", "wi", "wo")))
        total, loads = jnp.zeros_like(uncut), []
        for s in range(8):
            held = slice(8 * s, 8 * s + 8)
            share = dict(params, **{
                n: params[n][held] for n in ("wg", "wi", "wo")})
            y, load = _expert_layer((8 * s, 8 * s + 8)).apply(
                {"params": share}, x)
            # the reference is given the same share
            part = reference._experts(
                share, x, dict(model, experts_held=[8 * s, 8 * s + 8]))
            assert _max_rel(y, part) <= 1e-5, s
            total = total + (y - shared)
            loads.append(load)
        total = total + shared
    assert _max_rel(y_whole, uncut) <= 1e-5
    assert _max_rel(total, uncut) <= 1e-5
    # every pair is served by exactly one share
    assert float(sum(l.sum() for l in loads)) == 2 * 40 * 8
    np.testing.assert_array_equal(jnp.concatenate(loads), load_whole)
    # a share that left its routed part out would miss far more than that
    assert _max_rel(total - (y - shared), uncut) > 20 * _max_rel(total, uncut)


def test_expert_range_must_lie_inside_the_layer():
    x = jnp.zeros((1, 4, 16))
    with pytest.raises(ValueError, match="experts_held"):
        _expert_layer((60, 70)).init(jax.random.PRNGKey(0), x)


# ------------------------------------------------------------ the model
def test_layer_kinds_choose_mixer_and_ffn_and_the_gauges_say_so():
    from horovod_tpu import metrics

    cfg = _mla_config(
        num_layers=3, layer_kinds=("kda", "kda", "mla"),
        ffn_kinds=("dense", "experts", "experts"), num_experts=8,
        experts_held=(0, 4), expert_ff_dim=12, experts_per_token=2,
        n_group=2, topk_group=1, remat=True)
    model = transformer.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 20), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    assert set(params["block_0"]) == {"kda", "ln_attn", "ln_mlp", "mlp"}
    assert set(params["block_1"]) == {"kda", "ln_attn", "ln_mlp", "moe"}
    assert set(params["block_2"]) == {"attn", "ln_attn", "ln_mlp", "moe"}
    assert params["block_1"]["moe"]["wg"].shape == (4, 48, 12)
    assert params["block_1"]["moe"]["router"].shape == (48, 8)
    with metrics.traced_gauges() as bag:
        logits, _ = model.apply({"params": params}, tokens)
    assert logits.shape == (2, 20, 64)
    for kind, count in [("kda", 2), ("mla", 1), ("full", 0), ("dense", 1),
                        ("experts", 2), ("moe", 0)]:
        assert metrics.get_gauge("model.layer_kinds", {"kind": kind}) == count
    assert metrics.get_gauge("model.moe.experts_held") == 4
    assert 0 <= float(bag["model.moe.pairs_per_step"]) <= 2 * 2 * 20 * 2
    assert float(bag["model.moe.load_max_over_mean"]) >= 1
    with pytest.raises(ValueError, match="unknown kind"):
        transformer.layer_kind(
            dataclasses.replace(cfg, layer_kinds=("kda", "ssm", "mla")), 1)
    # heads of 16 are interpreted here, so both delta-rule layers' cores
    # ran as the kernel pair, and their convolutions as theirs
    assert metrics.get_gauge("model.kda.kernel_layers") == 2
    assert metrics.get_gauge("model.conv.kernel_layers") == 2
    # the older way of asking for the capacity MoE still reads the same
    old = dataclasses.replace(cfg, layer_kinds=(), ffn_kinds=(), moe_every=2)
    assert [transformer.layer_kind(old, i) for i in range(3)] == [
        ("full", "dense"), ("full", "moe"), ("full", "dense")]


@pytest.mark.parametrize("family, layers", [
    ("hybrid", 2), ("swa", 3), ("dense", None)])
def test_the_gauge_counts_the_layers_whose_product_took_the_kernels(
        family, layers):
    """Stand-ins of the two families with routed experts (delta rule and
    latent attention; window and full attention over grouped heads): every
    expert layer's grouped product runs as ``ops/expert_kernels``' pair,
    and ``model.moe.kernel_layers`` says so; a dense model sets no such
    gauge."""
    from horovod_tpu import metrics

    experts = dict(num_experts=8, experts_held=(0, 4), expert_ff_dim=12,
                   experts_per_token=2, n_group=2, topk_group=1)
    cfg = {
        "hybrid": lambda: _mla_config(
            num_layers=3, layer_kinds=("kda", "kda", "mla"),
            ffn_kinds=("dense", "experts", "experts"), **experts),
        "swa": lambda: dataclasses.replace(
            _mla_config(), layer_kinds=("full", "window", "window", "full"),
            num_layers=4, window=8, num_kv_heads=1,
            ffn_kinds=("dense", "experts", "experts", "experts"), **experts),
        "dense": lambda: dataclasses.replace(
            _mla_config(), layer_kinds=("full",)),
    }[family]()
    model = transformer.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 20), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)["params"]
    metrics.clear_gauge("model.moe.kernel_layers")
    calls = []
    forward = moe.expert_kernels.tile_forward
    try:
        moe.expert_kernels.tile_forward = lambda *a: (
            calls.append(1), forward(*a))[1]
        with metrics.traced_gauges():
            logits, _ = model.apply({"params": params}, tokens)
    finally:
        moe.expert_kernels.tile_forward = forward
    assert np.isfinite(np.asarray(logits)).all()
    assert metrics.get_gauge("model.moe.kernel_layers") == layers
    assert len(calls) == (layers or 0)      # a loop's body a layer


def _equations(jaxpr, outer=""):
    """(equation, its scope path from the top) of ``jaxpr`` and of the
    jaxprs inside it: a jitted call's body names its scopes from the
    call."""
    for eqn in jaxpr.eqns:
        scope = f"{outer}/{eqn.source_info.name_stack}"
        yield eqn, scope
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub, scope)


def test_the_delta_rule_mixer_stays_on_the_projections_layout():
    """Heads of 128, the chip's: from the projections to ``proj`` nothing
    under ``kda/`` is turned (no ``transpose`` of an activation, no
    [B, T, H, d] and no [n, B, H, C, d] array outside a kernel); the core
    is two kernel calls a layer in a gradient (the forward that keeps the
    states, the backward), both under ``kda/core`` where the benchmark's
    readers look, and no other kernel is there (the convolutions' pair,
    one for each of q, k and v each way, under ``kda/conv``, the output
    norm's under ``kda``); the gauges say that the layer's core and
    convolutions took the kernels."""
    from horovod_tpu import metrics

    cfg = _mla_config(num_layers=1, layer_kinds=("kda",), model_dim=32,
                      num_heads=2, head_dim=128, ff_dim=32)
    model = transformer.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 128), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)

    def loss(p):
        return jnp.sum(model.apply(p, tokens)[0])

    assert metrics.get_gauge("model.kda.kernel_layers") == 1 == \
        metrics.get_gauge("model.conv.kernel_layers") == \
        metrics.get_gauge("model.layer_kinds", {"kind": "kda"})
    under_kda = [(e, scope) for e, scope in _equations(
        jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        if "/kda/" in f"{scope}/"]
    calls = [(e, scope) for e, scope in under_kda
             if e.primitive.name == "pallas_call"]
    where = [scope.split("block_0/kda")[-1].split("/jit(")[0].rstrip("/")
             for _, scope in calls]
    # q's, k's and v's convolutions, the output's norm and gate: each once
    # each way
    assert sorted(where) == [""] * 2 + ["/conv"] * 6 + ["/core"] * 2
    for call, scope in calls:
        chunks = call.params["grid_mapping"].grid[1]
        assert (chunks == 128 // kda_kernels.CHUNK) == ("kda/core" in scope)
    assert {"conv", "gate", "core"} <= {
        part for _, scope in under_kda for part in scope.split("/")}
    calls = [call for call, _ in calls]
    for eqn, _ in under_kda:
        for out in eqn.outvars:
            # a matmul's gradient turns its [in, out] weight, nothing more
            assert eqn.primitive.name != "transpose" or out.aval.ndim == 2, \
                eqn
            # (the documents' marks a chunk are [B, n, 1, C] integers)
            assert (out.aval.ndim < 4 or eqn in calls
                    or out.aval.dtype == jnp.int32), eqn


def _olmo_config(**over):
    return _mla_config(**{
        **dict(num_layers=2, layer_kinds=("gdn", "full"), positions="none",
               pre_norm=False, post_norm=True, qk_norm=True, num_heads=2,
               head_dim=24, gdn_key_dim=16, gdn_value_dim=32),
        **over})


def test_olmo2_blocks_and_the_gdn_mixer():
    """OLMo 2's block (a norm on each sublayer's output, none on its
    input), q and k normed over their whole projections, no positions, and
    the Gated DeltaNet mixer: the parameters each makes, the gauges, and
    the mixer's scopes with the core as two kernel calls in a gradient."""
    from horovod_tpu import metrics

    cfg = _olmo_config()
    model = transformer.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (2, 70), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)
    p = params["params"]
    assert "wpe" not in p
    for i in range(2):
        assert {"ln_attn_post", "ln_mlp_post"} <= set(p[f"block_{i}"])
        assert not {"ln_attn", "ln_mlp"} & set(p[f"block_{i}"])
    gdn = p["block_0"]["gdn"]
    assert gdn["q"]["kernel"].shape == gdn["k"]["kernel"].shape == (48, 32)
    assert gdn["v"]["kernel"].shape == gdn["z"]["kernel"].shape == (48, 64)
    assert gdn["a"]["kernel"].shape == gdn["b"]["kernel"].shape == (48, 2)
    assert gdn["A_log"].shape == gdn["dt_bias"].shape == (2,)
    assert gdn["o_norm"]["scale"].shape == (32,)
    assert gdn["conv_v"].shape == (4, 64)
    attn = p["block_1"]["attn"]
    assert attn["q_norm"]["scale"].shape == attn["k_norm"]["scale"].shape \
        == (48,)
    assert float(jnp.min(-jnp.exp(gdn["A_log"]))) >= -16.0

    def loss(p):
        return jnp.sum(model.apply(p, tokens)[0])

    logits, _ = model.apply(params, tokens)
    assert np.isfinite(np.asarray(logits)).all()
    assert metrics.get_gauge("model.gdn.kernel_layers") == 1 == \
        metrics.get_gauge("model.conv.kernel_layers") == \
        metrics.get_gauge("model.layer_kinds", {"kind": "gdn"})
    scopes = [(e, scope) for e, scope in _equations(
        jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
        if "/gdn/" in f"{scope}/"]
    calls = [scope.split("block_0/gdn")[-1].split("/jit(")[0].rstrip("/")
             for e, scope in scopes if e.primitive.name == "pallas_call"]
    # the core's pair; q's, k's and v's convolutions and the output's norm
    # each way
    assert sorted(calls) == ["/conv"] * 6 + ["/core"] * 2 + ["/norm"] * 2
    assert {"conv", "gate", "core", "norm"} <= {
        part for _, scope in scopes for part in scope.split("/")}
    with pytest.raises(ValueError, match="pre_norm, post_norm"):
        transformer.Transformer(dataclasses.replace(
            cfg, post_norm=False)).init(jax.random.PRNGKey(1), tokens)


def test_a_width_the_chip_does_not_take_falls_back_and_the_gauge_says_so(
        monkeypatch):
    """On the chip keys of 16 and values of 24 are no multiple of 32
    lanes: the GDN core falls to the chunked form and its convolutions and
    norms to XLA (same output), and ``model.gdn.kernel_layers`` and
    ``model.conv.kernel_layers`` read 0."""
    from horovod_tpu import metrics
    from horovod_tpu.ops import pallas_kernels

    cfg = _olmo_config(layer_kinds=("gdn",), num_layers=1, gdn_value_dim=24)
    model = transformer.Transformer(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(0), (1, 40), 0, 64)
    params = model.init(jax.random.PRNGKey(1), tokens)
    want, _ = model.apply(params, tokens)
    assert metrics.get_gauge("model.gdn.kernel_layers") == 1
    called = []
    real = kda.kda_chunk_major
    monkeypatch.setattr(kda, "kda_chunk_major",
                        lambda *a: called.append(1) or real(*a))
    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    got, _ = model.apply(params, tokens)
    assert called and metrics.get_gauge("model.gdn.kernel_layers") == 0
    assert metrics.get_gauge("model.conv.kernel_layers") == 0
    assert _max_rel(got, want) <= 1e-4


# ------------------------------------------- one group: a 256-wide router's way
def test_expert_layer_of_one_group_is_plain_top_k():
    """``n_group`` = ``topk_group`` = 1 (Laguna-XS.2's router): the 8
    largest s + b over the router's whole width, weights 2.5 s_i / sum of
    the chosen s, through ``ExpertFFN`` against ``lax.top_k`` and a dense
    loop."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 50, 16))
    layer = moe.ExpertFFN(
        num_experts=32, experts_held=(0, 32), hidden=12, k=8, n_group=1,
        topk_group=1, routed_scaling=2.5, dtype=jnp.float32)
    params = layer.init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (32,))
    xf = x.reshape(-1, 16)
    with jax.default_matmul_precision("highest"):
        y, load = layer.apply({"params": params}, x)
        scores = jax.nn.sigmoid(xf @ params["router"])
        _, ids = jax.lax.top_k(scores + params["router_bias"], 8)
        chosen = jnp.take_along_axis(scores, ids, axis=-1)
        weights = 2.5 * chosen / chosen.sum(-1, keepdims=True)
        shared = (jax.nn.silu(xf @ params["shared"]["wg"]["Dense_0"]["kernel"])
                  * (xf @ params["shared"]["wi"]["Dense_0"]["kernel"])
                  ) @ params["shared"]["wo"]["Dense_0"]["kernel"]
        want = shared + _dense_loop(
            xf, weights, params["wg"], params["wi"], params["wo"], ids)
    assert _max_rel(y.reshape(-1, 16), want) <= 1e-5
    np.testing.assert_array_equal(
        load, np.bincount(np.asarray(ids).ravel(), minlength=32))
    assert float(load.sum()) == 100 * 8


def test_the_shares_of_a_one_group_layer_add_up():
    """8 shares of 4 experts of a 32-expert layer routed as one group: the
    routed parts summed and the shared expert counted once are the uncut
    layer, which is the plain reference's of the window/full-attention
    family (every token through every expert)."""
    from benchmark.reference import swamoe as reference

    def layer(held):
        return moe.ExpertFFN(
            num_experts=32, experts_held=held, hidden=12, k=8, n_group=1,
            topk_group=1, routed_scaling=2.5, dtype=jnp.float32)

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 40, 16))
    params = layer((0, 32)).init(jax.random.PRNGKey(1), x)["params"]
    params["router_bias"] = 0.05 * jax.random.normal(
        jax.random.PRNGKey(2), (32,))
    model = dict(num_experts_per_tok=8, moe_routed_scaling_factor=2.5,
                 experts_held=[0, 32])
    with jax.default_matmul_precision("highest"):
        uncut = reference._experts(params, x, model)
        y_whole, load_whole = layer((0, 32)).apply({"params": params}, x)
        shared = reference._swiglu(
            x, *(params["shared"][n]["Dense_0"]["kernel"]
                 for n in ("wg", "wi", "wo")))
        total, loads = jnp.zeros_like(uncut), []
        for s in range(8):
            held = slice(4 * s, 4 * s + 4)
            share = dict(params, **{
                n: params[n][held] for n in ("wg", "wi", "wo")})
            y, load = layer((4 * s, 4 * s + 4)).apply({"params": share}, x)
            # the reference is given the same share
            part = reference._experts(
                share, x, dict(model, experts_held=[4 * s, 4 * s + 4]))
            assert _max_rel(y, part) <= 1e-5, s
            total = total + (y - shared)
            loads.append(load)
        total = total + shared
    assert _max_rel(y_whole, uncut) <= 1e-5
    assert _max_rel(total, uncut) <= 1e-5
    # every pair is served by exactly one share
    assert float(sum(l.sum() for l in loads)) == 2 * 40 * 8
    np.testing.assert_array_equal(jnp.concatenate(loads), load_whole)
    # the shared expert counted in every share would be seven too many
    assert _max_rel(total + 7 * shared, uncut) > 100 * _max_rel(total, uncut)


@pytest.mark.parametrize("expected, rows", [
    (64, 256), (120, 256), (160, 512), (170, 512), (256, 512), (384, 768),
    (512, 1024), (2048, 1024)])
def test_a_tile_leaves_room_for_the_routers_draw(expected, rows):
    """Twice an expert's expected pairs, in multiples of 256 up to four:
    64 pairs an expert keep the tile of 256, 160 and 256 pairs an expert
    take 512 (one tile an expert whatever the seed)."""
    assert moe.tile_rows(expected) == rows


def test_a_wider_tile_is_the_same_product():
    s, k, d, f, n = 700, 2, 16, 24, 3
    keys = jax.random.split(jax.random.PRNGKey(3), 6)
    local = jax.random.randint(keys[5], (s, k), 0, n + 1).astype(jnp.int32)
    x = jax.random.normal(keys[0], (s, d))
    weights = jax.random.uniform(keys[1], (s, k))
    wg, wu = (jax.random.normal(kk, (n, d, f)) / 4 for kk in keys[2:4])
    wd = jax.random.normal(keys[4], (n, f, d)) / 5
    with jax.default_matmul_precision("highest"):
        want, g_want = jax.value_and_grad(lambda *a: jnp.sum(
            _dense_loop(*a, local) ** 2), argnums=range(5))(
                x, weights, wg, wu, wd)
        for tile in (256, 512):
            got, g_got = jax.value_and_grad(lambda *a: jnp.sum(
                moe.grouped_experts(*a, local, tile) ** 2),
                argnums=range(5))(x, weights, wg, wu, wd)
            assert float(got) == pytest.approx(float(want), rel=1e-5)
            for a, w in zip(g_got, g_want):
                assert _max_rel(a, w, 1.0) <= 1e-5, tile
