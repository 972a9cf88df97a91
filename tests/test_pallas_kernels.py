"""Pallas kernel parity tests (interpret mode on the CPU mesh).

Mirrors the reference's CUDA-kernel coverage: scale/cast parity with
the plain XLA path (``test_torch.py`` prescale/postscale cases) and
flash attention vs the exact ``full_attention`` reference.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from horovod_tpu.ops import pallas_kernels
from horovod_tpu.ops.pallas_kernels import flash_attention, scale_buffer
from horovod_tpu.parallel.ring_attention import full_attention


@pytest.mark.parametrize(
    "shape,dtype,out_dtype",
    [
        ((17,), jnp.float32, None),
        ((10, 100), jnp.float32, jnp.bfloat16),
        ((3, 5, 7), jnp.bfloat16, jnp.float32),
        ((65536,), jnp.float32, None),
    ],
)
def test_scale_buffer(shape, dtype, out_dtype):
    x = jnp.arange(int(np.prod(shape)), dtype=dtype).reshape(shape) / 100
    got = scale_buffer(x, 0.25, out_dtype)
    want = (x.astype(jnp.float32) * 0.25).astype(out_dtype or dtype)
    assert got.shape == x.shape and got.dtype == want.dtype
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32)
    )


def test_scale_buffer_jit_and_grad():
    x = jnp.ones((256,), jnp.float32)
    y = jax.jit(lambda a: scale_buffer(a, 2.0))(x)
    np.testing.assert_allclose(np.asarray(y), 2.0 * np.ones(256))
    # custom VJP: d/dx (x*2).sum() == 2, d/dscale == Σx
    dx = jax.grad(lambda a: scale_buffer(a, 2.0).sum())(x)
    np.testing.assert_allclose(np.asarray(dx), 2.0 * np.ones(256))
    dscale = jax.grad(lambda s: scale_buffer(x, s).sum())(jnp.float32(2.0))
    np.testing.assert_allclose(float(dscale), 256.0)


def test_flash_attention_rejects_unequal_seq_lens():
    from horovod_tpu.ops.pallas_kernels import flash_attention

    q = jnp.zeros((1, 64, 2, 32))
    kv = jnp.zeros((1, 128, 2, 32))
    with pytest.raises(ValueError, match="equal q/k/v sequence lengths"):
        flash_attention(q, kv, kv)


# name -> (H, D, heads a grid step owns): the kernels cut [B, T, H·D] into
# column slabs of whole 128-lane columns where the heads allow it.
_SLABS = {
    "pair_d64": (12, 64, 2),      # gpt2s
    "one_d128": (16, 128, 1),     # ouro26b
    "four_d32": (4, 32, 4),
    "all_d16": (2, 16, 2),        # 8 heads would fill a column: all of them
    "all_odd_d64": (3, 64, 3),    # a pair does not divide 3: all of them
}


@pytest.mark.parametrize("name", list(_SLABS))
def test_slab_rule(name):
    h, d, g = _SLABS[name]
    assert pallas_kernels._slab_heads(h, d) == g
    q = jnp.zeros((2, 64, h, d), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda q: _grads(flash_attention, q, q, q, causal=True))(q)
    calls = [e for e in _eqns(jaxpr.jaxpr)
             if e.primitive.name == "pallas_call"]
    assert [c.params["grid_mapping"].grid for c in calls] == [
        (2, h // g), (2, h // g, 1)]


# (b, t, h, d), causal, the caller's (block_q, block_k).  Both kernels take
# their tile from T and the smaller cap: 64 here, else a multiple of 128.
_FORWARD_CASES = {
    "one_tile": ((2, 128, 4, 64), False, (64, 64)),
    "one_tile_causal": ((2, 128, 4, 64), True, (64, 64)),
    "ragged": ((1, 100, 2, 32), True, (64, 64)),  # ragged T → padding path
    "ragged_blocks": ((1, 257, 3, 64), False, (64, 64)),
    "d128_blocks": ((1, 320, 2, 128), True, (64, 64)),  # heads of 128 (Ouro)
    # T = 1100: three tiles of 384 from the shapes alone, 52 rows of padding
    "padded_three_tiles": ((1, 1100, 2, 32), True, (512, 512)),
    "padded_three_tiles_noncausal": ((1, 1100, 2, 32), False, (512, 512)),
    # heads of 128, five tiles of 128
    "d128_tiles": ((1, 640, 2, 128), True, (512, 512)),
    "d128_tiles_noncausal": ((1, 640, 2, 128), False, (512, 512)),
    # blocks capped under 128 by the caller, and unequal: tiles of 32
    "capped_blocks": ((2, 200, 2, 32), True, (48, 32)),
    "capped_blocks_noncausal": ((2, 200, 2, 32), False, (32, 48)),
    # the slab rule (_SLABS): T = 200 is four tiles of 64, 56 rows padded
    **{f"slab_{name}{'' if causal else '_noncausal'}":
       ((1, 200, h, d), causal, (64, 64))
       for name, (h, d, _) in _SLABS.items() for causal in (True, False)},
}


@pytest.mark.parametrize("case", list(_FORWARD_CASES))
def test_flash_attention_forward(case):
    shape, causal, (block_q, block_k) = _FORWARD_CASES[case]
    q, k, v = jax.random.normal(jax.random.PRNGKey(0), (3,) + shape)
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, block_q, block_k)
    assert out.shape == ref.shape and out.dtype == ref.dtype
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5
    )


def _segments(b, t, cuts):
    seg = np.zeros((b, t), np.int32)
    for i, c in enumerate(cuts):
        seg[:, c:] = i + 1
    return jnp.asarray(seg)


def _logsumexp_reference(q, k, causal, seg):
    """The row logsumexp of the scaled, masked scores, [B, H, T] float32."""
    t = q.shape[1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    mask = jnp.ones((t, t), bool)
    if causal:
        mask = jnp.tril(mask)
    mask = mask[None, None]
    if seg is not None:
        mask = mask & (seg[:, None, :, None] == seg[:, None, None, :])
    return jax.nn.logsumexp(jnp.where(mask, s, -jnp.inf), axis=-1)


# (b, t, h, d), segment cuts, the caller's blocks
_LSE_CASES = {
    "padded_three_tiles": ((1, 1100, 2, 32), None, (512, 512)),
    "d128_tiles": ((1, 640, 2, 128), None, (512, 512)),
    "packed_capped_blocks": ((2, 70, 2, 8), (23, 41), (16, 16)),
    "packed_tiles": ((1, 300, 2, 32), (77, 130, 260), (128, 128)),
    **{f"slab_{name}": ((1, 200, h, d), (70, 150) if h < 12 else None,
                        (64, 64)) for name, (h, d, _) in _SLABS.items()},
}


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("case", list(_LSE_CASES))
def test_flash_forward_logsumexp(case, causal):
    """The residual the backward kernel rebuilds every tile's p from:
    one float32 a row, [B, H, T], against a float32 reference."""
    shape, cuts, (block_q, block_k) = _LSE_CASES[case]
    q, k, v = jax.random.normal(jax.random.PRNGKey(4), (3,) + shape)
    seg = None if cuts is None else _segments(shape[0], shape[1], cuts)
    b, t, h, d = shape
    _, (_, _, lse) = pallas_kernels._flash_fwd_res(
        tuple(x.reshape(b, t, h * d) for x in (q, k, v)), h, causal,
        d ** -0.5, block_q, block_k, seg)
    assert lse.shape == (b, h, t) and lse.dtype == jnp.float32
    np.testing.assert_allclose(
        np.asarray(lse), np.asarray(_logsumexp_reference(q, k, causal, seg)),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_packed_padding_rows(causal, monkeypatch):
    """A packed row whose documents end inside a tile (at 77, 130 and
    260 of tiles of 128) and whose padding, 300 to 384, carries the
    wrapper's -1: such a row sees no key at all, and the kernel gives it
    0 and a logsumexp of -1e30 (not exp(0) a key), where the wrapper
    cuts it off; every row inside T is finite and the reference's."""
    raw = []
    real = pallas_kernels.pl.pallas_call

    def spy(*args, **kwargs):
        call = real(*args, **kwargs)

        def run(*operands):
            raw.append(call(*operands))
            return raw[-1]

        return run

    monkeypatch.setattr(pallas_kernels.pl, "pallas_call", spy)
    b, t, h, d = 1, 300, 2, 32
    q, k, v = jax.random.normal(jax.random.PRNGKey(5), (3, b, t, h, d))
    seg = _segments(b, t, (77, 130, 260))
    out = flash_attention(q, k, v, causal, None, 128, 128, segment_ids=seg)
    ref = full_attention(q, k, v, causal=causal, segment_ids=seg)
    assert np.isfinite(np.asarray(out)).all()
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(ref), atol=2e-5, rtol=2e-5)
    (out_raw, lse_raw), = raw
    # the kernel's own layouts: o [B, T, H·D], lse [B, H, n, 1, block]
    out_raw = np.asarray(out_raw)
    assert out_raw.shape == (b, 384, h * d)
    lse_raw = np.asarray(lse_raw).reshape(b, h, 384)
    assert np.isfinite(lse_raw[:, :, :t]).all()
    assert (out_raw[:, t:] == 0).all() and (lse_raw[:, :, t:] <= -1e30).all()


def _grads(attn, q, k, v, **kw):
    def loss(q, k, v):
        return (attn(q, k, v, **kw).astype(jnp.float32) ** 2).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


# (b, t, h, d), causal, segment cuts, dtype, forward blocks, tolerance.
# The backward's tile is min(block_q, block_k) where that is under 128,
# else a multiple of 128 from T: several blocks in every multi-block case.
_GRAD_CASES = {
    # the two cases this test had before the backward became a kernel
    "noncausal": ((1, 96, 2, 32), False, None, jnp.float32, (32, 32), 1e-4),
    "causal": ((1, 96, 2, 32), True, None, jnp.float32, (32, 32), 1e-4),
    "packed": ((2, 70, 2, 8), True, (23, 41), jnp.float32, (16, 16), 1e-4),
    "packed_noncausal":
        ((1, 300, 2, 16), False, (50, 170), jnp.float32, (512, 512), 1e-4),
    # ragged T, three 128-blocks from the shapes alone
    "ragged_blocks":
        ((1, 257, 3, 64), True, None, jnp.float32, (512, 512), 2e-4),
    "ragged_packed_blocks":
        ((1, 300, 2, 32), True, (77, 130, 260), jnp.float32, (512, 512), 2e-4),
    # forward blocks that differ: the backward takes the smaller
    "unequal_blocks": ((1, 100, 2, 32), True, None, jnp.float32, (64, 32), 1e-4),
    "t_below_block": ((1, 9, 2, 16), True, None, jnp.float32, (512, 512), 1e-4),
    "bf16": ((2, 128, 2, 32), True, None, jnp.bfloat16, (512, 512), 2e-2),
    "bf16_packed_blocks":
        ((1, 256, 2, 64), True, (100,), jnp.bfloat16, (512, 512), 2e-2),
    # heads of 128 (Ouro's): three 128-blocks, dense and packed, both types
    "d128_blocks": ((1, 384, 2, 128), True, None, jnp.float32, (512, 512), 2e-4),
    "d128_packed_blocks":
        ((1, 384, 2, 128), True, (90, 200), jnp.float32, (512, 512), 2e-4),
    "d128_bf16_packed":
        ((1, 256, 2, 128), True, (100,), jnp.bfloat16, (512, 512), 2e-2),
    # the slab rule (_SLABS), four tiles of 64 with 56 rows padded: causal,
    # not causal, and packed
    **{f"slab_{name}_{kind}":
       ((1, 200, h, d), kind != "noncausal",
        (70, 150) if kind == "packed" else None, jnp.float32, (64, 64), 2e-4)
       for name, (h, d, _) in _SLABS.items()
       for kind in ("causal", "noncausal", "packed")},
}


@pytest.mark.parametrize("case", list(_GRAD_CASES))
def test_flash_attention_grads(case):
    shape, causal, cuts, dtype, (block_q, block_k), tol = _GRAD_CASES[case]
    q, k, v = jax.random.normal(jax.random.PRNGKey(1), (3,) + shape, dtype)
    seg = None if cuts is None else _segments(shape[0], shape[1], cuts)

    got = _grads(flash_attention, q, k, v, causal=causal, block_q=block_q,
                 block_k=block_k, segment_ids=seg)
    want = _grads(full_attention, *(x.astype(jnp.float32) for x in (q, k, v)),
                  causal=causal, segment_ids=seg)
    for g, w in zip(got, want):
        assert g.dtype == dtype and g.shape == shape
        # bf16: relative to the gradient's scale, as chip_smoke.py holds it
        scale = float(jnp.max(jnp.abs(w))) if dtype == jnp.bfloat16 else 1.0
        np.testing.assert_allclose(
            np.asarray(g, np.float32) / scale, np.asarray(w) / scale,
            atol=tol, rtol=tol)


def test_flash_attention_grads_under_shard_map():
    """The backward's outputs carry the operands' varying mesh axes
    (``_sds``), so the gradient composes with ``shard_map`` as Ulysses
    uses it; each device's rows match the unsharded gradient."""
    from jax.sharding import Mesh, PartitionSpec as P

    devices = jax.devices()[:2]
    if len(devices) < 2:
        pytest.skip("needs two devices")
    mesh = Mesh(np.array(devices), ("dp",))
    q, k, v = jax.random.normal(jax.random.PRNGKey(3), (3, 2, 48, 2, 16))

    def local(q, k, v):
        return _grads(flash_attention, q, k, v, causal=True,
                      block_q=16, block_k=16)

    spec = P("dp")
    got = jax.jit(jax.shard_map(
        local, mesh=mesh, in_specs=(spec,) * 3, out_specs=(spec,) * 3,
    ))(q, k, v)
    want = _grads(full_attention, q, k, v, causal=True)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                   atol=1e-4, rtol=1e-4)


def _eqns(jaxpr):
    """Every equation outside a ``pallas_call``'s kernel."""
    for eqn in jaxpr.eqns:
        yield eqn
        if eqn.primitive.name != "pallas_call":
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _eqns(sub)


@pytest.mark.parametrize("packed", [False, True])
def test_flash_attention_grad_keeps_scores_in_the_kernels(packed):
    """Neither pass loops in XLA or hands XLA a [.., T, T] array: the
    score tiles live in the two kernels only."""
    t = 256
    q = jnp.zeros((2, t, 2, 32), jnp.bfloat16)
    seg = _segments(2, t, (100,)) if packed else None
    jaxpr = jax.make_jaxpr(lambda q, k, v: _grads(
        flash_attention, q, k, v, causal=True, segment_ids=seg))(q, q, q)
    eqns = list(_eqns(jaxpr.jaxpr))
    names = [e.primitive.name for e in eqns]
    assert names.count("pallas_call") == 2
    assert not {"while", "scan"} & set(names)
    for eqn in eqns:
        for var in eqn.outvars:
            shape = getattr(var.aval, "shape", ())
            assert shape[-2:] != (t, t), (eqn.primitive.name, shape)


@pytest.mark.parametrize("packed", [False, True])
def test_flash_attention_grad_turns_nothing_outside_the_kernels(packed):
    """q, k, v, o and their gradients pass between the caller's
    [B, T, H, D] and the kernels by reshapes alone: no ``transpose``
    equation outside the two ``pallas_call``s, forward or backward."""
    q = jnp.zeros((2, 256, 12, 64), jnp.bfloat16)
    seg = _segments(2, 256, (100,)) if packed else None

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, segment_ids=seg)
        return (out.astype(jnp.float32) ** 2).sum()

    jaxpr = jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(
        q, q, q)
    names = [e.primitive.name for e in _eqns(jaxpr.jaxpr)]
    assert names.count("pallas_call") == 2
    assert "transpose" not in names and "dot_general" not in names


# (b, t, h, d), causal, segment cuts: the fused projection read in place
# (slabs of whole 128-lane columns) and cut up first (heads that fill none)
_FUSED_CASES = {
    "pair_d64": ((1, 200, 4, 64), True, None),
    "pair_d64_packed": ((1, 200, 4, 64), True, (70, 150)),
    "one_d128_noncausal": ((1, 160, 2, 128), False, None),
    "four_d32_packed": ((2, 96, 4, 32), True, (40,)),
    "all_d16": ((1, 100, 2, 16), True, None),
}


@pytest.mark.parametrize("case", list(_FUSED_CASES))
def test_flash_attention_of_a_fused_projection(case):
    """``flash_attention_qkv`` on [B, T, 3·H·D] against ``full_attention``
    on the three parts: the value and the one fused gradient."""
    from horovod_tpu.ops.pallas_kernels import flash_attention_qkv

    (b, t, h, d), causal, cuts = _FUSED_CASES[case]
    qkv = jax.random.normal(jax.random.PRNGKey(6), (b, t, 3 * h * d))
    seg = None if cuts is None else _segments(b, t, cuts)

    def parts(qkv):
        return (qkv.reshape(b, t, 3, h, d)[:, :, i] for i in range(3))

    def fused(qkv):
        return flash_attention_qkv(qkv, h, causal, segment_ids=seg)

    def reference(qkv):
        return full_attention(
            *parts(qkv), causal=causal, segment_ids=seg).reshape(b, t, h * d)

    np.testing.assert_allclose(
        np.asarray(fused(qkv)), np.asarray(reference(qkv)),
        atol=2e-5, rtol=2e-5)
    got = jax.grad(lambda x: (fused(x) ** 2).sum())(qkv)
    want = jax.grad(lambda x: (reference(x) ** 2).sum())(qkv)
    assert got.shape == qkv.shape
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-4, rtol=2e-4)


def test_fused_projection_is_read_in_place():
    """With slabs of whole 128-lane columns both kernels take the one
    [B, T, 3·H·D] array three times and nothing cuts it up; heads that
    fill no column are cut out first."""
    from horovod_tpu.ops.pallas_kernels import flash_attention_qkv

    def operands(h, d):
        qkv = jnp.zeros((1, 128, 3 * h * d), jnp.float32)
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda x: flash_attention_qkv(x, h, True).sum()))(qkv)
        eqns = list(_eqns(jaxpr.jaxpr))
        calls = [e for e in eqns if e.primitive.name == "pallas_call"]
        return {e.primitive.name for e in eqns}, [
            [v.aval.shape for v in c.invars[:3]] for c in calls]

    names, shapes = operands(4, 64)
    assert "slice" not in names
    assert shapes == [[(1, 128, 768)] * 3] * 2
    names, shapes = operands(2, 16)
    assert "slice" in names
    assert shapes == [[(1, 128, 32)] * 3] * 2


def _kernel_scopes(model, tokens):
    """The scope paths of the kernels in the compiled gradient of a model,
    which is what the device trace's events carry.  (A jitted wrapper's
    path is put together from its call site's when the module is compiled:
    the lowered text has only the callee's part.  Interpreted, a kernel is
    a ``while`` over its grid where the Mosaic call would be, which reads
    ``<this path>/pallas_call``: tests/test_tpu_compile.py.)"""
    params = model.init(jax.random.PRNGKey(0), tokens)

    def loss(p):
        return model.apply(p, tokens)[0].astype(jnp.float32).sum()

    text = jax.jit(jax.grad(loss)).lower(params).compile().as_text()
    return set(re.findall(
        r'op_name="(jit\([^"]*?/attn(?:/[^"/]+)*?)/while/', text))


def test_flash_backward_scope_is_not_the_forwards():
    """The benchmark's ``flash_fwd_roofline`` sums the Mosaic calls whose
    scope path holds "attn/pallas_call"; the backward's call, traced from
    the same ``attn`` module, must not read so."""
    from horovod_tpu.models.transformer import Transformer, TransformerConfig

    calls = _kernel_scopes(Transformer(TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=32, num_heads=2, head_dim=16,
        ff_dim=64, max_len=32)), jnp.zeros((2, 32), jnp.int32))
    forward = {c for c in calls if c.endswith("/attn")}
    backward = calls - forward
    assert len(forward) == 1 and "transpose(" not in next(iter(forward))
    assert len(backward) == 1
    path = next(iter(backward))
    assert "transpose(" in path and "/attn/flash_bwd/" in path


def test_rope_kernels_scope_is_neither_flash_kernels():
    """A model with rotary positions calls the rotary kernel from the
    same ``attn`` module; its calls read ".../attn/jit(_rope_call)/rope/
    pallas_call", forward and backward, so neither roofline's anchor
    ("attn/pallas_call", "flash_bwd") finds them."""
    from horovod_tpu.models.transformer import Transformer, TransformerConfig

    calls = _kernel_scopes(Transformer(TransformerConfig(
        vocab_size=64, num_layers=1, model_dim=32, num_heads=2, head_dim=16,
        ff_dim=64, max_len=32, positions="rope", fused_qkv=False)),
        jnp.zeros((2, 32), jnp.int32))
    rope = {c for c in calls if c.endswith("/attn/jit(_rope_call)/rope")}
    assert len(rope) == 2  # q's and k's share a path; forward, backward
    flash = calls - rope
    assert len({c for c in flash if c.endswith("/attn")}) == 1
    assert len({c for c in flash if "/attn/flash_bwd/" in c}) == 1
    assert len(flash) == 2


def test_flash_attention_bf16():
    rng = jax.random.PRNGKey(2)
    q, k, v = jax.random.normal(rng, (3, 2, 64, 2, 32), jnp.bfloat16)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 32, 32)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        atol=2e-2, rtol=2e-2,
    )


def test_flash_packed_multiblock_matches_full():
    """Packed segment masking across MULTIPLE k/q blocks (block=16,
    T=70 not a block multiple): exercises the cross-block online-softmax
    correction under segment masks and the -1 segment padding."""
    from horovod_tpu.ops.pallas_kernels import flash_attention
    from horovod_tpu.parallel.ring_attention import full_attention

    rng = np.random.RandomState(0)
    b, t, h, d = 2, 70, 2, 8
    q = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    k = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    v = jnp.asarray(rng.randn(b, t, h, d), jnp.float32)
    seg = np.zeros((b, t), np.int32)
    # segments straddle the 16-wide block boundaries
    seg[:, :23] = 1
    seg[:, 23:41] = 2
    seg[:, 41:] = 3
    seg = jnp.asarray(seg)

    for causal in (False, True):
        out = flash_attention(q, k, v, causal=causal, block_q=16,
                              block_k=16, segment_ids=seg)
        ref = full_attention(q, k, v, causal=causal, segment_ids=seg)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5
        )

    # gradient parity at the same block geometry
    def f_flash(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16,
            segment_ids=seg,
        ) ** 2)

    def f_full(q, k, v):
        return jnp.sum(full_attention(q, k, v, causal=True,
                                      segment_ids=seg) ** 2)

    g1 = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_full, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b_), rtol=1e-4, atol=1e-4
        )


def _rope_reference(x, cos, sin):
    """Rotate-half on [B, T, H, D] as plain jnp, float32 inside."""
    cos, sin = cos[..., None, :], sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


# (b, t, h, d), a table a row (packed positions), dtype
_ROPE_CASES = {
    "one_d128": ((2, 96, 2, 128), False, jnp.float32),
    "one_d128_bf16_long": ((1, 1100, 2, 128), False, jnp.bfloat16),
    "pair_d64": ((1, 100, 4, 64), False, jnp.float32),
    "four_d32_tables_per_row": ((2, 64, 4, 32), True, jnp.float32),
    "all_d16": ((2, 40, 2, 16), True, jnp.float32),
    "all_odd_d64": ((1, 50, 3, 64), False, jnp.float32),
}


@pytest.mark.parametrize("case", list(_ROPE_CASES))
def test_rope_kernel_matches_rotate_half(case):
    """The kernel on [B, T, H·D] against rotate-half written on
    [B, T, H, D]: the value and the gradient (the turn the other way)."""
    (b, t, h, d), per_row, dtype = _ROPE_CASES[case]
    x = jax.random.normal(jax.random.PRNGKey(7), (b, t, h, d), dtype)
    pos = jnp.arange(t) if not per_row else (
        jnp.arange(b * t).reshape(b, t) * 3 % 17)
    angles = pos[..., None] * (
        1.0 / 1e4 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(angles), jnp.sin(angles)

    def kernel(x):
        return pallas_kernels.rope(
            x.reshape(b, t, h * d), cos, sin, h).reshape(b, t, h, d)

    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    got, want = kernel(x), _rope_reference(x, cos, sin)
    assert got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=tol, rtol=tol)
    w = jax.random.normal(jax.random.PRNGKey(8), (b, t, h, d), jnp.float32)
    grad = lambda f: jax.grad(
        lambda x: (f(x).astype(jnp.float32) * w).sum())(x)
    np.testing.assert_allclose(
        np.asarray(grad(kernel), np.float32),
        np.asarray(grad(lambda x: _rope_reference(x, cos, sin)), np.float32),
        atol=tol, rtol=tol)


# ---------------------------------------------------------------------------
# A window and grouped key/value heads
# ---------------------------------------------------------------------------

# name -> (B, T, H, G, D, window, tile, packed): windows smaller than,
# equal to and larger than the tile, T that is no multiple of it, groups
# of 1, 6 and 8 query heads a key/value head, packed rows with a window.
_WINDOW_CASES = {
    "window_under_the_tile": (1, 256, 4, 4, 32, 64, 128, False),
    "window_is_the_tile": (1, 256, 4, 4, 32, 128, 128, False),
    "window_over_the_tile": (1, 256, 4, 4, 32, 200, 128, False),
    "window_of_two_tiles": (1, 384, 2, 2, 32, 256, 128, False),
    "window_of_one": (1, 200, 2, 2, 16, 1, 64, False),
    "window_over_the_row": (1, 200, 2, 2, 16, 500, 64, False),
    "ragged_rows": (2, 200, 6, 1, 32, 64, 128, False),
    "group_of_6": (1, 256, 6, 1, 128, None, 128, False),
    "group_of_6_window": (1, 256, 6, 1, 128, 64, 128, False),
    "group_of_8_window": (1, 256, 8, 1, 128, 128, 128, False),
    "group_of_6_of_2": (1, 256, 12, 2, 128, None, 128, False),
    "group_of_3_narrow_heads": (2, 150, 6, 2, 16, None, 64, False),
    "packed_window": (2, 256, 4, 4, 32, 100, 128, True),
    "packed_window_groups": (1, 256, 6, 2, 16, 100, 64, True),
}


def _window_inputs(case):
    b, t, h, g, d, window, tile, packed = _WINDOW_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(keys[0], (b, t, h, d))
    k = jax.random.normal(keys[1], (b, t, g, d))
    v = jax.random.normal(keys[2], (b, t, g, d))
    w = jax.random.normal(keys[3], (b, t, h, d))
    seg = None
    if packed:
        seg = jnp.asarray(np.sort(
            np.random.RandomState(0).randint(1, 4, (b, t)), axis=1))
    kw = dict(segment_ids=seg, window=window)
    return (q, k, v, w), dict(kw, block_q=tile, block_k=tile), kw


@pytest.mark.parametrize("case", list(_WINDOW_CASES))
def test_flash_attention_window_and_groups(case):
    """Both kernels against ``full_attention``: the output and dq, dk, dv
    (dk and dv the sums over a group's query heads)."""
    (q, k, v, w), flash_kw, full_kw = _window_inputs(case)
    got = jax.jit(lambda *a: flash_attention(*a, True, **flash_kw))(q, k, v)
    want = jax.jit(lambda *a: full_attention(*a, True, **full_kw))(q, k, v)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
    g_got = jax.jit(jax.grad(lambda *a: jnp.sum(
        flash_attention(*a, True, **flash_kw) * w), (0, 1, 2)))(q, k, v)
    g_want = jax.jit(jax.grad(lambda *a: jnp.sum(
        full_attention(*a, True, **full_kw) * w), (0, 1, 2)))(q, k, v)
    for name, a, b_ in zip("dq dk dv".split(), g_got, g_want):
        assert a.shape == b_.shape, name
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=5e-5, err_msg=name)


def test_a_window_is_not_the_whole_triangle():
    """The comparison above would pass a kernel and a reference that both
    ignored the window: the window moves the output."""
    (q, k, v, _), flash_kw, _ = _window_inputs("window_under_the_tile")
    windowed = flash_attention(q, k, v, True, **flash_kw)
    whole = flash_attention(q, k, v, True, **dict(flash_kw, window=None))
    np.testing.assert_array_equal(windowed[:, :64], whole[:, :64])
    assert float(jnp.max(jnp.abs(windowed[:, 64:] - whole[:, 64:]))) > 0.1


def test_grouped_heads_read_consecutive_query_heads():
    """Query head h reads key/value head h // group, not h % G."""
    (q, k, v, _), flash_kw, _ = _window_inputs("group_of_6_of_2")
    got = flash_attention(q, k, v, True, **flash_kw)
    repeated = flash_attention(
        q, jnp.repeat(k, 6, axis=2), jnp.repeat(v, 6, axis=2), True,
        **flash_kw)
    np.testing.assert_allclose(got, repeated, atol=1e-6)
    tiled = flash_attention(
        q, jnp.tile(k, (1, 1, 6, 1)), jnp.tile(v, (1, 1, 6, 1)), True,
        **flash_kw)
    assert float(jnp.max(jnp.abs(got - tiled))) > 0.1


def test_a_window_needs_the_causal_mask():
    q = jnp.zeros((1, 64, 2, 32))
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, False, window=16)
    with pytest.raises(ValueError, match="divides"):
        flash_attention(jnp.zeros((1, 64, 3, 32)), q, q, True)


@pytest.mark.parametrize("t, window, want", [
    (8192, None, 136), (8192, 512, 31), (8192, 513, 31), (8192, 514, 45),
    (8192, 1024, 45), (4096, 512, 15), (1024, 512, 3), (300, 64, 1),
    (100, 512, 1)])
def test_tiles_a_head_visits(t, window, want):
    """The count the ``model.attn.tiles_per_head`` gauge gives: tiles of
    512 (``_tile_edge``), a Q block's walk from the diagonal to the last
    tile its window touches."""
    assert pallas_kernels.flash_tiles_per_head(t, True, window) == want


# sha256 of the bytes of (out, dq, dk, dv), of seeded inputs, computed by
# the kernels as they were before they took a window and grouped heads
# (commit 8df907a, interpreted on the CPU): the causal, equal-heads case
# is the same arithmetic in the same order.
_BEFORE = {
    "dense": ((2, 256, 4, 32), jnp.float32, False, "f9f535415fbb2a6a"),
    "packed_ragged": ((2, 200, 4, 32), jnp.float32, True,
                      "10fe4e5a66eb3bd4"),
    "bf16_head_of_128": ((1, 256, 2, 128), jnp.bfloat16, False,
                         "730a473a8a198dfb"),
}


def _digest(*arrays):
    import hashlib

    h = hashlib.sha256()
    for a in arrays:
        h.update(np.asarray(a).tobytes())
    return h.hexdigest()[:16]


@pytest.mark.parametrize("case", list(_BEFORE))
def test_equal_heads_causal_case_is_bit_equal_to_what_it_was(case):
    shape, dtype, packed, want = _BEFORE[case]
    b, t, _, _ = shape
    q, k, v, w = (
        jax.random.normal(key, shape, jnp.float32).astype(dtype)
        for key in jax.random.split(jax.random.PRNGKey(42), 4))
    seg = None
    if packed:
        seg = jnp.asarray(np.sort(
            np.random.RandomState(3).randint(1, 4, (b, t)), axis=1))

    def f(q, k, v):
        return flash_attention(q, k, v, True, block_q=128, block_k=128,
                               segment_ids=seg)

    grads = jax.grad(lambda *a: jnp.sum(
        f(*a).astype(jnp.float32) * w.astype(jnp.float32)), (0, 1, 2))(
            q, k, v)
    assert _digest(f(q, k, v), *grads) == want


def test_fused_projection_is_bit_equal_to_what_it_was():
    qkv = jax.random.normal(jax.random.PRNGKey(5), (2, 256, 3 * 4 * 64))
    g = jax.grad(lambda x: jnp.sum(
        pallas_kernels.flash_attention_qkv(x, 4, True) ** 2))(qkv)
    assert _digest(
        pallas_kernels.flash_attention_qkv(qkv, 4, True), g
    ) == "7b257bd8ad5fcadc"


@pytest.mark.parametrize("heads, d, turning", [
    (4, 128, 64), (2, 64, 16), (4, 32, 8), (3, 128, 128)])
def test_rope_kernel_turns_part_of_a_head(heads, d, turning):
    """Tables narrower than half a head turn the first ``turning``
    channels of every head, pair (i, i + turning/2), and leave the rest
    as they were, bit for bit; the transpose turns back."""
    b, t = 2, 70
    x = jax.random.normal(jax.random.PRNGKey(1), (b, t, heads * d))
    angles = jnp.arange(t)[:, None] * (
        1.0 / 10000 ** (jnp.arange(0, turning, 2) / turning))
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    got = pallas_kernels.rope(x, cos, sin, heads).reshape(b, t, heads, d)
    xs = x.reshape(b, t, heads, d)
    first, second = xs[..., :turning // 2], xs[..., turning // 2:turning]
    c, s = cos[:, None], sin[:, None]
    np.testing.assert_allclose(
        got[..., :turning], jnp.concatenate(
            [first * c - second * s, second * c + first * s], axis=-1),
        atol=1e-5)
    np.testing.assert_array_equal(got[..., turning:], xs[..., turning:])
    back = jax.grad(lambda a: jnp.sum(
        pallas_kernels.rope(a, cos, sin, heads) ** 2))(x)
    np.testing.assert_allclose(back, 2 * x, atol=1e-5)
