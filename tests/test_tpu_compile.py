"""The Pallas kernels of the training path, compiled for a v5e chip that
is described and not attached.

Interpret mode (every other kernel test) cannot see what Mosaic refuses:
a block that does not fit the tiling, a dynamic index it cannot lower,
more VMEM than a kernel may use.  The TPU compiler is installed here, so
these compile at the benchmark's widths in a second or two each; nothing
runs, and no time is taken.  The topology is described inside a fixture
and in this file only: one process may hold the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from horovod_tpu.ops import pallas_kernels


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def mosaic(monkeypatch):
    """The kernels as the chip gets them, not interpreted."""
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.setattr(pallas_kernels, "_interpret", lambda: False)
    # the jitted wrappers (_flash_backward, _rope_call) keep what they
    # traced by shapes alone: nothing interpreted may be found again here,
    # and nothing of Mosaic's by the interpreted tests after
    jax.clear_caches()
    # an executable for a described chip cannot be read back from the
    # persistent cache without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile_grad(one_chip, shape, dtype, causal, packed):
    x = jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    seg = jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=one_chip)

    def grads(q, k, v, w, seg):
        def loss(q, k, v):
            out = pallas_kernels.flash_attention(
                q, k, v, causal, segment_ids=seg if packed else None)
            return jnp.sum(out.astype(jnp.float32) * w)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    return jax.jit(grads).lower(x, x, x, x, seg).compile()


@pytest.mark.parametrize("shape, dtype, causal, packed", [
    # gpt2s.dense / gpt2s.dp4 and gpt2s.packed, a chip's share
    ((16, 1024, 12, 64), jnp.bfloat16, True, False),
    ((16, 1024, 12, 64), jnp.bfloat16, True, True),
    ((2, 1024, 4, 128), jnp.bfloat16, False, False),
    # ragged: five 128-blocks, the padding's mask with the segments'
    ((2, 600, 4, 64), jnp.bfloat16, True, True),
    # one tile shorter than a lane row; float32 throughout
    ((2, 100, 4, 64), jnp.float32, True, False),
    # a long sequence: one head's q, do and dq stay in VMEM
    ((1, 16384, 2, 64), jnp.bfloat16, True, False),
    # ouro26b.ring2x4096: heads of 128 at 4096 tokens, dense and packed
    ((2, 4096, 16, 128), jnp.bfloat16, True, False),
    ((2, 4096, 16, 128), jnp.bfloat16, True, True),
])
def test_flash_attention_grad_compiles_for_the_chip(
        one_chip, mosaic, shape, dtype, causal, packed):
    compiled = _compile_grad(one_chip, shape, dtype, causal, packed)
    calls = [line for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line]
    # the forward kernel and the backward kernel, and no loop around them
    assert len(calls) == 2
    assert sum("flash_bwd_dq_dkv/pallas_call" in c for c in calls) == 1
    assert " while(" not in compiled.as_text()
    b, t, h, d = shape
    scores = b * h * t * t * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores / 2


def _entry(text):
    """The instructions of a compiled module's entry computation."""
    return text[text.index("\nENTRY "):].splitlines()


def _result_and_opcode(line):
    """("bf16[2,8]{1,0}", "copy") of "%copy.1 = bf16[2,8]{1,0} copy(%p)"."""
    head = line.split(" = ", 1)[-1].split("(")[0].split(" ")
    return head[0], head[-1]


@pytest.mark.parametrize("packed", [False, True])
def test_fused_projection_passes_to_the_kernels_without_a_copy(
        one_chip, mosaic, packed):
    """gpt2s: both kernels take the [B, T, 3·H·D] projection as it is,
    three times, and give [B, T, H·D] arrays; on the way XLA copies,
    transposes and cuts nothing (the cotangents' concatenation is what
    is left of the glue)."""
    b, t, h, d = 16, 1024, 12, 64
    qkv = jax.ShapeDtypeStruct((b, t, 3 * h * d), jnp.bfloat16,
                               sharding=one_chip)
    w = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)

    def grad(qkv, w, seg):
        return jax.grad(lambda x: jnp.sum(
            pallas_kernels.flash_attention_qkv(
                x, h, True, segment_ids=seg if packed else None
            ).astype(jnp.float32) * w))(qkv)

    entry = _entry(jax.jit(grad).lower(qkv, w, seg).compile().as_text())
    calls = [ln for ln in entry if "tpu_custom_call" in ln]
    assert len(calls) == 2
    for call in calls:
        operands = call.split("custom-call(")[1].split(")")[0].split(", ")
        assert operands[0] == operands[1] == operands[2]
        assert f"bf16[{b},{t},{3 * h * d}]{{2,1,0}}" in call.split(
            "operand_layout_constraints=")[1]
    for ln in entry:
        assert _result_and_opcode(ln)[1] not in (
            "copy", "transpose", "slice"), ln


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        else:
            for sub in jax.core.jaxprs_in_params(eqn.params):
                yield from _pallas_calls(sub)


@pytest.mark.parametrize("packed", [False, True])
@pytest.mark.parametrize("shape", [
    (16, 1024, 12, 64),  # gpt2s.dense / gpt2s.dp4 / gpt2s.packed
    (2, 4096, 16, 128),  # ouro26b.ring2x4096
])
def test_flash_attention_forward_is_one_call_on_a_grid_of_heads(
        one_chip, mosaic, shape, packed):
    """What the benchmark's forward rooflines count on: the forward is
    exactly one Mosaic call, found under ".../attn/pallas_call" and not
    under flash_bwd.  Its grid is (batch, slab of heads): no K-block axis
    whose steps could be empty.  And the row logsumexp leaves it one float32 a
    row: neither an operand nor a result is a float32 array with 128
    lanes of row statistics."""
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct(shape[:2], jnp.int32, sharding=one_chip)

    def forward(q, k, v, seg):
        with jax.named_scope("attn"):
            return pallas_kernels.flash_attention(
                q, k, v, True, segment_ids=seg if packed else None)

    call, = _pallas_calls(jax.make_jaxpr(forward)(x, x, x, seg).jaxpr)
    slabs = shape[2] // pallas_kernels._slab_heads(*shape[2:])
    assert call.params["grid_mapping"].grid == (shape[0], slabs)

    text = jax.jit(forward).lower(x, x, x, seg).compile().as_text()
    line, = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert "attn/pallas_call" in line and "flash_bwd" not in line
    signature = line.split("custom_call_target")[0]
    statistics = re.findall(r"f32\[([\d,]+)\]", signature)
    assert statistics  # the logsumexp, [B, H, n, 1, block]
    for dims in statistics:
        assert not dims.endswith(",128"), dims
    b, t, h, _ = shape
    assert f"f32[{b},{h},{t // 512},1,512]" in signature


@pytest.mark.parametrize("shape, per_row", [
    ((2, 4096, 16, 128), False),  # ouro26b.ring2x4096: a head a slab
    ((2, 4096, 16, 128), True),   # a table a row, as packed rows have
    ((16, 1024, 12, 64), False),  # a pair of heads a slab: two turns
    ((2, 600, 4, 32), False),     # four heads a slab, ragged T
])
def test_rope_compiles_for_the_chip(one_chip, mosaic, shape, per_row):
    """The rotary kernel and its transpose on [B, T, H·D]: two Mosaic
    calls under a scope of their own, and XLA copies nothing around
    them."""
    b, t, h, d = shape
    x = jax.ShapeDtypeStruct((b, t, h * d), jnp.bfloat16, sharding=one_chip)
    table = jax.ShapeDtypeStruct(
        ((b,) if per_row else ()) + (t, d // 2), jnp.float32,
        sharding=one_chip)

    def grad(x, w, cos, sin):
        with jax.named_scope("attn"):
            return jax.value_and_grad(lambda x: jnp.sum(
                pallas_kernels.rope(x, cos, sin, h).astype(jnp.float32)
                * w))(x)

    entry = _entry(jax.jit(grad).lower(x, x, table, table).compile().as_text())
    calls = [ln for ln in entry if "tpu_custom_call" in ln]
    assert len(calls) == 2
    for call in calls:  # not the flash forward's ".../attn/pallas_call"
        scope, = re.findall(r'op_name="([^"]*)"', call)
        assert scope.endswith("/rope/pallas_call"), scope
        assert "attn/pallas_call" not in scope and "flash_bwd" not in scope
    for ln in entry:  # of x, its gradient or the result
        result, opcode = _result_and_opcode(ln)
        assert opcode not in ("copy", "transpose") or (
            f"{t},{h * d}]" not in result), ln


def test_scale_buffer_compiles_for_the_chip(one_chip, mosaic):
    x = jax.ShapeDtypeStruct((1 << 20,), jnp.float32, sharding=one_chip)
    compiled = jax.jit(
        lambda a: pallas_kernels.scale_buffer(a, 0.5, jnp.bfloat16)
    ).lower(x).compile()
    assert "scale_cast/pallas_call" in compiled.as_text()


@pytest.mark.parametrize("shape, dtype, packed", [
    # ling3flash.ring1x4096: 32 heads of 128, a head one 128-lane slab
    ((1, 4096, 32, 128), jnp.bfloat16, False),
    ((1, 4096, 32, 128), jnp.bfloat16, True),
    # ragged T, heads of two slabs, float32 operands
    ((2, 150, 4, 256), jnp.float32, True),
])
def test_delta_rule_grad_compiles_for_the_chip(
        one_chip, mosaic, shape, dtype, packed):
    """The delta rule's kernel pair (``ops/kda_kernels.py``) on the
    projections' [B, T, H·d]: a gradient is the forward that keeps the
    states and the backward, two Mosaic calls, with no copy or transpose
    of an operand between the arguments and the calls, and the only
    temporaries what the backward keeps (a state and an inverse a chunk
    and head) beside o's cotangent."""
    from horovod_tpu.ops import kda, kda_kernels

    b, t, h, d = shape
    wide = jax.ShapeDtypeStruct((b, t, h * d), dtype, sharding=one_chip)
    gate = jax.ShapeDtypeStruct((b, t, h * d), jnp.float32, sharding=one_chip)
    beta = jax.ShapeDtypeStruct((b, t, h), jnp.float32, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)
    assert kda_kernels.takes(d) and not kda_kernels.takes(64)

    def grads(q, k, v, g, beta, w, seg):
        def loss(*operands):
            return jnp.sum(kda.kda(*operands, seg if packed else None) * w)

        return jax.grad(loss, argnums=range(5))(q, k, v, g, beta)

    compiled = jax.jit(grads).lower(
        wide, wide, wide, gate, beta, gate, seg).compile()
    text = compiled.as_text()
    assert len([ln for ln in text.splitlines()
                if "tpu_custom_call" in ln]) == 2
    assert " while(" not in text
    padded = -(-t // kda_kernels.CHUNK) * kda_kernels.CHUNK
    for ln in _entry(text):
        result, opcode = _result_and_opcode(ln)
        if opcode in ("copy", "transpose") and padded == t:
            assert f"{t},{h * d}]" not in result, ln
    chunks = padded // kda_kernels.CHUNK
    kept = b * chunks * h * (d * d + 64 * 128) * 4
    assert compiled.memory_analysis().temp_size_in_bytes < \
        kept + 3 * b * padded * h * d * 4


@pytest.mark.parametrize("shape, dtype", [
    ((1, 4096, 32, 128), jnp.bfloat16),  # ling3flash.ring1x4096
    ((2, 300, 4, 256), jnp.float32),     # ragged T, heads of two slabs
])
def test_head_norms_compile_for_the_chip(one_chip, mosaic, shape, dtype):
    """The per-head norms around the delta rule on [B, T, H·d]: q's L2
    norm and the output's RMSNorm and gate, each a kernel forward and one
    backward, with no copy of a [T, H·d] array beside them."""
    from horovod_tpu.ops import kda

    b, t, h, d = shape
    wide = jax.ShapeDtypeStruct((b, t, h * d), jnp.float32, sharding=one_chip)
    weight = jax.ShapeDtypeStruct((d,), jnp.float32, sharding=one_chip)
    gate = jax.ShapeDtypeStruct((b, t, h), jnp.float32, sharding=one_chip)

    def grads(x, o, weight, gate):
        def loss(x, o, weight, gate):
            q = kda.unit_heads(x, h, d ** -0.5, dtype)
            y = kda.rms_gate_heads(o, weight, gate, 1e-6, dtype)
            return jnp.sum((q * y).astype(jnp.float32))

        return jax.grad(loss, argnums=range(4))(x, o, weight, gate)

    text = jax.jit(grads).lower(wide, wide, weight, gate).compile().as_text()
    assert len([ln for ln in text.splitlines()
                if "tpu_custom_call" in ln]) == 4
    for ln in _entry(text):
        result, opcode = _result_and_opcode(ln)
        if opcode in ("copy", "transpose"):
            assert f"{t},{h * d}]" not in result, ln


@pytest.mark.parametrize("heads, kv_heads, window, packed", [
    # laguna_xs2.ring1x8192: a sliding layer, 64 query heads over 8
    (64, 8, 512, False),
    # its full layers, 48 over 8
    (48, 8, None, False),
    # packed rows under a window
    (64, 8, 512, True),
])
def test_window_and_grouped_heads_compile_for_the_chip(
        one_chip, mosaic, heads, kv_heads, window, packed):
    """Both kernels at the window/full-attention cell's widths, one row of
    8192 tokens: k and v enter at their own 8 heads (no array of them at
    the query heads' width is an operand), a grid step owns one query
    head, and the forward's grid has no K-block axis."""
    b, t, d = 1, 8192, 128
    q = jax.ShapeDtypeStruct((b, t, heads, d), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, t, kv_heads, d), jnp.bfloat16,
                              sharding=one_chip)
    seg = jax.ShapeDtypeStruct((b, t), jnp.int32, sharding=one_chip)

    def grads(q, k, v, w, seg):
        def loss(q, k, v):
            out = pallas_kernels.flash_attention(
                q, k, v, True, segment_ids=seg if packed else None,
                window=window)
            return jnp.sum(out.astype(jnp.float32) * w)

        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    compiled = jax.jit(grads).lower(q, kv, kv, q, seg).compile()
    text = compiled.as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    assert sum("flash_bwd_dq_dkv/pallas_call" in c for c in calls) == 1
    for call in calls:
        operands = call.split("custom-call(")[1].split(")")[0].split(", ")
        assert len(operands) >= 3
        constraints = call.split("operand_layout_constraints=")[1]
        assert constraints.count(f"bf16[{b},{t},{kv_heads * d}]") >= 2
    assert " while(" not in text
    scores = b * heads * t * t * 4
    assert compiled.memory_analysis().temp_size_in_bytes < scores / 16


@pytest.mark.parametrize("heads, turning", [(48, 64), (64, 128), (8, 64)])
def test_rope_of_a_wide_array_fits_the_kernels_memory(
        one_chip, mosaic, heads, turning):
    """The window/full-attention cell's q (6144 and 8192 lanes) and k
    (1024): a block of rows is cut to what a kernel's VMEM holds, half a
    head turns where the tables are narrower than it."""
    b, t, d = 1, 8192, 128
    x = jax.ShapeDtypeStruct((b, t, heads * d), jnp.bfloat16,
                             sharding=one_chip)
    table = jax.ShapeDtypeStruct((t, turning // 2), jnp.float32,
                                 sharding=one_chip)

    def grad(x, w, cos, sin):
        return jax.value_and_grad(lambda x: jnp.sum(
            pallas_kernels.rope(x, cos, sin, heads).astype(jnp.float32)
            * w))(x)

    text = jax.jit(grad).lower(x, x, table, table).compile().as_text()
    calls = [ln for ln in _entry(text) if "tpu_custom_call" in ln]
    assert len(calls) == 2
    for ln in _entry(text):  # of x, its gradient or the result
        result, opcode = _result_and_opcode(ln)
        assert opcode not in ("copy", "transpose") or (
            f"{t},{heads * d}]" not in result), ln


@pytest.mark.parametrize("tokens, router, held, d, f", [
    # laguna_xs2.ring1x8192: 32 of 256 experts held, tiles of 512 rows
    (8192, 256, 32, 2048, 512),
    # ling3flash.ring1x4096: 8 of 512, tiles of 256 rows
    (4096, 512, 8, 2560, 768),
])
def test_routed_experts_tile_compiles_for_the_chip(
        one_chip, mosaic, tokens, router, held, d, f):
    """The grouped product's kernel pair (``ops/expert_kernels.py``) at
    the cells' widths, rows in bfloat16 and the experts' matrices float32
    as the parameters are: a gradient is two loops of one Mosaic call
    each; no whole ``[n, d, f]`` matrix is converted or copied on the way
    in (the kernel casts the block it reads) or out (the float32 sums are
    the gradients), and the loops' carried sums are updated in place."""
    from horovod_tpu.ops import expert_kernels
    from horovod_tpu.parallel import moe

    k = 8
    sds = lambda shape, dtype: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)
    up, down = sds((held, d, f), jnp.float32), sds((held, f, d), jnp.float32)
    assert expert_kernels.takes(d, f) and not expert_kernels.takes(d, 24)
    tile = moe.tile_rows(tokens * k / router)

    def grads(x, weights, wg, wu, wd, local, cot):
        return jax.value_and_grad(lambda *a: jnp.sum(moe.grouped_experts(
            *a, local, tile) * cot), argnums=range(5))(x, weights, wg, wu, wd)

    compiled = jax.jit(grads).lower(
        sds((tokens, d), jnp.bfloat16), sds((tokens, k), jnp.float32),
        up, up, down, sds((tokens, k), jnp.int32),
        sds((tokens, d), jnp.float32)).compile()
    text = compiled.as_text()
    assert len([ln for ln in text.splitlines()
                if "tpu_custom_call" in ln]) == 2
    assert len([ln for ln in text.splitlines() if " while(" in ln]) == 2
    whole = (f"[{held},{d},{f}]", f"[{held},{f},{d}]")
    for ln in text.splitlines():
        if " = " not in ln:
            continue
        result, opcode = _result_and_opcode(ln)
        if opcode in ("copy", "convert", "transpose"):
            assert not any(shape in result for shape in whole), ln
    # the three gradients (results) and nothing else of their size
    assert compiled.memory_analysis().temp_size_in_bytes < held * d * f * 4
