"""One tile of the routed experts' grouped product (``parallel/moe.py``)
as a pair of Pallas kernels, forward and a hand-written backward: the
tile's rows ``[tile, d]`` through its expert's SwiGLU,

    y = w * (silu(x Wg) * (x Wu)) Wd,

with the expert's matrices read **in place** in the float32 parameters
``wg, wu [n, d, f]`` and ``wd [n, f, d]``: the expert is a prefetched
scalar that the block specs index by, a grid step owns one block of the
hidden width f, casts its three float32 blocks to the rows' type in VMEM
and multiplies; a, u and h never reach HBM and no copy of any expert is
made.  Matmul operands are the rows' type, sums float32, SwiGLU float32, h
rounded to the rows' type before the down product: ``parallel/moe.py``'s
arithmetic as it was.

The backward makes a, u and h again a block of f at a time, and from them
the rows' gradient, the routing weights' (``sum((dy Wdᵀ) * h)``: no second
``h Wd``) and the three matrices', **float32 sums in the expert's blocks
of ``[n, d, f]`` accumulators** passed through the call
(``input_output_aliases``): an expert's first tile writes its blocks, a
later one fetches them and adds, the other experts' blocks are not
touched, and nothing is rounded on the way to a float32 parameter.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_kernels as pk

_F32 = jnp.float32
LANES = pk._LANES
# Hidden channels a grid step owns, and VMEM for what the body makes beside
# its blocks.  One expert's three float32 matrices are 12.6 and 23.6 MB in
# the benchmark's cells and the pipeline holds a block twice; what a call
# is not given, XLA keeps for the [S, d] arrays the loop around it gathers
# from and scatters into (``_params``).
_F_BLOCK = 128
_BODY = 6 << 20


def takes(d: int, f: int) -> bool:
    """Whether the kernels take experts ``d`` wide with ``f`` hidden
    channels: any widths where Pallas is interpreted, whole 128-lane slabs
    on the chip."""
    return pk._interpret() or (d % LANES == 0 and f % LANES == 0)


def _f_block(f: int) -> int:
    """Hidden channels a grid step owns: ``_F_BLOCK`` where f is whole
    128-lane slabs (all of f where Pallas is interpreted and it is not)."""
    return _F_BLOCK if f % LANES == 0 else f


def _dot(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, preferred_element_type=_F32)


def _blocks(wg_ref, wu_ref, wd_ref, dtype):
    """The expert's blocks of this grid step in the rows' type: Wg, Wu
    [d, fb] and Wd [fb, d]."""
    return (wg_ref[0].astype(dtype), wu_ref[0].astype(dtype),
            wd_ref[0].astype(dtype))


def _forward_kernel(s_ref, x_ref, w_ref, wg_ref, wu_ref, wd_ref, y_ref):
    del s_ref  # the block specs' index
    step = pl.program_id(0)
    x = x_ref[...]
    wg, wu, wd = _blocks(wg_ref, wu_ref, wd_ref, x.dtype)
    h = (jax.nn.silu(_dot(x, wg)) * _dot(x, wu)).astype(x.dtype)
    part = _dot(h, wd)

    @pl.when(step == 0)
    def _():
        y_ref[...] = part

    @pl.when(step > 0)
    def _():
        y_ref[...] += part

    @pl.when(step == pl.num_programs(0) - 1)
    def _():
        y_ref[...] *= w_ref[...]


def _backward_kernel(s_ref, x_ref, dy_ref, w_ref, wg_ref, wu_ref, wd_ref,
                     dwg_hbm, dwu_hbm, dwd_hbm,
                     dx_ref, dw_ref, dwg_ref, dwu_ref, dwd_ref,
                     up_ref, down_ref, arrived):
    e, first = s_ref[0], s_ref[1]
    step = pl.program_id(0)
    x, dy, w = x_ref[...], dy_ref[...], w_ref[...]
    dtype = x.dtype
    wg, wu, wd = _blocks(wg_ref, wu_ref, wd_ref, dtype)
    a, u = _dot(x, wg), _dot(x, wu)
    sig = jax.nn.sigmoid(a)
    act = a * sig
    h = act * u
    through = _dot(dy, wd, pk._NT)                    # dy Wdᵀ, [tile, fb]
    dw = jnp.sum(through * h.astype(dtype).astype(_F32), axis=1,
                 keepdims=True)
    dh = through * w
    da = (dh * u * (sig + act * (1.0 - sig))).astype(dtype)
    du = (dh * act).astype(dtype)
    dx = _dot(da, wg, pk._NT) + _dot(du, wu, pk._NT)

    @pl.when(step == 0)
    def _():
        dx_ref[...] = dx
        dw_ref[...] = dw

    @pl.when(step > 0)
    def _():
        dx_ref[...] += dx
        dw_ref[...] += dw

    sums = (_dot(x, da, pk._TN), _dot(x, du, pk._TN),
            _dot((h * w).astype(dtype), dy, pk._TN))

    @pl.when(first == 1)
    def _():  # the expert's first tile starts its sums
        dwg_ref[0], dwu_ref[0], dwd_ref[0] = sums

    @pl.when(first == 0)
    def _():  # a later one fetches what the earlier tiles left
        fb = wg_ref.shape[-1]
        lanes = pl.ds(pl.multiple_of(step * fb, fb), fb)
        copies = [
            pltpu.make_async_copy(
                dwg_hbm.at[e, :, lanes], up_ref.at[0], arrived.at[0]),
            pltpu.make_async_copy(
                dwu_hbm.at[e, :, lanes], up_ref.at[1], arrived.at[1]),
            pltpu.make_async_copy(
                dwd_hbm.at[e, lanes, :], down_ref, arrived.at[2])]
        for copy in copies:
            copy.start()
        for copy in copies:
            copy.wait()
        dwg_ref[0] = up_ref[0] + sums[0]
        dwu_ref[0] = up_ref[1] + sums[1]
        dwd_ref[0] = down_ref[...] + sums[2]


def _specs(tile: int, d: int, f: int):
    """(hidden blocks, the block spec of a tile's [tile, d] rows, of its
    [tile, 1] weights, of an expert's block of [n, d, f], of [n, f, d])."""
    fb = _f_block(f)
    return (f // fb,
            pl.BlockSpec((tile, d), lambda s, e: (0, 0)),
            pl.BlockSpec((tile, 1), lambda s, e: (0, 0)),
            pl.BlockSpec((1, d, fb), lambda s, e: (e[0], 0, s)),
            pl.BlockSpec((1, fb, d), lambda s, e: (e[0], s, 0)))


def _params(tile: int, d: int, f: int, rows: int, arrays: int):
    """The hidden blocks in order (the rows' outputs are sums over them),
    and no more VMEM than a grid step holds: ``rows`` bytes a row element
    of the tile's arrays in and out and ``arrays`` float32 blocks of the
    expert's matrices, each twice for the pipeline, and ``_BODY``.  XLA
    keeps the loop's [S, d] arrays in VMEM across the calls; asked for more
    than they leave, it moves one out and in again around every call, 33 MB
    each way (PERF.md, PR 35)."""
    held = 2 * (tile * d * rows + arrays * d * _f_block(f) * 4)
    return pltpu.CompilerParams(
        dimension_semantics=("arbitrary",),
        vmem_limit_bytes=held + _BODY)


def tile_forward(e, x, w, wg, wu, wd):
    """``w * E_e(x)`` [tile, d] float32 of the tile's rows x [tile, d], their
    routing weights w [tile, 1] float32 and expert ``e`` (an int32 scalar)
    of wg, wu [n, d, f], wd [n, f, d]."""
    tile, d = x.shape
    f = wg.shape[-1]
    steps, rows, column, up, down = _specs(tile, d, f)
    return pl.pallas_call(
        _forward_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[rows, column, up, up, down], out_specs=rows),
        out_shape=pk._sds((tile, d), _F32, x),
        compiler_params=_params(tile, d, f, x.dtype.itemsize + 4, 3),
        interpret=pk._interpret(),
    )(e.reshape(1), x, w, wg, wu, wd)


def tile_backward(e, first, x, dy, w, wg, wu, wd, dwg, dwu, dwd):
    """The tile's gradients: (dx [tile, d] float32, the routing weights'
    [tile, 1], and ``dwg, dwu, dwd`` with expert e's blocks added to, or
    written where the tile is the expert's ``first``: whatever they held
    is then never read); ``dy`` [tile, d] in x's type is the cotangent of
    the rows' outputs before their weights."""
    tile, d = x.shape
    f = wg.shape[-1]
    steps, rows, column, up, down = _specs(tile, d, f)
    fb = f // steps
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pl.pallas_call(
        _backward_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(steps,),
            in_specs=[rows, rows, column, up, up, down] + [anywhere] * 3,
            out_specs=[rows, column, up, up, down],
            scratch_shapes=[pltpu.VMEM((2, d, fb), _F32),
                            pltpu.VMEM((fb, d), _F32),
                            pltpu.SemaphoreType.DMA((3,))]),
        out_shape=[pk._sds((tile, d), _F32, x), pk._sds((tile, 1), _F32, x),
                   pk._sds(dwg.shape, _F32, x), pk._sds(dwu.shape, _F32, x),
                   pk._sds(dwd.shape, _F32, x)],
        # the scalars count: operand 7 is dwg
        input_output_aliases={7: 2, 8: 3, 9: 4},
        compiler_params=_params(tile, d, f, 2 * x.dtype.itemsize + 4, 8),
        interpret=pk._interpret(),
    )(jnp.stack([e, first.astype(jnp.int32)]), x, dy, w, wg, wu, wd,
      dwg, dwu, dwd)
