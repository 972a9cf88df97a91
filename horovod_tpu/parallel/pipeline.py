"""Pipeline parallelism: GPipe-style microbatching over the ``pp`` axis.

SURVEY.md §2.5 lists PP as absent from the reference.  TPU-native
design (the "collective pipeline" of the scaling playbook): every
device holds one stage's parameters, activations circulate one hop per
step with ``lax.ppermute``, and the schedule is a single ``fori_loop``
of M + n − 1 steps — fully static control flow, compiled once.  The
whole pipeline is a differentiable pure function, so ``jax.grad``
through it yields the standard GPipe backward schedule without any
hand-written bubble management; wrap the stage in ``jax.checkpoint`` to
trade recompute for activation memory exactly where GPipe does.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax

from .mesh import PP_AXIS


def pipeline_apply(
    stage_fn: Callable[[jax.Array, jax.Array], jax.Array],
    stage_params,
    microbatches: jax.Array,
    axis: str = PP_AXIS,
    broadcast_outputs: bool = True,
    remat_stage: bool = False,
) -> jax.Array:
    """Run microbatches through the n-stage pipeline.

    Must be called inside ``shard_map`` over ``axis``, with
    ``stage_params`` already sharded so each device holds ITS stage's
    parameters (e.g. a [n_stages, ...] stacked pytree sharded on dim 0
    and squeezed).  ``microbatches`` is [M, B, ...]; stage activations
    must be shape-preserving ([B, ...] in == out), the usual transformer
    -block invariant.

    Returns [M, B, ...] outputs — on every device when
    ``broadcast_outputs`` (one psum), else valid on the last stage only.

    ``remat_stage=True`` wraps the stage in ``jax.checkpoint`` so the
    backward recomputes each stage invocation's *internal*
    intermediates instead of storing them — the per-step stage inputs
    (the loop carry) are still saved by the scan backward, so memory
    remains linear in the schedule length; what shrinks is the
    per-step constant (roughly the stage's intermediates-to-input
    ratio, ~an order of magnitude for a transformer block).
    """
    if remat_stage:
        stage_fn = jax.checkpoint(stage_fn)
    from .. import xir

    n = lax.axis_size(axis)
    stage = lax.axis_index(axis)
    m = microbatches.shape[0]
    shift = [(j, (j + 1) % n) for j in range(n)]

    def _hop(y):
        # The stage-to-stage activation hop through the exchange IR:
        # the interpreter emits lax.ppermute on the dense wire; the
        # hop's bytes land in the PIPELINE_EXCHANGE lane + kind-labeled
        # gauges, with the DCN share computed from which (src, dst)
        # pairs cross a slice boundary.
        op = xir.permute(
            axis, shift, wire=xir.wire_request(),
            nbytes=y.size * y.dtype.itemsize, dtype=y.dtype,
        )
        return xir.execute(xir.program("pipeline", [op]), [y],
                           axis_size=n)[0]

    # pcast marks the loop state device-varying so the fori_loop carry
    # type matches its (varying, post-ppermute) outputs under shard_map.
    act0 = lax.pcast(
        jnp.zeros_like(microbatches[0]), (axis,), to="varying"
    )
    out0 = lax.pcast(
        jnp.zeros((m,) + microbatches.shape[1:], microbatches.dtype),
        (axis,), to="varying",
    )

    def step(s, carry):
        act, out = carry
        # Stage 0 ingests microbatch s (clipped: steps ≥ M feed a dummy
        # that never reaches the output window); later stages consume
        # the activation ppermuted from their predecessor.
        x_in = lax.dynamic_index_in_dim(
            microbatches, jnp.clip(s, 0, m - 1), 0, keepdims=False
        )
        inp = jnp.where(stage == 0, x_in, act)
        y = stage_fn(stage_params, inp)
        # The last stage finishes microbatch s-(n-1) at step s.
        out_idx = jnp.clip(s - (n - 1), 0, m - 1)
        prev = lax.dynamic_index_in_dim(out, out_idx, 0, keepdims=False)
        write = jnp.logical_and(stage == n - 1, s >= n - 1)
        out = lax.dynamic_update_index_in_dim(
            out, jnp.where(write, y.astype(out.dtype), prev), out_idx, 0
        )
        act = _hop(y)
        return act, out

    _, out = lax.fori_loop(0, m + n - 1, step, (act0, out0))
    if broadcast_outputs:
        out = lax.psum(jnp.where(stage == n - 1, out, jnp.zeros_like(out)), axis)
    return out
