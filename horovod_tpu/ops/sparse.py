"""Sparse (indexed-slices) gradient collectives.

Reference: sparse gradients are allreduced as an *allgather of slices* —
``horovod/tensorflow/__init__.py:95-162`` (``tf.IndexedSlices`` branch:
allgather values + allgather indices, divide by size for Average) and
``horovod/torch/optimizer.py`` (``sparse_as_dense`` knob densifying
up front).  Embedding-heavy models touch a tiny fraction of the table
per step; gathering only the touched rows moves O(touched) bytes
instead of O(table).

TPU-first shape discipline: XLA needs static shapes, so an
:class:`IndexedSlices` carries a *fixed row capacity* (``nnz`` rows,
padding rows flagged by a negative index convention is avoided —
padding uses index 0 with zero values, which scatter-adds to a no-op).
``dense_grad_to_indexed_slices`` builds one from a dense embedding
gradient plus the batch's token ids (the JAX-native way to recover
sparsity, since JAX gradients are dense pytrees by construction).
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ..runtime import WORLD_AXIS
from ..process_sets import ProcessSet
from . import traced


class IndexedSlices(NamedTuple):
    """A sparse slab of rows of a larger dense tensor.

    ``values[i]`` is the row at ``indices[i]`` of a dense tensor of
    shape ``dense_shape``.  Duplicate indices mean contributions that
    sum (tf.IndexedSlices semantics).  Padding entries use index 0 with
    all-zero values.
    """

    indices: jax.Array            # (nnz,) int32
    values: jax.Array             # (nnz, *row_dims)
    dense_shape: Tuple[int, ...]  # static


def _flatten(s: IndexedSlices):
    return (s.indices, s.values), s.dense_shape


def _unflatten(dense_shape, children):
    return IndexedSlices(children[0], children[1], dense_shape)


jax.tree_util.register_pytree_node(IndexedSlices, _flatten, _unflatten)


def dense_grad_to_indexed_slices(
    dense_grad: jax.Array, ids: jax.Array, nnz: int
) -> IndexedSlices:
    """Extract the touched rows of a dense embedding gradient.

    ``ids`` are the token ids of the local batch (any shape); ``nnz``
    is the static row capacity (>= number of distinct ids; extra slots
    become no-op padding — ``nnz = ids.size`` is always safe).
    Deduplicates ids so each touched row is extracted exactly once —
    the dense gradient row already holds the *sum* over occurrences, so
    duplicates would double-count on densify.

    Capacity overflow (more distinct ids than ``nnz``) cannot be
    represented with static shapes; rather than silently dropping
    gradient rows, the values are poisoned to NaN so the
    misconfiguration surfaces on the first loss/update.  When
    ``nnz >= ids.size`` overflow is impossible and no check is traced.
    """
    flat = ids.reshape(-1).astype(jnp.int32)
    if nnz >= flat.shape[0]:
        uids = jnp.unique(flat, size=nnz, fill_value=-1)
    else:
        ext = jnp.unique(flat, size=nnz + 1, fill_value=-1)
        uids = ext[:nnz]
        overflow = ext[nnz] >= 0  # an (nnz+1)-th distinct id exists
        uids = jnp.where(overflow, jnp.full_like(uids, -1), uids)
    mask = uids >= 0
    safe = jnp.where(mask, uids, 0)
    values = dense_grad[safe] * mask.astype(dense_grad.dtype)[
        (...,) + (None,) * (dense_grad.ndim - 1)
    ]
    if nnz < flat.shape[0]:
        values = jnp.where(overflow, jnp.nan, values.astype(values.dtype))
    return IndexedSlices(safe, values, tuple(dense_grad.shape))


def densify(s: IndexedSlices) -> jax.Array:
    """Scatter-add the slices into the dense tensor."""
    out = jnp.zeros(s.dense_shape, s.values.dtype)
    return out.at[s.indices].add(s.values)


def _routed_gather(s: IndexedSlices, axis, process_set):
    """The embedding exchange through the exchange IR: one
    ``gather_dense_from_sparse`` op (allgather of indices + values).
    The interpreter emits a ``traced.allgather`` pair on the dense
    wire; a bf16 ``HVD_TPU_XIR_WIRE`` request casts only the
    values leg, indices always ride dense int wire.  The exchange gains
    the SPARSE_EMBED_EXCHANGE timeline lane, kind-labeled byte gauges,
    and a persistent-store key."""
    from .. import xir

    op = xir.gather_dense_from_sparse(
        axis, wire=xir.wire_request(),
        set_ranks=(tuple(process_set.ranks)
                   if process_set is not None else None),
        nbytes=s.values.size * s.values.dtype.itemsize,
        dtype=s.values.dtype,
    )
    return xir.execute(
        xir.program("sparse_embed", [op]), [(s.indices, s.values)],
        process_set=process_set,
    )[0]


def sparse_allreduce(
    s: IndexedSlices,
    axis=WORLD_AXIS,
    op: int = traced.Average,
    process_set: Optional[ProcessSet] = None,
) -> IndexedSlices:
    """Allreduce-by-allgather-of-slices (in-jit, SPMD).

    Matches the reference lowering exactly
    (``tensorflow/__init__.py:123-162``): allgather the values and the
    indices; ``Average`` divides the values by the set size.  The result
    has ``nnz * set_size`` rows — duplicate indices across ranks stay
    duplicated and sum on :func:`densify`, like concatenated
    IndexedSlices.
    """
    if op not in (traced.Average, traced.Sum):
        raise ValueError("sparse_allreduce supports op=Average or Sum")
    idx, vals = _routed_gather(s, axis, process_set)
    if op == traced.Average:
        if process_set is not None:
            denom = len(process_set.ranks)
        else:
            denom = lax.psum(1, axis)
        vals = (vals.astype(jnp.float32) / denom).astype(s.values.dtype)
    return IndexedSlices(idx, vals, s.dense_shape)


def sparse_allreduce_eager(
    s: IndexedSlices,
    average: bool = True,
    process_set: Optional[ProcessSet] = None,
    name: Optional[str] = None,
) -> IndexedSlices:
    """Eager stacked-layout sparse allreduce (reference
    ``torch/mpi_ops.py`` ``sparse_allreduce_async``).

    ``indices``: (size, nnz); ``values``: (size, nnz, *row).  Every rank
    row of the result carries all ``size * nnz`` gathered slices.
    """
    from . import eager

    idx = eager.allgather(s.indices, process_set=process_set, name=name)
    vals = eager.allgather(s.values, process_set=process_set, name=name)
    if average:
        denom = (
            len(process_set.ranks) if process_set is not None
            else idx.shape[0]
        )
        vals = vals / denom
    return IndexedSlices(idx, vals, s.dense_shape)
