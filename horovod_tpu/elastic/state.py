"""Elastic state: in-memory checkpoint + cross-process sync.

Reference: ``horovod/common/elastic.py:26-148`` (State/ObjectState) and
the per-framework subclasses (``horovod/torch/elastic/state.py``,
``tensorflow/elastic.py``).  A State owns everything that must survive a
membership change: ``commit()`` snapshots to host memory, ``restore()``
rolls back after a failure, ``sync()`` re-broadcasts from the new rank 0
after a re-rendezvous.
"""

from __future__ import annotations

import copy
from typing import Any, Dict, Optional

import jax

from .. import functions, runtime
from ..exceptions import HostsUpdatedInterrupt, RemeshInterrupt


class State:
    """Base elastic state (reference ``common/elastic.py:26``)."""

    def __init__(self, **kwargs):
        self._host_messages: list = []
        self._reset_callbacks: list = []
        self._known_hosts: Optional[frozenset] = None
        self._remesh_request = None
        self._sharded: Dict[str, Any] = {}
        self._commit_count = 0
        self._tenant_placement: Optional[Dict[str, int]] = None

    def register_reset_callbacks(self, callbacks) -> None:
        self._reset_callbacks.extend(callbacks)

    def register_sharded(self, name: str, spec) -> None:
        """Register a sharded-state adapter (e.g.
        :class:`~horovod_tpu.elastic.remesh.ShardedZeroState`) whose
        per-rank shards the in-process remesh must exchange — see
        ``docs/fault_tolerance.md``.  Replicated attributes need no
        registration: ``save()``/``restore()``/``sync()`` already carry
        them across a remesh."""
        self._sharded[name] = spec

    def sharded_attrs(self) -> Dict[str, Any]:
        return dict(self._sharded)

    def on_reset(self) -> None:
        self.reset()
        for cb in self._reset_callbacks:
            cb()

    def on_hosts_updated(self, timestamp, update_res) -> None:
        self._host_messages.append((timestamp, update_res))

    def on_remesh_requested(self, request) -> None:
        """Driver authorized an in-process remesh: the next commit
        boundary raises :class:`RemeshInterrupt` instead of the plain
        restart interrupt (``runner/elastic_worker.py`` poller)."""
        self._remesh_request = request

    def on_placement_updated(self, placement) -> None:
        """An SLO slice handoff changed the tenant→slice placement
        (``runner/slo_consumer.py``).  The arbiter-weight half is
        already enacted by the consumer; the default here just records
        the placement — a state that shards per tenant overrides this
        to reshard at its next commit boundary."""
        self._tenant_placement = dict(placement)

    def commit(self) -> None:
        """Snapshot + check for host changes (reference ``elastic.py:60``).

        In an elastic job the snapshot is also persisted to the launcher
        KV store (rank 0): workers restart across membership rounds on
        TPU (see runner/elastic_driver.py), so host memory alone cannot
        carry state between rounds the way the reference's surviving
        processes do.
        """
        from .. import faults

        self._commit_count += 1
        # Deterministic kill-at-step-boundary site: the fault plan's
        # kill_at_step sugar targets exactly this arrival counter
        # (docs/fault_tolerance.md — seed-reproducible kill-and-resize
        # remesh tests).
        faults.inject("worker.commit", step=self._commit_count)
        self.save()
        self._persist()
        self.check_host_updates()

    def _persist(self) -> None:
        from ..runner import elastic_worker

        mgr = elastic_worker.get_notification_manager()
        if mgr is not None:
            mgr.init()
            blob = self._serialize()
            if blob is not None:
                mgr.save_state_blob(blob)
            elif not getattr(self, "_warned_no_serialize", False):
                self._warned_no_serialize = True
                from ..utils.logging import get_logger

                get_logger().warning(
                    "elastic job with a State that does not serialize: "
                    "progress cannot survive worker restarts — use "
                    "ObjectState/ArrayState or override _serialize()"
                )

    def _load_persisted(self) -> bool:
        """Adopt the previous round's snapshot — only on the FIRST sync
        after process start (later syncs must not roll live progress back
        to the last commit) and only on rank 0 (the subsequent broadcast
        overwrites every other rank anyway)."""
        if getattr(self, "_restore_attempted", False):
            return False
        self._restore_attempted = True
        from ..runner import elastic_worker

        mgr = elastic_worker.get_notification_manager()
        if mgr is None or mgr.rank != 0:
            return False
        mgr.init()
        blob = mgr.load_state_blob()
        if blob is None:
            return False
        return self._deserialize(blob)

    # Serialization hooks for cross-round persistence (subclasses with
    # array state override to host-ify leaves).
    def _serialize(self):
        return None

    def _deserialize(self, blob) -> bool:
        return False

    def check_host_updates(self) -> None:
        """Raise HostsUpdatedInterrupt when membership changed
        (reference ``elastic.py:73-96``) — or :class:`RemeshInterrupt`
        when the driver authorized resharding live state in place
        (``elastic/remesh.py``)."""
        if self._remesh_request is not None:
            req, self._remesh_request = self._remesh_request, None
            self._host_messages.clear()
            raise RemeshInterrupt(req)
        if self._host_messages:
            self._host_messages.clear()
            raise HostsUpdatedInterrupt()

    # Subclass responsibilities (reference elastic.py:99-113):
    def save(self) -> None:
        raise NotImplementedError

    def restore(self) -> None:
        raise NotImplementedError

    def sync(self) -> None:
        raise NotImplementedError

    def reset(self) -> None:
        """Re-initialize the runtime/mesh after membership change."""
        from ..ops import eager

        eager.clear_cache()


class ObjectState(State):
    """Checkpoints arbitrary python attributes (reference
    ``common/elastic.py:116``): attributes passed as kwargs are saved /
    restored / synced by broadcast from rank 0."""

    def __init__(self, **kwargs):
        super().__init__()
        self._saved_state: Dict[str, Any] = dict(kwargs)
        for k, v in kwargs.items():
            setattr(self, k, v)

    def save(self) -> None:
        for k in self._saved_state:
            self._saved_state[k] = copy.deepcopy(getattr(self, k))

    def restore(self) -> None:
        for k, v in self._saved_state.items():
            setattr(self, k, copy.deepcopy(v))

    def sync(self) -> None:
        # Deliberate deviation: broadcast *live* attribute values from
        # rank 0.  The reference broadcasts the last-saved snapshot, but
        # its commit() saves before checking for host updates, so
        # saved == live at every interrupt point; saving first here is
        # equivalent there and additionally avoids rolling back progress
        # when sync() is reached outside a commit boundary.
        if self._saved_state:
            # Fresh elastic round: adopt the persisted snapshot from the
            # previous round, if any, before broadcasting.
            self._load_persisted()
            self.save()
            synced = functions.broadcast_object(self._saved_state, root_rank=0)
            for k, v in synced.items():
                self._saved_state[k] = v
                setattr(self, k, v)

    def _serialize(self):
        import pickle

        return pickle.dumps(self._saved_state)

    def _deserialize(self, blob) -> bool:
        import pickle

        try:
            saved = pickle.loads(blob)
        except Exception:
            return False
        if set(saved) != set(self._saved_state):
            return False
        self._saved_state.update(saved)
        for k, v in saved.items():
            setattr(self, k, v)
        return True


class ArrayState(ObjectState):
    """Elastic state for JAX pytrees (params/opt_state): the TPU-native
    ``TorchState`` (reference ``torch/elastic/state.py:27-140``).

    Pytree attributes are snapshotted to host memory with
    ``jax.device_get`` (surviving a mesh re-initialization) and restored
    with ``jax.device_put``; ``sync`` broadcasts from the root process.
    """

    def __init__(self, **kwargs):
        self._array_attrs = {
            k for k, v in kwargs.items() if _is_pytree_of_arrays(v)
        }
        super().__init__(**kwargs)
        self.save()

    def save(self) -> None:
        for k in list(self._saved_state):
            v = getattr(self, k)
            if k in self._array_attrs:
                self._saved_state[k] = jax.device_get(v)
            else:
                self._saved_state[k] = copy.deepcopy(v)

    def restore(self) -> None:
        for k, v in self._saved_state.items():
            if k in self._array_attrs:
                setattr(self, k, jax.device_put(v))
            else:
                setattr(self, k, copy.deepcopy(v))

    def _replicated(self) -> Dict[str, Any]:
        """The saved attributes that rank 0 broadcasts and persists.  In a
        job of several processes one that a registered sharded adapter
        carries spans the other processes' devices, which rank 0 cannot
        read: each process keeps its own shards, and the adapter moves
        them at a remesh."""
        if jax.process_count() == 1:
            return dict(self._saved_state)
        owned = {getattr(spec, "owns", None)
                 for spec in self._sharded.values()}
        return {k: v for k, v in self._saved_state.items() if k not in owned}

    def sync(self) -> None:
        if self._saved_state:
            self._load_persisted()
            self.save()
            synced = functions.broadcast_object(
                self._replicated(), root_rank=0)
            for k, v in synced.items():
                self._saved_state[k] = v
                setattr(
                    self, k, jax.device_put(v) if k in self._array_attrs else v
                )

    def _serialize(self):
        import pickle

        return pickle.dumps(self._replicated())

    def _deserialize(self, blob) -> bool:
        import pickle

        try:
            saved = pickle.loads(blob)
        except Exception:
            return False
        if set(saved) != set(self._replicated()):
            return False
        self._saved_state.update(saved)
        # re-device the array attributes (the blob holds host arrays)
        for k, v in saved.items():
            setattr(self, k,
                    jax.device_put(v) if k in self._array_attrs else v)
        return True


# Framework-flavored alias matching reference naming (TorchState /
# TensorFlowState -> TpuState).
TpuState = ArrayState


def _is_pytree_of_arrays(v: Any) -> bool:
    leaves = jax.tree.leaves(v)
    return bool(leaves) and all(
        hasattr(l, "shape") and hasattr(l, "dtype") for l in leaves
    )
