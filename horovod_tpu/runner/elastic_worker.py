"""Worker-side elastic plumbing.

Reference: ``horovod/runner/elastic/worker.py`` — a
``WorkerNotificationManager`` listens for driver host-update
notifications and flags registered ``State`` objects, whose next
``commit()``/``check_host_updates()`` raises ``HostsUpdatedInterrupt``.

Here the notification channel is the launcher KV store: the driver sets
``__elastic__/hosts_updated_<round>``; a poller thread flags states.
State persistence across worker restarts also lives here (the driver
respawns processes on membership change — see elastic_driver.py).
"""

from __future__ import annotations

import os
import pickle
import threading
import time
from typing import Any, List, Optional

from . import controller_py

RESTART_CODE = 73
_POLL_PERIOD_S = 0.5
_HEARTBEAT_PERIOD_S = 1.0

_manager: Optional["WorkerNotificationManager"] = None
_manager_lock = threading.Lock()


def in_elastic_job() -> bool:
    return os.environ.get("HVD_TPU_ELASTIC") == "1"


def get_notification_manager() -> Optional["WorkerNotificationManager"]:
    global _manager
    if not in_elastic_job():
        return None
    with _manager_lock:
        if _manager is None:
            _manager = WorkerNotificationManager()
        return _manager


class WorkerNotificationManager:
    def __init__(self):
        self._listeners: List[Any] = []
        self._lock = threading.Lock()
        self._client = None
        self._thread: Optional[threading.Thread] = None
        self._hb_thread: Optional[threading.Thread] = None
        self._stop = threading.Event()
        self.round = int(os.environ.get("HVD_TPU_ELASTIC_ROUND", "0"))
        self.rank = int(os.environ.get("HVD_TPU_CROSS_RANK", "0"))
        # SLO remediation consumer (runner/slo_consumer.py): the
        # heartbeat polls __slo__ so the driver's preempt/degrade/
        # placement actions are enacted in THIS process, not just
        # published.
        from . import slo_consumer

        self._slo_consumer = slo_consumer.SLOActionConsumer(
            rank_fn=lambda: self.rank,
            on_placement=self._notify_placement,
        )

    def _notify_placement(self, placement) -> None:
        """Fan a newly enacted tenant→slice placement out to registered
        states: a state that shards per tenant reacts at its next
        commit boundary (``State.on_placement_updated``)."""
        with self._lock:
            listeners = list(self._listeners)
        for state in listeners:
            notify = getattr(state, "on_placement_updated", None)
            if notify is not None:
                notify(placement)

    def init(self) -> None:
        if self._client is not None:
            return
        from ..faults import inject
        from ..utils.retry import RetryPolicy

        def connect():
            inject("worker.connect", rank=self.rank, round=self.round)
            return controller_py.make_client(
                os.environ["HVD_TPU_RENDEZVOUS_ADDR"],
                int(os.environ["HVD_TPU_RENDEZVOUS_PORT"]),
                os.environ["HVD_TPU_SECRET"],
                self.rank,
            )

        # the KV server may still be mid-bind when an early worker dials
        self._client = RetryPolicy(
            max_attempts=3, base_delay_s=0.2, name="worker.connect"
        ).call(connect)
        # Schedule-DB seeding: merge the driver-published entries into
        # the local store BEFORE training starts, so a ScheduleTuner
        # built later in this process warm-starts from fleet state.
        self._fetch_schedules()
        self._thread = threading.Thread(target=self._poll, daemon=True)
        self._thread.start()
        # Heartbeat: the driver's health monitor distinguishes a hung
        # worker (process alive, heartbeat stalled) from a crashed one
        # (process gone) — see ElasticDriver._find_hung_worker.
        self._hb_thread = threading.Thread(target=self._heartbeat,
                                           daemon=True)
        self._hb_thread.start()

    def _fetch_schedules(self) -> None:
        """Pull the driver-published schedule DB (``__schedules__/db``)
        into the local ``HVD_TPU_TUNE_DB`` store.  No-op without a
        configured store; any failure is advisory (a worker must start
        without fleet state)."""
        import json

        from .. import metrics
        from ..sched.store import ScheduleStore

        store = ScheduleStore.from_env()
        if store is None or self._client is None:
            return
        try:
            raw = self._client.get("__schedules__", "db", timeout_ms=1000)
            if not raw:
                return
            merged = store.merge(json.loads(raw).get("entries", {}))
            if merged:
                metrics.inc_counter("sched.tune.kv_seeded", merged)
        except Exception:
            pass

    def _push_schedules(self, client) -> None:
        """Push the local schedule DB to the driver when it changed
        (piggybacked on the heartbeat like the metrics snapshot, but
        gated on file mtime — convergence is rare, heartbeats are
        not)."""
        import json

        from ..utils import env as hvd_env

        path = hvd_env.get_env(hvd_env.TUNE_DB)
        if not path:
            return
        try:
            mtime = os.path.getmtime(path)
        except OSError:
            return
        if mtime == getattr(self, "_sched_db_mtime", None):
            return
        self._sched_db_mtime = mtime
        with open(path) as fh:
            data = json.load(fh)
        client.put(
            "__schedules__", f"rank_{self.rank}",
            json.dumps(
                {"entries": data.get("entries", {})}
            ).encode(),
        )

    def _heartbeat(self) -> None:
        from .. import metrics
        from ..faults import inject

        seq = 0
        while not self._stop.is_set():
            seq += 1
            # key recomputed per tick: an in-process remesh can change
            # this worker's rank mid-round (elastic/remesh.py), and the
            # driver's hang monitor then watches the NEW key
            key = f"hb_{self.round}_{self.rank}"
            try:
                client = self._client
                if client is None:
                    return
                client.put("__elastic__", key, str(seq).encode())
                # Piggyback the telemetry push on the heartbeat: the
                # driver's /metrics endpoint folds the latest snapshot
                # per rank into its scrape (telemetry_http.py).
                client.put(
                    "__metrics__", f"rank_{self.rank}",
                    metrics.render_json().encode(),
                )
                self._push_schedules(client)
                # Enact any newly published SLO remediation action
                # (poll() never raises — see slo_consumer.py).
                self._slo_consumer.poll(client)
            except Exception:
                pass  # KV blips must never kill the worker
            # a 'hang' fault here freezes the heartbeat AFTER it
            # registered, without touching the training thread — the
            # scripted stand-in for a wedged worker the driver's health
            # monitor must catch
            inject("worker.heartbeat", rank=self.rank, round=self.round)
            self._stop.wait(_HEARTBEAT_PERIOD_S)

    def _poll(self) -> None:
        key = f"hosts_updated_{self.round}"
        remesh_key = f"begin_{self.round}"
        notified_remesh = None
        while not self._stop.is_set():
            # Remesh authorization first: when the driver chose the
            # in-process reshard path it publishes __remesh__/begin_*
            # INSTEAD of the restart signal; listeners get a
            # RemeshInterrupt at their next commit (elastic/remesh.py).
            try:
                raw = self._client.get(
                    "__remesh__", remesh_key, timeout_ms=0
                )
            except Exception:
                raw = None
            if raw is not None:
                try:
                    from ..elastic.remesh import RemeshRequest

                    req = RemeshRequest.from_json(raw.decode())
                except Exception:
                    req = None
                if req is not None and req.remesh_id != notified_remesh:
                    notified_remesh = req.remesh_id
                    with self._lock:
                        for state in self._listeners:
                            notify = getattr(
                                state, "on_remesh_requested", None
                            )
                            if notify is not None:
                                notify(req)
            try:
                val = self._client.get("__elastic__", key, timeout_ms=0)
            except Exception:
                val = None
            if val is not None:
                with self._lock:
                    for state in self._listeners:
                        state.on_hosts_updated(time.time(), "updated")
                return  # one notification per round
            self._stop.wait(_POLL_PERIOD_S)

    def register_listener(self, state) -> None:
        with self._lock:
            self._listeners.append(state)

    def remove_listener(self, state) -> None:
        with self._lock:
            if state in self._listeners:
                self._listeners.remove(state)

    # -- in-process remesh plumbing (elastic/remesh.py) -----------------
    def kv_client(self):
        """The rendezvous KV client (shard transport of the remesh
        state exchange)."""
        self.init()
        return self._client

    def remesh_ack(self, remesh_id: int, phase: str) -> None:
        """Acknowledge one remesh phase to the driver:
        ``__remesh__/<phase>_<id>_<rank>``.  ``pause`` and ``snapshot``
        acks carry the OLD rank, ``done`` the NEW one (the manager's
        rank is updated by :meth:`on_world_changed` in between)."""
        self.kv_client().put(
            "__remesh__", f"{phase}_{int(remesh_id)}_{self.rank}", b"1"
        )

    def remesh_wait_go(self, remesh_id: int,
                       timeout_s: float = 60.0) -> None:
        """Block until the driver flips ``go`` (every survivor
        published its shards) — or raise on ``abort``/timeout so the
        caller falls back to the restart path instead of wedging."""
        from ..exceptions import RemeshError

        deadline = time.monotonic() + max(timeout_s, 1.0)
        client = self.kv_client()
        while True:
            try:
                if client.get("__remesh__", f"abort_{int(remesh_id)}",
                              timeout_ms=0) is not None:
                    raise RemeshError(
                        f"driver aborted remesh {remesh_id}"
                    )
                if client.get("__remesh__", f"go_{int(remesh_id)}",
                              timeout_ms=0) is not None:
                    return
            except RemeshError:
                raise
            except Exception:
                pass  # KV blip: keep polling until the deadline
            if time.monotonic() > deadline:
                raise RemeshError(
                    f"remesh {remesh_id}: no go/abort from the driver "
                    f"within {timeout_s:.0f}s"
                )
            if self._stop.wait(0.1):
                raise RemeshError("worker shutting down mid-remesh")

    def on_world_changed(self, new_rank: int) -> None:
        """Adopt the post-remesh rank: heartbeats and later acks key on
        it (``reinit_world`` already rewrote the env triple)."""
        self.rank = int(new_rank)

    def remesh_join_request(self):
        """The :class:`~horovod_tpu.elastic.remesh.RemeshRequest` this
        worker was spawned to JOIN (``HVD_TPU_REMESH_JOIN=<id>`` in the
        spawn env), or None for a normal round worker."""
        raw_id = os.environ.get("HVD_TPU_REMESH_JOIN")
        if not raw_id:
            return None
        from ..elastic.remesh import RemeshRequest

        raw = self.kv_client().get(
            "__remesh__", f"begin_{self.round}", timeout_ms=10000
        )
        if raw is None:
            return None
        req = RemeshRequest.from_json(raw.decode())
        if req.remesh_id != int(raw_id):
            return None
        return req

    # -- state persistence across rounds (rank 0 writes) ----------------
    # Blobs are chunked: the controller protocol caps one frame at 64MB
    # (native hvd_ctrl_get also truncates reads at its buffer cap), so a
    # model+optimizer snapshot ships as <=16MB pieces with a manifest.
    _CHUNK = 16 << 20

    def save_state_blob(self, blob: bytes) -> None:
        if self.rank != 0 or self._client is None:
            return
        import hashlib

        n = max(1, (len(blob) + self._CHUNK - 1) // self._CHUNK)
        for i in range(n):
            self._client.put(
                "__elastic_state__", f"chunk_{i}",
                blob[i * self._CHUNK : (i + 1) * self._CHUNK],
            )
        manifest = f"{n}:{len(blob)}:{hashlib.sha256(blob).hexdigest()}"
        self._client.put("__elastic_state__", "manifest", manifest.encode())

    def load_state_blob(self) -> Optional[bytes]:
        if self._client is None:
            return None
        import hashlib

        manifest = self._client.get("__elastic_state__", "manifest", timeout_ms=0)
        if manifest is None:
            return None
        n, total, digest = manifest.decode().split(":")
        parts = []
        for i in range(int(n)):
            chunk = self._client.get(
                "__elastic_state__", f"chunk_{i}", timeout_ms=5000
            )
            if chunk is None:
                return None
            parts.append(chunk)
        blob = b"".join(parts)[: int(total)]
        if hashlib.sha256(blob).hexdigest() != digest:
            return None  # torn write (a newer commit is in flight)
        return blob

    def close(self) -> None:
        self._stop.set()
        # The poll and heartbeat threads call into the client, and the
        # native client's close() deletes it: freeing it under a call
        # still in flight is a use-after-free that takes the whole
        # process down.  Both threads wake on _stop, so wait for them; a
        # thread that does not finish (a scripted hang) keeps the client
        # alive instead.
        threads = [
            t for t in (self._thread, self._hb_thread)
            if t is not None and t is not threading.current_thread()
        ]
        for t in threads:
            t.join(timeout=5.0)
        if self._client is not None:
            if not any(t.is_alive() for t in threads):
                self._client.close()
            self._client = None
