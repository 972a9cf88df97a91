"""Elastic driver: membership rounds with full worker respawn.

Reference: ``horovod/runner/elastic/driver.py`` — background discovery
loop, rank reassignment preserving surviving workers, worker respawn on
new slots, blacklist on failure, ``reset_limit`` bound on membership
changes.

TPU redesign rationale: XLA compiles for a fixed mesh, and a plain
``jax.distributed`` re-``initialize()`` in-process fails once the
backend exists (probe artifact: ``tools/probe_remesh_findings.json``,
case B).  An in-process survivor path DOES exist through a full backend
reset (case B2, exposed as the experimental
``hvd.elastic.reinit_world``), but this driver defaults to restarting
*all* worker processes per round: the respawn path is validated on
every backend (live-TPU PJRT teardown via ``clear_backends`` is not),
invalidates no in-flight host state, and recompilation — the dominant
restart cost either way — is bounded by the persistent compilation
cache, not by process reuse.  Training state survives rounds through
the launcher KV store / checkpoints (``elastic/state.py`` persists
commits when elastic env is present), which also covers the
all-workers-lost case the reference cannot (its in-memory state dies
with the last survivor).

Worker exit-code contract (read by this driver):
  0                    job finished -> round succeeds, driver exits
  73 (RESTART_CODE)    host update acknowledged -> respawn a new round
  anything else        failure -> blacklist the worker's host, new round
"""

from __future__ import annotations

import os
import secrets as pysecrets
import threading
import time
from typing import Callable, Dict, List, Optional

from .. import events, faults, metrics
from ..elastic.discovery import HostManager
from ..utils import env as hvd_env
from ..utils.logging import get_logger
from ..utils.retry import RetryPolicy
from . import controller_py, exec_utils
from . import hosts as hosts_mod
from .launch import (
    free_port,
    make_worker_env,
    require_one_tpu_process_per_host,
)

RESTART_CODE = 73
# A worker resharded AWAY by an in-process remesh exits with this code
# (elastic/remesh.py REMESH_SHED_CODE): a clean departure — its state
# was handed off through the KV store — not a failure, so its host is
# NOT blacklisted and the round keeps running with the survivors.
REMESH_SHED_CODE = 75

DISCOVERY_PERIOD_S = 1.0  # reference driver.py:30

# HTTP /metrics + /health endpoint (runner/telemetry_http.py): set
# HVD_TPU_TELEMETRY_PORT to enable (0 = OS-assigned port); unset
# disables.  Workers feed it by pushing metric snapshots through the
# rendezvous KV (__metrics__/rank_<r>, elastic_worker.py heartbeat).
TELEMETRY_PORT = "TELEMETRY_PORT"

# Health-monitor knobs (HVD_TPU_/HOROVOD_ prefixed via utils.env):
# a worker that registered a heartbeat and then went silent this long
# (while its process is still alive) is declared HUNG — terminated and
# blacklisted like a crash, but counted separately.  0 disables.
ELASTIC_HANG_TIMEOUT = "ELASTIC_HANG_TIMEOUT"
DEFAULT_HANG_TIMEOUT_S = 30.0
# Watchdog bound on one round's wall clock; 0 (default) disables.
ELASTIC_ROUND_TIMEOUT = "ELASTIC_ROUND_TIMEOUT"
# Transient worker-spawn failures (ssh flake, agent staleness) retry
# this many times before the host is blamed.
SPAWN_RETRIES = "SPAWN_RETRIES"
# In-process remesh (HVD_TPU_ELASTIC_REMESH=1): on a membership change
# the driver pauses survivors at a step boundary and coordinates a live
# state reshard (elastic/remesh.py) instead of a tear-down + restore
# round.  Off by default — the respawn path is validated on every
# backend; remesh is the opt-in fast path, and ANY remesh failure
# degrades to the respawn round automatically.
ELASTIC_REMESH = "ELASTIC_REMESH"
# Per-phase wall-clock bound on a remesh attempt (ack/exchange/reinit
# waits); past it the driver aborts the attempt and falls back.
REMESH_TIMEOUT = "REMESH_TIMEOUT"
DEFAULT_REMESH_TIMEOUT_S = 60.0


def _with_compilation_cache(extra_env):
    """Default the persistent XLA compilation cache into the worker env
    (recompilation dominates respawn-per-round restart cost on TPU;
    measured in tests/integration/test_elastic.py::
    test_elastic_restart_cost_bounded).

    Precedence: HVD_TPU_NO_COMPILATION_CACHE=1 disables; an explicit
    extra_env dir wins; otherwise the directory ``utils/compile_cache``
    has in effect — the driver's ``JAX_COMPILATION_CACHE_DIR`` when set
    (copied, because remote ssh workers never inherit the driver
    environment), else the fixed ``<checkout>/.jax_cache``.
    """
    from ..utils import compile_cache

    env = dict(extra_env or {})
    if os.environ.get("HVD_TPU_NO_COMPILATION_CACHE", "") != "1":
        env.setdefault("JAX_COMPILATION_CACHE_DIR",
                       compile_cache.directory())
    return env


class ElasticDriver:
    def __init__(
        self,
        host_manager: HostManager,
        min_np: int,
        max_np: Optional[int] = None,
        reset_limit: Optional[int] = None,
        cooldown_s: float = 0.5,
        hang_timeout_s: Optional[float] = None,
        round_timeout_s: Optional[float] = None,
        spawn_retry: Optional[RetryPolicy] = None,
        telemetry_port: Optional[int] = None,
        remesh: Optional[bool] = None,
        remesh_timeout_s: Optional[float] = None,
    ):
        self.host_manager = host_manager
        self.min_np = min_np
        self.max_np = max_np
        self.reset_limit = reset_limit
        self.cooldown_s = cooldown_s
        if remesh is None:
            remesh = hvd_env.get_bool(ELASTIC_REMESH, False)
        self.remesh = remesh
        if remesh_timeout_s is None:
            remesh_timeout_s = hvd_env.get_float(
                REMESH_TIMEOUT, DEFAULT_REMESH_TIMEOUT_S
            )
        self.remesh_timeout_s = remesh_timeout_s
        self._remesh_seq = 0
        # round-scoped spawn context so a mid-round remesh can spawn
        # joiners with the same transport the round's workers used
        self._round_spawn = None
        if hang_timeout_s is None:
            hang_timeout_s = hvd_env.get_float(
                ELASTIC_HANG_TIMEOUT, DEFAULT_HANG_TIMEOUT_S
            )
        self.hang_timeout_s = hang_timeout_s
        if round_timeout_s is None:
            round_timeout_s = hvd_env.get_float(ELASTIC_ROUND_TIMEOUT, 0.0)
        self.round_timeout_s = round_timeout_s
        self.spawn_retry = spawn_retry or RetryPolicy(
            max_attempts=max(1, hvd_env.get_int(SPAWN_RETRIES, 2)),
            base_delay_s=0.2,
            max_delay_s=2.0,
            name="elastic.spawn",
        )
        if telemetry_port is None:
            raw = hvd_env.get_env(TELEMETRY_PORT)
            telemetry_port = int(raw) if raw not in (None, "") else None
        self.telemetry_port = telemetry_port
        self.rounds = 0
        self._shutdown = threading.Event()
        self._membership_changed = threading.Event()
        self._discovery_thread: Optional[threading.Thread] = None
        self._telemetry = None
        # Persistent schedule store (sched/store.py): backed by
        # HVD_TPU_TUNE_DB when set, in-memory otherwise, so the
        # /schedules endpoint + KV fan-out work either way.  Created
        # here (not per round) — entries outlive rounds by design.
        self._schedule_store = None
        # round state read by the /health endpoint
        self._last_assignments: List[hosts_mod.SlotInfo] = []
        self._round_active = False
        # SLO self-healing (runner/slo.py + elastic/remediate.py):
        # built with the telemetry server when HVD_TPU_SLO_SPEC names
        # any tenant, ticked from the round watch loop, served as /slo.
        self._slo = None
        self._slo_workers_fn = None
        self._slo_enactment_fn = None

    def schedule_store(self):
        """The driver-side schedule store (lazy: first use reads
        ``HVD_TPU_TUNE_DB``)."""
        if self._schedule_store is None:
            from ..sched.store import ScheduleStore

            self._schedule_store = (
                ScheduleStore.from_env() or ScheduleStore(None)
            )
        return self._schedule_store

    # -- discovery loop (reference driver.py:181) ------------------------
    def start_discovery(self) -> None:
        def loop():
            while not self._shutdown.is_set():
                try:
                    if self.host_manager.update_available_hosts():
                        self._membership_changed.set()
                        events.emit(
                            events.DISCOVERY_CHANGE,
                            hosts=self.host_manager.current_hosts,
                        )
                except Exception as e:  # discovery script hiccup
                    get_logger().warning("host discovery failed: %s", e)
                metrics.set_gauge(
                    "elastic.available_slots",
                    self.host_manager.available_slots(),
                )
                self._shutdown.wait(DISCOVERY_PERIOD_S)

        self.host_manager.update_available_hosts()
        self._membership_changed.clear()
        self._discovery_thread = threading.Thread(target=loop, daemon=True)
        self._discovery_thread.start()

    def stop(self) -> None:
        self._shutdown.set()
        if self._discovery_thread:
            self._discovery_thread.join(timeout=5)

    def wait_for_available_slots(
        self, min_np: int, timeout_s: Optional[float] = None
    ) -> bool:
        """Block until the discovered world can host min_np workers
        (reference ``wait_for_available_slots``; timeout from
        ``HVD_TPU_ELASTIC_TIMEOUT`` / ``HOROVOD_ELASTIC_TIMEOUT``,
        default 600 s like reference ``ELASTIC_TIMEOUT_SECS``)."""
        if timeout_s is None:
            from ..utils import env as hvd_env

            timeout_s = hvd_env.get_float(hvd_env.ELASTIC_TIMEOUT, 600.0)
        deadline = time.monotonic() + timeout_s
        while True:
            # slots first, deadline second: a zero timeout must still
            # succeed immediately when capacity is already there
            if self.host_manager.available_slots() >= min_np:
                return True
            remaining = deadline - time.monotonic()
            if self._shutdown.is_set() or remaining <= 0:
                return False
            # shutdown-responsive sleep, clipped so fractional timeouts
            # are honored instead of overshooting by a full period
            self._shutdown.wait(min(DISCOVERY_PERIOD_S, remaining))

    def current_assignments(self) -> List[hosts_mod.SlotInfo]:
        hosts = [
            hosts_mod.HostInfo(h, s)
            for h, s in sorted(self.host_manager.current_hosts.items())
        ]
        total = sum(h.slots for h in hosts)
        np_ = min(total, self.max_np) if self.max_np else total
        if np_ < self.min_np:
            raise RuntimeError(
                f"only {total} slot(s) available, need min_np={self.min_np}"
            )
        assignments = hosts_mod.get_host_assignments(hosts, np_, max_np=np_)
        require_one_tpu_process_per_host(assignments)
        return assignments

    # -- main loop -------------------------------------------------------
    def run_rounds(
        self,
        command: List[str],
        *,
        extra_env: Optional[Dict[str, str]] = None,
        ssh_port: Optional[int] = None,
        ssh_identity_file: Optional[str] = None,
        publish: Optional[Dict[tuple, bytes]] = None,
        worker_factory: Optional[Callable] = None,
        rendezvous_addr: Optional[str] = None,
        result_collector: Optional[Callable] = None,
    ) -> int:
        """Spawn worker rounds until success, failure beyond limits, or
        reset_limit exhausted.  Returns the job exit code.

        ``publish`` entries ({(scope, key): blob}) are put into the
        rendezvous KV before the first round — how function payloads
        reach workers (e.g. ``task_runner`` fetches ``__run__/func``),
        mirroring ``horovod.run``'s KV-store func delivery.

        ``worker_factory`` replaces the ssh/local exec
        (``exec_utils.WorkerProcess``) with another transport that
        spawns ``command`` on a slot's host — e.g. the Spark task-agent
        dispatch (``spark/elastic.py``).  ``rendezvous_addr`` overrides
        the NIC probe when the caller already knows the address workers
        can dial (Spark agents dialed it to register).
        ``result_collector(control, np, round_id)`` runs on success
        before the KV server closes — how ``spark.run_elastic`` fetches
        the winning round's per-rank results.
        """
        # Respawn-per-round makes recompilation the dominant restart
        # cost on TPU; the persistent XLA compilation cache turns
        # round-2+ compiles into cache reads (measured in
        # tests/integration/test_elastic.py::test_elastic_restart_cost
        # _bounded).  Opt out with HVD_TPU_NO_COMPILATION_CACHE=1; place
        # it with JAX_COMPILATION_CACHE_DIR.
        extra_env = _with_compilation_cache(extra_env)
        secret = pysecrets.token_hex(16)
        server = controller_py.make_server(secret, self.min_np)
        control = controller_py.make_client(
            "127.0.0.1", server.port, secret, rank=-1
        )
        for (scope, key), blob in (publish or {}).items():
            control.put(scope, key, blob)
        if self.telemetry_port is not None:
            self._telemetry = self._start_telemetry(control)
        try:
            while True:
                if not self.wait_for_available_slots(self.min_np):
                    return 1
                try:
                    assignments = self.current_assignments()
                except RuntimeError as e:
                    get_logger().warning("%s", e)
                    time.sleep(DISCOVERY_PERIOD_S)
                    continue
                self.rounds += 1
                round_id = self.rounds
                metrics.inc_counter("elastic.rounds")
                metrics.set_gauge("elastic.round", round_id)
                metrics.set_gauge("elastic.workers", len(assignments))
                self._last_assignments = assignments
                self._round_active = True
                events.emit(
                    events.ROUND_START, round=round_id,
                    np=len(assignments),
                    hosts=sorted({a.hostname for a in assignments}),
                )
                self._membership_changed.clear()
                control.put("__elastic__", "round", str(round_id).encode())
                control.put("__elastic__", f"round_{round_id}_np",
                            str(len(assignments)).encode())
                self._publish_schedules(control)
                get_logger().warning(
                    "elastic round %d: %d worker(s) on %d host(s)",
                    round_id, len(assignments), assignments[-1].cross_size,
                )
                coordinator_host = (
                    "127.0.0.1"
                    if exec_utils.is_local(assignments[0].hostname)
                    else assignments[0].hostname
                )
                coordinator_addr = f"{coordinator_host}:{free_port()}"
                # The rendezvous KV runs in this driver process: remote
                # workers must dial our routable address, not loopback —
                # mutually verified via the NIC probe on multi-NIC hosts
                # (unless the caller's transport already knows it).
                round_rdv_addr = rendezvous_addr
                if round_rdv_addr is None:
                    round_rdv_addr = exec_utils.probe_routable_addr(
                        assignments, ssh_port=ssh_port,
                        ssh_identity_file=ssh_identity_file,
                    )
                make_worker = worker_factory or exec_utils.WorkerProcess
                begin = getattr(make_worker, "begin_round", None)
                if begin is not None:
                    begin(round_id)
                # Round-scoped spawn context: a mid-round remesh spawns
                # JOINER workers through the same transport/env recipe.
                self._round_spawn = {
                    "command": command,
                    "extra_env": extra_env,
                    "rdv_addr": round_rdv_addr,
                    "rdv_port": server.port,
                    "secret": secret,
                    "make_worker": make_worker,
                    "ssh_port": ssh_port,
                    "ssh_identity_file": ssh_identity_file,
                }
                workers = []
                spawn_failed_host = None
                for slot in assignments:
                    env = make_worker_env(
                        slot, coordinator_addr, round_rdv_addr, server.port,
                        secret, extra_env,
                    )
                    env["HVD_TPU_ELASTIC"] = "1"
                    env["HVD_TPU_ELASTIC_ROUND"] = str(round_id)

                    def spawn(slot=slot, env=env):
                        faults.inject(
                            "driver.spawn", host=slot.hostname,
                            rank=slot.rank, round=round_id,
                        )
                        return make_worker(
                            slot.rank, slot.hostname, command, env,
                            ssh_port=ssh_port,
                            ssh_identity_file=ssh_identity_file,
                        )

                    try:
                        # transient spawn failures (ssh flake, agent
                        # staleness) retry before the host is blamed
                        workers.append(self.spawn_retry.call(spawn))
                    except Exception as e:
                        # A host lost between assignment and spawn (e.g.
                        # a Spark executor death in the discovery
                        # staleness window) fails the ROUND, not the
                        # job: blacklist and go again.
                        get_logger().warning(
                            "worker spawn on %s failed: %s",
                            slot.hostname, e,
                        )
                        events.emit(
                            events.SPAWN_FAILED, round=round_id,
                            host=slot.hostname, worker_rank=slot.rank,
                            error=str(e),
                        )
                        spawn_failed_host = slot.hostname
                        break
                if spawn_failed_host is not None:
                    for w in workers:
                        w.terminate()
                    for w in workers:
                        w.wait()
                    self.host_manager.blacklist(spawn_failed_host)
                    if self.host_manager.available_slots() >= self.min_np:
                        time.sleep(self.cooldown_s)
                        continue
                    return 1
                rc = self._watch_round(workers, assignments, control, round_id)
                self._round_active = False
                self._collect_schedules(control)
                events.emit(
                    events.ROUND_END, round=round_id, exit_code=rc,
                    restart=(rc == RESTART_CODE),
                )
                if rc == 0:
                    if result_collector is not None:
                        result_collector(
                            control, len(assignments), round_id
                        )
                    return 0
                if rc == RESTART_CODE:
                    events.emit(events.RESTART, round=round_id)
                    if (
                        self.reset_limit is not None
                        and self.rounds > self.reset_limit
                    ):
                        get_logger().error(
                            "reset_limit %d exceeded", self.reset_limit
                        )
                        return 1
                    time.sleep(self.cooldown_s)
                    continue
                # real failure: can we keep going?
                if self.host_manager.available_slots() >= self.min_np:
                    time.sleep(self.cooldown_s)
                    continue
                return rc
        finally:
            if self._telemetry is not None:
                self._telemetry.stop()
                self._telemetry = None
            control.close()
            server.stop()
            self.stop()

    def _start_telemetry(self, control):
        """Start the HTTP /metrics + /health endpoint for this job.

        ``/metrics`` folds in the latest snapshot each worker pushed
        through the KV store; ``/health`` reports round/membership
        state.  Scrape-time only — zero cost to the driver loop."""
        import json as _json

        from .telemetry_http import TelemetryServer

        def workers_fn():
            out = []
            for slot in list(self._last_assignments):
                try:
                    raw = control.get(
                        "__metrics__", f"rank_{slot.rank}", timeout_ms=0
                    )
                except Exception:
                    raw = None
                if raw:
                    try:
                        out.append((slot.rank, _json.loads(raw)))
                    except ValueError:
                        pass
            return out

        def health_fn():
            slots = self.host_manager.available_slots()
            return {
                "status": "ok" if slots >= self.min_np else "degraded",
                "round": self.rounds,
                "round_active": self._round_active,
                "workers": len(self._last_assignments),
                "min_np": self.min_np,
                "max_np": self.max_np,
                "available_slots": slots,
                "current_hosts": self.host_manager.current_hosts,
            }

        def trace_fn():
            # Cross-rank straggler detection (trace/straggler.py) over
            # the per-rank phase summaries the workers' heartbeats
            # already push: one pass per scrape, verdicts published as
            # trace.straggler{rank=,phase=} gauges AND returned as the
            # /trace body, with round context so an operator can line
            # the summary up against /health.
            from ..trace import straggler

            per_rank = {rank: snap for rank, snap in workers_fn()}
            payload = straggler.trace_payload(per_rank)
            payload["round"] = self.rounds
            payload["workers"] = len(self._last_assignments)
            return payload

        def tenants_fn():
            # Per-tenant accounting for the multi-tenant arbiter
            # (svc/arbiter.py, docs/multitenant.md): queue depth, rail
            # bytes, and wait quantiles per tenant aggregated from the
            # same per-rank KV pushes, with round context so share
            # shifts can be lined up against membership changes.
            from ..svc.arbiter import tenants_payload

            per_rank = {rank: snap for rank, snap in workers_fn()}
            payload = tenants_payload(per_rank)
            payload["round"] = self.rounds
            payload["workers"] = len(self._last_assignments)
            return payload

        def prof_fn():
            # GET /prof: the device-time profiling plane (prof/,
            # docs/observability.md) — per-rank host-gap / MFU /
            # regression digests from the same KV pushes, with round
            # context like /trace and /tenants.
            from .. import prof

            per_rank = {rank: snap for rank, snap in workers_fn()}
            payload = prof.prof_payload(per_rank)
            payload["round"] = self.rounds
            payload["workers"] = len(self._last_assignments)
            return payload

        self._slo = self._build_slo(control)
        self._slo_workers_fn = workers_fn
        slo_fn = None
        if self._slo is not None:
            controller = self._slo

            def slo_fn():
                # GET /slo: the watchdog's last window + remediation
                # history, with round context like /trace and /tenants
                # — plus per-action worker ack counts, so a handoff
                # that no worker enacted is visible as such.
                payload = controller.payload()
                payload["round"] = self.rounds
                payload["workers"] = len(self._last_assignments)
                enact = getattr(self, "_slo_enactment_fn", None)
                if enact is not None:
                    try:
                        payload["enactment"] = enact()
                    except Exception:  # pragma: no cover - defensive
                        pass
                return payload

        from .telemetry_http import probe_payload

        return TelemetryServer(
            port=self.telemetry_port, health_fn=health_fn,
            workers_fn=workers_fn, schedule_store=self.schedule_store(),
            trace_fn=trace_fn, tenants_fn=tenants_fn, slo_fn=slo_fn,
            prof_fn=prof_fn, probe_fn=probe_payload,
        )

    def _build_slo(self, control):
        """Build the SLO controller (watchdog + remediation ladder)
        when ``HVD_TPU_SLO_SPEC`` names any tenant; None otherwise.

        The driver's actuators publish every rung on the KV store
        (``__slo__/preempt|degrade|placement``, seq-stamped) and the
        workers' heartbeat threads consume and enact them in-process
        (``runner/slo_consumer.py``): preempt gates the worker's
        arbiter lanes, degrade applies the knob flip there, and a
        placement handoff shifts the arbiter's tenant weights (rail
        shares follow slices at the next scheduling cycle) and reaches
        registered states at their next commit boundary through
        ``on_placement_updated`` — no restarts.  Rollback republishes
        the old placement, and the degrade revert published by
        :meth:`~horovod_tpu.elastic.remediate.Remediator.reset` on SLO
        recovery rides the same degrade channel.  Each worker acks
        what it enacted; ``GET /slo`` folds the ack counts in
        (``enactment``), so the history reports what workers DID, not
        just what the driver said.
        """
        import itertools
        import json as _json

        from ..elastic import remediate
        from . import slo as slo_mod
        from .slo_consumer import ack_key

        seq_counter = itertools.count(1)
        published: Dict[str, int] = {}

        def publish(key: str, payload: Dict) -> None:
            # Advisory channel: a KV hiccup must fail the RUNG (so its
            # RetryPolicy retries), not the driver loop — hence raise.
            seq = next(seq_counter)
            payload = dict(payload, seq=seq)
            control.put("__slo__", key, _json.dumps(payload).encode())
            published[key] = seq

        def preempt(tenant, breach):
            from ..svc import service as service_mod

            svc = service_mod.get_service_or_none()
            if svc is not None:
                svc.arbiter.request_preempt(tenant)
            publish("preempt", {"tenant": tenant,
                                "kind": breach.get("kind")})

        def degrade(tenant, breach):
            changes = remediate._default_degrade(tenant, breach)
            publish("degrade", {"tenant": tenant, "changes": changes})
            return changes

        def undegrade(tenant, restored):
            # Remediator.reset() reverting degraded mode after SLO
            # recovery: workers un-apply through the same channel
            # (null value = unset the knob).
            publish("degrade", {"tenant": tenant, "changes": restored,
                                "revert": True})

        def handoff(old_placement, new_placement, breach):
            publish("placement", {
                "placement": new_placement,
                "tenant": breach.get("tenant"),
                "previous": old_placement,
            })

        def rollback(old_placement, new_placement, breach):
            publish("placement", {
                "placement": old_placement,
                "tenant": breach.get("tenant"),
                "rollback": True,
            })

        def enactment() -> Dict:
            # Which ranks acked the latest publication of each action —
            # the /slo proof that a remediation was enacted, not merely
            # announced.  Non-blocking KV reads, scrape-time only.
            out: Dict[str, Dict] = {}
            for key, seq in published.items():
                acked = []
                for slot in list(self._last_assignments):
                    try:
                        if control.get(
                            "__slo__", ack_key(key, seq, slot.rank),
                            timeout_ms=0,
                        ) is not None:
                            acked.append(slot.rank)
                    except Exception:
                        pass
                out[key] = {
                    "seq": seq,
                    "acked_ranks": sorted(acked),
                    "workers": len(self._last_assignments),
                }
            return out

        self._slo_enactment_fn = enactment
        remediator = remediate.Remediator(actuators={
            "preempt": preempt, "degrade": degrade,
            "undegrade": undegrade,
            "handoff": handoff, "rollback": rollback,
        })
        return slo_mod.SLOController.from_env(remediator)

    def _publish_schedules(self, control) -> None:
        """Seed the round's workers with the schedule DB: the store's
        entries ride the rendezvous KV (``__schedules__/db``) so a
        worker can warm-start its ``ScheduleTuner`` before its first
        window (``elastic_worker.py`` fetches at startup).  Fleet
        serving's in-job half — the HTTP ``/schedules`` endpoint covers
        cross-job."""
        import json as _json

        try:
            entries = self.schedule_store().entries()
            control.put(
                "__schedules__", "db",
                _json.dumps({"entries": entries}).encode(),
            )
        except Exception as e:  # advisory channel: never fail a round
            get_logger().warning("schedule publish failed: %s", e)

    def _collect_schedules(self, control) -> None:
        """Fold worker-pushed schedule entries (``__schedules__/
        rank_<r>``, pushed by the heartbeat thread when the worker's
        local DB changes) into the driver store — one tuned worker
        seeds every later identical job."""
        import json as _json

        merged = 0
        for slot in list(self._last_assignments):
            try:
                raw = control.get(
                    "__schedules__", f"rank_{slot.rank}", timeout_ms=0
                )
            except Exception:
                raw = None
            if not raw:
                continue
            try:
                merged += self.schedule_store().merge(
                    _json.loads(raw).get("entries", {})
                )
            except Exception as e:
                get_logger().warning(
                    "bad schedule push from rank %s: %s", slot.rank, e
                )
        if merged:
            metrics.inc_counter("sched.tune.db_collected", merged)

    def _watch_round(
        self,
        workers: List[exec_utils.WorkerProcess],
        assignments: List[hosts_mod.SlotInfo],
        control,
        round_id: int,
    ) -> int:
        """Wait for the round to end.  Membership change -> signal workers
        (they exit RESTART_CODE at the next commit); failure -> blacklist
        and terminate; success of all -> 0.

        Health monitoring: workers that run ``hvd.elastic.run`` publish
        heartbeats into the KV store (``__elastic__/hb_<round>_<rank>``,
        elastic_worker.py).  A worker whose process is alive but whose
        heartbeat stopped advancing for ``hang_timeout_s`` is declared
        HUNG — without this, a wedged worker (deadlocked collective,
        stuck I/O) stalls the job forever, indistinguishable from slow
        progress.  Crash and hang are counted separately
        (``elastic.worker_crash`` / ``elastic.worker_hang``) because
        they point at different root causes.  Workers that never
        heartbeat (plain scripts) are exempt from hang detection.
        ``round_timeout_s`` additionally bounds the whole round.
        """
        pending = set(range(len(workers)))
        saw_failure = 0
        t_round_start = time.monotonic()
        # rank -> (last heartbeat payload, monotonic time it changed)
        hb_seen: Dict[int, tuple] = {}
        last_hb_check = t_round_start

        def _fail_worker(i: int, why: str) -> None:
            nonlocal saw_failure, pending
            metrics.inc_counter(f"elastic.worker_{why}")
            events.emit(
                events.WORKER_CRASH if why == "crash" else events.WORKER_HANG,
                round=round_id, worker_rank=assignments[i].rank,
                host=assignments[i].hostname, verdict=why,
            )
            self.host_manager.blacklist(assignments[i].hostname)
            # a dead peer wedges collectives: end the round
            for j in pending:
                workers[j].terminate()
            for j in pending:
                workers[j].wait()
            pending = set()

        while pending:
            if self._membership_changed.is_set():
                self._membership_changed.clear()
                remeshed = None
                if self.remesh:
                    remeshed = self._try_remesh(
                        workers, assignments, control, round_id
                    )
                if remeshed is not None:
                    # Live reshard succeeded: the round continues with
                    # the NEW worker set — no respawn, no checkpoint
                    # restore on the hot path.
                    workers, assignments = remeshed
                    pending = set(range(len(workers)))
                    hb_seen.clear()
                    metrics.set_gauge("elastic.workers", len(workers))
                    self._last_assignments = assignments
                else:
                    control.put(
                        "__elastic__", f"hosts_updated_{round_id}", b"1"
                    )
            for i in sorted(pending):
                rc = workers[i].returncode
                if rc is None:
                    continue
                pending.discard(i)
                if rc == 0:
                    continue
                if rc == REMESH_SHED_CODE:
                    # resharded away by a remesh: clean departure, the
                    # host stays in rotation
                    continue
                if rc == RESTART_CODE:
                    # graceful restart request: drain the others too
                    control.put(
                        "__elastic__", f"hosts_updated_{round_id}", b"1"
                    )
                    saw_failure = saw_failure or RESTART_CODE
                    continue
                get_logger().warning(
                    "worker %d on %s crashed (exit %d)",
                    assignments[i].rank, assignments[i].hostname, rc,
                )
                saw_failure = rc
                _fail_worker(i, "crash")
                break
            now = time.monotonic()
            if pending and self.hang_timeout_s > 0 and (
                now - last_hb_check >= 1.0
            ):
                last_hb_check = now
                hung = self._find_hung_worker(
                    pending, assignments, control, round_id, hb_seen
                )
                if hung is not None:
                    get_logger().error(
                        "worker %d on %s is HUNG (no heartbeat for "
                        "%.1fs, process alive) — terminating",
                        assignments[hung].rank,
                        assignments[hung].hostname, self.hang_timeout_s,
                    )
                    pending.discard(hung)
                    workers[hung].terminate()
                    workers[hung].wait()
                    saw_failure = saw_failure or 1
                    _fail_worker(hung, "hang")
            if pending and self.round_timeout_s > 0 and (
                time.monotonic() - t_round_start > self.round_timeout_s
            ):
                get_logger().error(
                    "round %d exceeded watchdog timeout %.1fs; "
                    "restarting", round_id, self.round_timeout_s,
                )
                metrics.inc_counter("elastic.round_timeout")
                events.emit(
                    events.WATCHDOG_TIMEOUT, round=round_id,
                    timeout_s=self.round_timeout_s,
                )
                for j in pending:
                    workers[j].terminate()
                for j in pending:
                    workers[j].wait()
                pending = set()
                saw_failure = saw_failure or RESTART_CODE
            if self._slo is not None and self._slo_workers_fn is not None:
                # SLO watchdog tick (runner/slo.py): rate-limited to
                # HVD_TPU_SLO_CHECK_INTERVAL internally and never
                # raises — a breach remediates, it never ends a round.
                self._slo.maybe_tick(lambda: {
                    r: snap for r, snap in self._slo_workers_fn()
                })
            time.sleep(0.1)
        for w in workers:
            w.wait()
        if saw_failure == RESTART_CODE:
            return RESTART_CODE
        if saw_failure:
            return RESTART_CODE if self.host_manager.available_slots() >= self.min_np else saw_failure
        return 0

    # -- in-process remesh coordination (elastic/remesh.py) --------------
    def _await_remesh_keys(self, control, keys, deadline: float,
                           workers=None) -> bool:
        """Poll the KV store until every key in ``keys`` exists or the
        deadline passes.  With ``workers``, a worker death while
        waiting fails the attempt immediately (a dead peer can never
        ack)."""
        remaining = set(keys)
        while remaining:
            for key in list(remaining):
                try:
                    if control.get("__remesh__", key,
                                   timeout_ms=0) is not None:
                        remaining.discard(key)
                except Exception:
                    pass
            if not remaining:
                return True
            if workers is not None and any(
                w.returncode not in (None, 0, REMESH_SHED_CODE)
                for w in workers
            ):
                get_logger().warning(
                    "remesh: a worker died while waiting for %s",
                    sorted(remaining),
                )
                return False
            if time.monotonic() > deadline:
                get_logger().warning(
                    "remesh: timed out waiting for %s", sorted(remaining)
                )
                return False
            time.sleep(0.05)
        return True

    def _plan_remesh_world(self, workers, assignments, new_np: int,
                           new_hosts):
        """Old world -> new world placement: survivors keep their host
        (new ranks assigned in old-rank order), shed workers are those
        on removed hosts or beyond the new size, joiner slots fill the
        remaining capacity.  Returns (survivors {old->new}, shed old
        ranks, joiner SlotInfos, full new SlotInfo list by new rank)."""
        capacity = dict(new_hosts)
        keep: List[int] = []  # old ranks surviving, in old-rank order
        shed: List[int] = []
        for slot in assignments:
            if len(keep) < new_np and capacity.get(slot.hostname, 0) > 0:
                capacity[slot.hostname] -= 1
                keep.append(slot.rank)
            else:
                shed.append(slot.rank)
        survivors = {old: new for new, old in enumerate(keep)}
        host_of: Dict[int, str] = {}
        by_old = {s.rank: s for s in assignments}
        for old, new in survivors.items():
            host_of[new] = by_old[old].hostname
        joiner_ranks = list(range(len(keep), new_np))
        for nr in joiner_ranks:
            for h in sorted(capacity):
                if capacity[h] > 0:
                    capacity[h] -= 1
                    host_of[nr] = h
                    break
            else:
                return None  # capacity accounting failed
        # per-host local/cross numbering over the final placement
        hosts_in_order: List[str] = []
        for nr in range(new_np):
            if host_of[nr] not in hosts_in_order:
                hosts_in_order.append(host_of[nr])
        local_index: Dict[str, int] = {h: 0 for h in hosts_in_order}
        slots: List[hosts_mod.SlotInfo] = []
        per_host = {
            h: list(host_of.values()).count(h) for h in hosts_in_order
        }
        for nr in range(new_np):
            h = host_of[nr]
            slots.append(hosts_mod.SlotInfo(
                hostname=h, rank=nr,
                local_rank=local_index[h],
                cross_rank=hosts_in_order.index(h),
                size=new_np,
                local_size=per_host[h],
                cross_size=len(hosts_in_order),
            ))
            local_index[h] += 1
        joiners = [slots[nr] for nr in joiner_ranks]
        return survivors, shed, joiners, slots

    def _try_remesh(self, workers, assignments, control, round_id):
        """Attempt a zero-downtime in-process remesh for the current
        membership change.  Returns ``(workers, assignments)`` for the
        new world on success; ``None`` falls back to the respawn-round
        path (the caller then publishes the restart signal).  Every
        failure mode is bounded by ``remesh_timeout_s`` and ends in
        either success or a clean fallback — never a wedged round."""
        from ..elastic.remesh import RemeshRequest

        try:
            new_assignments = self.current_assignments()
        except RuntimeError as e:
            get_logger().warning("remesh: %s", e)
            return None
        np_old, np_new = len(assignments), len(new_assignments)
        live = [w for w in workers if w.returncode is None]
        if len(live) != np_old:
            # someone already died: that is the crash path's job
            return None
        new_hosts: Dict[str, int] = {}
        for a in new_assignments:
            new_hosts[a.hostname] = new_hosts.get(a.hostname, 0) + 1
        if np_new == np_old and all(
            new_hosts.get(s.hostname, 0) > 0 for s in assignments
        ):
            return None  # not a resize; nothing to reshard
        planned = self._plan_remesh_world(
            workers, assignments, np_new, new_hosts
        )
        if planned is None:
            return None
        survivors, shed, joiners, new_slots = planned
        if not survivors:
            return None  # no survivor to carry state: full restart
        metrics.inc_counter("remesh.driver_attempts")
        self._remesh_seq += 1
        rid = self._remesh_seq
        coord_host = (
            "127.0.0.1"
            if exec_utils.is_local(new_slots[0].hostname)
            else new_slots[0].hostname
        )
        request = RemeshRequest(
            remesh_id=rid, round_id=round_id,
            np_old=np_old, np_new=np_new,
            coordinator_addr=f"{coord_host}:{free_port()}",
            survivors=survivors,
            deadline_s=self.remesh_timeout_s,
        )
        events.emit(
            events.REMESH_START, remesh_id=rid, round=round_id,
            np_old=np_old, np_new=np_new,
            survivors=sorted(survivors), shed=sorted(shed),
            joiners=[s.rank for s in joiners],
        )
        get_logger().warning(
            "remesh %d: %d -> %d worker(s) (%d survivor(s), %d shed, "
            "%d joining) — resharding in place",
            rid, np_old, np_new, len(survivors), len(shed), len(joiners),
        )
        control.put("__remesh__", f"begin_{round_id}",
                    request.to_json().encode())
        deadline = time.monotonic() + self.remesh_timeout_s
        old_ranks = sorted(s.rank for s in assignments)
        joiner_procs = []

        def fallback(why: str):
            metrics.inc_counter("remesh.driver_fallback")
            events.emit(
                events.REMESH_FALLBACK, remesh_id=rid, round=round_id,
                error=why,
            )
            get_logger().warning(
                "remesh %d failed (%s); falling back to the respawn "
                "round", rid, why,
            )
            try:
                control.put("__remesh__", f"abort_{rid}", b"1")
            except Exception:
                pass
            for p in joiner_procs:
                p.terminate()
            for p in joiner_procs:
                p.wait()
            return None

        # Phase 1+2: every live old rank pauses at a step boundary and
        # publishes its shards (pause acks piggyback on the heartbeat
        # KV channel).
        if not self._await_remesh_keys(
            control, [f"pause_{rid}_{r}" for r in old_ranks],
            deadline, workers,
        ):
            return fallback("pause ack timeout")
        if not self._await_remesh_keys(
            control, [f"snapshot_{rid}_{r}" for r in old_ranks],
            deadline, workers,
        ):
            return fallback("snapshot ack timeout")

        # Phase 3: spawn joiners into the NEW world, then authorize the
        # exchange.  Joiners rendezvous on the new coordinator with the
        # reinit-ing survivors.
        ctx = self._round_spawn or {}
        make_worker = ctx.get("make_worker", exec_utils.WorkerProcess)
        for slot in joiners:
            env = make_worker_env(
                slot, request.coordinator_addr, ctx.get("rdv_addr"),
                ctx.get("rdv_port"), ctx.get("secret"),
                ctx.get("extra_env"),
            )
            env["HVD_TPU_ELASTIC"] = "1"
            env["HVD_TPU_ELASTIC_ROUND"] = str(round_id)
            env["HVD_TPU_REMESH_JOIN"] = str(rid)
            try:
                joiner_procs.append(self.spawn_retry.call(
                    lambda slot=slot, env=env: make_worker(
                        slot.rank, slot.hostname, ctx.get("command"),
                        env, ssh_port=ctx.get("ssh_port"),
                        ssh_identity_file=ctx.get("ssh_identity_file"),
                    )
                ))
            except Exception as e:
                return fallback(f"joiner spawn on {slot.hostname}: {e}")
        control.put("__remesh__", f"go_{rid}", b"1")

        # Phase 4: survivors reinit + fetch, joiners fetch; shed ranks
        # leave.  Done acks are keyed by NEW ranks.
        new_ranks = list(range(np_new))
        if not self._await_remesh_keys(
            control,
            [f"done_{rid}_{r}" for r in new_ranks]
            + [f"shed_{rid}_{r}" for r in shed],
            deadline + self.remesh_timeout_s,  # reinit is the long pole
            list(live) + joiner_procs,
        ):
            return fallback("exchange/reinit timeout")

        # Reap shed workers (clean exits, hosts stay in rotation).
        by_old = {s.rank: i for i, s in enumerate(assignments)}
        survivor_procs = {}
        for old, new in survivors.items():
            survivor_procs[new] = workers[by_old[old]]
        for r in shed:
            workers[by_old[r]].wait()
        new_workers = [
            survivor_procs[nr] if nr in survivor_procs
            else joiner_procs[[s.rank for s in joiners].index(nr)]
            for nr in range(np_new)
        ]
        metrics.inc_counter("remesh.driver_success")
        metrics.set_gauge("elastic.remesh", rid)
        events.emit(
            events.REMESH_OK, remesh_id=rid, round=round_id, np=np_new,
        )
        get_logger().warning(
            "remesh %d complete: round %d continues with %d worker(s)",
            rid, round_id, np_new,
        )
        return new_workers, new_slots

    def _find_hung_worker(
        self,
        pending,
        assignments: List[hosts_mod.SlotInfo],
        control,
        round_id: int,
        hb_seen: Dict[int, tuple],
    ) -> Optional[int]:
        """First pending worker whose heartbeat registered and then went
        silent past ``hang_timeout_s``; updates ``hb_seen`` in place."""
        now = time.monotonic()
        for i in sorted(pending):
            rank = assignments[i].rank
            try:
                val = control.get(
                    "__elastic__", f"hb_{round_id}_{rank}", timeout_ms=0
                )
            except Exception:
                val = None
            if val is None:
                continue  # never heartbeat: plain script, exempt
            prev = hb_seen.get(rank)
            if prev is None or prev[0] != val:
                hb_seen[rank] = (val, now)
                continue
            if now - prev[1] > self.hang_timeout_s:
                return i
        return None
