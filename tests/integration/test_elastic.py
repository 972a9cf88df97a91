"""Elastic integration: real worker processes, membership change
mid-training, state carried across rounds.

Reference analog: ``test/integration/elastic_common.py`` +
``test_elastic_torch.py`` — scripted discovery emitting different host
lists over time, real elastic jobs, asserting world sizes and state
continuity per round.
"""

import os
import sys
import textwrap
import threading
import time

import pytest

from horovod_tpu import faults, metrics
from horovod_tpu.elastic.discovery import HostDiscovery, HostManager
from horovod_tpu.runner.elastic_driver import ElasticDriver
from horovod_tpu.utils.retry import RetryPolicy

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
WORKER_ENV = {
    "PYTHONPATH": REPO_ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""),
}

pytestmark = pytest.mark.integration

WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import horovod_tpu as hvd
    from horovod_tpu.elastic import ObjectState

    hvd.init()
    out = open(os.environ["RESULTS_FILE"] + f".{os.environ['HVD_TPU_CROSS_RANK']}", "a")

    state = ObjectState(epoch=0)

    @hvd.elastic.run
    def train(state):
        while state.epoch < 6:
            time.sleep(0.8)  # one "epoch" of work
            state.epoch += 1
            print(f"epoch {state.epoch} world {hvd.size()}", flush=True)
            out.write(f"round={os.environ['HVD_TPU_ELASTIC_ROUND']} "
                      f"epoch={state.epoch} size={hvd.size()}\\n")
            out.flush()
            state.commit()
        return state.epoch

    import time
    final = train(state)
    out.write(f"done epoch={final}\\n")
    out.close()
    """
)


class ScriptedDiscovery(HostDiscovery):
    """Host set changes after a delay (the scripted-discovery fake)."""

    def __init__(self, phases):
        # phases: list of (duration_s, {host: slots}); last phase persists
        self._phases = phases
        self._t0 = time.monotonic()

    def find_available_hosts_and_slots(self):
        t = time.monotonic() - self._t0
        acc = 0.0
        for duration, hosts in self._phases:
            acc += duration
            if t < acc:
                return dict(hosts)
        return dict(self._phases[-1][1])


@pytest.mark.multiproc
def test_elastic_membership_change(tmp_path):
    script = tmp_path / "worker.py"
    script.write_text(WORKER_SCRIPT)
    results_file = str(tmp_path / "results")

    discovery = ScriptedDiscovery([
        (3.0, {"localhost": 2}),
        (1e9, {"localhost": 3}),  # scale up after 3s
    ])
    driver = ElasticDriver(HostManager(discovery), min_np=2, max_np=4)
    driver.start_discovery()
    rc = driver.run_rounds(
        [sys.executable, str(script)],
        extra_env={"RESULTS_FILE": results_file, **WORKER_ENV},
    )
    assert rc == 0
    assert driver.rounds >= 2, "membership change should have forced a new round"

    # parse per-rank logs: epochs must be monotonic across rounds (state
    # survived the restart) and the final round must run at size 3
    lines = []
    for fn in os.listdir(tmp_path):
        if fn.startswith("results."):
            lines += (tmp_path / fn).read_text().splitlines()
    assert any(l.startswith("done epoch=6") for l in lines)
    by_round = {}
    for l in lines:
        if l.startswith("round="):
            parts = dict(kv.split("=") for kv in l.split())
            by_round.setdefault(int(parts["round"]), []).append(
                (int(parts["epoch"]), int(parts["size"]))
            )
    first_round = min(by_round)
    last_round = max(by_round)
    assert first_round != last_round
    assert all(s == 2 for _, s in by_round[first_round])
    assert all(s == 3 for _, s in by_round[last_round])
    max_epoch_first = max(e for e, _ in by_round[first_round])
    min_epoch_last = min(e for e, _ in by_round[last_round])
    assert min_epoch_last >= max_epoch_first, (
        f"state lost across rounds: round {first_round} reached "
        f"{max_epoch_first}, round {last_round} restarted at {min_epoch_last}"
    )


COST_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, sys, time
    t_start = time.perf_counter()
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    import jax
    jax.config.update("jax_platforms", "cpu")
    import jax.numpy as jnp
    import optax
    import horovod_tpu as hvd
    from horovod_tpu.elastic import ObjectState

    hvd.init()
    params = {"w": jnp.zeros((32, 32)), "b": jnp.zeros((32,))}
    tx = hvd.DistributedOptimizer(optax.adam(1e-3))

    def loss_fn(p, batch):
        h = jnp.tanh(batch @ p["w"] + p["b"])
        return jnp.mean((h @ p["w"]) ** 2)

    step = hvd.distributed_train_step(loss_fn, tx)
    opt_state = step.init(params)
    batch = jnp.ones((4 * hvd.size(), 32))
    state = ObjectState(epoch=0)

    first_step = [True]

    @hvd.elastic.run
    def train(state):
        global params, opt_state
        while state.epoch < 4:
            p2, o2, loss = step(params, opt_state, batch)
            params, opt_state = p2, o2
            float(loss)
            if first_step[0]:
                first_step[0] = False
                # init -> first completed step = the round's restart cost
                # (fresh process per round, so this fires once per round)
                cost = time.perf_counter() - t_start
                with open(os.environ["RESULTS_FILE"]
                          + f".{os.environ['HVD_TPU_CROSS_RANK']}", "a") as fh:
                    fh.write(f"round={os.environ['HVD_TPU_ELASTIC_ROUND']} "
                             f"restart_cost_s={cost:.3f}\\n")
            time.sleep(0.4)
            state.epoch += 1
            state.commit()
        return state.epoch

    train(state)
    """
)


@pytest.mark.multiproc
def test_elastic_restart_cost_bounded(tmp_path):
    """Measures the full cost of a membership-change restart (process
    respawn + hvd re-init + recompile + first step) and bounds the
    second round via the persistent XLA compilation cache (reference
    concern: elastic reset cost; TPU twist: recompilation dominates, so
    JAX_COMPILATION_CACHE_DIR turns round-2 compiles into cache reads)."""
    script = tmp_path / "worker.py"
    script.write_text(COST_WORKER_SCRIPT)
    results_file = str(tmp_path / "results")
    cache_dir = str(tmp_path / "xla_cache")

    discovery = ScriptedDiscovery([
        (2.5, {"localhost": 2}),
        (1e9, {"localhost": 3}),
    ])
    driver = ElasticDriver(HostManager(discovery), min_np=2, max_np=4)
    driver.start_discovery()
    rc = driver.run_rounds(
        [sys.executable, str(script)],
        extra_env={
            "RESULTS_FILE": results_file,
            "JAX_COMPILATION_CACHE_DIR": cache_dir,
            "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS": "0",
            "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES": "0",
            **WORKER_ENV,
        },
    )
    assert rc == 0
    assert driver.rounds >= 2

    costs = {}
    for fn in os.listdir(tmp_path):
        if fn.startswith("results."):
            for l in (tmp_path / fn).read_text().splitlines():
                parts = dict(kv.split("=") for kv in l.split())
                rnd = int(parts["round"])
                costs.setdefault(rnd, []).append(
                    float(parts["restart_cost_s"])
                )
    assert len(costs) >= 2, f"need costs from >=2 rounds, got {costs}"
    first, last = min(costs), max(costs)
    c1 = max(costs[first])
    c2 = max(costs[last])
    print(f"elastic restart cost: round{first}={c1:.2f}s "
          f"round{last}={c2:.2f}s (cache dir {cache_dir})")
    # The restart (world resize!) must not cost more than the cold
    # start plus slack: compile work is bounded by the persistent
    # cache.  The slack is generous because this is wall-clock on a
    # shared box — under a fully loaded single-core host (e.g. the
    # whole matrix running) scheduler noise alone can double a round.
    assert c2 <= c1 * 3.0 + 5.0, (first, c1, last, c2)


def test_elastic_worker_failure_blacklists_and_continues(tmp_path):
    """A worker that dies is handled: the driver starts a new round
    (reference fault-tolerance-without-scaling case)."""
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys, time
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
        import jax
        jax.config.update("jax_platforms", "cpu")
        import horovod_tpu as hvd
        hvd.init()
        round_id = int(os.environ["HVD_TPU_ELASTIC_ROUND"])
        rank = int(os.environ["HVD_TPU_CROSS_RANK"])
        host = os.environ["HVD_TPU_HOSTNAME"]
        marker = os.environ["RESULTS_FILE"] + f".round{round_id}.rank{rank}"
        open(marker, "w").write(f"size={hvd.size()} host={host}\\n")
        if round_id == 1 and host == "127.0.0.1":
            os._exit(7)  # simulated crash of the 127.0.0.1 "host"
        time.sleep(1.0)
        """
    ))
    results_file = str(tmp_path / "marks")
    discovery = ScriptedDiscovery([(1e9, {"localhost": 1, "127.0.0.1": 1})])
    driver = ElasticDriver(HostManager(discovery), min_np=1, max_np=2)
    driver.start_discovery()
    rc = driver.run_rounds(
        [sys.executable, str(script)],
        extra_env={"RESULTS_FILE": results_file, **WORKER_ENV},
    )
    assert rc == 0
    assert driver.rounds == 2
    marks = sorted(os.listdir(tmp_path))
    assert any("round2" in m for m in marks)


# ---- deterministic fault injection (HVD_TPU_FAULT_PLAN) ---------------

# This worker exercises the real worker-side fault-tolerance plumbing
# (KV rendezvous + heartbeats + host-update notification + cross-round
# state persistence via elastic_worker) without multi-process jax
# collectives — the CPU backend in CI cannot run those (see
# test_elastic_membership_change, which degrades for the same reason),
# and the subject under test here is the DRIVER's failure handling.
FAULT_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, pickle, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    from horovod_tpu import faults
    from horovod_tpu.runner import elastic_worker

    round_id = int(os.environ["HVD_TPU_ELASTIC_ROUND"])
    rank = int(os.environ["HVD_TPU_CROSS_RANK"])
    size = int(os.environ["HVD_TPU_CROSS_SIZE"])
    host = os.environ["HVD_TPU_HOSTNAME"]

    class Flag:
        updated = False
        def on_hosts_updated(self, ts, res):
            self.updated = True

    flag = Flag()
    mgr = elastic_worker.get_notification_manager()
    mgr.register_listener(flag)
    mgr.init()  # KV connect (retried) + notification poll + heartbeats

    blob = mgr.load_state_blob()
    epoch = pickle.loads(blob) if blob else 0
    out = open(os.environ["RESULTS_FILE"] + f".{rank}", "a")
    target = int(os.environ.get("TARGET_EPOCHS", "10"))
    while epoch < target:
        time.sleep(float(os.environ.get("EPOCH_SECS", "0.5")))
        # the scripted failure site: the env fault plan decides if,
        # when, and on which host/round/rank this fires
        faults.inject("worker.step", rank=rank, round=round_id,
                      host=host, epoch=epoch)
        epoch += 1
        out.write(f"round={round_id} epoch={epoch} size={size}\\n")
        out.flush()
        mgr.save_state_blob(pickle.dumps(epoch))
        if flag.updated:
            out.write(f"restart round={round_id}\\n")
            out.close()
            sys.stdout.flush()
            os._exit(73)  # RESTART_CODE: ack the membership change
    out.write(f"done epoch={epoch}\\n")
    out.close()
    mgr.close()
    """
)


@pytest.mark.faults
def test_injected_crash_blacklist_cooldown_recovery(tmp_path):
    """The acceptance-criteria scenario: a seeded fault plan crashes the
    127.0.0.1 worker mid-round-1; the driver blacklists the host and
    restarts at reduced size; the blacklist cooldown expires while the
    survivors train on; discovery re-admits the host and the final round
    runs at full size to completion — with the whole story visible in
    the metrics counters."""
    metrics.reset_counters()
    script = tmp_path / "worker.py"
    script.write_text(FAULT_WORKER_SCRIPT)
    results_file = str(tmp_path / "results")

    discovery = ScriptedDiscovery([(1e9, {"localhost": 1, "127.0.0.1": 1})])
    driver = ElasticDriver(
        HostManager(discovery, cooldown_s=2.0, cooldown_max_s=8.0),
        min_np=1, max_np=2,
    )
    driver.start_discovery()
    rc = driver.run_rounds(
        [sys.executable, str(script)],
        extra_env={
            "RESULTS_FILE": results_file,
            "TARGET_EPOCHS": "10",
            "EPOCH_SECS": "0.6",
            "HVD_TPU_FAULT_PLAN":
                "worker.step:crash:host=127.0.0.1,round=1,nth=1,code=5",
            **WORKER_ENV,
        },
    )
    assert rc == 0
    assert driver.rounds >= 3, (
        "expected crash round + degraded round + recovered round, got "
        f"{driver.rounds}"
    )

    lines = []
    for fn in os.listdir(tmp_path):
        if fn.startswith("results."):
            lines += (tmp_path / fn).read_text().splitlines()
    assert any(l.startswith("done epoch=10") for l in lines)
    sizes_by_round = {}
    for l in lines:
        if l.startswith("round="):
            parts = dict(kv.split("=") for kv in l.split())
            sizes_by_round.setdefault(int(parts["round"]), set()).add(
                int(parts["size"])
            )
    # degraded round at size 1 while 127.0.0.1 cooled down, then
    # recovery back to size 2
    assert any(s == {1} for s in sizes_by_round.values()), sizes_by_round
    assert sizes_by_round[max(sizes_by_round)] == {2}, sizes_by_round

    got = metrics.get_counters("elastic.")
    assert got.get("elastic.worker_crash", 0) >= 1, got
    assert got.get("elastic.blacklist", 0) >= 1, got
    assert got.get("elastic.unblacklist", 0) >= 1, got
    assert not driver.host_manager.is_blacklisted("127.0.0.1")
    assert driver.host_manager.failure_count("127.0.0.1") == 1


@pytest.mark.faults
def test_injected_hang_detected_by_heartbeat(tmp_path):
    """A worker whose heartbeat freezes (process alive, no progress
    signal) is declared hung by the driver's health monitor, terminated,
    and its host blacklisted — counted as a hang, not a crash."""
    metrics.reset_counters()
    script = tmp_path / "worker.py"
    script.write_text(FAULT_WORKER_SCRIPT)
    results_file = str(tmp_path / "results")

    discovery = ScriptedDiscovery([(1e9, {"localhost": 1, "127.0.0.1": 1})])
    driver = ElasticDriver(
        HostManager(discovery, cooldown_s=300.0),
        min_np=1, max_np=2, hang_timeout_s=2.5,
    )
    driver.start_discovery()
    rc = driver.run_rounds(
        [sys.executable, str(script)],
        extra_env={
            "RESULTS_FILE": results_file,
            "TARGET_EPOCHS": "30",
            "EPOCH_SECS": "0.4",
            # rank 0 lands on 127.0.0.1 (hosts sort lexically); its
            # heartbeat thread freezes in round 1 after registering
            "HVD_TPU_FAULT_PLAN":
                "worker.heartbeat:hang:rank=0,round=1,secs=120",
            **WORKER_ENV,
        },
    )
    assert rc == 0
    got = metrics.get_counters("elastic.")
    assert got.get("elastic.worker_hang", 0) == 1, got
    assert got.get("elastic.worker_crash", 0) == 0, got
    assert driver.host_manager.is_blacklisted("127.0.0.1")
    lines = []
    for fn in os.listdir(tmp_path):
        if fn.startswith("results."):
            lines += (tmp_path / fn).read_text().splitlines()
    assert any(l.startswith("done epoch=30") for l in lines)


@pytest.mark.faults
def test_spawn_flake_absorbed_by_retry(tmp_path):
    """A transient spawn failure (injected in the DRIVER process at the
    driver.spawn site) is retried instead of blacklisting the host."""
    metrics.reset_counters()
    faults.set_plan("driver.spawn:error:nth=1")
    try:
        script = tmp_path / "worker.py"
        script.write_text("import sys; sys.exit(0)\n")
        discovery = ScriptedDiscovery([(1e9, {"localhost": 1})])
        driver = ElasticDriver(
            HostManager(discovery), min_np=1, max_np=1,
            spawn_retry=RetryPolicy(
                max_attempts=2, base_delay_s=0.0, name="elastic.spawn"
            ),
        )
        driver.start_discovery()
        rc = driver.run_rounds([sys.executable, str(script)],
                               extra_env=dict(WORKER_ENV))
    finally:
        faults.set_plan(None)
    assert rc == 0
    assert driver.rounds == 1  # the flake cost a retry, not a round
    assert not driver.host_manager.is_blacklisted("localhost")
    assert metrics.get_counter("retry.elastic.spawn.retries") == 1
    assert metrics.get_counter("faults.injected.driver.spawn.error") == 1


@pytest.mark.faults
def test_round_watchdog_restarts_stuck_round(tmp_path):
    """round_timeout_s bounds a round that makes no progress at all
    (e.g. every worker stuck before hvd.init); the watchdog restarts it
    rather than hanging the job forever."""
    metrics.reset_counters()
    script = tmp_path / "worker.py"
    script.write_text(textwrap.dedent(
        """
        import os, sys, time
        if int(os.environ["HVD_TPU_ELASTIC_ROUND"]) == 1:
            time.sleep(60)
        sys.exit(0)
        """
    ))
    discovery = ScriptedDiscovery([(1e9, {"localhost": 1})])
    driver = ElasticDriver(
        HostManager(discovery), min_np=1, max_np=1,
        round_timeout_s=2.0, cooldown_s=0.1,
    )
    driver.start_discovery()
    t0 = time.monotonic()
    rc = driver.run_rounds([sys.executable, str(script)],
                           extra_env=dict(WORKER_ENV))
    assert rc == 0
    assert time.monotonic() - t0 < 30.0
    assert driver.rounds == 2
    assert metrics.get_counter("elastic.round_timeout") == 1


@pytest.mark.faults
def test_corrupt_checkpoint_falls_back_in_elastic_context(tmp_path):
    """Corruption injected at checkpoint-write time (seeded plan) is
    detected on restore and resume falls back to the last good step,
    with the failure counters visible in metrics output."""
    import horovod_tpu as hvd

    metrics.reset_counters("checkpoint.")
    hvd.init()
    try:
        path = str(tmp_path / "ckpt")
        for s in (1, 2):
            hvd.save_checkpoint(path, {"epoch": s}, step=s,
                                use_orbax=False)
        faults.set_plan("checkpoint.write:corrupt:nth=1")
        try:
            hvd.save_checkpoint(path, {"epoch": 3}, step=3,
                                use_orbax=False)
        finally:
            faults.set_plan(None)
        state, step = hvd.restore_or_init(path, {"epoch": 0})
        assert (state["epoch"], step) == (2, 2)
        got = metrics.get_counters("checkpoint.")
        assert got["checkpoint.corrupt_detected"] >= 1
        assert got["checkpoint.fallback"] >= 1
    finally:
        hvd.shutdown()


# ---- unified telemetry: the PR-2 acceptance scenario ------------------

TELEMETRY_WORKER_SCRIPT = textwrap.dedent(
    """
    import os, pickle, sys, time
    os.environ["JAX_PLATFORMS"] = "cpu"
    from horovod_tpu import faults, metrics
    from horovod_tpu.runner import elastic_worker
    from horovod_tpu.utils.timeline import Timeline

    round_id = int(os.environ["HVD_TPU_ELASTIC_ROUND"])
    rank = int(os.environ["HVD_TPU_CROSS_RANK"])
    size = int(os.environ["HVD_TPU_CROSS_SIZE"])
    host = os.environ["HVD_TPU_HOSTNAME"]

    mgr = elastic_worker.get_notification_manager()
    mgr.init()  # KV connect + heartbeats (which push metric snapshots)

    tl = Timeline(
        os.environ["TRACE_DIR"] + f"/timeline.rank{rank}.json", rank=rank
    )
    blob = mgr.load_state_blob()
    epoch = pickle.loads(blob) if blob else 0
    target = int(os.environ.get("TARGET_EPOCHS", "6"))
    while epoch < target:
        time.sleep(float(os.environ.get("EPOCH_SECS", "0.4")))
        faults.inject("worker.step", rank=rank, round=round_id,
                      host=host, epoch=epoch)
        epoch += 1
        metrics.inc_counter("train.steps")
        metrics.observe("train.step_seconds", 0.4)
        tl.record_op(f"epoch{epoch}", "STEP", 0)
        mgr.save_state_blob(pickle.dumps(epoch))
    tl.close()
    mgr.close()
    """
)


@pytest.mark.faults
def test_fault_injected_run_produces_postmortem_record(tmp_path):
    """PR-2 acceptance criteria end to end: one fault-injected elastic
    run (PR 1's HVD_TPU_FAULT_PLAN) yields (1) per-rank timelines that
    merge into a valid Chrome trace with rank lanes, (2) a live
    Prometheus scrape from the driver's /metrics endpoint carrying
    hvd_tpu_ counter/gauge/histogram families (driver-local and
    worker-pushed), and (3) a JSONL elastic event log that reconstructs
    the injected failure sequence in order."""
    import json as _json
    import urllib.request

    from horovod_tpu import events

    metrics.reset_counters()
    event_log = str(tmp_path / "elastic_events.jsonl")
    events.set_event_log(events.EventLog(event_log))
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    script = tmp_path / "worker.py"
    script.write_text(TELEMETRY_WORKER_SCRIPT)

    discovery = ScriptedDiscovery([(1e9, {"localhost": 1, "127.0.0.1": 1})])
    driver = ElasticDriver(
        HostManager(discovery, cooldown_s=1.0, cooldown_max_s=4.0),
        min_np=1, max_np=2, telemetry_port=0,
    )
    driver.start_discovery()
    scrapes = []

    def run():
        rc = driver.run_rounds(
            [sys.executable, str(script)],
            extra_env={
                "TRACE_DIR": str(trace_dir),
                "TARGET_EPOCHS": "6",
                "EPOCH_SECS": "0.4",
                "HVD_TPU_ELASTIC_EVENT_LOG": event_log,
                "HVD_TPU_FAULT_PLAN":
                    "worker.step:crash:host=127.0.0.1,round=1,nth=1,code=9",
                **WORKER_ENV,
            },
        )
        scrapes.append(("rc", rc))

    t = threading.Thread(target=run)
    t.start()
    try:
        deadline = time.monotonic() + 60.0
        got_worker_series = False
        while t.is_alive() and time.monotonic() < deadline:
            srv = driver._telemetry
            if srv is not None:
                try:
                    body = urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/metrics", timeout=2
                    ).read().decode()
                    scrapes.append(("metrics", body))
                    if 'rank="' in body:
                        got_worker_series = True
                    health = _json.loads(urllib.request.urlopen(
                        f"http://127.0.0.1:{srv.port}/health", timeout=2
                    ).read())
                    scrapes.append(("health", health))
                except Exception:
                    pass  # endpoint races the round teardown
            time.sleep(0.5)
    finally:
        t.join(timeout=60)
        events.set_event_log(None)
    assert not t.is_alive(), "elastic run did not finish"
    assert ("rc", 0) in scrapes

    # (2) Prometheus scrape: hvd_tpu_ families of all three kinds, from
    # the driver registry and from worker pushes (rank-labeled).
    bodies = [b for k, b in scrapes if k == "metrics"]
    assert bodies, "never scraped /metrics"
    final = bodies[-1]
    assert "hvd_tpu_elastic_rounds_total" in final          # counter
    assert "hvd_tpu_elastic_round " in final or \
        "hvd_tpu_elastic_round{" in final                    # gauge
    assert got_worker_series, "no worker-pushed rank series ever seen"
    joined = "\n".join(bodies)
    assert "hvd_tpu_train_steps_total{rank=" in joined
    assert "hvd_tpu_train_step_seconds_bucket" in joined     # histogram
    healths = [h for k, h in scrapes if k == "health"]
    assert healths and all("round" in h for h in healths)

    # (3) the event log reconstructs the injected failure sequence
    evs = events.read_events(event_log)
    names = [e["event"] for e in evs]
    assert "round_start" in names and "worker_crash" in names
    assert "blacklist" in names and "round_end" in names
    i_start = names.index("round_start")
    i_crash = names.index("worker_crash")
    i_black = names.index("blacklist")
    assert i_start < i_crash < i_black, names
    crash = evs[i_crash]
    assert crash["host"] == "127.0.0.1" and crash["verdict"] == "crash"
    assert crash["round"] == 1
    # both clocks present; driver-side order is monotonic
    driver_evs = [e for e in evs if e["pid"] == os.getpid()]
    monos = [e["mono_ts"] for e in driver_evs]
    assert monos == sorted(monos)
    # the run recovered: a later round started after the blacklist
    later_rounds = [e for e in evs[i_black:] if e["event"] == "round_start"]
    assert later_rounds and later_rounds[-1]["round"] >= 2

    # (1) per-rank timelines merge into one valid Chrome trace
    traces = sorted(
        str(trace_dir / f) for f in os.listdir(trace_dir)
        if f.endswith(".json")
    )
    assert len(traces) >= 2, traces
    merged = hvd_merge(traces)
    _json.loads(_json.dumps(merged))  # valid JSON (Perfetto-loadable)
    lanes = {e["pid"] for e in merged["traceEvents"]}
    assert lanes == {0, 1}, lanes
    steps = [e for e in merged["traceEvents"] if e.get("cat") == "STEP"]
    assert steps, "no per-epoch step events in the merged trace"


def hvd_merge(paths):
    from horovod_tpu.utils.timeline import merge_timeline_files

    return merge_timeline_files(paths)
