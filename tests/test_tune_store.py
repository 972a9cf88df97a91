"""Persistent schedule autotuning: the ScheduleStore (key derivation,
keep-best merge, corruption tolerance, stale invalidation), the
ScheduleTuner warm-start path (converged at window 0 on a hit, zero
exploration windows, write-back on a miss), the /schedules fleet
endpoint, the driver/worker KV seeding hooks, and the bench probe-cache
knob fingerprint."""

import json
import urllib.request

import pytest

from horovod_tpu import metrics, sched
from horovod_tpu.sched.store import (
    ScheduleStore,
    knob_fingerprint,
    make_key,
)

pytestmark = [pytest.mark.tune, pytest.mark.sched]

SIG = ("allreduce", (((0, 1), 4096, ("float32",), False, "off", "flat"),))


@pytest.fixture(autouse=True)
def _clean(monkeypatch, tmp_path):
    metrics.reset_counters("sched.tune")
    metrics.reset_counters("train.")
    monkeypatch.delenv("HVD_TPU_TUNE_DB", raising=False)
    yield
    metrics.reset_counters("sched.tune")
    metrics.reset_counters("train.")


def _drive_to_convergence(tuner, windows=8):
    """Feed synthetic registry windows until the tuner converges."""
    for _ in range(windows):
        if tuner.converged:
            break
        tuner.begin_window()
        metrics.inc_counter("train.steps", 10)
        metrics.observe("train.step_seconds", 0.5)
        metrics.set_gauge("sched.bytes_per_step", 1000.0)
        tuner.end_window()
    return tuner


# ------------------------------------------------------------- store

class TestScheduleStore:
    def test_record_lookup_roundtrip(self, tmp_path):
        db = tmp_path / "tune.json"
        store = ScheduleStore(str(db))
        key = make_key(SIG)
        store.record(key, bucket_bytes=1 << 20, wire="int8",
                     lowering="flat", score=7.0)
        # a fresh store instance reads the persisted entry
        entry = ScheduleStore(str(db)).lookup(key)
        assert entry["bucket_bytes"] == 1 << 20
        assert entry["wire"] == "int8"
        assert entry["lowering"] == "flat"
        assert entry["score"] == 7.0
        # on-disk schema carries version + provenance
        data = json.loads(db.read_text())
        assert data["version"] == 1
        assert data["entries"][key]["jax"]

    def test_key_covers_all_identity_components(self, monkeypatch):
        base = make_key(SIG)
        assert make_key(SIG) == base  # deterministic
        assert make_key(("other",)) != base
        assert make_key(SIG, topo_spec="2x4(4)") != base
        assert make_key(SIG, jaxver="9.9.9") != base
        monkeypatch.setenv("HVD_TPU_SCHED_WIRE", "fp8")
        assert make_key(SIG) != base  # knob fingerprint changed

    def test_knob_fingerprint_tracks_sched_wire_topo_quant(
        self, monkeypatch
    ):
        base = knob_fingerprint()
        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        f1 = knob_fingerprint()
        assert f1 != base
        monkeypatch.setenv("HOROVOD_QUANT_BLOCK", "128")
        assert knob_fingerprint() != f1
        # unrelated env never moves the fingerprint
        monkeypatch.setenv("HVD_TPU_ELASTIC", "1")
        monkeypatch.setenv("SOME_RANDOM_VAR", "x")
        assert knob_fingerprint() == knob_fingerprint()
        monkeypatch.delenv("HOROVOD_QUANT_BLOCK")
        assert knob_fingerprint() == f1

    def test_merge_keeps_best_score(self, tmp_path):
        store = ScheduleStore(str(tmp_path / "db.json"))
        key = "k" * 64
        store.record(key, bucket_bytes=100, wire="off", lowering="flat",
                     score=5.0)
        n = store.merge({key: {"bucket_bytes": 200, "wire": "bf16",
                               "lowering": "flat", "score": 9.0}})
        assert n == 1
        assert store.lookup(key)["bucket_bytes"] == 200
        # a worse entry never clobbers the stored winner
        n = store.merge({key: {"bucket_bytes": 300, "wire": "off",
                               "lowering": "flat", "score": 1.0}})
        assert n == 0
        assert store.lookup(key)["bucket_bytes"] == 200

    def test_merge_rejects_malformed_entries(self, tmp_path):
        store = ScheduleStore(str(tmp_path / "db.json"))
        assert store.merge({"k": {"score": 1.0}}) == 0  # missing fields
        assert store.merge("not a dict") == 0
        assert store.entries() == {}

    def test_corrupted_db_ignored_with_one_warning(self, tmp_path):
        from horovod_tpu.sched import store as store_mod

        db = tmp_path / "garbage.json"
        db.write_text("{definitely not json")
        s1 = ScheduleStore(str(db))
        s2 = ScheduleStore(str(db))
        assert s1.entries() == {} and s2.entries() == {}
        # log-once: the path registers in the warned set exactly once
        # (the horovod_tpu logger does not propagate, so the guard set
        # is the observable), while every load attempt still counts
        assert str(db) in store_mod._warned_paths
        assert metrics.get_counter("sched.tune.db_corrupt") >= 2
        # and a later record() rewrites the file cleanly
        s1.record("a" * 64, bucket_bytes=1, wire="off", lowering="flat",
                  score=1.0)
        assert json.loads(db.read_text())["version"] == 1

    def test_wrong_shape_json_ignored(self, tmp_path):
        db = tmp_path / "shape.json"
        db.write_text(json.dumps({"entries": [1, 2, 3]}))
        assert ScheduleStore(str(db)).entries() == {}
        db.write_text(json.dumps(
            {"entries": {"k": {"bucket_bytes": 1, "wire": "off",
                               "lowering": "flat"},
                         "bad": "not-an-object"}}
        ))
        assert list(ScheduleStore(str(db)).entries()) == ["k"]

    def test_stale_entry_invalidated_by_cost_model(self, tmp_path):
        from horovod_tpu import topo
        from horovod_tpu.topo.model import Topology

        topo.reset()
        topo.set_topology_override(Topology(num_slices=2, slice_size=4))
        try:
            store = ScheduleStore(str(tmp_path / "db.json"),
                                  stale_factor=4.0)
            key = "s" * 64
            store.record(key, bucket_bytes=1 << 20, wire="off",
                         lowering="hier", score=3.0)
            assert store.lookup(key) is not None
            # fake a recorded price 100x off today's model
            entry = store.entries()[key]
            entry["pred_cost_s"] = entry["pred_cost_s"] * 100.0
            store.merge({key: dict(entry, score=entry["score"] + 1)})
            assert store.lookup(key) is None
            assert metrics.get_counter("sched.tune.db_stale") == 1
        finally:
            topo.reset()

    def test_in_memory_store_without_path(self):
        store = ScheduleStore(None)
        store.record("m" * 64, bucket_bytes=7, wire="off",
                     lowering="flat", score=1.0)
        assert store.lookup("m" * 64)["bucket_bytes"] == 7


# ------------------------------------------------------ tuner warm start

class TestTunerWarmStart:
    def test_cold_then_warm(self, tmp_path, monkeypatch):
        db = tmp_path / "tune.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        # run 1 (cold): explores, converges, writes back
        t1 = sched.ScheduleTuner(warmup_windows=2, store="env",
                                 store_key=SIG)
        assert not t1.converged
        assert metrics.get_counter("sched.tune.db_miss") == 1
        _drive_to_convergence(t1)
        assert t1.converged
        assert metrics.get_counter("sched.tune.db_store") == 1
        assert db.exists()

        # run 2 (warm): converged at window 0, zero exploration windows
        metrics.reset_counters("sched.tune")
        t2 = sched.ScheduleTuner(warmup_windows=2, store="env",
                                 store_key=SIG)
        assert t2.converged  # window 0
        assert metrics.get_counter("sched.tune.db_hit") == 1
        assert t2.tuner._windows == 0  # no exploration ever ran
        assert t2.bucket_bytes() == t1.bucket_bytes()
        assert t2.wire() == t1.wire()
        assert t2.lowering() == t1.lowering()
        # warm windows score but never re-write the DB
        _drive_to_convergence(t2, windows=1)
        assert metrics.get_counter("sched.tune.db_store") == 0

    def test_warm_start_applies_stored_schedule(self, tmp_path,
                                                monkeypatch):
        db = tmp_path / "tune.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        store = ScheduleStore(str(db))
        key = make_key(SIG)
        store.record(key, bucket_bytes=512, wire="off", lowering="flat",
                     score=42.0)
        tuner = sched.ScheduleTuner(explore_wire=True, store="env",
                                    store_key=SIG)
        assert tuner.converged
        schedule = sched.build_schedule([256, 256, 512],
                                        ["float32"] * 3)
        stamped = tuner.apply(schedule)
        assert all(b.wire == "off" for b in stamped.buckets)
        assert all(b.lowering == "flat" for b in stamped.buckets)

    def test_corrupted_db_never_crashes_tuner(self, tmp_path,
                                              monkeypatch):
        db = tmp_path / "tune.json"
        db.write_text("\x00\x01 garbage \xff")
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        tuner = sched.ScheduleTuner(warmup_windows=2, store="env",
                                    store_key=SIG)
        assert not tuner.converged  # treated as a miss
        _drive_to_convergence(tuner)
        assert tuner.converged
        # convergence rewrote the DB into a valid file
        assert json.loads(db.read_text())["version"] == 1

    def test_no_db_env_means_no_store(self, monkeypatch):
        monkeypatch.delenv("HVD_TPU_TUNE_DB", raising=False)
        tuner = sched.ScheduleTuner(warmup_windows=2, store="env",
                                    store_key=SIG)
        assert tuner._store is None
        _drive_to_convergence(tuner)
        assert tuner.converged
        assert metrics.get_counter("sched.tune.db_store") == 0
        assert metrics.get_counter("sched.tune.db_hit") == 0
        assert metrics.get_counter("sched.tune.db_miss") == 0

    def test_unknown_stored_values_degrade_safely(self, tmp_path):
        store = ScheduleStore(str(tmp_path / "db.json"))
        key = make_key(SIG)
        store.record(key, bucket_bytes=4096, wire="exotic-wire",
                     lowering="exotic-lowering", score=1.0)
        tuner = sched.ScheduleTuner(store=store, store_key=SIG)
        assert tuner.converged
        assert tuner.wire() == "off"
        assert tuner.lowering() == "auto"


# -------------------------------------------------- /schedules endpoint

class TestSchedulesEndpoint:
    def _server(self, store):
        from horovod_tpu.runner.telemetry_http import TelemetryServer

        return TelemetryServer(port=0, bind_host="127.0.0.1",
                               schedule_store=store)

    def test_get_and_post(self, tmp_path):
        store = ScheduleStore(str(tmp_path / "db.json"))
        key = "a" * 64
        store.record(key, bucket_bytes=1 << 18, wire="bf16",
                     lowering="flat", score=3.0)
        srv = self._server(store)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            got = json.load(urllib.request.urlopen(f"{base}/schedules"))
            assert got["entries"][key]["wire"] == "bf16"
            got = json.load(urllib.request.urlopen(
                f"{base}/schedules?key={key}"
            ))
            assert list(got["entries"]) == [key]
            got = json.load(urllib.request.urlopen(
                f"{base}/schedules?key={'f' * 64}"
            ))
            assert got["entries"] == {}
            # POST merges keep-best
            body = json.dumps({"entries": {
                "b" * 64: {"bucket_bytes": 64, "wire": "off",
                           "lowering": "flat", "score": 1.0},
            }}).encode()
            req = urllib.request.Request(
                f"{base}/schedules", data=body, method="POST"
            )
            assert json.load(urllib.request.urlopen(req))["merged"] == 1
            assert "b" * 64 in store.entries()
        finally:
            srv.stop()

    def test_no_store_404s(self):
        srv = self._server(None)
        try:
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/schedules"
                )
            assert exc.value.code == 404
        finally:
            srv.stop()

    def test_bad_post_is_400_and_survives(self, tmp_path):
        store = ScheduleStore(str(tmp_path / "db.json"))
        srv = self._server(store)
        try:
            base = f"http://127.0.0.1:{srv.port}"
            req = urllib.request.Request(
                f"{base}/schedules", data=b"not json", method="POST"
            )
            with pytest.raises(urllib.error.HTTPError) as exc:
                urllib.request.urlopen(req)
            assert exc.value.code == 400
            # server is still alive
            assert json.load(urllib.request.urlopen(
                f"{base}/schedules"
            )) == {"entries": {}}
        finally:
            srv.stop()


# ------------------------------------------------ KV seeding plumbing

class _FakeControl:
    """Dict-backed stand-in for the rendezvous KV client."""

    def __init__(self):
        self.kv = {}

    def put(self, scope, key, blob):
        self.kv[(scope, key)] = blob

    def get(self, scope, key, timeout_ms=0):
        return self.kv.get((scope, key))


class TestKVSeeding:
    def test_driver_publish_and_collect(self, tmp_path, monkeypatch):
        from horovod_tpu.elastic.discovery import FixedHosts, HostManager
        from horovod_tpu.runner.elastic_driver import ElasticDriver
        from horovod_tpu.runner.hosts import SlotInfo

        monkeypatch.setenv("HVD_TPU_TUNE_DB",
                           str(tmp_path / "driver.json"))
        driver = ElasticDriver(
            HostManager(FixedHosts({"localhost": 1})), min_np=1
        )
        driver.schedule_store().record(
            "d" * 64, bucket_bytes=1 << 16, wire="off", lowering="flat",
            score=2.0,
        )
        control = _FakeControl()
        driver._publish_schedules(control)
        published = json.loads(control.kv[("__schedules__", "db")])
        assert "d" * 64 in published["entries"]

        # a worker push at round end folds into the driver store
        driver._last_assignments = [
            SlotInfo(hostname="localhost", rank=0, local_rank=0,
                     cross_rank=0, local_size=1, cross_size=1, size=1)
        ]
        control.put("__schedules__", "rank_0", json.dumps({"entries": {
            "w" * 64: {"bucket_bytes": 1 << 22, "wire": "int8",
                       "lowering": "flat", "score": 9.0},
        }}).encode())
        driver._collect_schedules(control)
        assert "w" * 64 in driver.schedule_store().entries()
        assert metrics.get_counter("sched.tune.db_collected") == 1

    def test_worker_fetch_seeds_local_db(self, tmp_path, monkeypatch):
        from horovod_tpu.runner.elastic_worker import (
            WorkerNotificationManager,
        )

        local = tmp_path / "worker.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(local))
        mgr = WorkerNotificationManager()
        mgr._client = _FakeControl()
        mgr._client.put("__schedules__", "db", json.dumps({"entries": {
            "f" * 64: {"bucket_bytes": 1 << 20, "wire": "bf16",
                       "lowering": "flat", "score": 4.0},
        }}).encode())
        mgr._fetch_schedules()
        assert metrics.get_counter("sched.tune.kv_seeded") == 1
        assert "f" * 64 in ScheduleStore(str(local)).entries()
        # ...and the heartbeat-side push mirrors a local change back
        mgr._push_schedules(mgr._client)
        pushed = json.loads(mgr._client.kv[("__schedules__", "rank_0")])
        assert "f" * 64 in pushed["entries"]

    def test_worker_fetch_without_db_is_noop(self, monkeypatch):
        from horovod_tpu.runner.elastic_worker import (
            WorkerNotificationManager,
        )

        monkeypatch.delenv("HVD_TPU_TUNE_DB", raising=False)
        mgr = WorkerNotificationManager()
        mgr._client = _FakeControl()
        mgr._fetch_schedules()  # must not raise
        mgr._push_schedules(mgr._client)
        assert mgr._client.kv == {}
