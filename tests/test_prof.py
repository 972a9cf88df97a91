"""Profiling plane (prof/): compiled-step introspection, the step clock
and host-gap attribution, online MFU, perf-regression sentinel, /prof.

Contracts under test:

* **Introspection** — wrapping a jitted fn records XLA cost-analysis
  FLOPs/bytes, compile wall-clock, and call counts per program key,
  returns bitwise-identical results, recompiles once per argument
  signature, and degrades to the raw fn (one attempt, forever) when
  AOT lowering is impossible.
* **Host gap** — ``attribute()`` is pure math on a span tree: busy is
  the *union* of executor-call intervals (overlap never double counts),
  gap is the call's duration minus busy, the step's time is the
  interval the span carries (else its duration) and the gap's share is
  of that, dispatches count exec/dispatch spans plus the service-loop
  counter delta, and tenant busy splits by the trace tenant slot.
  ``TrainStep`` observes the same two clocks as ``train.step_seconds``
  (entry to entry) and ``train.dispatch_seconds`` (the call).
* **MFU** — cost-analysis FLOPs over the step's time (the interval,
  never the dispatch) against a pinned peak gives the exact expected
  ratio, not clamped, per workload and per tenant; ``publish()`` is
  the bench-side entry point.
* **Sentinel** — the baseline store roundtrips through the
  ScheduleStore machinery (keep-best keeps the fastest run), an
  identical second run verdicts ``ok``, a slower run verdicts
  ``regression`` (gauge + counter), and the no-DB/no-data paths stay
  inert.
* **Endpoint** — ``GET /prof`` answers 200 with the full structure
  even on an empty plane; worker snapshots fold into a per-rank
  digest; ``GET /health`` carries the probe doctor's verdict without
  flipping health status.
* **Neutrality** — TrainStep losses are bitwise identical with
  profiling on vs off (AOT runs the same HLO the jit call would).
* **Retention** — flight-recorder dumps prune oldest-first to
  ``HVD_TPU_TRACE_DUMP_KEEP`` per rank; svc cache entries carry their
  accumulated compile bill and rank by it.
"""

import json
import os
import time
import urllib.request
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import pytest

import horovod_tpu as hvd
from horovod_tpu import metrics, prof, svc, trace, xir
from horovod_tpu.prof import baseline, capture, hostgap, introspect, mfu, peak
from horovod_tpu.runner import telemetry_http
from horovod_tpu.runner.telemetry_http import TelemetryServer
from horovod_tpu.runtime import WORLD_AXIS
from horovod_tpu.sched import store as store_mod
from horovod_tpu.trace.recorder import FlightRecorder
from horovod_tpu.trace.tracer import Span

pytestmark = pytest.mark.prof

N = 8


@pytest.fixture(autouse=True)
def _prof_isolation():
    prof.reset()
    metrics.reset_counters("prof.")
    metrics.reset_counters("svc.")
    metrics.reset_counters("trace.")
    for g in ("prof.mfu", "prof.flops", "prof.bytes_accessed",
              "prof.peak_hbm_bytes", "prof.host_gap_frac",
              "prof.dispatches_per_step", "prof.regression",
              "prof.flops_per_step", "prof.emitted_ops"):
        metrics.clear_gauge(g)
    trace.set_level_override("summary")
    yield
    prof.set_enabled_override(None)
    prof.reset()
    trace.set_level_override(None)
    trace.reset()
    svc.reset_service()
    for var in ("HVD_TPU_PROF_DB", "HVD_TPU_PROF_CHECK_EVERY",
                "HVD_TPU_TRACE_DIR", "HVD_TPU_TRACE_DUMP_KEEP"):
        os.environ.pop(var, None)


def _span(name, phase, t0, t1, tenant="", **attrs):
    s = Span(name, phase, t0, tenant=tenant, attrs=attrs or None)
    s.t1 = t1
    return s


def _step_span(wall, children=(), **attrs):
    root = _span("step", "step", 0.0, wall, **attrs)
    root.children.extend(children)
    return root


# ---------------------------------------------------------------- intro


class TestIntrospection:
    def test_wrap_records_cost_and_matches_raw(self):
        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        ex = introspect.wrap(f, key="intro_a", kind="step", workload="wa")
        x = jnp.full((16, 16), 0.25, jnp.float32)
        out = ex(x)
        assert float(out) == float(f(x))  # AOT runs the jit's HLO
        rec = introspect.get("intro_a")
        assert rec is not None and rec["compiles"] == 1
        assert rec["flops"] is not None and rec["flops"] > 0
        assert rec["compile_seconds"] > 0
        assert ex.compiled().as_text().startswith("HloModule")
        assert metrics.get_counter("prof.compiles") == 1
        assert metrics.get_gauge(
            "prof.flops", {"key": "intro_a", "kind": "step"}) == rec["flops"]

    def test_compile_record_in_three_parts(self):
        # jit's own three stages, each on the record and under a span
        # of its own; compile_seconds stays their sum.
        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        ex = introspect.wrap(f, key="intro_p", kind="step")
        with trace.span("outer", "test") as outer:
            ex(jnp.ones((8, 8), jnp.float32))
        rec = introspect.get("intro_p")
        parts = [rec[k] for k in ("trace_seconds", "lower_seconds",
                                  "backend_seconds")]
        assert all(p > 0 for p in parts)
        assert rec["compile_seconds"] == pytest.approx(sum(parts))
        assert rec["cache_hit"] is False  # no persistent cache here
        names = [c.name for c in outer.children]
        assert names == ["compile_trace", "compile_lower",
                         "compile_backend", "exec.step"]  # workload = kind
        assert outer.children[2].attrs["cache_hit"] is False
        assert [c.dur for c in outer.children[:3]] == pytest.approx(
            parts, abs=1e-3)
        ex(jnp.ones((4, 4), jnp.float32))  # a second variant adds up
        again = introspect.get("intro_p")
        assert again["compiles"] == 2
        assert again["compile_seconds"] == pytest.approx(
            again["trace_seconds"] + again["lower_seconds"]
            + again["backend_seconds"])
        assert again["compile_seconds"] > rec["compile_seconds"]

    def test_compiles_once_per_signature(self):
        f = jax.jit(lambda x: x * 2.0)
        ex = introspect.wrap(f, key="intro_b", kind="step")
        ex(jnp.ones((4,), jnp.float32))
        ex(jnp.ones((4,), jnp.float32))
        assert introspect.get("intro_b")["compiles"] == 1
        assert introspect.get("intro_b")["calls"] == 2
        ex(jnp.ones((8,), jnp.float32))  # new shape -> one more compile
        assert introspect.get("intro_b")["compiles"] == 2

    def test_unlowerable_fn_falls_back_forever(self):
        calls = []

        def raw(x):
            calls.append(1)
            return x + 1

        ex = introspect.wrap(raw, key="intro_c", kind="step")
        assert ex(1) == 2 and ex(5) == 6  # results survive the fallback
        assert len(calls) == 2
        assert introspect.get("intro_c")["fallback"] is True
        assert metrics.get_counter("prof.fallbacks") >= 1
        assert metrics.get_counter("prof.compiles") == 0

    class _FakeJit:
        """A lowerable whose Compiled raises ``exc`` when called."""

        def __init__(self, exc=None, compile_exc=None):
            self.exc, self.compile_exc = exc, compile_exc
            self.calls, self.lowers = [], 0

        def trace(self, *args):
            return self

        def lower(self):
            self.lowers += 1
            return self

        def compile(self):
            if self.compile_exc is not None:
                raise self.compile_exc
            outer = self

            class Compiled:
                def cost_analysis(self):
                    return [{"flops": 1.0}]

                def memory_analysis(self):
                    return None

                def __call__(self, *args):
                    raise outer.exc

            return Compiled()

        def __call__(self, x):
            self.calls.append(x)
            return x + 1

    def test_argument_check_failure_falls_back(self):
        # The Compiled's pre-execution argument check (layout/sharding
        # drift the signature key cannot see) raises ValueError or
        # TypeError before anything runs: demote to the raw fn.
        fake = self._FakeJit(exc=ValueError("input shardings disagree"))
        ex = introspect.wrap(fake, key="intro_e", kind="step")
        assert ex(1) == 2 and ex(2) == 3  # results survive the fallback
        assert fake.calls == [1, 2]  # raw fn served both calls
        assert introspect.get("intro_e")["fallback"] is True
        assert metrics.get_counter("prof.fallbacks") >= 1

    def test_execution_failure_surfaces_as_itself(self):
        # An error from the execution itself (device OOM, a failed
        # collective) arrives after donation: retrying through the raw
        # fn would report deleted buffers instead of the real fault.
        fake = self._FakeJit(exc=RuntimeError("RESOURCE_EXHAUSTED"))
        ex = introspect.wrap(fake, key="intro_f", kind="step")
        with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
            ex(1)
        assert fake.calls == []  # never retried
        assert introspect.get("intro_f")["fallback"] is False
        assert metrics.get_counter("prof.fallbacks") == 0

    def test_compile_failure_surfaces_once(self):
        # A program that cannot compile fails as jit would have — not
        # swallowed, and not compiled a second time through the raw fn.
        fake = self._FakeJit(compile_exc=RuntimeError("Mosaic failed"))
        ex = introspect.wrap(fake, key="intro_g", kind="step")
        with pytest.raises(RuntimeError, match="Mosaic failed"):
            ex(1)
        assert fake.lowers == 1 and fake.calls == []
        assert metrics.get_counter("prof.fallbacks") == 0
        assert metrics.get_counter("prof.compiles") == 0

    def test_off_returns_fn_unwrapped(self):
        prof.set_enabled_override(False)
        f = jax.jit(lambda x: x)
        assert introspect.wrap(f, key="intro_d", kind="step") is f

    def test_ranked_orders_by_compile_cost(self):
        fa = jax.jit(lambda x: x + 1.0)
        fb = jax.jit(lambda x: jnp.tanh(x @ x))
        ea = introspect.wrap(fa, key="rank_a", kind="step")
        eb = introspect.wrap(fb, key="rank_b", kind="step")
        ea(jnp.ones((4,), jnp.float32))
        eb(jnp.ones((32, 32), jnp.float32))
        rows = introspect.ranked()
        assert [r["key"] for r in rows[:2]] == sorted(
            ("rank_a", "rank_b"),
            key=lambda k: introspect.get(k)["compile_seconds"],
            reverse=True,
        )


# -------------------------------------------------------------- hostgap


class TestHostGap:
    def test_attribute_union_gap_dispatch_tenant(self):
        root = _step_span(1.0, [
            _span("exec.a", "exec", 0.1, 0.4, tenant="ta"),
            # overlaps the exec span: union covers [0.1, 0.6], not 0.6s
            _span("disp", "dispatch", 0.3, 0.6),
            _span("rs", "rs_ici", 0.7, 0.8, tenant="tb"),
            # rail attribution without a rail phase name still counts
            _span("x", "custom", 0.85, 0.9, rail="ici"),
            # host-side phase: never device-busy
            _span("neg", "negotiate", 0.0, 1.0),
        ])
        stats = hostgap.attribute(root)
        assert stats["wall_s"] == pytest.approx(1.0)
        assert stats["step_s"] == pytest.approx(1.0)  # no interval: its own
        assert stats["busy_s"] == pytest.approx(0.5 + 0.1 + 0.05)
        assert stats["gap_s"] == pytest.approx(1.0 - 0.65)
        assert stats["dispatches"] == 2  # exec + dispatch, not rails
        assert stats["tenant_busy_s"] == {
            "ta": pytest.approx(0.3), "tb": pytest.approx(0.1)}

    def test_busy_capped_at_wall(self):
        root = _step_span(0.2, [_span("e", "exec", 0.0, 5.0)])
        stats = hostgap.attribute(root)
        assert stats["busy_s"] == pytest.approx(0.2)
        assert stats["gap_s"] == 0.0

    def test_gap_is_inside_the_call_and_its_share_is_of_the_step(self):
        # A 10 ms call with 4 ms under the executor, in a step that
        # lasts 200 ms entry to entry: the gap is the call's other
        # 6 ms, a 3% share of the step, never 60% of the dispatch.
        root = _step_span(0.010, [_span("e", "exec", 0.002, 0.006)],
                          interval_s=0.2)
        stats = hostgap.on_step(root)
        assert stats["wall_s"] == pytest.approx(0.010)
        assert stats["step_s"] == 0.2
        assert stats["gap_s"] == pytest.approx(0.006)
        assert metrics.get_gauge("prof.host_gap_frac") == pytest.approx(
            0.006 / 0.2)
        gap = metrics.get_histogram("prof.host_gap_seconds")
        assert gap["count"] == 1 and gap["sum"] == pytest.approx(0.006)
        # the sentinel's clock is the interval, never the dispatch
        assert hostgap.step_p50() == 0.2
        # a span that carries no interval keeps its own duration
        hostgap.reset()
        hostgap.on_step(_step_span(0.010, [_span("e", "exec", 0.002, 0.006)]))
        assert metrics.get_gauge("prof.host_gap_frac") == pytest.approx(0.6)
        assert hostgap.step_p50() == pytest.approx(0.010)

    def test_on_step_adds_svc_dispatch_delta(self):
        first = hostgap.on_step(_step_span(0.1))
        assert first["dispatches"] == 0  # no counter history yet
        metrics.inc_counter("svc.dispatches", 3)
        second = hostgap.on_step(
            _step_span(0.1, [_span("e", "exec", 0.0, 0.05)]))
        assert second["dispatches"] == 1 + 3
        assert metrics.get_gauge("prof.dispatches_per_step") == 4.0
        summ = hostgap.summary()
        assert summ["steps"] == 2
        assert summ["step_p50_s"] == pytest.approx(0.1)

    def test_on_step_disabled_is_none(self):
        prof.set_enabled_override(False)
        assert hostgap.on_step(_step_span(0.1)) is None
        assert hostgap.summary()["steps"] == 0


# ------------------------------------------------------------------ mfu


class TestMFU:
    def _introspected(self, key):
        f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
        ex = introspect.wrap(f, key=key, kind="step", workload=key)
        ex(jnp.full((32, 32), 0.5, jnp.float32))
        return introspect.get(key)["flops"]

    def test_mfu_exact_against_pinned_peak(self):
        flops = self._introspected("mfu_w")
        assert flops and flops > 0
        peak.set_peak_override(1.0)  # 1 TFLOP/s
        wall = 2.0  # the interval; the span itself lasts 5 ms
        root = _step_span(0.005, [
            _span("exec.mfu_w", "exec", 0.0, 0.005, tenant="t0",
                  program="mfu_w"),
        ], interval_s=wall)
        mfu.on_step(root, hostgap.attribute(root))
        expect = flops / (wall * 1.0 * 1e12)
        assert metrics.get_gauge("prof.mfu", {"workload": "mfu_w"}) == expect
        assert metrics.get_gauge("prof.mfu", {"tenant": "t0"}) == expect
        assert mfu.observed() == expect
        assert metrics.get_gauge("prof.flops_per_step") == flops

    def test_mfu_over_one_is_shown_not_clamped(self):
        # A value above 1 is a fault to see (a wrong peak, a wrong
        # clock), not to hide behind a clamp.
        flops = self._introspected("mfu_c")
        peak.set_peak_override(1e-12)  # absurdly slow "peak"
        root = _step_span(0.5, [
            _span("e", "exec", 0.0, 0.1, tenant="t0", program="mfu_c")])
        mfu.on_step(root, hostgap.attribute(root))
        got = metrics.get_gauge("prof.mfu", {"workload": "mfu_c"})
        assert got == flops / 0.5 and got > 1.0
        assert metrics.get_gauge("prof.mfu", {"tenant": "t0"}) == got
        assert mfu.publish("bench_over", 3.0, peak_tflops=2.0) == 1.5

    def test_no_peak_on_cpu_means_no_mfu(self):
        # Off the chip there is no peak: MFU is absent, never estimated.
        peak.reset()
        assert peak.peak_tflops(jax.devices()[0]) is None
        self._introspected("mfu_cpu")
        root = _step_span(2.0, [
            _span("e", "exec", 0.0, 0.5, program="mfu_cpu")])
        mfu.on_step(root, hostgap.attribute(root))
        assert metrics.get_gauge(
            "prof.mfu", {"workload": "mfu_cpu"}) is None
        assert mfu.publish("mfu_cpu", 0.5) is None

    def test_unknown_tpu_kind_raises(self):
        from horovod_tpu.exceptions import HorovodTpuError

        class Dev:
            platform = "tpu"
            device_kind = "TPU v99 (unreleased)"

        peak.reset()
        with pytest.raises(HorovodTpuError, match="v99"):
            peak.peak_tflops(Dev())
        Dev.device_kind = "TPU v5 lite"
        assert peak.peak_tflops(Dev()) == (197.0, "table")

    def test_untraced_step_publishes_nothing(self):
        root = _step_span(0.5)  # no exec spans -> no FLOPs known
        mfu.on_step(root, hostgap.attribute(root))
        assert mfu.last() == {}
        assert mfu.observed() is None

    def test_publish_for_bench_records(self):
        assert mfu.publish("bench_w", 0.5, peak_tflops=2.0) == 0.25
        assert metrics.get_gauge(
            "prof.mfu", {"workload": "bench_w"}) == 0.25
        assert mfu.observed() == 0.25


# ------------------------------------------------------------- sentinel


class TestBaselineSentinel:
    SIG = ("wl",)

    def _key(self):
        return store_mod.make_key(self.SIG, kind="prof_baseline")

    def test_store_roundtrips_and_keeps_best(self, tmp_path):
        path = str(tmp_path / "prof_db.json")
        store = baseline.PerfBaselineStore(path)
        key = self._key()
        store.record_perf(key, step_p50_s=0.2, mfu_v=0.3)
        reopened = baseline.PerfBaselineStore(path)
        assert reopened.lookup(key)["step_p50_s"] == 0.2
        store.record_perf(key, step_p50_s=0.5)  # slower: keep-best wins
        assert store.lookup(key)["step_p50_s"] == 0.2
        store.record_perf(key, step_p50_s=0.1)  # faster: tightens
        assert store.lookup(key)["step_p50_s"] == 0.1

    def test_schedule_entries_rejected_by_shape(self, tmp_path):
        store = baseline.PerfBaselineStore(str(tmp_path / "db.json"))
        merged = store.merge({
            self._key(): {"bucket_bytes": 1, "wire": "f32",
                          "lowering": "flat", "score": 9.0},
        })
        assert merged == 0  # a schedule record is not a perf baseline

    def test_sentinel_verdict_ladder(self, tmp_path):
        store = baseline.PerfBaselineStore(str(tmp_path / "db.json"))
        sent = baseline.Sentinel(store)
        baseline.set_sentinel(sent)
        assert sent.check(self.SIG)["verdict"] == "no_data"
        hostgap.on_step(_step_span(0.2))
        assert sent.check(self.SIG)["verdict"] == "baseline_created"
        # identical run vs its own baseline: ok, gauge stays clear
        v = sent.check(self.SIG)
        assert v["verdict"] == "ok"
        assert metrics.get_gauge("prof.regression") == 0.0
        # pin a much faster baseline -> this run is a regression
        store.record_perf(self._key(), step_p50_s=0.01)
        v = sent.check(self.SIG)
        assert v["verdict"] == "regression" and v["slow"]
        assert metrics.get_gauge("prof.regression") == 1.0
        assert metrics.get_counter("prof.regressions") == 1
        assert v["baseline"]["step_p50_s"] == 0.01

    def test_mfu_drop_is_a_regression(self, tmp_path):
        store = baseline.PerfBaselineStore(str(tmp_path / "db.json"))
        sent = baseline.Sentinel(store)
        hostgap.on_step(_step_span(0.2))
        mfu.publish("wl", 0.1, peak_tflops=1.0)  # observed MFU 0.1
        store.record_perf(self._key(), step_p50_s=0.2, mfu_v=0.9)
        v = sent.check(self.SIG)
        assert v["verdict"] == "regression"
        assert v["mfu_drop"] and not v["slow"]

    def test_no_db_is_observe_only(self):
        sent = baseline.Sentinel(None)
        hostgap.on_step(_step_span(0.2))
        v = sent.check(self.SIG)
        assert v["verdict"] == "no_baseline"
        assert v["db"] is None
        assert sent.last()["verdict"] == "no_baseline"

    def test_auto_check_cadence(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HVD_TPU_PROF_CHECK_EVERY", "2")
        store = baseline.PerfBaselineStore(str(tmp_path / "db.json"))
        sent = baseline.Sentinel(store)
        baseline.set_sentinel(sent)
        hostgap.on_step(_step_span(0.1))
        baseline.drain_async()
        assert sent.last() is None  # step 1: below cadence
        hostgap.on_step(_step_span(0.1))
        baseline.drain_async()  # check runs off the step path
        assert sent.last() is not None  # step 2: sentinel ran
        assert sent.last()["verdict"] == "baseline_created"

    def test_capture_inert_without_dir(self):
        assert capture.maybe_capture("test") is False
        assert capture.stats()["active"] is False
        assert metrics.get_counter("prof.captures") == 0


# ------------------------------------------------------------- endpoint


class TestEndpoint:
    def _get(self, port, route):
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{route}", timeout=10) as resp:
            return resp.status, json.loads(resp.read())

    def test_prof_empty_plane_answers_200(self):
        srv = TelemetryServer(port=0, bind_host="127.0.0.1")
        try:
            code, data = self._get(srv.port, "/prof")
        finally:
            srv.stop()
        assert code == 200
        assert data["enabled"] is True
        assert data["programs"] == []
        assert data["host_gap"]["steps"] == 0
        assert data["baseline"] == {"db": None, "last": None}

    def test_prof_folds_worker_snapshots(self):
        mfu.publish("wl", 0.5, peak_tflops=1.0)
        hostgap.on_step(_step_span(0.1, [_span("e", "exec", 0.0, 0.05)]))
        snap = metrics.snapshot()
        srv = TelemetryServer(port=0, bind_host="127.0.0.1",
                              workers_fn=lambda: [(0, snap), (1, snap)])
        try:
            code, data = self._get(srv.port, "/prof")
        finally:
            srv.stop()
        assert code == 200
        assert set(data["ranks"]) == {"0", "1"}
        rank0 = data["ranks"]["0"]
        assert rank0["mfu"]["wl"] == 0.5
        assert rank0["dispatches_per_step"] == 1.0

    def test_health_carries_probe_verdict(self):
        srv = TelemetryServer(
            port=0, bind_host="127.0.0.1",
            health_fn=lambda: {"status": "ok", "round": 3},
            probe_fn=lambda: {"status": "sick",
                              "verdict": {"stage": "first_compute"}},
        )
        try:
            code, data = self._get(srv.port, "/health")
        finally:
            srv.stop()
        assert code == 200  # a sick probe never flips driver health
        assert data["round"] == 3
        assert data["probe"]["status"] == "sick"
        assert data["probe"]["verdict"]["stage"] == "first_compute"

    def test_probe_payload_pending_then_cached(self, monkeypatch):
        doctor = SimpleNamespace(diagnose=lambda: {
            "status": "ok", "verdict": None,
            "stages": [{"stage": "import", "status": "ok"}],
        })
        monkeypatch.setattr(
            telemetry_http, "_load_probe_doctor", lambda: doctor)
        telemetry_http.reset_probe_cache()
        try:
            first = telemetry_http.probe_payload()
            assert first["status"] in ("pending", "ok")
            deadline = time.monotonic() + 10
            while (telemetry_http.probe_payload()["status"] == "pending"
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            final = telemetry_http.probe_payload()
            assert final == {"status": "ok", "verdict": None,
                             "failing_stage": None, "stderr_tail": None}
        finally:
            telemetry_http.reset_probe_cache()


# ---------------------------------------------------------- retention


class TestDumpRetention:
    def _dump_n(self, rec, n):
        step = _span("step", "step", 0.0, 0.001)
        step.attrs = {"step": 1}
        for _ in range(n):
            rec.on_background(step)
            rec.dump("test")

    def test_prunes_oldest_beyond_keep(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HVD_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("HVD_TPU_TRACE_DUMP_KEEP", "3")
        rec = FlightRecorder(capacity=4)
        self._dump_n(rec, 6)
        files = sorted(f for f in os.listdir(tmp_path)
                       if f.endswith(".json"))
        assert len(files) == 3
        seqs = sorted(int(f.rsplit("_", 1)[1][:-5]) for f in files)
        assert seqs == [4, 5, 6]  # newest survive
        assert metrics.get_counter("trace.dumps_pruned") == 3

    def test_zero_keep_is_unbounded(self, monkeypatch, tmp_path):
        monkeypatch.setenv("HVD_TPU_TRACE_DIR", str(tmp_path))
        monkeypatch.setenv("HVD_TPU_TRACE_DUMP_KEEP", "0")
        rec = FlightRecorder(capacity=4)
        self._dump_n(rec, 5)
        assert len(os.listdir(tmp_path)) == 5
        assert metrics.get_counter("trace.dumps_pruned") == 0

    def test_default_keep(self):
        from horovod_tpu.trace import recorder
        assert recorder.dump_keep() == recorder.DEFAULT_DUMP_KEEP == 64


# -------------------------------------------------------- compile cost


class TestCompileCost:
    def test_cache_ranks_by_compile_bill(self):
        from horovod_tpu.svc.cache import CachedResponse, ResponseCache
        cache = ResponseCache(cap=8)
        cache.insert(("sig_cheap", 8), CachedResponse(
            program=SimpleNamespace(kind="tr"), compile_seconds=0.01))
        cache.insert(("sig_dear", 8), CachedResponse(
            program=SimpleNamespace(kind="hier"), compile_seconds=0.8))
        rows = cache.top_by_compile_cost()
        assert [r["kind"] for r in rows] == ["hier", "tr"]
        assert rows[0]["compile_seconds"] == 0.8
        assert rows[0]["axis_size"] == 8

    @pytest.mark.usefixtures("hvd_module")
    def test_service_accounts_lowering_cost(self):
        prog = xir.program("tr", [
            xir.all_reduce(WORLD_AXIS, reduce="mean", bucket=0,
                           nbytes=32, dtype="float32"),
        ])
        s = svc.get_service()
        s.submit(prog, [jnp.ones((N, 4), jnp.float32)],
                 producer="prof").result(timeout=60)
        s.drain(timeout_s=10)
        assert metrics.quantile("svc.compile_seconds", 0.5) is not None
        rows = s.cache.top_by_compile_cost()
        assert rows and rows[0]["compile_seconds"] > 0
        # the emission hook saw the dispatch too
        assert metrics.get_counter("prof.emissions") >= 1

    def test_note_emission_respects_off(self):
        prof.set_enabled_override(False)
        prof.note_emission("sched.tr", 4)
        assert metrics.get_counter("prof.emissions") == 0
        prof.set_enabled_override(True)
        prof.note_emission("sched.tr", 4)
        assert metrics.get_counter("prof.emissions") == 1
        assert metrics.get_gauge(
            "prof.emitted_ops", {"src": "sched.tr"}) == 4.0


# -------------------------------------------------------- step clock


def _tiny_step(**kwargs):
    import optax
    from horovod_tpu.optim.distributed_optimizer import TrainStep

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params["w"] - y) ** 2)

    step = TrainStep(loss_fn, optax.sgd(0.01), **kwargs)
    params = {"w": jnp.ones((4, 2), jnp.float32)}
    x = jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4) / 32.0
    return step, params, step.init(params), (x, jnp.ones((N, 2), jnp.float32))


@pytest.mark.usefixtures("hvd_module")
class TestStepClock:
    def test_step_seconds_is_the_interval_dispatch_the_call(self):
        metrics.reset_counters("train.")
        step, params, state, batch = _tiny_step()
        pause = 0.05
        for _ in range(5):
            params, state, loss = step(params, state, batch)
            float(loss)
            time.sleep(pause)
        steps = metrics.get_histogram("train.step_seconds")
        calls = metrics.get_histogram("train.dispatch_seconds")
        assert metrics.get_counter("train.steps") == 5
        # entry to entry: four intervals for five calls, and the one
        # that holds the first call's build stays out of the histogram
        assert steps["count"] == 3 and calls["count"] == 5
        assert 3 * pause <= steps["sum"] < 3 * pause + 0.1
        # the step span carries the same interval, for the recorder,
        # the sentinel and MFU
        spans = [r["spans"] for r in trace.get_recorder().steps()]
        assert spans[0]["attrs"]["interval_s"] is None
        assert [sp["attrs"]["interval_holds_build"] for sp in spans] == [
            False, True, False, False, False]
        assert all(sp["attrs"]["interval_s"] >= pause for sp in spans[1:])
        assert all(sp["dur"] < sp["attrs"]["interval_s"]
                   for sp in spans[2:])
        assert hostgap.step_p50() >= pause
        # the gap's share is of the step, so the loop's pause dilutes it
        assert metrics.get_gauge("prof.host_gap_frac") < 0.5

    def test_online_mfu_divides_by_the_rolling_interval(self):
        peak.set_peak_override(1e-9)  # 1 kFLOP/s: the tiny step registers
        step, params, state, batch = _tiny_step()
        pauses = [0.05] * 6 + [0.6]  # the last step is held up
        for pause in pauses:
            time.sleep(pause)
            params, state, loss = step(params, state, batch)
            float(loss)
        flops = introspect.get("train_step_0")["flops"]
        last = trace.get_recorder().steps()[-1]["spans"]
        assert last["attrs"]["interval_s"] >= 0.6 > 0.05 > last["dur"]
        # neither the dispatch nor the one held-up step: the rolling p50
        p50 = hostgap.step_p50()
        assert 0.05 <= p50 < 0.3
        assert metrics.get_gauge(
            "prof.mfu", {"workload": "train_step"}) == pytest.approx(
            flops / (p50 * 1e3))

    def test_compiled_is_public(self):
        step, params, state, batch = _tiny_step()
        assert step.compiled() is None  # nothing has run yet
        for _ in range(3):
            params, state, _ = step(params, state, batch)
        compiled = step.compiled()
        assert "hvd_compute_grads" in compiled.as_text()
        # what the benchmark reads today keeps working
        (executor,) = step._step_cache.values()
        assert list(executor._compiled.values()) == [compiled]
        rec = introspect.get(executor.key)
        assert rec["compiles"] == 1 and rec["calls"] == 3
        assert rec["compile_seconds"] == pytest.approx(
            rec["trace_seconds"] + rec["lower_seconds"]
            + rec["backend_seconds"])
        assert rec["peak_hbm_bytes"] is not None

    def test_off_off_makes_no_span_annotation_or_signature(
            self, monkeypatch):
        from horovod_tpu.trace import tracer

        made = []
        monkeypatch.setattr(
            tracer, "Span", lambda *a, **k: made.append("span"))
        monkeypatch.setattr(
            tracer, "TraceAnnotation",
            lambda *a, **k: made.append("annotation"))
        monkeypatch.setattr(
            introspect, "_args_signature",
            lambda args: made.append("signature"))
        trace.set_level_override("off")
        prof.set_enabled_override(False)
        metrics.reset_counters("train.")
        step, params, state, batch = _tiny_step()
        for _ in range(4):
            params, state, loss = step(params, state, batch)
        assert made == [] and step.compiled() is None
        assert jnp.isfinite(loss)
        # the two clocks do not depend on tracing
        assert metrics.get_histogram("train.step_seconds")["count"] == 2
        assert metrics.get_histogram(
            "train.dispatch_seconds")["count"] == 4


# ------------------------------------------------------------ parity


@pytest.mark.usefixtures("hvd_module")
class TestBitwiseParity:
    def _losses(self):
        import optax
        from horovod_tpu.optim.distributed_optimizer import TrainStep

        def loss_fn(params, batch):
            x, y = batch
            return jnp.mean((x @ params["w"] - y) ** 2)

        step = TrainStep(loss_fn, optax.sgd(0.01), donate=False)
        params = {"w": jnp.ones((4, 2), jnp.float32)}
        state = step.init(params)
        x = jnp.arange(N * 4, dtype=jnp.float32).reshape(N, 4) / 32.0
        batch = (x, jnp.ones((N, 2), jnp.float32))
        losses = []
        for _ in range(3):
            params, state, loss = step(params, state, batch)
            losses.append(float(loss))
        return losses

    def test_prof_on_equals_off(self):
        prof.set_enabled_override(True)
        on = self._losses()
        prof.reset()
        prof.set_enabled_override(False)
        off = self._losses()
        assert on == off  # bitwise: profiling is host-side only

    def test_prof_on_populates_plane(self):
        prof.set_enabled_override(True)
        self._losses()
        assert metrics.get_counter("prof.compiles") >= 1
        payload = prof.prof_payload()
        assert payload["host_gap"]["steps"] >= 1
        assert payload["host_gap"]["dispatches_per_step"] >= 1
        assert any(r["workload"] == "train_step"
                   for r in payload["programs"])
