"""End-to-end exchange tracing (trace/): spans, flight recorder,
straggler detection, merge tooling.

Contracts under test:

* **Propagation** — one TraceContext rides a submission end to end:
  the queue/negotiation/cache/dispatch spans the service emits all
  carry the submitting program's trace id, and the flight-recorder
  dump contains them.
* **Negotiation** — the negotiate span names the LAST-ARRIVING
  participant (who everyone waited on).
* **Cache** — a repeat signature's span set has a cache hit and NO
  "lower" span (the hit skips the whole lowering pass).
* **Nesting** — rail-phase spans (rs_ici / dcn / ag_ici) emitted while
  a hier step traces nest under that step's span tree, and the
  measured ``topo.rail_busy_frac{rail=}`` gauges come out nonzero.
* **One clock** — entering a span enters a profiler annotation of the
  stable name ``hvd_<span>`` (attributes as keyword arguments, never
  in the name) and leaving it leaves the annotation; ``off`` makes
  neither.  A step call is one tree: ``hvd_step`` with exactly the
  children docs/tracing.md tabulates, joined by parent ids, and
  ``hvd_step_finalize`` after it.
* **Flight recorder** — the ring evicts FIFO at capacity; anomaly
  dumps fire on an injected slow step (the interval the span carries,
  else its duration, against z x rolling p50) and on a ``svc.loop``
  fault, writing JSON to ``HVD_TPU_TRACE_DIR``.
* **Neutrality** — f32 dense losses are bitwise identical with
  tracing off / summary / full (host-side spans, no inserted ops).
* **Tools** — ``merge_timeline_files`` reports per-file parse status
  and merges trace exports + flight dumps; the straggler detector
  names the slow (rank, phase) and the /trace endpoint serves it.
"""

import json
import os
import time

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import faults, metrics, sched, svc, topo, trace, xir
from horovod_tpu.runtime import WORLD_AXIS
from horovod_tpu.topo import model as topo_model
from horovod_tpu.trace import straggler
from horovod_tpu.trace.recorder import FlightRecorder

pytestmark = pytest.mark.trace

N = 8
T24 = topo_model.Topology(num_slices=2, slice_size=4)


@pytest.fixture(autouse=True)
def _trace_isolation():
    trace.reset()
    metrics.reset_counters("trace.")
    metrics.reset_counters("svc.")
    metrics.reset_counters("faults.")
    metrics.clear_gauge("topo.rail_busy_frac")
    trace.set_level_override("summary")
    yield
    trace.set_level_override(None)
    trace.reset()
    svc.set_enabled_override(None)
    svc.reset_service()
    sched.set_config_override(None)
    topo.set_topology_override(None)
    faults.set_plan(None)
    metrics.reset_counters("faults.")
    os.environ.pop("HVD_TPU_TRACE_DIR", None)


def _ar_program(kind="tr", nbytes=32, bucket=0):
    return xir.program(kind, [
        xir.all_reduce(WORLD_AXIS, reduce="mean", bucket=bucket,
                       nbytes=nbytes, dtype="float32"),
    ])


def _walk(d):
    yield d
    for c in d.get("children", ()):
        yield from _walk(c)


def _all_spans():
    """Every span dict currently in the recorder (steps + background)."""
    rec = trace.get_recorder()
    out = []
    for r in rec.steps() + list(rec._background):
        out.extend(_walk(r["spans"]))
    return out


class TestLevels:
    def test_off_is_shared_noop(self):
        trace.set_level_override("off")
        assert trace.span("a", "b") is trace.tracer.NOOP
        assert trace.step() is trace.tracer.NOOP
        assert trace.record_complete("a", "b", 0.0) is None

    def test_level_spellings(self, monkeypatch):
        trace.set_level_override(None)
        for raw, want in (("off", "off"), ("0", "off"),
                          ("summary", "summary"), ("full", "full"),
                          ("on", "full"), ("1", "full"),
                          ("bogus", "summary")):
            monkeypatch.setenv("HVD_TPU_TRACE", raw)
            assert trace.level() == want, raw

    def test_context_ids_unique_and_child(self):
        a = trace.new_context("p")
        b = trace.new_context("p")
        assert a.trace_id != b.trace_id
        assert a.child("s9").span_id == "s9"
        assert a.child("s9").trace_id == a.trace_id


class _Annotations:
    """Stands in for ``jax.profiler.TraceAnnotation`` inside the
    tracer: a log of what was entered and left, in order."""

    def __init__(self):
        self.log = []

    def __call__(self, name, **kwargs):
        outer = self

        class Annotation:
            def __enter__(self):
                outer.log.append(("enter", name, kwargs))
                return self

            def __exit__(self, *exc):
                outer.log.append(("exit", name, kwargs))
                return False

        return Annotation()

    def names(self, what):
        return [n for w, n, _ in self.log if w == what]


@pytest.fixture()
def annotations(monkeypatch):
    fake = _Annotations()
    monkeypatch.setattr(trace.tracer, "TraceAnnotation", fake)
    return fake


class TestOneClock:
    def test_span_enters_and_leaves_a_stable_annotation(self, annotations):
        with trace.span("outer", "test", bucket=3):
            assert annotations.log == [
                ("enter", "hvd_outer", {"bucket": 3})]
            with trace.span("inner.kind", "test"):
                pass
        assert annotations.names("enter") == ["hvd_outer", "hvd_inner.kind"]
        assert annotations.names("exit") == ["hvd_inner.kind", "hvd_outer"]

    def test_step_number_is_an_argument_not_in_the_name(self, annotations):
        for _ in range(2):
            with trace.step(compiled=True) as sp:
                assert sp.name == "step"
        entered = [(n, k) for w, n, k in annotations.log if w == "enter"]
        assert [n for n, _ in entered] == [
            "hvd_step", "hvd_step_finalize"] * 2
        assert [k["step_num"] for n, k in entered if n == "hvd_step"] == [
            1, 2]
        # the step's own annotation has ended before its tree is folded
        assert annotations.log[1][:2] == ("exit", "hvd_step")
        assert annotations.log[2][:2] == ("enter", "hvd_step_finalize")
        # folding is timed by itself
        assert metrics.get_histogram("trace.finalize_seconds")["count"] == 2

    def test_off_makes_no_annotation(self, annotations):
        trace.set_level_override("off")
        with trace.span("a", "b"), trace.step():
            pass
        assert annotations.log == []

    def test_real_annotation_outside_a_profile(self):
        # no profile is open: the annotation is a flag check, and the
        # span tree is what it was
        with trace.span("outer", "test", n=1) as outer:
            with trace.span("inner", "test"):
                pass
        assert [c.name for c in outer.children] == ["inner"]


@pytest.mark.usefixtures("hvd_module")
class TestStepTree:
    """docs/tracing.md's table: what one ``TrainStep`` call is made
    of, on a miss and on a hit."""

    def _steps(self, n=3):
        def lf(p, b):
            x, y = b
            return jnp.mean((x @ p["w"] - y) ** 2)

        tx = hvd.DistributedOptimizer(optax.sgd(0.05))
        step = hvd.distributed_train_step(lf, tx)
        p = {"w": jnp.ones((4, 2), jnp.float32)}
        st = step.init(p)
        batch = (jnp.ones((N, 4), jnp.float32),
                 jnp.ones((N, 2), jnp.float32))
        for _ in range(n):
            p, st, _ = step(p, st, batch)
        return [r["spans"] for r in trace.get_recorder().steps()]

    def test_children_are_the_tables_and_parent_ids_join(self, annotations):
        miss, after_miss, hit = self._steps()
        assert [c["name"] for c in hit["children"]] == [
            "step_resolve", "train_step"]
        assert [c["name"] for c in miss["children"]] == [
            "step_resolve", "step_build", "train_step"]
        build = miss["children"][1]
        assert [c["name"] for c in build["children"]] == [
            "step_place", "compile_trace", "compile_lower",
            "compile_backend"]
        assert build["children"][3]["attrs"]["cache_hit"] is False
        # the exchange is emitted while jax traces the step
        emitted = [sp["name"] for sp in _walk(build["children"][1])]
        assert "exchange.dense_grad" in emitted
        for tree in (miss, hit):
            assert tree["name"] == "step" and "parent_id" not in tree
        # (spans under compile_trace join the exchange program's own
        # context, as they did)
        for parent in (miss, hit, build):
            for child in parent["children"]:
                assert child["parent_id"] == parent["span_id"]
                assert child["t0"] >= parent["t0"]
        assert miss["attrs"]["compiled"] is False
        assert hit["attrs"]["compiled"] is True
        # the step's time rides each span: none on the first call, one
        # that holds the build on the second, the step's on the third
        assert miss["attrs"]["interval_s"] is None
        assert after_miss["attrs"]["interval_holds_build"] is True
        assert after_miss["attrs"]["interval_s"] > miss["dur"]
        assert hit["attrs"]["interval_holds_build"] is False
        assert hit["attrs"]["interval_s"] >= after_miss["dur"]
        # every one of them is on the profiler's clock under hvd_<name>
        entered = annotations.names("enter")
        for name in ("hvd_step", "hvd_step_resolve", "hvd_step_build",
                     "hvd_step_place", "hvd_compile_trace",
                     "hvd_compile_lower", "hvd_compile_backend",
                     "hvd_train_step", "hvd_step_finalize"):
            assert name in entered, name
        assert entered.count("hvd_train_step") == 3
        assert entered.count("hvd_step_build") == 1
        assert sorted(annotations.names("exit")) == sorted(entered)

    def test_step_call_holds_no_literal_annotation(self):
        import inspect

        from horovod_tpu.optim.distributed_optimizer import TrainStep

        assert "TraceAnnotation" not in inspect.getsource(TrainStep)


@pytest.mark.usefixtures("hvd_module")
class TestPropagation:
    def test_submission_spans_share_trace_id_and_reach_dump(self, tmp_path):
        os.environ["HVD_TPU_TRACE_DIR"] = str(tmp_path)
        ctx = trace.new_context("prop")
        prog = _ar_program(nbytes=64).with_trace(ctx)
        x = jnp.ones((N, 4), jnp.float32)
        s = svc.get_service()
        s.submit(prog, [x], producer="prop").result(timeout=60)
        s.drain(timeout_s=10)
        spans = _all_spans()
        tagged = {sp["name"]: sp for sp in spans
                  if sp.get("trace_id") == ctx.trace_id}
        # queue wait, dispatch, and the lowering underneath all carry
        # the submission's trace id
        assert any(sp["phase"] == "queue" for sp in tagged.values()), spans
        assert any(sp["phase"] == "dispatch" for sp in tagged.values())
        assert any(sp["phase"] == "lower" for sp in tagged.values())
        # ... and a dump carries them out to disk
        path = trace.get_recorder().dump("test")
        assert path is not None and os.path.exists(path)
        disk = json.load(open(path))
        disk_ids = {
            sp.get("trace_id")
            for rec in disk["steps"] + disk["background"]
            for sp in _walk(rec["spans"])
        }
        assert ctx.trace_id in disk_ids

    def test_program_with_trace_keeps_signature(self):
        prog = _ar_program()
        tagged = prog.with_trace(trace.new_context("x"))
        assert tagged.signature() == prog.signature()
        assert tagged == prog  # compare=False field

    def test_negotiation_records_last_arriver(self):
        s = svc.get_service()
        x = jnp.ones((N, 2), jnp.float32)
        parts = ("alpha", "beta")
        fa = s.submit(_ar_program(nbytes=16), [x], producer="alpha",
                      participants=parts)
        time.sleep(0.2)  # let alpha's post land first
        fb = s.submit(_ar_program(nbytes=16), [x], producer="beta",
                      participants=parts)
        fa.result(timeout=60)
        fb.result(timeout=60)
        neg = [sp for sp in _all_spans() if sp["phase"] == "negotiate"]
        assert neg, "no negotiation span recorded"
        assert neg[0]["attrs"]["last_arriver"] == "beta"
        assert "alpha" in neg[0]["attrs"]["participants"]

    def test_cache_hit_spans_skip_lowering(self):
        svc.set_enabled_override(True)
        s = svc.get_service()
        prog = _ar_program(nbytes=1 << 16)

        def spans_of(ctx):
            return [sp for sp in _all_spans()
                    if sp.get("trace_id") == ctx.trace_id]

        cold_ctx = trace.new_context("cold")
        s.submit_traced(prog.with_trace(cold_ctx), producer="cold")
        cold = {sp["phase"] for sp in spans_of(cold_ctx)}
        assert "lower" in cold, "cold path must lower"

        warm_ctx = trace.new_context("warm")
        s.submit_traced(prog.with_trace(warm_ctx), producer="warm")
        warm = spans_of(warm_ctx)
        warm_phases = {sp["phase"] for sp in warm}
        assert "lower" not in warm_phases, \
            f"cache hit re-lowered: {warm}"
        hits = [sp for sp in warm if sp["phase"] == "cache"]
        assert hits and hits[0]["attrs"]["hit"] == 1


@pytest.mark.usefixtures("hvd_module")
class TestStepNesting:
    def _hier_train(self, iters=3):
        topo.set_topology_override(T24)
        sched.set_config_override(sched.SchedConfig(
            bucket_bytes=2048, lowering="hier",
        ))
        rng = np.random.RandomState(0)
        X = rng.randn(16, 32).astype(np.float32)
        Y = (X @ rng.randn(32, 4).astype(np.float32)).astype(np.float32)

        def lf(p, b):
            x, y = b
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        p = {
            "w": jnp.asarray(rng.randn(32, 4).astype(np.float32) * 0.1),
            "b": jnp.zeros((4,), jnp.float32),
        }
        tx = hvd.DistributedOptimizer(optax.sgd(0.05))
        step = hvd.distributed_train_step(lf, tx)
        st = step.init(p)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        losses = []
        for _ in range(iters):
            p, st, loss = step(p, st, batch)
            losses.append(float(loss))
        return losses

    def test_rail_spans_nest_under_step_span(self):
        self._hier_train()
        rec = trace.get_recorder()
        steps = rec.steps()
        assert steps, "no step spans recorded"
        # the traced (first) step carries the exchange tree
        tree = steps[0]["spans"]
        assert tree["phase"] == "step"
        phases = [sp["phase"] for sp in _walk(tree)]
        for want in ("exchange", "rs_ici", "dcn", "ag_ici"):
            assert want in phases, f"{want} not nested under step: {phases}"
        # rails measured from those spans
        ici = metrics.get_gauge("topo.rail_busy_frac", {"rail": "ici"})
        dcn = metrics.get_gauge("topo.rail_busy_frac", {"rail": "dcn"})
        assert ici is not None and ici > 0
        assert dcn is not None and dcn > 0
        assert xir.pipeline.measured_rail_busy()["dcn"] == dcn

    def test_losses_bitwise_identical_across_levels(self):
        base = None
        for level in ("off", "summary", "full"):
            trace.reset()
            trace.set_level_override(level)
            losses = self._hier_train()
            if base is None:
                base = losses
            else:
                assert losses == base, \
                    f"tracing level {level} perturbed losses"


class TestFlightRecorder:
    def _mk_span(self, name="s", phase="step", dur=0.001, step=None,
                 interval=None):
        """A finished step span: its own time (the dispatch) is
        ``dur``, the step's time is ``interval`` where it carries
        one."""
        sp = trace.tracer.Span(name, phase, time.monotonic())
        sp.t1 = sp.t0 + dur
        if step is not None:
            sp.attrs["step_num"] = step
        if interval is not None:
            sp.attrs["interval_s"] = interval
        return sp

    def test_ring_evicts_fifo(self):
        rec = FlightRecorder(capacity=3)
        for i in range(5):
            rec.on_step(self._mk_span(step=i))
        kept = [r["step"] for r in rec.steps()]
        assert kept == [2, 3, 4], kept

    def test_anomaly_dump_fires_on_slow_step(self, tmp_path):
        os.environ["HVD_TPU_TRACE_DIR"] = str(tmp_path)
        rec = FlightRecorder(capacity=8)
        for i in range(6):
            rec.on_step(self._mk_span(dur=0.01, step=i))
        assert rec.dump_seq == 0
        rec.on_step(self._mk_span(dur=1.0, step=6))  # >> 3 x p50
        assert rec.dump_seq == 1
        path = rec.last_dump_path()
        assert path and os.path.exists(path)
        dump = json.load(open(path))
        assert dump["reason"] == "slow_step"
        assert dump["detail"]["step_seconds"] == pytest.approx(1.0, rel=0.1)
        assert len(dump["steps"]) >= 6

    def test_no_dump_without_history(self):
        rec = FlightRecorder(capacity=8)
        rec.on_step(self._mk_span(dur=5.0))  # first step: no baseline
        assert rec.dump_seq == 0

    def test_slow_step_is_judged_on_the_interval_it_carries(self):
        rec = FlightRecorder(capacity=16)
        for i in range(6):
            rec.on_step(self._mk_span(interval=0.01, step=i))
        # a long dispatch inside a step of the usual length is not a
        # slow step ...
        rec.on_step(self._mk_span(dur=1.0, interval=0.01, step=6))
        assert rec.dump_seq == 0
        # ... a long interval is, however short the call
        rec.on_step(self._mk_span(interval=1.0, step=7))
        assert rec.dump_seq == 1
        detail = rec.last_dump()["detail"]
        assert detail["step_seconds"] == 1.0
        assert detail["rolling_p50"] == 0.01
        last = rec.steps()[-1]
        assert last["step_s"] == 1.0 and last["dur_s"] < 0.01

    @pytest.mark.usefixtures("hvd_module")
    def test_anomaly_dump_fires_on_svc_loop_fault(self, tmp_path):
        os.environ["HVD_TPU_TRACE_DIR"] = str(tmp_path)
        # seed the ring so the fault trigger has something to dump
        with trace.step():
            pass
        faults.set_plan("svc.loop:error:nth=1")
        s = svc.get_service()
        x = jnp.ones((N, 2), jnp.float32)
        s.submit(_ar_program(nbytes=8), [x], producer="t").result(
            timeout=60)
        assert s.dead
        dumps = [f for f in os.listdir(tmp_path)
                 if f.startswith("flight_rank")]
        assert dumps, "no flight dump written on svc.loop fault"
        reasons = {json.load(open(tmp_path / f))["reason"] for f in dumps}
        assert {"fault:svc.loop", "svc_death"} & reasons, reasons
        assert metrics.get_counter("trace.anomaly_dumps") >= 1
        assert metrics.get_gauge("trace.last_anomaly_dump") >= 1

    def test_remesh_trigger_reason(self):
        with trace.step():
            pass
        trace.trigger_dump("remesh", np_old=8, np_new=4)
        dump = trace.get_recorder().last_dump()
        assert dump is not None and dump["reason"] == "remesh"
        # no HVD_TPU_TRACE_DIR: retained in memory, not on disk
        assert trace.get_recorder().last_dump_path() is None


class TestStraggler:
    def _snap(self, dcn_s, n=20):
        metrics.reset_counters("trace.")
        for _ in range(n):
            metrics.observe("trace.phase_seconds.dcn", dcn_s)
            metrics.observe("trace.phase_seconds.rs_ici", 0.001)
        metrics.inc_counter("trace.anomaly_dumps", 1)
        metrics.set_gauge("trace.last_anomaly_dump", 1)
        return metrics.snapshot()

    def test_detects_slow_rank_and_phase(self):
        per_rank = {0: self._snap(0.002), 1: self._snap(0.002),
                    2: self._snap(0.300), 3: self._snap(0.002)}
        found = straggler.detect(per_rank)
        assert found, "straggler not detected"
        assert found[0]["rank"] == 2
        assert found[0]["phase"] == "dcn"
        assert found[0]["ratio"] > 2.0

    def test_no_false_positive_on_uniform_ranks(self):
        per_rank = {r: self._snap(0.002) for r in range(4)}
        assert straggler.detect(per_rank) == []

    def test_publish_gauges_and_clear(self):
        found = straggler.detect(
            {0: self._snap(0.002), 1: self._snap(0.300)})
        straggler.publish(found)
        assert metrics.get_gauge(
            "trace.straggler", {"rank": "1", "phase": "dcn"}) is not None
        straggler.publish([])
        assert metrics.get_gauge(
            "trace.straggler", {"rank": "1", "phase": "dcn"}) is None
        assert metrics.get_gauge("trace.stragglers") == 0

    def test_trace_endpoint_names_straggler(self):
        import urllib.request

        from horovod_tpu.runner.telemetry_http import TelemetryServer

        snaps = [(0, self._snap(0.002)), (1, self._snap(0.300))]
        srv = TelemetryServer(port=0, workers_fn=lambda: list(snaps))
        try:
            body = json.load(urllib.request.urlopen(
                f"http://127.0.0.1:{srv.port}/trace"))
        finally:
            srv.stop()
        assert body["stragglers"][0]["rank"] == 1
        assert body["stragglers"][0]["phase"] == "dcn"
        assert body["ranks"]["1"]["anomaly_dumps"] == 1
        assert body["ranks"]["1"]["phases"]["dcn"]["p50"] > \
            body["ranks"]["0"]["phases"]["dcn"]["p50"]

    def test_trace_endpoint_404_without_sources(self):
        import urllib.error, urllib.request

        from horovod_tpu.runner.telemetry_http import TelemetryServer

        srv = TelemetryServer(port=0)
        try:
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(
                    f"http://127.0.0.1:{srv.port}/trace")
            assert ei.value.code == 404
        finally:
            srv.stop()


class TestExportAndMerge:
    def test_full_level_writes_mergeable_chrome_trace(self, tmp_path):
        os.environ["HVD_TPU_TRACE_DIR"] = str(tmp_path)
        trace.set_level_override("full")
        with trace.step():
            with trace.span("b0.dcn", "dcn", rail="dcn"):
                time.sleep(0.002)
        trace.reset()  # closes the writer -> valid JSON
        files = [f for f in os.listdir(tmp_path)
                 if f.startswith("trace_rank")]
        assert files
        events = json.load(open(tmp_path / files[0]))
        names = {e.get("name") for e in events}
        assert "HVD_PROC_META" in names and "b0.dcn" in names
        cats = {e.get("cat") for e in events if e.get("ph") == "X"}
        assert "TRACE_DCN" in cats and "TRACE_STEP" in cats

    def test_merge_report_flags_unparseable_file(self, tmp_path):
        from horovod_tpu.utils.timeline import merge_timeline_files

        good = tmp_path / "t.json"
        good.write_text(json.dumps([
            {"name": "HVD_PROC_META", "ph": "i", "ts": 0, "pid": 1,
             "args": {"rank": 1, "epoch_wall_us": 0.0}},
            {"name": "x", "cat": "SVC_EXCHANGE", "ph": "X", "ts": 1,
             "dur": 1, "pid": 1, "tid": 0},
        ]))
        bad = tmp_path / "bad.json"
        bad.write_text("{{{")
        report = []
        merged = merge_timeline_files([str(good), str(bad)],
                                      report=report)
        by_path = {r["path"]: r for r in report}
        assert by_path[str(good)]["status"] == "ok"
        assert by_path[str(bad)]["status"] == "error"
        # the SVC_EXCHANGE event landed on a named lane
        lanes = [e for e in merged["traceEvents"]
                 if e.get("name") == "thread_name"
                 and e["args"]["name"] == "SVC_EXCHANGE"]
        assert lanes, merged["traceEvents"]

    def test_merge_cli_exits_nonzero_on_unparseable(self, tmp_path):
        import subprocess
        import sys

        bad = tmp_path / "bad.json"
        bad.write_text("nope")
        out = tmp_path / "merged.json"
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        proc = subprocess.run(
            [sys.executable,
             os.path.join(repo, "tools", "merge_timeline.py"),
             str(bad), "-o", str(out)],
            capture_output=True, text=True, timeout=120,
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        assert proc.returncode != 0
        assert "error" in (proc.stderr + proc.stdout)

    def test_flight_dump_merges_with_anchor(self, tmp_path):
        from horovod_tpu.utils.timeline import merge_timeline_files

        with trace.step():
            with trace.span("d", "dcn", rail="dcn"):
                pass
        os.environ["HVD_TPU_TRACE_DIR"] = str(tmp_path)
        path = trace.get_recorder().dump("test")
        report = []
        merged = merge_timeline_files([path], report=report)
        assert report[0]["status"] == "ok"
        evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
        assert any(e.get("cat") == "TRACE_DCN" for e in evs)
