"""The readers of what the program says of its own host side: its
``hvd_*`` spans on the profiler's host plane, its compile record and its
MFU gauge.  On the synthetic trace of ``test_benchmark_trace.py`` by hand,
on a program from before the spans existed (nothing is read, nothing
raises), and on a tiny cell on the CPU mesh."""

import json

import pytest

from benchmark import harness, manifest
from benchmark.trace import hlo, reduce, spans, xplane

import test_benchmark_trace as base
from tiny_cells import CHECKOUT, run_tiny

MS = base.MS
PER_LAYER = {m["name"]: m for m in json.loads(
    (CHECKOUT / "BENCHMARK.json").read_text())["per_layer"]}
NEW = ["entry.interval_ms_p50", "entry.resolve_ms_p50",
       "entry.enqueue_ms_p50", "entry.finalize_ms_p50", "entry.online_mfu",
       "compile.trace_s", "compile.lower_s", "compile.backend_s"]
SPAN_READERS = NEW[:4]

# one step call a step of 100 ms, on the main thread
MAIN = [("hvd_step", 72, 77), ("hvd_step_resolve", 72, 73),
        ("hvd_train_step", 73, 76.5), ("hvd_step_finalize", 77, 78.5)]


def _run(program_spans=True, chips=1, **record):
    """A finished traced run around the synthetic trace."""
    trace = base._synthetic(chips)
    if program_spans:
        trace.host().lines["python3"].extend(
            base._event(n, a, b, 100 * k) for k in range(4)
            for n, a, b in MAIN)
    run = harness.Run(cell=None, chips=chips, platform="tpu",
                      device_kind="TPU v5 lite", seconds_asked=1.0,
                      process_start=0.0, step_record=dict(record))
    run._module = hlo.Module(base.HLO)
    run._reduced = reduce.Reduced(trace, run._module)
    return run


def _read(name, run):
    m = PER_LAYER[name]
    return harness.read_metric(
        manifest.Metric(m["name"], m["unit"], m["source"], False), run)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("name, by_hand", [
    # starts 172 and 272 lie in the window [100, 300): one interval
    ("entry.interval_ms_p50", 100.0),
    ("entry.resolve_ms_p50", 1.0),
    ("entry.enqueue_ms_p50", 3.5),
    ("entry.finalize_ms_p50", 1.5),
])
def test_span_reader_on_the_synthetic_trace_by_hand(name, by_hand, chips):
    assert _read(name, _run(chips=chips)) == pytest.approx(by_hand)


@pytest.mark.parametrize("name", SPAN_READERS)
def test_span_reader_finds_nothing_in_a_program_without_the_spans(
        name, capsys):
    """The parent's program under this PR's readers: no ``hvd_`` span but
    the literal ``hvd_train_step``.  Each reader gives None, raises
    nothing, falls back to no stopwatch, and the run says which span it
    missed on an earlier line."""
    run = _run(program_spans=False)
    run.dispatch_seconds = [0.004] * 8  # the benchmark's own stopwatch
    assert _read(name, run) is None
    out = capsys.readouterr().out
    assert "program_spans.hvd_" in out and "none" in out
    # and without a trace at all
    bare = harness.Run(cell=None, chips=1, platform="tpu",
                       device_kind="TPU v5 lite", seconds_asked=1.0,
                       process_start=0.0)
    assert _read(name, bare) is None


def test_compile_parts_come_from_the_steps_record():
    run = _run(compile_seconds=6.0, trace_seconds=3.5, lower_seconds=0.5,
               backend_seconds=2.0)
    parts = [_read(f"compile.{p}_s", run)
             for p in ("trace", "lower", "backend")]
    assert parts == [3.5, 0.5, 2.0]
    assert sum(parts) == _read("compile.step_s", run)
    old = _run(compile_seconds=6.0)  # a record from before the split
    assert [_read(f"compile.{p}_s", old)
            for p in ("trace", "lower", "backend")] == [None] * 3


def test_online_mfu_is_the_programs_gauge_in_percent():
    from horovod_tpu import metrics

    labels = {"workload": "train_step"}
    metrics.clear_gauge("prof.mfu")
    assert _read("entry.online_mfu", _run()) is None
    metrics.set_gauge("prof.mfu", 0.331, labels)
    try:
        assert _read("entry.online_mfu", _run()) == pytest.approx(33.1)
    finally:
        metrics.clear_gauge("prof.mfu")


def test_span_events_keep_to_the_first_chips_window_and_name():
    reduced = _run(chips=4).reduced()
    found = spans.events(reduced, "hvd_step")
    assert [e.start / MS for e in found] == [172, 272]
    # hvd_step is not hvd_step_resolve; arguments may ride behind a '#'
    reduced.trace.host().lines["python3"].append(
        xplane.Event("hvd_step#step_num=9#", 180 * MS, 181 * MS))
    assert len(spans.events(reduced, "hvd_step")) == 3
    assert len(spans.events(reduced, "hvd_step_resolve")) == 2


def test_new_entries_are_appended_and_read_no_clock_of_their_own():
    entries = json.loads(
        (CHECKOUT / "BENCHMARK.json").read_text())["per_layer"]
    assert [m["name"] for m in entries[18:26]] == NEW
    layers = {m["layer"] for m in entries[:18]}
    end_to_end = {"mfu", "setup_s"}
    for m in entries[18:26]:
        assert m["layer"] in layers and m["moves"] in end_to_end
        assert m["source"] == "program_span" and "workloads" not in m
        text = (harness.PACKAGE_DIR / "layer_metrics"
                / f"{m['name']}.py").read_text()
        assert "horovod_tpu.trace" not in text and "import time" not in text
        assert "dispatch_seconds" not in text and "perf_counter" not in text


# ------------------------------------------------- on the CPU mesh, tiny
def test_tiny_cell_puts_the_programs_spans_on_the_profiles_host_plane(
        tiny_root, quiet_runtime):
    from horovod_tpu.prof import introspect

    introspect.reset()  # the worker's earlier tests built steps too
    cell, run, correct = run_tiny(tiny_root, "gpt_tiny.dense", trace=True)
    assert correct
    record = run.step_record
    assert record["compile_seconds"] == pytest.approx(
        record["trace_seconds"] + record["lower_seconds"]
        + record["backend_seconds"])
    assert record["compiles"] == 1
    # under their plain names (the annotation's arguments are the
    # event's stats), beside the benchmark's own spans
    host = xplane.load(run.trace_file).host()
    events = [e for line in host.lines.values() for e in line]
    names = {e.name for e in events}
    assert {"hvd_step", "hvd_step_resolve", "hvd_train_step",
            "hvd_step_finalize", "bench_dispatch"} <= names
    # each step call lies inside the benchmark's span around it
    dispatches = [e for e in events if e.name == "bench_dispatch"]
    steps = [e for e in events if e.name == "hvd_step"]
    assert steps and len(steps) <= len(dispatches)
    assert all(any(d.start <= s.start and s.end <= d.end
                   for d in dispatches) for s in steps)
    # off the chip a time is never written under a metric's name
    line = harness.metrics_of(run, cell.per_layer, on_chip=False)
    assert not set(NEW) & set(line)
