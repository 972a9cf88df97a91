"""The readers of the step's second phase cut by the program's scopes
(``benchmark/trace/phase.py`` and the eight metrics of PR 36): on an HLO
text and a device trace made by hand, on the same program from before the
scopes (nothing is read, nothing raises), and on a tiny packed cell on the
CPU mesh."""

import json

import pytest

from benchmark import harness, manifest
from benchmark.trace import hlo, phase, reduce, xplane

import test_benchmark_trace as base
from tiny_cells import CHECKOUT, run_tiny

PER_LAYER = {m["name"]: m for m in json.loads(
    (CHECKOUT / "BENCHMARK.json").read_text())["per_layer"]}
NEW = ["update.optimizer_ms", "update.wire_cast_ms",
       "update.wire_cast_gb_per_step", "update.written_gb_per_step",
       "exchange.bucket_copy_ms", "exchange.collective_ms",
       "input.pack_busy_share", "input.pack_ms_per_batch"]
COUNTS = NEW[2:4]

P = "jit(step_body)/shard_map/hvd_reduce_and_update"
BUCKET = "hvd_exchange/hvd_sched_bucket0_6144B_bf16_flat"
# Two leaves: ``a`` (1024 values), whose cast to the wire is fused into
# its gradient's fusion and whose cast back into its update's, and ``e``
# (2048), whose two casts stand alone.
HLO = f'''HloModule jit_step_body, is_scheduled=true

%fused_grad (x: f32[1024]) -> bf16[1024] {{
  %x = f32[1024]{{0}} parameter(0)
  %dw = f32[1024]{{0}} multiply(%x, %x), metadata={{op_name="jit(step_body)/shard_map/hvd_compute_grads/transpose(jvp(T))/mul"}}
  ROOT %cast.out = bf16[1024]{{0}} convert(%dw), metadata={{op_name="{P}/hvd_exchange/wire_out/convert_element_type"}}
}}

%fused_update (p: f32[1024], g: bf16[1024]) -> f32[1024] {{
  %p = f32[1024]{{0}} parameter(0)
  %g = bf16[1024]{{0}} parameter(1)
  %cast.in = f32[1024]{{0}} convert(%g), metadata={{op_name="{P}/hvd_exchange/wire_in/convert_element_type"}}
  ROOT %new = f32[1024]{{0}} subtract(%p, %cast.in), metadata={{op_name="{P}/hvd_update/sub"}}
}}

%fused_update.2 (p: f32[2048], g: f32[2048]) -> (f32[2048], f32[2048]) {{
  %p = f32[2048]{{0}} parameter(0)
  %g = f32[2048]{{0}} parameter(1)
  %mu = f32[2048]{{0}} add(%p, %g), metadata={{op_name="{P}/hvd_update/add"}}
  %new = f32[2048]{{0}} subtract(%p, %g), metadata={{op_name="{P}/hvd_update/sub"}}
  ROOT %both = (f32[2048]{{0}}, f32[2048]{{0}}) tuple(%new, %mu)
}}

%add (x: bf16[], y: bf16[]) -> bf16[] {{
  %x = bf16[] parameter(0)
  %y = bf16[] parameter(1)
  ROOT %s = bf16[] add(%x, %y)
}}

ENTRY %main.9 (a: f32[1024], e: f32[2048]) -> (f32[1024], f32[2048]) {{
  %a = f32[1024]{{0}} parameter(0)
  %e = f32[2048]{{0}} parameter(1)
  %fusion.grad = bf16[1024]{{0}} fusion(%a), kind=kLoop, calls=%fused_grad, metadata={{op_name="jit(step_body)/shard_map/hvd_compute_grads/transpose(jvp(T))/mul"}}
  %convert.7 = bf16[2048]{{0}} convert(%e), metadata={{op_name="{P}/hvd_exchange/wire_out/convert_element_type"}}
  %pack.1 = bf16[3072]{{0}} concatenate(%fusion.grad, %convert.7), dimensions={{0}}, metadata={{op_name="{P}/{BUCKET}/concatenate"}}
  %all-reduce.1 = bf16[3072]{{0}} all-reduce(%pack.1), channel_id=1, replica_groups={{{{0,1,2,3}}}}, use_global_device_ids=true, to_apply=%add, metadata={{op_name="{P}/{BUCKET}/psum"}}
  %cut.1 = bf16[1024]{{0}} slice(%all-reduce.1), slice={{[0:1024]}}, metadata={{op_name="{P}/{BUCKET}/dynamic_slice"}}
  %cut.2 = bf16[2048]{{0}} slice(%all-reduce.1), slice={{[1024:3072]}}, metadata={{op_name="{P}/{BUCKET}/dynamic_slice"}}
  %opt-barrier.1 = (bf16[1024]{{0}}, bf16[2048]{{0}}) opt-barrier(%tied), metadata={{op_name="{P}/hvd_exchange/wire_in/optimization_barrier"}}
  %convert.8 = f32[2048]{{0}} convert(%cut.2), metadata={{op_name="{P}/hvd_exchange/wire_in/convert_element_type"}}
  %fusion.upd = f32[1024]{{0}} fusion(%a, %cut.1), kind=kLoop, calls=%fused_update, metadata={{op_name="{P}/hvd_update/sub"}}
  %fusion.upd2 = (f32[2048]{{0}}, f32[2048]{{0}}) fusion(%e, %convert.8), kind=kLoop, calls=%fused_update.2, metadata={{op_name="{P}/hvd_update/sub"}}
  %gte.1 = f32[2048]{{0}} get-tuple-element(%fusion.upd2), index=0, metadata={{op_name="{P}/hvd_update/sub"}}
  %all-reduce.3 = f32[]{{:T(128)}} all-reduce(%loss), channel_id=3, replica_groups={{{{0}},{{1}},{{2}},{{3}}}}, to_apply=%add, metadata={{op_name="jit(step_body)/shard_map/psum"}}
  ROOT %out = (f32[1024]{{0}}, f32[2048]{{0}}) tuple(%fusion.upd, %gte.1), metadata={{op_name="{P}/hvd_update/sub"}}
}}
'''
# the same program from before this PR: no child scope under the phase
OLD_HLO = HLO
for scope in ("/hvd_exchange", "/wire_out", "/wire_in", "/hvd_update"):
    OLD_HLO = OLD_HLO.replace(scope, "")

# one step of 100 ms, as (instruction, start, end) in ms from its start
STEP = [
    ("%fusion.grad = bf16[1024] fusion(%a)", 0, 50),
    ("%convert.7 = bf16[2048] convert(%e)", 50, 53),          # wire out
    ("%pack.1 = bf16[3072] concatenate(...)", 53, 55),        # bucket
    ("%all-reduce.1 = bf16[3072] all-reduce(%pack.1)", 55, 65),
    ("%cut.1 = bf16[1024] slice(...)", 58, 59),   # while the collective runs
    ("%cut.2 = bf16[2048] slice(...)", 65, 66.5),             # bucket
    ("%convert.8 = f32[2048] convert(%cut.2)", 66.5, 70.5),   # wire in
    ("%fusion.upd = f32[1024] fusion(%a, %cut.1)", 70.5, 80.5),
    ("%fusion.upd2 = (f32[2048], f32[2048]) fusion(...)", 80.5, 95),
    ("%all-reduce.3 = f32[] all-reduce(%loss)", 95, 96),
]
# the packer's thread: three windows start inside [100, 300), the last
# ends after it and counts whole; one before and one after do not count
PACK = [(50, 55), (110, 115), (210, 217), (290, 310), (305, 306)]


def _trace(chips=1, pack=True):
    planes = []
    for chip in range(chips):
        ops, modules = [], []
        for k in range(4):
            modules.append(base._event("jit_step_body(1)", 0, 100, 100 * k))
            ops += [base._event(n, a, b, 100 * k) for n, a, b in STEP]
        planes.append(xplane.Plane(
            f"/device:TPU:{chip}",
            {xplane.OPS_LINE: sorted(ops, key=lambda e: (e.start, -e.end)),
             xplane.MODULES_LINE: modules}))
    host = {"python3": [base._event("bench_dispatch", 72 + 100 * k,
                                    76 + 100 * k) for k in range(4)]}
    if pack:
        host["bench_traffic"] = [
            base._event("hvd_pack_window", a, b) for a, b in PACK]
    planes.append(xplane.Plane(xplane.HOST_PLANE, host))
    return xplane.Trace(planes)


def _run(step_hlo=HLO, traced=True, chips=1, pack=True):
    """A finished traced run around the fixture."""
    run = harness.Run(cell=None, chips=chips, platform="tpu",
                      device_kind="TPU v5 lite", seconds_asked=1.0,
                      process_start=0.0, step_hlo=step_hlo)
    if traced:
        run._reduced = reduce.Reduced(_trace(chips, pack), run.module())
    return run


def _read(name, run):
    m = PER_LAYER[name]
    return harness.read_metric(
        manifest.Metric(m["name"], m["unit"], m["source"], False), run)


@pytest.mark.parametrize("chips", [1, 4])
@pytest.mark.parametrize("name, by_hand", [
    ("update.optimizer_ms", 10 + 14.5),
    ("update.wire_cast_ms", 3 + 4),
    # convert.7 4096 + convert.8 8192 alone, cast.out 2048 + cast.in 4096
    # inside fusions
    ("update.wire_cast_gb_per_step", 18432e-9),
    # fusion.upd 4096 + fusion.upd2's two arrays 16384; not its
    # get-tuple-element, nor the ROOT tuple
    ("update.written_gb_per_step", 20480e-9),
    ("exchange.bucket_copy_ms", 2 + 1 + 1.5),
    # [55, 65]; the loss's all-reduce is inside a group of one chip
    ("exchange.collective_ms", 10.0),
    ("input.pack_busy_share", 100 * (5 + 7 + 20) / 200),
    ("input.pack_ms_per_batch", (5 + 7 + 20) / 2),
])
def test_reader_on_the_fixture_by_hand(name, by_hand, chips):
    assert _read(name, _run(chips=chips)) == pytest.approx(by_hand)


def test_the_parts_add_up_to_the_phase_and_nothing_is_left_bare():
    run = _run()
    parts = sum(_read(n, run) for n in (
        "update.optimizer_ms", "update.wire_cast_ms",
        "exchange.bucket_copy_ms"))
    assert parts == pytest.approx(_read("update.device_ms", run))
    # exposed: the collective's [55, 65] less cut.1's [58, 59]
    assert _read("exchange.exposed_ms", run) == pytest.approx(9.0)
    assert _read("exchange.exposed_ms", run) <= _read(
        "exchange.collective_ms", run)


def test_where_the_wires_casts_stand_goes_to_an_earlier_line(capsys):
    _read("update.wire_cast_gb_per_step", _run())
    _read("update.written_gb_per_step", _run())
    out = capsys.readouterr().out
    assert ("update.wire_cast_converts: {'alone': 2, 'fused': 2, "
            "'fusions_named_under': {'hvd_compute_grads': 1, "
            "'hvd_update': 1}}") in out
    assert "update.written_instructions: 2" in out


@pytest.mark.parametrize("name", NEW)
def test_reader_finds_nothing_in_a_program_without_the_scopes(name):
    """The parent's program under this PR's readers: the phase, its bucket
    scopes and its collectives are there, the children and the packer's
    span are not.  Each reader gives None and raises nothing; the older
    readers read what they read."""
    old = _run(OLD_HLO, pack=False)
    assert _read(name, old) is None
    assert _read("update.device_ms", old) == pytest.approx(36.0)
    assert _read("exchange.exposed_ms", old) == pytest.approx(9.0)
    # and without a trace, or without the step's text, at all
    assert _read(name, _run(traced=False, step_hlo=None)) is None
    if name not in COUNTS:
        assert _read(name, _run(traced=False)) is None


def test_counts_are_read_off_the_chip_and_times_are_not():
    metrics = [manifest.Metric(n, PER_LAYER[n]["unit"],
                               PER_LAYER[n]["source"], False) for n in NEW]
    line = harness.metrics_of(_run(), metrics, on_chip=False)
    assert sorted(line) == sorted(COUNTS)
    assert len(harness.metrics_of(_run(), metrics, on_chip=True)) == 8


def test_a_one_chip_program_has_no_collective_time():
    text = HLO.replace("replica_groups={{0,1,2,3}}", "replica_groups={{0}}")
    run = _run(text)
    assert _read("exchange.collective_ms", run) is None
    # the all-reduce of a group of one counts as the bucket's own work:
    # its [55, 65] less cut.1's [58, 59] inside it
    assert _read("exchange.bucket_copy_ms", run) == pytest.approx(
        4.5 + 10 - 1)


def test_part_of_names_the_most_specific_scope():
    assert phase.part_of(f"{P}/hvd_exchange/wire_in/convert") == "wire_in"
    assert phase.part_of(f"{P}/{BUCKET}/psum") == "hvd_sched_bucket"
    assert phase.part_of(f"{P}/hvd_exchange/mul") == "hvd_exchange"
    assert phase.part_of(f"{P}/hvd_update/sub") == "hvd_update"
    assert phase.part_of(f"{P}/add") == "hvd_reduce_and_update"
    assert phase.part_of("jit(step_body)/hvd_compute_grads/mul") == \
        "hvd_compute_grads"
    assert phase.part_of("") == "none"


def test_new_entries_are_appended_and_nothing_that_was_there_moved():
    entries = json.loads(
        (CHECKOUT / "BENCHMARK.json").read_text())["per_layer"]
    assert [m["name"] for m in entries[59:67]] == NEW
    older = {m["name"]: m for m in entries[:59]}
    for name in ("update.device_ms", "input.wait_ms_p50",
                 "exchange.exposed_ms"):
        assert name in older
    layer_moves = {m["layer"]: m["moves"] for m in entries[:59]
                   if m["layer"] in ("update", "exchange", "input")}
    for m in entries[59:67]:
        # what its layer's older metrics move; but a metric every cell
        # reports moves one every cell reports, and ResNet has no tokens
        assert m["moves"] == ("mfu" if m["name"] == "exchange.bucket_copy_ms"
                              else layer_moves[m["layer"]])
        assert "roofline" not in m["name"] and "mfu" not in m["name"]
        text = (harness.PACKAGE_DIR / "layer_metrics"
                / f"{m['name']}.py").read_text()
        assert "horovod_tpu" not in text.replace("``horovod_tpu", "")
        assert "import time" not in text and "perf_counter" not in text
    assert entries[64]["workloads"] == ["gpt2s.dp4"]
    assert [m.get("workloads") for m in entries[65:67]] == [
        ["gpt2s.packed"]] * 2
    assert all("workloads" not in m for m in entries[59:64])


# ------------------------------------------------- on the CPU mesh, tiny
def test_tiny_packed_cell_puts_the_packers_span_on_its_own_thread(
        tiny_root, quiet_runtime):
    from horovod_tpu.prof import introspect

    introspect.reset()  # the worker's earlier tests built steps too
    cell, run, correct = run_tiny(tiny_root, "gpt_tiny.packed", trace=True)
    assert correct
    # the loader merges the lines of one name, and here every thread is
    # called "python": read the threads' lines apart
    from jax.profiler import ProfileData

    host, = [p for p in ProfileData.from_file(str(run.trace_file)).planes
             if p.name == xplane.HOST_PLANE]
    threads = [{e.name for e in line.events} for line in host.lines]
    packing = [i for i, names in enumerate(threads)
               if "hvd_pack_window" in names]
    looping = [i for i, names in enumerate(threads)
               if "bench_dispatch" in names]
    assert packing and looping and not set(packing) & set(looping)
    # the step of the tiny cell is cut as the real ones are
    module = hlo.Module(run.step_hlo)
    names = [i.op_name for i in module.instructions.values()]
    assert any("hvd_exchange/wire_in" in n for n in names)
    assert any("/hvd_update/" in n for n in names)
    # counts are read off the chip, a time is never written from a CPU run
    line = harness.metrics_of(run, cell.per_layer, on_chip=False)
    assert set(COUNTS) <= set(line) and not (set(NEW) - set(COUNTS)) & set(
        line)
    assert line["update.written_gb_per_step"]["value"] > 0
