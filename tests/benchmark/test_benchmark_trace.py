"""The reduction from a device trace to numbers: the interval arithmetic,
the join from events to scopes through the HLO, and all of it on a trace
recorded on four v5e chips (``data/``)."""

import gzip

import pytest

from benchmark.trace import hlo, reduce, xplane

from tiny_cells import HERE

MS = 1e6  # the traces count nanoseconds

HLO = '''HloModule jit_step_body, is_scheduled=true

%fused_computation (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(f32[8]{0} %p, f32[8]{0} %p)
}

%add (x: bf16[], y: bf16[]) -> bf16[] {
  %x = bf16[]{:T(128)} parameter(0)
  %y = bf16[]{:T(128)} parameter(1)
  ROOT %s = bf16[]{:T(128)} add(%x, %y)
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[]{:T(128)}, f32[8]{0:T(128)}) parameter(0)
  %g = f32[8]{0:T(128)} get-tuple-element(%t), index=1
  %inner.1 = f32[8]{0:T(128)} fusion(f32[8]{0:T(128)} %g), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_body)/hvd_compute_grads/transpose(jvp(T))/block_1/attn/while/body/mul" stack_frame_id=4}
  ROOT %r = (s32[]{:T(128)}, f32[8]{0:T(128)}) tuple(%c, %inner.1)
}

ENTRY %main.7 (a: f32[8], b: bf16[1024], q: bf16[2,2,256,64]) -> f32[8] {
  %a = f32[8]{0:T(128)} parameter(0), metadata={op_name="params"}
  %b = bf16[1024]{0:T(1024)(128)(2,1)} parameter(1)
  %c2 = bf16[512]{0:T(512)(128)(2,1)} slice(%b), slice={[0:512]}
  %d = f32[]{:T(128)} constant(0)
  %attn.1 = (bf16[2,2,256,64]{3,2,1,0:T(8,128)(2,1)}, f32[2,2,256,128]{3,2,1,0:T(8,128)}) custom-call(%q, %q, %q), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[2,2,256,64]{3,2,1,0}}, metadata={op_name="jit(step_body)/hvd_compute_grads/jvp(T)/block_0/attn/pallas_call" stack_frame_id=1}, backend_config={"custom_call_config":{"body":"TUzvUg"}}
  %fusion.1 = f32[8]{0:T(128)} fusion(f32[8]{0:T(128)} %a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_body)/hvd_compute_grads/jvp(T)/block_0/mlp/mul"}
  %while.1 = (s32[]{:T(128)}, f32[8]{0:T(128)}) while(%tup), condition=%cond, body=%body, metadata={op_name="jit(step_body)/hvd_compute_grads/transpose(jvp(T))/block_1/attn/while"}
  %all-reduce-start.2 = bf16[512]{0:T(512)(128)(2,1)} all-reduce-start(bf16[512]{0:T(512)(128)(2,1)} %c2), channel_id=2, replica_groups=[1,4]<=[4], to_apply=%add, metadata={op_name="jit(step_body)/hvd_reduce_and_update/hvd_bucket1/psum"}
  %fusion.9 = f32[8]{0:T(128)} fusion(%a), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_body)/hvd_reduce_and_update/adamw/mul"}
  %all-reduce-done.2 = bf16[512]{0:T(512)(128)(2,1)} all-reduce-done(%all-reduce-start.2), metadata={op_name="jit(step_body)/hvd_reduce_and_update/hvd_bucket1/psum"}
  %all-reduce.1 = bf16[1024]{0:T(1024)(128)(2,1)} all-reduce(bf16[1024]{0:T(1024)(128)(2,1)} %b), channel_id=1, replica_groups={{0,1,2,3}}, use_global_device_ids=true, to_apply=%add, metadata={op_name="jit(step_body)/hvd_reduce_and_update/hvd_bucket0/psum"}
  %all-reduce.3 = f32[]{:T(128)} all-reduce(%d), channel_id=3, replica_groups={{0},{1},{2},{3}}, to_apply=%add, metadata={op_name="jit(step_body)/psum"}
  ROOT %copy.5 = f32[8]{0:T(128)} copy(%fusion.9)
}
'''

# one step of 100 ms, as (instruction, start, end) in ms from its start
STEP = [
    ("%attn.1 = (bf16[2,2,256,64]{3,2,1,0}) custom-call(...)", 0, 10),
    ("%fusion.1 = f32[8]{0:T(128)} fusion(f32[8] %a)", 10, 30),
    ("%while.1 = (s32[], f32[8]) while(%tup)", 30, 60),
    ("%inner.1 = f32[8] fusion(%g)", 35, 45),
    ("%inner.1 = f32[8] fusion(%g)", 48, 58),
    ("%all-reduce-start.2 = bf16[512] all-reduce-start(%c2)", 60, 61),
    ("%fusion.9 = f32[8] fusion(%a)", 61, 70),
    ("%all-reduce-done.2 = bf16[512] all-reduce-done(...)", 75, 80),
    ("%all-reduce.1 = bf16[1024] all-reduce(%b)", 80, 90),
    ("%all-reduce.3 = f32[] all-reduce(%d)", 90, 91),
    ("%copy.5 = f32[8] copy(%fusion.9)", 91, 95),
]
HOST = [("bench_block", 0, 72), ("bench_dispatch", 72, 76),
        ("bench_input_wait", 96, 99)]


def _event(name, start, end, shift=0.0):
    return xplane.Event(name, (start + shift) * MS, (end + shift) * MS)


def _synthetic(chips=1, steps=4):
    planes = []
    for chip in range(chips):
        ops, modules = [], []
        for k in range(steps):
            modules.append(_event("jit_step_body(1)", 0, 100, 100 * k))
            ops += [_event(n, a, b, 100 * k) for n, a, b in STEP]
        # a short program of something else does not become "the step"
        modules.append(_event("jit_other(2)", 0, 1, 100 * steps))
        planes.append(xplane.Plane(
            f"/device:TPU:{chip}",
            {xplane.OPS_LINE: sorted(ops, key=lambda e: (e.start, -e.end)),
             xplane.MODULES_LINE: modules}))
    host = [_event(n, a, b, 100 * k) for k in range(steps)
            for n, a, b in HOST]
    planes.append(xplane.Plane(xplane.HOST_PLANE, {"python3": host}))
    return xplane.Trace(planes)


# ------------------------------------------------------------- arithmetic
def test_union_total_clip_subtract():
    assert reduce.union([(5, 7), (0, 2), (1, 3), (3, 4), (9, 9)]) == [
        (0, 4), (5, 7)]
    assert reduce.total([(0, 4), (5, 7)]) == 6
    assert reduce.clip([(0, 4), (5, 7), (8, 9)], (3, 6)) == [(3, 4), (5, 6)]
    assert reduce.subtract([(0, 10), (20, 30)],
                           [(2, 3), (5, 12), (19, 21), (29, 40)]) == [
        (0, 2), (3, 5), (21, 29)]
    assert reduce.subtract([(0, 10)], []) == [(0, 10)]
    assert reduce.overlap((0, 5), (3, 9)) == 2


def test_self_time_takes_nested_events_out():
    events = [_event("while", 0, 30), _event("a", 5, 15),
              _event("b", 18, 28), _event("c", 30, 40)]
    assert reduce.self_times(events) == [10 * MS, 10 * MS, 10 * MS, 10 * MS]


# -------------------------------------------------------------------- hlo
def test_hlo_parse_scopes_collectives_and_kernels():
    module = hlo.Module(HLO)
    attn = module.get("%attn.1 = (bf16[2,2,256,64]) custom-call(%q)")
    assert attn.opcode == "custom-call" and module.is_mosaic("attn.1")
    assert attn.op_name.endswith("block_0/attn/pallas_call")
    assert attn.result_bytes == 2 * 2 * 256 * 64 * 2 + 2 * 2 * 256 * 128 * 4
    assert module.get("inner.1").computation == "body"
    assert "hvd_compute_grads" in module.scope_of("%inner.1 = f32[8] ...")
    assert module.get("while.1").opcode == "while"
    assert module.collective_kind("all-reduce.1") == "all-reduce"
    assert module.collective_kind("all-reduce-start.2") == "all-reduce"
    assert module.collective_kind("all-reduce-done.2") == "all-reduce"
    assert module.get("all-reduce-start.2").group_size == 4
    # a group of one chip moves nothing
    assert module.collective_kind("all-reduce.3") is None
    assert module.collective_kind("fusion.1") is None
    assert module.scope_of("copy.5") == "" and module.get("nothing") is None
    # -done is its -start's other half: 2 calls, 1024 + 512 bf16 elements
    assert module.exchange_per_step() == (2, 2 * 1024 + 2 * 512)


def test_shape_bytes():
    assert hlo.shape_bytes("f32[16,1024]{1,0:T(8,128)S(1)}") == 65536
    assert hlo.shape_bytes("(bf16[8]{0}, s32[]{:T(128)}, pred[3])") == 23
    assert hlo.shape_bytes("f8e4m3fn[10]") == 10


# -------------------------------------------------------------- reduction
@pytest.mark.parametrize("chips", [1, 4])
def test_synthetic_trace_reduces_to_the_numbers_by_hand(chips):
    r = reduce.Reduced(_synthetic(chips), hlo.Module(HLO))
    assert r.usable and len(r.chips) == chips
    # of four executions the first and the last are left out
    assert r.steps() == 2
    assert r.window_seconds() == pytest.approx(0.2)
    # busy: [0, 70] and [75, 95] of every 100 ms
    assert r.busy_seconds() == pytest.approx(0.18)
    # kernel 10 + fusion 20 + the while's own 10 + its two bodies 20
    assert r.scope_ms_per_step("hvd_compute_grads") == pytest.approx(60)
    assert r.scope_ms_per_step(
        "hvd_compute_grads", "transpose(", "/attn/") == pytest.approx(30)
    assert r.scope_ms_per_step(
        "hvd_compute_grads", without=("/block_1",)) == pytest.approx(30)
    assert r.scope_ms_per_step(
        "hvd_reduce_and_update", collectives=False) == pytest.approx(9)
    assert r.scope_ms_per_step(
        "hvd_reduce_and_update", collectives=True) == pytest.approx(16)
    # the copy and the loss's group-of-one psum carry neither scope
    assert r.scope_ms_per_step() == pytest.approx(5)
    assert r.kernel_seconds_per_step("attn/pallas_call") == \
        pytest.approx(0.010)
    assert r.kernel_seconds_per_step("no_such_kernel") is None
    # asynchronous span [60, 80] less the update's [61, 70], and the
    # synchronous all-reduce [80, 90]
    assert r.exposed_collective_ms_per_step() == pytest.approx(11 + 10)


def test_breakdown_adds_layers_up_and_names_the_hosts_span():
    b = reduce.Reduced(_synthetic(), hlo.Module(HLO)).breakdown()
    ops = dict(b["device_ops"])
    assert len(b["device_ops"]) <= 10
    assert ops["hvd_compute_grads/jvp(T)/block_*/mlp/mul"] == \
        pytest.approx(0.020)
    assert ops["hvd_compute_grads/transpose(jvp(T))/block_*/attn/while/"
               "body/mul"] == pytest.approx(0.020)
    assert ops["copy (no scope)"] == pytest.approx(0.004)
    gaps = dict(b["idle_gaps"])
    # idle [70, 75]: 2 ms under bench_block, 3 under bench_dispatch;
    # idle [95, 100]: 3 ms under bench_input_wait, 2 under nothing
    assert gaps == pytest.approx({
        "bench_block": 0.002, "bench_dispatch": 0.003,
        "bench_input_wait": 0.003, "no_bench_span": 0.002})


def test_without_the_hlo_collectives_are_known_by_name_only():
    r = reduce.Reduced(_synthetic())
    assert r.scope_ms_per_step("hvd_compute_grads") == 0
    assert r.collective_of(_event("%all-reduce.1 = bf16[4] ...", 0, 1)) == \
        "all-reduce"
    assert r.collective_of(_event("%fusion.1 = f32[8] fusion()", 0, 1)) \
        is None


def test_too_few_steps_is_not_usable():
    assert not reduce.Reduced(_synthetic(steps=3)).usable
    assert not reduce.Reduced(xplane.Trace([])).usable


# ------------------------------------------- recorded on four v5e chips
DATA = HERE / "data"


@pytest.fixture(scope="module")
def recorded():
    """``data/README.txt`` says what was recorded and how it was cut."""
    module = hlo.Module(gzip.decompress(
        (DATA / "rec_dp4.step.hlo.txt.gz").read_bytes()).decode())
    trace = xplane.load(DATA / "rec_dp4.xplane.pb")
    return trace, module, reduce.Reduced(trace, module)


def test_recorded_trace_has_four_chips_and_every_event_joins(recorded):
    trace, module, r = recorded
    assert [p.name for p in trace.devices()] == [
        f"/device:TPU:{i}" for i in range(4)]
    assert r.usable and len(r.chips) == 4 and r.steps() == 6
    for plane in trace.devices():
        assert len(plane.lines[xplane.MODULES_LINE]) == 8
        for e in plane.lines[xplane.OPS_LINE]:
            assert module.get(e.name) is not None, e.name
    host = trace.host().lines["python3"]
    assert {e.name for e in host} >= set(reduce.HOST_SPANS)


def test_recorded_busy_union_and_idle_share(recorded):
    _, _, r = recorded
    assert r.window_seconds() == pytest.approx(0.0191711085, rel=1e-9)
    assert r.busy_seconds() == pytest.approx(0.00168590475, rel=1e-9)
    idle = 1 - r.busy_seconds() / r.window_seconds()
    assert idle == pytest.approx(0.91206, abs=1e-5)  # the host is slower
    # nothing overlaps on a chip's line but a while and its body, so the
    # self times of the three groups of scopes add up to the busy union
    parts = (r.scope_ms_per_step("hvd_compute_grads")
             + r.scope_ms_per_step("hvd_reduce_and_update")
             + r.scope_ms_per_step())
    assert parts == pytest.approx(
        1e3 * r.busy_seconds() / r.steps(), rel=1e-6)
    assert r.scope_ms_per_step("hvd_compute_grads") == pytest.approx(
        0.2062918, rel=1e-6)


def test_recorded_collectives_are_the_hlos_and_all_exposed(recorded):
    trace, module, r = recorded
    assert module.exchange_per_step() == (2, 990212.0)
    for chip in r.chips:
        seen = [op.collective for op in chip.ops if op.collective]
        assert seen == ["all-reduce"] * 2 * chip.steps
    in_collectives = r.scope_ms_per_step("", collectives=True)
    assert in_collectives == pytest.approx(0.02978604, rel=1e-6)
    # synchronous all-reduces: nothing else runs on the chip meanwhile
    assert r.exposed_collective_ms_per_step() == pytest.approx(
        in_collectives, rel=1e-9)
    assert r.scope_ms_per_step(
        "hvd_reduce_and_update", collectives=False) == pytest.approx(
        r.scope_ms_per_step("hvd_reduce_and_update") - 0.02603354, rel=1e-5)


def test_recorded_kernel_and_breakdown(recorded):
    _, module, r = recorded
    kernel = r.kernel_seconds_per_step("attn/pallas_call")
    assert kernel == pytest.approx(4.0845417e-05, rel=1e-6)
    mosaic = [i for i in module.instructions.values()
              if module.is_mosaic(i.name)]
    assert len(mosaic) == 2  # one flash forward call a layer
    b = r.breakdown()
    assert b["device_ops"][0] == [
        "hvd_compute_grads/jvp(Transformer)/block_*/attn/pallas_call",
        pytest.approx(kernel)]
    assert 1 <= len(b["device_ops"]) <= 10
    gaps = dict(b["idle_gaps"])
    assert set(gaps) <= set(reduce.HOST_SPANS) | {"no_bench_span"}
    # the device waits for the host's enqueue, not for its blocking read
    assert max(gaps, key=gaps.get) == "bench_dispatch"
    first = r.chips[0]
    idle_first_chip = (first.window[1] - first.window[0]
                       - reduce.total(first.busy())) / first.steps / 1e9
    assert sum(gaps.values()) == pytest.approx(idle_first_chip, rel=1e-6)
