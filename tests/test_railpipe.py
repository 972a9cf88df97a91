"""XIR rail pipeliner unit tests (xir/pipeline.py + its hooks).

The execution-parity column lives in
tests/test_collective_matrix.py::TestPipelineColumn; this file covers
the pass itself: the knob, engagement rules, the max-of-rails pricing
and split-point search, the cross-workload merge rules, the plan-stage
hook, ZeRO-1 / grad-sync parity under the rail chains, tuner
exploration with tune-DB persistence, and the store fingerprint fold.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu import metrics, sched, topo, xir
from horovod_tpu.exceptions import HorovodTpuError
from horovod_tpu.topo import model as topo_model
from horovod_tpu.xir import pipeline as railpipe

pytestmark = pytest.mark.railpipe


@pytest.fixture(autouse=True)
def _clean():
    yield
    railpipe.set_mode_override(None)
    sched.set_config_override(None)


@pytest.fixture()
def two_slice(monkeypatch):
    monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
    topo.reset()
    yield
    topo.reset()


def _bucket(nbytes, lowering="hier", wire="off", dtypes=("float32",)):
    from horovod_tpu.sched.plan import Bucket

    return Bucket(indices=(0,), nbytes=nbytes, wire_dtypes=tuple(dtypes),
                  wire=wire, lowering=lowering)


class _Sched:
    def __init__(self, buckets):
        self.buckets = tuple(buckets)


# ----------------------------------------------------------- the knob

class TestKnob:
    def test_default_is_auto(self, monkeypatch):
        monkeypatch.delenv("HVD_TPU_XIR_PIPELINE", raising=False)
        assert railpipe.mode() == "auto"

    @pytest.mark.parametrize("raw,want", [
        ("off", "off"), ("0", "off"), ("false", "off"),
        ("on", "on"), ("1", "on"), ("auto", "auto"), ("AUTO", "auto"),
    ])
    def test_spellings(self, monkeypatch, raw, want):
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", raw)
        assert railpipe.mode() == want

    def test_bad_spelling_raises(self, monkeypatch):
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "sideways")
        with pytest.raises(HorovodTpuError, match="XIR_PIPELINE"):
            railpipe.mode()

    def test_override_wins_and_validates(self, monkeypatch):
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "off")
        railpipe.set_mode_override("on")
        assert railpipe.mode() == "on"
        with pytest.raises(HorovodTpuError):
            railpipe.set_mode_override("diagonal")


# --------------------------------------------------------- engagement

class TestEngagement:
    def test_off_never_engages(self, two_slice):
        railpipe.set_mode_override("off")
        s = _Sched([_bucket(1 << 20), _bucket(1 << 20)])
        assert not railpipe.engaged(s, 8)

    def test_needs_two_decomposable_buckets(self, two_slice):
        railpipe.set_mode_override("on")
        assert not railpipe.engaged(_Sched([_bucket(1 << 20)]), 8)
        assert railpipe.engaged(
            _Sched([_bucket(1 << 20), _bucket(1 << 20)]), 8
        )

    def test_hier_adasum_and_flat_not_decomposable(self):
        assert railpipe.decomposable(_bucket(1, "hier"))
        assert not railpipe.decomposable(_bucket(1, "hier_adasum"))
        assert not railpipe.decomposable(_bucket(1, "flat"))
        assert not railpipe.decomposable(
            _bucket(1, "hier", dtypes=("float32", "bfloat16"))
        )

    def test_auto_engages_on_multi_slice(self, two_slice):
        railpipe.set_mode_override("auto")
        s = _Sched([_bucket(1 << 22), _bucket(1 << 22)])
        assert railpipe.engaged(s, 8)

    def test_single_slice_never_engages(self):
        # default topology of the 8-CPU world: one slice, so plans
        # resolve flat and nothing decomposes
        railpipe.set_mode_override("on")
        s = sched.build_schedule(
            [1 << 20] * 4, ["float32"] * 4,
            sched.SchedConfig(bucket_bytes=1 << 20),
        )
        assert not railpipe.engaged(s, 8)


# ------------------------------------------------------------ pricing

class TestPricing:
    def test_pipelined_bounds(self, two_slice):
        items = [("all_reduce", 1 << 22, "hier")] * 4
        serial = railpipe.estimate_schedule_cost(items, 8)
        pipe = railpipe.estimate_schedule_cost(items, 8, pipelined=True)
        splits = [railpipe.rail_times(*i, 8) for i in items]
        max_rail = max(sum(s[0] for s in splits),
                       sum(s[1] for s in splits))
        assert max_rail <= pipe < serial

    def test_rail_times_sum_to_estimate(self, two_slice):
        t = topo_model.current()
        for lowering in ("flat", "hier", "hier_adasum"):
            ici, dcn = t.rail_times("all_reduce", 1 << 20, lowering, 8)
            assert abs(
                (ici + dcn)
                - t.estimate_cost("all_reduce", 1 << 20, lowering, 8)
            ) < 1e-12

    def test_estimate_program_cost_hook(self, two_slice):
        prog = xir.program("dense_grad", [
            xir.all_reduce("hvd", lowering="hier", nbytes=1 << 22,
                           dtype="float32", bucket=i)
            for i in range(3)
        ])
        serial = xir.estimate_program_cost(prog, 8, pipelined=False)
        pipe = xir.estimate_program_cost(prog, 8, pipelined=True)
        assert 0 < pipe < serial

    def test_empty_schedule_costs_zero(self):
        assert railpipe.estimate_schedule_cost([], 8) == 0.0
        assert railpipe.estimate_schedule_cost(
            [], 8, pipelined=True
        ) == 0.0


# ------------------------------------------------------- split points

class TestSplitPoints:
    def test_suggests_only_under_on(self, two_slice):
        railpipe.set_mode_override("auto")
        assert railpipe.plan_bucket_bytes(1 << 24, 8) is None
        railpipe.set_mode_override("on")
        b = railpipe.plan_bucket_bytes(1 << 24, 8)
        assert b is not None and 65536 <= b <= (1 << 23)

    def test_single_slice_declines(self):
        railpipe.set_mode_override("on")
        topo.set_topology_override(
            topo_model.Topology(num_slices=1, slice_size=8)
        )
        try:
            assert railpipe.plan_bucket_bytes(1 << 24, 8) is None
        finally:
            topo.set_topology_override(None)

    def test_tiny_payload_declines(self, two_slice):
        railpipe.set_mode_override("on")
        assert railpipe.plan_bucket_bytes(1024, 8) is None

    def test_plan_stage_adopts_split(self, two_slice):
        """build_schedule with no pinned size splits under on-mode —
        and produces the identical (unsplit) plan under auto."""
        sizes = [1 << 22] * 8  # 32 MiB of gradients
        cfg = sched.SchedConfig(bucket_bytes=None, lowering="hier")
        railpipe.set_mode_override("auto")
        auto_plan = sched.build_schedule(sizes, ["float32"] * 8, cfg,
                                         axis_size=8)
        railpipe.set_mode_override("off")
        off_plan = sched.build_schedule(sizes, ["float32"] * 8, cfg,
                                        axis_size=8)
        assert auto_plan.signature() == off_plan.signature()
        railpipe.set_mode_override("on")
        on_plan = sched.build_schedule(sizes, ["float32"] * 8, cfg,
                                       axis_size=8)
        assert len(on_plan) >= 2  # a pipeline to run
        assert on_plan.total_bytes == off_plan.total_bytes


# -------------------------------------------------------------- merge

class TestMerge:
    def _dense(self, lowering="flat", axis="hvd"):
        return xir.program("dense_grad", [
            xir.all_reduce(axis, lowering=lowering, nbytes=1 << 22,
                           dtype="float32", bucket=i) for i in range(2)
        ])

    def _a2a_subgroup(self):
        # slice-local subgroups: ICI-only traffic
        groups = tuple(tuple(range(j * 4, (j + 1) * 4))
                       for j in range(2))
        return xir.program("moe", [xir.all_to_all(
            "hvd", split_axis=0, concat_axis=1, groups=groups,
            nbytes=1 << 18, dtype="float32",
        )])

    def test_rails_disjoint_dcn_vs_ici(self, two_slice):
        dense = xir.lower_program(self._dense("flat"), 8, store=False)
        a2a = xir.lower_program(self._a2a_subgroup(), 8, store=False)
        assert railpipe.program_rails(dense, 8) == frozenset({"dcn"})
        assert railpipe.program_rails(a2a, 8) == frozenset({"ici"})
        assert railpipe.rails_disjoint(dense, a2a, 8)

    def test_merge_declines_shared_rails(self, two_slice):
        railpipe.set_mode_override("on")
        hier = xir.lower_program(self._dense("hier"), 8, store=False)
        a2a = xir.lower_program(self._a2a_subgroup(), 8, store=False)
        assert railpipe.merge([hier, a2a], 8) is None  # hier = both rails
        assert railpipe.merge([hier], 8) is None  # one program

    def test_merge_declines_when_off(self, two_slice):
        railpipe.set_mode_override("off")
        dense = xir.lower_program(self._dense("flat"), 8, store=False)
        a2a = xir.lower_program(self._a2a_subgroup(), 8, store=False)
        assert railpipe.merge([dense, a2a], 8) is None

    def test_merge_interleaves_rails(self, two_slice):
        railpipe.set_mode_override("on")
        dense = xir.lower_program(self._dense("flat"), 8, store=False)
        a2a = xir.lower_program(self._a2a_subgroup(), 8, store=False)
        merged = railpipe.merge([dense, a2a], 8)
        assert merged is not None
        assert merged.kind == "dense_grad+moe"
        assert len(merged.ops) == 3
        rails = [railpipe.op_rail(op, 8) for op in merged.ops]
        # the ICI rider lands between the two DCN buckets
        assert rails[0] != rails[1]
        assert [op.bucket for op in merged.ops] == [0, 1, 2]
        # deterministic: same inputs, same order
        again = railpipe.merge([dense, a2a], 8)
        assert again.signature() == merged.signature()


# ------------------------------------------- zero1 / grad_sync parity

class TestRailParity:
    def _losses_zero1(self, mode, hvdm):
        import optax

        railpipe.set_mode_override(mode)
        cfg = sched.SchedConfig(bucket_bytes=16 * 1024,
                                lowering="hier")
        rng = np.random.RandomState(5)
        X = rng.randn(16, 32).astype(np.float32)
        Y = rng.randn(16, 4).astype(np.float32)

        def loss_fn(p, b):
            x, y = b
            return jnp.mean((x @ p["w"] - y) ** 2)

        p = {"w": jnp.asarray(
            np.random.RandomState(2).randn(32, 4).astype(np.float32)
        )}
        step = sched.bucketed_zero_step(loss_fn, optax_sgd(), cfg=cfg)
        st = step.init(p)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        losses = []
        for _ in range(4):
            p, st, loss = step(p, st, batch)
            losses.append(float(loss))
        return losses

    def test_bucketed_zero_step_bitwise(self, hvd_module, two_slice):
        off = self._losses_zero1("off", hvd_module)
        on = self._losses_zero1("on", hvd_module)
        assert off == on

    def test_grad_sync_bucketed_bitwise(self, hvd_module, two_slice):
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.runtime import WORLD_AXIS, get_runtime
        from horovod_tpu.sched.execute import sync_gradients_bucketed

        g = {"a": np.random.RandomState(9).randn(8, 64)
             .astype(np.float32)}
        cfg = sched.SchedConfig(bucket_bytes=64,
                                lowering="hier")

        def f(grads):
            return sync_gradients_bucketed(grads, None, (WORLD_AXIS,),
                                           cfg)

        def run():
            return np.asarray(jax.jit(jax.shard_map(
                f, mesh=get_runtime().mesh,
                in_specs=({"a": P(WORLD_AXIS)},),
                out_specs={"a": P(WORLD_AXIS)}, check_vma=False,
            ))(g)["a"])

        railpipe.set_mode_override("off")
        off = run()
        railpipe.set_mode_override("on")
        on = run()
        np.testing.assert_array_equal(off, on)


def optax_sgd():
    import optax

    return optax.sgd(0.05)


# ----------------------------------------------------- tuner + store

class TestTunerPipelineKnob:
    SIG = ("railpipe-test-sig", 1)

    def _drive(self, tuner, favored="on", windows=16):
        for _ in range(windows):
            if tuner.converged:
                break
            tuner.begin_window()
            cand = tuner.pipeline()
            steps = 30 if cand == favored else 10
            metrics.inc_counter("train.steps", steps)
            metrics.observe("train.step_seconds", 0.5)
            metrics.set_gauge("sched.bytes_per_step", 1000.0)
            tuner.end_window()
        return tuner

    def test_explores_and_freezes_winner(self, two_slice, monkeypatch):
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "auto")
        tuner = sched.ScheduleTuner(explore_pipeline=True,
                                    warmup_windows=2)
        assert not tuner.converged
        seen = set()
        for _ in range(3):
            tuner.begin_window()
            seen.add(tuner.pipeline())
            metrics.inc_counter(
                "train.steps", 30 if tuner.pipeline() == "on" else 10
            )
            metrics.observe("train.step_seconds", 0.5)
            metrics.set_gauge("sched.bytes_per_step", 1000.0)
            tuner.end_window()
        assert seen == {"off", "on", "auto"}  # every candidate ran
        assert tuner._pipeline_frozen == "on"
        # the winner is pinned into the env knob for the trace
        assert railpipe.mode() == "on"

    def test_single_slice_pins_off(self):
        topo.set_topology_override(
            topo_model.Topology(num_slices=1, slice_size=8)
        )
        try:
            tuner = sched.ScheduleTuner(explore_pipeline=True)
            assert tuner.pipeline() == "off"
        finally:
            topo.set_topology_override(None)

    def test_cold_db_converges_to_pipelined_and_warm_starts(
            self, two_slice, tmp_path, monkeypatch):
        """The acceptance loop: a cold DB explores, the pipelined
        candidate wins, the winner persists (meta.pipeline), and a
        second tuner warm-starts already pipelined at window 0."""
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "auto")
        db = tmp_path / "tune.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        t1 = sched.ScheduleTuner(explore_pipeline=True,
                                 warmup_windows=2, store="env",
                                 store_key=self.SIG)
        self._drive(t1, favored="on")
        assert t1.converged
        assert t1.pipeline() == "on"
        entries = json.loads(db.read_text())["entries"]
        assert any(
            (e.get("meta") or {}).get("pipeline") == "on"
            for e in entries.values()
        )
        # warm start: converged at window 0, knob re-pinned
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "auto")
        t2 = sched.ScheduleTuner(explore_pipeline=True, store="env",
                                 store_key=self.SIG)
        assert t2.converged
        assert t2.pipeline() == "on"
        assert railpipe.mode() == "on"

    def test_fingerprint_folds_resolved_mode(self, monkeypatch):
        from horovod_tpu.sched.store import knob_fingerprint

        monkeypatch.delenv("HVD_TPU_XIR_PIPELINE", raising=False)
        unset = knob_fingerprint()
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "auto")
        assert knob_fingerprint() == unset  # unset ≡ explicit default
        monkeypatch.setenv("HVD_TPU_XIR_PIPELINE", "on")
        assert knob_fingerprint() != unset  # split points differ
