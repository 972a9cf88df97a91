"""Unified exchange IR (xir/): plan→lower→execute pipeline tests.

The parity contract under test: an IR-routed exchange on the dense
wire emits the identical collective its direct-``lax`` predecessor
did, so ``HVD_TPU_XIR`` on/off is bitwise-invisible — for the dense
DP scheduler (PR 7 equivalence), MoE dispatch/combine, Ulysses flips,
the sparse embedding exchange, pipeline ppermute, and FSDP RS+AG.
Plus: lowering-pass resolution against the topology cost model, wire
eligibility gating per op class, byte accounting by network class,
workload-kind keying in the persistent store, and the observability
surface (kind-labeled gauges, XIR counters, timeline lanes).
"""

import json

import jax
from jax import lax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import metrics, sched, xir
from horovod_tpu.exceptions import HorovodTpuError
from horovod_tpu.runtime import WORLD_AXIS

pytestmark = pytest.mark.xir

N = 8


@pytest.fixture(autouse=True)
def _clean_overrides():
    yield
    sched.set_config_override(None)


def _shard_run(fn, *args, mesh=None, n_out=1):
    mesh = mesh or hvd.mesh()
    spec = P(WORLD_AXIS)
    out_specs = spec if n_out == 1 else (spec,) * n_out
    return jax.jit(jax.shard_map(
        fn, mesh=mesh, in_specs=(spec,) * len(args),
        out_specs=out_specs, check_vma=False,
    ))(*args)


class TestIrConstruction:
    def test_op_set_and_validation(self):
        with pytest.raises(HorovodTpuError, match="unknown exchange op"):
            xir.ExchangeOp("broadcast", "hvd")
        with pytest.raises(HorovodTpuError, match="unknown wire"):
            xir.ExchangeOp("all_reduce", "hvd", wire="fp4")
        with pytest.raises(HorovodTpuError, match="unknown lowering"):
            xir.ExchangeOp("all_reduce", "hvd", lowering="ring")

    def test_signature_deterministic_and_kind_sensitive(self):
        def build(kind):
            return xir.program(kind, [
                xir.all_to_all("ep", split_axis=0, concat_axis=1,
                               nbytes=1024, dtype="float32"),
            ])

        assert build("moe").signature() == build("moe").signature()
        assert build("moe").signature() != build("ulysses").signature()

    def test_attrs_hashable_and_accessible(self):
        op = xir.permute("pp", [(0, 1), (1, 0)], nbytes=64,
                         dtype="float32")
        assert op.attr("perm") == ((0, 1), (1, 0))
        hash(op.signature())  # must not raise

    def test_from_schedule_one_op_per_bucket(self):
        schedule = sched.build_schedule(
            [256, 256, 256], ["float32"] * 3,
            sched.SchedConfig(bucket_bytes=256),
        )
        prog = xir.from_schedule(schedule, kind="dense_grad")
        assert len(prog) == len(schedule)
        assert prog.kind == "dense_grad"
        for op, b in zip(prog.ops, schedule.buckets):
            assert op.op == "all_reduce"
            assert op.wire == b.wire
            assert op.lowering == b.lowering
            assert op.attr("nbytes") == b.nbytes
        rs = sched.build_schedule(
            [256], ["float32"],
            sched.SchedConfig(bucket_bytes=256, mode="reduce_scatter"),
        )
        rs_prog = xir.from_schedule(rs)
        assert rs_prog.ops[0].op == "reduce_scatter"
        assert rs_prog.ops[0].attr("paired_all_gather") is True


class TestEligibility:
    def test_reduce_ops_keep_quantized_wire(self):
        for op in xir.REDUCE_OPS:
            assert xir.eligible_wire(op, "int8", "float32") == "int8"
            assert xir.eligible_wire(op, "fp8", "float32") == "fp8"

    def test_shuffle_ops_cap_at_bf16(self):
        for op in ("all_to_all", "permute", "gather_dense_from_sparse"):
            assert xir.eligible_wire(op, "int8", "float32") == "off"
            assert xir.eligible_wire(op, "fp8", "float32") == "off"
            assert xir.eligible_wire(op, "bf16", "float32") == "bf16"

    def test_non_floating_always_dense(self):
        assert xir.eligible_wire("all_to_all", "bf16", "int32") == "off"
        assert xir.eligible_wire("all_reduce", "int8", "int32") == "off"

    def test_bf16_payload_needs_no_cast(self):
        assert xir.eligible_wire("all_to_all", "bf16", "bfloat16") == "off"


class TestLowering:
    def test_single_slice_resolves_flat(self, hvd_module):
        prog = xir.program("dense_grad", [
            xir.all_reduce(WORLD_AXIS, nbytes=1 << 24, dtype="float32"),
        ])
        lowered = xir.lower_program(prog, store=False)
        assert lowered.ops[0].lowering == "flat"
        assert lowered.lowered

    def test_two_slice_large_bucket_goes_hier(self, hvd_module,
                                              monkeypatch):
        from horovod_tpu import topo

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            prog = xir.program("dense_grad", [
                xir.all_reduce(WORLD_AXIS, nbytes=1 << 26,
                               dtype="float32"),
                xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1,
                               nbytes=1 << 26, dtype="float32"),
            ])
            lowered = xir.lower_program(prog, axis_size=8, store=False)
            assert lowered.ops[0].lowering == "hier"
            # shuffle ops never stage hierarchically
            assert lowered.ops[1].lowering == "flat"
        finally:
            topo.reset()

    def test_explicit_groups_stay_flat(self, hvd_module, monkeypatch):
        from horovod_tpu import topo

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            prog = xir.program("dense_grad", [
                xir.all_reduce(WORLD_AXIS, nbytes=1 << 26,
                               dtype="float32",
                               groups=[[0, 1, 2, 3], [4, 5, 6, 7]]),
            ])
            lowered = xir.lower_program(prog, axis_size=8, store=False)
            assert lowered.ops[0].lowering == "flat"
        finally:
            topo.reset()


class TestByteAccounting:
    def test_alltoall_split_single_slice(self, hvd_module):
        op = xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1,
                            nbytes=8000, dtype="float32")
        by = xir.op_network_bytes(op, axis_size=8)
        # single slice: everything is ICI, (n-1)/n of the buffer moves
        assert by["dcn"] == 0
        assert by["ici"] == int(8000 * 7 / 8)

    def test_alltoall_split_two_slice(self, hvd_module, monkeypatch):
        from horovod_tpu import topo

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            op = xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1,
                                nbytes=8000, dtype="float32")
            by = xir.op_network_bytes(op, axis_size=8)
            # k-1=3 same-slice peers, n-k=4 cross-slice peers
            assert by["ici"] == int(8000 * 3 / 8)
            assert by["dcn"] == int(8000 * 4 / 8)
        finally:
            topo.reset()

    def test_permute_dcn_share_from_perm(self, hvd_module, monkeypatch):
        from horovod_tpu import topo

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            ring = [(j, (j + 1) % 8) for j in range(8)]
            op = xir.permute(WORLD_AXIS, ring, nbytes=8000,
                             dtype="float32")
            by = xir.op_network_bytes(op, axis_size=8)
            # exactly 2 of the 8 hops cross the slice boundary
            assert by["dcn"] == int(8000 * 2 / 8)
            assert by["ici"] == 8000 - by["dcn"]
        finally:
            topo.reset()

    def test_bf16_wire_halves_payload(self, hvd_module):
        dense = xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1,
                               nbytes=8000, dtype="float32")
        bf16 = dense.replace(wire="bf16")
        assert xir.op_wire_nbytes(bf16) == xir.op_wire_nbytes(dense) // 2


class TestStoreKeying:
    def test_kind_discriminates_keys(self):
        sig = ("payload", (1, 2, 3))
        k_dense = sched.make_key(sig, kind="dense_grad")
        k_moe = sched.make_key(sig, kind="moe")
        assert k_dense != k_moe
        assert k_dense == sched.make_key(sig)  # default kind is dense

    def test_program_seeded_into_db(self, hvd_module, tmp_path,
                                    monkeypatch):
        db = tmp_path / "tune.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        xir.lower.reset()
        metrics.reset_counters("xir.db")
        prog = xir.program("moe", [
            xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1,
                           nbytes=4096, dtype="float32"),
        ])
        lowered = xir.lower_program(prog)
        assert metrics.get_counter("xir.db_seeded") == 1
        data = json.loads(db.read_text())
        (entry,) = data["entries"].values()
        assert entry["meta"]["kind"] == "moe"
        assert entry["bucket_bytes"] == 4096
        # second lowering of the same program: memoized, no extra write
        xir.lower_program(prog)
        assert metrics.get_counter("xir.db_seeded") == 1
        # a fresh process (reset memo) hits the stored entry
        xir.lower.reset()
        xir.lower_program(lowered)
        assert metrics.get_counter("xir.db_hit") == 1

    def test_stored_wire_adopted_when_eligible(self, hvd_module,
                                               tmp_path, monkeypatch):
        from horovod_tpu.sched.store import ScheduleStore

        db = tmp_path / "tune.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        xir.lower.reset()
        prog = xir.program("moe", [
            xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1,
                           nbytes=4096, dtype="float32"),
        ])
        key = xir.tuner_key(xir.lower_program(prog, store=False))
        ScheduleStore(str(db)).record(
            key, bucket_bytes=4096, wire="int8", lowering="hier",
            score=9.0,
        )
        lowered = xir.lower_program(prog)
        # int8 is ineligible for a shuffle op -> off; hier -> flat
        assert lowered.ops[0].wire == "off"
        assert lowered.ops[0].lowering == "flat"


class TestDenseGradParity:
    """The tentpole acceptance: f32 dense DP programs through the IR
    are bitwise-identical to a per-leaf ``pmean`` and the optax
    update."""

    def _losses(self, through_ir):
        import optax

        X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
        Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)

        def loss_fn(p, b):
            x, y = b
            return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)

        params = {"w1": jnp.full((4, 4), 0.2),
                  "w2": jnp.full((4, 2), 0.5), "b": jnp.zeros((2,))}
        batch = (jnp.asarray(X), jnp.asarray(Y))
        if through_ir:
            sched.set_config_override(
                sched.SchedConfig(bucket_bytes=64)
            )
            tx = hvd.DistributedOptimizer(optax.sgd(0.1))
            step = hvd.distributed_train_step(loss_fn, tx)
            st = step.init(params)
        else:
            tx = optax.sgd(0.1)

            def plain(p, st, b):
                loss, g = jax.value_and_grad(loss_fn)(p, b)
                g = jax.tree.map(lambda x: lax.pmean(x, WORLD_AXIS), g)
                updates, st = tx.update(g, st, p)
                return (optax.apply_updates(p, updates), st,
                        lax.pmean(loss, WORLD_AXIS))

            step = jax.jit(jax.shard_map(
                plain, mesh=hvd.mesh(),
                in_specs=(P(), P(), P(WORLD_AXIS)),
                out_specs=(P(), P(), P()), check_vma=False,
            ))
            st = tx.init(params)
        out = []
        for _ in range(8):
            params, st, loss = step(params, st, batch)
            out.append(float(loss))
        return out

    def test_f32_dense_losses_bitwise(self, hvd_module):
        assert self._losses(True) == self._losses(False)

    def test_dense_program_counted(self, hvd_module):
        metrics.reset_counters("xir.programs")
        self._losses(True)
        assert metrics.get_counter("xir.programs.dense_grad") > 0


class TestWorkloadParity:
    def test_pipeline_permute_bitwise(self, hvd_module):
        from horovod_tpu.parallel.pipeline import pipeline_apply

        mb = np.random.RandomState(3).randn(4, 2, 6).astype(np.float32)
        w = np.random.RandomState(4).randn(8, 6, 6).astype(
            np.float32) * 0.1

        def pp(wstack, m):
            return pipeline_apply(
                lambda p, a: jnp.tanh(a @ p), wstack[0], m,
                axis=WORLD_AXIS,
            )

        def plain(wstack, m):
            # the same schedule unrolled, its hops lax.ppermute itself
            stage = lax.axis_index(WORLD_AXIS)
            shift = [(j, (j + 1) % N) for j in range(N)]
            act, out = jnp.zeros_like(m[0]), jnp.zeros_like(m)
            for s in range(m.shape[0] + N - 1):
                x_in = m[min(s, m.shape[0] - 1)]
                y = jnp.tanh(jnp.where(stage == 0, x_in, act) @ wstack[0])
                if s >= N - 1:
                    k = s - (N - 1)
                    out = out.at[k].set(
                        jnp.where(stage == N - 1, y, out[k]))
                act = lax.ppermute(y, WORLD_AXIS, shift)
            return lax.psum(
                jnp.where(stage == N - 1, out, jnp.zeros_like(out)),
                WORLD_AXIS)

        def run(fn):
            return np.asarray(jax.jit(jax.shard_map(
                fn, mesh=hvd.mesh(), in_specs=(P(WORLD_AXIS), P()),
                out_specs=P(), check_vma=False,
            ))(w, mb))

        np.testing.assert_array_equal(run(pp), run(plain))

    def test_fsdp_step_bitwise(self, hvd_module):
        import optax

        from horovod_tpu.optim.zero import fsdp_train_step

        X = np.random.RandomState(5).randn(8, 4).astype(np.float32)
        params = {"w": jnp.asarray(
            np.random.RandomState(6).randn(4, 2).astype(np.float32))}

        def loss_fn(p, b):
            return jnp.mean((b @ p["w"]) ** 2)

        step = fsdp_train_step(loss_fn, optax.sgd(0.1))
        ps, st = step.init(params)
        got = []
        for _ in range(3):
            ps, st, loss = step(ps, st, jnp.asarray(X))
            got.append(float(loss))

        # the plain reference: the same step on the raveled vector, one
        # element a rank, written with lax's own collectives
        tx = optax.sgd(0.1)

        def plain(shard, st, b):
            full = lax.all_gather(shard, WORLD_AXIS, tiled=True)
            loss, g = jax.value_and_grad(
                lambda f: loss_fn({"w": f.reshape(4, 2)}, b))(full)
            g = lax.psum_scatter(
                g, WORLD_AXIS, scatter_dimension=0, tiled=True) / N
            updates, st = tx.update(g, st, shard)
            return (optax.apply_updates(shard, updates), st,
                    lax.pmean(loss, WORLD_AXIS))

        ref_step = jax.jit(jax.shard_map(
            plain, mesh=hvd.mesh(),
            in_specs=(P(WORLD_AXIS), P(), P(WORLD_AXIS)),
            out_specs=(P(WORLD_AXIS), P(), P()), check_vma=False,
        ))
        shard, ref_st = params["w"].reshape(-1), tx.init(jnp.zeros((1,)))
        want = []
        for _ in range(3):
            shard, ref_st, loss = ref_step(shard, ref_st, jnp.asarray(X))
            want.append(float(loss))
        assert got == want

    def test_sparse_exchange_bitwise_and_observable(self, hvd_module):
        from horovod_tpu.ops.sparse import IndexedSlices, sparse_allreduce

        idx = np.tile(np.arange(4, dtype=np.int32), N)
        vals = np.random.RandomState(2).randn(N * 4, 3).astype(np.float32)

        def sp(i, v):
            out = sparse_allreduce(
                IndexedSlices(i, v, (16, 3)), axis=WORLD_AXIS
            )
            return out.values

        def plain(i, v):
            return lax.all_gather(v, WORLD_AXIS, tiled=True) / N

        def run(fn):
            return np.asarray(jax.jit(jax.shard_map(
                fn, mesh=hvd.mesh(),
                in_specs=(P(WORLD_AXIS), P(WORLD_AXIS)),
                out_specs=P(WORLD_AXIS), check_vma=False,
            ))(idx, vals))

        metrics.reset_counters("xir.programs.sparse_embed")
        np.testing.assert_array_equal(run(sp), run(plain))
        assert metrics.get_counter("xir.programs.sparse_embed") == 1
        assert metrics.get_gauge(
            "sched.wire_bytes", {"wire": "off", "kind": "sparse_embed"}
        ) > 0


class TestInterpReduceOps:
    def test_all_reduce_matches_psum(self, hvd_module):
        x = np.random.RandomState(7).randn(N, 5).astype(np.float32)

        def f(a):
            op = xir.all_reduce(WORLD_AXIS, nbytes=a.size * 4,
                                dtype="float32", lowering="flat")
            return xir.run_op(op, a), jax.lax.psum(a, WORLD_AXIS)

        got, want = _shard_run(f, x, n_out=2)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    def test_rs_ag_roundtrip(self, hvd_module):
        x = np.random.RandomState(8).randn(N, 16).astype(np.float32)

        def f(a):
            flat = a.reshape(-1)
            rs = xir.reduce_scatter(WORLD_AXIS, lowering="flat")
            ag = xir.all_gather(WORLD_AXIS, lowering="flat")
            shard = xir.run_op(rs, flat)
            out = xir.run_op(ag, shard)
            return out.reshape(a.shape), jax.lax.psum(a, WORLD_AXIS)

        got, want = _shard_run(f, x, n_out=2)
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6
        )

    def test_reduce_scatter_indivisible_raises(self, hvd_module):
        x = np.random.RandomState(9).randn(N, 9).astype(np.float32)

        def f(a):
            rs = xir.reduce_scatter(WORLD_AXIS, lowering="flat")
            return xir.run_op(rs, a.reshape(-1))

        with pytest.raises(Exception, match="divide"):
            _shard_run(f, x)

    def test_execute_arity_mismatch(self, hvd_module):
        prog = xir.program("moe", [
            xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=1),
        ])
        with pytest.raises(HorovodTpuError, match="payloads"):
            xir.execute(prog, [1, 2], store=False)


class TestWireKnob:
    def test_wire_request_default_off_and_validated(self, monkeypatch):
        monkeypatch.delenv("HVD_TPU_XIR_WIRE", raising=False)
        monkeypatch.setenv("HVD_TPU_SCHED_WIRE", "int8")
        # deliberately NOT inherited from the gradient wire knob
        assert xir.wire_request() == "off"
        monkeypatch.setenv("HVD_TPU_XIR_WIRE", "e4m3")
        assert xir.wire_request() == "fp8"
        monkeypatch.setenv("HVD_TPU_XIR_WIRE", "fp4")
        with pytest.raises(HorovodTpuError, match="XIR_WIRE"):
            xir.wire_request()
