"""Collective x dtype x process-set sweep and error-case matrix.

Models the reference's exhaustive parallel test enumeration
(``test/parallel/test_torch.py`` — allreduce/allgather/broadcast across
every supported dtype, process-set variants, and typed error cases)."""

import jax.numpy as jnp
import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.exceptions import HorovodTpuError

N = 8

DTYPES = [
    np.float32, np.float64, np.float16, jnp.bfloat16,
    np.int32, np.int64, np.int8, np.uint8,
]


def _tol(dtype):
    if dtype in (np.float16, jnp.bfloat16):
        return dict(rtol=1e-2, atol=1e-2)
    # float64 silently downcasts to f32 under JAX's default x64-disabled
    # mode, so exact comparison is off the table for it too.
    return dict(rtol=1e-5, atol=1e-6)


def _is_float(dtype):
    return jnp.issubdtype(jnp.dtype(dtype), jnp.floating)


def _data(dtype, shape=(N, 5), seed=0):
    rng = np.random.RandomState(seed)
    if _is_float(dtype):
        return rng.uniform(-2, 2, shape).astype(dtype)
    return rng.randint(0, 7, shape).astype(dtype)


class TestDtypeSweep:
    @pytest.mark.parametrize("dtype", DTYPES, ids=str)
    def test_allreduce_sum(self, hvd_module, dtype):
        x = _data(dtype)
        y = np.asarray(hvd.allreduce(x, op=hvd.Sum)).astype(np.float64)
        expect = np.asarray(x).astype(np.float64).sum(axis=0)
        for r in range(N):
            np.testing.assert_allclose(y[r], expect, **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES, ids=str)
    def test_allreduce_average(self, hvd_module, dtype):
        x = _data(dtype, seed=1)
        y = np.asarray(hvd.allreduce(x, average=True)).astype(np.float64)
        expect = np.asarray(x).astype(np.float64).mean(axis=0)
        if not _is_float(dtype):
            # integer average truncates like the reference's int path
            expect = np.trunc(expect)
        for r in range(N):
            np.testing.assert_allclose(y[r], expect, **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES, ids=str)
    @pytest.mark.parametrize("opname", ["min", "max"])
    def test_allreduce_minmax(self, hvd_module, dtype, opname):
        x = _data(dtype, seed=2)
        op = hvd.Min if opname == "min" else hvd.Max
        y = np.asarray(hvd.allreduce(x, op=op)).astype(np.float64)
        red = np.min if opname == "min" else np.max
        expect = red(np.asarray(x).astype(np.float64), axis=0)
        for r in range(N):
            np.testing.assert_allclose(y[r], expect, **_tol(dtype))

    @pytest.mark.parametrize("dtype", DTYPES, ids=str)
    def test_allgather(self, hvd_module, dtype):
        x = _data(dtype, shape=(N, 2, 3), seed=3)
        y = np.asarray(hvd.allgather(x))
        expect = np.asarray(x).reshape(N * 2, 3).astype(np.float64)
        for r in range(N):
            np.testing.assert_allclose(
                y[r].astype(np.float64), expect, **_tol(dtype)
            )

    @pytest.mark.parametrize("dtype", DTYPES, ids=str)
    def test_broadcast(self, hvd_module, dtype):
        x = _data(dtype, seed=4)
        y = np.asarray(hvd.broadcast(x, root_rank=3))
        for r in range(N):
            np.testing.assert_allclose(
                y[r].astype(np.float64),
                np.asarray(x)[3].astype(np.float64), **_tol(dtype)
            )

    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.int32],
                             ids=str)
    def test_alltoall(self, hvd_module, dtype):
        x = _data(dtype, shape=(N, N, 2), seed=5)
        y = np.asarray(hvd.alltoall(x))
        for r in range(N):
            for j in range(N):
                np.testing.assert_array_equal(y[r, j], np.asarray(x)[j, r])

    @pytest.mark.parametrize("dtype", [np.float32, np.float16, np.int32],
                             ids=str)
    def test_reducescatter(self, hvd_module, dtype):
        x = _data(dtype, shape=(N, N, 3), seed=6)
        y = np.asarray(hvd.reducescatter(x, op=hvd.Sum)).astype(np.float64)
        full = np.asarray(x).astype(np.float64).sum(axis=0)
        for r in range(N):  # rank r's shard keeps the leading dim: (1, 3)
            np.testing.assert_allclose(y[r], full[r : r + 1], **_tol(dtype))


class TestAllgatherV:
    def test_ragged_first_dims(self, hvd_module):
        rng = np.random.RandomState(0)
        xs = [rng.randn(r + 1, 3).astype(np.float32) for r in range(N)]
        out = np.asarray(hvd.allgather_v(xs))
        expect = np.concatenate(xs, axis=0)
        assert out.shape == (N * (N + 1) // 2, 3)
        np.testing.assert_allclose(out, expect, rtol=1e-6)

    def test_ragged_with_empty_rank(self, hvd_module):
        xs = [np.ones((2, 2), np.float32) for _ in range(N)]
        xs[3] = np.zeros((0, 2), np.float32)  # a rank with no rows
        out = np.asarray(hvd.allgather_v(xs))
        assert out.shape == ((N - 1) * 2, 2)
        np.testing.assert_allclose(out, 1.0)

    def test_ragged_subset(self, hvd_module, monkeypatch):
        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        ps = hvd.add_process_set([0, 1, 2])
        xs = [np.full((r + 1, 2), float(r), np.float32) for r in range(N)]
        out = np.asarray(hvd.allgather_v(xs, process_set=ps))
        expect = np.concatenate([xs[0], xs[1], xs[2]], axis=0)
        np.testing.assert_allclose(out, expect)
        hvd.remove_process_set(ps)

    def test_trailing_mismatch_rejected(self, hvd_module):
        from horovod_tpu.exceptions import HorovodTpuError

        xs = [np.ones((2, 3))] * (N - 1) + [np.ones((2, 4))]
        with pytest.raises(HorovodTpuError, match="trailing"):
            hvd.allgather_v(xs)


class TestProcessSetSweep:
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16, np.int32],
                             ids=str)
    @pytest.mark.parametrize("members", [[0, 1, 2, 3], [1, 5, 6]],
                             ids=["partition", "arbitrary"])
    def test_allreduce_sum_subset(self, hvd_module, monkeypatch, dtype,
                                  members):
        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        ps = hvd.add_process_set(members)
        x = _data(dtype, seed=7)
        y = np.asarray(hvd.allreduce(x, op=hvd.Sum, process_set=ps)).astype(
            np.float64
        )
        expect = np.asarray(x[members]).astype(np.float64).sum(axis=0)
        for r in members:
            np.testing.assert_allclose(y[r], expect, **_tol(dtype))
        others = [r for r in range(N) if r not in members]
        np.testing.assert_array_equal(
            y[others], np.asarray(x)[others].astype(np.float64)
        )
        hvd.remove_process_set(ps)


class TestErrorMatrix:
    def test_average_and_op_mutually_exclusive(self, hvd_module):
        with pytest.raises(ValueError, match="either average or op"):
            hvd.allreduce(np.zeros((N, 2), np.float32), average=True,
                          op=hvd.Sum)

    def test_wrong_leading_dim_rejected(self, hvd_module):
        with pytest.raises(HorovodTpuError, match="leading"):
            hvd.allreduce(np.zeros((N + 1, 2), np.float32))

    def test_scalar_rejected(self, hvd_module):
        with pytest.raises(HorovodTpuError):
            hvd.allreduce(np.float32(1.0))

    def test_unregistered_process_set_rejected(self, hvd_module):
        from horovod_tpu.process_sets import ProcessSet

        ghost = ProcessSet([0, 1])
        with pytest.raises(HorovodTpuError, match="not registered"):
            hvd.allreduce(np.zeros((N, 2), np.float32), process_set=ghost)

    def test_alltoall_bad_splits_sum(self, hvd_module):
        splits = np.full((N, N), 1)
        splits[0, 0] = 2  # row sums no longer equal the row count
        with pytest.raises(HorovodTpuError, match="sum"):
            hvd.alltoall(np.zeros((N, N, 2), np.float32), splits=splits)

    def test_alltoall_bad_splits_shape(self, hvd_module):
        with pytest.raises(HorovodTpuError, match="shape"):
            hvd.alltoall(np.zeros((N, N, 2), np.float32),
                         splits=np.ones((2, 2), np.int32))

    def test_reducescatter_indivisible(self, hvd_module):
        with pytest.raises(Exception, match="divisible"):
            hvd.reducescatter(np.zeros((N, N + 1, 2), np.float32))

    def test_grouped_allreduce_empty(self, hvd_module):
        assert hvd.grouped_allreduce([]) == []

    def test_adasum_with_average_flag_conflict(self, hvd_module):
        with pytest.raises(ValueError, match="either average or op"):
            hvd.allreduce(np.zeros((N, 2), np.float32), average=True,
                          op=hvd.Adasum)


class TestNames:
    def test_duplicate_names_allowed_by_design(self, hvd_module):
        """The reference errors on a duplicate in-flight tensor name
        (its background queue keys submissions by name,
        ``operations.cc`` EnqueueTensorAllreduce duplicate check).
        Here there is no queue to collide in — XLA orders the program —
        so the same name may be reused freely, sync or async."""
        x = np.ones((N, 2), np.float32)
        a = hvd.allreduce_async(x, name="dup", op=hvd.Sum)
        b = hvd.allreduce_async(x, name="dup", op=hvd.Sum)
        np.testing.assert_allclose(np.asarray(hvd.synchronize(a)),
                                   np.asarray(hvd.synchronize(b)))
        y1 = hvd.allreduce(x, name="dup", op=hvd.Sum)
        y2 = hvd.allreduce(x, name="dup", op=hvd.Sum)
        np.testing.assert_allclose(np.asarray(y1), np.asarray(y2))

    def test_async_poll_and_wait(self, hvd_module):
        h = hvd.allreduce_async(np.ones((N, 3), np.float32), name="h1")
        assert hvd.poll(h) in (True, False)
        out = np.asarray(h.wait())
        np.testing.assert_allclose(out, 1.0)


class TestGroupedErrorCases:
    def test_grouped_mismatched_leading_dim(self, hvd_module):
        xs = [np.ones((N, 2), np.float32), np.ones((N + 1, 2), np.float32)]
        with pytest.raises(HorovodTpuError, match="leading"):
            hvd.grouped_allreduce(xs, op=hvd.Sum)

    def test_grouped_scalar_member_rejected(self, hvd_module):
        xs = [np.ones((N, 2), np.float32), np.float32(3.0)]
        with pytest.raises(HorovodTpuError):
            hvd.grouped_allreduce(xs, op=hvd.Sum)


class TestGroupedOps:
    @pytest.mark.parametrize("dtype", [np.float32, jnp.bfloat16], ids=str)
    def test_grouped_mixed_shapes(self, hvd_module, dtype):
        xs = [_data(dtype, shape=(N, s), seed=s) for s in (3, 7, 1)]
        ys = hvd.grouped_allreduce(xs, op=hvd.Sum)
        for x, y in zip(xs, ys):
            expect = np.asarray(x).astype(np.float64).sum(axis=0)
            for r in range(N):
                np.testing.assert_allclose(
                    np.asarray(y)[r].astype(np.float64), expect, **_tol(dtype)
                )

    def test_grouped_mixed_dtypes(self, hvd_module):
        xs = [
            _data(np.float32, shape=(N, 4), seed=10),
            _data(np.int32, shape=(N, 4), seed=11),
            _data(np.float32, shape=(N, 2), seed=12),
        ]
        ys = hvd.grouped_allreduce(xs, op=hvd.Sum)
        for x, y in zip(xs, ys):
            expect = np.asarray(x).astype(np.float64).sum(axis=0)
            for r in range(N):
                np.testing.assert_allclose(
                    np.asarray(y)[r].astype(np.float64), expect, rtol=1e-5
                )


class TestHierarchicalColumn:
    """Hierarchical (ICI/DCN two-level) lowering column of the matrix:
    flat vs hier equality across dtypes, process-set interplay, and a
    dp×tp hybrid mesh (topo/, forced 2-slice topology)."""

    @pytest.fixture(autouse=True)
    def _forced_two_slice(self, monkeypatch):
        from horovod_tpu import topo

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        yield
        topo.reset()

    def _run(self, fn, *args, n_out=2):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.runtime import WORLD_AXIS, get_runtime

        mesh = get_runtime().mesh
        spec = P(WORLD_AXIS)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * len(args),
            out_specs=(spec,) * n_out, check_vma=False,
        ))(*args)

    @pytest.mark.parametrize(
        "dtype", [np.float32, np.float16, jnp.bfloat16, np.int32], ids=str
    )
    def test_allreduce_flat_vs_hier(self, hvd_module, dtype):
        import jax

        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        x = _data(dtype, shape=(N, 37), seed=20)

        def f(a):
            return jax.lax.psum(a, WORLD_AXIS), \
                topo.hierarchical_all_reduce(a, WORLD_AXIS, op=Sum)

        flat, hier = self._run(f, x)
        if _is_float(dtype):
            np.testing.assert_allclose(
                np.asarray(flat, np.float64),
                np.asarray(hier, np.float64), **_tol(dtype)
            )
        else:
            # integer sums are exact: hier must be bitwise equal
            np.testing.assert_array_equal(
                np.asarray(flat), np.asarray(hier)
            )

    def test_allreduce_bitwise_f32_exact_sums(self, hvd_module):
        """f32 with integer values: all partial sums representable, so
        the two lowerings agree bit for bit."""
        import jax

        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        x = np.random.RandomState(21).randint(
            -16, 17, (N, 129)
        ).astype(np.float32)

        def f(a):
            return jax.lax.psum(a, WORLD_AXIS), \
                topo.hierarchical_all_reduce(a, WORLD_AXIS, op=Sum)

        flat, hier = self._run(f, x)
        np.testing.assert_array_equal(np.asarray(flat), np.asarray(hier))

    def test_rs_then_ag_matches_flat(self, hvd_module):
        import jax

        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        x = _data(np.float32, shape=(N, 53), seed=22)

        def f(a):
            sh = topo.hierarchical_reduce_scatter(a, WORLD_AXIS, op=Sum)
            out = topo.hierarchical_all_gather(sh, WORLD_AXIS)
            return jax.lax.psum(a, WORLD_AXIS), \
                out[:a.size].reshape(a.shape)

        flat, rt = self._run(f, x)
        np.testing.assert_allclose(np.asarray(flat), np.asarray(rt),
                                   rtol=1e-6, atol=1e-6)

    def test_process_set_restriction_stays_flat(self, hvd_module,
                                                monkeypatch):
        """A process-set-restricted optimizer exchange cannot carry the
        hier groups (they factor the whole axis): the plan downgrades
        to flat and values match the per-set allreduce exactly."""
        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        from horovod_tpu import sched

        ps = hvd.add_process_set([0, 1, 2, 3])
        sched.set_config_override(
            sched.SchedConfig(bucket_bytes=64, lowering="hier")
        )
        try:
            x = _data(np.float32, seed=23)
            y = np.asarray(hvd.allreduce(x, op=hvd.Sum, process_set=ps))
            expect = np.asarray(x[:4]).sum(axis=0)
            for r in range(4):
                np.testing.assert_allclose(y[r], expect, rtol=1e-5)
        finally:
            sched.set_config_override(None)
            hvd.remove_process_set(ps)

    def test_non_tiling_set_raises_shared_error_type(self, hvd_module,
                                                     monkeypatch):
        from horovod_tpu.exceptions import ProcessSetTilingError
        from horovod_tpu.process_sets import tiling_groups

        with pytest.raises(ProcessSetTilingError, match="tile"):
            tiling_groups([0, 1, 2], N)

    @pytest.mark.parametrize("degrees", [(2, 2), (4, 2)],
                             ids=["dp2xtp2", "dp4xtp2"])
    def test_grad_sync_hier_column_on_dp_tp_mesh(self, hvd_module,
                                                 degrees):
        """dp×tp meshes: the hier lowering must agree with flat
        (dp2xtp2's dp axis cannot factor across 2 slices — clean
        degeneration; dp4xtp2's dp axis factors 2x2)."""
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import sched
        from horovod_tpu.parallel import make_mesh, sync_gradients

        dp, tp = degrees
        devices = jax.devices()[: dp * tp]
        mesh = make_mesh(dp=dp, tp=tp, devices=devices)
        g = {"a": _data(np.float32, shape=(dp * tp, 5), seed=24),
             "b": _data(np.float32, shape=(dp * tp, 5), seed=25)}
        shard_axes = {"a": "", "b": "tp"}

        def f(grads):
            return sync_gradients(grads, shard_axes, axes=("dp", "tp"))

        outs = {}
        spec = {"a": P("dp"), "b": P("dp")}
        for lower in ("flat", "hier"):
            sched.set_config_override(sched.SchedConfig(
                bucket_bytes=64, lowering=lower))
            try:
                outs[lower] = jax.jit(jax.shard_map(
                    f, mesh=mesh, in_specs=(spec,), out_specs=spec,
                    check_vma=False,
                ))(g)
            finally:
                sched.set_config_override(None)
        for key in g:
            np.testing.assert_allclose(
                np.asarray(outs["flat"][key]),
                np.asarray(outs["hier"][key]), rtol=1e-6, atol=1e-6,
            )

    def test_cost_model_choice_never_exceeds_flat_dcn(self, hvd_module):
        """Property column: for random bucket sizes, the plan's chosen
        lowering never moves more DCN bytes than flat would."""
        from horovod_tpu import sched
        from horovod_tpu.topo import model as topo_model

        topo = topo_model.current()
        rng = np.random.RandomState(42)
        sizes = [int(rng.randint(64, 1 << 24)) for _ in range(40)]
        schedule = sched.build_schedule(
            sizes, ["float32"] * len(sizes),
            sched.SchedConfig(bucket_bytes=1 << 18, lowering="auto"),
        )
        for b in schedule.buckets:
            chosen = topo.lowering_bytes("all_reduce", b.nbytes,
                                         b.lowering)
            flat = topo.lowering_bytes("all_reduce", b.nbytes, "flat")
            assert chosen["dcn"] <= flat["dcn"], b


def _adasum_pair_np(a, b):
    a = a.astype(np.float64)
    b = b.astype(np.float64)
    dot, na, nb = (a * b).sum(), (a * a).sum(), (b * b).sum()
    ca = 1.0 - dot / (2.0 * na) if na > 0 else 1.0
    cb = 1.0 - dot / (2.0 * nb) if nb > 0 else 1.0
    return ca * a + cb * b


@pytest.mark.adasum
class TestHierAdasumColumn:
    """hier_adasum lowering column: plain sum over ICI, Adasum's
    adaptive combination across slices on the DCN hop (topo/, forced
    2-slice topology) — dtype sweep vs the NumPy reference, single-
    slice flat degeneration, process-set downgrade, quantized DCN hop,
    and the scheduler/ZeRO-1/tuner integration gauges."""

    @pytest.fixture(autouse=True)
    def _forced_two_slice(self, monkeypatch):
        from horovod_tpu import topo

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        yield
        topo.reset()

    def _run(self, fn, *args, n_out=1):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.runtime import WORLD_AXIS, get_runtime

        mesh = get_runtime().mesh
        spec = P(WORLD_AXIS)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * len(args),
            out_specs=(spec,) * n_out if n_out > 1 else spec,
            check_vma=False,
        ))(*args)

    def _sched_losses(self, lowering, steps=8, op=None, compression=None):
        import jax.numpy as jnp
        import optax

        from horovod_tpu import sched

        X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
        Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)

        def loss_fn(p, b):
            x, y = b
            return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)

        params = {"w1": jnp.full((4, 4), 0.2),
                  "w2": jnp.full((4, 2), 0.5), "b": jnp.zeros((2,))}
        sched.set_config_override(sched.SchedConfig(
            bucket_bytes=64, lowering=lowering))
        try:
            kw = {}
            if op is not None:
                kw["op"] = op
            if compression is not None:
                kw["compression"] = compression
            tx = hvd.DistributedOptimizer(optax.sgd(0.1), **kw)
            step = hvd.distributed_train_step(loss_fn, tx)
            st = step.init(params)
            batch = (jnp.asarray(X), jnp.asarray(Y))
            out = []
            for _ in range(steps):
                params, st, loss = step(params, st, batch)
                out.append(float(loss))
            return out
        finally:
            sched.set_config_override(None)

    @pytest.mark.parametrize(
        "dtype", [np.float32, np.float16, jnp.bfloat16], ids=str
    )
    def test_allreduce_vs_numpy_reference(self, hvd_module, dtype):
        """op=Average: Adasum of per-slice mean gradients (the
        reference AdasumGpuAllreduceOp postscale semantics)."""
        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Average
        from horovod_tpu.runtime import WORLD_AXIS

        x = _data(dtype, shape=(N, 37), seed=30)

        def f(a):
            return topo.hierarchical_adasum_all_reduce(
                a, WORLD_AXIS, op=Average
            )

        out = np.asarray(self._run(f, x), np.float64)
        xs = np.asarray(x, np.float64)
        expect = _adasum_pair_np(xs[:4].mean(0), xs[4:].mean(0))
        for r in range(N):
            np.testing.assert_allclose(out[r], expect, **_tol(dtype))

    def test_allreduce_sum_semantics(self, hvd_module):
        """op=Sum: Adasum of per-slice sums."""
        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        x = _data(np.float32, shape=(N, 53), seed=31)

        def f(a):
            return topo.hierarchical_adasum_all_reduce(
                a, WORLD_AXIS, op=Sum
            )

        out = np.asarray(self._run(f, x), np.float64)
        xs = np.asarray(x, np.float64)
        expect = _adasum_pair_np(xs[:4].sum(0), xs[4:].sum(0))
        np.testing.assert_allclose(out[0], expect, rtol=1e-5, atol=1e-5)

    def test_non_float_rejected_and_bucket_resolves_flat(self,
                                                         hvd_module):
        from horovod_tpu import sched, topo
        from horovod_tpu.runtime import WORLD_AXIS

        x = _data(np.int32, shape=(N, 8), seed=32)
        with pytest.raises(HorovodTpuError, match="floating"):
            self._run(
                lambda a: topo.hierarchical_adasum_all_reduce(
                    a, WORLD_AXIS
                ),
                x,
            )
        # plan-level eligibility: integer buckets resolve flat
        s = sched.build_schedule(
            [4096], ["int32"],
            sched.SchedConfig(bucket_bytes=8192,
                              lowering="hier_adasum"),
        )
        assert s.buckets[0].lowering == "flat"

    def test_single_slice_resolves_flat_bitwise(self, hvd_module,
                                                monkeypatch):
        """Acceptance: on a forced single-slice topology a hier_adasum
        request resolves flat and f32 dense losses are bitwise
        identical to the flat run (and auto never selects it)."""
        from horovod_tpu import sched, topo

        monkeypatch.setenv("HVD_TPU_TOPO", "1x8")
        topo.reset()
        try:
            assert sched.resolve_lowering("hier_adasum", 1 << 20) == \
                "flat"
            flat = self._sched_losses("flat")
            ha = self._sched_losses("hier_adasum")
            auto = self._sched_losses("auto")
            assert flat == ha == auto
        finally:
            topo.reset()

    def test_auto_never_selects_hier_adasum(self, hvd_module):
        from horovod_tpu import sched

        rng = np.random.RandomState(7)
        sizes = [int(rng.randint(64, 1 << 24)) for _ in range(30)]
        schedule = sched.build_schedule(
            sizes, ["float32"] * len(sizes),
            sched.SchedConfig(bucket_bytes=1 << 18, lowering="auto"),
        )
        assert all(b.lowering in ("flat", "hier")
                   for b in schedule.buckets)

    def test_process_set_restriction_stays_flat(self, hvd_module,
                                                monkeypatch):
        """A process-set-restricted exchange cannot carry the slice
        groups: the plan downgrades to flat and values match the
        per-set allreduce exactly."""
        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        from horovod_tpu import sched

        ps = hvd.add_process_set([0, 1, 2, 3])
        sched.set_config_override(
            sched.SchedConfig(bucket_bytes=64, lowering="hier_adasum")
        )
        try:
            x = _data(np.float32, seed=33)
            y = np.asarray(hvd.allreduce(x, op=hvd.Sum, process_set=ps))
            expect = np.asarray(x[:4]).sum(axis=0)
            for r in range(4):
                np.testing.assert_allclose(y[r], expect, rtol=1e-5)
        finally:
            sched.set_config_override(None)
            hvd.remove_process_set(ps)

    def test_two_slice_sched_gauges(self, hvd_module):
        """Acceptance: on the 2-slice sim mesh hier_adasum buckets
        publish nonzero dcn/ici gauges, the per-lowering bucket count,
        and DCN bytes <= hier's for the same schedule."""
        from horovod_tpu import metrics, sched

        self._sched_losses("hier")
        dcn_hier = metrics.get_gauge("topo.dcn_bytes")
        losses = self._sched_losses("hier_adasum")
        assert all(np.isfinite(losses))
        dcn = metrics.get_gauge("topo.dcn_bytes")
        ici = metrics.get_gauge("topo.ici_bytes")
        buckets = metrics.get_gauge(
            "topo.buckets", {"lowering": "hier_adasum"}
        )
        assert dcn and dcn > 0
        assert ici and ici > 0
        assert buckets and buckets >= 1
        assert dcn <= dcn_hier
        # byte-model property on random sizes too
        from horovod_tpu.topo import model as topo_model

        topo = topo_model.current()
        rng = np.random.RandomState(9)
        for _ in range(20):
            nb = int(rng.randint(64, 1 << 24))
            ha = topo.lowering_bytes("all_reduce", nb, "hier_adasum")
            hi = topo.lowering_bytes("all_reduce", nb, "hier")
            assert ha["dcn"] <= hi["dcn"], nb

    def test_op_adasum_routes_hierarchical(self, hvd_module):
        """DistributedOptimizer(op=Adasum) lowers its buckets
        hier_adasum on a cross-slice topology."""
        from horovod_tpu import metrics

        losses = self._sched_losses("auto", op=hvd.Adasum)
        assert all(np.isfinite(losses))
        assert metrics.get_gauge(
            "topo.buckets", {"lowering": "hier_adasum"}
        ) >= 1

    def test_quantized_dcn_hop(self, hvd_module):
        """Compression.int8 + op=Adasum rides the hier_adasum lowering
        (only the DCN gather quantizes) and stays close to the dense
        trajectory; a bf16/int8 wire on hier_adasum sum buckets too."""
        dense = self._sched_losses("hier_adasum")
        quant = self._sched_losses(
            "hier_adasum", compression=hvd.Compression.int8
        )
        assert abs(dense[-1] - quant[-1]) < 1e-2
        ad = self._sched_losses("auto", op=hvd.Adasum)
        adq = self._sched_losses(
            "auto", op=hvd.Adasum, compression=hvd.Compression.int8
        )
        assert abs(ad[-1] - adq[-1]) < 1e-2

    def test_quantized_flat_adasum_still_raises(self, hvd_module,
                                                monkeypatch):
        """The narrowed satellite contract: single-slice topologies
        (flat VHDD Adasum) still raise QuantizedWireError."""
        from horovod_tpu import topo
        from horovod_tpu.exceptions import QuantizedWireError

        monkeypatch.setenv("HVD_TPU_TOPO", "1x8")
        topo.reset()
        try:
            with pytest.raises(QuantizedWireError, match="Average"):
                self._sched_losses(
                    "auto", steps=1, op=hvd.Adasum,
                    compression=hvd.Compression.int8,
                )
        finally:
            topo.reset()

    def test_zero1_hier_adasum_buckets(self, hvd_module):
        """bucketed_zero_step: hier_adasum buckets shard k-fold over
        the ICI sub-axis and the Adasum combine happens on the 1/k DCN
        shard before the sharded update."""
        import jax.numpy as jnp
        import optax

        from horovod_tpu import sched
        from horovod_tpu.sched.zero1 import bucket_layouts, bucketed_zero_step

        X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
        Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)

        def loss_fn(p, b):
            x, y = b
            return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)

        params = {"w1": jnp.full((4, 4), 0.2),
                  "w2": jnp.full((4, 2), 0.5), "b": jnp.zeros((2,))}
        cfg = sched.SchedConfig(
            bucket_bytes=64, mode="reduce_scatter",
            lowering="hier_adasum",
        )
        lays = bucket_layouts(params, 8, cfg)
        assert all(l.lowering == "hier_adasum" for l in lays)
        assert all(l.shards == 4 for l in lays)  # k = slice_size
        step = bucketed_zero_step(loss_fn, optax.adam(0.05), cfg=cfg)
        st = step.init(params)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        loss = None
        for _ in range(5):
            params, st, loss = step(params, st, batch)
        assert np.isfinite(float(loss))

    def test_xir_eligibility_and_interp(self, hvd_module):
        """XIR column: eligible_lowering gates hier_adasum to float
        reduce ops; an all_reduce op carrying it interprets to the topo
        primitive (bitwise vs the direct call)."""
        import jax

        from horovod_tpu import xir
        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Average
        from horovod_tpu.runtime import WORLD_AXIS

        assert xir.eligible_lowering(
            "all_reduce", "hier_adasum", "float32") == "hier_adasum"
        assert xir.eligible_lowering(
            "all_reduce", "hier_adasum", "int32") == "flat"
        assert xir.eligible_lowering(
            "all_to_all", "hier_adasum", "float32") == "flat"
        assert xir.eligible_lowering(
            "all_gather", "hier_adasum", "float32") == "flat"
        assert xir.eligible_lowering("hier", "hier", None) == "hier"

        x = _data(np.float32, shape=(N, 21), seed=34)
        op = xir.all_reduce(
            WORLD_AXIS, reduce="mean", lowering="hier_adasum",
            nbytes=x[0].nbytes, dtype="float32",
        )

        def f(a):
            return xir.run_op(op, a)

        def g(a):
            return topo.hierarchical_adasum_all_reduce(
                a, WORLD_AXIS, op=Average
            )

        via_ir = np.asarray(self._run(f, x))
        direct = np.asarray(self._run(g, x))
        np.testing.assert_array_equal(via_ir, direct)

    def test_tuner_candidates_include_hier_adasum(self, hvd_module):
        from horovod_tpu.sched.tune import ScheduleTuner

        tuner = ScheduleTuner(explore_lowering=True)
        seen = set()
        # drain the exploration order without scoring
        for _ in range(4):
            lo = tuner.lowering()
            seen.add(lo)
            tuner._lowering_scores[lo] = 1.0
            if all(c in tuner._lowering_scores
                   for c in tuner._lowering_candidates):
                break
        assert {"flat", "hier", "hier_adasum"} <= seen | set(
            tuner._lowering_candidates
        )
        assert "hier_adasum" in tuner._lowering_candidates


class TestXirColumn:
    """Unified exchange IR column of the matrix: IR-routed MoE
    dispatch/combine and Ulysses flips against the direct ``lax`` path
    — bitwise on the f32 dense wire, 1e-6 on the bf16 wire (payloads
    chosen bf16-representable: a shuffle has no accumulation, so the
    cast round trip is exact) — on a 2x2 hybrid mesh, a simulated
    2-slice topology, and process-set subgroups."""

    def _bf16_exact(self, shape, seed):
        # integer-valued f32: exactly representable in bf16, so the
        # bf16 wire's cast round trip changes nothing.
        return np.random.RandomState(seed).randint(
            -8, 9, shape
        ).astype(np.float32)

    def test_moe_dispatch_combine_2x2_mesh(self, hvd_module):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel import make_mesh
        from horovod_tpu.parallel.moe import (
            moe_alltoall_combine,
            moe_alltoall_dispatch,
        )

        mesh = make_mesh(dp=2, ep=2, devices=jax.devices()[:4])
        x = _data(np.float32, shape=(4, 4, 8), seed=30)  # per-dev [2,2,8]

        def roundtrip(a):
            buf = moe_alltoall_dispatch(a, "ep")
            return moe_alltoall_combine(buf, "ep")

        def direct(a):
            buf = jax.lax.all_to_all(a, "ep", split_axis=0,
                                     concat_axis=1, tiled=True)
            return jax.lax.all_to_all(buf, "ep", split_axis=1,
                                      concat_axis=0, tiled=True)

        def run(fn):
            return np.asarray(jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P("dp", "ep"),),
                out_specs=P("dp", "ep"), check_vma=False,
            ))(x))

        np.testing.assert_array_equal(run(roundtrip), run(direct))

    def test_moe_bf16_wire_2x2_mesh(self, hvd_module, monkeypatch):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel import make_mesh
        from horovod_tpu.parallel.moe import moe_alltoall_dispatch

        mesh = make_mesh(dp=2, ep=2, devices=jax.devices()[:4])
        x = self._bf16_exact((4, 4, 8), seed=31)  # per-dev [2,2,8]

        def run(fn):
            return np.asarray(jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P("dp", "ep"),),
                out_specs=P("dp", "ep"), check_vma=False,
            ))(x))

        want = run(lambda a: jax.lax.all_to_all(
            a, "ep", split_axis=0, concat_axis=1, tiled=True))
        monkeypatch.setenv("HVD_TPU_XIR_WIRE", "bf16")
        got = run(lambda a: moe_alltoall_dispatch(a, "ep"))
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)

    def test_moe_two_slice_world_with_byte_gauges(self, hvd_module,
                                                  monkeypatch):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import metrics, topo
        from horovod_tpu.parallel.moe import moe_alltoall_dispatch
        from horovod_tpu.runtime import WORLD_AXIS

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            x = _data(np.float32, shape=(64, 3), seed=32)

            def run(fn):
                return np.asarray(jax.jit(jax.shard_map(
                    fn, mesh=hvd.mesh(), in_specs=(P(WORLD_AXIS),),
                    out_specs=P(WORLD_AXIS), check_vma=False,
                ))(x))

            want = run(lambda a: jax.lax.all_to_all(
                a, WORLD_AXIS, split_axis=0, concat_axis=1, tiled=True))
            got = run(lambda a: moe_alltoall_dispatch(a, WORLD_AXIS))
            np.testing.assert_array_equal(got, want)
            # the previously-invisible a2a traffic, split by network
            assert metrics.get_gauge(
                "topo.dcn_bytes", {"kind": "moe"}
            ) > 0
            assert metrics.get_gauge(
                "topo.ici_bytes", {"kind": "moe"}
            ) > 0
        finally:
            topo.reset()

    @pytest.mark.parametrize("wire", ["off", "bf16"], ids=str)
    def test_ulysses_flips_2x2_mesh(self, hvd_module, monkeypatch, wire):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.parallel import make_mesh
        from horovod_tpu.parallel.ulysses import ulysses_attention

        mesh = make_mesh(dp=2, sp=2, devices=jax.devices()[:4])
        # [dev-sharded B, T_loc, H, D]; integer-valued for the bf16 leg
        q = self._bf16_exact((4, 2, 4, 2), seed=33)
        passthrough = lambda qq, kk, vv, causal=False: qq

        def ul(a):
            return ulysses_attention(
                a, a, a, axis="sp", attn_fn=passthrough
            )

        def run(fn):
            return np.asarray(jax.jit(jax.shard_map(
                fn, mesh=mesh, in_specs=(P("dp", "sp"),),
                out_specs=P("dp", "sp"), check_vma=False,
            ))(q))

        def direct(a):
            h = jax.lax.all_to_all(a, "sp", split_axis=2, concat_axis=1,
                                   tiled=True)
            return jax.lax.all_to_all(h, "sp", split_axis=1,
                                      concat_axis=2, tiled=True)

        want = run(direct)
        monkeypatch.setenv("HVD_TPU_XIR_WIRE", wire)
        on = run(ul)
        if wire == "off":
            np.testing.assert_array_equal(on, want)
        else:
            np.testing.assert_allclose(on, want, rtol=1e-6, atol=1e-6)

    def test_ulysses_two_slice_full_attention(self, hvd_module,
                                              monkeypatch):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import topo
        from horovod_tpu.parallel.ring_attention import full_attention
        from horovod_tpu.parallel.ulysses import ulysses_attention
        from horovod_tpu.runtime import WORLD_AXIS

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            q = _data(np.float32, shape=(16, 4, 16, 2), seed=34)

            def ul(a):
                return ulysses_attention(a, a, a, axis=WORLD_AXIS)

            def direct(a):
                h = jax.lax.all_to_all(a, WORLD_AXIS, split_axis=2,
                                       concat_axis=1, tiled=True)
                return jax.lax.all_to_all(
                    full_attention(h, h, h, causal=False), WORLD_AXIS,
                    split_axis=1, concat_axis=2, tiled=True)

            def run(fn):
                return np.asarray(jax.jit(jax.shard_map(
                    fn, mesh=hvd.mesh(), in_specs=(P(WORLD_AXIS),),
                    out_specs=P(WORLD_AXIS), check_vma=False,
                ))(q))

            np.testing.assert_array_equal(run(ul), run(direct))
        finally:
            topo.reset()

    def test_alltoall_process_set_subgroups(self, hvd_module):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import xir
        from horovod_tpu.process_sets import tiling_groups
        from horovod_tpu.runtime import WORLD_AXIS

        groups = tiling_groups(range(4), N)  # [[0..3], [4..7]]
        x = _data(np.float32, shape=(32, 3), seed=35)

        def via_ir(a):
            op = xir.all_to_all(
                WORLD_AXIS, split_axis=0, concat_axis=1,
                groups=groups, nbytes=a.size * 4, dtype=a.dtype,
            )
            return xir.execute(
                xir.program("moe", [op]), [a], store=False
            )[0]

        def direct(a):
            return jax.lax.all_to_all(
                a, WORLD_AXIS, split_axis=0, concat_axis=1, tiled=True,
                axis_index_groups=[list(g) for g in groups],
            )

        def run(fn):
            return np.asarray(jax.jit(jax.shard_map(
                fn, mesh=hvd.mesh(), in_specs=(P(WORLD_AXIS),),
                out_specs=P(WORLD_AXIS), check_vma=False,
            ))(x))

        np.testing.assert_array_equal(run(via_ir), run(direct))

    def test_sparse_exchange_process_set(self, hvd_module, monkeypatch):
        """IR-routed sparse embedding exchange over a process-set
        subgroup: identical to the direct allgather-of-slices path."""
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.ops import traced
        from horovod_tpu.ops.sparse import IndexedSlices, sparse_allreduce
        from horovod_tpu.runtime import WORLD_AXIS

        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        ps = hvd.add_process_set([0, 1, 2, 3])
        try:
            idx = np.tile(np.arange(4, dtype=np.int32), N)
            vals = _data(np.float32, shape=(N * 4, 3), seed=36)

            def sp(i, v):
                out = sparse_allreduce(
                    IndexedSlices(i, v, (16, 3)), axis=WORLD_AXIS,
                    process_set=ps,
                )
                return out.values

            def direct(i, v):
                return traced.allgather(
                    v, axis=WORLD_AXIS, process_set=ps) / len(ps.ranks)

            def run(fn):
                return np.asarray(jax.jit(jax.shard_map(
                    fn, mesh=hvd.mesh(),
                    in_specs=(P(WORLD_AXIS), P(WORLD_AXIS)),
                    out_specs=P(WORLD_AXIS), check_vma=False,
                ))(idx, vals))

            np.testing.assert_array_equal(run(sp), run(direct))
        finally:
            hvd.remove_process_set(ps)


@pytest.mark.railpipe
class TestPipelineColumn:
    """XIR rail-pipeliner column of the matrix: the phase-interleaved
    emission (``HVD_TPU_XIR_PIPELINE``, xir/pipeline.py) against the
    serialized per-bucket chain — bitwise on the f32 dense wire, 1e-3
    on int8+EF — plus per-rail byte-gauge invariance, the merged
    a2a+dense program, and the max-of-rails cost properties."""

    @pytest.fixture(autouse=True)
    def _forced_two_slice(self, monkeypatch):
        from horovod_tpu import sched, topo
        from horovod_tpu.xir import pipeline as railpipe

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        yield
        railpipe.set_mode_override(None)
        sched.set_config_override(None)
        topo.reset()

    def _train(self, mode, wire="off", iters=5, lowering="hier"):
        import optax

        from horovod_tpu import metrics, sched
        from horovod_tpu.xir import pipeline as railpipe

        rng = np.random.RandomState(7)
        X = rng.randn(32, 64).astype(np.float32)
        Y = (X @ rng.randn(64, 8).astype(np.float32)).astype(np.float32)

        def loss_fn(p, b):
            x, y = b
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] - y) ** 2)

        r = np.random.RandomState(3)
        p = {
            "w1": jnp.asarray(r.randn(64, 256).astype(np.float32) * 0.05),
            "b1": jnp.zeros((256,)),
            "w2": jnp.asarray(r.randn(256, 8).astype(np.float32) * 0.05),
        }
        railpipe.set_mode_override(mode)
        sched.set_config_override(sched.SchedConfig(
            bucket_bytes=16 * 1024, lowering=lowering,
            wire=wire,
        ))
        overlap0 = metrics.get_counter("sched.pipeline.overlap_windows")
        try:
            tx = hvd.DistributedOptimizer(optax.sgd(0.05))
            step = hvd.distributed_train_step(loss_fn, tx)
            st = step.init(p)
            batch = (jnp.asarray(X), jnp.asarray(Y))
            losses = []
            for _ in range(iters):
                p, st, loss = step(p, st, batch)
                losses.append(float(loss))
            gauges = {
                "dcn": metrics.get_gauge("topo.dcn_bytes"),
                "ici": metrics.get_gauge("topo.ici_bytes"),
            }
            overlaps = metrics.get_counter(
                "sched.pipeline.overlap_windows"
            ) - overlap0
            return losses, gauges, overlaps
        finally:
            from horovod_tpu import sched as _s

            _s.set_config_override(None)
            railpipe.set_mode_override(None)

    def test_pipelined_vs_serialized_bitwise_f32(self, hvd_module):
        off, _, n_off = self._train("off")
        on, _, n_on = self._train("on")
        assert off == on  # bitwise: reordering never touches values
        assert n_off == 0
        assert n_on > 0  # the rail chains actually engaged

    def test_auto_mode_bitwise_and_engaged(self, hvd_module):
        off, _, _ = self._train("off")
        auto, _, n_auto = self._train("auto")
        assert off == auto
        assert n_auto > 0  # cost model prices pipelined cheaper here

    def test_int8_ef_within_tolerance(self, hvd_module):
        """Quantized buckets serialize inside the pipelined emission
        (they occupy both rails), so pipelined == serialized holds to
        the wire's own tolerance; both stay close to dense."""
        dense, _, _ = self._train("off")
        off, _, _ = self._train("off", wire="int8")
        on, _, _ = self._train("on", wire="int8")
        np.testing.assert_allclose(off, on, rtol=1e-3, atol=1e-3)
        np.testing.assert_allclose(dense, on, rtol=1e-3, atol=1e-3)

    def test_rail_byte_gauges_identical(self, hvd_module):
        """Pipelining is ordering-only: the planned per-rail traffic —
        topo.dcn_bytes / topo.ici_bytes — is identical either way."""
        _, g_off, _ = self._train("off")
        _, g_on, _ = self._train("on")
        assert g_off == g_on
        assert g_on["dcn"] > 0 and g_on["ici"] > 0

    def test_merged_a2a_dense_program_parity(self, hvd_module):
        """Cross-workload merge on a 2x2 dp×ep mesh: a dense-grad
        all_reduce program over dp merged with a MoE all_to_all over
        ep — executed as one rail-interleaved emission — is bitwise
        identical to executing the programs separately."""
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu import xir
        from horovod_tpu.parallel import make_mesh
        from horovod_tpu.xir import pipeline as railpipe

        mesh = make_mesh(dp=2, ep=2, devices=jax.devices()[:4])
        g = _data(np.float32, shape=(4, 8), seed=40)
        a = _data(np.float32, shape=(4, 4, 8), seed=41)

        def progs():
            dense = xir.program("dense_grad", [xir.all_reduce(
                "dp", lowering="flat", nbytes=g.size * 4,
                dtype="float32",
            )])
            moe = xir.program("moe", [xir.all_to_all(
                "ep", split_axis=0, concat_axis=1,
                nbytes=a.size * 4, dtype="float32",
            )])
            return dense, moe

        def merged(gg, aa):
            dense, moe = progs()
            outs = xir.execute_merged(
                [dense, moe], [[gg], [aa]], store=False
            )
            return outs[0][0], outs[1][0]

        def separate(gg, aa):
            dense, moe = progs()
            o1 = xir.execute(dense, [gg], store=False)[0]
            o2 = xir.execute(moe, [aa], store=False)[0]
            return o1, o2

        def run(fn):
            return jax.jit(jax.shard_map(
                fn, mesh=mesh,
                in_specs=(P("dp"), P("dp", "ep")),
                out_specs=(P("dp"), P("dp", "ep")),
                check_vma=False,
            ))(g, a)

        railpipe.set_mode_override("on")
        m1, m2 = run(merged)
        railpipe.set_mode_override("off")
        s1, s2 = run(separate)
        np.testing.assert_array_equal(np.asarray(m1), np.asarray(s1))
        np.testing.assert_array_equal(np.asarray(m2), np.asarray(s2))

    def test_cost_model_properties(self, hvd_module):
        """max(rail sums) ≤ pipelined ≤ serialized for random
        schedules, and the rail coefficient rows partition the
        serialized row exactly."""
        from horovod_tpu.topo import model as topo_model
        from horovod_tpu.xir import pipeline as railpipe

        topo = topo_model.current()
        rng = np.random.RandomState(11)
        for _ in range(20):
            items = [
                ("all_reduce", int(rng.randint(1 << 10, 1 << 24)),
                 rng.choice(["hier", "flat"]))
                for _ in range(int(rng.randint(2, 8)))
            ]
            serial = railpipe.estimate_schedule_cost(items, 8)
            pipe = railpipe.estimate_schedule_cost(
                items, 8, pipelined=True
            )
            splits = [railpipe.rail_times(c, b, lo, 8)
                      for c, b, lo in items]
            max_rail = max(sum(s[0] for s in splits),
                           sum(s[1] for s in splits))
            assert max_rail <= pipe <= serial, (items, max_rail, pipe,
                                                serial)
        for lowering in ("flat", "hier", "hier_adasum"):
            for coll in ("all_reduce", "reduce_scatter", "all_gather"):
                full = topo_model.cost_coefficients(
                    coll, 1 << 20, lowering, 8, topo
                )
                ici, dcn = topo_model.rail_cost_coefficients(
                    coll, 1 << 20, lowering, 8, topo
                )
                for f, i, d in zip(full, ici, dcn):
                    assert abs(f - (i + d)) < 1e-9
        # the single-op pipelined estimate is the max of its rails
        t = topo.estimate_cost("all_reduce", 1 << 20, "hier", 8,
                               pipelined=True)
        assert abs(
            t - max(topo.rail_times("all_reduce", 1 << 20, "hier", 8))
        ) < 1e-12


class TestCompiledStepColumn:
    """Compiled-step column of the matrix: ``TrainStep``'s one jitted
    program — exchange schedule, the exchange→update barrier and the
    optimizer update — against the plain reference, a ``pmean`` a leaf
    and the optax update.  Bitwise on the flat f32 dense wire (a
    barrier is value-identity and bucketing moves no value), 1e-6 with
    the hier lowering (another order of summation), 1e-3 on int8+EF,
    the same under the rail-pipelined ordering, plus donation parity
    for both step classes with ``donate=False`` as the numerics hook."""

    @pytest.fixture(autouse=True)
    def _forced_two_slice(self, monkeypatch):
        from horovod_tpu import sched, topo
        from horovod_tpu.xir import interp as xinterp
        from horovod_tpu.xir import pipeline as railpipe

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        yield
        xinterp.set_onestep_override(None)
        railpipe.set_mode_override(None)
        sched.set_config_override(None)
        topo.reset()

    def _problem(self):
        rng = np.random.RandomState(7)
        X = rng.randn(32, 64).astype(np.float32)
        Y = (X @ rng.randn(64, 8).astype(np.float32)).astype(np.float32)

        def loss_fn(p, b):
            x, y = b
            h = jnp.tanh(x @ p["w1"] + p["b1"])
            return jnp.mean((h @ p["w2"] - y) ** 2)

        r = np.random.RandomState(3)
        p = {
            "w1": jnp.asarray(r.randn(64, 256).astype(np.float32) * 0.05),
            "b1": jnp.zeros((256,)),
            "w2": jnp.asarray(r.randn(256, 8).astype(np.float32) * 0.05),
        }
        return loss_fn, p, (jnp.asarray(X), jnp.asarray(Y))

    def _train(self, wire="off", pipeline="off", iters=5,
               lowering="hier", donate=True):
        import optax

        from horovod_tpu import sched
        from horovod_tpu.xir import pipeline as railpipe

        loss_fn, p, batch = self._problem()
        railpipe.set_mode_override(pipeline)
        sched.set_config_override(sched.SchedConfig(
            bucket_bytes=16 * 1024, lowering=lowering,
            wire=wire,
        ))
        try:
            tx = hvd.DistributedOptimizer(optax.sgd(0.05))
            step = hvd.distributed_train_step(loss_fn, tx, donate=donate)
            st = step.init(p)
            losses = []
            for _ in range(iters):
                p, st, loss = step(p, st, batch)
                losses.append(float(loss))
            return losses
        finally:
            sched.set_config_override(None)
            railpipe.set_mode_override(None)

    def _reference(self, iters=5):
        """Plain jax: a pmean a leaf and the optax update."""
        import jax
        import optax
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.runtime import WORLD_AXIS

        loss_fn, p, batch = self._problem()
        tx = optax.sgd(0.05)

        def body(p, st, b):
            loss, g = jax.value_and_grad(loss_fn)(p, b)
            g = jax.tree.map(lambda x: lax.pmean(x, WORLD_AXIS), g)
            updates, st = tx.update(g, st, p)
            return (optax.apply_updates(p, updates), st,
                    lax.pmean(loss, WORLD_AXIS))

        step = jax.jit(jax.shard_map(
            body, mesh=hvd.mesh(), in_specs=(P(), P(), P(WORLD_AXIS)),
            out_specs=(P(), P(), P()), check_vma=False,
        ))
        st = tx.init(p)
        losses = []
        for _ in range(iters):
            p, st, loss = step(p, st, batch)
            losses.append(float(loss))
        return losses

    def test_flat_bitwise_f32(self, hvd_module):
        # bitwise: buckets, barriers and the update tie move no value
        assert self._train(lowering="flat") == self._reference()

    def test_hier_lowering_matches_reference(self, hvd_module):
        np.testing.assert_allclose(
            self._train(lowering="hier"), self._reference(),
            rtol=1e-6, atol=1e-6)

    def test_int8_ef_within_tolerance(self, hvd_module):
        """The quantize/dequantize phases sit in the same program as
        everything else; the step stays within the wire's own
        tolerance of the dense reference."""
        np.testing.assert_allclose(
            self._train(wire="int8"), self._reference(),
            rtol=1e-3, atol=1e-3)

    def test_composes_with_pipelined_ordering(self, hvd_module):
        """The update follows whatever ordering the rail pipeliner
        emitted: pipelined == serialized, bitwise (ordering is
        value-identity), and both match the reference."""
        serial = self._train(pipeline="off")
        piped = self._train(pipeline="on")
        assert serial == piped
        np.testing.assert_allclose(
            piped, self._reference(), rtol=1e-6, atol=1e-6)

    def test_train_step_donation_parity(self, hvd_module):
        """Donated program == undonated, bitwise — ``donate=False`` is
        the numerics hook when in-place buffer reuse is suspected."""
        donated = self._train(lowering="flat", donate=True)
        undonated = self._train(lowering="flat", donate=False)
        assert donated == undonated == self._reference()

    @pytest.mark.onestep
    def test_stale_step_donation_parity_under_onestep(self, hvd_module):
        import optax

        from horovod_tpu import svc
        from horovod_tpu.svc.stale import StaleTrainStep
        from horovod_tpu.xir import interp as xinterp

        svc.set_enabled_override(True)
        svc.set_staleness_override(1)
        xinterp.set_onestep_override("on")

        def lf(p, b):
            return jnp.sum((p["w"] - 3.0) ** 2) + 0.0 * jnp.sum(b)

        def run(donate):
            step = StaleTrainStep(lf, optax.sgd(0.2), k=1,
                                  donate=donate)
            sp, st = step.init({"w": jnp.zeros((4,), jnp.float32)})
            batch = jnp.zeros((N, 1), jnp.float32)
            losses = []
            for _ in range(8):
                sp, st, loss = step(sp, st, batch)
                losses.append(float(loss))
            step.drain()
            return losses

        try:
            donated = run(True)
            svc.reset_service()
            undonated = run(False)
            assert donated == undonated, \
                "stale onestep donation changed numerics"
        finally:
            svc.set_enabled_override(None)
            svc.set_staleness_override(None)
            svc.reset_service()


@pytest.mark.pallas
@pytest.mark.quant
class TestFusedQuantColumn:
    """Fused quantized-wire backend column of the matrix
    (``HVD_TPU_QUANT_BACKEND=fused`` → ops/pallas_quant.py ring
    kernels, interpret mode + ppermute transport on the CPU mesh):
    fused vs phase across dtypes, exact-payload bitwise agreement,
    process-set subgroups, the hierarchical lowering with the fused
    backend on its quantized hop, and EF residual equivalence."""

    def _run(self, fn, *args, n_out=1):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.runtime import WORLD_AXIS, get_runtime

        mesh = get_runtime().mesh
        spec = P(WORLD_AXIS)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * len(args),
            out_specs=(spec,) * n_out if n_out > 1 else spec,
            check_vma=False,
        ))(*args)

    @pytest.mark.parametrize(
        "dtype", [np.float32, np.float16, jnp.bfloat16], ids=str
    )
    @pytest.mark.parametrize("wire", ["int8", "fp8"])
    def test_allreduce_fused_vs_phase(self, hvd_module, dtype, wire):
        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum

        x = _data(dtype, shape=(N, 777), seed=40)

        def f(backend):
            return self._run(
                lambda a, _b=backend: quantized_allreduce(
                    a[0], op=Sum, wire=wire, backend=_b
                ).astype(jnp.float32)[None], x,
            )

        # same grid, same fp32 accumulation — only summation order
        # differs between the ring and the all_to_all wire, so f32
        # agrees at 1e-6 and the half dtypes at their own rounding
        # (the phase primitive casts its fp32 result back to dtype)
        tol = dict(rtol=1e-6, atol=1e-6) if dtype == np.float32 \
            else _tol(dtype)
        np.testing.assert_allclose(
            np.asarray(f("phase"), np.float64),
            np.asarray(f("fused"), np.float64), **tol,
        )

    def test_bitwise_when_every_block_quantizes_exactly(self,
                                                        hvd_module):
        """Payload crafted so every quantization block has amax 127 and
        integer values: both backends' grids are exact, partial sums
        are exactly representable, so summation order cannot matter —
        fused must equal phase bit for bit."""
        from horovod_tpu.ops.quantized import quant_block, quantized_allreduce
        from horovod_tpu.ops.traced import Sum

        block = quant_block()
        rng = np.random.RandomState(41)
        x = rng.randint(-16, 17, (N, 2 * block)).astype(np.float32)
        x[:, ::block] = 127.0  # pin every block's amax -> scale == 1

        def f(backend):
            return np.asarray(self._run(
                lambda a, _b=backend: quantized_allreduce(
                    a[0], op=Sum, wire="int8", backend=_b
                )[None], x,
            ))

        np.testing.assert_array_equal(f("phase"), f("fused"))

    def test_process_set_subgroups(self, hvd_module, monkeypatch):
        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        ps = hvd.add_process_set([0, 1, 2, 3])
        try:
            x = _data(np.float32, shape=(N, 1030), seed=42)

            def f(backend):
                return np.asarray(self._run(
                    lambda a, _b=backend: quantized_allreduce(
                        a[0], WORLD_AXIS, op=Sum, process_set=ps,
                        backend=_b,
                    )[None], x,
                ))

            ph, fu = f("phase"), f("fused")
            np.testing.assert_allclose(ph, fu, rtol=1e-6, atol=1e-6)
            # and the grouped reduction actually stayed within the set
            expect = np.asarray(x[:4], np.float64).sum(axis=0)
            np.testing.assert_allclose(
                np.asarray(fu[0], np.float64), expect,
                rtol=1e-2, atol=1e-1,
            )
        finally:
            hvd.remove_process_set(ps)

    def test_hier_lowering_fused_quantized_hop(self, hvd_module,
                                               monkeypatch):
        """Hierarchical lowering on a forced 2-slice topology with a
        quantized wire: the quantized hop dispatches through the
        backend knob — fused must agree with phase (on hardware the
        cross-slice DCN hop falls back to phase and only ICI-resident
        rings go fused; the CPU mesh exercises the fused kernels on
        the same groups)."""
        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        try:
            x = _data(np.float32, shape=(N, 1100), seed=43)

            def f(backend):
                monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", backend)
                return np.asarray(self._run(
                    lambda a: topo.hierarchical_all_reduce(
                        a, WORLD_AXIS, op=Sum, wire="int8"
                    ), x,
                ))

            np.testing.assert_allclose(
                f("phase"), f("fused"), rtol=1e-6, atol=1e-6
            )
        finally:
            topo.reset()

    def test_ef_residual_equivalence(self, hvd_module):
        """End-to-end EF: quantize(g + r) on the wire under both
        backends — reduced values agree to summation order and the new
        residual (one local quantization) is bitwise identical."""
        from horovod_tpu.ops.quantized import quantized_allreduce_ef
        from horovod_tpu.ops.traced import Sum

        x = _data(np.float32, shape=(N, 1536), seed=44)
        r = _data(np.float32, shape=(N, 1536), seed=45) * 0.01

        def f(backend):
            def body(a, b):
                out, rn = quantized_allreduce_ef(
                    a, b, op=Sum, backend=backend
                )
                return out, rn

            o, rn = self._run(body, x, r, n_out=2)
            return np.asarray(o), np.asarray(rn)

        o_p, r_p = f("phase")
        o_f, r_f = f("fused")
        np.testing.assert_allclose(o_p, o_f, rtol=1e-6, atol=1e-6)
        np.testing.assert_array_equal(r_p, r_f)


class TestGroupFusionKnob:
    def test_disable_group_fusion_matches_fused(self, hvd_module,
                                                monkeypatch):
        """HOROVOD_DISABLE_GROUP_FUSION: same numerics, unfused lowering
        (reference knob of the same name)."""
        xs = [_data(np.float32, shape=(N, s), seed=s) for s in (3, 5)]
        fused = [np.asarray(y) for y in hvd.grouped_allreduce(xs, op=hvd.Sum)]
        monkeypatch.setenv("HVD_TPU_DISABLE_GROUP_FUSION", "1")
        unfused = [np.asarray(y)
                   for y in hvd.grouped_allreduce(xs, op=hvd.Sum)]
        for a, b in zip(fused, unfused):
            np.testing.assert_allclose(a, b, rtol=1e-6)

    def test_disable_group_fusion_traced(self, hvd_module, monkeypatch):
        import jax

        from horovod_tpu.ops import traced

        xs = [np.ones((4, 3), np.float32), np.ones((4, 2), np.float32)]

        def run():
            def f(*ts):
                return tuple(
                    traced.grouped_allreduce(list(ts), op=traced.Sum)
                )

            from jax.sharding import PartitionSpec as P

            from horovod_tpu.runtime import WORLD_AXIS, get_runtime
            mesh = get_runtime().mesh
            spec = P(WORLD_AXIS)
            return [
                np.asarray(y) for y in jax.jit(jax.shard_map(
                    f, mesh=mesh, in_specs=(spec, spec),
                    out_specs=(spec, spec), check_vma=False,
                ))(*[np.tile(x, (2, 1)) for x in xs])
            ]

        fused = run()
        monkeypatch.setenv("HVD_TPU_DISABLE_GROUP_FUSION", "1")
        unfused = run()
        for a, b in zip(fused, unfused):
            np.testing.assert_allclose(a, b, rtol=1e-6)


@pytest.mark.backend
class TestBackendColumn:
    """The gpu backend-family column of the matrix
    (``HVD_TPU_BACKEND=gpu`` → backend/registry.py routes quantized
    reduce ops through ops/mosaic_quant.py, interpret mode on the CPU
    mesh): gpu-interpret vs phase vs dense parity, gpu-vs-tpu family
    bitwise identity (the two families share the kernel math), the
    forced 2-slice hierarchical lowering, process-set subgroups, the
    hardware-ineligibility fallback, and the acceptance counters
    (nonzero ``backend.gpu.*``, zero silent fallbacks)."""

    @pytest.fixture(autouse=True)
    def _fresh_backend(self, monkeypatch):
        from horovod_tpu import topo
        from horovod_tpu.backend import registry

        monkeypatch.delenv("HVD_TPU_BACKEND", raising=False)
        monkeypatch.delenv("HVD_TPU_QUANT_BACKEND", raising=False)
        registry.reset()
        topo.reset()
        yield
        registry.reset()
        topo.reset()

    def _force(self, monkeypatch, fam):
        from horovod_tpu import topo
        from horovod_tpu.backend import registry

        monkeypatch.setenv("HVD_TPU_BACKEND", fam)
        registry.reset()
        topo.reset()

    def _run(self, fn, *args, n_out=1):
        import jax
        from jax.sharding import PartitionSpec as P

        from horovod_tpu.runtime import WORLD_AXIS, get_runtime

        mesh = get_runtime().mesh
        spec = P(WORLD_AXIS)
        return jax.jit(jax.shard_map(
            fn, mesh=mesh, in_specs=(spec,) * len(args),
            out_specs=(spec,) * n_out if n_out > 1 else spec,
            check_vma=False,
        ))(*args)

    @pytest.mark.parametrize("wire", ["int8", "fp8"])
    def test_gpu_family_vs_phase_vs_dense(self, hvd_module, monkeypatch,
                                          wire):
        """Under the gpu family the UNSET quant knob routes through the
        mosaic ring (family default ``fused``); it must agree with an
        explicit phase backend at summation-order tolerance and with
        the dense sum at quantization tolerance."""
        from jax import lax

        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        x = _data(np.float32, shape=(N, 999), seed=50)
        self._force(monkeypatch, "gpu")
        gpu = np.asarray(self._run(
            lambda a: quantized_allreduce(a[0], op=Sum, wire=wire)[None],
            x,
        ))
        phase = np.asarray(self._run(
            lambda a: quantized_allreduce(
                a[0], op=Sum, wire=wire, backend="phase"
            )[None], x,
        ))
        dense = np.asarray(self._run(
            lambda a: lax.psum(a[0], WORLD_AXIS)[None], x,
        ))
        np.testing.assert_allclose(gpu, phase, rtol=1e-6, atol=1e-6)
        # dense tolerance is the wire's quantization error summed over
        # N contributions (fp8 e4m3 carries ~6% per-element error)
        dense_tol = dict(rtol=1e-2, atol=1e-1) if wire == "int8" \
            else dict(rtol=1e-1, atol=1.0)
        np.testing.assert_allclose(gpu, dense, **dense_tol)

    def test_bitwise_exact_grid_gpu_phase_dense(self, hvd_module,
                                                monkeypatch):
        """Payload crafted so BOTH quantization grids are exact: the
        contribution hop sees amax 127 (scale 1) over integer values,
        and the gathered-sum hop sees amax 1016 = 8 x 127 (scale 8)
        over multiple-of-8 sums — so gpu == phase == dense bit for
        bit."""
        from jax import lax

        from horovod_tpu.ops.quantized import quant_block, quantized_allreduce
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        block = quant_block()
        rng = np.random.RandomState(51)
        x = (8 * rng.randint(-15, 16, (N, 2 * block))).astype(np.float32)
        # Pin the amax on every 8-aligned run — the ring path re-chunks
        # rows before blocking, and whatever block the quantizer lands
        # on must contain a 127 (and the reduced tensor a 1016).
        x[:, ::8] = 127.0
        self._force(monkeypatch, "gpu")
        gpu = np.asarray(self._run(
            lambda a: quantized_allreduce(a[0], op=Sum, wire="int8")[None],
            x,
        ))
        phase = np.asarray(self._run(
            lambda a: quantized_allreduce(
                a[0], op=Sum, wire="int8", backend="phase"
            )[None], x,
        ))
        dense = np.asarray(self._run(
            lambda a: lax.psum(a[0], WORLD_AXIS)[None], x,
        ))
        np.testing.assert_array_equal(gpu, phase)
        np.testing.assert_array_equal(gpu, dense)

    def test_gpu_family_bitwise_equals_tpu_family(self, hvd_module,
                                                  monkeypatch):
        """mosaic_quant imports pallas_quant's kernels rather than
        copying them, so the two families' fused interpret paths are
        the same program — bitwise, for arbitrary payloads."""
        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum

        x = _data(np.float32, shape=(N, 1234), seed=52)
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "fused")

        def f():
            return np.asarray(self._run(
                lambda a: quantized_allreduce(
                    a[0], op=Sum, wire="int8"
                )[None], x,
            ))

        self._force(monkeypatch, "gpu")
        out_gpu = f()
        self._force(monkeypatch, "tpu")
        out_tpu = f()
        np.testing.assert_array_equal(out_gpu, out_tpu)

    def test_forced_two_slice_hier_gpu_family(self, hvd_module,
                                              monkeypatch):
        """Forced 2-slice topology + gpu family: the hierarchical
        lowering's quantized hop dispatches through the mosaic module
        on the same tiling groups the tpu family uses — identical hop
        math, bitwise-equal result."""
        from horovod_tpu import topo
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "fused")
        x = _data(np.float32, shape=(N, 1100), seed=53)

        def f():
            return np.asarray(self._run(
                lambda a: topo.hierarchical_all_reduce(
                    a, WORLD_AXIS, op=Sum, wire="int8"
                ), x,
            ))

        self._force(monkeypatch, "gpu")
        assert topo.current().num_slices == 2  # spec wins over family
        out_gpu = f()
        self._force(monkeypatch, "tpu")
        out_tpu = f()
        np.testing.assert_array_equal(out_gpu, out_tpu)

    def test_process_set_subgroups_gpu_family(self, hvd_module,
                                              monkeypatch):
        monkeypatch.setenv("HVD_TPU_DYNAMIC_PROCESS_SETS", "1")
        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum
        from horovod_tpu.runtime import WORLD_AXIS

        self._force(monkeypatch, "gpu")
        ps = hvd.add_process_set([0, 1, 2, 3])
        try:
            x = _data(np.float32, shape=(N, 1030), seed=54)
            out = np.asarray(self._run(
                lambda a: quantized_allreduce(
                    a[0], WORLD_AXIS, op=Sum, process_set=ps
                )[None], x,
            ))
            expect = np.asarray(x[:4], np.float64).sum(axis=0)
            np.testing.assert_allclose(
                np.asarray(out[0], np.float64), expect,
                rtol=1e-2, atol=1e-1,
            )
        finally:
            hvd.remove_process_set(ps)

    def test_hardware_ineligibility_falls_back_to_phase(
        self, hvd_module, monkeypatch
    ):
        """A 'real GPU' whose jax build lacks the Triton lowering:
        dispatch_mode returns None, the collective falls back to the
        phase backend with the ``quant.fused_fallback`` counter — and
        the answer is still right."""
        from horovod_tpu import metrics
        from horovod_tpu.ops import mosaic_quant
        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum

        self._force(monkeypatch, "gpu")
        monkeypatch.setattr(mosaic_quant, "_on_gpu", lambda: True)
        monkeypatch.setattr(mosaic_quant, "_HAS_PLGPU", False)
        assert mosaic_quant.dispatch_mode(None, N) is None
        metrics.reset_counters("quant.")
        metrics.reset_counters("backend.")
        x = _data(np.float32, shape=(N, 512), seed=55)
        out = np.asarray(self._run(
            lambda a: quantized_allreduce(a[0], op=Sum)[None], x,
        ))
        assert metrics.get_counter("quant.fused_fallback") > 0
        assert metrics.get_counter("backend.gpu.quant_collectives") == 0
        expect = np.asarray(x, np.float64).sum(axis=0)
        np.testing.assert_allclose(
            np.asarray(out[0], np.float64), expect,
            rtol=1e-2, atol=1e-1,
        )

    def test_acceptance_counters_nonzero_no_silent_fallback(
        self, hvd_module, monkeypatch
    ):
        """The PR's acceptance gauge: under ``HVD_TPU_BACKEND=gpu`` a
        quantized reduce op routes through the mosaic lowering —
        nonzero ``backend.gpu.*`` counters, zero fallbacks."""
        from horovod_tpu import metrics
        from horovod_tpu.ops.quantized import quantized_allreduce
        from horovod_tpu.ops.traced import Sum

        self._force(monkeypatch, "gpu")
        metrics.reset_counters("quant.")
        metrics.reset_counters("backend.")
        x = _data(np.float32, shape=(N, 768), seed=56)
        self._run(
            lambda a: quantized_allreduce(a[0], op=Sum)[None], x,
        )
        assert metrics.get_counter("backend.gpu.quant_collectives") > 0
        assert metrics.get_counter("backend.gpu.quant_bytes") > 0
        assert metrics.get_counter("quant.fused_collectives") > 0
        assert metrics.get_counter("quant.fused_fallback") == 0
