"""Fused quantized collectives (ops/pallas_quant.py): kernel-level
checks in Pallas interpret mode, backend dispatch, and fused-vs-phase
parity of the primitives on the 8-device CPU mesh.

The end-to-end fused column (dtype sweep, process-set subgroups, hier
lowering, EF equivalence) lives in tests/test_collective_matrix.py;
this file pins the kernel math itself — the shared quantization grid,
odd shapes, the block-size sweep — and the dispatch/knob surface.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu.exceptions import QuantizedWireError
from horovod_tpu.ops import traced
from horovod_tpu.ops.quantized import (
    _block_scale,
    _dequantize_blocks,
    _quantize_blocks,
    quant_backend,
    quantized_all_gather,
    quantized_allreduce,
    quantized_reduce_scatter,
)
from horovod_tpu.runtime import WORLD_AXIS, get_runtime

pytestmark = [pytest.mark.pallas, pytest.mark.quant]

N = 8


def _mesh():
    return get_runtime().mesh


def _run(fn, *args, n_out=1):
    spec = P(WORLD_AXIS)
    out_specs = (spec,) * n_out if n_out > 1 else spec
    f = jax.jit(shard_map(
        fn, mesh=_mesh(), in_specs=(spec,) * len(args),
        out_specs=out_specs, check_vma=False,
    ))
    return f(*[jnp.asarray(a) for a in args])


# ------------------------------------------------------- kernel math


class TestHopKernel:
    """The interpret-mode hop kernel must reproduce the phase
    backend's quantization grid bit for bit (shared _block_scale)."""

    @pytest.mark.parametrize("wire", ["int8", "fp8"])
    @pytest.mark.parametrize("block", [64, 128, 512])
    def test_quant_math_matches_phase_grid(self, wire, block):
        from horovod_tpu.ops.pallas_quant import _quant_math

        rng = np.random.RandomState(0)
        c = 4 * block
        x = rng.randn(c).astype(np.float32) * 3.0
        # both sides under jit: XLA rewrites the /qmax into a
        # reciprocal multiply, so an eager reference would differ in
        # the last bit — the contract is jitted-grid == jitted-grid
        q_ref, s_ref = jax.jit(
            lambda v: _quantize_blocks(v[None], wire, block)
        )(jnp.asarray(x))
        q, s, deq = jax.jit(
            lambda v: _quant_math(v.reshape(c // block, block), wire)
        )(jnp.asarray(x))
        np.testing.assert_array_equal(
            np.asarray(q).reshape(-1), np.asarray(q_ref).reshape(-1)
        )
        np.testing.assert_array_equal(
            np.asarray(s).reshape(-1), np.asarray(s_ref).reshape(-1)
        )
        want_deq = _dequantize_blocks(q_ref, s_ref, block)
        np.testing.assert_array_equal(
            np.asarray(deq).reshape(-1), np.asarray(want_deq).reshape(-1)
        )

    @pytest.mark.parametrize("wire", ["int8", "fp8"])
    def test_zero_block_dequantizes_to_exact_zero(self, wire):
        """The _block_scale guard: an all-zero block must quantize→
        dequantize to exactly zero (divisor clamped to 1.0, never
        0/0)."""
        from horovod_tpu.ops.pallas_quant import _quant_math

        z = jnp.zeros((2, 128), jnp.float32)
        q, s, deq = jax.jit(lambda v: _quant_math(v, wire))(z)
        assert np.asarray(deq).max() == 0.0
        assert np.all(np.isfinite(np.asarray(s)))
        # phase backend agrees through the same guard
        qp, sp = _quantize_blocks(z.reshape(1, 256), wire, 128)
        np.testing.assert_array_equal(
            np.asarray(_dequantize_blocks(qp, sp, 128)),
            np.zeros((1, 256), np.float32),
        )

    def test_nonfinite_block_propagates_nan_scale(self):
        from horovod_tpu.ops.pallas_quant import _quant_math

        x = jnp.full((1, 128), jnp.inf, jnp.float32)
        _, s, deq = jax.jit(lambda v: _quant_math(v, "int8"))(x)
        assert np.isnan(np.asarray(s)).all()
        assert np.isnan(np.asarray(deq)).all()

    def test_block_scale_guard_values(self):
        scale, safe = _block_scale(jnp.asarray([0.0, 127.0, jnp.nan]),
                                   127.0)
        np.testing.assert_array_equal(np.asarray(safe)[:2], [1.0, 1.0])
        assert np.asarray(safe)[2] == 1.0
        assert np.isnan(np.asarray(scale)[2])
        assert np.asarray(scale)[0] == 1.0  # zero block: clamped once


# -------------------------------------------------- fused primitives


class TestFusedPrimitives:
    @pytest.mark.parametrize("wire", ["int8", "fp8"])
    def test_reduce_scatter_matches_phase_1e6(self, hvd_module, wire):
        rng = np.random.RandomState(1)
        x = rng.randn(N, 3000).astype(np.float32)

        def rs(backend):
            return np.asarray(_run(
                lambda v, _b=backend: quantized_reduce_scatter(
                    v[0], op=traced.Sum, wire=wire, backend=_b
                )[None], x,
            ))

        np.testing.assert_allclose(rs("phase"), rs("fused"),
                                   rtol=1e-6, atol=1e-6)

    def test_all_gather_bitwise_matches_phase(self, hvd_module):
        """No accumulation in the gather: fused == phase bit for bit
        for every input."""
        rng = np.random.RandomState(2)
        shard = rng.randn(N, 1024).astype(np.float32) * 10.0

        def ag(backend):
            return np.asarray(_run(
                lambda v, _b=backend: quantized_all_gather(
                    v[0], wire="int8", backend=_b
                )[None], shard,
            ))

        np.testing.assert_array_equal(ag("phase"), ag("fused"))

    def test_ef_residual_bitwise_matches_phase(self, hvd_module):
        """One quantization per contribution on both backends: the EF
        residual is computed from the same local grid and must be
        bitwise identical."""
        rng = np.random.RandomState(3)
        x = rng.randn(N, 2048).astype(np.float32)

        def rs_ef(backend):
            def body(v):
                m, r = quantized_reduce_scatter(
                    v[0], op=traced.Sum, ef=True, backend=backend
                )
                return m[None], r[None]

            return [np.asarray(o) for o in _run(body, x, n_out=2)]

        m_p, r_p = rs_ef("phase")
        m_f, r_f = rs_ef("fused")
        np.testing.assert_array_equal(r_p, r_f)
        np.testing.assert_allclose(m_p, m_f, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("v", [65, 513, 4097])
    def test_odd_shapes_pad_like_phase(self, hvd_module, v):
        """Lengths that don't divide n*block: the fused chunk layout is
        the phase one (block-aligned pad), so results line up slot for
        slot."""
        rng = np.random.RandomState(4)
        x = rng.randn(N, v).astype(np.float32)
        ph = np.asarray(_run(
            lambda t: quantized_allreduce(
                t[0], op=traced.Average, wire="int8"
            )[None], x,
        ))
        fu = np.asarray(_run(
            lambda t: quantized_allreduce(
                t[0], op=traced.Average, wire="int8", backend="fused"
            )[None], x,
        ))
        np.testing.assert_allclose(ph, fu, rtol=1e-6, atol=1e-6)

    @pytest.mark.parametrize("block", [64, 256])
    def test_block_size_sweep(self, hvd_module, block):
        rng = np.random.RandomState(5)
        x = rng.randn(N, 8 * block).astype(np.float32)
        ph = np.asarray(_run(
            lambda t: quantized_allreduce(
                t[0], op=traced.Sum, wire="int8", block=block
            )[None], x,
        ))
        fu = np.asarray(_run(
            lambda t: quantized_allreduce(
                t[0], op=traced.Sum, wire="int8", block=block,
                backend="fused",
            )[None], x,
        ))
        np.testing.assert_allclose(ph, fu, rtol=1e-6, atol=1e-6)

    def test_fused_counters_tick(self, hvd_module):
        from horovod_tpu import metrics

        before = metrics.get_counter("quant.fused_collectives")
        rng = np.random.RandomState(6)
        x = rng.randn(N, 600).astype(np.float32)
        _run(lambda t: quantized_allreduce(
            t[0], op=traced.Sum, backend="fused"
        )[None], x)
        assert metrics.get_counter("quant.fused_collectives") > before
        assert metrics.get_counter("quant.fused_bytes") > 0


# ------------------------------------------------- dispatch and knobs


class TestBackendDispatch:
    def test_knob_default_is_phase(self, monkeypatch):
        monkeypatch.delenv("HVD_TPU_QUANT_BACKEND", raising=False)
        assert quant_backend() == "phase"

    def test_knob_spellings(self, monkeypatch):
        for raw, want in [("fused", "fused"), ("PALLAS", "fused"),
                          ("ring", "fused"), ("phase", "phase"),
                          ("off", "phase"), ("xla", "phase")]:
            monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", raw)
            assert quant_backend() == want, raw

    def test_unknown_backend_rejected(self, monkeypatch):
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "warp")
        with pytest.raises(QuantizedWireError, match="QUANT_BACKEND"):
            quant_backend()

    def test_dispatch_interp_off_tpu(self):
        from horovod_tpu.ops.pallas_quant import dispatch_mode

        # the CPU mesh serves any axis/groups combination in interpret
        # mode — including the hierarchical DCN hop's cross-slice groups
        assert dispatch_mode(None, N) == "interp"
        assert dispatch_mode(((0, 1, 2, 3), (4, 5, 6, 7)), 4) == "interp"
        assert dispatch_mode(None, 1) is None  # degenerate ring

    def test_dispatch_refuses_fused_on_tpu(self, monkeypatch):
        """On a TPU the ring kernels do not compile: a requested
        ``fused`` is an error naming the compiler's reason, never a
        quiet ``phase``."""
        from horovod_tpu.exceptions import HorovodTpuError
        from horovod_tpu.ops import pallas_quant

        monkeypatch.setattr(pallas_quant, "_interpret", lambda: False)
        with pytest.raises(HorovodTpuError, match="aligned to tiling"):
            pallas_quant.dispatch_mode(None, N)

    def test_env_knob_reaches_primitives(self, hvd_module, monkeypatch):
        from horovod_tpu import metrics

        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "fused")
        before = metrics.get_counter("quant.fused_collectives")
        rng = np.random.RandomState(7)
        x = rng.randn(N, 700).astype(np.float32)
        _run(lambda t: quantized_allreduce(
            t[0], op=traced.Sum
        )[None], x)
        assert metrics.get_counter("quant.fused_collectives") > before

    def test_backend_in_store_fingerprint(self, monkeypatch):
        """fused vs phase winners must never collide in the tune DB —
        and 'unset' must equal an explicit 'phase'."""
        from horovod_tpu.sched.store import knob_fingerprint

        monkeypatch.delenv("HVD_TPU_QUANT_BACKEND", raising=False)
        unset = knob_fingerprint()
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "phase")
        assert knob_fingerprint() == unset
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "fused")
        assert knob_fingerprint() != unset

    def test_bucketed_zero1_composes_with_fused(self, hvd_module,
                                                monkeypatch):
        """ZeRO-1 composes unchanged: the per-bucket quantized RS and
        the post-update quantized AG dispatch through the backend knob
        — fused reaches the phase trajectory within the wire's own
        noise and the state structure (incl. EF residuals) is
        identical."""
        import optax

        from horovod_tpu import sched

        rng = np.random.RandomState(8)
        X = rng.randn(16, 6).astype(np.float32)
        Y = (X @ np.full((6, 2), 0.5)).astype(np.float32)
        params = {"w": jnp.full((6, 2), 0.3), "b": jnp.zeros((2,))}

        def loss_fn(p, b):
            x, y = b
            return jnp.mean((x @ p["w"] + p["b"] - y) ** 2)

        def run(backend):
            monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", backend)
            step = sched.bucketed_zero_step(
                loss_fn, optax.sgd(0.05),
                cfg=sched.SchedConfig(bucket_bytes=32, wire="int8"),
            )
            st = step.init(params)
            p = jax.tree.map(jnp.array, params)
            losses = []
            for _ in range(10):
                p, st, loss = step(p, st, (jnp.asarray(X),
                                           jnp.asarray(Y)))
                losses.append(float(loss))
            return losses, st

        ph, st_p = run("phase")
        fu, st_f = run("fused")
        np.testing.assert_allclose(ph, fu, rtol=1e-4, atol=1e-5)
        assert jax.tree.structure(st_p) == jax.tree.structure(st_f)

    def test_tuner_explores_and_freezes_backend(self, monkeypatch):
        """ScheduleTuner(explore_backend=True): one window per
        candidate, best score freezes and pins the env knob — the
        fused backend is a tuner-selectable dimension."""
        import horovod_tpu.sched.tune as tune_mod
        from horovod_tpu import metrics
        from horovod_tpu.sched.tune import ScheduleTuner

        # setenv (not delenv) so monkeypatch restores the pre-test
        # state even though the tuner itself mutates the knob
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "phase")
        scores = {"phase": 50.0, "fused": 80.0}
        t = ScheduleTuner(explore_backend=True, store=None)
        seen = []
        for _ in range(2):
            b = t.backend()
            seen.append(b)
            monkeypatch.setattr(
                tune_mod, "window_score",
                lambda *_a, _b=b: scores[_b],
            )
            t.begin_window()
            assert os.environ["HVD_TPU_QUANT_BACKEND"] == b
            t.end_window()
        assert sorted(seen) == ["fused", "phase"]
        assert t.backend() == "fused"  # higher window score wins
        assert os.environ["HVD_TPU_QUANT_BACKEND"] == "fused"
        assert metrics.get_gauge(
            "sched.tune_backend_frozen", {"backend": "fused"}
        ) == 1.0

    def test_tuner_default_defers_backend_to_env(self, monkeypatch):
        from horovod_tpu.sched.tune import ScheduleTuner

        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "fused")
        t = ScheduleTuner(store=None)
        assert t.backend() == "fused"
        monkeypatch.delenv("HVD_TPU_QUANT_BACKEND")
        assert t.backend() == "phase"

    def test_store_roundtrips_backend(self, monkeypatch, tmp_path):
        """A converged fused winner warm-starts a later tuner with the
        backend pinned (and the knob fingerprint keys fused entries
        apart from phase ones)."""
        from horovod_tpu.sched.store import ScheduleStore, make_key
        from horovod_tpu.sched.tune import ScheduleTuner

        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "phase")
        store = ScheduleStore(str(tmp_path / "db.json"))
        key = make_key(("sig",))
        store.record(key, bucket_bytes=1 << 20, wire="int8",
                     lowering="flat", score=9.0,
                     meta={"backend": "fused"})
        t = ScheduleTuner(explore_backend=True, store=store,
                          store_key=key)
        assert t.backend() == "fused"
        assert os.environ.get("HVD_TPU_QUANT_BACKEND") == "fused"
        assert t.converged  # warm start: zero exploration windows

    def test_xir_lowering_gates_backend_per_op_class(self, monkeypatch):
        from horovod_tpu import xir

        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "fused")
        red = xir.reduce_scatter(
            WORLD_AXIS, wire="int8", nbytes=4096, dtype="float32"
        )
        assert xir.lower.resolve_backend(
            red.replace(lowering="flat")
        ) == "fused"
        # shuffle ops never quantize, and even a hypothetical quantized
        # one pins the phase pipeline — there is no ring to fuse
        a2a = xir.ExchangeOp("all_to_all", WORLD_AXIS, wire="int8",
                             lowering="flat")
        assert xir.lower.resolve_backend(a2a) == "phase"
        dense = red.replace(wire="off")
        assert xir.lower.resolve_backend(dense) is None
        lowered = xir.lower_program(
            xir.program("dense_grad", [red]), axis_size=N, store=False
        )
        assert lowered.ops[0].attr("qbackend") == "fused"
