"""Quantized wire v2 through the scheduler: per-bucket wire choice,
error-feedback residual state (DistributedOptimizer / ZeRO-1), the
reduce_scatter-mode routing, wire observability gauges, the tuner's
wire exploration, and the 2×2 dp×tp acceptance run."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import metrics, sched
from horovod_tpu.exceptions import QuantizedWireError
from horovod_tpu.runtime import WORLD_AXIS
from horovod_tpu.sched import SchedConfig, build_schedule, hooks

pytestmark = [pytest.mark.quant, pytest.mark.sched]

F32 = 4


@pytest.fixture(autouse=True)
def _clean_sched_state():
    hooks.reset()
    sched.set_config_override(None)
    yield
    hooks.reset()
    sched.set_config_override(None)


def fresh(tree):
    return jax.tree.map(lambda a: jnp.array(a), tree)


# ------------------------------------------------------------- plan

def test_config_wire_from_env(monkeypatch):
    monkeypatch.setenv("HVD_TPU_SCHED_WIRE", "int8")
    monkeypatch.setenv("HVD_TPU_SCHED_WIRE_EF", "0")
    cfg = SchedConfig.from_env()
    assert cfg.wire == "int8"
    assert not cfg.wire_ef
    monkeypatch.setenv("HVD_TPU_SCHED_WIRE", "e4m3")
    assert SchedConfig.from_env().wire == "fp8"
    monkeypatch.setenv("HVD_TPU_SCHED_WIRE", "none")
    assert SchedConfig.from_env().wire == "off"
    monkeypatch.setenv("HVD_TPU_SCHED_WIRE", "int4")
    with pytest.raises(ValueError, match="HVD_TPU_SCHED_WIRE"):
        SchedConfig.from_env()


def test_default_wire_is_off():
    assert SchedConfig().wire == "off"
    s = build_schedule([100, 100], ["float32"] * 2, SchedConfig())
    assert all(b.wire == "off" for b in s.buckets)


def test_bucket_wire_eligibility():
    cfg = SchedConfig(bucket_bytes=400, wire="int8")
    s = build_schedule(
        [100, 100, 100], ["float32", "float32", "int32"], cfg,
    )
    by_dtype = {b.wire_dtypes: b.wire for b in s.buckets}
    assert by_dtype[("float32",)] == "int8"
    assert by_dtype[("int32",)] == "off"  # non-float: never quantized
    # pinned mixed-dtype buckets downgrade too
    s2 = build_schedule(
        [100, 100], ["float32", "bfloat16"], cfg, pinned=[[0, 1]],
    )
    assert s2.buckets[0].wire == "off"
    # bf16 wire allows any floating bucket
    s3 = build_schedule(
        [100, 100], ["float32", "bfloat16"],
        SchedConfig(bucket_bytes=400, wire="bf16"), pinned=[[0, 1]],
    )
    assert s3.buckets[0].wire == "bf16"


def test_wire_bytes_ratio():
    from horovod_tpu.sched.plan import wire_bytes

    cfg = SchedConfig(bucket_bytes=1 << 20, wire="int8")
    s = build_schedule([4096 * F32], ["float32"], cfg)
    dense = build_schedule([4096 * F32], ["float32"],
                           SchedConfig(bucket_bytes=1 << 20))
    ratio = wire_bytes(dense.buckets[0]) / wire_bytes(s.buckets[0])
    assert ratio >= 3.0  # 4 bytes -> 1 byte + scale sidecar


def test_signature_includes_wire():
    a = build_schedule([100], ["float32"], SchedConfig(wire="int8"))
    b = build_schedule([100], ["float32"], SchedConfig())
    assert a.signature() != b.signature()


# ------------------------------------------- DistributedOptimizer + EF

def _problem(out_dim=2):
    X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
    Y = (X @ np.full((4, out_dim), 0.7)).astype(np.float32)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)

    params = {
        "w1": jnp.full((4, 4), 0.2),
        "w2": jnp.full((4, out_dim), 0.5),
        "b": jnp.zeros((out_dim,)),
    }
    return params, (jnp.asarray(X), jnp.asarray(Y)), loss_fn


def _run_steps(loss_fn, params, batch, cfg, n=5, **opt_kwargs):
    sched.set_config_override(cfg)
    try:
        tx = hvd.DistributedOptimizer(optax.sgd(0.1), **opt_kwargs)
        step = hvd.distributed_train_step(loss_fn, tx)
        st = step.init(params)
        p = fresh(params)
        losses = []
        for _ in range(n):
            p, st, loss = step(p, st, batch)
            losses.append(float(loss))
        return p, losses, st
    finally:
        sched.set_config_override(None)


def test_wire_off_bitwise_identical_to_dense(hvd_module):
    """Acceptance: HVD_TPU_SCHED_WIRE=off (the default) keeps losses
    f32-bitwise-identical to the PR 3 scheduler behavior."""
    params, batch, loss_fn = _problem()
    _, dense, _ = _run_steps(loss_fn, params, batch,
                             SchedConfig(bucket_bytes=64))
    _, off, st = _run_steps(loss_fn, params, batch,
                            SchedConfig(bucket_bytes=64, wire="off"))
    assert dense == off
    assert st.residual is None  # no EF state allocated


@pytest.mark.parametrize("wire", ["int8", "fp8"])
def test_ef_wire_trains_close_to_dense(hvd_module, wire):
    params, batch, loss_fn = _problem()
    _, dense, _ = _run_steps(loss_fn, params, batch,
                             SchedConfig(bucket_bytes=64), n=30)
    _, quant, st = _run_steps(
        loss_fn, params, batch,
        SchedConfig(bucket_bytes=64, wire=wire), n=30,
    )
    assert st.residual is not None
    assert quant[-1] == pytest.approx(dense[-1], abs=1e-3)


def test_ef_residual_state_is_nonzero_after_steps(hvd_module):
    params, batch, loss_fn = _problem()
    _, _, st = _run_steps(
        loss_fn, params, batch, SchedConfig(bucket_bytes=64, wire="int8"),
    )
    total = sum(
        float(jnp.abs(r).sum()) for r in jax.tree.leaves(st.residual)
    )
    assert total > 0.0  # the wire is lossy; EF captured the error


def test_wire_ef_off_allocates_no_residual(hvd_module):
    params, batch, loss_fn = _problem()
    _, _, st = _run_steps(
        loss_fn, params, batch,
        SchedConfig(bucket_bytes=64, wire="int8", wire_ef=False),
    )
    assert st.residual is None


def test_bf16_wire_rides_per_bucket(hvd_module):
    params, batch, loss_fn = _problem()
    _, dense, _ = _run_steps(loss_fn, params, batch,
                             SchedConfig(bucket_bytes=64))
    _, b16, _ = _run_steps(loss_fn, params, batch,
                           SchedConfig(bucket_bytes=64, wire="bf16"))
    np.testing.assert_allclose(b16, dense, rtol=5e-2)


def test_wire_bytes_gauges_and_ratio(hvd_module):
    """sched.wire_bytes{wire=int8} reads the bytes the wire format
    defines, n + 4 * ceil(n / block) a bucket; against the fp32 wire on
    the same schedule that is >= 3x once a bucket fills its blocks (a
    26-element problem pays 12 bytes of scales on 26 of payload)."""
    from horovod_tpu.ops.quantized import quant_block

    def wire_gauges(params, batch, loss_fn, bucket_bytes):
        metrics.reset_counters("sched.")
        _run_steps(loss_fn, params, batch,
                   SchedConfig(bucket_bytes=bucket_bytes))
        dense = metrics.get_gauge("sched.wire_bytes", {"wire": "off"})
        metrics.reset_counters("sched.")
        _run_steps(loss_fn, params, batch,
                   SchedConfig(bucket_bytes=bucket_bytes, wire="int8"))
        return dense, metrics.get_gauge("sched.wire_bytes",
                                        {"wire": "int8"})

    block = quant_block()
    params, batch, loss_fn = _problem()
    elems = [int(np.prod(v.shape)) for v in params.values()]
    assert max(elems) * F32 <= 64 and sum(elems) < block
    dense_bytes, int8_bytes = wire_gauges(params, batch, loss_fn, 64)
    assert dense_bytes == sum(elems) * F32
    # every bucket is smaller than one block: one fp32 scale each
    buckets = metrics.get_gauge("sched.buckets_per_step")
    assert int8_bytes == sum(elems) + 4 * buckets
    assert metrics.get_counter("sched.wire_bytes.int8") > 0

    n = 8 * block  # one bucket of whole blocks
    wide = {"w": jnp.full((n,), 0.01)}
    x = jnp.ones((16, n))
    dense_bytes, int8_bytes = wire_gauges(
        wide, x, lambda p, b: jnp.mean((b @ p["w"]) ** 2), n * F32)
    assert dense_bytes == n * F32
    assert int8_bytes == n + 4 * (n // block)
    assert dense_bytes / int8_bytes >= 3.0
    assert metrics.get_gauge("sched.compression_ratio") >= 3.0


def test_gradient_accumulation_threads_residual(hvd_module):
    params, batch, loss_fn = _problem()
    X, Y = batch
    cfg = SchedConfig(bucket_bytes=64, wire="int8")
    sched.set_config_override(cfg)
    try:
        tx = hvd.DistributedOptimizer(
            optax.sgd(0.1), backward_passes_per_step=2)
        step = hvd.distributed_train_step(loss_fn, tx)
        p = fresh(params)
        st = step.init(p)
        for _ in range(2):
            p, st, _ = step(p, st, (X[:8], Y[:8]))
            p, st, _ = step(p, st, (X[8:], Y[8:]))
        assert st.residual is not None
        total = sum(
            float(jnp.abs(r).sum()) for r in jax.tree.leaves(st.residual)
        )
        assert total > 0.0
    finally:
        sched.set_config_override(None)


# ------------------------------------------ reduce_scatter mode routing

def test_int8_compression_routes_quantized_in_rs_mode(hvd_module):
    """Satellite: Compression.int8 + HVD_TPU_SCHED_MODE=reduce_scatter
    must run the quantized RS/AG primitives, not silently degrade to the
    dense path — the wire gauges prove which wire carried the bytes."""
    params, batch, loss_fn = _problem()
    metrics.reset_counters("sched.")
    _, losses, st = _run_steps(
        loss_fn, params, batch,
        SchedConfig(bucket_bytes=64, mode="reduce_scatter"),
        n=30, compression=hvd.Compression.int8,
    )
    assert st.residual is not None  # EF rides the explicit int8 wire
    int8_bytes = metrics.get_gauge("sched.wire_bytes", {"wire": "int8"})
    assert int8_bytes and int8_bytes > 0
    assert metrics.get_gauge("sched.wire_bytes", {"wire": "off"}) is None
    # and it still trains to the dense answer
    _, dense, _ = _run_steps(
        loss_fn, params, batch,
        SchedConfig(bucket_bytes=64, mode="reduce_scatter"), n=30,
    )
    assert losses[-1] == pytest.approx(dense[-1], abs=1e-3)


def test_rs_mode_wire_env_matches_allreduce_mode(hvd_module):
    params, batch, loss_fn = _problem()
    _, ar, _ = _run_steps(
        loss_fn, params, batch,
        SchedConfig(bucket_bytes=64, wire="int8"), n=10,
    )
    _, rs, _ = _run_steps(
        loss_fn, params, batch,
        SchedConfig(bucket_bytes=64, wire="int8", mode="reduce_scatter"),
        n=10,
    )
    # for a quantized bucket the RS+AG decomposition IS the allreduce
    assert ar == rs


def test_quantized_wire_raises_for_adasum(hvd_module):
    """Satellite: unsupported combinations raise QuantizedWireError
    instead of silently degrading."""
    from horovod_tpu.optim.distributed_optimizer import _reduce_gradients
    from horovod_tpu.ops.traced import Adasum

    sched.set_config_override(
        SchedConfig(bucket_bytes=64, wire="int8"))
    try:
        with pytest.raises(QuantizedWireError, match="Average"):
            jax.jit(shard_map(
                lambda g: _reduce_gradients(
                    [g[0]], axis=WORLD_AXIS, op=Adasum,
                    compression=hvd.Compression.none,
                    prescale_factor=1.0, postscale_factor=1.0,
                    process_set=None, fusion_threshold_bytes=None,
                )[0][None],
                mesh=hvd.mesh(), in_specs=(P(WORLD_AXIS),),
                out_specs=P(WORLD_AXIS), check_vma=False,
            ))(jnp.ones((8, 16)))
    finally:
        sched.set_config_override(None)


# --------------------------------------------------- bucketed ZeRO-1

def test_bucketed_zero_int8_ef_matches_dense(hvd_module):
    """Acceptance: bucketed_zero_step composes with the quantized wire
    — int8+EF reaches the dense final loss within 1e-3, optimizer
    update fed in fp32, state carries per-bucket residuals."""
    params, batch, loss_fn = _problem()

    def run(cfg):
        step = sched.bucketed_zero_step(loss_fn, optax.adam(1e-2), cfg=cfg)
        st = step.init(params)
        p = fresh(params)
        loss = None
        for _ in range(30):
            p, st, loss = step(p, st, batch)
        return float(loss), st

    dense_loss, dense_st = run(SchedConfig(bucket_bytes=32))
    q_loss, q_st = run(SchedConfig(bucket_bytes=32, wire="int8"))
    assert q_loss == pytest.approx(dense_loss, abs=1e-3)
    # dense state structure unchanged; quantized buckets carry {"tx","ef"}
    assert not any(isinstance(s, dict) for s in dense_st)
    assert all(isinstance(s, dict) and "ef" in s for s in q_st)


def test_bucketed_zero_int8_state_still_sharded(hvd_module):
    params, batch, loss_fn = _problem()
    world = hvd.size()
    step = sched.bucketed_zero_step(
        loss_fn, optax.adam(1e-2),
        cfg=SchedConfig(bucket_bytes=32, wire="int8"),
    )
    st = step.init(params)
    for s in st:
        mu = s["tx"][0].mu
        assert len(mu.sharding.device_set) == world


def test_zero_train_step_int8_wire(hvd_module):
    from horovod_tpu.optim.zero import zero_train_step

    params, batch, loss_fn = _problem()

    def run(wire):
        step = zero_train_step(loss_fn, optax.sgd(0.05), wire=wire)
        st = step.init(params)
        p = fresh(params)
        loss = None
        for _ in range(30):
            p, st, loss = step(p, st, batch)
        return float(loss)

    assert run("int8") == pytest.approx(run("off"), abs=1e-3)


# ---------------------------------------------------- 2x2 dp x tp mesh

def test_2x2_dp_tp_int8_ef_matches_dense(hvd_module):
    """Acceptance: a 2×2 dp×tp CPU-mesh train loop with int8 wire + EF
    (residuals threaded through sync_gradients_bucketed) matches the
    dense path's final loss within 1e-3, with >= 3x wire reduction."""
    from horovod_tpu.parallel import make_mesh

    d, n_tp, n_dp = 8, 2, 2
    rng = np.random.RandomState(9)
    x = rng.randn(8, d).astype(np.float32)
    tgt = rng.randn(8, d).astype(np.float32)
    w_rep0 = (rng.randn(d, d) * 0.3).astype(np.float32)
    wo0 = (rng.randn(n_tp, d, d) * 0.1).astype(np.float32)  # tp-sharded
    mesh = make_mesh(dp=n_dp, tp=n_tp, devices=jax.devices()[:4])
    shard_axes = {"w_rep": "", "wo": "tp"}
    specs = {"w_rep": P(), "wo": P("tp")}
    lr = 0.05

    def make_step(cfg, ef):
        def body(p, res, x, tgt):
            def loss_fn(p):
                y = jnp.tanh(x @ p["w_rep"]) @ p["wo"][0]
                y = jax.lax.psum(y, "tp")
                return jnp.mean((y - tgt) ** 2)

            loss, g = jax.value_and_grad(loss_fn)(p)
            if res is not None:
                g, res = sched.sync_gradients_bucketed(
                    g, shard_axes, axes=("dp", "tp"), cfg=cfg,
                    residuals=res,
                )
            else:
                g = sched.sync_gradients_bucketed(
                    g, shard_axes, axes=("dp", "tp"), cfg=cfg,
                )
            p = jax.tree.map(lambda w, gw: w - lr * gw, p, g)
            return (p, res, loss) if res is not None else (p, loss)

        if ef:
            return body
        return lambda p, x, tgt: body(p, None, x, tgt)

    def run(cfg, ef):
        p = {"w_rep": jnp.asarray(w_rep0), "wo": jnp.asarray(wo0)}
        res = (
            jax.tree.map(lambda a: jnp.zeros_like(a), p) if ef else None
        )
        in_specs = (specs,) + ((specs,) if ef else ()) + (P("dp"), P("dp"))
        out_specs = (specs,) + ((specs,) if ef else ()) + (P(),)
        f = jax.jit(shard_map(
            make_step(cfg, ef), mesh=mesh, in_specs=in_specs,
            out_specs=out_specs, check_vma=False,
        ))
        loss = None
        for _ in range(30):
            if ef:
                p, res, loss = f(p, res, jnp.asarray(x), jnp.asarray(tgt))
            else:
                p, loss = f(p, jnp.asarray(x), jnp.asarray(tgt))
        return float(loss)

    metrics.reset_counters("sched.")
    dense = run(SchedConfig(bucket_bytes=64), ef=False)
    dense_bytes = metrics.get_gauge("sched.wire_bytes", {"wire": "off"})
    metrics.reset_counters("sched.")
    quant = run(SchedConfig(bucket_bytes=64, wire="int8"), ef=True)
    int8_bytes = metrics.get_gauge("sched.wire_bytes", {"wire": "int8"})
    assert quant == pytest.approx(dense, abs=1e-3), (dense, quant)
    assert int8_bytes and dense_bytes
    assert dense_bytes / int8_bytes >= 3.0


# -------------------------------------------------------------- tuner

def test_tuner_explores_and_freezes_wire():
    metrics.reset_counters("train.")
    metrics.reset_counters("sched.")
    tuner = sched.ScheduleTuner(explore_wire=True, warmup_windows=2)
    seen = []
    # off/bf16/int8/fp8 each get one scored window; int8 made fastest
    rates = {"off": 5, "bf16": 8, "int8": 20, "fp8": 10}
    for _ in range(4):
        w = tuner.wire()
        seen.append(w)
        tuner.begin_window()
        metrics.inc_counter("train.steps", rates[w])
        metrics.observe("train.step_seconds", 1.0)
        metrics.set_gauge("sched.bytes_per_step", 1000.0)
        assert tuner.end_window() > 0
    assert seen == ["off", "bf16", "int8", "fp8"]
    assert tuner.wire() == "int8"  # frozen winner
    assert metrics.get_gauge(
        "sched.tune_wire_score", {"wire": "int8"}) is not None
    # bucket-size tuning proceeds under the frozen wire
    assert not tuner.converged
    for _ in range(2):
        tuner.begin_window()
        metrics.inc_counter("train.steps", 10)
        metrics.observe("train.step_seconds", 1.0)
        tuner.end_window()
    assert tuner.converged


def test_tuner_apply_keeps_small_buckets_dense():
    tuner = sched.ScheduleTuner(explore_wire=False,
                                wire_min_bucket_bytes=1024)
    tuner._wire_frozen = "int8"
    s = build_schedule(
        [2048, 100], ["float32", "float32"],
        SchedConfig(bucket_bytes=2048),
    )
    applied = tuner.apply(s)
    wires = {b.nbytes: b.wire for b in applied.buckets}
    assert wires[2048] == "int8"
    assert wires[100] == "off"


# ------------------------------------------- checkpoint / elastic flow

def test_ef_residual_survives_checkpoint_roundtrip(hvd_module, tmp_path):
    """The EF residual is ordinary optimizer-state pytree: it rides
    save_checkpoint/load_checkpoint (and therefore elastic
    restore) without special handling, and training resumes from the
    restored residual exactly."""
    params, batch, loss_fn = _problem()
    cfg = SchedConfig(bucket_bytes=64, wire="int8")
    sched.set_config_override(cfg)
    try:
        tx = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = hvd.distributed_train_step(loss_fn, tx)
        p = fresh(params)
        st = step.init(p)
        for _ in range(3):
            p, st, _ = step(p, st, batch)

        path = str(tmp_path / "ckpt")
        hvd.save_checkpoint(path, {"params": p, "opt_state": st}, step=3)
        loaded = hvd.load_checkpoint(path, step=3)
        restored = jax.tree.unflatten(
            jax.tree.structure(st), jax.tree.leaves(loaded["opt_state"])
        )
        for a, b in zip(
            jax.tree.leaves(st.residual),
            jax.tree.leaves(restored.residual),
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

        # resuming from the restored state tracks the uninterrupted run
        p1, st1, l1 = step(p, st, batch)
        p2, st2, l2 = step(
            jax.tree.unflatten(
                jax.tree.structure(p), jax.tree.leaves(loaded["params"])
            ),
            restored, batch,
        )
        assert float(l1) == float(l2)
    finally:
        sched.set_config_override(None)
