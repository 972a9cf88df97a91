"""Bucketed overlap scheduler (sched/): plan determinism,
reverse-backward order, exchange-mode equivalence, bucketed ZeRO-1,
per-bucket compression, and registry-fed bucket-size tuning."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax import lax
from jax.sharding import PartitionSpec as P

import horovod_tpu as hvd
from horovod_tpu import metrics, sched
from horovod_tpu.ops import fusion
from horovod_tpu.sched import SchedConfig, build_schedule, hooks

pytestmark = pytest.mark.sched

F32 = 4  # bytes


def fresh(tree):
    return jax.tree.map(lambda a: jnp.array(a), tree)


@pytest.fixture(autouse=True)
def _clean_sched_state():
    hooks.reset()
    sched.set_config_override(None)
    yield
    hooks.reset()
    sched.set_config_override(None)


# ---------------------------------------------------------------- plan

def test_plan_deterministic():
    sizes = [100 * F32] * 6
    dtypes = ["float32"] * 6
    cfg = SchedConfig(bucket_bytes=200 * F32)
    a = build_schedule(sizes, dtypes, cfg)
    b = build_schedule(sizes, dtypes, cfg)
    assert a.signature() == b.signature()
    # config changes the plan identity
    c = build_schedule(sizes, dtypes, SchedConfig(bucket_bytes=300 * F32))
    assert a.signature() != c.signature()


def test_plan_reverse_backward_order():
    """Default order: last-registered leaves exchange first (their
    gradients finish the backward first)."""
    sizes = [100 * F32] * 6
    dtypes = ["float32"] * 6
    s = build_schedule(sizes, dtypes, SchedConfig(bucket_bytes=200 * F32))
    assert [b.indices for b in s.buckets] == [(4, 5), (2, 3), (0, 1)]
    assert s.total_bytes == 600 * F32


def test_plan_observed_order_overrides_reversed_default():
    sizes = [10 * F32] * 4
    dtypes = ["float32"] * 4
    s = build_schedule(
        sizes, dtypes, SchedConfig(bucket_bytes=20 * F32),
        order=[1, 0, 3, 2],
    )
    assert [b.indices for b in s.buckets] == [(0, 1), (2, 3)]


def test_plan_pinned_groups_fuse_atomically():
    sizes = [10 * F32] * 5
    dtypes = ["float32"] * 5
    s = build_schedule(
        sizes, dtypes, SchedConfig(bucket_bytes=10 * F32), pinned=[[0, 3]],
    )
    pinned = [b for b in s.buckets if b.pinned]
    assert len(pinned) == 1 and pinned[0].indices == (0, 3)
    # every leaf exchanged exactly once
    all_idx = sorted(i for b in s.buckets for i in b.indices)
    assert all_idx == [0, 1, 2, 3, 4]


def test_plan_incomplete_order_falls_back():
    sizes = [10 * F32] * 3
    dtypes = ["float32"] * 3
    s = build_schedule(
        sizes, dtypes, SchedConfig(bucket_bytes=10 * F32), order=[2, 2, 0],
    )
    assert [b.indices for b in s.buckets] == [(2,), (1,), (0,)]


# ------------------------------------------------- fusion look-ahead

def test_bucket_plan_look_ahead_closes_stale_bucket():
    """A same-dtype tensor arriving more than look_ahead positions after
    a different-dtype bucket opened must NOT rejoin the old bucket
    (it would break reverse-backward exchange ordering)."""
    sizes = [10, 10, 10, 10, 10, 10]
    dtypes = ["float32", "bfloat16", "bfloat16", "bfloat16", "bfloat16",
              "float32"]
    got = fusion.bucket_plan(sizes, dtypes, 1000, look_ahead=3)
    # bf16 bucket opened at position 1; f32 tensor 5 is 4 > 3 positions
    # past it -> the f32 bucket from position 0 is closed.
    assert got == [[0], [1, 2, 3, 4], [5]]
    # legacy unbounded look-ahead keeps the stale join
    legacy = fusion.bucket_plan(sizes, dtypes, 1000, look_ahead=-1)
    assert legacy == [[0, 5], [1, 2, 3, 4]]


def test_bucket_plan_look_ahead_allows_short_interleave():
    sizes = [10, 10, 10, 10]
    dtypes = ["float32", "bfloat16", "float32", "bfloat16"]
    got = fusion.bucket_plan(sizes, dtypes, 1000, look_ahead=3)
    assert got == [[0, 2], [1, 3]]


# ------------------------------------------------------------- hooks

def test_backward_order_capture():
    params = {"a": jnp.ones((4, 4)), "b": jnp.ones((4, 4)),
              "c": jnp.ones((4, 4))}

    def loss(p, x):
        return jnp.sum(x @ p["a"] @ p["b"] @ p["c"])

    jax.grad(hooks.capturing_loss(loss))(params, jnp.ones((2, 4)))
    order = hooks.consume_order(3)
    # c's cotangent materializes first (it is the last matmul applied)
    assert order == [2, 1, 0]


def test_consume_order_rejects_mismatched_leaf_count():
    params = {"a": jnp.ones(3)}
    jax.grad(hooks.capturing_loss(lambda p, x: jnp.sum(p["a"] * x)))(
        params, jnp.ones(3)
    )
    assert hooks.consume_order(7) is None
    assert hooks.consume_order(1) is None  # consumed above, cleared


# ------------------------------------------------- exchange equivalence

def _problem():
    X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
    Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)

    def loss_fn(p, b):
        x, y = b
        return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)

    params = {
        "w1": jnp.full((4, 4), 0.2),
        "w2": jnp.full((4, 2), 0.5),
        "b": jnp.zeros((2,)),
    }
    return params, (jnp.asarray(X), jnp.asarray(Y)), loss_fn


def _run_steps(loss_fn, params, batch, cfg, n=3, **opt_kwargs):
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
        return p, losses
    finally:
        sched.set_config_override(None)


def _reference_steps(loss_fn, params, batch, n=3, wire_dtype=None):
    """The plain reference: a ``pmean`` a leaf (in ``wire_dtype`` when
    given) and the optax update, nothing of the scheduler."""
    tx = optax.sgd(0.1)

    def body(p, st, b):
        loss, g = jax.value_and_grad(loss_fn)(p, b)
        if wire_dtype is not None:
            g = jax.tree.map(lambda x: x.astype(wire_dtype), g)
        g = jax.tree.map(lambda x: lax.pmean(x, hvd.WORLD_AXIS), g)
        g = jax.tree.map(lambda x, q: x.astype(q.dtype), g, p)
        updates, st = tx.update(g, st, p)
        return (optax.apply_updates(p, updates), st,
                lax.pmean(loss, hvd.WORLD_AXIS))

    step = jax.jit(jax.shard_map(
        body, mesh=hvd.mesh(), in_specs=(P(), P(), P(hvd.WORLD_AXIS)),
        out_specs=(P(), P(), P()), check_vma=False,
    ))
    p, st = fresh(params), tx.init(params)
    losses = []
    for _ in range(n):
        p, st, loss = step(p, st, batch)
        losses.append(float(loss))
    return p, losses


def test_sched_losses_identical_to_per_leaf_reference_f32(hvd_module):
    """The scheduler engine is numerics-identical (f32, rtol=0) to a
    per-leaf pmean and the plain optax update."""
    params, batch, loss_fn = _problem()
    # tiny buckets: the three grads exchange as separate buckets
    p_on, l_on = _run_steps(
        loss_fn, params, batch, SchedConfig(bucket_bytes=64))
    p_ref, l_ref = _reference_steps(loss_fn, params, batch)
    assert l_on == l_ref  # bitwise: same floats through repr round-trip
    for k in params:
        np.testing.assert_array_equal(
            np.asarray(p_on[k]), np.asarray(p_ref[k])
        )
    assert metrics.get_gauge("sched.buckets_per_step") >= 2


def test_sched_barrier_chain_is_ordering_only(hvd_module):
    """Three barrier-chained buckets and one bucket (no chain) give the
    reference's losses alike: the barriers touch no value."""
    params, batch, loss_fn = _problem()
    chained = _run_steps(
        loss_fn, params, batch, SchedConfig(bucket_bytes=64))
    assert metrics.get_gauge("sched.buckets_per_step") >= 2
    single = _run_steps(
        loss_fn, params, batch, SchedConfig(bucket_bytes=1 << 20))
    assert metrics.get_gauge("sched.buckets_per_step") == 1
    ref = _reference_steps(loss_fn, params, batch)
    assert chained[1] == single[1] == ref[1]


def test_reduce_scatter_mode_matches_allreduce(hvd_module):
    params, batch, loss_fn = _problem()
    p_ar, l_ar = _run_steps(
        loss_fn, params, batch, SchedConfig(mode="allreduce"))
    p_rs, l_rs = _run_steps(
        loss_fn, params, batch, SchedConfig(mode="reduce_scatter"))
    np.testing.assert_allclose(l_ar, l_rs, rtol=1e-6)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p_ar[k]), np.asarray(p_rs[k]),
            rtol=1e-6, atol=1e-7,
        )


def test_sched_with_gradient_accumulation(hvd_module):
    """backward_passes_per_step defers the exchange to the boundary
    microbatch; the scheduler engine must keep the k-step union-batch
    equivalence."""
    params, batch, loss_fn = _problem()
    X, Y = batch
    cfg = SchedConfig(bucket_bytes=64)
    sched.set_config_override(cfg)
    try:
        tx2 = hvd.DistributedOptimizer(
            optax.sgd(0.1), backward_passes_per_step=2)
        s2 = hvd.distributed_train_step(loss_fn, tx2)
        st2 = s2.init(params)
        p2 = fresh(params)
        p2, st2, _ = s2(p2, st2, (X[:8], Y[:8]))
        p2, st2, _ = s2(p2, st2, (X[8:], Y[8:]))

        tx1 = hvd.DistributedOptimizer(optax.sgd(0.1))
        s1 = hvd.distributed_train_step(loss_fn, tx1)
        p1 = fresh(params)
        st1 = s1.init(p1)
        p1, st1, _ = s1(p1, st1, (X, Y))
    finally:
        sched.set_config_override(None)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p2[k]), np.asarray(p1[k]), rtol=1e-5
        )


def test_explicit_groups_ride_as_pinned_buckets(hvd_module):
    params, batch, loss_fn = _problem()
    a = _run_steps(loss_fn, params, batch, SchedConfig(bucket_bytes=64),
                   groups=[[0, 2]])
    b = _reference_steps(loss_fn, params, batch)
    assert a[1] == b[1]


# ------------------------------------------------ per-bucket compression

def test_compression_round_trip_per_bucket(hvd_module):
    """bf16 wire: the plan carries the bucket's wire dtype, the
    exchange casts per leaf, and the decompressed output restores f32
    — identical to a per-leaf pmean of the bf16 casts."""
    params, batch, loss_fn = _problem()
    on = SchedConfig(bucket_bytes=64)
    p_on, l_on = _run_steps(loss_fn, params, batch, on,
                            compression=hvd.Compression.bf16)
    p_off, l_off = _reference_steps(loss_fn, params, batch,
                                    wire_dtype=jnp.bfloat16)
    assert l_on == l_off
    for k in params:
        assert p_on[k].dtype == jnp.float32
        np.testing.assert_array_equal(
            np.asarray(p_on[k]), np.asarray(p_off[k])
        )
    # and close to the uncompressed trajectory
    p_fp, _ = _run_steps(loss_fn, params, batch, on)
    np.testing.assert_allclose(
        np.asarray(p_on["w2"]), np.asarray(p_fp["w2"]),
        rtol=2e-2, atol=2e-2,
    )


def test_schedule_wire_dtype_recorded():
    s = build_schedule(
        [100, 100], ["bfloat16", "bfloat16"], SchedConfig()
    )
    assert s.buckets[0].wire_dtypes == ("bfloat16",)


# ------------------------------------------------------ bucketed ZeRO-1

def test_bucketed_zero_matches_unsharded_adam(hvd_module):
    params, batch, loss_fn = _problem()
    cfg = SchedConfig(bucket_bytes=32)  # forces several buckets
    step = sched.bucketed_zero_step(loss_fn, optax.adam(1e-2), cfg=cfg)
    st = step.init(params)
    assert len(step.schedule) >= 2
    p = fresh(params)
    for _ in range(5):
        p, st, loss = step(p, st, batch)

    ref_tx = optax.adam(1e-2)
    rp = fresh(params)
    rst = ref_tx.init(rp)
    for _ in range(5):
        g = jax.grad(loss_fn)(rp, batch)
        u, rst = ref_tx.update(g, rst, rp)
        rp = optax.apply_updates(rp, u)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-5
        )


def test_bucketed_zero_state_shapes_reduced(hvd_module):
    """Optimizer state shrinks N-fold: the per-bucket adam moments sum
    to ~n_params total elements (each rank holds 1/N), not N copies."""
    params, batch, loss_fn = _problem()
    n_params = sum(x.size for x in jax.tree.leaves(params))
    world = hvd.size()
    step = sched.bucketed_zero_step(
        loss_fn, optax.adam(1e-2), cfg=SchedConfig(bucket_bytes=32))
    st = step.init(params)
    total_mu = sum(int(s[0].mu.size) for s in st)
    # padded per bucket: at most world-1 pad elements each
    assert n_params <= total_mu <= n_params + len(st) * world
    for s in st:
        mu = s[0].mu
        assert len(mu.sharding.device_set) == world
        assert {sh.data.shape for sh in mu.addressable_shards} == {
            (mu.shape[0] // world,)
        }


def test_bucketed_zero_with_global_norm_clip(hvd_module):
    from horovod_tpu.optim.zero import clip_by_global_norm

    params, (X, Y), loss_fn = _problem()
    batch = (X, Y * 100.0)  # big grads so the clip engages
    step = sched.bucketed_zero_step(
        loss_fn, optax.sgd(0.01), cfg=SchedConfig(bucket_bytes=32),
        pre_update=clip_by_global_norm(1.0),
    )
    st = step.init(params)
    p, st, loss = step(fresh(params), st, batch)

    ref_tx = optax.chain(optax.clip_by_global_norm(1.0), optax.sgd(0.01))
    rp = fresh(params)
    rst = ref_tx.init(rp)
    g = jax.grad(loss_fn)(rp, batch)
    u, rst = ref_tx.update(g, rst, rp)
    rp = optax.apply_updates(rp, u)
    for k in params:
        np.testing.assert_allclose(
            np.asarray(p[k]), np.asarray(rp[k]), rtol=1e-5, atol=1e-6
        )


# -------------------------------------------------------------- tuning

def test_tuner_scores_windows_from_registry():
    metrics.reset_counters("train.")
    metrics.reset_counters("sched.")
    tuner = sched.ScheduleTuner(warmup_windows=2)
    tuner.begin_window()
    metrics.inc_counter("train.steps", 10)
    metrics.observe("train.step_seconds", 0.5)
    metrics.set_gauge("sched.bytes_per_step", 1000.0)
    score = tuner.end_window()
    # 10 steps / 0.5 s * 1000 bytes/step = 20 kB/s
    assert score == pytest.approx(20_000.0)
    assert metrics.get_counter("sched.tune_windows") == 1

    tuner.begin_window()
    metrics.inc_counter("train.steps", 10)
    metrics.observe("train.step_seconds", 1.0)
    tuner.end_window()
    assert tuner.converged
    assert tuner.bucket_bytes() >= 1


def test_tuner_idle_window_not_observed():
    metrics.reset_counters("train.")
    tuner = sched.ScheduleTuner(warmup_windows=2)
    tuner.begin_window()
    assert tuner.end_window() == 0.0  # no steps ran
    assert not tuner.converged


def test_window_score_falls_back_to_steps_per_sec():
    from horovod_tpu.sched.tune import window_score

    before = {"steps": 0, "step_seconds_sum": 0.0, "bytes_per_step": 0.0,
              "mono": 0.0}
    after = {"steps": 4, "step_seconds_sum": 2.0, "bytes_per_step": 0.0,
             "mono": 9.0}
    assert window_score(before, after) == pytest.approx(2.0)


# ------------------------------------------------------- observability

def test_exchange_metrics_and_gauges(hvd_module):
    metrics.reset_counters("sched.")
    params, batch, loss_fn = _problem()
    _run_steps(loss_fn, params, batch, SchedConfig(bucket_bytes=64), n=2)
    assert metrics.get_counter("sched.plans") >= 1
    assert metrics.get_gauge("sched.buckets_per_step") >= 2
    assert metrics.get_gauge("sched.bytes_per_step") > 0
    hist = metrics.get_histogram("sched.bytes_per_bucket")
    assert hist is not None and hist["count"] >= 2
    assert metrics.get_histogram("sched.exchange_seconds") is not None


def test_sched_config_from_env(monkeypatch):
    monkeypatch.setenv("HVD_TPU_SCHED_MODE", "reduce_scatter")
    monkeypatch.setenv("HVD_TPU_SCHED_BUCKET_BYTES", "4096")
    monkeypatch.setenv("HVD_TPU_SCHED_LOOK_AHEAD", "7")
    cfg = SchedConfig.from_env()
    assert cfg.mode == "reduce_scatter"
    assert cfg.bucket_bytes == 4096
    assert cfg.look_ahead == 7
    # every field is a plan or wire choice: the engine has no off switch
    assert {f.name for f in dataclasses.fields(SchedConfig)} == {
        "mode", "bucket_bytes", "look_ahead", "wire", "wire_ef", "lowering"}


# ------------------------------------------- the compiled step's program

_CHAIN = {"a": (4, 4), "b": (4, 6), "c": (6, 2)}  # applied a, b, c


def _lowered_step_hlo(case, k):
    """The HLO ``TrainStep`` hands the compiler (the CPU backend expands
    barriers away, so the compiled text no longer shows them) for a
    three-matmul chain whose gradients, one bucket each, become ready
    c, b, a."""
    params = {n: jnp.full(s, 0.1) for n, s in _CHAIN.items()}
    x = jnp.ones((16, 4))

    def chain(p, b):
        return jnp.sum(b @ p["a"] @ p["b"] @ p["c"])

    compression = hvd.Compression.none
    stateful = False
    loss_fn = chain
    if case == "bf16_compression":
        compression = hvd.Compression.bf16
    elif case == "stateful":
        stateful = True

        def loss_fn(p, state, b):
            return chain(p, b), {"seen": state["seen"] + 1.0}

    sched.set_config_override(SchedConfig(bucket_bytes=8))
    tx = hvd.DistributedOptimizer(
        optax.sgd(0.1), compression=compression,
        backward_passes_per_step=k)
    step = hvd.distributed_train_step(loss_fn, tx, stateful=stateful)
    st = step.init(params)
    model_state = {"seen": jnp.zeros((3,))} if stateful else None
    fn = step._build_step(step._state_specs(st))
    return fn.lower(params, model_state, st, x).as_text(dialect="hlo")


@pytest.mark.parametrize("k", [1, 2], ids=["k1", "k2"])
@pytest.mark.parametrize(
    "case", ["dense_f32", "bf16_compression", "stateful"])
def test_step_program_orders_exchange_before_update(hvd_module, case, k):
    """What the step's program holds of the exchange: one all-reduce a
    bucket in reverse-backward order, chained by barriers, and — only
    where the update follows at once (``backward_passes_per_step=1``) —
    exactly one barrier over all reduced wire-dtype leaves after the
    last of them."""
    import re

    hlo = _lowered_step_hlo(case, k).splitlines()
    dtype = "bf16" if case == "bf16_compression" else "f32"
    sizes = {int(np.prod(s)): n for n, s in _CHAIN.items()}
    reduces = []  # (line number, leaf name) of the gradients' all-reduces
    for i, line in enumerate(hlo):
        m = re.search(r"= (\w+)\[(\d+)\]\{0\} all-reduce\(", line)
        if m and m.group(1) == dtype and int(m.group(2)) in sizes:
            reduces.append((i, sizes[int(m.group(2))]))
    assert [name for _, name in reduces] == ["c", "b", "a"]
    barriers = [i for i, line in enumerate(hlo) if " opt-barrier(" in line]
    first, last = reduces[0][0], reduces[-1][0]
    # the chain: a barrier with the carried token before buckets 1 and 2
    assert len([i for i in barriers if first < i < last]) >= 2
    after = [hlo[i] for i in barriers if i > last]
    if k == 1:
        (tie,) = after
        operands = re.search(r"= \((.*)\) opt-barrier\(", tie).group(1)
        assert re.findall(r"(\w+)\[", operands) == [dtype] * 3, tie
    else:
        assert after == []
