"""Async exchange service (svc/): queue, negotiation, cache, faults,
bounded staleness.

Contracts under test:

* **Submission** — TensorQueue ordering/depth accounting; coordinator-
  bitvector negotiation gates multi-participant programs and releases
  deterministically.
* **ResponseCache** — repeated program signatures hit the cache with
  ZERO re-lowering, results bitwise-equal to the cold path; the key
  folds in the topo-fit epoch so a cost-model refit invalidates it.
* **Producers** — N concurrent threads submitting interleaved dense-
  grad + a2a programs drain deterministically; the traced producers
  (sched/execute.py, xir/interp.py) make HVD_TPU_SVC on/off bitwise
  identical at staleness 0.
* **Faults** — svc.submit / svc.drain / svc.loop fault sites kill the
  service mid-flight and every submission degrades to synchronous
  inline dispatch (svc.fallback_sync), never a wedged step.
* **Staleness** — the delayed-DCN-sync pipeline converges on the
  quadratic bowl with k=1 while overlapping hops into later steps
  (svc.overlap_steps).
* **Satellites** — the xir/lower.py store-sync memo invalidates on a
  topo-fit refit; service accounting renders on the /metrics surface.
"""

import threading

import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu import faults, metrics, sched, svc, topo, xir
from horovod_tpu.exceptions import HorovodTpuError
from horovod_tpu.runtime import WORLD_AXIS
from horovod_tpu.svc.cache import CachedResponse, ResponseCache
from horovod_tpu.svc.negotiate import Negotiator
from horovod_tpu.svc.queue import Submission, SvcFuture, TensorQueue
from horovod_tpu.topo import model as topo_model

pytestmark = pytest.mark.svc

N = 8
T24 = topo_model.Topology(num_slices=2, slice_size=4)


@pytest.fixture(autouse=True)
def _svc_isolation():
    metrics.reset_counters("svc.")
    yield
    svc.set_enabled_override(None)
    svc.set_staleness_override(None)
    svc.reset_service()
    sched.set_config_override(None)
    topo.set_topology_override(None)
    faults.set_plan(None)
    xir.lower.reset()


def _sub(program, args=(), producer="p", participants=(), seq=None,
         queue=None):
    return Submission(
        seq=seq if seq is not None else (queue or TensorQueue()).next_seq(),
        producer=producer, program=program, args=list(args),
        future=SvcFuture(), participants=tuple(participants),
    )


def _ar_program(kind="test", nbytes=32, bucket=0, reduce="mean"):
    return xir.program(kind, [
        xir.all_reduce(WORLD_AXIS, reduce=reduce, bucket=bucket,
                       nbytes=nbytes, dtype="float32"),
    ])


class TestTensorQueue:
    def test_fifo_order_and_depth_gauges(self):
        q = TensorQueue()
        p = _ar_program()
        for producer in ("a", "b", "a"):
            q.put(_sub(p, producer=producer, seq=q.next_seq()))
        assert q.depth() == 3
        assert q.depth("a") == 2 and q.depth("b") == 1
        assert metrics.get_gauge("svc.queue_depth") == 3
        assert metrics.get_gauge(
            "svc.queue_depth", {"producer": "a"}) == 2
        batch = q.pop_batch(timeout=0)
        assert [s.producer for s in batch] == ["a", "b", "a"]
        assert [s.seq for s in batch] == sorted(s.seq for s in batch)
        # drained producers read 0, not a stale last value
        assert metrics.get_gauge(
            "svc.queue_depth", {"producer": "a"}) == 0

    def test_close_rejects_puts_and_returns_leftovers(self):
        q = TensorQueue()
        q.put(_sub(_ar_program(), seq=q.next_seq()))
        left = q.close()
        assert len(left) == 1
        with pytest.raises(HorovodTpuError, match="closed"):
            q.put(_sub(_ar_program(), seq=q.next_seq()))

    def test_capacity_bound(self):
        q = TensorQueue(capacity=2)
        q.put(_sub(_ar_program(), seq=q.next_seq()))
        q.put(_sub(_ar_program(), seq=q.next_seq()))
        with pytest.raises(HorovodTpuError, match="capacity"):
            q.put(_sub(_ar_program(), seq=q.next_seq()))


class TestNegotiator:
    def test_single_producer_bypasses_negotiation(self):
        neg = Negotiator()
        s = _sub(_ar_program(), producer="solo")
        assert neg.post(s) == [s]
        assert neg.pending_count() == 0

    def test_bitvector_gates_until_every_participant_posts(self):
        neg = Negotiator()
        p = _ar_program()
        a = _sub(p, producer="a", participants=("a", "b"), seq=1)
        assert neg.post(a) == []
        assert neg.pending_count() == 1
        b = _sub(p, producer="b", participants=("a", "b"), seq=2)
        ready = neg.post(b)
        # deterministic release order: participant-sorted
        assert [s.producer for s in ready] == ["a", "b"]
        assert neg.pending_count() == 0
        assert metrics.get_counter("svc.negotiations") == 1
        hist = metrics.get_histogram("svc.negotiation_seconds")
        assert hist is not None and hist["count"] == 1

    def test_different_signatures_do_not_cross_release(self):
        neg = Negotiator()
        a = _sub(_ar_program(nbytes=32), producer="a",
                 participants=("a", "b"), seq=1)
        b = _sub(_ar_program(nbytes=64), producer="b",
                 participants=("a", "b"), seq=2)
        assert neg.post(a) == [] and neg.post(b) == []
        assert neg.pending_count() == 2

    def test_abandon_counts_and_returns_orphans(self):
        neg = Negotiator()
        s = _sub(_ar_program(), producer="a", participants=("a", "b"),
                 seq=1)
        neg.post(s)
        orphans = neg.abandon()
        assert orphans == [s]
        assert metrics.get_counter("svc.negotiations_abandoned") == 1

    def test_release_order_invariant_under_post_permutations(self):
        """Cross-producer property (the fusion-layout contract): a
        released class — which the FusionPacker will pack into ONE
        buffer — must come out in deterministic global order no matter
        which order the producers posted in.  Release is participant-
        sorted (never arrival-sorted), and the packer's (producer, seq)
        member order is invariant under arrival permutations, so every
        process computes the identical fused layout."""
        import itertools

        from horovod_tpu.svc import fuse

        producers = ("a", "b", "c")
        releases, layouts = [], []
        for perm in itertools.permutations(producers):
            neg = Negotiator()
            prog = xir.program("test", [
                xir.all_reduce(WORLD_AXIS, reduce="mean",
                               lowering="flat", nbytes=64,
                               dtype="float32"),
            ])
            ready = []
            for seq, producer in enumerate(perm, start=1):
                sub = _sub(prog, args=[jnp.zeros((N, 16), jnp.float32)],
                           producer=producer, participants=producers,
                           seq=seq)
                ready = neg.post(sub)
            assert [s.producer for s in ready] == list(producers)
            releases.append([s.producer for s in ready])
            buffers, passthrough = fuse.plan_cycle(
                [(s, s.program) for s in ready], threshold=1 << 20
            )
            assert passthrough == [] and len(buffers) == 1
            layouts.append(
                [m.sub.producer for m in buffers[0].members]
            )
        assert all(r == releases[0] for r in releases), releases
        assert all(lo == layouts[0] for lo in layouts), layouts


class TestResponseCache:
    def test_miss_insert_hit_counters(self):
        cache = ResponseCache(cap=8)
        key = ResponseCache.key(_ar_program(), None)
        assert cache.lookup(key) is None
        cache.insert(key, CachedResponse(program=_ar_program()))
        assert cache.lookup(key) is not None
        assert metrics.get_counter("svc.cache_miss") == 1
        assert metrics.get_counter("svc.cache_hit") == 1

    def test_lru_eviction(self):
        cache = ResponseCache(cap=2)
        keys = [ResponseCache.key(_ar_program(nbytes=32 * (i + 1)), None)
                for i in range(3)]
        for k in keys:
            cache.insert(k, CachedResponse(program=_ar_program()))
        assert len(cache) == 2
        assert metrics.get_counter("svc.cache_evict") == 1
        assert cache.lookup(keys[0]) is None  # the oldest went

    def test_zero_capacity_disables(self):
        cache = ResponseCache(cap=0)
        key = ResponseCache.key(_ar_program(), None)
        cache.insert(key, CachedResponse(program=_ar_program()))
        assert cache.lookup(key) is None

    def test_key_folds_in_fit_epoch(self):
        from horovod_tpu.topo import fit

        p = _ar_program()
        k1 = ResponseCache.key(p, None)
        assert k1 == ResponseCache.key(p, None)
        _force_fit_epoch_bump()
        assert ResponseCache.key(p, None) != k1
        fit.reset()


def _force_fit_epoch_bump():
    """Drive a real measured fit so the epoch advances the way it does
    in production (never by poking the counter)."""
    from horovod_tpu.topo import fit
    from horovod_tpu.topo.model import cost_coefficients

    topo.set_topology_override(T24)
    before = fit.fit_epoch()
    for lo in ("flat", "hier"):
        for nb in (1 << 16, 1 << 18, 1 << 20, 1 << 22):
            c = cost_coefficients("all_reduce", nb, lo, N, T24)
            base = (
                c[0] * T24.phase_overhead_s
                + c[1] * T24.ici_latency_s + c[2] * T24.dcn_latency_s
                + c[3] / (T24.ici_gbps * 1e9)
                + c[4] / (T24.dcn_gbps * 1e9)
            )
            for _ in range(5):
                fit.record_observation("all_reduce", lo, nb, N, base)
    fp = fit.refresh(force=True)
    assert fp is not None, "synthetic observations did not fit"
    assert fit.fit_epoch() == before + 1
    return fp


@pytest.mark.usefixtures("hvd_module")
class TestServiceHostPath:
    def test_all_reduce_matches_numpy_and_cache_hits_bitwise(self):
        s = svc.get_service()
        x = jnp.asarray(
            np.random.RandomState(0).randn(N, 16).astype(np.float32)
        )
        prog = _ar_program(nbytes=64)
        cold = s.submit(prog, [x], producer="t").result(timeout=60)[0]
        np.testing.assert_allclose(
            np.asarray(cold),
            np.broadcast_to(np.asarray(x).mean(0), (N, 16)),
            rtol=1e-6,
        )
        lowerings = metrics.get_counter("svc.lowerings")
        warm = s.submit(prog, [x], producer="t").result(timeout=60)[0]
        # zero re-lowering on the repeat, bitwise-equal payloads
        assert metrics.get_counter("svc.lowerings") == lowerings
        assert metrics.get_counter("svc.cache_hit") >= 1
        assert (np.asarray(warm) == np.asarray(cold)).all()

    def test_all_to_all_program(self):
        s = svc.get_service()
        x = jnp.arange(N * N, dtype=jnp.float32).reshape(N, N)
        prog = xir.program("moe", [
            xir.all_to_all(WORLD_AXIS, split_axis=0, concat_axis=0,
                           nbytes=int(x.nbytes), dtype="float32"),
        ])
        out = s.submit(prog, [x], producer="moe").result(timeout=60)[0]
        # one row per rank in == transposed block layout out
        np.testing.assert_array_equal(
            np.asarray(out).reshape(N, N), np.asarray(x).reshape(N, N).T
        )

    def test_negotiated_multi_producer_submission(self):
        s = svc.get_service()
        x = jnp.ones((N, 4), jnp.float32)
        prog = _ar_program(nbytes=16, reduce="sum")
        fa = s.submit(prog, [x], producer="a", participants=("a", "b"))
        assert not fa.done()  # gated on b's bit
        fb = s.submit(prog, [x * 2], producer="b",
                      participants=("a", "b"))
        ra = fa.result(timeout=60)[0]
        rb = fb.result(timeout=60)[0]
        np.testing.assert_allclose(np.asarray(ra), N * 1.0)
        np.testing.assert_allclose(np.asarray(rb), N * 2.0)
        assert metrics.get_counter("svc.negotiations") == 1

    def test_concurrent_producers_drain_deterministically(self):
        """Satellite: N threads submitting interleaved dense-grad +
        a2a programs drain deterministically, with response-cache hits
        bitwise-equal to cold-path results."""
        rng = np.random.RandomState(3)
        grads = [
            jnp.asarray(rng.randn(N, 8).astype(np.float32))
            for _ in range(4)
        ]
        shuf = jnp.asarray(rng.randn(N, N, 2).astype(np.float32))

        def run_once():
            s = svc.get_service()
            results = {}

            def dense_producer(tid):
                prog = _ar_program("dense_grad", nbytes=32, bucket=tid)
                futs = [
                    s.submit(prog, [g], producer=f"dense{tid}")
                    for g in grads
                ]
                results[f"dense{tid}"] = [
                    np.asarray(f.result(timeout=60)[0]) for f in futs
                ]

            def a2a_producer(tid):
                prog = xir.program("moe", [
                    xir.all_to_all(WORLD_AXIS, split_axis=0,
                                   concat_axis=0,
                                   nbytes=int(shuf.nbytes),
                                   dtype="float32"),
                ])
                futs = [
                    s.submit(prog, [shuf], producer=f"moe{tid}")
                    for _ in range(3)
                ]
                results[f"moe{tid}"] = [
                    np.asarray(f.result(timeout=60)[0]) for f in futs
                ]

            threads = [
                threading.Thread(target=dense_producer, args=(i,))
                for i in range(2)
            ] + [
                threading.Thread(target=a2a_producer, args=(i,))
                for i in range(2)
            ]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=120)
            assert s.drain(timeout_s=30)
            return results

        first = run_once()
        hits_after_first = metrics.get_counter("svc.cache_hit")
        assert hits_after_first > 0  # repeat submissions hit in-run
        svc.reset_service()
        second = run_once()
        assert sorted(first) == sorted(second)
        for key in first:
            for a, b in zip(first[key], second[key]):
                assert (a == b).all(), f"nondeterministic drain: {key}"


@pytest.mark.faults
@pytest.mark.usefixtures("hvd_module")
class TestServiceFaults:
    def test_submit_fault_kills_service_and_falls_back_inline(self):
        faults.set_plan("svc.submit:error:nth=1")
        s = svc.get_service()
        x = jnp.ones((N, 4), jnp.float32)
        out = s.submit(_ar_program(nbytes=16), [x],
                       producer="t").result(timeout=60)[0]
        np.testing.assert_allclose(np.asarray(out), 1.0)
        assert s.dead
        assert metrics.get_counter("svc.fallback_sync") >= 1
        assert metrics.get_counter("svc.deaths") == 1
        # the dead service keeps serving, synchronously
        out2 = s.submit(_ar_program(nbytes=16), [x * 3],
                        producer="t").result(timeout=60)[0]
        np.testing.assert_allclose(np.asarray(out2), 3.0)

    def test_loop_fault_mid_flight_resolves_queued_futures(self):
        faults.set_plan("svc.loop:error:nth=1")
        s = svc.get_service()
        x = jnp.ones((N, 4), jnp.float32)
        futs = [
            s.submit(_ar_program(nbytes=16, bucket=i), [x * (i + 1)],
                     producer="t")
            for i in range(3)
        ]
        for i, f in enumerate(futs):
            np.testing.assert_allclose(
                np.asarray(f.result(timeout=60)[0]), float(i + 1)
            )
        assert s.dead
        assert metrics.get_counter(
            "faults.injected.svc.loop.error") == 1

    def test_drain_fault_degrades_clean(self):
        faults.set_plan("svc.drain:error:nth=1")
        s = svc.get_service()
        assert s.drain(timeout_s=5) is False
        assert s.dead
        # a post-death submit still resolves inline
        x = jnp.ones((N, 2), jnp.float32)
        out = s.submit(_ar_program(nbytes=8), [x],
                       producer="t").result(timeout=60)[0]
        np.testing.assert_allclose(np.asarray(out), 1.0)

    def test_traced_producer_fault_falls_back_to_local_lowering(self):
        faults.set_plan("svc.submit:error:nth=1")
        svc.set_enabled_override(True)
        prog = _ar_program(nbytes=1 << 20)
        lowered = svc.get_service().submit_traced(prog, producer="x")
        assert lowered.lowered
        assert metrics.get_counter("svc.fallback_sync") >= 1

    def _assert_depth_gauges_zero(self, producers):
        # The PR 13 satellite contract: after ANY fault-injection path,
        # every queue-depth gauge — global and per-producer — decays to
        # 0: a submission that degraded to inline dispatch after a
        # service death must not leave the gauge it incremented.
        assert metrics.get_gauge("svc.queue_depth") in (0, 0.0), \
            "global svc.queue_depth did not decay to 0"
        for prod in producers:
            g = metrics.get_gauge("svc.queue_depth", {"producer": prod})
            assert g in (None, 0, 0.0), \
                f"svc.queue_depth{{producer={prod}}} leaked at {g}"

    def test_queue_depth_decays_to_zero_after_loop_fault(self):
        faults.set_plan("svc.loop:error:nth=1")
        s = svc.get_service()
        x = jnp.ones((N, 4), jnp.float32)
        futs = [
            s.submit(_ar_program(nbytes=16, bucket=i), [x],
                     producer=f"p{i % 2}")
            for i in range(4)
        ]
        for f in futs:
            f.result(timeout=60)
        assert s.dead
        self._assert_depth_gauges_zero(["p0", "p1"])
        # submissions AFTER the death take the closed-queue fallback
        # and must not resurrect any depth series
        out = s.submit(_ar_program(nbytes=8), [x],
                       producer="late").result(timeout=60)
        assert out is not None
        self._assert_depth_gauges_zero(["p0", "p1", "late"])

    def test_queue_depth_decays_to_zero_after_submit_and_drain_faults(self):
        for site in ("svc.submit", "svc.drain"):
            svc.reset_service()
            metrics.reset_counters("svc.")
            faults.set_plan(f"{site}:error:nth=1")
            s = svc.get_service()
            x = jnp.ones((N, 2), jnp.float32)
            if site == "svc.drain":
                s.submit(_ar_program(nbytes=8), [x], producer="a")
                s.drain(timeout_s=5)
            else:
                s.submit(_ar_program(nbytes=8), [x],
                         producer="a").result(timeout=60)
            assert s.dead
            self._assert_depth_gauges_zero(["a"])
            faults.set_plan(None)

    def test_dead_service_loop_thread_terminates(self):
        # The loop must EXIT after a kill, not spin hot on the closed
        # queue (the pre-PR-13 behavior burned a core per dead service).
        faults.set_plan("svc.loop:error:nth=1")
        s = svc.get_service()
        x = jnp.ones((N, 2), jnp.float32)
        s.submit(_ar_program(nbytes=8), [x], producer="t").result(
            timeout=60)
        assert s.dead
        t = s._thread
        if t is not None:
            t.join(timeout=10)
            assert not t.is_alive(), "dead service loop still running"


class TestNegotiationStallInspector:
    def test_stall_names_missing_participants(self, caplog):
        neg = Negotiator()
        prog = _ar_program(kind="stallk")
        sub = _sub(prog, producer="a", participants=("a", "b", "ghost"))
        assert neg.post(sub) == []
        # nothing stalls before the timeout
        assert neg.check_stalls(timeout_s=60.0) == []
        reports = neg.check_stalls(timeout_s=0.0)
        assert len(reports) == 1
        assert reports[0]["missing"] == ["b", "ghost"]
        assert reports[0]["posted"] == ["a"]
        assert sorted(reports[0]["expected"]) == ["a", "b", "ghost"]
        assert metrics.get_counter("svc.stall") == 1
        assert metrics.get_gauge("svc.stalled_negotiations") == 1
        # warn-once: a second sweep reports but does not re-count
        neg.check_stalls(timeout_s=0.0)
        assert metrics.get_counter("svc.stall") == 1
        # completion clears the stall bookkeeping
        for prod in ("b", "ghost"):
            neg.post(_sub(prog, producer=prod,
                          participants=("a", "b", "ghost")))
        assert neg.check_stalls(timeout_s=0.0) == []
        assert metrics.get_gauge("svc.stalled_negotiations") == 0

    def test_service_loop_runs_stall_check(self):
        import time as _time

        from horovod_tpu.utils import env as hvd_env

        # A 2-participant program with one producer missing: the live
        # service loop itself must emit the svc.stall warning once the
        # (tiny) timeout passes — no drain needed to see it.
        hvd_env.set_env(hvd_env.STALL_TIMEOUT, "0.2")
        try:
            s = svc.get_service()
            x = jnp.ones((N, 2), jnp.float32)
            s.submit(_ar_program(nbytes=8), [x], producer="a",
                     participants=("a", "never"))
            deadline = _time.monotonic() + 15
            while metrics.get_counter("svc.stall") == 0 \
                    and _time.monotonic() < deadline:
                _time.sleep(0.05)
            assert metrics.get_counter("svc.stall") >= 1, \
                "service loop never flagged the stalled negotiation"
        finally:
            import os

            os.environ.pop("HVD_TPU_STALL_TIMEOUT", None)
            svc.reset_service()


def _train(svc_on, iters=6, lr=0.05):
    svc.set_enabled_override(svc_on)
    sched.set_config_override(
        sched.SchedConfig(bucket_bytes=2048)
    )
    try:
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
        tx = hvd.DistributedOptimizer(optax.sgd(lr))
        step = hvd.distributed_train_step(lf, tx)
        st = step.init(p)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        losses = []
        for _ in range(iters):
            p, st, loss = step(p, st, batch)
            losses.append(float(loss))
        return losses
    finally:
        sched.set_config_override(None)
        svc.set_enabled_override(None)


@pytest.mark.usefixtures("hvd_module")
class TestTracedProducers:
    def test_svc_on_off_bitwise_identical_at_staleness_zero(self):
        off = _train(False)
        on = _train(True)
        assert off == on, f"svc on diverged from off: {on} vs {off}"
        assert metrics.get_counter("svc.submits") > 0

    def test_xir_execute_routes_lowering_through_cache(self):
        svc.set_enabled_override(True)
        # lowering="auto": the program arrives unlowered, so execute()
        # must resolve it — through the service's ResponseCache.
        prog = xir.program("fsdp", [
            xir.all_reduce(WORLD_AXIS, lowering="auto",
                           nbytes=1024, dtype="float32"),
        ])
        x = jnp.arange(N * N, dtype=jnp.float32).reshape(N, N)

        def body(v):
            return xir.execute(prog, [v], store=False)[0]

        from tests.test_xir import _shard_run

        lowerings0 = metrics.get_counter("svc.lowerings")
        out1 = _shard_run(body, x)
        hits0 = metrics.get_counter("svc.cache_hit")
        out2 = _shard_run(lambda v: body(v) * 1.0, x)  # fresh trace
        assert metrics.get_counter("svc.cache_hit") > hits0
        assert metrics.get_counter("svc.lowerings") == lowerings0 + 1
        np.testing.assert_array_equal(np.asarray(out1),
                                      np.asarray(out2))


@pytest.mark.usefixtures("hvd_module")
class TestBoundedStaleness:
    def test_single_slice_is_ineligible(self):
        topo.set_topology_override(
            topo_model.Topology(num_slices=1, slice_size=8)
        )
        assert svc.stale.eligible() is not None

    def test_staleness_zero_returns_synchronous_step(self):
        svc.set_enabled_override(True)
        svc.set_staleness_override(0)
        tx = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = hvd.distributed_train_step(lambda p, b: jnp.sum(p), tx)
        from horovod_tpu.optim.distributed_optimizer import TrainStep

        assert isinstance(step, TrainStep)

    def test_quadratic_bowl_converges_with_overlap(self):
        topo.set_topology_override(T24)
        svc.set_enabled_override(True)
        svc.set_staleness_override(1)

        def lf(p, b):
            return jnp.sum((p["w"] - 3.0) ** 2) + 0.0 * jnp.sum(b)

        tx = hvd.DistributedOptimizer(optax.sgd(0.2))
        step = hvd.distributed_train_step(lf, tx)
        assert isinstance(step, svc.StaleTrainStep)
        sp, st = step.init({"w": jnp.zeros((4,), jnp.float32)})
        batch = jnp.zeros((N, 1), jnp.float32)
        loss = None
        for _ in range(40):
            sp, st, loss = step(sp, st, batch)
        assert float(loss) < 1e-6, float(loss)
        final = step.consolidate(sp)
        np.testing.assert_allclose(np.asarray(final["w"]), 3.0,
                                   atol=1e-3)
        assert metrics.get_counter("svc.overlap_steps") > 0
        assert metrics.get_gauge("svc.staleness") == 1
        step.drain()

    def test_ineligible_optimizer_stays_synchronous(self):
        topo.set_topology_override(T24)
        svc.set_enabled_override(True)
        svc.set_staleness_override(1)
        # Sum (not Average) reduction is ineligible for the delayed
        # correction: the pipeline falls back to the sync step.
        from horovod_tpu.ops.traced import Sum

        tx = hvd.DistributedOptimizer(optax.sgd(0.1), op=Sum)
        step = hvd.distributed_train_step(lambda p, b: jnp.sum(p), tx)
        from horovod_tpu.optim.distributed_optimizer import TrainStep

        assert isinstance(step, TrainStep)


@pytest.mark.tune
class TestFitEpochMemoInvalidation:
    def test_store_sync_memo_revalidates_after_refit(self, tmp_path,
                                                     monkeypatch):
        """Satellite regression: xir/lower.py's per-process store-sync
        memo must re-consult the tune DB after topo/fit.py refits the
        cost model — before the fix it served the pre-fit entry
        forever."""
        from horovod_tpu.sched.store import ScheduleStore
        from horovod_tpu.topo import fit
        from horovod_tpu.xir import lower as lower_mod

        db = tmp_path / "tune.json"
        monkeypatch.setenv("HVD_TPU_TUNE_DB", str(db))
        topo.set_topology_override(T24)
        lower_mod.reset()
        fit.reset()
        metrics.reset_counters("xir.db")

        prog = _ar_program("dense_grad", nbytes=1 << 20)
        first = lower_mod.lower(prog)
        assert metrics.get_counter("xir.db_seeded") == 1
        # a better-scored winner lands in the DB (a fleet peer tuned it)
        store = ScheduleStore.from_env()
        key = lower_mod.tuner_key(first)
        store.record(key, bucket_bytes=1 << 20, wire="bf16",
                     lowering=first.ops[0].lowering, score=99.0)
        # same epoch: the memo serves the stale adoption (by design —
        # one store read per process per program)
        again = lower_mod.lower(prog)
        assert again.ops[0].wire == first.ops[0].wire
        # refit: epoch bumps, the memo key changes, the store is
        # re-consulted and the new winner adopted
        _force_fit_epoch_bump()
        refreshed = lower_mod.lower(prog)
        assert metrics.get_counter("xir.db_hit") >= 1
        assert refreshed.ops[0].wire == "bf16"
        fit.reset()


@pytest.mark.usefixtures("hvd_module")
class TestMetricsSurface:
    def test_service_accounting_renders_on_metrics_endpoint(self):
        """Satellite: per-producer queue depth, negotiation quantiles,
        and cache hit/miss counters all reach the Prometheus surface
        the elastic driver scrapes."""
        s = svc.get_service()
        x = jnp.ones((N, 4), jnp.float32)
        prog = _ar_program(nbytes=16)
        fa = s.submit(prog, [x], producer="tenant_a",
                      participants=("tenant_a", "tenant_b"))
        fb = s.submit(prog, [x], producer="tenant_b",
                      participants=("tenant_a", "tenant_b"))
        fa.result(timeout=60), fb.result(timeout=60)
        s.submit(prog, [x], producer="tenant_a").result(timeout=60)

        from horovod_tpu.runner.telemetry_http import TelemetryServer

        server = TelemetryServer(port=0, bind_host="127.0.0.1")
        try:
            import urllib.request

            body = urllib.request.urlopen(
                f"http://127.0.0.1:{server.port}/metrics", timeout=10
            ).read().decode()
        finally:
            server.stop()
        assert 'hvd_tpu_svc_queue_depth{producer="tenant_a"}' in body
        assert "hvd_tpu_svc_negotiation_seconds" in body
        assert 'quantile="0.99"' in body
        assert "hvd_tpu_svc_cache_hit_total" in body
        assert "hvd_tpu_svc_cache_miss_total" in body
        assert "hvd_tpu_svc_dispatches_total" in body
