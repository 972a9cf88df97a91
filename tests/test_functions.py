"""broadcast/allgather helpers, SyncBatchNorm, metric averaging, elastic
state (reference analogs: torch/functions tests in test_torch.py,
sync batch norm tests, test_torch_elastic.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

import horovod_tpu as hvd
from horovod_tpu.elastic import ArrayState, ObjectState


def test_broadcast_parameters_single_process(hvd_module):
    params = {"w": jnp.ones((3, 3)), "b": jnp.zeros((3,))}
    out = hvd.broadcast_parameters(params, root_rank=0)
    assert out is params  # single controller: identity


def test_broadcast_object_and_allgather_object(hvd_module):
    obj = {"epoch": 3, "name": "abc"}
    assert hvd.broadcast_object(obj) == obj
    assert hvd.allgather_object(obj) == [obj]


def test_metric_average_single_process(hvd_module):
    assert hvd.metric_average(0.5) == 0.5


def test_sync_batch_norm_module(hvd_module):
    """SyncBatchNorm inside the distributed step: moments averaged over
    the world axis -> identical to BN over the global batch."""
    import flax.linen as nn
    from jax.sharding import PartitionSpec as P

    class Net(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Dense(4)(x)
            x = hvd.SyncBatchNorm(use_running_average=not train)(x)
            return x

    model = Net()
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 4), jnp.float32)
    # init in eval mode: the moments collective needs the mesh axis,
    # which only exists inside shard_map
    variables = model.init(jax.random.PRNGKey(0), x[:2], train=False)
    params, stats = variables["params"], variables["batch_stats"]

    mesh = hvd.mesh()

    def fwd(p, s, xb):
        out, updated = model.apply(
            {"params": p, "batch_stats": s}, xb, train=True,
            mutable=["batch_stats"],
        )
        return out, updated["batch_stats"]

    f = jax.jit(
        jax.shard_map(
            fwd, mesh=mesh,
            in_specs=(P(), P(), P(hvd.WORLD_AXIS)),
            out_specs=(P(hvd.WORLD_AXIS), P()),
            check_vma=False,
        )
    )
    out_sharded, stats_sharded = f(params, stats, x)

    # single-device reference: identical net with a plain (unsynced)
    # BatchNorm over the full global batch — same leaf names
    # (scale/bias, mean/var), module key renamed across the trees
    class NetRef(nn.Module):
        @nn.compact
        def __call__(self, x, train=True):
            x = nn.Dense(4)(x)
            x = nn.BatchNorm(use_running_average=not train)(x)
            return x

    def renamed(tree):
        return {
            ("BatchNorm_0" if k == "SyncBatchNorm_0" else k): v
            for k, v in tree.items()
        }

    out_ref, updated_ref = NetRef().apply(
        {"params": renamed(params), "batch_stats": renamed(stats)}, x,
        train=True, mutable=["batch_stats"],
    )
    np.testing.assert_allclose(
        np.asarray(out_sharded), np.asarray(out_ref), rtol=1e-4, atol=1e-5
    )
    np.testing.assert_allclose(
        np.asarray(stats_sharded["SyncBatchNorm_0"]["mean"]),
        np.asarray(updated_ref["batch_stats"]["BatchNorm_0"]["mean"]),
        rtol=1e-4, atol=1e-6,
    )


def test_object_state_commit_restore(hvd_module):
    state = ObjectState(epoch=0, batch=0)
    state.epoch = 5
    state.commit()
    state.epoch = 9
    state.restore()
    assert state.epoch == 5


def test_array_state_save_restore(hvd_module):
    params = {"w": jnp.ones((2, 2))}
    state = ArrayState(params=params, epoch=1)
    state.params = jax.tree.map(lambda a: a * 3, state.params)
    state.commit()
    state.params = jax.tree.map(lambda a: a * 7, state.params)
    state.restore()
    np.testing.assert_allclose(np.asarray(state.params["w"]), 3.0)
    assert state.epoch == 1


def test_array_state_leaves_a_sharded_attribute_to_each_process(
        monkeypatch):
    """In a job of several processes the attribute a sharded adapter
    carries is neither broadcast from rank 0 nor persisted by it (its
    shards live on the other processes' devices, and pickling it fails);
    in one process it is persisted like every other."""
    import pickle

    from horovod_tpu.elastic.remesh import ShardedZeroState

    state = ArrayState(params={"w": jnp.ones((2, 2))}, opt_state=None,
                       epoch=1)
    state.register_sharded("zero", ShardedZeroState(state))
    state.opt_state = (jnp.zeros((4,)),)
    state.save()
    assert set(pickle.loads(state._serialize())) == {
        "params", "opt_state", "epoch"}
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    blob = state._serialize()
    assert set(pickle.loads(blob)) == {"params", "epoch"}
    state.epoch, live = 7, state.opt_state
    assert state._deserialize(blob)
    assert state.epoch == 1 and state.opt_state is live


def test_elastic_run_retry_loop(hvd_module):
    """HorovodInternalError restores committed state and retries
    (reference elastic.py:151 run_fn)."""
    from horovod_tpu.elastic.run import run_fn

    calls = {"n": 0}
    state = ObjectState(step=0)

    def train(st):
        calls["n"] += 1
        if calls["n"] == 1:
            st.step = 99  # uncommitted progress, lost on failure
            raise hvd.HorovodInternalError("simulated peer failure")
        return st.step

    resets = {"n": 0}
    wrapped = run_fn(train, lambda: resets.__setitem__("n", resets["n"] + 1))
    result = wrapped(state)
    assert result == 0  # restored to committed value
    assert calls["n"] == 2 and resets["n"] == 1


def test_elastic_hosts_updated_continues(hvd_module):
    from horovod_tpu.elastic.run import run_fn

    calls = {"n": 0}
    state = ObjectState(step=0)

    def train(st):
        calls["n"] += 1
        if calls["n"] == 1:
            st.step = 42  # live progress survives a host update
            raise hvd.HostsUpdatedInterrupt()
        return st.step

    wrapped = run_fn(train, lambda: None)
    assert wrapped(state) == 42
    assert calls["n"] == 2


def test_broadcast_optimizer_state_and_variables_aliases(hvd_module):
    """broadcast_variables / broadcast_optimizer_state mirror the
    reference surfaces (tensorflow/functions.py:276,
    torch/functions.py:118) over optax pytrees."""
    import optax

    params = {"w": jnp.ones((4, 2))}
    tx = optax.adam(1e-3)
    state = tx.init(params)
    # single-controller broadcast: result equals input, full structure
    out = hvd.broadcast_optimizer_state(state, root_rank=0)
    assert jax.tree.structure(out) == jax.tree.structure(state)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(state)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b))
    v = hvd.broadcast_variables({"w": jnp.full((3,), 7.0)}, root_rank=0)
    np.testing.assert_allclose(np.asarray(v["w"]), 7.0)


class TestChunkedBroadcast:
    """Size-boundary contract (VERDICT r3 item 6): large payloads ride
    chunked flat-buffer device broadcasts, small ones the single-call
    path; array data never pickles on the large path."""

    @staticmethod
    def _spy(monkeypatch):
        from jax.experimental import multihost_utils

        calls = []

        def fake_bcast(x, is_source):
            calls.append(x)
            return x

        monkeypatch.setattr(
            multihost_utils, "broadcast_one_to_all", fake_bcast
        )
        return calls

    def test_small_tree_single_call(self, hvd_module, monkeypatch):
        from horovod_tpu import functions
        from horovod_tpu.runtime import get_runtime

        calls = self._spy(monkeypatch)
        monkeypatch.setattr(get_runtime(), "process_count", 2)
        params = {"w": np.ones((4, 4), np.float32)}
        out = functions.broadcast_parameters(params, root_rank=0)
        # plan header + whole tree in one call
        assert len(calls) == 2
        np.testing.assert_allclose(out["w"], params["w"])

    def test_large_tree_chunks_and_never_pickles(self, hvd_module,
                                                 monkeypatch):
        from horovod_tpu import functions
        from horovod_tpu.runtime import get_runtime

        calls = self._spy(monkeypatch)
        monkeypatch.setattr(get_runtime(), "process_count", 2)
        monkeypatch.setenv("HVD_TPU_BCAST_PICKLE_THRESHOLD", "1024")
        monkeypatch.setenv("HVD_TPU_BCAST_CHUNK_BYTES", "65536")

        def no_pickle(*a, **k):
            raise AssertionError("array payload must not pickle")

        monkeypatch.setattr(functions.pickle, "dumps", no_pickle)
        params = {
            "w": np.arange(40_000, dtype=np.float32).reshape(200, 200),
            "b": np.ones((7,), np.int32),
        }
        out = functions.broadcast_parameters(params, root_rank=0)
        # plan header + 160_000 B f32 at 65536 B chunks -> 3, + 1 i32 chunk
        assert len(calls) == 5, [np.asarray(c).nbytes for c in calls]
        assert all(np.asarray(c).ndim == 1 for c in calls)
        np.testing.assert_allclose(out["w"], params["w"])
        np.testing.assert_allclose(out["b"], params["b"])

    def test_wide_dtypes_stay_bit_exact_via_pickle(self, hvd_module,
                                                   monkeypatch):
        """64-bit leaves must NOT ride the device path (x64-disabled JAX
        would truncate them in flight); they pickle bit-exactly."""
        from horovod_tpu import functions
        from horovod_tpu.runtime import get_runtime

        calls = self._spy(monkeypatch)
        monkeypatch.setattr(get_runtime(), "process_count", 2)
        monkeypatch.setenv("HVD_TPU_BCAST_PICKLE_THRESHOLD", "1024")
        big = np.array([2**40 + 3, -(2**35)], np.int64)
        params = {
            "w": np.arange(64_000, dtype=np.float32),
            "wide": big,
            "dbl": np.array([1.0 + 2**-40], np.float64),
        }
        out = functions.broadcast_parameters(params, root_rank=0)
        assert out["wide"].dtype == np.int64
        np.testing.assert_array_equal(out["wide"], big)
        assert out["dbl"].dtype == np.float64
        assert out["dbl"][0] == params["dbl"][0]  # bit-exact
        np.testing.assert_allclose(out["w"], params["w"])
        # wide leaves went via pickled broadcast_object (u8 buffers),
        # never as raw 64-bit device arrays
        for c in calls:
            leaves = np.asarray(c) if not isinstance(c, dict) else None
            if leaves is not None and leaves.dtype.itemsize > 4:
                # the only allowed 8-byte items are tiny int64 metadata
                # headers (plan negotiation / broadcast_object length),
                # never array payload
                assert leaves.dtype == np.int64 and leaves.size <= 3, (
                    leaves.dtype, leaves.shape,
                )

    def test_large_object_buffer_chunks(self, hvd_module, monkeypatch):
        from horovod_tpu import functions
        from horovod_tpu.runtime import get_runtime

        calls = self._spy(monkeypatch)
        monkeypatch.setattr(get_runtime(), "process_count", 2)
        monkeypatch.setenv("HVD_TPU_BCAST_PICKLE_THRESHOLD", "1024")
        monkeypatch.setenv("HVD_TPU_BCAST_CHUNK_BYTES", "65536")
        blob = {"x": b"q" * 200_000}
        out = functions.broadcast_object(blob, root_rank=0)
        assert out == blob
        # 1 length call + ceil(~200k/65536)=4 buffer chunks
        assert len(calls) == 5, [np.asarray(c).size for c in calls]


@pytest.mark.integration
@pytest.mark.multiproc
def test_multiprocess_chunked_broadcast_parameters():
    """Two real processes: a large (above-threshold) pytree must reach
    rank 1 bit-correct through the chunked device path, 64-bit leaves
    through the pickle path."""
    import sys

    import cloudpickle

    import horovod_tpu.runner as runner

    def worker():
        import numpy as np

        import horovod_tpu as hvd
        from horovod_tpu import functions

        hvd.init()
        rng = np.random.RandomState(0)  # same seed: root value known
        big = rng.randn(300_000).astype(np.float32)   # 1.2 MB > 1 MB
        wide = np.array([2**40 + 7, -(2**33)], np.int64)
        if hvd.process_rank() == 0:
            params = {"big": big, "wide": wide}
        else:
            params = {"big": np.zeros_like(big),
                      "wide": np.zeros_like(wide)}
        out = functions.broadcast_parameters(params, root_rank=0)
        ok_big = bool(np.allclose(np.asarray(out["big"]), big))
        ok_wide = bool((np.asarray(out["wide"]) == wide).all())
        return [ok_big, ok_wide]

    cloudpickle.register_pickle_by_value(sys.modules[__name__])
    results = runner.run(worker, np=2, use_cpu_devices=True)
    assert results == [[True, True], [True, True]], results
