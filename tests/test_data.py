"""Data loaders + elastic sampler (reference
``horovod/data/data_loader_base.py`` and torch ElasticSampler tests)."""

import numpy as np
import pytest

import horovod_tpu as hvd
from horovod_tpu.data import (
    ArrayDataLoader,
    AsyncArrayDataLoader,
    ElasticSampler,
)


def _arrays(n=64, d=4):
    rng = np.random.RandomState(0)
    return [rng.randn(n, d).astype(np.float32), rng.randint(0, 3, size=n)]


def test_array_loader_batches(hvd_module):
    x, y = _arrays()
    loader = ArrayDataLoader([x, y], batch_size=8, shuffle=False)
    batches = list(loader)
    assert len(batches) == len(loader)
    xb, yb = batches[0]
    assert xb.shape == (8, 4) and yb.shape == (8,)
    # full epoch covers the shard exactly once
    seen = np.concatenate([b[1] for b in batches])
    assert len(seen) == len(loader) * 8


def test_array_loader_epoch_shuffle(hvd_module):
    x, y = _arrays()
    loader = ArrayDataLoader([x, y], batch_size=8, shuffle=True, seed=3)
    loader.set_epoch(0)
    e0 = np.concatenate([b[1] for b in loader])
    loader.set_epoch(1)
    e1 = np.concatenate([b[1] for b in loader])
    assert not np.array_equal(e0, e1)
    loader.set_epoch(0)
    again = np.concatenate([b[1] for b in loader])
    np.testing.assert_array_equal(e0, again)


def test_async_loader_matches_sync(hvd_module):
    x, y = _arrays()
    sync = ArrayDataLoader([x, y], batch_size=8, shuffle=False)
    async_ = AsyncArrayDataLoader([x, y], batch_size=8, shuffle=False)
    sb = [b[1] for b in sync]
    ab = [b[1] for b in async_]
    assert len(sb) == len(ab)
    for s, a in zip(sb, ab):
        np.testing.assert_array_equal(s, a)
    async_.close_async_loader()


def test_async_loader_close_midway(hvd_module):
    x, y = _arrays(n=128)
    loader = AsyncArrayDataLoader([x, y], batch_size=4, queue_size=2)
    it = iter(loader)
    next(it)
    loader.close_async_loader()  # must not hang


def test_async_loader_propagates_errors(hvd_module):
    from horovod_tpu.data import AsyncDataLoaderMixin

    x, y = _arrays(n=8)

    class Bad(ArrayDataLoader):
        def _iterate(self):
            yield (x[:2], y[:2])
            raise RuntimeError("boom")

    class AsyncBad(AsyncDataLoaderMixin, Bad):
        pass

    loader = AsyncBad([x, y], batch_size=2)
    with pytest.raises(RuntimeError, match="boom"):
        list(loader)


# ---- ElasticSampler ----------------------------------------------------

def test_elastic_sampler_full_coverage():
    s = ElasticSampler(dataset_size=20, shuffle=False, rank=0, num_replicas=2)
    s2 = ElasticSampler(dataset_size=20, shuffle=False, rank=1, num_replicas=2)
    assert sorted(list(s) + list(s2)) == list(range(20))
    assert len(s) == 10


def test_elastic_sampler_resume_skips_processed():
    s = ElasticSampler(dataset_size=16, shuffle=False, rank=0, num_replicas=2)
    first_two_batches = s.indices[:4]
    s.record_batch(0, 2)
    s.record_batch(1, 2)
    state = s.state_dict()

    s2 = ElasticSampler(dataset_size=16, shuffle=False, rank=0, num_replicas=2)
    s2.load_state_dict(state)
    remaining = set(s2) | set(
        ElasticSampler(dataset_size=16, shuffle=False, rank=1, num_replicas=2)
        .indices
    )
    for idx in first_two_batches:
        assert idx not in set(s2.indices)


def test_elastic_sampler_reshard_on_world_change():
    s = ElasticSampler(dataset_size=24, shuffle=True, seed=7, rank=0,
                       num_replicas=3)
    s.record_batch(0, 4)
    processed = set(s.processed_indices)
    # world shrinks 3 -> 2; remaining work redistributed
    s.reset(rank=0, num_replicas=2)
    other = ElasticSampler(dataset_size=24, shuffle=True, seed=7, rank=1,
                           num_replicas=2)
    other.load_state_dict({"epoch": 0,
                           "processed_indices": list(processed)})
    combined = set(s.indices) | set(other.indices)
    assert combined.isdisjoint(processed)
    # everything unprocessed is covered
    assert combined == set(range(24)) - processed


def test_elastic_sampler_pads_when_fewer_remaining_than_replicas():
    # 1 unprocessed index, 4 replicas: every rank must still get exactly
    # num_samples indices or collective step counts desynchronize.
    s0 = ElasticSampler(dataset_size=5, shuffle=False, rank=0, num_replicas=4)
    s0.load_state_dict({"epoch": 0, "processed_indices": [0, 1, 2, 3]})
    for r in range(4):
        s = ElasticSampler(dataset_size=5, shuffle=False, rank=r,
                           num_replicas=4)
        s.load_state_dict({"epoch": 0, "processed_indices": [0, 1, 2, 3]})
        assert list(s) == [4], (r, list(s))


def test_elastic_sampler_epoch_reset():
    s = ElasticSampler(dataset_size=10, shuffle=True, rank=0, num_replicas=1)
    s.record_batch(0, 5)
    assert len(s.processed_indices) == 5
    s.set_epoch(1)
    assert s.processed_indices == []
    assert len(s) == 10


class TestParquetStreamLoader:
    """Row-group streaming reader (petastorm data-loader analog,
    VERDICT r3 item 9): epochs stream bounded windows, never a shard."""

    @staticmethod
    def _write_parts(tmp_path, n_parts=3, rows=50, fmt="parquet"):
        from horovod_tpu.spark.store import write_shard

        rng = np.random.RandomState(0)
        paths, allx, ally = [], [], []
        for p in range(n_parts):
            x = rng.randn(rows, 4).astype(np.float32)
            y = rng.randn(rows).astype(np.float32)
            paths.append(write_shard(
                str(tmp_path / f"part-{p:05d}"),
                {"features": x, "label": y}, fmt=fmt,
            ))
            allx.append(x)
            ally.append(y)
        return paths, np.concatenate(allx), np.concatenate(ally)

    @pytest.mark.parametrize("fmt", ["parquet", "npz"])
    def test_streams_all_rows_exactly_once(self, tmp_path, fmt):
        from horovod_tpu.data import ParquetStreamLoader

        paths, X, Y = self._write_parts(tmp_path, fmt=fmt)
        loader = ParquetStreamLoader(
            paths, ["features", "label"], batch_size=16,
            shuffle=False, window_rows=16,  # window << shard
        )
        assert len(loader) == 150 // 16
        got_x, got_y = [], []
        for xb, yb in loader:
            assert xb.shape == (16, 4) and yb.shape == (16,)
            got_x.append(xb)
            got_y.append(yb)
        got_x = np.concatenate(got_x)
        # unshuffled stream preserves order; drop_last trims the tail
        np.testing.assert_allclose(got_x, X[: len(got_x)])
        np.testing.assert_allclose(np.concatenate(got_y), Y[: len(got_x)])

    def test_carry_across_windows_and_parts(self, tmp_path):
        """batch_size not dividing the window exercises the carry
        buffer across window AND part boundaries."""
        from horovod_tpu.data import ParquetStreamLoader

        paths, X, _ = self._write_parts(tmp_path, n_parts=2, rows=50)
        loader = ParquetStreamLoader(
            paths, ["features", "label"], batch_size=24,
            shuffle=False, window_rows=25,
        )
        batches = [xb for xb, _ in loader]
        assert len(batches) == len(loader) == 100 // 24
        np.testing.assert_allclose(np.concatenate(batches), X[:96])

    def test_shuffle_is_seeded_and_epoch_varying(self, tmp_path):
        from horovod_tpu.data import ParquetStreamLoader

        paths, X, _ = self._write_parts(tmp_path)

        def epoch_rows(epoch):
            # batch divides 150 exactly: no dropped tail, so each epoch
            # emits the same multiset and the permutation check holds
            loader = ParquetStreamLoader(
                paths, ["features", "label"], batch_size=15, seed=7,
                window_rows=32,
            )
            loader.set_epoch(epoch)
            return np.concatenate([xb for xb, _ in loader])

        a0, b0, a1 = epoch_rows(0), epoch_rows(0), epoch_rows(1)
        np.testing.assert_allclose(a0, b0)  # same epoch -> same stream
        assert not np.allclose(a0, a1)      # epochs reshuffle
        # windowed shuffle is still a permutation of the data it emits
        key = lambda m: sorted(map(tuple, np.round(m, 5)))
        assert key(a0) == key(a1)

    def test_async_wrapper_matches_sync(self, tmp_path):
        from horovod_tpu.data import (
            AsyncParquetStreamLoader,
            ParquetStreamLoader,
        )

        paths, _, _ = self._write_parts(tmp_path, n_parts=1)
        kw = dict(columns=["features", "label"], batch_size=10,
                  shuffle=False, window_rows=16)
        sync = ParquetStreamLoader(paths, **kw)
        asyn = AsyncParquetStreamLoader(paths, **kw)
        try:
            for (xs, _), (xa, _) in zip(sync, asyn):
                np.testing.assert_allclose(xs, xa)
        finally:
            asyn.close_async_loader()


# ------------------------------------------ the packer's own span (PR 36)
def _pack_docs(seed=0, n=120):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 50, int(k), dtype=np.int32)
            for k in rng.integers(3, 40, n)]


def _packed(level, *, on_suspend=None, **kw):
    """Every batch ``pack_batches`` gives at trace level ``level`` and the
    ``pack_window`` spans it left in the flight recorder."""
    from horovod_tpu import trace
    from horovod_tpu.data.packing import pack_batches

    trace.reset()
    trace.set_level_override(level)
    try:
        batches = []
        for batch in pack_batches(iter(_pack_docs()), seq_len=32,
                                  batch_size=4, **kw):
            if on_suspend is not None:
                on_suspend()
            batches.append(batch)
        spans = [r["spans"] for r in trace.get_recorder()._background
                 if r["spans"]["name"] == "pack_window"]
        return batches, spans
    finally:
        trace.set_level_override(None)
        trace.reset()


@pytest.mark.parametrize("drop_remainder", [True, False])
def test_pack_batches_spans_its_own_work_a_window(drop_remainder):
    from horovod_tpu import trace

    open_at_yield = []
    batches, spans = _packed(
        "summary", drop_remainder=drop_remainder,
        # the generator stands suspended: nothing is open on this thread
        on_suspend=lambda: open_at_yield.append(
            len(trace.get_tracer()._stack())))
    assert batches and open_at_yield == [0] * len(batches)
    # a window closes when it holds two batches' tokens; the last is
    # what is left
    docs, windows, held = _pack_docs(), 0, 0
    for d in docs:
        held += len(d)
        if held >= 2 * 4 * 32:
            windows, held = windows + 1, 0
    assert len(spans) == windows + (1 if held else 0)
    assert sum(s["attrs"]["docs"] for s in spans) == len(docs)
    assert sum(s["attrs"]["tokens"] for s in spans) == sum(
        len(d) for d in docs)
    rows = sum(s["attrs"]["rows"] for s in spans)
    full = 4 * (len(batches) - (0 if drop_remainder else 1))
    assert full <= rows < full + 4 + (0 if drop_remainder else 4)
    for s in spans:
        assert s["phase"] == "input" and s["dur"] >= 0.0
        assert set(s["attrs"]) == {"docs", "rows", "tokens"}
        assert "children" not in s


def test_pack_batches_makes_no_span_at_level_off_and_the_same_batches():
    on, spans_on = _packed("summary")
    off, spans_off = _packed("off")
    assert spans_on and not spans_off
    assert len(on) == len(off)
    for (t1, s1), (t2, s2) in zip(on, off):
        assert t1.dtype == t2.dtype == np.int32
        np.testing.assert_array_equal(t1, t2)
        np.testing.assert_array_equal(s1, s2)


def test_pack_batches_is_what_packing_each_window_by_hand_gives():
    """The reference: windows cut where the running total reaches two
    batches, each packed by ``pack_documents``, rows dealt out in order."""
    from horovod_tpu.data.packing import pack_documents

    docs, rows_t, rows_s, window = _pack_docs(), [], [], []
    for d in docs:
        window.append(d)
        if sum(len(w) for w in window) >= 2 * 4 * 32:
            t, s = pack_documents(window, 32)
            rows_t.extend(t), rows_s.extend(s)
            window = []
    t, s = pack_documents(window, 32)
    rows_t.extend(t), rows_s.extend(s)
    batches, _ = _packed("summary")
    assert len(batches) == len(rows_t) // 4
    for i, (bt, bs) in enumerate(batches):
        np.testing.assert_array_equal(bt, np.stack(rows_t[4 * i:4 * i + 4]))
        np.testing.assert_array_equal(bs, np.stack(rows_s[4 * i:4 * i + 4]))
