"""The traffic generator: everything from the seed, work counted from the
documents' real lengths, both kinds of supply."""

import jax
import numpy as np
import pytest

from benchmark import manifest
from benchmark.traffic import Traffic, Work, token_work

from tiny_cells import CHECKOUT, HERE

TOKENS = {"kind": "tokens", "vocab_size": 256}
IMAGES = {"kind": "images", "image_size": 16, "num_classes": 10}


def _mesh(n=1):
    return jax.sharding.Mesh(np.array(jax.devices()[:n]), ("world",))


def _mix(name):
    return manifest.load_json(HERE / "fixture" / "traffic" / f"{name}.json")


def _first(traffic, n):
    traffic.start()
    try:
        return [jax.tree.map(np.asarray, traffic.next()[0])
                for _ in range(n)]
    finally:
        traffic.close()


@pytest.mark.parametrize("mix, element", [
    ("ring-8x64", TOKENS), ("packed-docs-8x64", TOKENS),
    ("ring-8-images", IMAGES)])
def test_same_seed_same_batches_other_seed_other_batches(mix, element):
    def batches(seed):
        return _first(Traffic(_mix(mix), element, _mesh(), "world", seed), 3)

    a, b, c = batches(7), batches(7), batches(8)
    for x, y, z in zip(a, b, c):
        same = jax.tree.map(np.array_equal, x, y)
        other = jax.tree.map(np.array_equal, x, z)
        assert all(jax.tree.leaves(same))
        assert not all(jax.tree.leaves(other))


def test_ring_goes_round_and_shards_rows_over_the_chips():
    traffic = Traffic(_mix("ring-8x64"), TOKENS, _mesh(4), "world", 1)
    traffic.start()
    try:
        served = [traffic.next() for _ in range(4)]
    finally:
        traffic.close()
    assert served[3][0] is served[0][0]  # a ring of 3
    assert served[0][1] == Work(8 * 64, 8 * 64 * 64, 8 * 64)
    shards = served[0][0].addressable_shards
    assert sorted(s.data.shape for s in shards) == [(2, 64)] * 4
    assert len(traffic.wait_seconds) == traffic.served == 4


def test_packed_stream_counts_documents_and_stops_its_thread():
    traffic = Traffic(_mix("packed-docs-8x64"), TOKENS, _mesh(), "world", 3)
    traffic.start()
    thread = traffic._thread
    try:
        (tokens, segments), work = traffic.next()
    finally:
        traffic.close()
    assert not thread.is_alive()
    segments = np.asarray(segments)
    assert tokens.shape == segments.shape == (8, 64)
    assert work == token_work(segments)
    assert work.units == (segments > 0).sum() < work.positions == 8 * 64
    lengths = [np.sum(row == s) for row in segments
               for s in range(1, row.max() + 1)]
    assert work.sum_sq == sum(int(n) ** 2 for n in lengths)
    assert 4 <= min(lengths) and max(lengths) <= 64


def test_check_sample_is_not_a_served_batch():
    traffic = Traffic(_mix("ring-8x64"), TOKENS, _mesh(), "world", 1)
    sample = traffic.sample(8)
    assert sample.shape == (8, 64)
    assert np.array_equal(sample, traffic.sample(8))
    served = next(traffic.host_batches(stream=0, rows=8))[0]
    assert not np.array_equal(sample, served)


def test_a_dead_generator_is_an_error_with_its_cause():
    mix = dict(_mix("packed-docs-8x64"))
    mix["documents"] = dict(mix["documents"], length="uniform")
    traffic = Traffic(mix, TOKENS, _mesh(), "world", 1)
    with pytest.raises(RuntimeError, match="traffic thread") as error:
        traffic.start()
    assert "unknown length law" in str(error.value.__cause__)
    traffic.close()


@pytest.mark.parametrize("name", [
    p.stem for p in sorted((CHECKOUT / "benchmark/traffic").glob("*.json"))])
def test_real_mix_has_the_generators_parameters(name):
    mix = manifest.load_json(CHECKOUT / "benchmark/traffic" / f"{name}.json")
    assert mix["supply"] in ("device_ring", "host_stream")
    assert mix["rows"] > 0 and mix["what"]
    assert ("ring" in mix) == (mix["supply"] == "device_ring")
    if "documents" in mix:
        assert mix["documents"]["max"] <= mix["seq_len"]
