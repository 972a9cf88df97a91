"""The one traffic generator: it reads a mix's parameters
(``traffic/<name>.json``) and the configuration's ``element`` (what a row
is) and supplies batches on the mesh, all drawn from ``--seed``.

Parameters of a mix:

* ``rows``            rows of a batch in all (over every chip of the cell)
* ``seq_len``         tokens a row (token elements only)
* ``documents``       optional; token rows are then documents of random
                      length packed by ``horovod_tpu.data.packing`` (the
                      system's input layer) and a batch is ``(tokens,
                      segment_ids)``.  ``length: lognormal`` with ``mu``,
                      ``sigma``, ``min``, ``max``.
* ``supply``          ``device_ring``: ``ring`` batches are put on the
                      device in set-up and served round-robin, so the input
                      layer is bypassed; ``host_stream``: a host thread
                      makes every batch anew and keeps ``prefetch`` of them
                      ahead on the device, so the input layer is live.

Without ``documents`` a ring is made on the device itself; everything else
is made on the host with numpy and placed with ``jax.device_put``.
"""

from __future__ import annotations

import dataclasses
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Tuple

import numpy as np


@dataclasses.dataclass(frozen=True)
class Work:
    """What one batch asks of the model (see ``ops/``): ``units`` are its
    non-padding tokens or its images, ``sum_sq`` the sum of its documents'
    squared lengths, ``positions`` its rows x seq_len with the padding."""

    units: float
    sum_sq: float
    positions: int


def token_work(segment_ids: np.ndarray) -> Work:
    """Work of packed rows, from the documents' real lengths."""
    lengths = np.concatenate([
        np.bincount(row[row > 0])[1:] for row in np.asarray(segment_ids)
    ]).astype(np.float64)
    return Work(float(lengths.sum()), float(np.square(lengths).sum()),
                int(np.asarray(segment_ids).size))


class Traffic:
    """Batches of one mix for one configuration on one mesh."""

    def __init__(self, mix: Dict[str, Any], element: Dict[str, Any],
                 mesh, axis: str, seed: int):
        from jax.sharding import NamedSharding, PartitionSpec as P

        self.mix = mix
        self.element = element
        self.rows = int(mix["rows"])
        self.seed = int(seed)
        # Rows go to the chips the way chip_smoke.train places them, so the
        # step keeps one argument signature.
        self.sharding = NamedSharding(mesh, P(axis))
        chips = mesh.devices.size
        if self.rows % chips:
            raise ValueError(
                f"{self.rows} rows do not divide over {chips} chips")
        self.tokens = element["kind"] == "tokens"
        self.packed = "documents" in mix
        if self.packed and not self.tokens:
            raise ValueError("only token rows can be documents")
        if mix["supply"] not in ("device_ring", "host_stream"):
            raise ValueError(f"unknown supply {mix['supply']!r}")
        self.served = 0
        self.wait_seconds: List[float] = []
        self._ring: List[Tuple[Any, Work]] = []
        self._queue: "queue.Queue" = queue.Queue(
            maxsize=int(mix.get("prefetch", 2)))
        self._stop = threading.Event()
        self._thread = None
        self._error: Optional[BaseException] = None

    # ------------------------------------------------------------ host
    def _documents(self, rng: np.random.Generator) -> Iterator[np.ndarray]:
        d = self.mix["documents"]
        if d["length"] != "lognormal":
            raise ValueError(f"unknown length law {d['length']!r}")
        vocab = self.element["vocab_size"]
        while True:
            n = int(np.clip(rng.lognormal(d["mu"], d["sigma"]),
                            d["min"], d["max"]))
            yield rng.integers(0, vocab, n, dtype=np.int32)

    def host_batches(self, stream: int, rows: int) -> Iterator[Tuple[Any,
                                                                    Work]]:
        """Batches of ``rows`` rows made with numpy, without end.
        ``stream`` separates the draws of different uses of one seed."""
        rng = np.random.default_rng([self.seed, stream])
        if self.packed:
            from horovod_tpu.data.packing import pack_batches

            for tokens, segments in pack_batches(
                    self._documents(rng), self.mix["seq_len"], rows):
                yield (tokens, segments), token_work(segments)
        elif self.tokens:
            t = self.mix["seq_len"]
            work = self._uniform_work(rows)
            while True:
                yield rng.integers(0, self.element["vocab_size"], (rows, t),
                                   dtype=np.int32), work
        else:
            size = self.element["image_size"]
            work = self._uniform_work(rows)
            while True:
                images = rng.random((rows, size, size, 3), dtype=np.float32)
                labels = rng.integers(0, self.element["num_classes"], rows,
                                      dtype=np.int32)
                yield (images, labels), work

    def _uniform_work(self, rows: int) -> Work:
        """Work of ``rows`` full rows: every row one document of seq_len
        tokens, or one image."""
        if not self.tokens:
            return Work(float(rows), 0.0, rows)
        t = self.mix["seq_len"]
        return Work(float(rows * t), float(rows * t * t), rows * t)

    def sample(self, rows: int):
        """``rows`` rows for the reference check: the mix's own kind of
        rows, from a stream of the seed that no served batch uses."""
        batch, _ = next(self.host_batches(stream=1, rows=rows))
        return batch

    def place(self, batch):
        import jax

        return jax.device_put(batch, self.sharding)

    # ---------------------------------------------------------- device
    def _device_ring(self, n: int) -> List[Tuple[Any, Work]]:
        """``n`` batches of uniform rows made on the device."""
        import jax
        import jax.numpy as jnp

        rows = self.rows
        work = self._uniform_work(rows)
        if self.tokens:
            t = self.mix["seq_len"]

            def make(key):
                return jax.random.randint(
                    key, (rows, t), 0, self.element["vocab_size"], jnp.int32)
        else:
            size = self.element["image_size"]

            def make(key):
                k1, k2 = jax.random.split(key)
                return (
                    jax.random.uniform(k1, (rows, size, size, 3),
                                       jnp.float32),
                    jax.random.randint(k2, (rows,), 0,
                                       self.element["num_classes"],
                                       jnp.int32),
                )

        make = jax.jit(make, out_shardings=self.sharding)
        base = jax.random.fold_in(jax.random.PRNGKey(self.seed), 2)
        return [(make(jax.random.fold_in(base, i)), work) for i in range(n)]

    # ---------------------------------------------------------- supply
    def start(self) -> None:
        """Set-up: fill the ring, or start the thread and wait until it is
        ``prefetch`` batches ahead."""
        if self.mix["supply"] == "device_ring":
            n = int(self.mix["ring"])
            if self.packed:
                source = self.host_batches(stream=0, rows=self.rows)
                self._ring = [(self.place(b), w) for b, w in
                              (next(source) for _ in range(n))]
            else:
                self._ring = self._device_ring(n)
            return

        def produce():
            try:
                for batch, work in self.host_batches(stream=0,
                                                     rows=self.rows):
                    item = (self.place(batch), work)
                    while not self._stop.is_set():
                        try:
                            self._queue.put(item, timeout=0.1)
                            break
                        except queue.Full:
                            continue
                    if self._stop.is_set():
                        return
            except Exception as e:  # handed to the loop by _alive()
                self._error = e

        self._thread = threading.Thread(
            target=produce, name="bench_traffic", daemon=True)
        self._thread.start()
        while not self._queue.full():
            self._alive()
            time.sleep(0.005)

    def _alive(self) -> None:
        if not self._thread.is_alive():
            raise RuntimeError(
                "the traffic thread has ended") from self._error

    def next(self) -> Tuple[Any, Work]:
        """The next batch, on the device, and its work.  The seconds this
        call waited are kept in ``wait_seconds``."""
        t0 = time.perf_counter()
        if self._ring:
            item = self._ring[self.served % len(self._ring)]
        else:
            while True:
                try:
                    item = self._queue.get(timeout=1.0)
                    break
                except queue.Empty:
                    self._alive()
        self.wait_seconds.append(time.perf_counter() - t0)
        self.served += 1
        return item

    def close(self) -> None:
        """Stop the thread and wait until it has ended."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30)
            if self._thread.is_alive():
                raise RuntimeError("the traffic thread did not stop")
            self._thread = None
        self._ring = []
