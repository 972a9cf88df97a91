#!/usr/bin/env python3
"""Bring-up smoke: does the system still start, compile and step on the chip?

    python chip_smoke.py        # from the repo root; one process drives
                                # every local chip

GPT-2-small as ``models/transformer.py`` defines it, 16 rows of 1024
tokens per chip, through the entry points a user calls — ``hvd.init()``
-> ``hvd.DistributedOptimizer(optax.adamw, Compression.bf16)`` ->
``hvd.distributed_train_step`` — with no ``HVD_TPU_*`` variable set,
five steps, each ended by ``block_until_ready``.  Before that every
Pallas kernel that can run on a TPU runs once against its ``jax.numpy``
reference at the shapes the model uses.  With more than one chip it also
checks that the same global batch gives the same losses on one chip and
on all of them.

It proves that the program runs and is right, not how fast it is: the
seconds it prints are observations of one run, not a benchmark.  Nothing
is caught and carried past: a failed check ends the process with a
non-zero code, and the last line of standard output —
``{"ok": true, "device": {...}}`` — is printed only when every section
passed.  Without a TPU ``main()`` exits non-zero before it trains
anything.

The body (:func:`train`, :func:`parity`, :func:`kernels`) is a function
of the model and the batch size, so tier-1 calls the same code with
``gpt_tiny`` on the CPU mesh (tests/test_smoke.py); the device gate
lives in :func:`main`.
"""

from __future__ import annotations

import json
import sys
import time
from importlib import metadata

STEPS = 5
BATCH_PER_CHIP = 16
SEQ_LEN = 1024
# Elements of one default gradient bucket (HVD_TPU_FUSION_THRESHOLD,
# 64 MiB of float32).
BUCKET_ELEMS = 16 * 1024 * 1024
# bfloat16 machine epsilon: what "agrees to bf16 tolerance" means for a
# loss computed with bf16 activations and a bf16 gradient wire.
BF16_EPS = 2.0 ** -8

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
_CACHE_MISS = "/jax/compilation_cache/cache_misses"
_MOSAIC_CALL = "tpu_custom_call"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def say(key: str, value) -> None:
    print(f"{key}: {value}", flush=True)


class CompileLog:
    """Every XLA program this process builds while the block is open,
    from jax's own monitoring events: the seconds of each backend
    compile (a persistent-cache read counts as one, it is just short),
    and how many programs the persistent cache served (``reads``) or
    took in after a compile long enough to keep (``writes``)."""

    def __init__(self):
        self.builds: list = []
        self.reads = 0
        self.writes = 0

    def _on_duration(self, event: str, seconds: float, **_) -> None:
        if event == _BACKEND_COMPILE:
            self.builds.append(seconds)

    def _on_event(self, event: str, **_) -> None:
        if event == _CACHE_HIT:
            self.reads += 1
        elif event == _CACHE_MISS:  # recorded when the entry is written
            self.writes += 1

    def __enter__(self) -> "CompileLog":
        import jax.monitoring as mon

        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring as mon

        mon.unregister_event_duration_listener(self._on_duration)
        mon.unregister_event_listener(self._on_event)


def _max_err(got, want) -> float:
    """Largest absolute error, in units of the reference's largest
    magnitude (at least 1)."""
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.max(np.abs(got - want)) / max(1.0, np.max(np.abs(want))))


def _document_mix(rows: int, seq_len: int, vocab: int):
    """``bench.py``'s packed-document mix (lognormal lengths, mean about
    420 tokens at seq_len 1024, in proportion below that), packed into
    ``rows`` rows."""
    import numpy as np

    from horovod_tpu.data.packing import pack_documents

    rng = np.random.RandomState(3)
    unit = seq_len / 1024
    docs, full_rows = [], 0
    while full_rows < rows + 2:
        n = int(np.clip(rng.lognormal(5.8, 0.7) * unit, 32 * unit, seq_len))
        docs.append(rng.randint(0, vocab, n).astype(np.int32))
        full_rows = sum(len(d) for d in docs) // seq_len
    tokens, segments = pack_documents(docs, seq_len)
    return tokens[:rows], segments[:rows]


def kernels(batch: int, seq_len: int, heads: int, head_dim: int,
            bucket_elems: int, *, on_tpu: bool) -> None:
    """Each Pallas kernel once, against its ``jax.numpy`` reference:
    flash attention dense and packed (forward and gradient, bf16) and
    the scale/cast kernel on a buffer the size of a gradient bucket."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from horovod_tpu.ops import pallas_kernels
    from horovod_tpu.ops.pallas_kernels import (
        cast_buffer,
        flash_attention,
        scale_buffer,
    )
    from horovod_tpu.parallel.ring_attention import full_attention

    require(pallas_kernels._interpret() is (not on_tpu),
            f"_interpret() is {pallas_kernels._interpret()} with "
            f"on_tpu={on_tpu}")

    shape = (batch, seq_len, heads, head_dim)
    # q, k, v and the cotangent weights w
    qkvw = tuple(
        jax.random.normal(key, shape, jnp.float32).astype(jnp.bfloat16)
        for key in jax.random.split(jax.random.PRNGKey(7), 4)
    )
    _, seg_np = _document_mix(batch, seq_len, vocab=1000)
    seg = jnp.asarray(seg_np)
    say("kernels.packed_documents_per_row",
        round(float(np.mean(seg_np.max(axis=1))), 2))

    def value_and_grads(attn, q, k, v, w, segment_ids):
        def loss(q, k, v):
            out = attn(q, k, v, causal=True, segment_ids=segment_ids)
            return jnp.sum(out.astype(jnp.float32) * w), out

        return jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(
            q, k, v)

    def flash(q, k, v, w, segment_ids):
        return value_and_grads(flash_attention, q, k, v,
                               w.astype(jnp.float32), segment_ids)

    def reference(q, k, v, w, segment_ids):
        with jax.default_matmul_precision("highest"):
            return value_and_grads(
                full_attention,
                *(x.astype(jnp.float32) for x in (q, k, v, w)),
                segment_ids,
            )

    for name, segment_ids in (("dense", None), ("packed", seg)):
        (_, out), grads = jax.jit(flash)(*qkvw, segment_ids)
        (_, ref_out), ref_grads = jax.jit(reference)(*qkvw, segment_ids)
        require(out.shape == shape and out.dtype == jnp.bfloat16,
                f"flash {name}: output is {out.dtype}{out.shape}")
        errs = {"forward": _max_err(out, ref_out)}
        for g_name, g, g_ref in zip("qkv", grads, ref_grads):
            errs[f"d{g_name}"] = _max_err(g, g_ref)
        say(f"kernels.flash_{name}.max_err",
            {n: round(e, 5) for n, e in errs.items()})
        for n, e in errs.items():
            # the tolerance tests/test_pallas_kernels.py holds bf16 to
            require(np.isfinite(e) and e <= 2e-2,
                    f"flash {name} {n}: max error {e} against "
                    "full_attention exceeds 2e-2")

    x = jax.random.normal(jax.random.PRNGKey(8), (bucket_elems,),
                          jnp.float32)
    xb = x.astype(jnp.bfloat16)
    for name, got, want in (
        ("scale_buffer f32", jax.jit(lambda a: scale_buffer(a, 0.25))(x),
         x * 0.25),
        ("cast_buffer f32->bf16",
         jax.jit(lambda a: cast_buffer(a, jnp.bfloat16))(x), xb),
        ("cast_buffer bf16->f32",
         jax.jit(lambda a: cast_buffer(a, jnp.float32))(xb),
         xb.astype(jnp.float32)),
    ):
        require(got.dtype == want.dtype and got.shape == want.shape,
                f"{name}: got {got.dtype}{got.shape}")
        require(bool(jnp.array_equal(got, want)),
                f"{name}: differs from the jax.numpy reference")
    say("kernels.scale_cast", f"exact on {bucket_elems} elements")


def train(hvd, model, rows: int, seq_len: int, steps: int, *,
          on_tpu: bool) -> dict:
    """``steps`` train steps of ``model`` on one fixed batch of ``rows``
    x ``seq_len`` tokens over the initialized runtime's devices, through
    ``DistributedOptimizer`` -> ``distributed_train_step`` with default
    knobs.  Checks what the bring-up claims (see the requires below) and
    returns the report."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from horovod_tpu import metrics
    from horovod_tpu.models.transformer import token_cross_entropy
    from horovod_tpu.prof import introspect

    n = hvd.size()
    require(rows % n == 0, f"{rows} rows do not divide over {n} devices")
    tokens = jax.random.randint(
        jax.random.PRNGKey(2), (rows, seq_len), 0, model.cfg.vocab_size,
        jnp.int32,
    )
    params = model.init(jax.random.PRNGKey(0), tokens[:1])
    params = hvd.broadcast_parameters(params, root_rank=0)
    # The batch goes onto the mesh once: each chip keeps its shard,
    # instead of chip 0 re-sending the global batch every step.
    batch = jax.device_put(
        tokens, NamedSharding(hvd.mesh(), P(hvd.WORLD_AXIS))
    )
    tx = hvd.DistributedOptimizer(
        optax.adamw(3e-4), compression=hvd.Compression.bf16
    )

    def loss_fn(p, b):
        logits, aux = model.apply(p, b)
        return token_cross_entropy(
            logits, jnp.roll(b, -1, axis=-1)
        ) + 0.01 * aux

    step = hvd.distributed_train_step(loss_fn, tx)
    opt_state = step.init(params)

    program = "train_step_0"  # the first variant a TrainStep compiles
    before = introspect.get(program) or {"compiles": 0,
                                         "compile_seconds": 0.0}
    fallbacks_before = metrics.get_counter("prof.fallbacks")
    losses, seconds, builds = [], [], []
    with CompileLog() as log:
        for _ in range(steps):
            built = len(log.builds)
            t0 = time.perf_counter()
            params, opt_state, loss = step(params, opt_state, batch)
            jax.block_until_ready((params, opt_state, loss))
            seconds.append(time.perf_counter() - t0)
            builds.append(len(log.builds) - built)
            losses.append(float(loss))
        from_cache = log.reads > 0 and log.writes == 0
    after = introspect.get(program)
    require(after is not None, "the profiling plane is off (HVD_TPU_PROF)")
    report = {
        "devices": n,
        "losses": losses,
        "step_seconds": [round(s, 3) for s in seconds],
        "xla_builds_per_step": builds,
        "step_compiles": after["compiles"] - before["compiles"],
        "step_compile_seconds": round(
            after["compile_seconds"] - before["compile_seconds"], 2),
        "step_from_compile_cache": from_cache,
        # argument + output + temporary bytes per device, as XLA's
        # memory analysis of the compiled step states them
        "step_hbm_bytes_xla": after["peak_hbm_bytes"],
        "prof_fallbacks":
            metrics.get_counter("prof.fallbacks") - fallbacks_before,
    }

    require(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    require(losses[-1] < losses[0],
            f"loss did not fall on a fixed batch: {losses}")
    require(report["step_compiles"] == 1,
            f"the step compiled {report['step_compiles']} times for one "
            "shape")
    require(builds[0] >= 1 and sum(builds[1:]) == 0,
            f"XLA built programs after the first step: {builds}")
    require(report["prof_fallbacks"] == 0,
            f"prof.fallbacks == {report['prof_fallbacks']}")

    # Placement: a full replica of every parameter and one batch shard
    # on every device — not everything on the first.
    for leaf in jax.tree.leaves(params):
        require(leaf.sharding.is_fully_replicated
                and len(leaf.sharding.device_set) == n,
                f"a parameter is not replicated over {n} devices: "
                f"{leaf.sharding}")
    shard_rows = sorted(s.data.shape[0] for s in batch.addressable_shards)
    require(shard_rows == [rows // n] * n,
            f"batch shards hold {shard_rows} rows, expected "
            f"{n} x {rows // n}")

    # Memory on every chip, not on the first: what is live now (params,
    # optimizer state, a batch shard) must be alike; the peak may differ
    # by chip 0's transient copy of the initial params, never by the
    # whole job.  (The CPU backend reports no memory statistics.)
    stats = [d.memory_stats() for d in hvd.mesh().devices.flat]
    if all(stats):
        in_use = report["bytes_in_use"] = [s["bytes_in_use"] for s in stats]
        peaks = report["peak_bytes_in_use"] = [
            s["peak_bytes_in_use"] for s in stats]
        require(min(in_use) >= 0.9 * max(in_use),
                f"per-chip live memory is not alike: {in_use}")
        require(min(peaks) >= 0.5 * max(peaks),
                f"per-chip peak memory is not alike: {peaks}")

    # The step that ran holds the Mosaic kernels (or, on the CPU mesh,
    # none: there the kernels are interpreted).
    (executor,) = step._step_cache.values()
    lowered = executor.lower(params, None, opt_state, batch).as_text()
    report["mosaic_calls"] = lowered.count(_MOSAIC_CALL)
    require((report["mosaic_calls"] > 0) == on_tpu,
            f"{report['mosaic_calls']} Mosaic custom calls in the lowered "
            f"step with on_tpu={on_tpu}")
    return report


def parity(hvd, model, rows: int, seq_len: int, steps: int,
           device_counts, *, on_tpu: bool) -> dict:
    """The same global batch on each of ``device_counts`` devices, in
    this one process: the losses must agree to bf16 tolerance.  Leaves
    the runtime shut down.  Returns ``{count: report}``."""
    import jax
    import numpy as np

    reports = {}
    for count in device_counts:
        hvd.init(devices=jax.devices()[:count])
        try:
            require(hvd.size() == count, f"hvd.size() == {hvd.size()}")
            reports[count] = train(hvd, model, rows, seq_len, steps,
                                   on_tpu=on_tpu)
        finally:
            hvd.shutdown()
    first, *others = (reports[c]["losses"] for c in device_counts)
    for count, losses in zip(device_counts[1:], others):
        require(np.allclose(losses, first, rtol=BF16_EPS, atol=0.0),
                f"losses on {count} devices {losses} differ from "
                f"{device_counts[0]} device(s) {first} beyond bf16 "
                f"tolerance {BF16_EPS}")
    return reports


def main() -> int:
    import jax

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        print(
            f"chip_smoke: jax found no TPU (platform={device.platform!r}, "
            f"{len(devices)} device(s)); nothing was trained.",
            file=sys.stderr,
        )
        return 2

    import os

    import horovod_tpu as hvd
    from horovod_tpu import native
    from horovod_tpu.models.transformer import gpt_small
    from horovod_tpu.prof import peak
    from horovod_tpu.topo import model as topo_model
    from horovod_tpu.utils import compile_cache

    n = len(devices)
    started = time.perf_counter()

    def lap(section: str) -> None:
        nonlocal started
        now = time.perf_counter()
        say(f"seconds.{section}", round(now - started, 1))
        started = now

    say("platform", device.platform)
    say("device_kind", device.device_kind)
    say("device_count", n)
    say("versions", ", ".join(
        f"{pkg} {metadata.version(pkg)}"
        for pkg in ("jax", "jaxlib", "libtpu", "flax", "optax")
    ))
    say("compile_cache_dir", compile_cache.enable())
    say("compile_cache_dir_from",
        "JAX_COMPILATION_CACHE_DIR" if os.environ.get(
            "JAX_COMPILATION_CACHE_DIR") else "<checkout>/.jax_cache")
    native.ensure_built()
    require(native.load() is not None, "native core built but not loadable")
    say("native_core", "built from horovod_tpu/cpp/src, loaded")
    resolved = peak.peak_tflops(device)
    say("peak_bf16_tflops", resolved)
    require(resolved is not None and resolved[1] == "table",
            f"peak source is {resolved}, expected the datasheet table")
    lap("native_build")

    model = gpt_small(max_len=SEQ_LEN)
    cfg = model.cfg
    kernels(BATCH_PER_CHIP, SEQ_LEN, cfg.num_heads, cfg.head_dim,
            BUCKET_ELEMS, on_tpu=True)
    lap("kernels")

    hvd.init()
    require(hvd.size() == n, f"hvd.size() == {hvd.size()}, {n} devices")
    report = train(hvd, model, BATCH_PER_CHIP * n, SEQ_LEN, STEPS,
                   on_tpu=True)
    for key, value in report.items():
        say(f"train.{key}", value)
    require("peak_bytes_in_use" in report,
            "the TPU backend reported no memory statistics")
    say("topology", topo_model.current())
    hvd.shutdown()
    lap("train")

    if n > 1:
        reports = parity(hvd, model, BATCH_PER_CHIP, SEQ_LEN, 3, (1, n),
                         on_tpu=True)
        for count, r in reports.items():
            say(f"parity.losses.{count}_chips", r["losses"])
        lap("parity")
    else:
        say("not_run", "placement_across_chips, memory_alike_across_chips, "
            "parity_one_chip_vs_all (need more than one chip)")

    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": n,
    }}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
