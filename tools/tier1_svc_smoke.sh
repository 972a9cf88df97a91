#!/usr/bin/env bash
# Async exchange-service smoke: a 4-process CPU run on a forced 2x4
# topology must prove the three acceptance properties of the svc/
# subsystem end to end:
#
#   1. HVD_TPU_SVC=on with staleness=0 produces f32 dense losses
#      bitwise identical to =off (per process AND across processes) —
#      the traced-producer path only adds ResponseCache bookkeeping;
#   2. repeated-step programs hit the ResponseCache (nonzero
#      svc.cache_hit) with zero re-lowering on the repeat;
#   3. a staleness=1 run converges on the quadratic-bowl property test
#      while overlapping at least one DCN hop into a later step
#      (nonzero svc.overlap_steps on the simulated 2x4 mesh).
#
# Each of the 4 worker processes runs its own 8-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop): the assertions cover svc on==off inside every process
# AND bitwise agreement of the on-path trajectories across all 4
# (submission, negotiation and caching are deterministic).
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export HVD_TPU_TOPO=2x4
# the worker file lives in /tmp: put the repo root on the path
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_svc_smoke.XXXXXX.py)"
trap 'rm -rf "$WORKER" "$WORKER".out.*' EXIT

cat > "$WORKER" <<'EOF'
import json
import sys

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import metrics, sched, svc

hvd.init()

rng = np.random.RandomState(7)
X = rng.randn(32, 64).astype(np.float32)
Y = (X @ rng.randn(64, 8).astype(np.float32)).astype(np.float32)


def loss_fn(p, b):
    x, y = b
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


def params():
    r = np.random.RandomState(3)
    return {
        "w1": jnp.asarray(r.randn(64, 128).astype(np.float32) * 0.05),
        "b1": jnp.zeros((128,)),
        "w2": jnp.asarray(r.randn(128, 8).astype(np.float32) * 0.05),
    }


def train(svc_on, iters=8):
    svc.set_enabled_override(svc_on)
    svc.set_staleness_override(0)
    sched.set_config_override(sched.SchedConfig(
        bucket_bytes=16 * 1024,
    ))
    try:
        p = params()
        tx = hvd.DistributedOptimizer(optax.sgd(0.05))
        step = hvd.distributed_train_step(loss_fn, tx)
        st = step.init(p)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        losses = []
        for _ in range(iters):
            p, st, loss = step(p, st, batch)
            losses.append(float(loss))
        return losses
    finally:
        sched.set_config_override(None)
        svc.set_staleness_override(None)
        svc.set_enabled_override(None)


# --- 1. svc on == off, bitwise, at staleness 0 ----------------------
off = train(False)
on = train(True)
assert off == on, f"svc on != off (bitwise): {on} vs {off}"
assert metrics.get_counter("svc.submits") > 0, "service never submitted"

# --- 2. repeat programs hit the ResponseCache, zero re-lowering -----
s = svc.get_service()
from horovod_tpu import xir  # noqa: E402

prog = xir.program("dense_grad", [
    xir.all_reduce("hvd", reduce="mean", nbytes=256, dtype="float32"),
])
x = jnp.asarray(rng.randn(8, 64).astype(np.float32))
cold = np.asarray(s.submit(prog, [x]).result(timeout=60)[0])
lowerings = metrics.get_counter("svc.lowerings")
warm = np.asarray(s.submit(prog, [x]).result(timeout=60)[0])
assert metrics.get_counter("svc.cache_hit") > 0, "no cache hit"
assert metrics.get_counter("svc.lowerings") == lowerings, \
    "repeat submission re-lowered"
assert (cold == warm).all(), "cache hit diverged from cold path"
cache_hits = metrics.get_counter("svc.cache_hit")

# --- 3. staleness=1: quadratic bowl converges, hops overlap ---------
svc.set_enabled_override(True)
svc.set_staleness_override(1)


def bowl(p, b):
    return jnp.sum((p["w"] - 3.0) ** 2) + 0.0 * jnp.sum(b)


tx = hvd.DistributedOptimizer(optax.sgd(0.2))
step = hvd.distributed_train_step(bowl, tx)
assert isinstance(step, svc.StaleTrainStep), type(step)
sp, st = step.init({"w": jnp.zeros((8,), jnp.float32)})
batch = jnp.zeros((8, 1), jnp.float32)
stale_losses = []
for _ in range(40):
    sp, st, loss = step(sp, st, batch)
    stale_losses.append(float(loss))
assert stale_losses[-1] < 1e-6, f"bowl did not converge: {stale_losses[-1]}"
final = step.consolidate(sp)
assert np.allclose(np.asarray(final["w"]), 3.0, atol=1e-3)
overlap = metrics.get_counter("svc.overlap_steps")
assert overlap > 0, "no DCN hop overlapped a later step"
step.drain()
svc.set_staleness_override(None)
svc.set_enabled_override(None)

json.dump({"losses": on, "cache_hits": cache_hits,
           "overlap_steps": overlap,
           "stale_final": stale_losses[-1]}, sys.stdout)
EOF

pids=()
for i in 0 1 2 3; do
    python "$WORKER" > "$WORKER.out.$i" &
    pids+=($!)
done
for pid in "${pids[@]}"; do
    wait "$pid"
done

python - "$WORKER" <<'EOF'
import json
import sys

worker = sys.argv[1]
results = [json.load(open(f"{worker}.out.{i}")) for i in range(4)]
vals = [r["losses"] for r in results]
assert all(v == vals[0] for v in vals), \
    f"svc-on trajectories diverged across processes: {vals}"
assert all(r["cache_hits"] > 0 for r in results), results
assert all(r["overlap_steps"] > 0 for r in results), results
print(f"svc smoke OK x 4 procs: final loss {vals[0][-1]:.6f} "
      f"(on==off bitwise), {results[0]['cache_hits']} cache hits, "
      f"staleness=1 bowl -> {results[0]['stale_final']:.2e} with "
      f"{results[0]['overlap_steps']} overlapped DCN hops")
EOF
echo "SVC SMOKE OK"
