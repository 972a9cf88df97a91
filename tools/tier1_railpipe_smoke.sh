#!/usr/bin/env bash
# Rail-pipeliner smoke: a 4-process CPU run on a forced 2x4 topology
# must produce HVD_TPU_XIR_PIPELINE=on losses bitwise equal to =off
# for a hier multi-bucket training loop (the reorder-only contract),
# with a nonzero sched.pipeline.overlap_windows counter proving the
# per-rail chains actually engaged, and a ScheduleTuner that explores
# the pipeline knob (off -> on -> auto), freezes a winner, persists it
# in the tune DB (meta.pipeline), and warm-starts from it.
#
# Each of the 4 worker processes runs its own 8-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop): the assertions cover pipeline on==off inside every
# process AND bitwise agreement of the pipelined trajectories across
# all 4 processes (phase planning and rail chaining are
# deterministic).
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export HVD_TPU_TOPO=2x4
# the worker file lives in /tmp: put the repo root on the path
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_railpipe_smoke.XXXXXX.py)"
TUNEDIR="$(mktemp -d /tmp/hvd_tpu_railpipe_tune.XXXXXX)"
trap 'rm -rf "$WORKER" "$WORKER".out.* "$TUNEDIR"' EXIT
export HVD_TPU_RAILPIPE_SMOKE_TUNEDIR="$TUNEDIR"

cat > "$WORKER" <<'EOF'
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import metrics, sched
from horovod_tpu.xir import pipeline as railpipe

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
        "w1": jnp.asarray(r.randn(64, 256).astype(np.float32) * 0.05),
        "b1": jnp.zeros((256,)),
        "w2": jnp.asarray(r.randn(256, 8).astype(np.float32) * 0.05),
    }


def train(mode, iters=8):
    railpipe.set_mode_override(mode)
    sched.set_config_override(sched.SchedConfig(
        bucket_bytes=16 * 1024, lowering="hier",
    ))
    o0 = metrics.get_counter("sched.pipeline.overlap_windows")
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
        return losses, (
            metrics.get_counter("sched.pipeline.overlap_windows") - o0
        )
    finally:
        sched.set_config_override(None)
        railpipe.set_mode_override(None)


off, n_off = train("off")
on, n_on = train("on")
assert off == on, f"pipeline on != off (bitwise): {off} vs {on}"
assert n_off == 0, f"serialized run bumped overlap windows: {n_off}"
assert n_on > 0, "pipelined run never opened an overlap window"

# --- tuner explores the pipeline knob and persists the winner -------
rank = int(sys.argv[1])
db = os.path.join(
    os.environ["HVD_TPU_RAILPIPE_SMOKE_TUNEDIR"], f"tune_{rank}.json"
)
os.environ["HVD_TPU_TUNE_DB"] = db
SIG = ("railpipe-smoke", 16 * 1024)
t1 = sched.ScheduleTuner(explore_pipeline=True, warmup_windows=2,
                         store="env", store_key=SIG)
explored = set()
for _ in range(16):
    if t1.converged:
        break
    t1.begin_window()
    cand = t1.pipeline()
    explored.add(cand)
    # deterministic synthetic windows: the pipelined candidate scores
    # highest, so every process converges to the same winner
    metrics.inc_counter("train.steps", {"on": 30, "auto": 20}.get(cand, 10))
    metrics.observe("train.step_seconds", 0.5)
    metrics.set_gauge("sched.bytes_per_step", 1000.0)
    t1.end_window()
assert t1.converged, "tuner never converged"
assert explored >= {"off", "on", "auto"}, f"knob under-explored: {explored}"
assert t1.pipeline() == "on", f"wrong winner: {t1.pipeline()}"
entries = json.load(open(db))["entries"]
assert any((e.get("meta") or {}).get("pipeline") == "on"
           for e in entries.values()), "winner not persisted"
# warm start: converged at window 0, knob re-adopted
os.environ["HVD_TPU_XIR_PIPELINE"] = "auto"
t2 = sched.ScheduleTuner(explore_pipeline=True, store="env",
                         store_key=SIG)
assert t2.converged, "warm start did not converge at window 0"
assert t2.pipeline() == "on", "warm start lost the pipeline winner"

json.dump({"losses": on, "overlap_windows": n_on,
           "winner": t1.pipeline()}, sys.stdout)
EOF

pids=()
for i in 0 1 2 3; do
    python "$WORKER" "$i" > "$WORKER.out.$i" &
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
    f"pipelined trajectories diverged across processes: {vals}"
assert all(r["overlap_windows"] > 0 for r in results), results
assert all(r["winner"] == "on" for r in results), results
print(f"railpipe smoke OK x 4 procs: final loss "
      f"{results[0]['losses'][-1]:.6f}, "
      f"{results[0]['overlap_windows']} overlap windows/trace, "
      f"tuner winner '{results[0]['winner']}' persisted + warm-started")
EOF
echo "RAILPIPE SMOKE OK"
