#!/usr/bin/env bash
# Hierarchical-Adasum smoke: a 4-process CPU loop with HVD_TPU_TOPO
# forcing a 2x2 shape must (a) train under lowering=hier_adasum with
# finite losses, nonzero topo.dcn_bytes, and DCN bytes <= hier's for
# the same schedule; (b) agree bitwise across all 4 worker processes
# (the lowering, groups, and Adasum tree are deterministic); (c) on a
# single-slice (1x4) control, run bitwise identical to lowering=flat;
# and (d) let ScheduleTuner explore all three lowerings, converge to a
# hier_adasum entry in the persistent DB, and warm-start from it.
#
# Each of the 4 worker processes runs its own 4-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop).
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=4 ${XLA_FLAGS:-}"
export HVD_TPU_TOPO="2x2"
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_adasum_smoke.XXXXXX.py)"
TUNE_DB="$(mktemp /tmp/hvd_tpu_adasum_smoke_db.XXXXXX.json)"
rm -f "$TUNE_DB"
trap 'rm -f "$WORKER" "$WORKER".out.* "$TUNE_DB"' EXIT

cat > "$WORKER" <<'EOF'
import json
import sys

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import metrics, sched

hvd.init()
X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)


def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)


def run(cfg):
    params = {
        "w1": jnp.full((4, 4), 0.2),
        "w2": jnp.full((4, 2), 0.5),
        "b": jnp.zeros((2,)),
    }
    sched.set_config_override(cfg)
    try:
        tx = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = hvd.distributed_train_step(loss_fn, tx)
        st = step.init(params)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        losses = []
        for _ in range(15):
            params, st, loss = step(params, st, batch)
            losses.append(float(loss))
        return losses
    finally:
        sched.set_config_override(None)


hier = run(sched.SchedConfig(bucket_bytes=64,
                             lowering="hier"))
dcn_hier = metrics.get_gauge("topo.dcn_bytes")
adasum = run(sched.SchedConfig(bucket_bytes=64,
                               lowering="hier_adasum"))
dcn_adasum = metrics.get_gauge("topo.dcn_bytes")
buckets = metrics.get_gauge("topo.buckets", {"lowering": "hier_adasum"})

assert all(np.isfinite(v) for v in adasum), adasum
assert dcn_adasum and dcn_adasum > 0, f"topo.dcn_bytes: {dcn_adasum}"
assert dcn_hier and dcn_adasum <= dcn_hier, \
    f"hier_adasum DCN {dcn_adasum} > hier DCN {dcn_hier}"
assert buckets and buckets >= 1, f"topo.buckets{{hier_adasum}}: {buckets}"
json.dump({"adasum": adasum, "hier": hier,
           "dcn_adasum": dcn_adasum, "dcn_hier": dcn_hier},
          sys.stdout)
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
traj = [r["adasum"] for r in results]
assert all(t == traj[0] for t in traj), \
    f"hier_adasum trajectories diverged across processes: {traj}"
print(f"hier_adasum final loss {traj[0][-1]:.6f} bitwise across 4 "
      f"procs; DCN bytes hier {results[0]['dcn_hier']:.0f} -> "
      f"hier_adasum {results[0]['dcn_adasum']:.0f} (<=)")
EOF

# Single-slice control: a hier_adasum request on an undivided topology
# must be bitwise identical to lowering=flat (the plan resolves it).
HVD_TPU_TOPO="1x4" python - <<'EOF'
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import sched

hvd.init()
X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)


def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)


def losses(lowering):
    params = {
        "w1": jnp.full((4, 4), 0.2),
        "w2": jnp.full((4, 2), 0.5),
        "b": jnp.zeros((2,)),
    }
    sched.set_config_override(sched.SchedConfig(
        bucket_bytes=64, lowering=lowering))
    try:
        tx = hvd.DistributedOptimizer(optax.sgd(0.1))
        step = hvd.distributed_train_step(loss_fn, tx)
        st = step.init(params)
        batch = (jnp.asarray(X), jnp.asarray(Y))
        out = []
        for _ in range(10):
            params, st, loss = step(params, st, batch)
            out.append(float(loss))
        return out
    finally:
        sched.set_config_override(None)


adasum = losses("hier_adasum")
flat = losses("flat")
assert adasum == flat, \
    f"single-slice hier_adasum != flat bitwise: {adasum} vs {flat}"
print("single-slice hier_adasum == flat bitwise OK")
EOF

# Tuner: explore all three lowerings on real training windows, converge
# to a hier_adasum entry in the persistent DB, warm-start from it.
HVD_TPU_TUNE_DB="$TUNE_DB" python - <<'EOF'
import json
import os

import numpy as np

import horovod_tpu as hvd
from horovod_tpu import metrics, sched
from horovod_tpu.sched.tune import ScheduleTuner

hvd.init()
sig = ("adasum-smoke-sig", 2, 2)
tuner = ScheduleTuner(explore_lowering=True, store="env", store_key=sig)
seen = []
w = 0
while not tuner.converged and w < 80:
    lo = tuner.lowering()
    seen.append(lo)
    tuner.begin_window()
    # deterministic synthetic windows: hier_adasum scores best, so the
    # converged entry proves the DB can carry the third lowering
    boost = {"flat": 1.0, "hier": 1.2, "hier_adasum": 2.0}.get(lo, 1.0)
    metrics.inc_counter("train.steps", int(10 * boost))
    metrics.observe("train.step_seconds", 0.1)
    metrics.set_gauge("sched.bytes_per_step", 1000)
    tuner.end_window()
    w += 1
assert {"flat", "hier", "hier_adasum"} <= set(seen), \
    f"tuner did not explore all three lowerings: {sorted(set(seen))}"
assert tuner.lowering() == "hier_adasum", tuner.lowering()
db = json.load(open(os.environ["HVD_TPU_TUNE_DB"]))
entry = list(db["entries"].values())[0]
assert entry["lowering"] == "hier_adasum", entry

metrics.reset_counters("sched.tune.")
warm = ScheduleTuner(explore_lowering=True, store="env", store_key=sig)
assert warm.converged, "warm start did not converge at window 0"
assert warm.lowering() == "hier_adasum", warm.lowering()
assert metrics.get_counter("sched.tune.db_hit") == 1
print(f"tuner explored {sorted(set(seen))} in {w} windows, froze "
      "hier_adasum, DB warm-start hit OK")
EOF
echo "ADASUM SMOKE OK"
