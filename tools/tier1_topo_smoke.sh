#!/usr/bin/env bash
# Topology smoke: a 4-process CPU train loop with HVD_TPU_TOPO forcing
# a 2-slice shape must produce hier losses equal to flat within fp
# reordering tolerance, a live topo observability surface (nonzero
# topo.dcn_bytes with the hier gauge at flat/slice_size), and a
# single-slice (auto) run bitwise identical to lowering=off.
#
# Each of the 4 worker processes runs its own 8-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop): the assertions cover hier==flat inside every process
# AND bitwise agreement of the hier trajectory across all 4 processes
# (the lowering choice and groups are deterministic).
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export HVD_TPU_TOPO="2x4"
# the worker file lives in /tmp: put the repo root on the path
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_topo_smoke.XXXXXX.py)"
trap 'rm -f "$WORKER" "$WORKER".out.*' EXIT

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
        for _ in range(20):
            params, st, loss = step(params, st, batch)
            losses.append(float(loss))
        return losses
    finally:
        sched.set_config_override(None)


# small buckets so the scheduler emits several per step
flat = run(sched.SchedConfig(bucket_bytes=64,
                             lowering="flat"))
dcn_flat = metrics.get_gauge("topo.dcn_bytes")
hier = run(sched.SchedConfig(bucket_bytes=64,
                             lowering="hier"))
dcn_hier = metrics.get_gauge("topo.dcn_bytes")

assert dcn_hier and dcn_hier > 0, f"topo.dcn_bytes: {dcn_hier}"
# forced 2x4 topology: slice_size = 4, so hier DCN = flat DCN / 4
assert dcn_flat and abs(dcn_flat / dcn_hier - 4.0) < 1e-6, \
    f"DCN ratio: {dcn_flat} / {dcn_hier}"
assert max(abs(a - b) for a, b in zip(flat, hier)) <= 1e-6, \
    f"hier diverged from flat: {flat[-1]} vs {hier[-1]}"
json.dump({"flat": flat, "hier": hier,
           "dcn_flat": dcn_flat, "dcn_hier": dcn_hier}, sys.stdout)
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
hier = [r["hier"] for r in results]
assert all(h == hier[0] for h in hier), \
    f"hier trajectories diverged across processes: {hier}"
assert all(r["dcn_hier"] > 0 for r in results), results
print(f"hier final loss {hier[0][-1]:.6f} == flat within 1e-6 x 4 "
      f"procs; DCN bytes {results[0]['dcn_flat']:.0f} -> "
      f"{results[0]['dcn_hier']:.0f} (1/slice_size)")
EOF

# Single-slice degeneracy: auto lowering on an undivided topology must
# be bitwise identical to lowering=off (the flat path, unchanged).
HVD_TPU_TOPO="1x8" python - <<'EOF'
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


auto = losses("auto")
off = losses("off")
assert auto == off, f"single-slice auto != off bitwise: {auto} vs {off}"
print("single-slice auto == off bitwise OK")
EOF
echo "TOPO SMOKE OK"
