#!/usr/bin/env bash
# Quantized-wire smoke: a 4-process CPU train loop on the int8 wire
# with error feedback must reach the dense path's final loss within
# tolerance (the EF residual hides the quantization error in optimizer
# state — docs/quantization.md), and the wire observability surface
# must be live (nonzero sched.wire_bytes{wire="int8"}, compression
# ratio >= 3x vs the fp32 wire on the same schedule).
#
# Each of the 4 worker processes runs its own 8-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop): the assertion covers int8+EF ~= dense inside every
# process AND bitwise agreement of the quantized trajectory across all
# 4 processes (the quantizer is deterministic).
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
# the worker file lives in /tmp: put the repo root on the path
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_quant_smoke.XXXXXX.py)"
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
metrics.reset_counters("sched.")
dense = run(sched.SchedConfig(bucket_bytes=64))
dense_bytes = metrics.get_gauge("sched.wire_bytes", {"wire": "off"})
metrics.reset_counters("sched.")
quant = run(sched.SchedConfig(bucket_bytes=64,
                              wire="int8", wire_ef=True))
int8_bytes = metrics.get_gauge("sched.wire_bytes", {"wire": "int8"})

assert int8_bytes and int8_bytes > 0, \
    f'sched.wire_bytes{{wire="int8"}}: {int8_bytes}'
assert dense_bytes and dense_bytes / int8_bytes >= 3.0, \
    f"compression ratio: {dense_bytes} / {int8_bytes}"
assert abs(quant[-1] - dense[-1]) <= 1e-3, \
    f"int8+EF diverged from dense: {quant[-1]} vs {dense[-1]}"
json.dump({"dense": dense, "quant": quant,
           "wire_bytes_int8": int8_bytes,
           "ratio": dense_bytes / int8_bytes}, sys.stdout)
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
quant = [r["quant"] for r in results]
assert all(q == quant[0] for q in quant), \
    f"quantized trajectories diverged across processes: {quant}"
assert all(r["wire_bytes_int8"] > 0 for r in results), results
print(f"int8+EF final loss {quant[0][-1]:.6f} == dense within 1e-3 "
      f"x 4 procs; wire ratio {results[0]['ratio']:.2f}x")
print("QUANT SMOKE OK")
EOF
