#!/usr/bin/env bash
# Fused-quantized-collective smoke (HVD_TPU_QUANT_BACKEND): a
# 4-process CPU train loop proves the backend contract end to end —
#
#   1. the FUSED backend (ops/pallas_quant.py ring kernels, interpret
#      mode + ppermute transport on CPU) reaches the dense fp32 path's
#      final loss within 1e-3 (the same bound the phase backend
#      carries, docs/quantization.md);
#   2. the fused-path counters are live (nonzero
#      quant.fused_collectives / quant.fused_bytes, zero fallbacks on
#      the CPU mesh);
#   3. HVD_TPU_QUANT_BACKEND=phase is a true control: its trajectory
#      is BITWISE identical to leaving the knob unset (the pre-backend
#      code path), so shipping the dispatch layer changed nothing for
#      existing users;
#   4. the fused trajectory agrees bitwise across all 4 worker
#      processes (the kernels are deterministic).
#
# Each worker runs its own 8-virtual-device SPMD world (this jax
# build's CPU backend rejects cross-process computations), same
# structure as tools/tier1_quant_smoke.sh.  The same marker gates the
# unit tier: pytest -m pallas.
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_pallas_smoke.XXXXXX.py)"
trap 'rm -f "$WORKER" "$WORKER".out.*' EXIT

cat > "$WORKER" <<'EOF'
import json
import os
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


def run(cfg, backend=None):
    if backend is None:
        os.environ.pop("HVD_TPU_QUANT_BACKEND", None)
    else:
        os.environ["HVD_TPU_QUANT_BACKEND"] = backend
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
        os.environ.pop("HVD_TPU_QUANT_BACKEND", None)


dense_cfg = sched.SchedConfig(bucket_bytes=64)
quant_cfg = sched.SchedConfig(bucket_bytes=64,
                              wire="int8", wire_ef=True)

dense = run(dense_cfg)
control = run(quant_cfg)            # knob unset: the pre-backend path
phase = run(quant_cfg, "phase")     # explicit phase must be a no-op
metrics.reset_counters("quant.")
fused = run(quant_cfg, "fused")
fused_n = metrics.get_counter("quant.fused_collectives")
fused_b = metrics.get_counter("quant.fused_bytes")
fallbacks = metrics.get_counter("quant.fused_fallback")

assert phase == control, (
    "HVD_TPU_QUANT_BACKEND=phase is not bitwise-identical to the "
    f"unset knob: {phase} vs {control}"
)
assert abs(fused[-1] - dense[-1]) <= 1e-3, (
    f"fused int8+EF diverged from dense: {fused[-1]} vs {dense[-1]}"
)
assert abs(phase[-1] - dense[-1]) <= 1e-3, (
    f"phase int8+EF diverged from dense: {phase[-1]} vs {dense[-1]}"
)
assert fused_n > 0 and fused_b > 0, (fused_n, fused_b)
assert fallbacks == 0, f"unexpected fused fallbacks on CPU: {fallbacks}"
json.dump({"dense": dense, "phase": phase, "fused": fused,
           "fused_collectives": fused_n, "fused_bytes": fused_b},
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
fused = [r["fused"] for r in results]
assert all(f == fused[0] for f in fused), \
    f"fused trajectories diverged across processes: {fused}"
assert all(r["fused_collectives"] > 0 for r in results), results
print(f"fused final loss {fused[0][-1]:.6f} == dense within 1e-3 x 4 "
      f"procs; phase control bitwise == unset knob; "
      f"{results[0]['fused_collectives']} fused collectives, "
      f"{results[0]['fused_bytes']} fused wire bytes")
print("PALLAS SMOKE OK")
EOF
