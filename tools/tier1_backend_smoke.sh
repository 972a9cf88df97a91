#!/usr/bin/env bash
# Multi-backend lowering-plane smoke (HVD_TPU_BACKEND): a 4-process CPU
# train loop proves the backend-registry contract end to end —
#
#   1. a dense fp32 train loop under the forced gpu family is BITWISE
#      identical to the tpu family (the families change lowering
#      tables, never dense numerics) — per process and across all 4
#      worker processes;
#   2. under a quantized wire the gpu family routes reduce ops through
#      the mosaic lowering by default (nonzero
#      backend.gpu.quant_collectives / backend.gpu.quant_bytes, zero
#      quant.fused_fallback — no silent dense fallbacks) and still
#      reaches the dense loss within 1e-3;
#   3. the rail plane is live and relabeled: nonzero topo.ici_bytes
#      rail gauge from the scheduled exchange, with the gpu family
#      reporting the nvlink/ib display labels alongside the canonical
#      ici/dcn spellings (/prof rails view);
#   4. the tune DB keys by RESOLVED family: a winner recorded under the
#      gpu fingerprint warm-starts a fresh store under gpu and is
#      invisible under tpu keys (unset == tpu keeps pre-existing
#      entries).
#
# Each worker runs its own 8-virtual-device SPMD world (this jax
# build's CPU backend rejects cross-process computations), same
# structure as tools/tier1_pallas_smoke.sh.  The same marker gates the
# unit tier: pytest -m backend.
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_backend_smoke.XXXXXX.py)"
trap 'rm -f "$WORKER" "$WORKER".out.* "$WORKER".tune.json' EXIT

cat > "$WORKER" <<'EOF'
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import metrics, sched, topo
from horovod_tpu.backend import registry

hvd.init()
X = np.random.RandomState(1).randn(16, 4).astype(np.float32)
Y = (X @ np.full((4, 2), 0.7)).astype(np.float32)


def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)


def set_family(fam):
    if fam is None:
        os.environ.pop("HVD_TPU_BACKEND", None)
    else:
        os.environ["HVD_TPU_BACKEND"] = fam
    registry.reset()
    topo.reset()


def run(cfg, fam):
    set_family(fam)
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
        set_family(None)


dense_cfg = sched.SchedConfig(bucket_bytes=64)
quant_cfg = sched.SchedConfig(bucket_bytes=64,
                              wire="int8", wire_ef=True)

# 1. dense f32: gpu family bitwise == tpu family
dense_tpu = run(dense_cfg, "tpu")
dense_gpu = run(dense_cfg, "gpu")
assert dense_gpu == dense_tpu, (
    "dense f32 trajectory differs between backend families: "
    f"{dense_gpu} vs {dense_tpu}"
)

# 2. quantized wire under the gpu family routes through mosaic by
#    default (no HVD_TPU_QUANT_BACKEND set anywhere in this worker)
metrics.reset_counters("quant.")
metrics.reset_counters("backend.")
quant_gpu = run(quant_cfg, "gpu")
gpu_n = metrics.get_counter("backend.gpu.quant_collectives")
gpu_b = metrics.get_counter("backend.gpu.quant_bytes")
fallbacks = metrics.get_counter("quant.fused_fallback")
assert gpu_n > 0 and gpu_b > 0, (
    f"gpu family did not route through mosaic: {gpu_n} collectives, "
    f"{gpu_b} bytes"
)
assert fallbacks == 0, f"silent fallbacks under gpu family: {fallbacks}"
assert abs(quant_gpu[-1] - dense_tpu[-1]) <= 1e-3, (
    f"gpu int8+EF diverged from dense: {quant_gpu[-1]} vs {dense_tpu[-1]}"
)

# 3. rail plane: the scheduled exchange priced bytes onto the rails,
#    and the gpu family reports the nvlink/ib display labels
ici_gauge = metrics.get_gauge("topo.ici_bytes") or 0.0
assert ici_gauge > 0, f"topo.ici_bytes rail gauge is dead: {ici_gauge}"
set_family("gpu")
import horovod_tpu.prof as prof

rails = prof._rails_view()
assert rails["labels"] == {"ici": "nvlink", "dcn": "ib"}, rails
set_family(None)

# 4. tune DB keys by resolved family (worker 0 exercises persistence)
if os.environ.get("SMOKE_WORKER") == "0":
    from horovod_tpu.sched.store import (
        ScheduleStore, knob_fingerprint, make_key,
    )

    db = os.environ["SMOKE_TUNE_DB"]
    sig = ("backend_smoke", (("bucket", 64),))
    set_family("gpu")
    key_gpu = make_key(sig, knobs=knob_fingerprint())
    ScheduleStore(db).record(key_gpu, bucket_bytes=64, wire="int8",
                             lowering="flat", score=1.0)
    warm = ScheduleStore(db).lookup(key_gpu)  # fresh store = warm start
    assert warm is not None and warm["wire"] == "int8", warm
    set_family("tpu")
    key_tpu = make_key(sig, knobs=knob_fingerprint())
    assert key_tpu != key_gpu, "gpu fingerprint collided with tpu"
    assert ScheduleStore(db).lookup(key_tpu) is None
    set_family(None)

json.dump({"dense_tpu": dense_tpu, "dense_gpu": dense_gpu,
           "quant_gpu": quant_gpu, "gpu_collectives": gpu_n,
           "gpu_bytes": gpu_b}, sys.stdout)
EOF

pids=()
for i in 0 1 2 3; do
    SMOKE_WORKER="$i" SMOKE_TUNE_DB="$WORKER.tune.json" \
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
gpu = [r["dense_gpu"] for r in results]
assert all(g == gpu[0] for g in gpu), \
    f"gpu-family dense trajectories diverged across processes: {gpu}"
quant = [r["quant_gpu"] for r in results]
assert all(q == quant[0] for q in quant), \
    f"gpu-family quantized trajectories diverged across processes: {quant}"
assert all(r["gpu_collectives"] > 0 for r in results), results
print(f"gpu dense bitwise == tpu x 4 procs; quantized reduce ops "
      f"routed through mosaic ({results[0]['gpu_collectives']} "
      f"collectives, {results[0]['gpu_bytes']} wire bytes, 0 "
      f"fallbacks); rails live + relabeled nvlink/ib; tune DB keyed "
      f"by family")
print("BACKEND SMOKE OK")
EOF
