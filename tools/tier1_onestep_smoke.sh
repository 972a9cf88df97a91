#!/usr/bin/env bash
# Whole-step single-dispatch smoke (the exchange service's fold; the
# compiled train step is one program already and has no such knob): a
# 4-process CPU run on a forced 2x4 topology.  On the
# N-small-programs-across-several-fusion-classes service burst (the
# ROADMAP item 4 workload), the folded run must be bitwise equal to
# the per-unit one, pay exactly ONE svc dispatch per cycle
# (prof.dispatches_per_step p50 == 1 where the off run pays one per
# class) and show a measured host-gap reduction
# (prof.host_gap_seconds mean, off/on > 1.05; tools/topo_bench.py
# --onestep records the >= 1.15 solo-process number).  A
# ScheduleTuner(explore_onestep=True) explores off -> on -> auto,
# freezes a winner, persists it in the tune DB (meta.onestep), and
# warm-starts from it.
#
# Each of the 4 worker processes runs its own 8-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded burst): the assertions cover onestep on==off inside every
# process.
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export HVD_TPU_TOPO=2x4
# long cycle linger: 4 concurrent workers share the CPU, and a burst
# split across two cycles would double the folded dispatch count
export HVD_TPU_SVC_CYCLE_TIME=10.0
# the worker file lives in /tmp: put the repo root on the path
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_onestep_smoke.XXXXXX.py)"
TUNEDIR="$(mktemp -d /tmp/hvd_tpu_onestep_tune.XXXXXX)"
trap 'rm -rf "$WORKER" "$WORKER".out.* "$TUNEDIR"' EXIT
export HVD_TPU_ONESTEP_SMOKE_TUNEDIR="$TUNEDIR"

cat > "$WORKER" <<'EOF'
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np

import horovod_tpu as hvd
from horovod_tpu import metrics, sched, svc, trace, xir
from horovod_tpu.runtime import WORLD_AXIS
from horovod_tpu.xir import interp as xinterp

hvd.init()

rng = np.random.RandomState(7)

# --- service burst: one dispatch per cycle, measured host gap -------
rows, per_class = 64, 3
classes = [(red, dt) for red in ("mean", "sum")
           for dt in ("float32", "bfloat16", "float16")]
payloads, progs = [], []
for red, dt in classes:
    for _ in range(per_class):
        x = rng.randn(hvd.size(), rows).astype(np.float32)
        payloads.append(jnp.asarray(x, dtype=dt))
        progs.append(xir.program("dense_grad", [
            xir.all_reduce(WORLD_AXIS, reduce=red, lowering="flat",
                           nbytes=rows * 4, dtype=dt),
        ]))


def burst(mode, iters=16, warmup=3):
    svc.reset_service()
    svc.set_threshold_override(64 * 1024 * 1024)
    xinterp.set_onestep_override(mode)
    try:
        s = svc.get_service()

        def step():
            with trace.step():
                futs = [s.submit(p, [x], producer=f"p{i % 4}")
                        for i, (p, x) in enumerate(zip(progs, payloads))]
                return [f.result(timeout=120)[0] for f in futs]

        for _ in range(warmup):
            outs = step()
        jax.block_until_ready(outs)
        metrics.reset_counters("prof.host_gap")
        gauges = []
        for _ in range(iters):
            outs = step()
            gauges.append(metrics.get_gauge("prof.dispatches_per_step"))
        jax.block_until_ready(outs)
        gap = metrics.get_histogram("prof.host_gap_seconds") or {}
        return {
            "outs": [np.asarray(o, dtype=np.float32) for o in outs],
            "gap_mean_s": gap.get("sum", 0.0) / max(gap.get("count", 0), 1),
            "disp_p50": sorted(gauges)[len(gauges) // 2],
        }
    finally:
        svc.set_threshold_override(None)
        xinterp.set_onestep_override(None)
        svc.reset_service()


b_off = burst("off")
b_on = burst("on")
assert all((a == b).all() for a, b in zip(b_off["outs"], b_on["outs"])), \
    "folded service cycle diverged from per-unit (bitwise)"
assert b_on["disp_p50"] == 1.0, \
    f"folded cycle p50 dispatches/step != 1: {b_on['disp_p50']}"
assert b_off["disp_p50"] > 1.0, \
    f"off run lost its fusion classes: {b_off['disp_p50']}"
gap_ratio = b_off["gap_mean_s"] / max(b_on["gap_mean_s"], 1e-9)
assert gap_ratio > 1.05, \
    f"no measured host-gap reduction: off/on = {gap_ratio:.3f}"

# --- tuner explores the onestep knob and persists the winner --------
rank = int(sys.argv[1])
db = os.path.join(
    os.environ["HVD_TPU_ONESTEP_SMOKE_TUNEDIR"], f"tune_{rank}.json"
)
os.environ["HVD_TPU_TUNE_DB"] = db
SIG = ("onestep-smoke", 16 * 1024)
t1 = sched.ScheduleTuner(explore_onestep=True, warmup_windows=2,
                         store="env", store_key=SIG)
explored = set()
for _ in range(16):
    if t1.converged:
        break
    t1.begin_window()
    cand = t1.onestep()
    explored.add(cand)
    # deterministic synthetic windows: the folded candidate scores
    # highest, so every process converges to the same winner
    metrics.inc_counter("train.steps", {"on": 30, "auto": 20}.get(cand, 10))
    metrics.observe("train.step_seconds", 0.5)
    metrics.set_gauge("sched.bytes_per_step", 1000.0)
    t1.end_window()
assert t1.converged, "tuner never converged"
assert explored >= {"off", "on", "auto"}, f"knob under-explored: {explored}"
assert t1.onestep() == "on", f"wrong winner: {t1.onestep()}"
entries = json.load(open(db))["entries"]
assert any((e.get("meta") or {}).get("onestep") == "on"
           for e in entries.values()), "winner not persisted"
# warm start: converged at window 0, knob re-adopted
os.environ["HVD_TPU_ONESTEP"] = "auto"
t2 = sched.ScheduleTuner(explore_onestep=True, store="env",
                         store_key=SIG)
assert t2.converged, "warm start did not converge at window 0"
assert t2.onestep() == "on", "warm start lost the onestep winner"

json.dump({"disp_p50": b_on["disp_p50"],
           "gap_ratio": round(gap_ratio, 3),
           "winner": t1.onestep()}, sys.stdout)
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
assert all(r["disp_p50"] == 1.0 for r in results), results
assert all(r["winner"] == "on" for r in results), results
print(f"onestep smoke OK x 4 procs: dispatches/step p50 == 1, "
      f"host-gap off/on {min(r['gap_ratio'] for r in results):.2f}-"
      f"{max(r['gap_ratio'] for r in results):.2f}x, "
      f"tuner winner '{results[0]['winner']}' persisted + warm-started")
EOF
echo "ONESTEP SMOKE OK"
