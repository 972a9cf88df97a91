#!/usr/bin/env bash
# Exchange-tracing smoke: a 4-process CPU run on a forced 2x4 topology
# must prove the acceptance properties of the trace/ subsystem end to
# end:
#
#   1. HVD_TPU_TRACE=full produces f32 dense losses bitwise identical
#      to =off (per process AND across processes) — spans are host-
#      side, never ops;
#   2. hier buckets yield nonzero measured topo.rail_busy_frac on BOTH
#      rails;
#   3. an injected 300ms topo.dcn_phase slow fault on rank 2 is
#      (a) visible as a >=250ms DCN rail span in rank 2's trace file,
#      (b) dumped by rank 2's flight recorder as a fault anomaly, and
#      (c) named by rank and phase in the driver-side /trace straggler
#      summary built from the four ranks' metric snapshots;
#   4. the cross-rank merge of the four trace exports validates as
#      Chrome-trace JSON with one lane per rank and a clean per-file
#      parse report (exit 0).
#
# Each of the 4 worker processes runs its own 8-virtual-device SPMD
# world (this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop), exactly like the other tier1 smokes.
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
export HVD_TPU_TOPO=2x4
export HVD_TPU_TOPO_LOWER=hier
export PYTHONPATH="$(cd "$(dirname "$0")/.." && pwd)${PYTHONPATH:+:$PYTHONPATH}"

WORKDIR="$(mktemp -d /tmp/hvd_tpu_trace_smoke.XXXXXX)"
trap 'rm -rf "$WORKDIR"' EXIT
export HVD_TPU_TRACE_DIR="$WORKDIR/traces"
WORKER="$WORKDIR/worker.py"

cat > "$WORKER" <<'EOF'
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import faults, metrics, sched, trace

RANK = int(os.environ["HVD_TPU_CROSS_RANK"])
hvd.init()

rng = np.random.RandomState(7)
X = rng.randn(32, 64).astype(np.float32)
Y = (X @ rng.randn(64, 8).astype(np.float32)).astype(np.float32)


def loss_fn(p, b):
    x, y = b
    h = jnp.tanh(x @ p["w1"] + p["b1"])
    return jnp.mean((h @ p["w2"] - y) ** 2)


def params(extra=False):
    r = np.random.RandomState(3)
    p = {
        "w1": jnp.asarray(r.randn(64, 128).astype(np.float32) * 0.05),
        "b1": jnp.zeros((128,)),
        "w2": jnp.asarray(r.randn(128, 8).astype(np.float32) * 0.05),
    }
    if extra:
        p["b2"] = jnp.zeros((8,))
    return p


def train(level, iters=8, extra=False):
    trace.set_level_override(level)
    sched.set_config_override(sched.SchedConfig(
        bucket_bytes=16 * 1024, lowering="hier",
    ))
    try:
        p = params(extra)
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


# --- 1. tracing off == full, bitwise --------------------------------
off = train("off")
on = train("full")
assert off == on, f"tracing perturbed losses: {on} vs {off}"

# --- 2. measured rail utilization on hier buckets -------------------
ici = metrics.get_gauge("topo.rail_busy_frac", {"rail": "ici"})
dcn = metrics.get_gauge("topo.rail_busy_frac", {"rail": "dcn"})
assert ici and ici > 0, f"no measured ICI utilization: {ici}"
assert dcn and dcn > 0, f"no measured DCN utilization: {dcn}"

# --- 3. the scripted straggler (rank 2 only) ------------------------
# The ring is full from run 2; arm the fault and force a fresh trace
# (one extra parameter => new jit) so the 300ms delays land inside
# live DCN rail spans AND the fault trigger dumps the ring.
metrics.reset_counters("trace.phase_seconds")
if RANK == 2:
    faults.set_plan("topo.dcn_phase:slow:secs=0.3,times=0")
train("full", iters=2, extra=True)
faults.set_plan(None)

snap_path = os.path.join(os.environ["HVD_TPU_TRACE_DIR"],
                         f"snap_{RANK}.json")
with open(snap_path, "w") as fh:
    fh.write(metrics.render_json())

trace.reset()  # close the trace writer -> valid JSON on disk
json.dump({
    "rank": RANK,
    "losses": on,
    "rail_busy": {"ici": ici, "dcn": dcn},
    "anomaly_dumps": metrics.get_counter("trace.anomaly_dumps"),
}, sys.stdout)
EOF

pids=()
for i in 0 1 2 3; do
    HVD_TPU_CROSS_RANK=$i python "$WORKER" > "$WORKDIR/out.$i" &
    pids+=($!)
done
for pid in "${pids[@]}"; do
    wait "$pid"
done

# --- cross-rank merge must validate and report clean ----------------
python "$(dirname "$0")/merge_timeline.py" \
    "$HVD_TPU_TRACE_DIR"/trace_rank*.json -o "$WORKDIR/merged.json"

python - "$WORKDIR" <<'EOF'
import glob
import json
import os
import sys
import urllib.request

workdir = sys.argv[1]
tracedir = os.path.join(workdir, "traces")
results = [json.load(open(os.path.join(workdir, f"out.{i}")))
           for i in range(4)]

# 1. bitwise agreement across processes
vals = [r["losses"] for r in results]
assert all(v == vals[0] for v in vals), \
    f"traced trajectories diverged across processes: {vals}"

# 2. nonzero rails everywhere
for r in results:
    assert r["rail_busy"]["ici"] > 0 and r["rail_busy"]["dcn"] > 0, r

# 3a. the 300ms delay is a DCN rail span on rank 2's trace
def dcn_spans(rank):
    evs = json.load(open(os.path.join(tracedir,
                                      f"trace_rank{rank}.json")))
    return [e for e in evs if isinstance(e, dict)
            and e.get("cat") == "TRACE_DCN" and e.get("ph") == "X"]

slow = [e for e in dcn_spans(2) if e["dur"] >= 0.25e6]
assert slow, "rank 2's injected delay is not visible as a DCN span"
assert not [e for e in dcn_spans(0) if e["dur"] >= 0.25e6], \
    "control rank shows a slow DCN span"

# 3b. rank 2's flight recorder dumped the fault anomaly
dumps = glob.glob(os.path.join(tracedir, "flight_rank2_*.json"))
reasons = {json.load(open(p))["reason"] for p in dumps}
assert any(r.startswith("fault:topo.dcn_phase") or r == "slow_step"
           for r in reasons), f"no anomaly dump on rank 2: {reasons}"

# 3c. the driver-side /trace summary names rank 2 / phase dcn
from horovod_tpu.runner.telemetry_http import TelemetryServer

snaps = [(i, json.load(open(os.path.join(tracedir, f"snap_{i}.json"))))
         for i in range(4)]
srv = TelemetryServer(port=0, workers_fn=lambda: list(snaps))
try:
    body = json.load(urllib.request.urlopen(
        f"http://127.0.0.1:{srv.port}/trace"))
finally:
    srv.stop()
hits = [(f["rank"], f["phase"]) for f in body["stragglers"]]
assert (2, "dcn") in hits, f"straggler summary missed rank 2: {body}"

# 4. the merged trace is valid Chrome-trace JSON with 4 lanes
merged = json.load(open(os.path.join(workdir, "merged.json")))
events = merged["traceEvents"]
assert isinstance(events, list) and events
pids = {e.get("pid") for e in events if e.get("ph") == "X"}
assert pids >= {0, 1, 2, 3}, f"missing rank lanes: {pids}"

print(f"trace smoke OK x 4 procs: losses bitwise (off==full), "
      f"rail busy ici={results[0]['rail_busy']['ici']:.3f} "
      f"dcn={results[0]['rail_busy']['dcn']:.3f}, "
      f"{len(slow)} slow DCN span(s) on rank 2, "
      f"straggler named at {hits}, merged {len(events)} events")
EOF
echo "TRACE SMOKE OK"
