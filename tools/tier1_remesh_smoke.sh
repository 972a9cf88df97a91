#!/usr/bin/env bash
# Remesh smoke: kill-and-resize without a checkpoint restore on the
# hot path.
#
# Part 1 — four worker processes (each its own 8-virtual-device SPMD
# world; this jax build's CPU backend rejects cross-process
# computations, so the processes are independent replicas of the same
# seeded loop) each run the full in-process resize pipeline: train
# bucketed ZeRO-1 on 8 devices, fault-inject a kill_at_step plan that
# proves the step-boundary anchor, reshard the live state to a
# 4-device world through snapshot -> KV publish -> plan -> fetch ->
# install, and keep training.  Asserts per process: post-resize losses
# BITWISE equal to the checkpoint-restart reference, remesh.success
# counted, and checkpoint.fallback untouched (nothing restored on the
# hot path).  Asserts across processes: identical loss trajectories
# (the plan and exchange are deterministic).
#
# Part 2 — the driver coordination suite (pause/ack/go/done barriers,
# shed exit code, ack-timeout fallback) against scripted KV workers:
# the `remesh`-marked tier-1 tests minus the multiproc-only resize.
set -euo pipefail

export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
REPO="$(cd "$(dirname "$0")/.." && pwd)"
export PYTHONPATH="$REPO${PYTHONPATH:+:$PYTHONPATH}"

WORKER="$(mktemp /tmp/hvd_tpu_remesh_smoke.XXXXXX.py)"
trap 'rm -f "$WORKER" "$WORKER".out.*' EXIT

cat > "$WORKER" <<'EOF'
import json
import sys

import jax
import jax.numpy as jnp
import numpy as np
import optax

import horovod_tpu as hvd
from horovod_tpu import faults, metrics, sched
from horovod_tpu import runtime as rt
from horovod_tpu.elastic import ArrayState, remesh as rm
from horovod_tpu.sched.zero1 import bucket_layouts
from horovod_tpu.topo import model as topo_model
from jax.sharding import NamedSharding, PartitionSpec as P


class FakeKV:
    def __init__(self):
        self.d = {}

    def put(self, scope, key, val):
        self.d[(scope, key)] = bytes(val)

    def get(self, scope, key, timeout_ms=0):
        return self.d.get((scope, key))


X = np.random.RandomState(1).randn(8, 4).astype(np.float32)
Y = (X @ np.full((4, 3), 0.3)).astype(np.float32)


def loss_fn(p, b):
    x, y = b
    return jnp.mean((x @ p["w1"] @ p["w2"] + p["b"] - y) ** 2)


def fresh_params():
    return {
        "w1": jnp.full((4, 5), 0.2, jnp.float32),
        "w2": jnp.full((5, 3), 0.5, jnp.float32),
        "b": jnp.zeros((3,), jnp.float32),
    }


cfg = sched.SchedConfig(bucket_bytes=48, lowering="flat")
tx = optax.adam(0.05)
batch = (jnp.asarray(X), jnp.asarray(Y))

# The step-boundary anchor (same site/selector kill_at_step pins its
# crash to, fired non-fatally here so this worker survives to remesh;
# the real kill is proven in the launcher's subprocess check).
faults.set_plan("worker.commit:error:step=3")
hvd.init()
step = sched.bucketed_zero_step(loss_fn, tx, cfg=cfg)
params = fresh_params()
states = step.init(params)
state = ArrayState(params=params, opt_state=states, epoch=0)
killed_at = None
pre = []
for i in range(4):
    state.params, state.opt_state, loss = step(
        state.params, state.opt_state, batch
    )
    pre.append(float(loss))
    try:
        state.commit()
    except faults.FaultInjected:
        killed_at = i + 1
assert killed_at == 3, f"kill_at_step anchor fired at {killed_at}"
faults.set_plan(None)

# ---- remesh boundary: reshard the live state to 4 devices -----------
spec = rm.ShardedZeroState(state, "params", "opt_state", cfg=cfg)
req = rm.RemeshRequest(
    remesh_id=1, round_id=1, np_old=1, np_new=1,
    coordinator_addr="", survivors={0: 0}, dev_old=8, dev_new=4,
)
spec.snapshot()
store = rm.KVShardStore(FakeKV(), 1)
spec.publish(store, "zero", 0)
host_states = spec.reshard(req, store, "zero", 0)
host_params = jax.device_get(state.params)
snap_states = jax.device_get(state.opt_state)

restore_before = metrics.get_counter("checkpoint.fallback")
rt.shutdown()
topo_model.reset()
hvd.init(devices=jax.devices()[:4])
step4 = sched.bucketed_zero_step(loss_fn, tx, cfg=cfg)
p4 = jax.device_put(host_params)
step4.init(p4)
spec.install(host_states)
st4 = state.opt_state
losses = []
for _ in range(4):
    p4, st4, loss = step4(p4, st4, batch)
    losses.append(float(loss))

# ---- reference: checkpoint-restart restore onto the same world ------
lays8 = bucket_layouts(fresh_params(), 8, cfg)
lays4 = bucket_layouts(fresh_params(), 4, cfg)
mesh = rt.get_runtime().mesh


def restore_bucket(full_like, lay8, lay4):
    def leaf(x):
        arr = np.asarray(x)
        if arr.ndim >= 1 and arr.shape[0] == lay8.padded:
            out = np.zeros((lay4.padded,), arr.dtype)
            out[: lay8.n] = arr[: lay8.n]
            return jax.device_put(out, NamedSharding(mesh, P("hvd")))
        return jax.device_put(arr, NamedSharding(mesh, P()))

    return jax.tree.map(leaf, full_like)


ref_states = tuple(
    restore_bucket(snap_states[bi], lays8[bi], lays4[bi])
    for bi in range(len(snap_states))
)
step4b = sched.bucketed_zero_step(loss_fn, tx, cfg=cfg)
p4b = jax.device_put(host_params)
step4b.init(p4b)
ref = []
for _ in range(4):
    p4b, ref_states, loss = step4b(p4b, ref_states, batch)
    ref.append(float(loss))

assert losses == ref, f"remesh diverged from restart: {losses} vs {ref}"
assert metrics.get_counter("checkpoint.fallback") == restore_before, \
    "a checkpoint restore leaked onto the hot path"
json.dump({"pre": pre, "post": losses}, sys.stdout)
EOF

# Real kill_at_step: a worker that commits in a loop dies at EXACTLY
# the scripted step with the scripted exit code — seed-reproducible.
python - <<'EOF'
import os
import subprocess
import sys

child = (
    "from horovod_tpu.elastic.state import ObjectState\n"
    "s = ObjectState(epoch=0)\n"
    "for i in range(6):\n"
    "    s.commit()\n"
    "    print('committed', i + 1, flush=True)\n"
)
proc = subprocess.run(
    [sys.executable, "-c", child],
    env={**os.environ,
         "HVD_TPU_FAULT_PLAN": "worker.commit:kill_at_step:step=3,code=9"},
    capture_output=True, text=True, timeout=120,
)
assert proc.returncode == 9, (proc.returncode, proc.stderr[-400:])
lines = [l for l in proc.stdout.splitlines() if l.startswith("committed")]
assert lines == ["committed 1", "committed 2"], lines
print("kill_at_step: died at commit 3 with code 9, deterministically")
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
post = [r["post"] for r in results]
assert all(p == post[0] for p in post), \
    f"post-resize trajectories diverged across processes: {post}"
print(f"in-process 8->4 resize OK x4 procs; post-resize losses "
      f"{post[0]}")
EOF

# Part 2: driver coordination + layout exchange + fallback suite
python -m pytest "$REPO/tests/integration/test_remesh.py" \
    -q -m "remesh and not multiproc" -p no:cacheprovider \
    -k "not probe_report and not survivor_reinit"
echo "REMESH SMOKE OK"
