"""Environment-variable config layer.

The reference centralizes ~40 ``HOROVOD_*`` env knobs in
``horovod/common/common.h:107-139`` and parses them in
``BackgroundThreadLoop`` (``operations.cc:459-588``).  We keep the same
three-layer config model (env vars < CLI flags < per-call kwargs) with the
``HVD_TPU_*`` prefix, accepting the legacy ``HOROVOD_*`` spelling as a
fallback so reference users can switch without editing their job scripts.
"""

from __future__ import annotations

import os
from typing import Optional

# Knob names (HVD_TPU_ prefix; HOROVOD_ prefix accepted as fallback).
FUSION_THRESHOLD = "FUSION_THRESHOLD"  # bytes; reference default 64MB
CYCLE_TIME = "CYCLE_TIME"  # ms; kept for API parity (no bg thread on TPU)
CACHE_CAPACITY = "CACHE_CAPACITY"
TIMELINE = "TIMELINE"
TIMELINE_MARK_CYCLES = "TIMELINE_MARK_CYCLES"
AUTOTUNE = "AUTOTUNE"
AUTOTUNE_LOG = "AUTOTUNE_LOG"
LOG_LEVEL = "LOG_LEVEL"
# Debug mode: every eager collective cross-checks its wire Request
# (type/dtype/shape/name) across processes before dispatch, erroring on
# mismatch — the reference controller's negotiation-time validation
# (controller.cc ConstructResponse error joining) as an opt-in check.
CONSISTENCY_CHECK = "CONSISTENCY_CHECK"
STALL_CHECK_DISABLE = "STALL_CHECK_DISABLE"
STALL_CHECK_TIME_SECONDS = "STALL_CHECK_TIME_SECONDS"
STALL_SHUTDOWN_TIME_SECONDS = "STALL_SHUTDOWN_TIME_SECONDS"
ELASTIC_ENABLED = "ELASTIC"
ELASTIC_TIMEOUT = "ELASTIC_TIMEOUT"
# Structured JSONL elastic event log path (events.py).
ELASTIC_EVENT_LOG = "ELASTIC_EVENT_LOG"
# Elastic driver HTTP /metrics + /health port (0 = OS-assigned;
# unset = disabled) — runner/telemetry_http.py.
TELEMETRY_PORT = "TELEMETRY_PORT"
START_TIMEOUT = "START_TIMEOUT"
DISABLE_GROUP_FUSION = "DISABLE_GROUP_FUSION"
DYNAMIC_PROCESS_SETS = "DYNAMIC_PROCESS_SETS"
HIERARCHICAL_ALLREDUCE = "HIERARCHICAL_ALLREDUCE"  # reference HOROVOD_HIERARCHICAL_ALLREDUCE
# Payload bytes above which arbitrary (non-partition) process-set
# collectives use member-only ppermute rings/trees instead of masked
# whole-world collectives. No reference analog (MPI communicators always
# touch members only); the knob trades latency vs non-member bandwidth.
SET_RING_THRESHOLD = "SET_RING_THRESHOLD"
PROCESS_SETS = "PROCESS_SETS"
BATCH_D2D_MEMCOPIES = "BATCH_D2D_MEMCOPIES"
NUM_STREAMS = "NUM_STREAMS"
# Bucketed overlap scheduler (sched/): the gradient-exchange pipeline
# behind DistributedOptimizer; see docs/scheduler.md.
SCHED_MODE = "SCHED_MODE"  # allreduce (default) | reduce_scatter
SCHED_BUCKET_BYTES = "SCHED_BUCKET_BYTES"  # default: fusion threshold
SCHED_LOOK_AHEAD = "SCHED_LOOK_AHEAD"  # bucket-close look-ahead, default 3
# Quantized wire v2 (ops/quantized.py + sched/): per-bucket wire format
# for the scheduler's exchange — off (default; dense/compressor wire) |
# bf16 | int8 | fp8.  See docs/quantization.md.
SCHED_WIRE = "SCHED_WIRE"
# Error-feedback residuals for quantized wires (default on): carry
# r <- (g + r) - dequant(quantize(g + r)) in optimizer state.
SCHED_WIRE_EF = "SCHED_WIRE_EF"
# Elements per quantization block (fp32 scale granularity), default 512.
QUANT_BLOCK = "QUANT_BLOCK"
# Accelerator backend family (backend/registry.py): "auto" (default;
# resolved from jax.devices()[0].platform — gpu/cuda/rocm platforms
# pick the gpu family, everything else the tpu family), "tpu", or
# "gpu".  The override exists so CPU test meshes can force either
# family's lowering tables (rail names, fused-ring kernel module, peak
# table, topology discovery) without hardware.  The RESOLVED family
# folds into the tune-DB knob fingerprint (unset ≡ tpu, so existing
# entries keep their keys).  See docs/backends.md.
BACKEND = "BACKEND"
# Quantized-wire backend: "phase" (default; blockwise quantize ->
# all_to_all of wire chunks + scales -> dequant-accumulate as separate
# XLA HLOs) or "fused" (ops/pallas_quant.py Pallas ring kernels:
# quantize / remote-DMA / fp32 dequant-accumulate in one kernel per ICI
# hop, lax.ppermute standing in for the DMA off-TPU).  Same numerics
# contract either way; participates in the tune-DB knob fingerprint so
# fused and phase winners never collide.  See docs/quantization.md.
QUANT_BACKEND = "QUANT_BACKEND"
# Topology-aware hierarchical collectives (topo/): forced topology
# spec — "SxK" / "SxK1xK2" (S slices of an ICI mesh) or a JSON object
# ({"slices":2,"ici_shape":[2,2],...}) — for CPU tests and forced
# shapes; unset = discover from jax.devices().  See docs/topology.md.
TOPO = "TOPO"
# Lowering policy for gradient-exchange collectives over a multi-slice
# axis: auto (default; cost model picks flat vs hier per bucket) |
# flat/off (always today's single-collective path) | hier/on (force
# the ICI reduce_scatter -> DCN all_reduce -> ICI all_gather staging).
TOPO_LOWER = "TOPO_LOWER"
# Cost-model parameters (per-link bandwidth GB/s, per-hop latency us,
# per-collective-phase fixed overhead us).  Defaults model ~10x
# ICI-vs-DCN bandwidth (arXiv:1810.11112's two-level regime).
TOPO_ICI_GBPS = "TOPO_ICI_GBPS"
TOPO_DCN_GBPS = "TOPO_DCN_GBPS"
TOPO_ICI_LAT_US = "TOPO_ICI_LAT_US"
TOPO_DCN_LAT_US = "TOPO_DCN_LAT_US"
TOPO_PHASE_OVERHEAD_US = "TOPO_PHASE_OVERHEAD_US"
# Measured cost model (topo/fit.py): fit effective link parameters
# from the per-collective dispatch histograms and prefer them over the
# static TOPO_* env defaults.  off = static pricing only.
TOPO_FIT = "TOPO_FIT"  # on (default) | off
TOPO_FIT_MIN_OBS = "TOPO_FIT_MIN_OBS"  # observations before first fit
TOPO_FIT_REFIT_EVERY = "TOPO_FIT_REFIT_EVERY"  # new obs between refits
# Unified exchange IR (xir/): route every collective-shaped workload
# (dense DP buckets, MoE all_to_all, Ulysses flips, sparse embedding
# exchange, pipeline ppermute, FSDP RS+AG) through the explicit
# plan->lower->execute pipeline.  See docs/exchange_ir.md.
# Wire format non-gradient IR workloads request (default off — an
# explicit numerics opt-in, NOT inherited from HVD_TPU_SCHED_WIRE:
# these ops move activations/embedding rows, not EF-compensated
# gradients).  Shuffle-shaped ops (all_to_all/permute/sparse gather)
# cap at bf16 — int8/fp8 requests downgrade to off for them.
XIR_WIRE = "XIR_WIRE"
# XIR rail pipeliner (xir/pipeline.py): phase-interleave the ICI and
# DCN rails across hier buckets (bucket i's cross-slice DCN hop runs
# concurrently with bucket i+1's ICI reduce-scatter and bucket i-1's
# ICI all-gather, via per-rail optimization_barrier chains).
#   off  = per-bucket chains, PR 10 emission exactly;
#   auto = (default) reorder-only — engage the rail chains when the
#          cost model prices the pipelined order cheaper, never
#          changing the bucket plan;
#   on   = rail chains AND bucket split points chosen from the fitted
#          per-rail bandwidths (plan.build_schedule defers to
#          pipeline.plan_bucket_bytes when no explicit size is set).
# f32 dense losses are bitwise-identical in every mode: the barriers
# are identity on values and reordering never changes summation
# grouping within a bucket.  See docs/exchange_ir.md.
XIR_PIPELINE = "XIR_PIPELINE"
# Whole-step emission (xir/interp.py onestep): fold a step's entire
# exchange schedule — fused buffers, rail-interleaved ordering, AND the
# optimizer-update closure — into ONE compiled dispatch instead of one
# jitted executor per fused buffer / per bucket chain.
#   off  = per-unit dispatch, the PR 18 paths exactly;
#   auto = (default) fold whenever a step has >= 2 dispatch units
#          (like the rail pipeliner, engagement is a scheduling
#          decision, never a numerics one);
#   on   = always fold.
# f32 dense losses are bitwise-identical in every mode: the stitch is
# optimization_barrier ties (identity on values) and the folded units
# emit the same ops in the same per-unit order.  Resolved mode folds
# into the tune-DB knob_fingerprint.  See docs/exchange_ir.md
# ("Whole-step emission").
ONESTEP = "ONESTEP"
# Async exchange service (svc/): the TPU-native BackgroundThreadLoop —
# a persistent executor that accepts XIR programs from concurrent
# producers through a TensorQueue submission API, negotiates readiness
# across producers (the coordinator-bitvector analog), and serves
# repeated program signatures from a ResponseCache without re-lowering.
# off (default) = every exchange dispatches inline exactly as before
# (bitwise identical by construction); on = producers submit plans and
# the service owns the wires.  See docs/exchange_service.md.
SVC = "SVC"  # off (default) | on
# Bounded staleness for the service's dense-gradient pipeline
# (svc/stale.py): 0 (default) = fully synchronous — losses bitwise
# identical to SVC=off; k >= 1 = local SGD / delayed DCN sync — the
# cross-slice hop of step i completes during step i+k's backward
# (DCN-latency hiding across steps, riding the PR 11 rail model).
SVC_STALENESS = "SVC_STALENESS"
# Service-side fusion buffers (svc/fuse.py): bytes one fused wire
# buffer may hold.  The cycle's negotiated submissions coalesce into
# one padded buffer per compatibility class — (op kind, axis/groups,
# wire, lowering, reduce, dtype) — and dispatch as ONE collective (the
# reference FusionBufferManager's 64 MiB staging buffer,
# fusion_buffer_manager.{h,cc}).  0 disables fusion: every submission
# dispatches separately, exactly the PR 12/13 behavior.  Oversize
# programs (> threshold) always pass through unfused.
SVC_FUSION_THRESHOLD = "SVC_FUSION_THRESHOLD"  # bytes; default 64 MiB
# Service cycle time in milliseconds (the reference HOROVOD_CYCLE_TIME,
# common.h:110): after the loop sees a first submission it lingers this
# long before draining the queue, so a burst of producers lands in ONE
# cycle batch (and one fusion pass) instead of one cycle each.  Falls
# back to the legacy CYCLE_TIME knob; default 1.0 ms, 0 = drain
# immediately (the PR 12 behavior).
SVC_CYCLE_TIME = "SVC_CYCLE_TIME"
# Online (cycle_time, fusion_threshold) tuning for the service loop
# (svc/params.py, the reference ParameterManager applied to the two
# service knobs): off (default) = static env values; on = window-score
# candidate pairs from the metrics registry, freeze the winner, pin it
# into the env knobs, and persist it in the tune DB for warm starts.
SVC_TUNE = "SVC_TUNE"  # off (default) | on
# Multi-tenant exchange arbiter (svc/arbiter.py): weighted-fair rail
# scheduling of one cycle's released submissions across tenants.
#   off = (default) FIFO cycle dispatch, the PR 14 behavior exactly;
#   on  = deficit-round-robin across tenant lanes, each batch priced
#         by its ICI/DCN occupancy through the fitted per-rail cost
#         model and charged against the tenant's weighted share.
# Single-tenant worlds are bitwise-identical either way (one lane
# degenerates to seq order).  See docs/multitenant.md.
SVC_ARBITER = "SVC_ARBITER"  # off (default) | on
# This process's tenant name (stamped into every TraceContext and
# Submission).  Unset = derived from the submission's process set
# (``ps:<r0>-<rN>``) when one is attached, else "default".
SVC_TENANT = "SVC_TENANT"
# Per-tenant in-flight cap: how many submissions one tenant may have
# queued/negotiating/dispatching at once before its submit() calls
# block (admission backpressure instead of unbounded queue growth).
# 0 (default) = unbounded, the PR 14 behavior.
SVC_TENANT_INFLIGHT = "SVC_TENANT_INFLIGHT"
# Seconds an admission-throttled submit() waits before being admitted
# anyway (with svc.tenant.admission_timeouts counted) — backpressure
# must slow a producer, never wedge it.  Default 30.
SVC_ADMIT_TIMEOUT = "SVC_ADMIT_TIMEOUT"
# Tenant weights for the deficit-round-robin scheduler:
# "tenantA:2,tenantB:1" (unlisted tenants weigh 1).  A tenant's share
# of the priced rail seconds per scheduling round is proportional to
# its weight.
SVC_TENANT_WEIGHTS = "SVC_TENANT_WEIGHTS"
# DRR quantum in microseconds of priced rail time added to each lane's
# deficit per scheduling round (default 500).  Smaller = finer
# interleaving; any single batch still dispatches once its lane's
# deficit accumulates past its price, so progress is unconditional.
SVC_ARBITER_QUANTUM_US = "SVC_ARBITER_QUANTUM_US"
# Priority preemption bound: when a high-priority tenant requests
# preemption (Arbiter.request_preempt), lower-priority lanes' admission
# stays gated for at most this many service cycles (default 50) even
# if the high-priority backlog never drains — preemption is bounded,
# never a starvation primitive.  Priorities ride the weights knob:
# "tenantA:4" outranks "tenantB:1" (higher weight = higher priority).
SVC_PREEMPT_CYCLES = "SVC_PREEMPT_CYCLES"
# Seconds per service-tuner scoring window (default 0.25).
SVC_TUNE_WINDOW = "SVC_TUNE_WINDOW"
# --- elastic inference serving plane (horovod_tpu/serve/) ----------
# Request-level admission cap: how many accepted-but-unfinished
# requests one replica's batcher may hold before submit() blocks
# (admission backpressure through the arbiter lanes, the request-level
# twin of SVC_TENANT_INFLIGHT).  Default 64; 0 = unbounded.
SERVE_INFLIGHT = "SERVE_INFLIGHT"
# Maximum decode batch: how many active sequences one continuous-
# batching decode step advances together (default 8).
SERVE_BATCH = "SERVE_BATCH"
# KV-cache pool capacity in tokens per replica (default 4096); a full
# pool evicts finished sequences LRU-first and otherwise backpressures
# prefill admission.
SERVE_KV_TOKENS = "SERVE_KV_TOKENS"
# Wire format for the serving plane's tensor-parallel hops
# ("off" | "bf16" | "int8" | "fp8", default off).  EF-free quantized
# wires are exactly right here: inference TP exchanges carry no
# optimizer state to drift.
SERVE_WIRE = "SERVE_WIRE"
# ResponseCache capacity (entries).  Shares the reference's
# HOROVOD_CACHE_CAPACITY knob (common.h:118, response_cache.cc);
# 0 disables the cache (every submission renegotiates + re-lowers).
# CACHE_CAPACITY is declared above with the legacy knob block.
# Persistent schedule autotuning database (sched/store.py): JSON file
# recording converged (bucket_bytes, wire, lowering) per (schedule
# signature, topology, jax version, knob fingerprint); ScheduleTuner
# warm-starts from a hit.  Unset = no persistence (PR 6 behavior).
TUNE_DB = "TUNE_DB"
# A stored schedule is invalidated when the current (fitted) cost
# model's price for it disagrees with the recorded one by more than
# this factor in either direction.
TUNE_STALE_FACTOR = "TUNE_STALE_FACTOR"  # default 4.0
# End-to-end exchange tracing (trace/): span-based host-side tracing of
# the whole submission path (queue -> negotiation -> cache -> lowering
# -> rail phases) plus the per-rank flight recorder.
#   off     = every span call is a shared no-op (zero allocation);
#   summary = (default) spans feed the trace.phase_seconds.* histograms
#             and the flight-recorder ring, no per-span file output;
#   full    = summary + each rank streams its span trees as Chrome-
#             trace JSON (trace_rank<r>.json under HVD_TPU_TRACE_DIR,
#             mergeable by tools/merge_timeline.py).
# Tracing is host-side only: it inserts no ops into a traced step, so
# losses are bitwise identical at every level.  See docs/tracing.md.
TRACE = "TRACE"
# Directory the tracer and flight recorder write to (per-rank Chrome
# traces at level=full; anomaly dump JSON at any non-off level).
# Unset = dumps stay in memory (the last one is queryable), no file IO.
TRACE_DIR = "TRACE_DIR"
# Flight-recorder ring capacity: the last N steps' span trees kept per
# rank for anomaly dumps (default 16).
TRACE_RING = "TRACE_RING"
# Anomaly threshold: a step slower than z x the rolling p50 of recent
# step times dumps the ring (default 3.0).
TRACE_ANOMALY_Z = "TRACE_ANOMALY_Z"
# Cross-rank straggler threshold on the driver: a rank whose per-phase
# p50 exceeds z x the median rank's p50 is flagged in the /trace
# summary and the trace.straggler{rank=,phase=} gauges (default 2.0).
TRACE_STRAGGLER_Z = "TRACE_STRAGGLER_Z"
# On-disk flight-dump retention: keep only the newest N
# flight_rank<r>_*.json anomaly dumps per rank under HVD_TPU_TRACE_DIR,
# deleting oldest-first after each dump (default 64; 0 = unbounded).
# Pruned files bump the trace.dumps_pruned counter.
TRACE_DUMP_KEEP = "TRACE_DUMP_KEEP"
# Async-service negotiation stall timeout (seconds, default 60): a
# submission stuck in negotiation past this emits a svc.stall warning
# naming the missing participants (the PR 2 stall inspector extended to
# the service's producer-level bitvector).
STALL_TIMEOUT = "STALL_TIMEOUT"
# Stall escalation: after this many CONSECUTIVE stalled check intervals
# the negotiator abandons the entry and every posted participant's
# future resolves through the inline-fallback path (counter
# svc.stall_abandoned + a svc_stall_abandon event) — a permanently
# missing participant can never wedge multi-participant producers.
# 0 (default) = warn forever, never abandon (the pre-PR 16 behavior).
STALL_ABANDON = "STALL_ABANDON"
# Per-tenant SLO specs for the driver-side watchdog (runner/slo.py):
#   "tenantA:step=0.5,p99=0.05;tenantB:p99=0.1"
# step = target per-step exchange seconds (sum of the tenant's
# per-phase p50s from trace.tenant_seconds); p99 = target served-
# latency p99 (the arbiter's svc.tenant.wait_seconds histogram).
# Unset/empty = no watchdog, no remediation.  See docs/multitenant.md.
SLO_SPEC = "SLO_SPEC"
# Breach hysteresis: a tenant must breach the same target for this many
# CONSECUTIVE evaluation windows before the watchdog confirms it
# (default 3) — one noisy sample never triggers a remediation.
SLO_WINDOWS = "SLO_WINDOWS"
# Seconds between driver-side SLO evaluations (default 5).
SLO_CHECK_INTERVAL = "SLO_CHECK_INTERVAL"
# Seconds a tenant's remediation ladder holds at a rung before a
# still-confirmed breach escalates to the next rung (default 30) —
# every rung gets time to take effect before a costlier one fires.
SLO_COOLDOWN = "SLO_COOLDOWN"
# Remediation execution bounds (elastic/remediate.py): per-phase
# attempt timeout in seconds (default 30) and attempts per phase
# (default 2) for the RetryPolicy every escalation rung runs under.
REMEDIATE_TIMEOUT = "REMEDIATE_TIMEOUT"
REMEDIATE_RETRIES = "REMEDIATE_RETRIES"
# Device-time profiling plane (prof/): compiled-step introspection (XLA
# cost/memory analysis per program signature), the per-step host-gap
# profiler, online MFU gauges, and the perf-regression sentinel.
#   on  = (default) everything above; host-side only — profiling
#         inserts no ops into any compiled program, so losses are
#         bitwise identical on vs off.
#   off = every prof call is a no-op; executors are returned unwrapped
#         (exactly the pre-profiling code path).
PROF = "PROF"
# Persistent perf-baseline database (prof/baseline.py): JSON file
# (ScheduleStore machinery, entry kind "prof_baseline") recording
# step-time p50 / MFU / rail-busy per (workload signature, topology,
# knob fingerprint).  Unset = sentinel observes but never persists or
# compares ("no_baseline" verdicts).
PROF_DB = "PROF_DB"
# Regression threshold factor (default 1.5): the sentinel flags a
# regression when observed step p50 exceeds baseline x factor, or
# observed MFU falls below baseline / factor.
PROF_REGRESS_FACTOR = "PROF_REGRESS_FACTOR"
# Sentinel check cadence in steps (default 20); 0 = never auto-check
# (explicit Sentinel.check() only, e.g. from tests or the smoke).
PROF_CHECK_EVERY = "PROF_CHECK_EVERY"
# Directory for jax.profiler capture windows triggered by a confirmed
# perf regression or SLO breach.  Unset (default) = capture hooks are
# inert — no profiler trace is ever started.
PROF_CAPTURE_DIR = "PROF_CAPTURE_DIR"
# Capture-window length in seconds (default 5) and the maximum number
# of capture windows per process (default 2) — a flapping sentinel can
# never fill the disk with profiler traces.
PROF_CAPTURE_SECS = "PROF_CAPTURE_SECS"
PROF_CAPTURE_MAX = "PROF_CAPTURE_MAX"

# Launcher-provided rendezvous env (analog of reference gloo_run.py:65-103).
RANK = "RANK"
SIZE = "SIZE"
LOCAL_RANK = "LOCAL_RANK"
LOCAL_SIZE = "LOCAL_SIZE"
CROSS_RANK = "CROSS_RANK"
CROSS_SIZE = "CROSS_SIZE"
HOSTNAME = "HOSTNAME"
RENDEZVOUS_ADDR = "RENDEZVOUS_ADDR"
RENDEZVOUS_PORT = "RENDEZVOUS_PORT"
COORDINATOR_ADDR = "COORDINATOR_ADDR"  # jax.distributed coordinator

DEFAULT_FUSION_THRESHOLD = 64 * 1024 * 1024
# Fusion buffers are padded to this many bytes (reference common.h:146
# FUSION_BUFFER_ATOMIC_UNIT = 64); on TPU we align to the fp32 lane tile.
FUSION_BUFFER_ATOMIC_UNIT = 512


def _names(name: str) -> tuple[str, str]:
    return "HVD_TPU_" + name, "HOROVOD_" + name


def get_env(name: str, default: Optional[str] = None) -> Optional[str]:
    """Read a knob, preferring HVD_TPU_<name>, falling back to HOROVOD_<name>."""
    new, legacy = _names(name)
    val = os.environ.get(new)
    if val is None:
        val = os.environ.get(legacy)
    return default if val is None else val


def get_int(name: str, default: int) -> int:
    val = get_env(name)
    if val is None or val == "":
        return default
    try:
        return int(val)
    except ValueError:
        return default


def get_float(name: str, default: float) -> float:
    val = get_env(name)
    if val is None or val == "":
        return default
    try:
        return float(val)
    except ValueError:
        return default


def get_bool(name: str, default: bool = False) -> bool:
    val = get_env(name)
    if val is None or val == "":
        return default
    return val.strip().lower() in ("1", "true", "yes", "on")


def set_env(name: str, value: str) -> None:
    os.environ["HVD_TPU_" + name] = value
