"""Zero-downtime elastic remesh: reshard live training state across
membership changes instead of restarting.

Built on the validated :func:`reinit_world` probe (``tools/
probe_remesh.py`` → ``tools/probe_remesh_findings.json``): after a full
XLA backend reset (``jax.extend.backend.clear_backends``),
``jax.distributed`` accepts a fresh ``initialize()`` with a *different*
world in the same process — so a membership-change survivor CAN re-mesh
without respawning.  Horovod's elastic mode (arXiv:1802.05799) survives
membership changes by tearing workers down and restoring from
checkpoint; every distributed state we hold — ZeRO-1 optimizer shards
(arXiv:2004.13336, ``sched/zero1._BucketLayout``), EF residuals
(``optim/distributed_optimizer.DistributedOptimizerState.residual``),
bucket plans (``sched/plan.py``) — has a *deterministic* per-rank
layout, so a remesh is a computable shard exchange plus a plan rebuild,
not a checkpoint round-trip.

Three layers live here:

1. **Shard math** — :class:`ShardLayout` / :func:`plan_moves` compute
   the old-layout→new-layout movement of one flat sharded buffer as a
   deterministic interval exchange (a partition of the valid elements:
   every byte moves exactly once, verified by the layout-exchange unit
   tests).  :func:`plan_reshard` lifts that to whole bucket schedules
   (``sched/zero1.bucket_layouts``), validating that old and new plans
   agree on bucket membership (they must — the plan is a pure function
   of gradient metadata, not of world size).
2. **State movement** — :class:`KVShardStore` ships host shard blobs
   through the launcher KV store (chunked + sha256-checksummed, the
   general case covering disjoint old/new worlds);
   :func:`apply_moves` / :func:`reshard_bucket_state` reassemble a new
   rank's shard (and per-bucket optimizer-state pytrees) from fetched
   old shards, raising :class:`~horovod_tpu.exceptions.
   ShardChecksumError` on any integrity mismatch.  When old and new
   worlds overlap, the same plan drives an in-mesh ``all_to_all`` fast
   path — host-side KV is the fallback that always works.
3. **The worker pipeline** — :func:`run_remesh` sequences the phases
   (pause → snapshot → publish → barrier → reinit → fetch → rebuild)
   with per-phase ``remesh.*`` metrics, elastic event-log entries, and
   a ``REMESH`` timeline lane; any failure raises
   :class:`~horovod_tpu.exceptions.RemeshError` and the caller
   (``elastic/run.py``) falls back to the checkpoint-restore restart
   path — the remesh is an optimization, never a new way to wedge.

Use :func:`reinit_world` from a surviving worker after the launcher
hands it the new world description; all live jax Arrays from the old
backend become invalid — restore state from host copies or the KV
store (``elastic.State`` commits are host-side for exactly this
reason).
"""

from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..exceptions import RemeshError, ShardChecksumError
from ..utils.logging import get_logger


def reinit_world(
    coordinator_address: Optional[str] = None,
    num_processes: Optional[int] = None,
    process_id: Optional[int] = None,
) -> None:
    """Tear down the runtime + XLA backend and rejoin a new world.

    With no arguments, re-initializes single-process (the surviving
    worker continues alone on its local devices).  Passing the new
    coordination triple rejoins a resized multi-process world.

    EXPERIMENTAL: relies on ``jax.extend.backend.clear_backends``
    (internal-adjacent API).  Every jax Array created before the call
    is invalidated.
    """
    import jax

    from .. import runtime as _rt

    # Validate inputs and resolve the backend-reset entry point BEFORE
    # any teardown — failing after shutdown would strand the survivor
    # with no runtime at all.
    if coordinator_address is not None and (
        num_processes is None or process_id is None
    ):
        raise ValueError(
            "reinit_world: coordinator_address requires num_processes "
            "and process_id (a partial triple would silently fall back "
            "to a single-process world)"
        )
    reset = None
    try:
        from jax.extend import backend as _xb

        reset = getattr(_xb, "clear_backends", None)
    except ImportError:
        pass
    if reset is None:
        reset = getattr(jax, "clear_backends", None)
    if reset is None:
        raise RuntimeError(
            "reinit_world: this JAX exposes no backend-reset entry "
            "point (neither jax.extend.backend.clear_backends nor "
            "jax.clear_backends); use the respawn-per-round path"
        )

    _rt.shutdown()
    try:
        jax.distributed.shutdown()
    except Exception:  # not initialized / already down
        pass
    reset()

    # Clear BOTH env spellings the knob layer reads (utils/env.py
    # falls back from HVD_TPU_* to HOROVOD_*).
    for name in ("COORDINATOR_ADDR", "CROSS_RANK", "CROSS_SIZE"):
        os.environ.pop("HVD_TPU_" + name, None)
        os.environ.pop("HOROVOD_" + name, None)
    if coordinator_address is not None:
        os.environ["HVD_TPU_COORDINATOR_ADDR"] = coordinator_address
        os.environ["HVD_TPU_CROSS_SIZE"] = str(num_processes)
        os.environ["HVD_TPU_CROSS_RANK"] = str(process_id)
    get_logger().warning(
        "reinit_world: backend reset, rejoining world "
        "(coordinator=%s, processes=%s)",
        coordinator_address or "<single-process>", num_processes or 1,
    )
    _rt.init()


# =====================================================================
# 1. Shard math: deterministic old-layout -> new-layout interval moves
# =====================================================================


@dataclasses.dataclass(frozen=True)
class ShardLayout:
    """Layout of one flat buffer sharded contiguously over ranks.

    ``n`` valid elements, padded up to ``shards * shard_len``; rank
    ``r`` holds global elements ``[r*shard_len, (r+1)*shard_len)`` —
    exactly the ``sched/zero1._BucketLayout`` convention (``lowering=
    "flat"``: shards == world; ``"hier"``: shards == slice_size with
    the shard replicated across slices — either way the global
    element->rank map below is the layout's own)."""

    n: int
    shards: int
    shard_len: int

    def __post_init__(self):
        if self.shards < 1 or self.shard_len < 0 or self.n < 0:
            raise RemeshError(
                f"invalid shard layout n={self.n} shards={self.shards} "
                f"shard_len={self.shard_len}"
            )
        if self.n > self.shards * self.shard_len:
            raise RemeshError(
                f"shard layout too small: n={self.n} > "
                f"{self.shards}x{self.shard_len}"
            )

    @property
    def padded(self) -> int:
        return self.shards * self.shard_len

    def interval(self, rank: int) -> Tuple[int, int]:
        """Global ``[start, stop)`` of VALID elements rank holds (may be
        empty when the whole shard is padding)."""
        if not 0 <= rank < self.shards:
            raise RemeshError(
                f"rank {rank} out of range for {self.shards} shards"
            )
        start = rank * self.shard_len
        return min(start, self.n), min(start + self.shard_len, self.n)


@dataclasses.dataclass(frozen=True)
class Move:
    """One interval of a destination shard, sourced from one old rank.

    Offsets are shard-relative: copy ``length`` elements from the
    source rank's shard at ``src_off`` into the destination shard at
    ``dst_off``."""

    src_rank: int
    src_off: int
    dst_off: int
    length: int


def plan_moves(old: ShardLayout, new: ShardLayout,
               dst_rank: int) -> List[Move]:
    """Shard-exchange plan for one destination rank: which slices of
    which old ranks' shards assemble the new shard.

    Deterministic, pure, and a *partition*: across all ``dst_rank``
    values the moves cover every valid element exactly once (the
    layout-exchange unit tests assert this), so the exchange is a
    permutation of the data — checksums are preserved by construction.
    Elements past ``new.interval(dst_rank)`` are padding and are
    zero-filled by :func:`apply_moves`, never moved.
    """
    if old.n != new.n:
        raise RemeshError(
            f"reshard changes valid length: {old.n} != {new.n}"
        )
    lo, hi = new.interval(dst_rank)
    moves: List[Move] = []
    pos = lo
    while pos < hi:
        src_rank = pos // old.shard_len if old.shard_len else 0
        src_lo, src_hi = old.interval(src_rank)
        take = min(hi, src_hi) - pos
        if take <= 0:  # defensive: implies old layout inconsistency
            raise RemeshError(
                f"shard plan stuck at {pos} (old={old}, new={new})"
            )
        moves.append(Move(
            src_rank=src_rank,
            src_off=pos - src_rank * old.shard_len,
            dst_off=pos - dst_rank * new.shard_len,
            length=take,
        ))
        pos += take
    return moves


def apply_moves(
    moves: Sequence[Move],
    dst_len: int,
    dtype: Any,
    fetch: Callable[[int], np.ndarray],
) -> np.ndarray:
    """Assemble one destination shard from ``fetch(src_rank)`` host
    arrays.  Unsourced positions (padding) are zero.  A fetched shard
    that is too short for a planned move raises :class:`RemeshError`
    (the caller falls back to checkpoint restore)."""
    out = np.zeros((dst_len,), dtype=dtype)
    for m in moves:
        src = np.asarray(fetch(m.src_rank)).reshape(-1)
        if m.src_off + m.length > src.size:
            raise RemeshError(
                f"source shard from rank {m.src_rank} too short: need "
                f"[{m.src_off}:{m.src_off + m.length}), have {src.size}"
            )
        out[m.dst_off:m.dst_off + m.length] = (
            src[m.src_off:m.src_off + m.length]
        )
    return out


def reshard_shards(
    shards: Sequence[np.ndarray],
    old: ShardLayout,
    new: ShardLayout,
) -> List[np.ndarray]:
    """Re-partition one buffer's per-rank shard arrays from ``old`` to
    ``new`` in process — the slice-handoff executor of the SLO
    remediation ladder (``elastic/remediate.py``): a donor tenant's
    shrink and a recipient's grow are each ONE call through the same
    :func:`plan_moves`/:func:`apply_moves` pipeline the cross-process
    remesh rides, so the handoff inherits its permutation guarantee —
    every valid element lands exactly once, checksums preserved by
    construction.  Raises :class:`RemeshError` (caller rolls back) when
    the supplied shards do not match the old layout."""
    if len(shards) != old.shards:
        raise RemeshError(
            f"have {len(shards)} shard(s) for a {old.shards}-shard "
            "layout"
        )
    srcs = [np.asarray(s).reshape(-1) for s in shards]
    for r, s in enumerate(srcs):
        if s.size < old.shard_len:
            raise RemeshError(
                f"source shard {r} too short: {s.size} < "
                f"{old.shard_len}"
            )
    dtype = srcs[0].dtype if srcs else np.float32
    return [
        apply_moves(
            plan_moves(old, new, dst), new.shard_len, dtype,
            lambda src_rank: srcs[src_rank],
        )
        for dst in range(new.shards)
    ]


# =====================================================================
# 2. Bucket-schedule resharding (ZeRO-1 optimizer shards + EF state)
# =====================================================================


@dataclasses.dataclass(frozen=True)
class BucketReshard:
    """Reshard recipe for one bucket: the old/new flat layouts plus the
    bucket identity fields both plans must agree on."""

    indices: Tuple[int, ...]
    dtype: str
    old: ShardLayout
    new: ShardLayout


@dataclasses.dataclass(frozen=True)
class RemeshPlan:
    """Per-bucket reshard recipes for one parameter tree, old world ->
    new world.  Pure function of the two bucket-layout lists — every
    rank (and the driver) computes the identical plan."""

    buckets: Tuple[BucketReshard, ...]

    def moves_for(self, bucket: int, dst_rank: int) -> List[Move]:
        b = self.buckets[bucket]
        return plan_moves(b.old, b.new, dst_rank)

    def src_ranks(self, dst_rank: int) -> List[int]:
        """All old ranks the destination rank needs shards from."""
        out: set = set()
        for bi in range(len(self.buckets)):
            for m in self.moves_for(bi, dst_rank):
                out.add(m.src_rank)
        return sorted(out)


def _layout_of(lay: Any) -> ShardLayout:
    """A ``sched/zero1._BucketLayout`` (or anything with n/shards/
    shard_len) as a :class:`ShardLayout`."""
    return ShardLayout(
        n=int(lay.n), shards=int(lay.shards), shard_len=int(lay.shard_len)
    )


def plan_reshard(old_layouts: Sequence[Any],
                 new_layouts: Sequence[Any]) -> RemeshPlan:
    """Build the :class:`RemeshPlan` from two bucket-layout lists
    (``sched/zero1.bucket_layouts`` for the old and new worlds).

    Bucket membership is a pure function of gradient metadata — not of
    world size — so the two schedules MUST pair up bucket-for-bucket
    (same leaf ``indices``, same dtype, same valid length).  Any
    disagreement raises :class:`RemeshError`: the state cannot be
    exchanged shard-wise and the caller falls back to the checkpoint
    path.
    """
    if len(old_layouts) != len(new_layouts):
        raise RemeshError(
            f"bucket count changed across worlds: "
            f"{len(old_layouts)} != {len(new_layouts)} (plan must be "
            "world-size independent)"
        )
    buckets = []
    for bi, (o, nw) in enumerate(zip(old_layouts, new_layouts)):
        if tuple(o.indices) != tuple(nw.indices):
            raise RemeshError(
                f"bucket {bi} membership changed: {o.indices} != "
                f"{nw.indices}"
            )
        if str(o.dtype) != str(nw.dtype):
            raise RemeshError(
                f"bucket {bi} dtype changed: {o.dtype} != {nw.dtype}"
            )
        buckets.append(BucketReshard(
            indices=tuple(int(i) for i in o.indices),
            dtype=str(o.dtype),
            old=_layout_of(o),
            new=_layout_of(nw),
        ))
    return RemeshPlan(buckets=tuple(buckets))


def reshard_bucket_state(
    plan: RemeshPlan,
    bucket: int,
    dst_rank: int,
    fetch_state: Callable[[int], Any],
) -> Any:
    """Reshard one bucket's optimizer-state pytree to ``dst_rank``.

    ``fetch_state(src_rank)`` returns that old rank's HOST pytree for
    this bucket (e.g. one entry of ``bucketed_zero_step``'s state
    tuple, ``jax.device_get``-ed).  Leaves whose leading dimension is
    the old shard length (Adam ``m``/``v``, the parameter shard) are
    moved through the interval plan; everything else (step counters,
    scalars — replicated across ranks) is taken verbatim from the
    lowest-numbered source rank.  EF residual leaves (``"ef"``, shaped
    ``(old padded,)``) are re-zeroed: the residual is a *rank-local*
    quantization error and has no meaning under a new partition —
    zeros are safe (plain quantization until feedback refills).
    """
    import jax

    b = plan.buckets[bucket]
    moves = plan.moves_for(bucket, dst_rank)
    srcs = sorted({m.src_rank for m in moves}) or [0]
    cache: Dict[int, Any] = {}

    def state_of(rank: int) -> Any:
        if rank not in cache:
            cache[rank] = fetch_state(rank)
        return cache[rank]

    ref = state_of(srcs[0])

    def is_ef_dict(x):
        return isinstance(x, dict) and set(x) == {"tx", "ef"}

    if is_ef_dict(ref):
        new_ef = np.zeros((b.new.padded,), np.float32)
        tx = reshard_bucket_state(
            plan, bucket, dst_rank,
            lambda r: state_of(r)["tx"],
        )
        return {"tx": tx, "ef": new_ef}

    leaves, treedef = jax.tree.flatten(ref)
    out = []
    for li, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        if arr.ndim >= 1 and arr.shape[0] == b.old.shard_len:

            def fetch(src_rank: int, _li=li) -> np.ndarray:
                peer = jax.tree.leaves(state_of(src_rank))[_li]
                return np.asarray(peer).reshape(-1)

            out.append(apply_moves(
                moves, b.new.shard_len, arr.dtype, fetch
            ))
        else:
            out.append(arr)
    return jax.tree.unflatten(treedef, out)


def full_buffer(layout: ShardLayout,
                shards: Dict[int, np.ndarray]) -> np.ndarray:
    """Reassemble the valid flat buffer from per-rank shards (test and
    checksum helper: ``full_buffer(old, ...) == full_buffer(new, ...)``
    is the exchange-correctness invariant)."""
    parts = []
    for r in range(layout.shards):
        lo, hi = layout.interval(r)
        if hi > lo:
            parts.append(np.asarray(shards[r]).reshape(-1)[: hi - lo])
    if not parts:
        return np.zeros((0,), np.float32)
    return np.concatenate(parts)


# =====================================================================
# 3. Host-side shard movement through the launcher KV store
# =====================================================================


def _digest(data: bytes) -> str:
    return hashlib.sha256(data).hexdigest()


class KVShardStore:
    """Chunked, checksummed shard blobs in the rendezvous KV store.

    The general-case transport of the state exchange: works whether or
    not the old and new jax worlds overlap (survivors publish BEFORE
    the backend reset; joiners fetch AFTER — no live mesh required).
    One scope per remesh attempt so a torn exchange never pollutes the
    next; blobs are chunked under the controller protocol's frame cap
    and carry a sha256 manifest, so a torn or corrupted shard surfaces
    as :class:`ShardChecksumError` — never as silently wrong numerics.
    """

    _CHUNK = 16 << 20  # controller frames cap at 64MB; stay well under

    def __init__(self, client: Any, remesh_id: int):
        self._client = client
        self.scope = f"__remesh_state__{int(remesh_id)}"

    def _key(self, rank: int, name: str) -> str:
        return f"r{int(rank)}.{name}"

    def put(self, rank: int, name: str, arr: np.ndarray) -> None:
        arr = np.ascontiguousarray(arr)
        blob = arr.tobytes()
        from .. import faults

        if faults.inject("remesh.publish", rank=rank, name=name):
            # cooperative corruption: damage the payload after the
            # manifest digest is computed from the good bytes, so the
            # receiver's checksum verification MUST catch it
            blob = (b"\x00" * 8 + blob[8:]) if len(blob) >= 8 else b"\xff"
        key = self._key(rank, name)
        n = max(1, (len(blob) + self._CHUNK - 1) // self._CHUNK)
        for i in range(n):
            self._client.put(
                self.scope, f"{key}.chunk{i}",
                blob[i * self._CHUNK:(i + 1) * self._CHUNK],
            )
        manifest = json.dumps({
            "chunks": n,
            "bytes": len(arr.tobytes()),
            "sha256": _digest(arr.tobytes()),
            "dtype": str(arr.dtype),
            "shape": list(arr.shape),
        })
        self._client.put(self.scope, key, manifest.encode())

    def get(self, rank: int, name: str,
            timeout_ms: int = 10000) -> np.ndarray:
        key = self._key(rank, name)
        raw = self._client.get(self.scope, key, timeout_ms=timeout_ms)
        if raw is None:
            raise RemeshError(
                f"shard {key} missing from {self.scope} (source rank "
                "died before publishing?)"
            )
        manifest = json.loads(raw.decode())
        parts = []
        for i in range(int(manifest["chunks"])):
            chunk = self._client.get(
                self.scope, f"{key}.chunk{i}", timeout_ms=timeout_ms
            )
            if chunk is None:
                raise RemeshError(f"shard {key} chunk {i} missing")
            parts.append(chunk)
        blob = b"".join(parts)[: int(manifest["bytes"])]
        if _digest(blob) != manifest["sha256"]:
            raise ShardChecksumError(
                f"shard {key}: sha256 mismatch after transport"
            )
        return np.frombuffer(
            blob, dtype=np.dtype(manifest["dtype"])
        ).reshape(manifest["shape"]).copy()


# =====================================================================
# Remesh request + worker-side pipeline instrumentation
# =====================================================================


@dataclasses.dataclass(frozen=True)
class RemeshRequest:
    """The driver's broadcast describing one remesh attempt: the new
    world triple plus the old->new rank mapping."""

    remesh_id: int
    round_id: int
    np_old: int
    np_new: int
    coordinator_addr: str
    # old rank -> new rank for survivors (absent = shed); joiners get
    # new ranks not in the mapping's values.
    survivors: Dict[int, int]
    deadline_s: float = 60.0
    # Device worlds, when they differ from np * devices-per-process
    # (e.g. the single-process device-subset resize): None defaults to
    # the constant-devices-per-process fleet convention.
    dev_old: Optional[int] = None
    dev_new: Optional[int] = None

    def new_rank(self, old_rank: int) -> Optional[int]:
        return self.survivors.get(int(old_rank))

    def to_json(self) -> str:
        d = dataclasses.asdict(self)
        d["survivors"] = {str(k): v for k, v in self.survivors.items()}
        return json.dumps(d)

    @classmethod
    def from_json(cls, raw: str) -> "RemeshRequest":
        d = json.loads(raw)
        d["survivors"] = {
            int(k): int(v) for k, v in d.get("survivors", {}).items()
        }
        return cls(**d)


PHASES = ("pause", "snapshot", "publish", "barrier", "reinit",
          "fetch", "rebuild")


@contextlib.contextmanager
def remesh_phase(phase: str, **ctx: Any):
    """Instrument one remesh phase: ``remesh.phase.<name>`` counter,
    ``remesh.phase_seconds`` histogram, a REMESH timeline-lane event,
    an elastic event-log entry, and a fault-injection site
    (``remesh.<phase>``) — so a postmortem shows exactly which phase
    failed, and tests can fail any phase on demand."""
    from .. import events, faults, metrics
    from ..runtime import get_runtime_or_none

    faults.inject(f"remesh.{phase}", **ctx)
    metrics.inc_counter(f"remesh.phase.{phase}")
    events.emit(events.REMESH_PHASE, phase=phase, **ctx)
    rt = get_runtime_or_none()
    tl = rt.timeline if rt is not None else None
    if tl is not None:
        tl.begin(f"remesh.{phase}", "REMESH")
    t0 = time.perf_counter()
    try:
        yield
    finally:
        metrics.observe(
            "remesh.phase_seconds", time.perf_counter() - t0
        )
        rt = get_runtime_or_none()
        tl2 = rt.timeline if rt is not None else None
        if tl2 is tl and tl is not None:
            tl.end(f"remesh.{phase}", "REMESH")


def run_remesh(state: Any, manager: Any, request: RemeshRequest) -> None:
    """Worker-side remesh pipeline, called from the elastic retry loop
    (``elastic/run.py``) when a :class:`~horovod_tpu.exceptions.
    RemeshInterrupt` lands at a step boundary.

    Phases (each wrapped in :func:`remesh_phase` instrumentation):

    1. **pause** — ack the driver's request through the heartbeat/KV
       channel; the step boundary is the pause point.
    2. **snapshot** — ``state.save()`` snapshots replicated attrs to
       host; registered *sharded* attrs (``state.sharded_attrs``) are
       ``device_get``-ed per bucket shard.
    3. **publish** — this rank's shards go into the
       :class:`KVShardStore` (general-case transport: survivors
       publish before the backend reset so joiners — and survivors
       whose new shard needs foreign intervals — can fetch after).
    4. **barrier** — wait until every survivor published (the driver
       flips the ``go`` key once all snapshot acks are in).
    5. **reinit** — shed ranks exit cleanly; survivors
       :func:`reinit_world` into the new triple.
    6. **fetch/rebuild** — reassemble this rank's new shards through
       the :class:`RemeshPlan` and hand them back to the state
       (``state.import_sharded``); replicated attrs restore from the
       host snapshot.

    Any exception is re-raised as :class:`RemeshError` after emitting
    ``remesh.fallback`` bookkeeping — the caller degrades to the
    checkpoint-restore restart path.  A shed rank (not in
    ``request.survivors``) raises :class:`SystemExit` with the shed
    exit code after the publish barrier; the driver treats that exit
    as a clean departure, not a failure.
    """
    from .. import events, metrics

    old_rank = manager.rank
    new_rank = request.new_rank(old_rank)
    metrics.inc_counter("remesh.attempts")
    events.emit(
        events.REMESH_START, remesh_id=request.remesh_id,
        np_old=request.np_old, np_new=request.np_new,
        old_rank=old_rank, new_rank=new_rank,
    )
    # Flight-recorder anomaly trigger (trace/): a membership change is
    # a step-time discontinuity — dump the pre-remesh span ring so the
    # postmortem can see what the exchange path looked like before.
    from .. import trace as _trace

    _trace.trigger_dump(
        "remesh", remesh_id=request.remesh_id,
        np_old=request.np_old, np_new=request.np_new,
    )
    store = KVShardStore(manager.kv_client(), request.remesh_id)
    try:
        with remesh_phase("pause", remesh_id=request.remesh_id,
                          rank=old_rank):
            # Quiesce the exchange service at the pause point: every
            # in-flight submission (a delayed DCN hop, a tenant's
            # eager program) resolves before state snapshots, and the
            # service restarts lazily against the NEW mesh after
            # reinit — its cached executors must not cross a world
            # change.
            from .. import svc as _svc

            _svc.drain(timeout_s=request.deadline_s)
            _svc.reset_service()
            manager.remesh_ack(request.remesh_id, "pause")

        sharded = getattr(state, "sharded_attrs", lambda: {})()
        with remesh_phase("snapshot", rank=old_rank):
            state.save()
            for spec in sharded.values():
                spec.snapshot()

        with remesh_phase("publish", rank=old_rank):
            for name, spec in sharded.items():
                spec.publish(store, name, old_rank)
            manager.remesh_ack(request.remesh_id, "snapshot")

        with remesh_phase("barrier", rank=old_rank):
            manager.remesh_wait_go(
                request.remesh_id, timeout_s=request.deadline_s
            )

        if new_rank is None:
            # Shed: our shards are published; leave the mesh cleanly.
            # ("shed", not "done" — done keys are keyed by NEW ranks
            # and a shed worker's old rank could collide with one.)
            metrics.inc_counter("remesh.shed")
            manager.remesh_ack(request.remesh_id, "shed")
            raise SystemExit(REMESH_SHED_CODE)

        with remesh_phase("reinit", rank=old_rank, new_rank=new_rank):
            if request.np_new == 1:
                reinit_world()
            else:
                reinit_world(
                    coordinator_address=request.coordinator_addr,
                    num_processes=request.np_new,
                    process_id=new_rank,
                )
            manager.on_world_changed(new_rank)

        with remesh_phase("fetch", rank=new_rank):
            fetched: Dict[str, Any] = {}
            for name, spec in sharded.items():
                fetched[name] = spec.reshard(
                    request, store, name, new_rank
                )

        with remesh_phase("rebuild", rank=new_rank):
            # restore FIRST (replicated attrs re-device-put from the
            # host snapshot), THEN install the resharded shards — the
            # other order would clobber the exchanged state with the
            # stale old-world snapshot.
            state.restore()
            for name, spec in sharded.items():
                spec.install(fetched[name])
            manager.remesh_ack(request.remesh_id, "done")
        metrics.inc_counter("remesh.success")
        events.emit(
            events.REMESH_OK, remesh_id=request.remesh_id,
            rank=new_rank, np=request.np_new,
        )
    except SystemExit:
        raise
    except RemeshError as e:
        metrics.inc_counter("remesh.fallback")
        events.emit(
            events.REMESH_FALLBACK, remesh_id=request.remesh_id,
            rank=old_rank, error=str(e),
        )
        raise
    except Exception as e:
        metrics.inc_counter("remesh.fallback")
        events.emit(
            events.REMESH_FALLBACK, remesh_id=request.remesh_id,
            rank=old_rank, error=f"{type(e).__name__}: {e}",
        )
        raise RemeshError(
            f"remesh {request.remesh_id} failed: "
            f"{type(e).__name__}: {e}"
        ) from e


# Exit code a shed worker leaves with after a successful remesh hand-
# off: the driver counts it as a clean departure (the worker's state
# was resharded away), NOT a failure — its host is not blacklisted.
REMESH_SHED_CODE = 75


def join_remesh(state: Any, manager: Any,
                request: RemeshRequest) -> None:
    """Worker-side pipeline for a JOINER — a process the driver spawned
    into the new world mid-remesh (``HVD_TPU_REMESH_JOIN``).

    The joiner runs the user script from scratch, so by the time the
    elastic loop calls this its runtime is already initialized in the
    NEW world and its state holds fresh-init values.  All it needs is
    the fetch/rebuild tail of :func:`run_remesh`: reassemble its shard
    of every registered sharded attribute from the survivors' published
    blobs (replicated attributes arrive through the normal ``sync()``
    broadcast afterwards).  Failures raise :class:`RemeshError`; the
    caller exits for a restart round — a joiner has no state to lose.
    """
    from .. import events, metrics

    new_rank = manager.rank
    metrics.inc_counter("remesh.joins")
    events.emit(
        events.REMESH_START, remesh_id=request.remesh_id,
        np_old=request.np_old, np_new=request.np_new,
        old_rank=None, new_rank=new_rank, join=True,
    )
    store = KVShardStore(manager.kv_client(), request.remesh_id)
    sharded = getattr(state, "sharded_attrs", lambda: {})()
    try:
        with remesh_phase("snapshot", rank=new_rank, join=True):
            for spec in sharded.values():
                spec.snapshot()  # fresh-init treedefs/layouts only
        with remesh_phase("fetch", rank=new_rank, join=True):
            fetched = {
                name: spec.reshard(request, store, name, new_rank)
                for name, spec in sharded.items()
            }
        with remesh_phase("rebuild", rank=new_rank, join=True):
            for name, spec in sharded.items():
                spec.install(fetched[name])
            manager.remesh_ack(request.remesh_id, "done")
        events.emit(
            events.REMESH_OK, remesh_id=request.remesh_id,
            rank=new_rank, np=request.np_new, join=True,
        )
    except Exception as e:
        metrics.inc_counter("remesh.fallback")
        events.emit(
            events.REMESH_FALLBACK, remesh_id=request.remesh_id,
            rank=new_rank, join=True,
            error=f"{type(e).__name__}: {e}",
        )
        if isinstance(e, RemeshError):
            raise
        raise RemeshError(
            f"remesh join {request.remesh_id} failed: "
            f"{type(e).__name__}: {e}"
        ) from e


# =====================================================================
# Sharded-state adapters (what a State registers for remesh)
# =====================================================================


class ShardedZeroState:
    """Remesh adapter for a ``sched.bucketed_zero_step`` state tuple
    held on an elastic :class:`~horovod_tpu.elastic.state.State`.

    Registers via ``state.register_sharded("zero", ShardedZeroState(
    state, params_attr="params", states_attr="opt_state"))``.  The
    exchange runs at *process* granularity: each bucket's flat global
    buffer (``padded`` elements, contiguous device shards in
    slice-major order) splits into ``process_count`` equal slabs, the
    old→new slab movement is :func:`plan_moves`' interval exchange, and
    within a process the devices re-shard for free at ``device_put``
    time.  ZeRO leaves whose leading dimension is the slab length move
    through the plan; replicated leaves (step counters) copy from the
    lowest surviving rank; EF residuals (``{"tx","ef"}`` bucket states)
    re-zero — the residual is rank-local quantization error with no
    meaning under a new partition (zeros degrade to plain quantization
    until feedback refills, the documented EF cold-start).
    """

    def __init__(self, state: Any, params_attr: str = "params",
                 states_attr: str = "opt_state", cfg: Any = None):
        self._state = state
        self._params_attr = params_attr
        self._states_attr = states_attr
        self._cfg = cfg
        self._snap: Optional[Dict[str, Any]] = None

    @property
    def owns(self) -> str:
        """The state's attribute this adapter carries across a remesh."""
        return self._states_attr

    # -- helpers ------------------------------------------------------
    def _config(self):
        if self._cfg is not None:
            return self._cfg
        from ..sched.plan import current_config

        return current_config()

    def _proc_layouts(self, world_devices: int,
                      processes: int) -> List[Tuple[Any, ShardLayout]]:
        """(bucket_layout, process-granularity ShardLayout) pairs for a
        device world of ``world_devices`` split over ``processes``."""
        from ..sched.zero1 import bucket_layouts

        params = getattr(self._state, self._params_attr)
        lays = bucket_layouts(params, world_devices, self._config())
        out = []
        for lay in lays:
            if lay.lowering in ("hier", "hier_adasum"):
                # Hier-family buckets replicate their ICI-sharded state
                # across slices — the contiguous-slab exchange below
                # does not describe them.  Degrade honestly: the caller
                # falls back to checkpoint restore
                # (docs/fault_tolerance.md).
                raise RemeshError(
                    "in-place reshard of hierarchically-lowered ZeRO "
                    "buckets is not supported; set "
                    "HVD_TPU_TOPO_LOWER=flat for remeshable jobs or "
                    "rely on the checkpoint fallback"
                )
            padded = int(lay.padded)
            if padded % processes:
                raise RemeshError(
                    f"bucket padded length {padded} does not split "
                    f"over {processes} process slab(s)"
                )
            out.append((lay, ShardLayout(
                n=int(lay.n), shards=processes,
                shard_len=padded // processes,
            )))
        return out

    # -- remesh pipeline hooks ---------------------------------------
    def snapshot(self) -> None:
        """``device_get`` this process's slab of every bucket state
        leaf (full buffers in a single-process world)."""
        import jax

        from ..runtime import get_runtime

        rt = get_runtime()
        states = getattr(self._state, self._states_attr)
        self._old_devices = rt.size
        self._old_processes = rt.process_count
        self._local_devices = len(rt.local_devices)
        host = []
        for st in states:
            leaves, treedef = jax.tree.flatten(st)
            got = []
            for leaf in leaves:
                if hasattr(leaf, "addressable_shards") and \
                        rt.process_count > 1:
                    shards = sorted(
                        leaf.addressable_shards,
                        key=lambda s: (
                            s.index[0].start or 0
                            if s.index and s.index[0].start is not None
                            else 0
                        ),
                    )
                    got.append(np.concatenate(
                        [np.asarray(s.data).reshape(-1) for s in shards]
                    ))
                else:
                    got.append(np.asarray(jax.device_get(leaf)))
            host.append(jax.tree.unflatten(treedef, got))
        self._snap = {"states": host}

    def publish(self, store: KVShardStore, name: str,
                old_rank: int) -> None:
        if self._snap is None:
            raise RemeshError("ShardedZeroState.publish before snapshot")
        import jax

        for bi, st in enumerate(self._snap["states"]):
            for li, leaf in enumerate(jax.tree.leaves(st)):
                store.put(old_rank, f"{name}.b{bi}.l{li}",
                          np.asarray(leaf).reshape(-1)
                          if np.ndim(leaf) else np.asarray(leaf))

    def reshard(self, request: RemeshRequest, store: KVShardStore,
                name: str, new_rank: int) -> List[Any]:
        """Assemble this new rank's per-bucket host state slabs from
        the published old slabs."""
        import jax

        if self._snap is None:
            raise RemeshError("ShardedZeroState.reshard before snapshot")
        # Device worlds derive from the request so the SAME math runs
        # on survivors (snapshot taken in the old world) and joiners
        # (snapshot of their fresh-init state in the new world — used
        # only for treedefs): devices-per-process is the fleet-wide
        # slot convention unless the request pins explicit device
        # worlds (the single-process device-subset resize does).
        dev_per_proc = self._old_devices // self._old_processes
        old_dev = request.dev_old or dev_per_proc * request.np_old
        new_dev = request.dev_new or dev_per_proc * request.np_new
        old_pairs = self._proc_layouts(old_dev, request.np_old)
        new_pairs = self._proc_layouts(new_dev, request.np_new)
        plan = plan_reshard(
            [p for p, _ in old_pairs], [p for p, _ in new_pairs]
        )

        # old process rank -> which OLD rank id to fetch from: the
        # store is keyed by old ranks; survivors published under their
        # old ids, so the plan's src ranks map 1:1.
        out_states: List[Any] = []
        for bi, ((old_lay, old_proc), (new_lay, new_proc)) in enumerate(
            zip(old_pairs, new_pairs)
        ):
            ref = self._snap["states"][bi]
            is_ef = isinstance(ref, dict) and set(ref) == {"tx", "ef"}
            tx_ref = ref["tx"] if is_ef else ref
            moves = plan_moves(old_proc, new_proc, new_rank)
            leaves, treedef = jax.tree.flatten(tx_ref)
            new_leaves = []
            cache: Dict[Tuple[int, int], np.ndarray] = {}

            def fetch(src: int, li: int) -> np.ndarray:
                if (src, li) not in cache:
                    key = (
                        f"{name}.b{bi}.l{li}" if not is_ef
                        else f"{name}.b{bi}.l{li + 1}"
                    )
                    cache[(src, li)] = store.get(src, key)
                return cache[(src, li)]

            for li, leaf in enumerate(leaves):
                arr = np.asarray(leaf)
                if arr.ndim >= 1 and arr.shape[0] == old_proc.shard_len:
                    new_leaves.append(apply_moves(
                        moves, new_proc.shard_len, arr.dtype,
                        lambda src, _li=li: fetch(src, _li),
                    ))
                else:
                    # Replicated leaf (Adam count, hyperparam scalars):
                    # take the PUBLISHED old-rank-0 value, not the
                    # local snapshot — a joiner's fresh-init scalars
                    # (count=0) must not survive into the new world.
                    new_leaves.append(fetch(0, li).reshape(arr.shape)
                                      .astype(arr.dtype))
            tx_new = jax.tree.unflatten(treedef, new_leaves)
            if is_ef:
                # one residual buffer per local device, re-zeroed at
                # the new padded length
                ef = np.zeros(
                    (self._local_devices * new_lay.padded,), np.float32
                )
                out_states.append({"tx": tx_new, "ef": ef})
            else:
                out_states.append(tx_new)
        self._new_layouts = [lay for lay, _ in new_pairs]
        return out_states

    def install(self, host_states: List[Any]) -> None:
        """Device-put the resharded host slabs onto the NEW mesh and
        set them back on the state (must run after ``reinit_world``)."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..runtime import WORLD_AXIS, get_runtime

        rt = get_runtime()
        mesh = rt.mesh
        placed = []
        for st in host_states:
            def put(leaf):
                arr = np.asarray(leaf)
                if arr.ndim == 0:
                    return jax.device_put(
                        arr, NamedSharding(mesh, P())
                    )
                sharding = NamedSharding(mesh, P(WORLD_AXIS))
                if rt.process_count > 1:
                    return jax.make_array_from_process_local_data(
                        sharding, arr
                    )
                return jax.device_put(arr, sharding)

            placed.append(jax.tree.map(put, st))
        setattr(self._state, self._states_attr, tuple(placed))
        self._snap = None
