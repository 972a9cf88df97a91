"""Chrome-tracing timeline (reference ``horovod/common/timeline.{h,cc}``).

The reference feeds a lock-free SPSC queue drained by a dedicated writer
thread producing chrome://tracing JSON with per-tensor NEGOTIATE/QUEUE/op
phases.  On TPU there is no negotiation phase; we record the eager
dispatch lifecycle (ENQUEUE -> compiled-op) per named collective, with
the same JSON format so the file opens in chrome://tracing / Perfetto.
Deep device-level profiling is delegated to ``jax.profiler`` (the
``start_profile``/``stop_profile`` helpers), the TPU-native analog of the
reference's NVTX ranges (``nvtx_op_range.h``).
"""

from __future__ import annotations

import json
import os
import queue
import threading
import time
from typing import Optional


def _resolve_rank() -> int:
    """Best-effort rank for the process-metadata lane: the runtime's
    when initialized, the launcher env otherwise (timelines can start
    before ``hvd.init()``)."""
    try:
        from ..runtime import get_runtime_or_none

        rt = get_runtime_or_none()
        if rt is not None:
            return rt.rank
    except Exception:
        pass
    return int(os.environ.get("HVD_TPU_CROSS_RANK", "0") or 0)


class Timeline:
    """Background-thread JSON writer, mirroring ``TimelineWriter``.

    Mergeable across ranks: the first events are Chrome-trace metadata
    (process/thread names, sort index) plus one ``HVD_PROC_META``
    instant carrying this process's **wall-clock epoch base** in
    microseconds — ``ts`` values stay relative (cheap perf_counter
    deltas on the hot path) and ``tools/merge_timeline.py`` re-bases N
    per-rank traces onto the shared wall clock using that epoch.
    """

    def __init__(self, path: str, rank: Optional[int] = None,
                 queue_size: int = 1_000_000):
        self.path = path
        self._queue: "queue.Queue" = queue.Queue(maxsize=queue_size)
        # Two clocks sampled back to back: perf_counter anchors relative
        # ts, time.time() anchors the merge across processes.
        self._start = time.perf_counter()
        self._epoch_wall_us = time.time() * 1e6
        self.rank = _resolve_rank() if rank is None else int(rank)
        self._drop_logged = False
        self._closed = threading.Event()
        # Line-buffered: a worker killed mid-round (crash, driver
        # terminate) leaves every completed event on disk, so the trace
        # is salvageable for the postmortem merge.
        self._fh = open(path, "w", buffering=1)
        self._fh.write("[\n")
        self._first = True
        self._thread = threading.Thread(
            target=self._drain, name="hvd_tpu_timeline", daemon=True
        )
        self._thread.start()
        self._emit_process_metadata()

    def _emit_process_metadata(self) -> None:
        import socket

        pid = os.getpid()
        hostname = socket.gethostname()
        self._put({"name": "process_name", "ph": "M", "pid": pid,
                   "args": {"name": f"rank {self.rank} ({hostname})"}})
        self._put({"name": "process_sort_index", "ph": "M", "pid": pid,
                   "args": {"sort_index": self.rank}})
        for tid, lane in ((0, "dispatch"), (1, "measured")):
            self._put({"name": "thread_name", "ph": "M", "pid": pid,
                       "tid": tid, "args": {"name": lane}})
        self._put({
            "name": "HVD_PROC_META", "ph": "i", "ts": 0.0, "s": "p",
            "pid": pid, "tid": 0,
            "args": {
                "rank": self.rank, "hostname": hostname, "pid": pid,
                "epoch_wall_us": self._epoch_wall_us,
            },
        })

    def _now_us(self) -> float:
        return (time.perf_counter() - self._start) * 1e6

    def record_op(self, name: str, activity: str, nbytes: int) -> None:
        """One complete event per collective dispatch."""
        self._put(
            {
                "name": name,
                "cat": activity,
                "ph": "X",
                "ts": self._now_us(),
                "dur": 1,
                "pid": os.getpid(),
                "tid": 0,
                "args": {"bytes": int(nbytes), "activity": activity},
            }
        )

    def begin(self, name: str, activity: str) -> None:
        self._put(
            {"name": name, "cat": activity, "ph": "B", "ts": self._now_us(),
             "pid": os.getpid(), "tid": 0}
        )

    def end(self, name: str, activity: str) -> None:
        self._put(
            {"name": name, "cat": activity, "ph": "E", "ts": self._now_us(),
             "pid": os.getpid(), "tid": 0}
        )

    def record_span(self, name: str, activity: str, ts_us: float,
                    dur_us: float, args: Optional[dict] = None) -> None:
        """A MEASURED duration event (reference per-tensor activity
        begin/end records, ``common/timeline.cc``): unlike
        ``record_op``'s dispatch ticks, ``ts``/``dur`` here are real
        device-execution times (profiler-extracted)."""
        self._put(
            {
                "name": name,
                "cat": activity,
                "ph": "X",
                "ts": float(ts_us),
                "dur": max(float(dur_us), 0.001),
                "pid": os.getpid(),
                "tid": 1,  # measured lane, separate from dispatch lane 0
                "args": {"activity": activity, **(args or {})},
            }
        )

    def mark_cycle(self) -> None:
        """Reference ``HOROVOD_TIMELINE_MARK_CYCLES`` instant events."""
        self._put(
            {"name": "CYCLE", "ph": "i", "ts": self._now_us(), "s": "g",
             "pid": os.getpid(), "tid": 0}
        )

    def _put(self, event: dict) -> None:
        if self._closed.is_set():
            return
        try:
            self._queue.put_nowait(event)
        except queue.Full:
            # Drop like the reference's bounded lockfree queue — but
            # visibly: a truncated trace must be diagnosable.
            from .. import metrics

            metrics.inc_counter("timeline.dropped_events")
            if not self._drop_logged:
                self._drop_logged = True
                from .logging import get_logger

                get_logger().warning(
                    "timeline writer backlog full; dropping events "
                    "(see the timeline.dropped_events counter for the "
                    "total — the trace at %s is incomplete)", self.path,
                )

    def _drain(self) -> None:
        # The writer thread owns the file handle end to end: it drains the
        # backlog after close() signals, writes the epilogue, and closes —
        # so no event can land after the closing bracket.
        while not (self._closed.is_set() and self._queue.empty()):
            try:
                ev = self._queue.get(timeout=0.1)
            except queue.Empty:
                continue
            if not self._first:
                self._fh.write(",\n")
            self._first = False
            self._fh.write(json.dumps(ev))
        self._fh.write("\n]\n")
        self._fh.close()

    def close(self) -> None:
        self._closed.set()
        self._thread.join()


def start_timeline(path: str) -> None:
    """Attach a timeline writer to the running runtime (reference
    ``horovod_start_timeline``, ``operations.cc:1011`` — runtime
    activation without the env var).  Replaces any active timeline."""
    from .. import native
    from ..runtime import get_runtime

    rt = get_runtime()
    if rt.timeline is not None:
        rt.timeline.close()
    if native.available():
        rt.timeline = native.NativeTimeline(path)
    else:
        rt.timeline = Timeline(path)


def stop_timeline() -> None:
    """Flush and detach the active timeline (reference
    ``horovod_stop_timeline``)."""
    from ..runtime import get_runtime

    rt = get_runtime()
    if rt.timeline is not None:
        rt.timeline.close()
        rt.timeline = None


# ---- cross-rank merge (tools/merge_timeline.py CLI) ----------------------


def _load_trace_events(path: str, status: Optional[dict] = None) -> list:
    """Read one trace file: a bare JSON array (this writer's and the
    trace exporter's format), a ``{"traceEvents": [...]}`` object
    (Chrome's), or a flight-recorder dump (``{"steps": [...]}`` —
    rendered to events via ``trace/export.py``).

    A trace whose writer died mid-job (worker crash, driver terminate)
    has no closing bracket; the Chrome trace format itself permits that
    for exactly this reason, so fall back to salvaging the complete
    events line by line (this writer emits one event per line).

    ``status`` (a dict, mutated in place) reports how the file parsed:
    ``ok`` | ``salvaged`` (line-by-line recovery) | ``empty`` (parsed
    but no events) | ``error`` (unreadable / zero events recovered) —
    the per-file parse report ``tools/merge_timeline.py`` prints
    instead of silently dropping a rank."""
    status = status if status is not None else {}
    try:
        with open(path) as fh:
            text = fh.read()
    except OSError as e:
        status.update(status="error", detail=str(e), events=0)
        return []
    try:
        data = json.loads(text)
    except json.JSONDecodeError as e:
        events = []
        for line in text.splitlines():
            line = line.strip().rstrip(",").strip()
            if line in ("[", "]", ""):
                continue
            try:
                events.append(json.loads(line))
            except json.JSONDecodeError:
                continue  # the torn tail of the last write
        if events:
            status.update(status="salvaged", detail=str(e),
                          events=len(events))
        else:
            status.update(status="error",
                          detail=f"no events salvageable: {e}", events=0)
        return events
    if isinstance(data, dict):
        if "traceEvents" in data:
            events = list(data["traceEvents"])
        elif "steps" in data or "background" in data:
            # A flight-recorder dump: render its span trees as events
            # so an anomaly dump merges into the postmortem view.
            from ..trace.export import dump_to_events

            events = dump_to_events(data)
        else:
            events = []
    else:
        events = list(data)
    status.update(
        status="ok" if events else "empty",
        detail="", events=len(events),
    )
    return events


# Categories that get their own named lane in the merged view: the
# scheduler's per-bucket dispatch lane, the async service's submission
# lane, the hierarchical phase lane, and every per-workload
# <KIND>_EXCHANGE lane the XIR interpreter emits.  TRACE_* categories
# (the trace exporter) already carry their own thread_name metadata.
_LANE_CATS = ("SCHED_EXCHANGE", "SVC_EXCHANGE", "TOPO_PHASE")


def _lane_cat(cat: Optional[str]) -> Optional[str]:
    if not cat:
        return None
    if cat in _LANE_CATS or cat.endswith("_EXCHANGE"):
        return cat
    return None


def merge_timeline_files(paths, report: Optional[list] = None) -> dict:
    """Align N per-rank traces into one Chrome trace with per-rank
    lanes.

    Each file's ``HVD_PROC_META`` event supplies its rank and
    wall-clock epoch base; every ``ts`` is re-based to the earliest
    epoch across files so concurrent collectives line up even when the
    per-process ``perf_counter`` zeros (and wall clocks) are skewed.
    Lanes: ``pid`` is rewritten to the rank (with matching
    ``process_sort_index``), so Perfetto orders lanes rank 0..N-1
    top-down; events in the known activity lanes (SCHED_EXCHANGE /
    SVC_EXCHANGE / TOPO_PHASE / <KIND>_EXCHANGE) get a named thread
    lane per rank instead of piling onto the dispatch thread.  Files
    without metadata (pre-merge traces) fall back to their position in
    ``paths`` with a zero epoch, and merge with a warning rather than
    failing the whole postmortem.

    ``report`` (a list, appended in ``paths`` order) collects one
    per-file parse record: ``{"path", "status", "events", "rank",
    "detail"}`` with status ``ok``/``salvaged``/``empty``/``error`` —
    the CLI's per-file report, so an unparseable rank is named, not
    silently dropped.
    """
    from .logging import get_logger

    loaded = []  # (rank, epoch_wall_us, events, source_index)
    for i, path in enumerate(paths):
        status: dict = {}
        events = _load_trace_events(path, status)
        meta = next(
            (e for e in events if e.get("name") == "HVD_PROC_META"), None
        )
        if meta is not None:
            args = meta["args"]
        else:
            # Native-core traces (and the trace exporter's sidecar-less
            # crashed writers) carry the merge metadata in a JSON
            # sidecar (the C writer's event ABI has no args payload).
            args = None
            try:
                with open(path + ".hvdmeta.json") as fh:
                    args = json.load(fh)
            except (OSError, ValueError):
                pass
        if args is None:
            if events:
                get_logger().warning(
                    "%s has no HVD_PROC_META event or .hvdmeta.json "
                    "sidecar; assuming rank %d with epoch 0 (timestamps "
                    "will not align across files)", path, i,
                )
                if status.get("status") == "ok":
                    status["status"] = "no_meta"
            rank, epoch = i, 0.0
        else:
            rank = int(args.get("rank", i))
            epoch = float(args.get("epoch_wall_us", 0.0))
        if report is not None:
            report.append({
                "path": path, "rank": rank,
                "status": status.get("status", "error"),
                "events": status.get("events", len(events)),
                "detail": status.get("detail", ""),
            })
        loaded.append((rank, epoch, events, i))

    base = min((epoch for _, epoch, _, _ in loaded), default=0.0)
    merged: list = []
    lane_tids: dict = {}  # (rank, cat) -> tid
    files_per_rank: dict = {}  # rank -> files merged so far
    for rank, epoch, events, _src in sorted(
            loaded, key=lambda t: (t[0], t[3])):
        # Multiple files may legitimately share a rank (a timeline AND
        # a trace export): offset the later files' thread ids so their
        # lanes coexist instead of interleaving on tid 0.
        tid_off = 100 * files_per_rank.get(rank, 0)
        files_per_rank[rank] = files_per_rank.get(rank, 0) + 1
        offset = epoch - base
        for e in events:
            e = dict(e)
            e["pid"] = rank
            if tid_off and "tid" in e:
                e["tid"] = int(e.get("tid", 0)) + tid_off
            if e.get("ph") == "M":
                if e.get("name") == "process_sort_index":
                    e["args"] = {"sort_index": rank}
            elif "ts" in e:
                e["ts"] = float(e["ts"]) + offset
            cat = _lane_cat(e.get("cat"))
            if cat is not None and e.get("ph") != "M":
                key = (rank, cat)
                tid = lane_tids.get(key)
                if tid is None:
                    tid = 10 + len([k for k in lane_tids if k[0] == rank])
                    lane_tids[key] = tid
                    merged.append({
                        "name": "thread_name", "ph": "M", "pid": rank,
                        "tid": tid, "args": {"name": cat},
                    })
                e["tid"] = tid
            merged.append(e)
    return {"traceEvents": merged, "displayTimeUnit": "ms"}


# ---- measured per-bucket durations (reference timeline.cc activity
# records, activities common.h:73-105) ------------------------------------

_BUCKET_RE = None


def _bucket_re():
    global _BUCKET_RE
    if _BUCKET_RE is None:
        import re

        # the bucketed overlap scheduler's per-bucket scopes
        _BUCKET_RE = re.compile(r"hvd_sched_bucket(\d+)_(\d+)B")
    return _BUCKET_RE


def extract_bucket_spans(logdir: str, hlo_text: Optional[str] = None):
    """Extract ``hvd_bucket*`` execution spans from a ``jax.profiler``
    trace directory.

    Two join paths cover both backends: TPU traces carry the scoped op
    name directly in the event name/args; CPU traces carry only the HLO
    instruction name (``args.hlo_op``), which joins through the
    compiled module's ``op_name`` metadata (``hlo_text``).  Returns a
    list of ``(bucket_label, ts_us, dur_us)``.
    """
    import glob
    import gzip
    import json as _json

    op_to_bucket = {}
    if hlo_text:
        import re

        for m in re.finditer(
            r"(\S+)\s*=\s*[^\n]*op_name=\"([^\"]*hvd_(?:sched_)?bucket"
            r"(\d+)_(\d+)B[^\"]*)\"",
            hlo_text,
        ):
            op_to_bucket[m.group(1).lstrip("%")] = (
                f"bucket{m.group(3)}[{m.group(4)}B]"
            )
    spans = []
    pattern = os.path.join(logdir, "**", "*.trace.json.gz")
    for fp in glob.glob(pattern, recursive=True):
        with gzip.open(fp) as fh:
            events = _json.loads(fh.read()).get("traceEvents", [])
        for e in events:
            if e.get("ph") != "X":
                continue
            dur = float(e.get("dur", 0) or 0)
            if dur <= 0:
                continue
            args = e.get("args") or {}
            hay = f"{e.get('name', '')} {args.get('long_name', '')}"
            m = _bucket_re().search(hay)
            if m:
                label = f"bucket{m.group(1)}[{m.group(2)}B]"
            else:
                label = op_to_bucket.get(str(args.get("hlo_op", "")))
            if label is not None:
                spans.append((label, float(e.get("ts", 0) or 0), dur))
    return spans


def profile_bucket_step(fn, *args, logdir: Optional[str] = None, **kwargs):
    """Run ``fn(*args)`` ONCE under the device profiler and extract the
    MEASURED per-bucket execution durations (reference: the timeline's
    per-tensor activity begin/end records let a user see which fusion
    bucket is slow; here the ``hvd_bucket*`` named scopes planted by
    ``DistributedOptimizer`` are joined against the profiler trace).

    Emits one ``BUCKET_EXEC`` duration event per bucket into the active
    timeline (measured lane, real ``ts``/``dur``) and returns
    ``({bucket_label: total_duration_us}, step_output)``.  The step
    output MUST replace the caller's inputs: compiled train steps
    donate (params, state, opt_state) buffers, so the arguments passed
    in are consumed by the profiled step exactly as by a normal step.
    One profiler session is paid for the single diagnostic step — the
    hot path stays uninstrumented — and the HLO-metadata join (needed
    only on backends whose traces lack scoped op names, e.g. CPU) is
    built lazily so no second compile is paid where the name join
    succeeds.
    """
    import shutil
    import tempfile

    import jax

    created = None
    if logdir is None:
        logdir = created = tempfile.mkdtemp(prefix="hvd_bucket_prof_")
    try:
        jitted = fn if hasattr(fn, "lower") else jax.jit(fn)
        with jax.profiler.trace(logdir):
            out = jitted(*args, **kwargs)
            jax.block_until_ready(out)
        spans = extract_bucket_spans(logdir, None)
        if not spans:
            # Trace lacks scoped names (CPU backend): join through the
            # compiled module's op_name metadata instead.  Only this
            # fallback pays the AOT lower/compile for the text; TPU
            # traces carry scoped names and never reach here.
            try:
                hlo_text = (
                    jitted.lower(*args, **kwargs).compile().as_text()
                )
            except Exception:
                hlo_text = None
            if hlo_text:
                spans = extract_bucket_spans(logdir, hlo_text)
        totals: dict = {}
        starts: dict = {}
        for label, ts, dur in spans:
            totals[label] = totals.get(label, 0.0) + dur
            starts[label] = min(starts.get(label, ts), ts)
        from ..runtime import get_runtime_or_none

        rt = get_runtime_or_none()
        tl = rt.timeline if rt is not None else None
        if tl is not None and hasattr(tl, "record_span"):
            for label in sorted(totals):
                tl.record_span(
                    label, "BUCKET_EXEC", starts[label], totals[label],
                    args={"measured": True},
                )
        return totals, out
    finally:
        if created is not None:
            shutil.rmtree(created, ignore_errors=True)


# jax.profiler passthroughs (NVTX-range analog).
_profiler_active = False


def start_profile(logdir: str) -> None:
    global _profiler_active
    import jax

    jax.profiler.start_trace(logdir)
    _profiler_active = True


def stop_profile() -> None:
    global _profiler_active
    import jax

    if _profiler_active:
        jax.profiler.stop_trace()
        _profiler_active = False
