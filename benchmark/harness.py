"""One run of one cell: set-up with the system's check steps, the measured
window, then the reference alone on the chip and the comparison that
decides ``correct``, and the record the metrics are read from.

Nothing here knows a configuration, a mix or a metric by name: the cell
names its files (``manifest.py``), the family builds the system, the
generator reads the mix, each metric has its reader.  Nothing here asks
which platform it runs on either — ``run.py`` holds the device gate — so
tier-1 runs the same body at a tiny size on the CPU mesh, where the result
carries counts and no time.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import gc
import importlib
import importlib.util
import math
import shutil
import time
from pathlib import Path
from typing import Any, Dict, List, Optional, Sequence, Tuple

from . import check
from .compile_log import CompileLog
from .manifest import PACKAGE_DIR, Cell, Metric
from .traffic import Traffic, Work

WARMUP_STEPS = 3       # completions before the window opens
TRACE_AFTER = 0.3      # share of the window that passes before the trace
TRACE_STEPS = 8        # completions the profiler is on for
STEP_PROGRAM = "train_step_0"  # prof/introspect's key of a TrainStep's
#                                first compiled variant


def say(key: str, value) -> None:
    """An earlier line of the run's output: not a metric."""
    print(f"{key}: {value}", flush=True)


class Laps:
    """Seconds of each phase, on earlier lines: of set-up
    (``setup_seconds.``) and, once the window has closed, of the check
    (``check_seconds.``), which ``setup_s`` does not hold."""

    def __init__(self, start: float, prefix: str = "setup_seconds"):
        self.last = start
        self.prefix = prefix

    def lap(self, phase: str) -> None:
        now = time.perf_counter()
        say(f"{self.prefix}.{phase}", round(now - self.last, 2))
        self.last = now


@dataclasses.dataclass
class Completion:
    """A step whose loss has reached the host."""

    at: float          # perf_counter when the loss was in hand
    work: Work
    loss: float


@dataclasses.dataclass
class Run:
    """Everything a metric's reader may look at."""

    cell: Cell
    chips: int
    platform: str
    device_kind: str
    seconds_asked: float
    process_start: float
    window_start: float = 0.0
    window_end: float = 0.0
    completions: List[Completion] = dataclasses.field(default_factory=list)
    dispatch_seconds: List[float] = dataclasses.field(default_factory=list)
    wait_seconds: List[float] = dataclasses.field(default_factory=list)
    builds_in_window: int = 0
    step_record: Dict[str, Any] = dataclasses.field(default_factory=dict)
    step_hlo: Optional[str] = None
    trace_file: Optional[Path] = None
    failed: int = 0
    # every chip's allocator statistics when the window had closed and the
    # system's state was still alive: before the reference touched the chip
    allocator_stats: List[Dict[str, Any]] = dataclasses.field(
        default_factory=list)
    # what decided ``correct``: name -> (number, its limit)
    compared: Dict[str, Tuple[float, float]] = dataclasses.field(
        default_factory=dict)
    _module: Any = None
    _reduced: Any = None

    @property
    def model(self) -> Dict[str, Any]:
        return self.cell.config["model"]

    @property
    def ops(self):
        """``ops/<family>.py`` of the cell's configuration."""
        return importlib.import_module(
            f"benchmark.ops.{self.cell.config['family']}")

    @property
    def window_seconds(self) -> float:
        return self.window_end - self.window_start

    def work(self) -> Work:
        """The work of the steps completed inside the window."""
        return Work(
            sum(c.work.units for c in self.completions),
            sum(c.work.sum_sq for c in self.completions),
            sum(c.work.positions for c in self.completions))

    def step_seconds(self) -> List[float]:
        """Gaps between the completions of the window."""
        stamps = [self.window_start] + [c.at for c in self.completions]
        return [b - a for a, b in zip(stamps, stamps[1:])]

    def module(self):
        """The compiled step's HLO parsed (``trace/hlo.py``), or None when
        the run did not keep its text."""
        if self._module is None and self.step_hlo:
            from .trace import hlo

            self._module = hlo.Module(self.step_hlo)
        return self._module

    def reduced(self):
        """The device trace reduced (``trace/reduce.py``), or None when
        the run was not traced or no chip's plane shows a steady window."""
        if self._reduced is None and self.trace_file is not None:
            from .trace import reduce, xplane

            self._reduced = reduce.Reduced(
                xplane.load(self.trace_file), self.module())
        if self._reduced is not None and self._reduced.usable:
            return self._reduced
        return None


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of the readings, None without any."""
    if not values:
        return None
    ordered = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(ordered)))
    return ordered[rank - 1]


# ------------------------------------------------------------------ set-up
def make_step(hvd, system):
    """The system under test, through the entry points a user calls."""
    tx = hvd.DistributedOptimizer(
        system.optimizer,
        compression=getattr(hvd.Compression, system.compression))
    return hvd.distributed_train_step(
        system.loss_fn, tx, stateful=system.stateful)


class Trainer:
    """The carried state of the job and one way to advance it, whether or
    not the model has a state of its own."""

    def __init__(self, step, params, model_state, stateful: bool):
        self.step = step
        self.stateful = stateful
        self.params = params
        self.model_state = model_state
        self.opt_state = step.init(params)

    def advance(self, batch):
        """Enqueue one step; returns the loss (a device array)."""
        if self.stateful:
            (self.params, self.model_state, self.opt_state,
             loss) = self.step(self.params, self.model_state,
                               self.opt_state, batch)
        else:
            self.params, self.opt_state, loss = self.step(
                self.params, self.opt_state, batch)
        return loss


def system_check_steps(cell: Cell, trainer: Trainer, traffic: Traffic, mesh
                       ) -> Tuple[Any, List[float]]:
    """The system's side of the check of ``check.py``: its first optimizer
    steps on the seeded sample tiled to the cell's batch, which are the
    step's compilation and first warm-up.  Returns the sample (host arrays,
    for the reference) and the loss before each step."""
    spec = cell.config["check"]
    chips = mesh.devices.size
    sample = traffic.sample(chips * spec["sample_rows_per_chip"])
    tiled = traffic.place(
        check.tile_for_chips(sample, chips, traffic.rows // chips))
    return sample, [float(trainer.advance(tiled))
                    for _ in range(spec["steps"])]


def reference_side(cell: Cell, system, make_weights, sample, chips: int,
                   device) -> Tuple[List[float], int]:
    """The reference's side, alone on the chip: the weights made again from
    the seed by the program that made the system's, in float32 on
    ``device``, and the reference's steps on each chip's sample.  Returns
    its losses and the weights' fingerprint."""
    laps = Laps(time.perf_counter(), "check_seconds")
    say("check.bytes_in_use_before_reference", check.bytes_in_use(device))
    params, _ = make_weights()
    bits = check.fingerprint(params)
    params = check.float32_on(params, device)
    laps.lap("weights_again")
    reference = importlib.import_module(
        f"benchmark.reference.{cell.config['family']}")
    trained = check.train_reference(
        reference.loss, cell.config["model"], system.optimizer, params,
        check.chunks_for_chips(sample, chips), cell.config["check"]["steps"],
        device)
    del params  # donated to the reference's first update
    laps.lap("reference")
    say("check.reference_live_bytes", trained.live_bytes)
    say("check.reference_program_bytes", trained.program_bytes)
    return trained.losses, bits


def compiled_step(step):
    """The one ``Compiled`` the TrainStep has run, for its HLO text
    (``prof/introspect.ProfiledExecutor`` keeps it by argument
    signature)."""
    (executor,) = step._step_cache.values()
    (compiled,) = executor._compiled.values()
    return compiled


# ------------------------------------------------------------------ window
def run_window(run: Run, trainer: Trainer, traffic: Traffic, log: CompileLog,
               trace_dir: Optional[Path]) -> None:
    """The closed loop with one client: at most two steps in flight.
    Before step i is enqueued the loss of step i-2 is awaited and its
    completion stamped.  The window opens at the ``WARMUP_STEPS``-th
    completion and closes at the first one ``seconds_asked`` later, so it
    holds whole steps and no edge."""
    import jax

    annotate = jax.profiler.TraceAnnotation
    inflight: "collections.deque" = collections.deque()
    warm = 0
    tracing = "off" if trace_dir is None else "armed"
    traced = 0

    def complete() -> Completion:
        loss, work = inflight.popleft()
        with annotate("bench_block"):
            value = float(loss)
        return Completion(time.perf_counter(), work, value)

    while True:
        if len(inflight) == 2:
            done = complete()
            if not run.window_start:
                warm += 1
                if warm == WARMUP_STEPS:
                    run.window_start = done.at
                    traffic.wait_seconds.clear()
                    run.dispatch_seconds.clear()
            else:
                run.completions.append(done)
                if not math.isfinite(done.loss):
                    run.failed += 1
                elapsed = done.at - run.window_start
                if elapsed >= run.seconds_asked:
                    run.window_end = done.at
                    break
                if (tracing == "armed"
                        and elapsed >= TRACE_AFTER * run.seconds_asked):
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(
                        str(trace_dir), profiler_options=options)
                    tracing = "on"
                elif tracing == "on":
                    traced += 1
                    if traced == TRACE_STEPS:
                        jax.profiler.stop_trace()
                        tracing = "done"
        with annotate("bench_input_wait"):
            batch, work = traffic.next()
        t0 = time.perf_counter()
        with annotate("bench_dispatch"):
            loss = trainer.advance(batch)
        run.dispatch_seconds.append(time.perf_counter() - t0)
        inflight.append((loss, work))

    if tracing == "on":  # the window was shorter than the trace
        jax.profiler.stop_trace()
    while inflight:  # steps begun inside the window and ended after it
        complete()
    run.wait_seconds = list(traffic.wait_seconds)
    run.builds_in_window = log.builds_between(
        run.window_start, run.window_end)


# --------------------------------------------------------------------- run
@dataclasses.dataclass
class SystemSide:
    """What is left of the system's run when its state is gone."""

    sample: Any                 # the check's rows, on the host
    losses: List[float]         # before each of its check steps
    weights_bits: int           # check.fingerprint of what it started from
    same_before: bool           # replicas identical, before the window
    same_after: bool            # and after it


def drive_system(run: Run, hvd, system, make_weights, seed: int,
                 trace_dir: Optional[Path], log: CompileLog, laps: Laps
                 ) -> SystemSide:
    """The system's whole life on the chips: weights, the compiled step
    with its state, the check's steps, the window.  Everything it put on a
    chip is local to this call, so it is released when the call returns."""
    from horovod_tpu.prof import introspect

    cell = run.cell
    mesh = hvd.mesh()
    traffic = Traffic(cell.traffic, system.element, mesh, hvd.WORLD_AXIS,
                      seed)
    try:
        params, model_state = make_weights()
        params = hvd.broadcast_parameters(params, root_rank=0)
        weights_bits = check.fingerprint(params)
        laps.lap("weights")
        trainer = Trainer(make_step(hvd, system), params, model_state,
                          system.stateful)
        del params, model_state
        sample, losses = system_check_steps(cell, trainer, traffic, mesh)
        laps.lap("step_compile_and_check_steps")
        identical = check.replicas_identical(mesh, hvd.WORLD_AXIS)
        same_before = identical(trainer.params)
        traffic.start()
        laps.lap("replica_check_and_traffic")
        run_window(run, trainer, traffic, log, trace_dir)
        same_after = identical(trainer.params)
        run.allocator_stats = [
            d.memory_stats() or {} for d in mesh.devices.flat]
        run.step_record = introspect.get(STEP_PROGRAM) or {}
        if trace_dir is not None:
            run.step_hlo = compiled_step(trainer.step).as_text()
            # beside the trace, so that it can be reduced again by hand
            (trace_dir / "step.hlo.txt").write_text(run.step_hlo)
            found = sorted(trace_dir.glob("plugins/profile/*/*.xplane.pb"))
            run.trace_file = found[-1] if found else None
        return SystemSide(sample, losses, weights_bits, same_before,
                          same_after)
    finally:
        traffic.close()


def run_cell(cell: Cell, devices, seed: int, seconds: float, trace: bool,
             process_start: float, out_dir: Path) -> Tuple[Run, bool]:
    """One run of ``cell`` on ``devices``.  Returns the record and
    ``correct``.

    The system and the reference are never on the chips together: the
    system runs first, from its weights to the end of the window, the
    allocator's peak is read, its state is released; then the reference
    makes the same weights again and trains alone.  So a configuration is
    sized by what its step holds, and ``setup_s`` pays for no reference."""
    import jax

    import horovod_tpu as hvd
    from horovod_tpu import native
    from horovod_tpu.utils import compile_cache

    laps = Laps(process_start)
    laps.lap("imports")
    native.ensure_built()
    laps.lap("native_core")
    say("compile_cache_dir", compile_cache.enable())
    # Every program goes into the persistent cache, the small ones too, so
    # that a second run of the cell compiles nothing.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    run = Run(cell=cell, chips=len(devices), platform=devices[0].platform,
              device_kind=devices[0].device_kind, seconds_asked=seconds,
              process_start=process_start)
    family = importlib.import_module(
        f"benchmark.families.{cell.config['family']}")
    system = family.build(cell.config, cell.traffic)
    # one program from one key, for the system and again for the reference
    make_weights = functools.partial(
        jax.jit(system.init), jax.random.PRNGKey(seed))
    trace_dir = None
    if trace:
        trace_dir = Path(out_dir) / f"trace-{cell.name}"
        shutil.rmtree(trace_dir, ignore_errors=True)
    with CompileLog() as log:
        hvd.init(devices=list(devices))
        try:
            side = drive_system(run, hvd, system, make_weights, seed,
                                trace_dir, log, laps)
        finally:
            hvd.shutdown()
        gc.collect()  # the step and its state, should a cycle hold them
        reference_losses, reference_bits = reference_side(
            cell, system, make_weights, side.sample, run.chips, devices[0])
        builds = [round(s, 2) for _, s in log.builds if s >= 1.0]
        say("compile.builds_over_1s", builds)
        say("compile.cache_reads_writes", (len(log.reads), len(log.writes)))
    say("check.reference_peak_bytes", max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devices))
    rtol = cell.config["check"]["loss_rtol"]
    say("check.system_losses", side.losses)
    say("check.reference_losses", reference_losses)
    say("check.loss_rtol", rtol)
    say("check.replicas_identical", (side.same_before, side.same_after))
    say("traffic.wait_seconds_max", max(run.wait_seconds, default=0.0))
    say("window.losses_first_last",
        [c.loss for c in run.completions[:1] + run.completions[-1:]])
    gaps = check.loss_gaps(side.losses, reference_losses)
    run.compared = {
        **{f"loss_gap_step_{k}": (gap, rtol) for k, gap in enumerate(gaps)},
        "weights_differ": (float(reference_bits != side.weights_bits), 0.0),
        "replicas_differ_before": (float(not side.same_before), 0.0),
        "replicas_differ_after": (float(not side.same_after), 0.0),
        "losses_not_finite": (float(run.failed), 0.0),
        "no_step_completed": (float(not run.completions), 0.0),
    }
    correct = all(value <= limit for value, limit in run.compared.values())
    return run, correct


# ----------------------------------------------------------------- metrics
def read_metric(metric: Metric, run: Run) -> Optional[float]:
    """The value a metric's own reader gives for this run, or None when it
    found nothing to read."""
    path = PACKAGE_DIR / ("end_to_end" if metric.end_to_end
                          else "layer_metrics") / f"{metric.name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmark._readers.{metric.name.replace('.', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    value = module.read(run)
    return None if value is None else float(value)


def metrics_of(run: Run, metrics: Sequence[Metric], on_chip: bool
               ) -> Dict[str, Dict[str, Any]]:
    """``{name: {value, unit}}`` for the line.  Off the chip only counts
    are given: a time, a rate or a share of a device from a CPU run is
    never written under a device metric's name."""
    out = {}
    for metric in metrics:
        if metric.timed and not on_chip:
            continue
        value = read_metric(metric, run)
        if value is not None:
            out[metric.name] = {"value": value, "unit": metric.unit}
    return out
