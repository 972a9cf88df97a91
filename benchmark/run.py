"""``python3 -m benchmark.run --workload <cell> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell of ``BENCHMARK.json`` on the machine
it is started on, from the root of a checkout.

The last line of standard output is the result: one JSON object with
``correct``, ``attempted``, ``failed``, ``metrics`` and ``device`` (and,
traced, ``breakdown``), and last ``compared``: every number that decided
``correct`` beside its limit, which are also the last lines of standard
error.  Without a TPU, or with fewer chips than the cell asks for, nothing
is trained, no result is printed and the exit code is 2.
"""

from __future__ import annotations

import time

_PROCESS_START = time.perf_counter()  # before the heavy imports: set-up

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402


def result_line(run, correct: bool, traced: bool, devices) -> dict:
    """The contract's last line for a finished run on ``devices``."""
    from . import harness

    on_chip = devices[0].platform == "tpu"
    cell = run.cell
    metrics = harness.metrics_of(
        run, cell.per_layer if traced else cell.end_to_end, on_chip)
    # read when the window had closed, before the reference used the chip
    stats = run.allocator_stats or [{}]
    allocator_peak = max(s.get("peak_bytes_in_use", 0) for s in stats)
    step_bytes = int(run.step_record.get("peak_hbm_bytes") or 0)
    harness.say("memory.allocator_stats_first_chip", stats[0])
    harness.say("memory.allocator_peak_bytes_in_use", allocator_peak)
    harness.say("memory.step_bytes_xla", step_bytes)
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        # The allocator's statistic leaves the running program's
        # temporaries out on this runtime (PERF.md section 7), so the peak
        # is the larger of it and what XLA says the step holds per chip.
        "memory_peak_bytes": max(allocator_peak, step_bytes),
    }
    line = {
        "correct": correct,
        "attempted": len(run.completions),
        "failed": run.failed,
        "metrics": metrics,
        "device": device,
        "workload": cell.name,
        "host_window_s": run.window_seconds,
    }
    reduced = run.reduced() if traced and on_chip else None
    if reduced is not None:
        device["busy_s"] = reduced.busy_seconds()
        device["window_s"] = reduced.window_seconds()
        line["breakdown"] = reduced.breakdown()
    # last: every number that decided ``correct``, beside its limit
    line["compared"] = {
        name: {"value": value, "limit": limit}
        for name, (value, limit) in run.compared.items()}
    return line


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from . import manifest

    cell = manifest.load_cell(args.workload)
    if importlib.util.find_spec("horovod_tpu") is None:
        print("benchmark: the system under test, horovod_tpu, is not in "
              "this directory. Nothing was run.", file=sys.stderr)
        return 2

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(
            f"benchmark: {cell.name} needs {cell.chips} TPU chip(s); jax "
            f"found {len(devices)} device(s) of platform "
            f"{devices[0].platform!r}. Nothing was run.", file=sys.stderr)
        return 2
    devices = devices[:cell.chips]

    from . import harness, peaks

    peaks.for_kind(devices[0].device_kind)  # an unknown device is an error
    harness.say("platform", devices[0].platform)
    harness.say("device_kind", devices[0].device_kind)
    harness.say("device_count", len(devices))
    run, correct = harness.run_cell(
        cell, devices, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), process_start=_PROCESS_START,
        out_dir=manifest.PACKAGE_DIR / "out")
    line = result_line(run, correct, bool(args.trace), devices)
    print(json.dumps(line), flush=True)
    for name, pair in line["compared"].items():
        print(f"compared {name}: {pair['value']} (limit {pair['limit']})",
              file=sys.stderr, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
