"""The harness end to end at a tiny size on the virtual CPU mesh: the body
``run.py`` runs on the chip, every tiny cell, one device and four."""

import json

import jax
import pytest

from benchmark import harness, run as bench_run

from tiny_cells import TINY, run_tiny

CELLS = [tiny for tiny, _, _, _ in TINY.values()]


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("traced", [False, True], ids=["plain", "traced"])
def test_tiny_cell_gives_the_contracts_last_line(
        cell_name, traced, tiny_root, quiet_runtime, capsys):
    cell, run, correct = run_tiny(tiny_root, cell_name, trace=traced)
    devices = jax.devices()[:cell.chips]
    line = json.loads(json.dumps(
        bench_run.result_line(run, correct, traced, devices)))
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(line)
    assert line["correct"] is True and line["failed"] == 0
    # last in the line: what decided ``correct``, each beside its limit
    assert list(line)[-1] == "compared" and len(line["compared"]) == 9
    assert all(c["value"] <= c["limit"] for c in line["compared"].values())
    assert line["attempted"] == len(run.completions) >= 1
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == cell.chips
    # the window holds whole steps and opened after the check and warm-up
    assert run.window_seconds >= 0.3
    assert run.window_end == run.completions[-1].at
    assert run.builds_in_window == 0
    # a CPU run gives counts and never a time, a rate or a share of a chip
    counts = {m.name for m in cell.end_to_end + cell.per_layer
              if not m.timed}
    assert set(line["metrics"]) <= counts
    assert "busy_s" not in line["device"] and "breakdown" not in line
    if traced:
        assert line["metrics"]["compile.in_window"]["value"] == 0
        assert run.trace_file is not None and run.trace_file.is_file()
        assert (run.trace_file.parents[3] / "step.hlo.txt").is_file()
    out = capsys.readouterr().out
    assert "check.system_losses" in out and "setup_seconds." in out


def test_four_devices_exchange_what_the_hlo_says(tiny_root, quiet_runtime):
    cell, run, correct = run_tiny(tiny_root, "gpt_tiny.dp4", trace=True)
    assert correct
    calls, nbytes = run.module().exchange_per_step()
    params = sum(x.size for x in jax.tree.leaves(
        jax.eval_shape(lambda: _tiny_params(cell))))
    # every gradient once on the bf16 wire (which XLA's CPU backend widens
    # to float32 inside the all-reduce) and the scalar loss in float32
    assert nbytes - 4 in (2 * params, 4 * params)
    assert calls >= 1  # XLA may combine them into one tuple all-reduce
    metrics = harness.metrics_of(run, cell.per_layer, on_chip=False)
    assert metrics["exchange.bytes_per_step"]["value"] == nbytes
    assert metrics["exchange.collectives_per_step"]["value"] == calls


def _tiny_params(cell):
    from benchmark.families import gpt

    return gpt.build(cell.config, cell.traffic).init(
        jax.random.PRNGKey(0))[0]


def test_packed_cell_counts_only_useful_tokens(tiny_root, quiet_runtime):
    cell, run, correct = run_tiny(tiny_root, "gpt_tiny.packed")
    assert correct
    work = run.work()
    assert 0 < work.units < work.positions
    assert work.positions == len(run.completions) * 8 * 64
    # per-document attention needs fewer operations than full rows would
    dense = run.ops.train_flops(run.model, work.positions,
                                work.positions * 64)
    assert run.ops.train_flops(run.model, work.units, work.sum_sq) < dense
    metrics = harness.metrics_of(run, cell.per_layer, on_chip=False)
    assert metrics["input.useful_token_share"]["value"] == pytest.approx(
        100.0 * work.units / work.positions)


def test_command_refuses_a_cpu(capsys):
    """The device gate: on a CPU backend nothing is trained, no result is
    printed and the exit code is not 0."""
    code = bench_run.main(
        ["--workload", "gpt2s.dense", "--seed", "1", "--seconds", "1"])
    out, err = capsys.readouterr()
    assert code == 2 and out == "" and "TPU" in err
