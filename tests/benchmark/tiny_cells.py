"""What the benchmark's tests share: where things are, the tiny stand-in
of every real cell, and one run of a tiny cell on the virtual CPU mesh."""

import json
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent.parent


def _tiny_cells():
    """real cell -> (tiny cell, configuration, traffic mix, chips), from
    ``fixture/cells/<tiny cell>.json``: a new cell brings its stand-in as a
    file, and every test file finds it whichever is run alone."""
    found = {}
    for path in sorted((HERE / "fixture" / "cells").glob("*.json")):
        cell = json.loads(path.read_text())
        found[cell["stands_for"]] = (
            path.stem, cell["config"], cell["traffic"], cell["chips"])
    return found


TINY = _tiny_cells()


def run_tiny(root, cell_name, *, seed=1, seconds=0.3, trace=False,
             out_dir=None):
    """One run of a tiny cell on the CPU mesh: (cell, run, correct)."""
    import time

    import jax

    from benchmark import harness, manifest

    cell = manifest.load_cell(cell_name, root=root, data_dir=root)
    run, correct = harness.run_cell(
        cell, jax.devices()[:cell.chips], seed=seed, seconds=seconds,
        trace=trace, process_start=time.perf_counter(),
        out_dir=out_dir or (root / "out"))
    return cell, run, correct
