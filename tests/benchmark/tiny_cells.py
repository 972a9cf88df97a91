"""What the benchmark's tests share: where things are, the tiny stand-in
of every real cell, and one run of a tiny cell on the virtual CPU mesh."""

from pathlib import Path

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parent.parent

# real cell -> (tiny cell, configuration, traffic mix, chips)
TINY = {
    "gpt2s.dense": ("gpt_tiny.dense", "gpt_tiny", "ring-8x64", 1),
    "gpt2s.packed": ("gpt_tiny.packed", "gpt_tiny", "packed-docs-8x64", 1),
    "gpt2s.dp4": ("gpt_tiny.dp4", "gpt_tiny", "ring-8x64", 4),
    "resnet50.b256": ("resnet_cut.b8", "resnet_cut", "ring-8-images", 1),
}


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
