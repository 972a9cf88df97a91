"""``device.copy_gb_per_step``: the bytes the layout copies of the compiled
step's entry computation write, read from the step's HLO text."""

import json

import pytest

from benchmark import harness, manifest

from tiny_cells import CHECKOUT

NAME = "device.copy_gb_per_step"

# Two copies in the entry computation (one of them its ROOT's operand, with
# a layout that says where it lives), one inside a fused computation, and a
# prefetch's copy-start / copy-done, which is no layout copy.
HLO = """HloModule jit_step_body, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[16,1024,768]) -> bf16[16,1024,768] {
  %param_0.1 = bf16[16,1024,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %copy.9 = bf16[16,1024,768]{1,2,0:T(8,128)(2,1)} copy(%param_0.1)
}

ENTRY %main.7 (p0: bf16[16,1024,768], p1: f32[2,4096,16,64]) -> f32[8] {
  %p0 = bf16[16,1024,768]{2,1,0:T(8,128)(2,1)} parameter(0)
  %p1 = f32[2,4096,16,64]{3,2,1,0:T(8,128)} parameter(1)
  %copy.1 = bf16[16,1024,768]{1,2,0:T(8,128)(2,1)S(1)} copy(%p0), metadata={op_name="jit(step_body)/hvd_compute_grads/block_0/attn/transpose"}
  %fusion.1 = bf16[16,1024,768]{1,2,0:T(8,128)(2,1)} fusion(%p0), kind=kLoop, calls=%fused_computation.1
  %copy.2 = f32[2,4096,16,64]{1,3,2,0:T(8,128)} copy(%p1)
  %copy-start.1 = (f32[8]{0:T(128)S(1)}, f32[8]{0:T(128)}, u32[]{:S(2)}) copy-start(%p1)
  %copy-done.1 = f32[8]{0:T(128)S(1)} copy-done(%copy-start.1)
  ROOT %add.1 = f32[8]{0:T(128)} add(%copy-done.1, %copy-done.1)
}
"""


def _metric():
    entry, = [m for m in json.loads(
        (CHECKOUT / "BENCHMARK.json").read_text())["per_layer"]
        if m["name"] == NAME]
    assert entry == {"name": NAME, "unit": "GB", "better": "lower",
                     "source": "program_counter", "layer": "device",
                     "moves": "mfu"}
    return manifest.Metric(NAME, entry["unit"], entry["source"], False)


def _run(step_hlo):
    return harness.Run(
        cell=None, chips=1, platform="tpu", device_kind="TPU v5 lite",
        seconds_asked=1.0, process_start=0.0, step_hlo=step_hlo)


def test_only_the_entry_computations_copies_count():
    by_hand = 16 * 1024 * 768 * 2 + 2 * 4096 * 16 * 64 * 4
    assert harness.read_metric(_metric(), _run(HLO)) == pytest.approx(
        by_hand / 1e9)


def test_a_step_without_copies_reads_zero():
    text = "\n".join(line for line in HLO.splitlines()
                     if "%copy.1 =" not in line and "%copy.2 =" not in line)
    assert harness.read_metric(_metric(), _run(text)) == 0.0


@pytest.mark.parametrize("step_hlo", [None, ""])
def test_a_run_that_kept_no_hlo_reads_nothing(step_hlo):
    """An untraced run, or a program whose text could not be had: None,
    and the line leaves the metric out."""
    assert harness.read_metric(_metric(), _run(step_hlo)) is None


def test_it_is_a_count_and_so_read_off_the_chip_too():
    metric = _metric()
    assert not metric.timed
    out = harness.metrics_of(_run(HLO), [metric], on_chip=False)
    assert out[NAME]["unit"] == "GB" and out[NAME]["value"] > 0
