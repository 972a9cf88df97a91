"""``chip_smoke.py``'s body at ``gpt_tiny`` size on the virtual CPU mesh.

The chip run itself needs a TPU; what tier-1 can hold is that the same
functions run, and check what they claim, on 1 and on 4 devices.  (The
device gates of ``chip_smoke.main()`` and ``bench.py`` are in
tests/test_bench.py.)
"""

import jax
import pytest

import chip_smoke
import horovod_tpu as hvd
from horovod_tpu.models.transformer import gpt_tiny


@pytest.fixture()
def no_runtime():
    hvd.shutdown()
    yield
    hvd.shutdown()


def test_train_body_one_and_four_devices(no_runtime):
    """Losses fall, the step compiles once and nothing is built after
    the first step, ``prof.fallbacks == 0``, params replicated and the
    batch sharded (all required inside ``train``) — and four devices
    give one device's losses on the same global batch."""
    reports = chip_smoke.parity(
        hvd, gpt_tiny(), rows=8, seq_len=64, steps=5,
        device_counts=(1, 4), on_tpu=False,
    )
    for count, report in reports.items():
        assert report["devices"] == count
        assert report["losses"][-1] < report["losses"][0]
        assert report["step_compiles"] == 1
        assert report["xla_builds_per_step"][1:] == [0, 0, 0, 0]
        assert report["prof_fallbacks"] == 0
    assert reports[4]["losses"] == pytest.approx(
        reports[1]["losses"], rel=chip_smoke.BF16_EPS)
    assert not hvd.is_initialized()


def test_parity_reports_a_disagreement(no_runtime, monkeypatch):
    """A loss that differs beyond bf16 tolerance fails the smoke."""
    real = chip_smoke.train

    def skewed(hvd_, *args, **kwargs):
        report = real(hvd_, *args, **kwargs)
        if hvd_.size() == 2:
            report["losses"] = [x * 1.05 for x in report["losses"]]
        return report

    monkeypatch.setattr(chip_smoke, "train", skewed)
    with pytest.raises(chip_smoke.SmokeFailure, match="bf16 tolerance"):
        chip_smoke.parity(
            hvd, gpt_tiny(), rows=4, seq_len=32, steps=2,
            device_counts=(1, 2), on_tpu=False,
        )


def test_kernel_section_interpreted(no_runtime):
    """The kernel section against its references, interpreted, at a
    size with several documents per packed row."""
    chip_smoke.kernels(2, 256, 2, 16, 4096, on_tpu=False)
    with pytest.raises(chip_smoke.SmokeFailure, match="_interpret"):
        # on the chip the smoke requires the opposite: compiled kernels
        chip_smoke.kernels(2, 256, 2, 16, 4096, on_tpu=True)
    assert jax.default_backend() == "cpu"
