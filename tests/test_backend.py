"""Multi-backend lowering-plane tests (backend/ registry + gpu topo).

The registry column of the matrix: family resolution and its env
aliases, the gpu NVLink/IB discovery feeding the shared Topology cost
model, rail relabeling through the payload surfaces, the gpu peak
table, the family-dependent quantized-wire default, and tune-DB
fingerprint keying by RESOLVED family (unset ≡ tpu shares pre-PR-20
entries; gpu keys apart).  The collective-parity half of the column
lives in tests/test_collective_matrix.py::TestBackendColumn;
tools/tier1_backend_smoke.sh drives the same marker end-to-end.
"""

import numpy as np
import pytest

from horovod_tpu import metrics, topo
from horovod_tpu.backend import gpu_topo, registry
from horovod_tpu.exceptions import HorovodTpuError

pytestmark = pytest.mark.backend


@pytest.fixture(autouse=True)
def _reset_backend(monkeypatch):
    """Every test starts and ends on the unforced (auto → tpu-on-CPU)
    family with fresh platform and topology caches."""
    monkeypatch.delenv("HVD_TPU_BACKEND", raising=False)
    monkeypatch.delenv("HOROVOD_BACKEND", raising=False)
    registry.reset()
    topo.reset()
    yield
    registry.reset()
    topo.reset()


def _force(monkeypatch, fam):
    monkeypatch.setenv("HVD_TPU_BACKEND", fam)
    registry.reset()
    topo.reset()


class TestFamilyResolution:
    def test_auto_on_cpu_resolves_tpu(self):
        assert registry.family() == "tpu"
        assert registry.get().name == "tpu"
        assert registry.kernel_module_name("quant_ring") == "pallas_quant"

    def test_env_override_gpu(self, monkeypatch):
        _force(monkeypatch, "gpu")
        assert registry.family() == "gpu"
        assert registry.get().name == "gpu"
        assert registry.kernel_module_name("quant_ring") == "mosaic_quant"

    @pytest.mark.parametrize("raw,fam", [
        ("tpu", "tpu"), ("TPU", "tpu"),
        ("gpu", "gpu"), ("cuda", "gpu"), ("rocm", "gpu"),
        ("nvidia", "gpu"), (" Gpu ", "gpu"),
    ])
    def test_aliases(self, monkeypatch, raw, fam):
        _force(monkeypatch, raw)
        assert registry.family() == fam

    def test_legacy_horovod_spelling(self, monkeypatch):
        monkeypatch.setenv("HOROVOD_BACKEND", "gpu")
        registry.reset()
        assert registry.family() == "gpu"

    def test_unknown_spelling_raises(self, monkeypatch):
        _force(monkeypatch, "trainium")
        with pytest.raises(HorovodTpuError):
            registry.family()

    def test_unknown_op_class_has_no_kernel(self):
        assert registry.kernel_module_name("no_such_op") is None


class TestRailNaming:
    def test_tpu_labels_are_identity(self):
        assert registry.rail_labels() == {"ici": "ici", "dcn": "dcn"}
        assert topo.rail_labels() == {"ici": "ici", "dcn": "dcn"}

    def test_gpu_labels(self, monkeypatch):
        _force(monkeypatch, "gpu")
        assert registry.rail_labels() == {"ici": "nvlink", "dcn": "ib"}
        assert topo.rail_label("ici") == "nvlink"
        assert topo.rail_label("dcn") == "ib"

    @pytest.mark.parametrize("tag,canon", [
        ("ici", "ici"), ("NVLink", "ici"), ("nvswitch", "ici"),
        ("dcn", "dcn"), ("IB", "dcn"), ("infiniband", "dcn"),
        ("roce", "dcn"),
    ])
    def test_canon_rail(self, tag, canon):
        assert topo.canon_rail(tag) == canon

    def test_unknown_rail_tag_never_raises(self):
        # pass-through lowercased, both in canon and in labeling
        assert topo.canon_rail("MysteryRail") == "mysteryrail"
        assert topo.rail_label("mysteryrail") == "mysteryrail"
        assert registry.get().rail_label("mysteryrail") == "mysteryrail"

    def test_tenants_payload_aliases(self, monkeypatch):
        from horovod_tpu.svc import arbiter

        _force(monkeypatch, "gpu")
        snap = {"gauges": [
            {"name": "svc.tenant.ici_bytes", "value": 100.0,
             "labels": {"tenant": "t0"}},
            {"name": "svc.tenant.rail_seconds", "value": 2.5,
             "labels": {"tenant": "t0", "rail": "ici"}},
            {"name": "svc.tenant.rail_seconds", "value": 0.5,
             "labels": {"tenant": "t0", "rail": "weird_rail"}},
        ]}
        payload = arbiter.tenants_payload({0: snap})
        assert payload["rail_labels"] == {"ici": "nvlink", "dcn": "ib"}
        t0 = payload["tenants"]["t0"]
        assert t0["ici_bytes"] == 100.0
        assert t0["nvlink_bytes"] == 100.0  # display alias mirrors
        rank0 = payload["ranks"]["0"]["t0"]
        assert rank0["rail_seconds_ici"] == 2.5
        assert rank0["rail_seconds_nvlink"] == 2.5
        # unknown rail tag lands under its own (lowercased) key
        assert rank0["rail_seconds_weird_rail"] == 0.5

    def test_prof_payload_rails(self, monkeypatch):
        import horovod_tpu.prof as prof

        _force(monkeypatch, "gpu")
        metrics.set_gauge("topo.rail_busy_frac", 0.25, {"rail": "ici"})
        try:
            view = prof._rails_view()
            assert view["labels"] == {"ici": "nvlink", "dcn": "ib"}
            assert view["busy_frac"]["ici"] == 0.25
            assert view["busy_frac"]["nvlink"] == 0.25
            assert "rails" in prof.prof_payload()
        finally:
            metrics.set_gauge("topo.rail_busy_frac", 0.0, {"rail": "ici"})


class TestGpuTopoDiscovery:
    class _Dev:
        def __init__(self, pid):
            self.process_index = pid

    def test_nvlink_domains_become_slices(self, monkeypatch):
        _force(monkeypatch, "gpu")
        devs = [self._Dev(p) for p in (0, 0, 0, 0, 1, 1, 1, 1)]
        t = gpu_topo.discover(devs)
        assert (t.num_slices, t.slice_size) == (2, 4)
        assert t.source == "gpu"
        # NVLink ≈ ICI is priced faster than IB ≈ DCN
        assert t.ici_gbps > t.dcn_gbps

    def test_ragged_domains_degenerate_flat(self, monkeypatch):
        _force(monkeypatch, "gpu")
        devs = [self._Dev(p) for p in (0, 0, 0, 1, 1)]
        t = gpu_topo.discover(devs)
        assert (t.num_slices, t.slice_size) == (1, 5)

    def test_family_routes_current(self, monkeypatch):
        _force(monkeypatch, "gpu")
        t = topo.current()
        assert t.source == "gpu"
        assert t.num_slices * t.slice_size == 8

    def test_spec_override_wins_over_family(self, monkeypatch):
        _force(monkeypatch, "gpu")
        monkeypatch.setenv("HVD_TPU_TOPO", "2x4")
        topo.reset()
        t = topo.current()
        assert t.source == "env"
        assert (t.num_slices, t.slice_size) == (2, 4)

    def test_cache_keyed_by_family(self, monkeypatch):
        t_tpu = topo.current()
        _force(monkeypatch, "gpu")
        t_gpu = topo.current()
        assert t_tpu.source != t_gpu.source  # no stale cross-family hit

    def test_link_param_env_overrides(self, monkeypatch):
        _force(monkeypatch, "gpu")
        monkeypatch.setenv("HVD_TPU_TOPO_ICI_GBPS", "123.0")
        monkeypatch.setenv("HVD_TPU_TOPO_DCN_GBPS", "7.0")
        topo.reset()
        t = gpu_topo.discover([self._Dev(0)] * 4)
        assert t.ici_gbps == 123.0
        assert t.dcn_gbps == 7.0

    def test_cost_model_prices_gpu_topology(self, monkeypatch):
        _force(monkeypatch, "gpu")
        devs = [self._Dev(p) for p in (0, 0, 0, 0, 1, 1, 1, 1)]
        t = gpu_topo.discover(devs)
        flat = t.estimate_cost("all_reduce", 1 << 20, lowering="flat")
        hier = t.estimate_cost("all_reduce", 1 << 20, lowering="hier")
        assert flat > 0 and hier > 0  # fitted-model consumers see real prices


class TestGpuPeakTable:
    class _Dev:
        def __init__(self, kind):
            self.device_kind = kind

    @pytest.mark.parametrize("kind,tflops", [
        ("NVIDIA H100 80GB HBM3", 989.0),
        ("NVIDIA A100-SXM4-40GB", 312.0),
        ("AMD Instinct MI300X", 1307.0),
    ])
    def test_gpu_kinds_resolve(self, monkeypatch, kind, tflops):
        from horovod_tpu.prof import peak

        _force(monkeypatch, "gpu")
        assert peak.chip_peak_tflops(self._Dev(kind)) == tflops

    def test_tpu_family_keeps_tpu_table(self, monkeypatch):
        from horovod_tpu.prof import peak

        assert peak.chip_peak_tflops(self._Dev("TPU v4")) == 275.0
        # a GPU kind under the tpu family is an unknown chip
        assert peak.chip_peak_tflops(self._Dev("NVIDIA H100")) is None


class TestQuantDefaultByFamily:
    def test_tpu_default_is_phase(self):
        from horovod_tpu.ops.quantized import quant_backend

        assert quant_backend() == "phase"

    def test_gpu_default_is_fused(self, monkeypatch):
        from horovod_tpu.ops import quantized

        _force(monkeypatch, "gpu")
        assert quantized.quant_backend() == "fused"
        assert quantized.fused_kernel_module().__name__.endswith(
            "mosaic_quant"
        )

    def test_explicit_knob_beats_family(self, monkeypatch):
        from horovod_tpu.ops.quantized import quant_backend

        _force(monkeypatch, "gpu")
        monkeypatch.setenv("HVD_TPU_QUANT_BACKEND", "phase")
        assert quant_backend() == "phase"


class TestFingerprintKeying:
    def test_unset_equals_explicit_tpu(self, monkeypatch):
        from horovod_tpu.sched.store import knob_fingerprint

        unset = knob_fingerprint()
        _force(monkeypatch, "tpu")
        assert knob_fingerprint() == unset  # pre-PR-20 entries survive

    def test_gpu_keys_apart(self, monkeypatch):
        from horovod_tpu.sched.store import knob_fingerprint

        unset = knob_fingerprint()
        _force(monkeypatch, "gpu")
        assert knob_fingerprint() != unset

    def test_raw_env_spelling_never_leaks(self, monkeypatch):
        """Two spellings of the same family share one fold point —
        only the RESOLVED family is keyed, not the raw knob string."""
        from horovod_tpu.sched.store import knob_fingerprint

        _force(monkeypatch, "gpu")
        f_gpu = knob_fingerprint()
        _force(monkeypatch, "cuda")
        assert knob_fingerprint() == f_gpu

    def test_same_backend_warm_start(self, monkeypatch, tmp_path):
        """A winner recorded under the gpu fingerprint is found again
        by a fresh store under the same family, and invisible under
        tpu keys."""
        from horovod_tpu.sched.store import (
            ScheduleStore, knob_fingerprint, make_key,
        )

        sig = ("allreduce", ((0, 1), 4096))
        _force(monkeypatch, "gpu")
        key_gpu = make_key(sig, knobs=knob_fingerprint())
        db = str(tmp_path / "tune.json")
        ScheduleStore(db).record(
            key_gpu, bucket_bytes=1 << 20, wire="int8",
            lowering="flat", score=1.0,
        )
        warm = ScheduleStore(db).lookup(key_gpu)  # fresh process image
        assert warm is not None and warm["wire"] == "int8"
        _force(monkeypatch, "tpu")
        key_tpu = make_key(sig, knobs=knob_fingerprint())
        assert key_tpu != key_gpu
        assert ScheduleStore(db).lookup(key_tpu) is None


class TestDiagnostics:
    def test_probe_doctor_backend_record(self):
        import importlib.util
        import os

        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "probe_doctor.py")
        spec = importlib.util.spec_from_file_location("_pd_t", path)
        pd = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(pd)
        stages = [{"stage": "backend_init", "stdout": "cpu 8"}]
        rec = pd._backend_record({"HVD_TPU_BACKEND": "cuda"}, stages)
        assert rec == {"requested": "cuda", "platform": "cpu",
                       "family": "gpu"}
        rec = pd._backend_record({}, stages)
        assert rec["platform"] == "cpu" and rec["family"] == "tpu"
        # no stage output, no env: the record still resolves
        rec = pd._backend_record({"JAX_PLATFORMS": "gpu"}, [])
        assert rec["family"] == "gpu"
        rec = pd._backend_record({}, [])
        assert rec["family"] == "unknown"
        assert rec["platform"] == "uninitialized"
