"""Test fixtures: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's test strategy (SURVEY.md §4): parallel-tier
tests exercise real collectives — here on 8 XLA host devices
(``--xla_force_host_platform_device_count=8``), the CPU stand-in for a
TPU slice.
"""

import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=8 "
    + os.environ.get("XLA_FLAGS", "")
)

# In the environment too, so that every worker a test launches — and the
# launcher's own "is this a TPU job?" check — sees a CPU job.
os.environ["JAX_PLATFORMS"] = "cpu"

import jax

jax.config.update("jax_platforms", "cpu")

import pytest

import horovod_tpu as hvd

# ---------------------------------------------------------------------
# multiproc triage: tests marked @pytest.mark.multiproc need a CPU
# backend that can run cross-process computations (real worker
# processes rendezvousing through jax.distributed).  Some jax builds
# reject that outright ("Multiprocess computations aren't implemented
# on the CPU backend") — an environment limitation, not a regression —
# so those tests SKIP with the probe's reason instead of failing,
# keeping tier-1 output legible: skips = environment can't run this,
# failures = something actually broke.

_MULTIPROC_PROBE: list = []  # memoized [reason-or-None]

_PROBE_SRC = """
import os, sys
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
import jax
jax.config.update("jax_platforms", "cpu")
jax.distributed.initialize(
    coordinator_address=sys.argv[1], num_processes=2,
    process_id=int(sys.argv[2]), initialization_timeout=60,
)
import numpy as np
from jax.experimental import multihost_utils
out = multihost_utils.process_allgather(np.int32(1))
assert int(np.asarray(out).sum()) == 2
"""


def _multiproc_unavailable_reason():
    """Probe once per session: spawn two 1-device CPU workers and run
    one cross-process allgather.  Returns None when the distributed CPU
    backend works, else a one-line reason for the skip."""
    if _MULTIPROC_PROBE:
        return _MULTIPROC_PROBE[0]
    import socket
    import subprocess
    import sys

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    addr = f"127.0.0.1:{port}"
    env = {
        k: v for k, v in os.environ.items()
        if not k.startswith(("JAX_", "XLA_"))
    }
    reason = None
    try:
        procs = [
            subprocess.Popen(
                [sys.executable, "-c", _PROBE_SRC, addr, str(i)],
                env=env, stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT, text=True,
            )
            for i in range(2)
        ]
        outs = []
        for p in procs:
            try:
                out, _ = p.communicate(timeout=120)
            except subprocess.TimeoutExpired:
                p.kill()
                out, _ = p.communicate()
                out = (out or "") + "\n[probe timed out]"
            outs.append((p.returncode, out or ""))
        if any(rc != 0 for rc, _ in outs):
            lines = [
                ln.strip() for _, out in outs
                for ln in out.splitlines()
                if "Error" in ln or "error" in ln or "timed out" in ln
            ]
            reason = (lines[-1] if lines else "probe worker failed")[:200]
    except OSError as e:
        reason = f"could not spawn probe workers: {e}"
    _MULTIPROC_PROBE.append(reason)
    return reason


def pytest_collection_modifyitems(config, items):
    if not any(item.get_closest_marker("multiproc") for item in items):
        return
    reason = _multiproc_unavailable_reason()
    if reason is None:
        return
    skip = pytest.mark.skip(
        reason=f"distributed CPU backend unavailable: {reason}"
    )
    for item in items:
        if item.get_closest_marker("multiproc"):
            item.add_marker(skip)


@pytest.fixture(scope="session", autouse=True)
def _devices():
    assert len(jax.devices()) == 8, "expected 8 virtual CPU devices"
    yield


@pytest.fixture()
def hvd_init():
    hvd.init()
    yield hvd
    hvd.shutdown()


@pytest.fixture(scope="module")
def hvd_module():
    hvd.init()
    yield hvd
    hvd.shutdown()
