"""Launcher package (reference ``horovod/runner/``).

``hvdrun`` CLI: ``python -m horovod_tpu.runner -np 4 python train.py``.
Programmatic API: ``horovod_tpu.runner.run(func, np=4)`` pickles ``func``,
executes it on every worker, and returns the per-rank results (reference
``horovod.run()``, ``horovod/runner/__init__.py:92``, which ships results
through the launcher's KV store the same way).
"""

from __future__ import annotations

import pickle
import secrets as pysecrets
import socket
import sys
import time
from typing import Any, Callable, Dict, List, Optional

from . import controller_py, exec_utils, hosts as hosts_mod
from .launch import (  # noqa: F401
    free_port,
    launch_static,
    make_worker_env,
    require_one_tpu_process_per_host,
    run_commandline,
)


def _holds_tpu() -> bool:
    """Has this process already opened a TPU backend?  (Asked without
    opening one.)"""
    if "jax" not in sys.modules:
        return False
    import jax
    from jax._src import xla_bridge

    return (xla_bridge.backends_are_initialized()
            and jax.default_backend() == "tpu")


def run(
    func: Callable,
    args: tuple = (),
    kwargs: Optional[dict] = None,
    *,
    np: int = 1,
    hosts: Optional[str] = None,
    use_cpu_devices: bool = False,
    extra_env: Optional[Dict[str, str]] = None,
    verbose: bool = False,
) -> List[Any]:
    """Run ``func(*args, **kwargs)`` on ``np`` workers; returns the list
    of per-rank return values (rank order).

    ``use_cpu_devices=True`` forces workers onto the CPU backend (used by
    the integration tests, mirroring the reference's localhost gloo runs).
    Otherwise TPU workers are one per host (each drives every local
    chip): several on one host, or a local worker under a parent that
    already holds the chips, are refused with an error.
    """
    host_list = (
        hosts_mod.parse_hosts(hosts) if hosts else [hosts_mod.HostInfo("localhost", np)]
    )
    assignments = hosts_mod.get_host_assignments(host_list, np)
    if not use_cpu_devices:
        require_one_tpu_process_per_host(assignments, extra_env)
        if _holds_tpu() and any(
            exec_utils.is_local(a.hostname) for a in assignments
        ):
            raise RuntimeError(
                "runner.run() was called from a process that already "
                "holds this host's TPU chips; a worker that needs them "
                "would fail or hang. Call it before anything touches jax "
                "devices, drive the local chips from this process, or "
                "pass use_cpu_devices=True."
            )
    secret = pysecrets.token_hex(16)
    server = controller_py.make_server(secret, np)
    rendezvous_addr = "127.0.0.1" if all(
        exec_utils.is_local(a.hostname) for a in assignments
    ) else socket.gethostbyname(socket.gethostname())
    coordinator_host = (
        "127.0.0.1" if exec_utils.is_local(assignments[0].hostname)
        else assignments[0].hostname
    )
    coordinator_addr = f"{coordinator_host}:{free_port()}"

    # Publish the pickled function for the task runners (reference
    # horovod.run puts the pickled func in the KV store).
    publisher = controller_py.make_client(
        "127.0.0.1", server.port, secret, rank=-1
    )
    # cloudpickle ships closures/lambdas like the reference's run API
    import cloudpickle

    publisher.put(
        "__run__", "func", cloudpickle.dumps((func, args, kwargs or {}))
    )

    env_extra = dict(extra_env or {})
    if use_cpu_devices:
        env_extra.update({
            "JAX_PLATFORMS": "cpu",
            "HVD_TPU_FORCE_CPU": "1",
            # override any inherited forced device count (e.g. pytest's)
            "XLA_FLAGS": "--xla_force_host_platform_device_count=1",
        })
    workers = []
    try:
        for slot in assignments:
            env = make_worker_env(
                slot, coordinator_addr, rendezvous_addr, server.port, secret,
                env_extra,
            )
            workers.append(
                exec_utils.WorkerProcess(
                    slot.rank, slot.hostname,
                    [sys.executable, "-m", "horovod_tpu.runner.task_runner"],
                    env, prefix_output=verbose,
                )
            )
        for w in workers:
            rc = w.wait()
            if rc != 0:
                raise RuntimeError(
                    f"worker rank {w.rank} exited with code {rc}"
                )
        results = []
        for r in range(np):
            blob = publisher.get("__results__", str(r), timeout_ms=10_000)
            if blob is None:
                raise RuntimeError(f"no result from rank {r}")
            status, payload = pickle.loads(blob)
            if status == "error":
                raise RuntimeError(f"rank {r} failed: {payload}")
            results.append(payload)
        return results
    finally:
        for w in workers:
            w.terminate()
        publisher.close()
        server.stop()
