"""MPI launch path: drive workers through ``mpirun``.

Reference: ``horovod/runner/mpi_run.py`` — builds an
``mpirun --allow-run-as-root -np N -H hosts -x ENV... <cmd>`` line with
Open MPI / Intel MPI flavor detection, binding flags, and env
forwarding.  TPU re-design: MPI is only the *process launcher* (there
is no MPI data plane — collectives ride XLA), so the command wraps each
worker in :mod:`horovod_tpu.runner.mpi_worker`, a shim that translates
the MPI-provided rank env (``OMPI_COMM_WORLD_RANK`` / ``PMI_RANK``)
into this framework's worker env contract before exec'ing the user
command.  The launcher still runs the rendezvous/KV controller and
exports its address through ``-x``, exactly like the static launcher.
"""

from __future__ import annotations

import os
import shutil
import subprocess
import sys
from typing import Dict, List, Optional

from . import hosts as hosts_mod
from .launch import require_one_tpu_process_per_host, start_job_services
from ..utils.logging import get_logger

# env vars forwarded to workers (reference mpi_run.py's -x list is the
# analogous framework env surface)
_FORWARD_PREFIXES = ("HVD_TPU_", "HOROVOD_", "JAX_", "XLA_", "TPU_",
                     "PYTHONPATH", "PATH", "LD_LIBRARY_PATH")


def is_mpi_available() -> bool:
    """Reference ``mpi_available()`` (``runner/mpi_run.py``): can we
    find a usable ``mpirun``?"""
    return shutil.which("mpirun") is not None


def get_mpi_command(
    np_: int,
    hosts: Optional[str],
    command: List[str],
    env: Dict[str, str],
    *,
    mpi_args: Optional[List[str]] = None,
    forward_names: Optional[List[str]] = None,
) -> List[str]:
    """Build the full mpirun command line (exposed for tests, like the
    reference's unit-tested command construction)."""
    cmd = [
        "mpirun",
        "--allow-run-as-root",
        "-np", str(np_),
    ]
    if hosts:
        # hosts syntax "h1:slots,h2:slots" maps to mpirun -H
        cmd += ["-H", hosts]
    # forward the framework env plus anything the caller set explicitly
    names = sorted(
        {k for k in env if k.startswith(_FORWARD_PREFIXES)}
        | set(forward_names or ())
    )
    for k in names:
        cmd += ["-x", k]
    cmd += list(mpi_args or [])
    cmd += [
        sys.executable, "-m", "horovod_tpu.runner.mpi_worker",
    ] + list(command)
    return cmd


def mpi_run(
    np_: int,
    hosts: Optional[str],
    command: List[str],
    *,
    extra_env: Optional[Dict[str, str]] = None,
    mpi_args: Optional[List[str]] = None,
    verbose: bool = False,
) -> int:
    """Launch ``np_`` workers via mpirun; returns mpirun's exit code.

    The controller (KV/barrier/rendezvous) runs in this process for the
    job's lifetime, as in ``launch_static``.
    """
    if not is_mpi_available():
        raise RuntimeError(
            "mpirun not found on PATH (reference mpi_run.py raises the "
            "same); install Open MPI or use the default launcher"
        )
    host_list = (
        hosts_mod.parse_hosts(hosts) if hosts
        else [hosts_mod.HostInfo("localhost", np_)]
    )
    assignments = hosts_mod.get_host_assignments(host_list, np_)
    require_one_tpu_process_per_host(assignments, extra_env)
    server, service_env = start_job_services(
        np_, [a.hostname for a in assignments], nic_probe=False
    )
    env = dict(os.environ)
    env.update(service_env)
    if extra_env:
        env.update(extra_env)
    cmd = get_mpi_command(
        np_, hosts, command, env, mpi_args=mpi_args,
        forward_names=sorted(extra_env) if extra_env else None,
    )
    if verbose:
        get_logger().warning("mpirun launch: %s", " ".join(cmd))
    try:
        return subprocess.run(cmd, env=env).returncode
    finally:
        server.stop()
