"""``hvdrun`` — the launcher CLI.

Reference: ``horovodrun`` (``horovod/runner/launch.py``, 774 LoC): parses
np/hosts/elastic flags plus every HOROVOD_* knob, starts the rendezvous
server, computes host assignments, and execs workers over ssh with
per-slot env.  The TPU launcher keeps that surface but drops the
MPI/gloo controller choice (the data plane is XLA) and the NIC-discovery
driver (the JAX coordination service exchanges addresses itself).

Worker env contract (read by ``runtime._init_distributed`` /
``Runtime``):
  HVD_TPU_COORDINATOR_ADDR  host:port of the jax.distributed coordinator
                            (runs inside worker 0)
  HVD_TPU_CROSS_RANK/SIZE   process id / process count
  HVD_TPU_RENDEZVOUS_ADDR/PORT/SECRET  the controller KV store
"""

from __future__ import annotations

import argparse
import os
import secrets as pysecrets
import shlex
import signal
import socket
import sys
import time
from typing import Dict, List, Optional, Tuple

from ..utils.logging import get_logger
from ..version import __version__
from . import controller_py, exec_utils, hosts as hosts_mod


def free_port() -> int:
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def start_job_services(
    np_: int,
    worker_hosts: List[str],
    *,
    ssh_port: Optional[int] = None,
    ssh_identity_file: Optional[str] = None,
    nic_probe: bool = True,
) -> Tuple[object, Dict[str, str]]:
    """Start the KV/rendezvous controller in this (launcher) process and
    build the service env every launch path exports — one implementation
    shared by the static, mpirun, and jsrun paths so they cannot drift.

    ``worker_hosts`` is ordered: worker 0 — which hosts the
    ``jax.distributed`` coordinator per the env contract above — runs on
    ``worker_hosts[0]``.  Loopback addresses are only used when every
    worker is local to the launcher.  Returns ``(server, env)``; the
    caller owns ``server.stop()``.
    """
    secret = pysecrets.token_hex(16)
    server = controller_py.make_server(secret, np_)
    all_local = all(exec_utils.is_local(h) for h in worker_hosts)
    # Mutually-verified launcher address (the reference NIC-probe
    # protocol): one probe covers both the rendezvous KV and a
    # launcher-local coordinator.  Launchers that do not reach workers
    # over ssh (mpirun/jsrun own the remote exec) pass nic_probe=False
    # and keep the heuristic.
    if all_local:
        launcher_addr = "127.0.0.1"
    elif nic_probe:
        launcher_addr = exec_utils.probe_routable_addr(
            worker_hosts, ssh_port=ssh_port,
            ssh_identity_file=ssh_identity_file,
        )
    else:
        launcher_addr = exec_utils.routable_addr(worker_hosts)
    if all_local:
        coordinator_host = "127.0.0.1"
    elif exec_utils.is_local(worker_hosts[0]):
        # worker 0 runs on this launcher host but peers are remote: they
        # must dial a routable name, not the literal "localhost".
        coordinator_host = launcher_addr
    else:
        coordinator_host = worker_hosts[0]
    env = {
        "HVD_TPU_COORDINATOR_ADDR": f"{coordinator_host}:{free_port()}",
        "HVD_TPU_CROSS_SIZE": str(np_),
        "HVD_TPU_RENDEZVOUS_ADDR": launcher_addr,
        "HVD_TPU_RENDEZVOUS_PORT": str(server.port),
        "HVD_TPU_SECRET": secret,
    }
    return server, env


def slot_env_entries(slot: hosts_mod.SlotInfo) -> Dict[str, str]:
    """The per-slot half of the worker env contract."""
    return {
        "HVD_TPU_CROSS_RANK": str(slot.rank),
        "HVD_TPU_CROSS_SIZE": str(slot.size),
        "HVD_TPU_LOCAL_RANK": str(slot.local_rank),
        "HVD_TPU_LOCAL_SIZE": str(slot.local_size),
        "HVD_TPU_HOSTNAME": slot.hostname,
    }


def require_one_tpu_process_per_host(
    assignments: List[hosts_mod.SlotInfo],
    extra_env: Optional[Dict[str, str]] = None,
) -> None:
    """Refuse a launch that would put several TPU workers on one host.

    libtpu gives a host's chips to the first process that asks, and
    nothing here hands each local slot a chip of its own: on a four-chip
    v5e host ``hvdrun -np 4`` left one worker holding all four chips and
    waiting for peers that had died on libtpu's lockfile, and the
    launcher never returned.  One process drives every local chip
    (``hvd.init()`` takes all of ``jax.devices()``), so the launch of
    record is one process per host.  (libtpu's per-process variables do
    give each worker a chip, but the TPU runtime then numbers processes
    by chip coordinates, not by launcher rank: PERF.md, Bring-up 9.)"""
    if not tpu_backend_configured({**os.environ, **(extra_env or {})}):
        return
    crowded = sorted({a.hostname for a in assignments if a.local_size > 1})
    if crowded:
        raise ValueError(
            f"{len(assignments)} TPU worker processes were requested with "
            f"more than one on host(s) {', '.join(crowded)}. A TPU host's "
            "chips belong to one process at a time: use one process per "
            "host (e.g. -np <hosts> -H host1:1,host2:1) — it drives every "
            "local chip — or set JAX_PLATFORMS=cpu for CPU workers."
        )


def make_worker_env(
    slot: hosts_mod.SlotInfo,
    coordinator_addr: str,
    rendezvous_addr: str,
    rendezvous_port: int,
    secret: str,
    extra_env: Optional[Dict[str, str]] = None,
) -> Dict[str, str]:
    env = {
        "HVD_TPU_COORDINATOR_ADDR": coordinator_addr,
        "HVD_TPU_RENDEZVOUS_ADDR": rendezvous_addr,
        "HVD_TPU_RENDEZVOUS_PORT": str(rendezvous_port),
        "HVD_TPU_SECRET": secret,
        **slot_env_entries(slot),
    }
    if extra_env:
        env.update(extra_env)
    return env


def launch_static(
    np_: int,
    host_list: List[hosts_mod.HostInfo],
    command: List[str],
    *,
    ssh_port: Optional[int] = None,
    ssh_identity_file: Optional[str] = None,
    extra_env: Optional[Dict[str, str]] = None,
    verbose: bool = False,
) -> int:
    """Static (fixed world) launch (reference ``launch_gloo``,
    ``runner/gloo_run.py:226``).  Returns the first non-zero exit code,
    terminating the remaining workers on failure like the reference.
    """
    assignments = hosts_mod.get_host_assignments(host_list, np_)
    require_one_tpu_process_per_host(assignments, extra_env)
    server, service_env = start_job_services(
        np_, [a.hostname for a in assignments],
        ssh_port=ssh_port, ssh_identity_file=ssh_identity_file,
    )
    if verbose:
        get_logger().warning(
            "launching %d process(es) on %d host(s); rendezvous %s:%s",
            np_, assignments[-1].cross_size,
            service_env["HVD_TPU_RENDEZVOUS_ADDR"],
            service_env["HVD_TPU_RENDEZVOUS_PORT"],
        )
    workers = []
    try:
        for slot in assignments:
            env = dict(service_env)
            env.update(slot_env_entries(slot))
            if extra_env:
                env.update(extra_env)
            workers.append(
                exec_utils.WorkerProcess(
                    slot.rank, slot.hostname, command, env,
                    ssh_port=ssh_port, ssh_identity_file=ssh_identity_file,
                )
            )
        exit_code = 0
        pending = set(range(len(workers)))
        while pending:
            for i in sorted(pending):
                rc = workers[i].returncode
                if rc is not None:
                    pending.discard(i)
                    if rc != 0:
                        exit_code = exit_code or rc
                        # fail fast: a dead peer wedges collectives
                        for j in pending:
                            workers[j].terminate()
                        pending = set()
                        break
            time.sleep(0.1)
        for w in workers:
            w.wait()
        return exit_code
    finally:
        for w in workers:
            w.terminate()
        server.stop()


def tpu_backend_configured(environ: Optional[Dict[str, str]] = None) -> bool:
    """Would a jax process started with ``environ`` run on a TPU?
    Decided without opening a backend (which would hold the chips):
    ``libtpu`` is installed and ``JAX_PLATFORMS`` does not rule the TPU
    out."""
    import importlib.util

    if environ is None:
        environ = os.environ
    platforms = [
        p.strip().lower()
        for p in environ.get("JAX_PLATFORMS", "").split(",") if p.strip()
    ]
    return (importlib.util.find_spec("libtpu") is not None
            and (not platforms or "tpu" in platforms))


def check_build(out=None) -> None:
    """Print the capability report (reference ``check_build``,
    ``runner/launch.py:110`` — 'Available Frameworks/Controllers/Tensor
    Operations' box)."""
    def flag(ok: bool) -> str:
        return "[X]" if ok else "[ ]"

    lines = [f"horovod_tpu v{__version__}:", "", "Available Frameworks:"]
    for mod, name in [("jax", "JAX"), ("flax", "Flax"), ("optax", "Optax"),
                      ("orbax.checkpoint", "Orbax")]:
        try:
            __import__(mod)
            ok = True
        except ImportError:
            ok = False
        lines.append(f"    {flag(ok)} {name}")
    # Like the reference, report configured capabilities without
    # initializing backends (jax.devices() would hold the chips, and a
    # launcher that holds them starves its own workers).
    lines += ["", "Configured Device Backends:"]
    tpu_configured = tpu_backend_configured()
    lines.append(f"    {flag(tpu_configured)} TPU")
    lines.append(f"    {flag(True)} CPU (XLA host)")
    lines += ["", "Available Components:"]
    from .. import native

    lines.append(f"    {flag(native.available())} native core (C++)")
    try:
        from jax.experimental import pallas  # noqa: F401

        has_pallas = True
    except ImportError:
        has_pallas = False
    lines.append(f"    {flag(has_pallas)} Pallas kernels")
    for ok, name in [(True, "process sets"), (True, "elastic"),
                     (True, "timeline"), (True, "autotune"),
                     (True, "Adasum"), (True, "ZeRO/FSDP"),
                     (True, "TP/PP/SP/MoE"),
                     (True, "sequence packing"),
                     (True, "differentiable bridge collectives")]:
        lines.append(f"    {flag(ok)} {name}")
    lines += ["", "Available Bindings:"]
    import importlib.util as _ilu

    for mod, name in [("torch", "PyTorch (interop.torch)"),
                      ("tensorflow", "TensorFlow/Keras (interop.tf)"),
                      ("mxnet", "MXNet (interop.mxnet)")]:
        # find_spec, not import: a capability report must not pay
        # framework import time (or crash on a broken install)
        try:
            ok = _ilu.find_spec(mod) is not None
        except (ImportError, ValueError):
            ok = False
        lines.append(f"    {flag(ok)} {name}")
    lines += ["", "Available Launchers:"]
    import shutil as _shutil

    lines.append(f"    {flag(True)} static ssh (hvdrun)")
    lines.append(f"    {flag(_shutil.which('mpirun') is not None)} mpirun "
                 "(--use-mpi)")
    from . import lsf as _lsf

    lines.append(f"    {flag(_lsf.is_jsrun_installed())} jsrun "
                 "(--use-jsrun)")
    lines.append(f"    {flag(True)} elastic (--min-np/--max-np)")
    try:
        has_pyspark = _ilu.find_spec("pyspark") is not None
    except (ImportError, ValueError):
        has_pyspark = False
    lines.append(f"    {flag(has_pyspark)} elastic on Spark "
                 "(spark.run_elastic)")
    print("\n".join(lines), file=out)


def parse_args(argv: Optional[List[str]] = None) -> argparse.Namespace:
    parser = argparse.ArgumentParser(
        prog="hvdrun",
        description="Launch a horovod_tpu distributed job "
        "(the horovodrun equivalent).",
    )
    parser.add_argument("--version", action="version", version=__version__)
    parser.add_argument("-np", "--num-proc", type=int, dest="np",
                        help="total number of worker processes")
    parser.add_argument("-H", "--hosts",
                        help="comma list of host:slots (default localhost:np)")
    parser.add_argument("--hostfile",
                        help="hostfile with 'host slots=N' lines")
    parser.add_argument("-p", "--ssh-port", type=int, dest="ssh_port")
    parser.add_argument("-i", "--ssh-identity-file", dest="ssh_identity_file")
    parser.add_argument("--verbose", action="store_true")
    # elastic flags (reference --min-np/--max-np/--host-discovery-script)
    parser.add_argument("--min-np", type=int, dest="min_np")
    parser.add_argument("--max-np", type=int, dest="max_np")
    parser.add_argument("--host-discovery-script", dest="discovery_script")
    # knob flags -> env (reference config_parser.py maps flags to env)
    parser.add_argument("--fusion-threshold-mb", type=int)
    parser.add_argument("--timeline-filename")
    parser.add_argument("--timeline-mark-cycles", action="store_true",
                        help="mark each train-step cycle on the timeline "
                        "(reference HOROVOD_TIMELINE_MARK_CYCLES; maps to "
                        "HVD_TPU_TIMELINE_MARK_CYCLES)")
    parser.add_argument("--telemetry-port", type=int, default=None,
                        help="serve HTTP /metrics + /health from the "
                        "elastic driver on this port (0 = OS-assigned; "
                        "maps to HVD_TPU_TELEMETRY_PORT)")
    parser.add_argument("--autotune", action="store_true")
    parser.add_argument("--autotune-log-file")
    parser.add_argument("--log-level")
    parser.add_argument("--use-mpi", action="store_true",
                        help="launch workers via mpirun (reference "
                        "horovodrun --use-mpi; MPI is launcher-only — "
                        "collectives still ride XLA)")
    parser.add_argument("--use-jsrun", action="store_true",
                        help="launch workers via jsrun inside an LSF "
                        "allocation (reference js_run.py; launcher-only)")
    parser.add_argument("--mpi-args", default="",
                        help="extra args appended to the mpirun (or, "
                        "with --use-jsrun, the jsrun) command line")
    parser.add_argument("--config-file",
                        help="JSON/YAML config with the same knobs "
                        "(CLI flags win on conflict)")
    parser.add_argument("--check-build", action="store_true",
                        help="print the capability report and exit")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="worker command, e.g. python train.py")
    args = parser.parse_args(argv)
    if args.check_build:
        return args
    if args.config_file:
        from .config_parser import apply_config_to_args, parse_config_file

        apply_config_to_args(args, parse_config_file(args.config_file))
    # Launcher-conflict validation runs AFTER the config file is folded
    # in, so elastic knobs declared there are caught too.
    if args.use_mpi and args.use_jsrun:
        parser.error("--use-mpi and --use-jsrun are mutually exclusive")
    if args.use_jsrun and (args.min_np is not None or args.max_np is not None
                           or args.discovery_script):
        parser.error("--use-jsrun cannot be combined with elastic flags "
                     "(--min-np/--max-np/--host-discovery-script)")
    if not args.command:
        parser.error("no worker command given")
    if args.command[0] == "--":
        args.command = args.command[1:]
    if args.np is None and args.min_np is None:
        from . import lsf

        if not lsf.using_lsf():
            parser.error("-np (or --min-np for elastic) is required "
                         "(inferred from the allocation under LSF)")
    return args


def env_from_args(args: argparse.Namespace) -> Dict[str, str]:
    """Map CLI knob flags onto HVD_TPU_* env (reference
    ``runner/common/util/config_parser.py``)."""
    env: Dict[str, str] = {}
    if args.fusion_threshold_mb is not None:
        env["HVD_TPU_FUSION_THRESHOLD"] = str(args.fusion_threshold_mb << 20)
    if args.timeline_filename:
        env["HVD_TPU_TIMELINE"] = args.timeline_filename
    if getattr(args, "timeline_mark_cycles", False):
        env["HVD_TPU_TIMELINE_MARK_CYCLES"] = "1"
    if args.autotune:
        env["HVD_TPU_AUTOTUNE"] = "1"
    if args.autotune_log_file:
        env["HVD_TPU_AUTOTUNE_LOG"] = args.autotune_log_file
    if args.log_level:
        env["HVD_TPU_LOG_LEVEL"] = args.log_level
    return env


def run_commandline(argv: Optional[List[str]] = None) -> int:
    args = parse_args(argv)
    if args.check_build:
        check_build()
        return 0
    from . import lsf

    if args.np is None and args.min_np is None:
        # np was allowed to be omitted only under LSF: infer one worker
        # per allocated host BEFORE any launch branch consumes args.np —
        # but never against an explicit -H/--hostfile, whose slot layout
        # the user chose deliberately.
        if args.hosts or args.hostfile:
            print("hvdrun: -np is required when -H/--hostfile is given "
                  "(LSF inference applies only to allocation-derived "
                  "hosts)", file=sys.stderr)
            return 2
        try:
            args.np = len(lsf.get_compute_hosts())
        except RuntimeError as e:
            print(f"hvdrun: {e}", file=sys.stderr)
            return 2
    if args.discovery_script or args.min_np is not None:
        from .elastic_launch import launch_elastic

        return launch_elastic(args)
    if args.use_mpi:
        from .mpi_run import mpi_run

        hosts = args.hosts
        if args.hostfile and not hosts:
            # translate the hostfile to mpirun -H syntax
            hosts = ",".join(
                f"{h.hostname}:{h.slots}"
                for h in hosts_mod.parse_host_files(args.hostfile)
            )
        if not hosts and lsf.using_lsf():
            # Same allocation-derived hosts the static branch uses —
            # otherwise mpirun gets no -H and packs every worker onto
            # the launch host.
            hosts = ",".join(
                f"{h.hostname}:{h.slots}"
                for h in lsf.lsf_host_list(np_=args.np)
            )
        return mpi_run(
            args.np, hosts, args.command,
            extra_env=env_from_args(args),
            mpi_args=shlex.split(args.mpi_args) if args.mpi_args else None,
            verbose=args.verbose,
        )
    if args.use_jsrun:
        jsrun_hosts = None
        if args.hostfile and not args.hosts:
            jsrun_hosts = {
                h.hostname: h.slots
                for h in hosts_mod.parse_host_files(args.hostfile)
            }
        elif args.hosts:
            jsrun_hosts = {
                h.hostname: h.slots
                for h in hosts_mod.parse_hosts(args.hosts)
            }
        return lsf.js_run(
            args.np, args.command,
            hosts=jsrun_hosts,
            extra_env=env_from_args(args),
            extra_args=shlex.split(args.mpi_args) if args.mpi_args else None,
            verbose=args.verbose,
        )
    if args.hostfile:
        host_list = hosts_mod.parse_host_files(args.hostfile)
    elif args.hosts:
        host_list = hosts_mod.parse_hosts(args.hosts)
    elif lsf.using_lsf():
        # Inside an LSF allocation with no explicit hosts: use the
        # job's allocated hosts, one worker process per host — growing
        # slots when an explicit -np exceeds the host count (reference
        # launch.py consults LSFUtils the same way before defaulting to
        # localhost).
        host_list = lsf.lsf_host_list(np_=args.np)
    else:
        host_list = [hosts_mod.HostInfo("localhost", args.np)]
    extra_env = env_from_args(args)
    try:
        # a usage error, not a traceback (launch_static checks again for
        # callers that are not the command line)
        require_one_tpu_process_per_host(
            hosts_mod.get_host_assignments(host_list, args.np), extra_env
        )
    except ValueError as e:
        print(f"hvdrun: {e}", file=sys.stderr)
        return 2
    return launch_static(
        args.np,
        host_list,
        args.command,
        ssh_port=args.ssh_port,
        ssh_identity_file=args.ssh_identity_file,
        extra_env=extra_env,
        verbose=args.verbose,
    )


def main() -> None:
    # A killed launcher takes its workers with it: SIGTERM's default
    # action skips every ``finally`` that terminates them, and an
    # orphaned TPU worker keeps its chips (seen on the four-chip host).
    signal.signal(signal.SIGTERM,
                  lambda signum, frame: sys.exit(128 + signum))
    sys.exit(run_commandline())


if __name__ == "__main__":
    main()
