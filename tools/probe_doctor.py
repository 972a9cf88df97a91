"""Device-probe doctor: WHERE does a dead probe die?

A process that cannot reach its device says *that* it died (rc /
timeout + a stderr tail), not *which layer* died.  A device another
process holds, a libtpu version clash, and a broken Python env all look
alike from outside, and each one has a different owner.

This doctor reruns the probe as three separable stages, each its own
subprocess with its own timeout, per-stage wall clock, and stderr
capture:

``import_jax``
    ``import jax`` alone — a failure here is an install/env problem
    (missing wheel, broken libtpu import), no device involved;
``backend_init``
    ``jax.devices()`` — the first runtime/backend handshake; this is
    where a chip held by another process fails or hangs;
``compute``
    ``jnp.ones(8).sum()`` — first real compile + execute; a failure
    here with a live backend points at XLA/compilation, not transport.

The verdict is the FIRST failing stage — everything after it is
skipped (it would fail for the same reason and double the wait).  The
record is JSON-stable::

    {"status": "ok"|"sick", "verdict": {"stage", "cause", "detail",
                                        "backend_family"},
     "stages": [{"stage", "status", "seconds", "returncode",
                 "stderr_tail", "stdout", "timeout_s"}, ...],
     "platform": {...},
     "backend": {"requested", "platform", "family"}}

The ``backend`` record resolves the accelerator backend family the
way ``horovod_tpu/backend/registry.py`` does (env override, else the
probed platform) WITHOUT importing horovod_tpu — the doctor stays
runnable in an env so broken that only stdlib imports work.  It is
what lets a reader tell "no TPU on this host" from "GPU host, gpu
family" straight from the verdict.

Run standalone (``python tools/probe_doctor.py [--timeout-s N]
[--platform cpu]``); the telemetry server's ``GET /health`` attaches
the same record (``runner/telemetry_http.py``).  Each stage is a child
process that opens the device, so do not run it from a process that
holds the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time
from typing import Any, Dict, List, Optional

DEFAULT_STAGE_TIMEOUT_S = 60.0
STDERR_TAIL_CHARS = 800

# (stage, one-liner, cause when it fails) — ordered cheapest first;
# the first failure is the verdict and later stages are skipped.
STAGES = (
    ("import_jax",
     "import jax; print(jax.__version__)",
     "python environment: jax failed to import"),
    ("backend_init",
     "import jax; print(jax.default_backend(), len(jax.devices()))",
     "device runtime: backend handshake failed or hung"),
    ("compute",
     "import jax, jax.numpy as jnp; print(float(jnp.ones(8).sum()))",
     "compile/execute: backend alive but first computation failed"),
)


def _tail(err: Any) -> str:
    if err is None:
        return ""
    if isinstance(err, bytes):
        err = err.decode("utf-8", "replace")
    return str(err)[-STDERR_TAIL_CHARS:]


def run_stage(stage: str, code: str, timeout_s: float,
              env: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """One stage in its own interpreter: status ok|error|timeout, wall
    seconds, rc, and the stderr tail — everything the verdict needs."""
    t0 = time.monotonic()
    out: Dict[str, Any] = {
        "stage": stage, "status": "ok", "returncode": 0,
        "stderr_tail": "", "timeout_s": timeout_s,
    }
    try:
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True, text=True, timeout=timeout_s,
            env=dict(env if env is not None else os.environ),
        )
        out["returncode"] = proc.returncode
        out["stdout"] = (proc.stdout or "").strip()[:200]
        if proc.returncode != 0:
            out["status"] = "error"
            out["stderr_tail"] = _tail(proc.stderr)
    except subprocess.TimeoutExpired as e:
        out["status"] = "timeout"
        out["returncode"] = None
        out["stderr_tail"] = _tail(getattr(e, "stderr", None))
    except OSError as e:  # interpreter itself unlaunchable
        out["status"] = "error"
        out["returncode"] = None
        out["stderr_tail"] = f"{type(e).__name__}: {e}"
    out["seconds"] = round(time.monotonic() - t0, 3)
    return out


def _backend_record(env_map: Dict[str, str],
                    stages: List[Dict[str, Any]]) -> Dict[str, Any]:
    """Resolve requested/platform/family with stdlib only, mirroring
    ``backend/registry.py``'s rules: env override first (with the
    registry's aliases), else the platform the ``backend_init`` stage
    actually printed, else the JAX_PLATFORMS request."""
    requested = (env_map.get("HVD_TPU_BACKEND")
                 or env_map.get("HOROVOD_BACKEND") or "auto")
    platform = ""
    for rec in stages:
        if rec.get("stage") == "backend_init" and rec.get("stdout"):
            platform = rec["stdout"].split()[0].lower()
            break
    if not platform:
        platform = (env_map.get("JAX_PLATFORMS") or
                    "uninitialized").split(",")[0].strip().lower()
    fam = requested.strip().lower()
    fam = {"cuda": "gpu", "rocm": "gpu", "nvidia": "gpu"}.get(fam, fam)
    if fam not in ("tpu", "gpu"):
        if platform in ("gpu", "cuda", "rocm"):
            fam = "gpu"
        elif platform in ("tpu", "cpu"):
            fam = "tpu"  # registry: every non-gpu platform -> tpu
        else:
            fam = "unknown"
    return {"requested": requested, "platform": platform, "family": fam}


def diagnose(timeout_s: float = DEFAULT_STAGE_TIMEOUT_S,
             env: Optional[Dict[str, str]] = None) -> Dict[str, Any]:
    """Run the stage ladder; return the structured root-cause record.
    Never raises — a doctor that crashes mid-diagnosis is worse than
    no doctor."""
    stages: List[Dict[str, Any]] = []
    verdict: Optional[Dict[str, Any]] = None
    try:
        for stage, code, cause in STAGES:
            rec = run_stage(stage, code, timeout_s, env=env)
            stages.append(rec)
            if rec["status"] != "ok":
                verdict = {
                    "stage": stage,
                    "cause": cause,
                    "detail": (
                        f"{rec['status']} after {rec['seconds']}s"
                        + (f" (rc={rec['returncode']})"
                           if rec["returncode"] is not None else "")
                    ),
                }
                break
    except Exception as e:  # pragma: no cover - defensive
        verdict = {"stage": "doctor", "cause": "doctor itself failed",
                   "detail": f"{type(e).__name__}: {e}"}
    backend = _backend_record(dict(env if env is not None
                                   else os.environ), stages)
    if verdict is not None:
        verdict["backend_family"] = backend["family"]
    return {
        "status": "ok" if verdict is None else "sick",
        "verdict": verdict,
        "stages": stages,
        "platform": {
            "python": sys.version.split()[0],
            "jax_platforms": (env or os.environ).get(
                "JAX_PLATFORMS", os.environ.get("JAX_PLATFORMS", "")),
        },
        "backend": backend,
    }


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        description="Diagnose which layer of the device probe is sick."
    )
    ap.add_argument("--timeout-s", type=float,
                    default=DEFAULT_STAGE_TIMEOUT_S,
                    help="per-stage subprocess timeout (default 60)")
    ap.add_argument("--platform", default=None,
                    help="force JAX_PLATFORMS for the probes "
                         "(e.g. cpu)")
    ns = ap.parse_args(argv)
    env = dict(os.environ)
    if ns.platform:
        env["JAX_PLATFORMS"] = ns.platform
    report = diagnose(timeout_s=ns.timeout_s, env=env)
    print(json.dumps(report, indent=2))
    return 0 if report["status"] == "ok" else 1


if __name__ == "__main__":
    sys.exit(main())
