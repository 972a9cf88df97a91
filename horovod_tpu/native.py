"""ctypes bindings for the native core (libhvd_core.so).

The reference's Python layer loads its per-framework C++ extension with
ctypes (``horovod/common/basics.py:29`` loads the shared lib and calls
the C ABI); this module does the same for the TPU core, exposing:

  fusion_plan       — bucketing (reference FuseResponses)
  ResponseCache     — LRU negotiation-cache analog
  NativeTimeline    — chrome-tracing writer thread
  StallInspector    — pending-op watchdog
  ControllerServer/ControllerClient — authenticated TCP KV + barrier
                      (reference gloo rendezvous + driver/task RPC)
  Autotune          — GP/EI tuner (reference parameter_manager + optim/)
  encode_request/decode_request — wire message codec

``load()`` builds the library with make on first use if it is missing
(kept out of git; the source is the artifact).  All consumers fall back
to pure-Python implementations when the toolchain is unavailable.
"""

from __future__ import annotations

import ctypes
import os
import subprocess
import threading
from typing import List, Optional, Sequence, Tuple

_HERE = os.path.dirname(os.path.abspath(__file__))
_CPP_DIR = os.path.join(_HERE, "cpp")
_LIB_PATH = os.path.join(_CPP_DIR, "build", "libhvd_core.so")

_lib = None
_lib_lock = threading.Lock()
_build_failed = False


def _stale() -> bool:
    """True when the built lib is missing or older than any source —
    editing cpp/src must not leave a silently stale libhvd_core.so."""
    if not os.path.exists(_LIB_PATH):
        return True
    built = os.path.getmtime(_LIB_PATH)
    for sub in ("src", "include"):
        d = os.path.join(_CPP_DIR, sub)
        if not os.path.isdir(d):
            continue
        for f in os.listdir(d):
            if f.endswith((".cc", ".h")):
                if os.path.getmtime(os.path.join(d, f)) > built:
                    return True
    return False


def ensure_built() -> None:
    """Build the native core from ``cpp/src`` with make when it is
    missing or older than a source file.  Raises with make's output
    when the build fails — for callers (``chip_smoke.py``) to which a
    quiet pure-Python path would hide a broken toolchain or source."""
    # Serialize concurrent builds (multiple worker processes on one
    # host share cpp/build): flock + re-check.
    import fcntl

    lock_path = os.path.join(_CPP_DIR, ".build.lock")
    with open(lock_path, "w") as lock_fh:
        fcntl.flock(lock_fh, fcntl.LOCK_EX)
        if not _stale():
            return
        proc = subprocess.run(
            ["make", "-C", _CPP_DIR], capture_output=True, text=True,
            timeout=300,
        )
    if proc.returncode != 0:
        raise RuntimeError(
            f"native core build failed (make rc={proc.returncode}):\n"
            f"{(proc.stdout + proc.stderr)[-2000:]}"
        )


def load(build: bool = True) -> Optional[ctypes.CDLL]:
    """Load (building if needed) the native core; None if unavailable."""
    global _lib, _build_failed
    with _lib_lock:
        if _lib is not None:
            return _lib
        if _stale() and build and not _build_failed:
            try:
                ensure_built()
            except Exception:
                _build_failed = True
                # A failed REbuild must not abandon a loadable library
                # (e.g. stale mtimes after checkout on a host with no
                # toolchain): fall through and load what exists.
                if not os.path.exists(_LIB_PATH):
                    return None
        if not os.path.exists(_LIB_PATH):
            return None
        if _build_failed and _stale():
            import logging

            logging.getLogger("horovod_tpu").warning(
                "native core rebuild failed; loading stale %s built before "
                "the latest cpp/src change — native encode/decode may not "
                "match the Python wire format", _LIB_PATH,
            )
        lib = ctypes.CDLL(_LIB_PATH)
        _configure(lib)
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def _configure(lib: ctypes.CDLL) -> None:
    c = ctypes
    lib.hvd_version.restype = c.c_char_p
    lib.hvd_last_error.restype = c.c_char_p
    lib.hvd_fusion_plan.restype = c.c_int64
    lib.hvd_fusion_plan.argtypes = [
        c.POINTER(c.c_int64), c.POINTER(c.c_int32), c.c_int64, c.c_int64,
        c.POINTER(c.c_int64),
    ]
    lib.hvd_cache_new.restype = c.c_void_p
    lib.hvd_cache_new.argtypes = [c.c_int64]
    lib.hvd_cache_free.argtypes = [c.c_void_p]
    lib.hvd_cache_lookup.restype = c.c_int32
    lib.hvd_cache_lookup.argtypes = [c.c_void_p, c.c_char_p, c.c_uint64]
    lib.hvd_cache_erase.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_cache_size.restype = c.c_int64
    lib.hvd_cache_size.argtypes = [c.c_void_p]
    lib.hvd_timeline_open.restype = c.c_void_p
    lib.hvd_timeline_open.argtypes = [c.c_char_p]
    lib.hvd_timeline_close.argtypes = [c.c_void_p]
    lib.hvd_timeline_event.argtypes = [
        c.c_void_p, c.c_char_p, c.c_char_p, c.c_char, c.c_int64, c.c_int64,
        c.c_int32, c.c_int32, c.c_int64,
    ]
    lib.hvd_timeline_dropped.restype = c.c_int64
    lib.hvd_timeline_dropped.argtypes = [c.c_void_p]
    lib.hvd_stall_new.restype = c.c_void_p
    lib.hvd_stall_new.argtypes = [c.c_double, c.c_double]
    lib.hvd_stall_free.argtypes = [c.c_void_p]
    lib.hvd_stall_begin.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_stall_end.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_stall_report.restype = c.c_int64
    lib.hvd_stall_report.argtypes = [
        c.c_void_p, c.c_char_p, c.c_int64, c.POINTER(c.c_int32)
    ]
    lib.hvd_wire_encode_request.restype = c.c_int64
    lib.hvd_wire_encode_request.argtypes = [
        c.c_int32, c.c_int32, c.c_int32, c.c_int32, c.POINTER(c.c_int64),
        c.c_int32, c.c_char_p, c.POINTER(c.c_uint8), c.c_int64,
    ]
    lib.hvd_wire_decode_request.restype = c.c_int64
    lib.hvd_wire_decode_request.argtypes = [
        c.POINTER(c.c_uint8), c.c_int64, c.POINTER(c.c_int32),
        c.POINTER(c.c_int32), c.POINTER(c.c_int32), c.POINTER(c.c_int32),
        c.POINTER(c.c_int64), c.c_int32, c.POINTER(c.c_int32), c.c_char_p,
        c.c_int64,
    ]
    lib.hvd_wire_encode_response.restype = c.c_int64
    lib.hvd_wire_encode_response.argtypes = [
        c.c_int32, c.c_char_p, c.c_char_p, c.POINTER(c.c_int64),
        c.c_int32, c.POINTER(c.c_uint8), c.c_int64,
    ]
    lib.hvd_wire_decode_response.restype = c.c_int64
    lib.hvd_wire_decode_response.argtypes = [
        c.POINTER(c.c_uint8), c.c_int64, c.POINTER(c.c_int32), c.c_char_p,
        c.c_int64, c.c_char_p, c.c_int64, c.POINTER(c.c_int64), c.c_int32,
        c.POINTER(c.c_int32),
    ]
    lib.hvd_ctrl_server_start.restype = c.c_void_p
    lib.hvd_ctrl_server_start.argtypes = [c.c_char_p, c.c_int32, c.c_char_p,
                                          c.c_int32]
    lib.hvd_ctrl_server_port.restype = c.c_int32
    lib.hvd_ctrl_server_port.argtypes = [c.c_void_p]
    lib.hvd_ctrl_server_stop.argtypes = [c.c_void_p]
    lib.hvd_ctrl_client_connect.restype = c.c_void_p
    lib.hvd_ctrl_client_connect.argtypes = [c.c_char_p, c.c_int32, c.c_char_p,
                                            c.c_int32]
    lib.hvd_ctrl_client_close.argtypes = [c.c_void_p]
    lib.hvd_ctrl_put.restype = c.c_int32
    lib.hvd_ctrl_put.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_int64]
    lib.hvd_ctrl_get.restype = c.c_int64
    lib.hvd_ctrl_get.argtypes = [c.c_void_p, c.c_char_p, c.c_char_p,
                                 c.POINTER(c.c_uint8), c.c_int64, c.c_int64]
    lib.hvd_ctrl_delete_scope.restype = c.c_int32
    lib.hvd_ctrl_delete_scope.argtypes = [c.c_void_p, c.c_char_p]
    lib.hvd_ctrl_barrier.restype = c.c_int32
    lib.hvd_ctrl_barrier.argtypes = [c.c_void_p, c.c_char_p, c.c_int32,
                                     c.c_int64]
    lib.hvd_autotune_new.restype = c.c_void_p
    lib.hvd_autotune_new.argtypes = [c.c_double, c.c_double]
    lib.hvd_autotune_free.argtypes = [c.c_void_p]
    lib.hvd_autotune_observe.argtypes = [c.c_void_p, c.c_double, c.c_double]
    lib.hvd_autotune_suggest.restype = c.c_double
    lib.hvd_autotune_suggest.argtypes = [c.c_void_p]
    lib.hvd_autotune_best.restype = c.c_double
    lib.hvd_autotune_best.argtypes = [c.c_void_p, c.POINTER(c.c_double)]


# ---------------------------------------------------------------- fusion

def fusion_plan(
    sizes_bytes: Sequence[int], dtype_ids: Sequence[int], threshold_bytes: int
) -> Optional[List[List[int]]]:
    """Native bucket plan; None when the native core is unavailable."""
    lib = load()
    if lib is None:
        return None
    n = len(sizes_bytes)
    sizes = (ctypes.c_int64 * n)(*sizes_bytes)
    dtypes = (ctypes.c_int32 * n)(*dtype_ids)
    out = (ctypes.c_int64 * n)()
    nb = lib.hvd_fusion_plan(sizes, dtypes, n, threshold_bytes, out)
    if nb < 0:
        return None
    buckets: List[List[int]] = [[] for _ in range(nb)]
    for i in range(n):
        buckets[out[i]].append(i)
    return buckets


# ----------------------------------------------------------------- cache

class ResponseCache:
    def __init__(self, capacity: int = 1024):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._h = self._lib.hvd_cache_new(capacity)

    def lookup(self, name: str, signature: int) -> bool:
        return bool(
            self._lib.hvd_cache_lookup(self._h, name.encode(), signature)
        )

    def erase(self, name: str) -> None:
        self._lib.hvd_cache_erase(self._h, name.encode())

    def __len__(self) -> int:
        return self._lib.hvd_cache_size(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.hvd_cache_free(self._h)
            self._h = None


# -------------------------------------------------------------- timeline

class NativeTimeline:
    """Native chrome-tracing writer (preferred over the Python one)."""

    def __init__(self, path: str, rank: Optional[int] = None):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._h = self._lib.hvd_timeline_open(path.encode())
        if not self._h:
            raise OSError(f"cannot open timeline file {path}")
        import time

        self._t0 = time.perf_counter()
        # Merge metadata (tools/merge_timeline.py): the C writer has a
        # fixed event ABI with no args payload, so rank + wall-clock
        # epoch base go to a JSON sidecar instead of an in-band
        # HVD_PROC_META event (utils/timeline.py writes that form).
        import json
        import socket

        from .utils.timeline import _resolve_rank

        self.rank = _resolve_rank() if rank is None else int(rank)
        try:
            with open(path + ".hvdmeta.json", "w") as fh:
                json.dump({
                    "rank": self.rank,
                    "hostname": socket.gethostname(),
                    "pid": os.getpid(),
                    "epoch_wall_us": time.time() * 1e6,
                }, fh)
        except OSError:
            pass  # merge falls back to positional lanes

    def _now_us(self) -> int:
        import time

        return int((time.perf_counter() - self._t0) * 1e6)

    def record_op(self, name: str, activity: str, nbytes: int) -> None:
        self._lib.hvd_timeline_event(
            self._h, name.encode(), activity.encode(), b"X", self._now_us(),
            1, os.getpid(), 0, nbytes,
        )

    def begin(self, name: str, activity: str) -> None:
        self._lib.hvd_timeline_event(
            self._h, name.encode(), activity.encode(), b"B", self._now_us(),
            0, os.getpid(), 0, -1,
        )

    def end(self, name: str, activity: str) -> None:
        self._lib.hvd_timeline_event(
            self._h, name.encode(), activity.encode(), b"E", self._now_us(),
            0, os.getpid(), 0, -1,
        )

    def record_span(self, name: str, activity: str, ts_us: float,
                    dur_us: float, args: Optional[dict] = None) -> None:
        """Measured duration event (profiler-extracted ts/dur) on the
        measured lane (tid 1) — see ``Timeline.record_span``."""
        self._lib.hvd_timeline_event(
            self._h, name.encode(), activity.encode(), b"X",
            int(ts_us), max(int(dur_us), 1), os.getpid(), 1, -1,
        )

    def mark_cycle(self) -> None:
        self._lib.hvd_timeline_event(
            self._h, b"CYCLE", b"CYCLE", b"i", self._now_us(), 0,
            os.getpid(), 0, -1,
        )

    def dropped(self) -> int:
        return self._lib.hvd_timeline_dropped(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.hvd_timeline_close(self._h)
            self._h = None


# ----------------------------------------------------------------- stall

class StallInspector:
    """Pending-op watchdog (reference stall_inspector.cc)."""

    def __init__(self, warn_seconds: float = 60.0, shutdown_seconds: float = 0.0):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._h = self._lib.hvd_stall_new(warn_seconds, shutdown_seconds)

    def begin(self, name: str) -> None:
        self._lib.hvd_stall_begin(self._h, name.encode())

    def end(self, name: str) -> None:
        self._lib.hvd_stall_end(self._h, name.encode())

    def report(self) -> Tuple[List[str], bool]:
        buf = ctypes.create_string_buffer(65536)
        shutdown = ctypes.c_int32(0)
        n = self._lib.hvd_stall_report(self._h, buf, len(buf), ctypes.byref(shutdown))
        names = [s for s in buf.value.decode().split("\n") if s] if n else []
        return names, bool(shutdown.value)

    def close(self) -> None:
        if self._h:
            self._lib.hvd_stall_free(self._h)
            self._h = None


# ------------------------------------------------------------ controller

class ControllerServer:
    """Launcher-side KV/barrier service (reference RendezvousServer)."""

    def __init__(self, secret: str, world: int, bind_host: str = "0.0.0.0",
                 port: int = 0):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._h = self._lib.hvd_ctrl_server_start(
            bind_host.encode(), port, secret.encode(), world
        )
        if not self._h:
            raise OSError("controller server failed to start")

    @property
    def port(self) -> int:
        return self._lib.hvd_ctrl_server_port(self._h)

    def stop(self) -> None:
        if self._h:
            self._lib.hvd_ctrl_server_stop(self._h)
            self._h = None


class ControllerClient:
    """Worker-side client (reference gloo http_store client)."""

    def __init__(self, host: str, port: int, secret: str, rank: int):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._h = self._lib.hvd_ctrl_client_connect(
            host.encode(), port, secret.encode(), rank
        )
        if not self._h:
            raise OSError(f"cannot connect controller at {host}:{port}")

    def put(self, scope: str, key: str, value: bytes) -> None:
        buf = (ctypes.c_uint8 * len(value)).from_buffer_copy(value) if value else None
        rc = self._lib.hvd_ctrl_put(
            self._h, scope.encode(), key.encode(), buf, len(value)
        )
        if rc != 0:
            raise OSError("controller put failed")

    def get(self, scope: str, key: str, timeout_ms: int = -1) -> Optional[bytes]:
        cap = 64 << 20
        buf = (ctypes.c_uint8 * cap)()
        n = self._lib.hvd_ctrl_get(
            self._h, scope.encode(), key.encode(), buf, cap, timeout_ms
        )
        if n < 0:
            return None
        return bytes(buf[: min(n, cap)])

    def delete_scope(self, scope: str) -> None:
        self._lib.hvd_ctrl_delete_scope(self._h, scope.encode())

    def barrier(self, name: str, count: int, timeout_ms: int = -1) -> bool:
        return (
            self._lib.hvd_ctrl_barrier(self._h, name.encode(), count, timeout_ms)
            == 0
        )

    def close(self) -> None:
        if self._h:
            self._lib.hvd_ctrl_client_close(self._h)
            self._h = None


# -------------------------------------------------------------- autotune

class Autotune:
    """GP/EI tuner over log2(fusion threshold bytes)."""

    def __init__(self, low_log2_bytes: float = 16.0, high_log2_bytes: float = 28.0):
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native core unavailable")
        self._h = self._lib.hvd_autotune_new(low_log2_bytes, high_log2_bytes)

    def observe(self, log2_bytes: float, score: float) -> None:
        self._lib.hvd_autotune_observe(self._h, log2_bytes, score)

    def suggest(self) -> float:
        return self._lib.hvd_autotune_suggest(self._h)

    def best(self) -> Tuple[float, float]:
        score = ctypes.c_double(0)
        x = self._lib.hvd_autotune_best(self._h, ctypes.byref(score))
        return x, score.value

    def close(self) -> None:
        if self._h:
            self._lib.hvd_autotune_free(self._h)
            self._h = None


# ------------------------------------------------------------------ wire

# Request types (reference message.h:50-121)
REQUEST_ALLREDUCE = 0
REQUEST_ALLGATHER = 1
REQUEST_BROADCAST = 2
REQUEST_JOIN = 3
REQUEST_ADASUM = 4
REQUEST_ALLTOALL = 5
REQUEST_REDUCESCATTER = 6
REQUEST_BARRIER = 7

# Response types echo the request type; ERROR signals a rejected
# submission (reference message.h ResponseType).
RESPONSE_ERROR = 8


def encode_request(rank: int, rtype: int, dtype: int, root: int,
                   dims: Sequence[int], name: str) -> bytes:
    lib = load()
    if lib is None:
        raise RuntimeError("native core unavailable")
    cap = 64 + 8 * len(dims) + len(name)
    out = (ctypes.c_uint8 * cap)()
    dims_arr = (ctypes.c_int64 * max(1, len(dims)))(*dims) if dims else None
    n = lib.hvd_wire_encode_request(
        rank, rtype, dtype, root, dims_arr, len(dims), name.encode(), out, cap
    )
    if n < 0:
        raise ValueError("encode failed")
    return bytes(out[:n])


def decode_request(buf: bytes):
    lib = load()
    if lib is None:
        raise RuntimeError("native core unavailable")
    arr = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf)
    rank = ctypes.c_int32()
    rtype = ctypes.c_int32()
    dtype = ctypes.c_int32()
    root = ctypes.c_int32()
    ndim = ctypes.c_int32()
    dims = (ctypes.c_int64 * 16)()
    name = ctypes.create_string_buffer(4096)
    consumed = lib.hvd_wire_decode_request(
        arr, len(buf), ctypes.byref(rank), ctypes.byref(rtype),
        ctypes.byref(dtype), ctypes.byref(root), dims, 16, ctypes.byref(ndim),
        name, len(name),
    )
    if consumed < 0:
        raise ValueError("decode failed")
    return {
        "rank": rank.value,
        "type": rtype.value,
        "dtype": dtype.value,
        "root": root.value,
        "dims": list(dims[: ndim.value]),
        "name": name.value.decode(),
        "consumed": consumed,
    }


def encode_response(rtype: int, names: Sequence[str], error: str = "",
                    sizes: Sequence[int] = ()) -> bytes:
    lib = load()
    if lib is None:
        raise RuntimeError("native core unavailable")
    names_b = "\n".join(names).encode()
    error_b = error.encode()
    # cap from BYTE lengths (multibyte text expands past char counts)
    cap = 64 + len(names_b) + len(error_b) + 8 * len(sizes)
    out = (ctypes.c_uint8 * cap)()
    sizes_arr = (
        (ctypes.c_int64 * max(1, len(sizes)))(*sizes) if sizes else None
    )
    n = lib.hvd_wire_encode_response(
        rtype, names_b, error_b, sizes_arr, len(sizes), out, cap,
    )
    if n < 0:
        raise ValueError("encode failed")
    return bytes(out[:n])


def decode_response(buf: bytes):
    lib = load()
    if lib is None:
        raise RuntimeError("native core unavailable")
    arr = (ctypes.c_uint8 * len(buf)).from_buffer_copy(buf)
    rtype = ctypes.c_int32()
    nsizes = ctypes.c_int32()
    # every size costs 8 wire bytes, so len(buf)//8 + 1 can hold them all
    sizes_cap = len(buf) // 8 + 1
    sizes = (ctypes.c_int64 * sizes_cap)()
    names = ctypes.create_string_buffer(max(8192, len(buf) + 1))
    err = ctypes.create_string_buffer(max(4096, len(buf) + 1))
    consumed = lib.hvd_wire_decode_response(
        arr, len(buf), ctypes.byref(rtype), names, len(names), err,
        len(err), sizes, sizes_cap, ctypes.byref(nsizes),
    )
    if consumed < 0:
        raise ValueError("decode failed")
    names_s = names.value.decode()
    return {
        "type": rtype.value,
        "names": names_s.split("\n") if names_s else [],
        "error": err.value.decode(),
        "sizes": list(sizes[: nsizes.value]),
        "consumed": consumed,
    }


if __name__ == "__main__":
    import sys

    if "--build" in sys.argv:
        lib = load(build=True)
        print("built:", _LIB_PATH if lib is not None else "FAILED")
        sys.exit(0 if lib is not None else 1)
    print("usage: python -m horovod_tpu.native --build")
