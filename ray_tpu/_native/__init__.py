"""Build + load the native runtime library (libraytpu.so).

The C++ sources live in ``src/`` at the repo root. We compile them on first
import — the environment guarantees g++ — and rebuild whenever the sources'
content hash differs from the one recorded beside the library
(``<lib>.srchash``). The libraries themselves are not tracked by git: a
fresh checkout or a copy to another machine builds from what ``src/`` says.
This keeps the native components buildable without a packaging step, like
the reference's bazel-built core but without requiring bazel at runtime.
"""

from __future__ import annotations

import ctypes
import fcntl
import hashlib
import os
import subprocess
import threading

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
_SRC_DIRS = [
    os.path.join(_REPO, "src", "object_store"),
    os.path.join(_REPO, "src", "rpc"),
]
_LIB_PATH = os.path.join(_HERE, "libraytpu.so")

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None


def _sources() -> list[str]:
    out: list[str] = []
    for d in _SRC_DIRS:
        if os.path.isdir(d):
            out.extend(
                os.path.join(d, f) for f in sorted(os.listdir(d)) if f.endswith(".cc")
            )
    return out


def _build_key(flags: list[str], sources: list[str]) -> str:
    """Hash of everything the library is made from: the compiler flags and
    each source's name and bytes. Recorded beside the library, it is what
    decides a rebuild — mtimes say nothing after a checkout or a copy."""
    h = hashlib.sha256("\0".join(flags).encode())
    for src in sources:
        h.update(os.path.basename(src).encode() + b"\0")
        # rtlint: disable=blocking-in-async - one-time lazy toolchain build, memoized per process in load(); cold-start only, never on the steady-state loop
        with open(src, "rb") as f:
            h.update(f.read())
    return h.hexdigest()


def _recorded_key(lib_path: str) -> str | None:
    if not os.path.exists(lib_path):
        return None
    try:
        # rtlint: disable=blocking-in-async - one-time lazy toolchain build, memoized per process in load(); cold-start only, never on the steady-state loop
        with open(lib_path + ".srchash") as f:
            return f.read().strip()
    except OSError:
        return None


def _compile(lib_path: str, flags: list[str], sources: list[str], force: bool) -> str:
    key = _build_key(flags, sources)
    if not force and _recorded_key(lib_path) == key:
        return lib_path
    # Every process of a cluster (and every xdist worker) imports this at
    # once: one builds under the lock, the rest wait and find the hash.
    # rtlint: disable=blocking-in-async,non-atomic-write - one-time lazy toolchain build, memoized per process in load(); cold-start only, never on the steady-state loop; the lock file is empty, only flock()ed
    with open(lib_path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if force or _recorded_key(lib_path) != key:
            tmp = f"{lib_path}.tmp.{os.getpid()}"
            # rtlint: disable=blocking-in-async - one-time lazy toolchain build, memoized per process in load(); cold-start only, never on the steady-state loop
            subprocess.run(
                ["g++", *flags, "-o", tmp, *sources],
                check=True, capture_output=True, text=True,
            )
            os.replace(tmp, lib_path)
            # rtlint: disable=blocking-in-async - one-time lazy toolchain build, memoized per process in load(); cold-start only, never on the steady-state loop
            with open(f"{tmp}.srchash", "w") as f:
                f.write(key)
            os.replace(f"{tmp}.srchash", lib_path + ".srchash")
    return lib_path


def build(force: bool = False) -> str:
    sources = _sources()
    if not sources:
        raise RuntimeError(f"no native sources found under {_SRC_DIRS}")
    flags = ["-std=c++17", "-O2", "-fPIC", "-shared", "-pthread"]
    return _compile(_LIB_PATH, flags, sources, force)


def load() -> ctypes.CDLL:
    global _lib
    with _lock:
        if _lib is None:
            path = build()
            lib = ctypes.CDLL(path)
            lib.raytpu_store_start.restype = ctypes.c_void_p
            lib.raytpu_store_start.argtypes = [
                ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint64, ctypes.c_char_p,
            ]
            lib.raytpu_store_stop.argtypes = [ctypes.c_void_p]
            # --- rpc transport (src/rpc/transport.cc) ---
            lib.rt_engine_new.restype = ctypes.c_void_p
            lib.rt_engine_stop.argtypes = [ctypes.c_void_p]
            lib.rt_notify_fd.argtypes = [ctypes.c_void_p]
            lib.rt_notify_fd.restype = ctypes.c_int
            lib.rt_connect_tcp.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ]
            lib.rt_connect_tcp.restype = ctypes.c_long
            lib.rt_connect_unix.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.rt_connect_unix.restype = ctypes.c_long
            lib.rt_listen_tcp.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
                ctypes.POINTER(ctypes.c_int),
            ]
            lib.rt_listen_tcp.restype = ctypes.c_long
            lib.rt_listen_unix.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.rt_listen_unix.restype = ctypes.c_long
            lib.rt_next_msgid.argtypes = [ctypes.c_void_p, ctypes.c_long]
            lib.rt_next_msgid.restype = ctypes.c_uint32
            lib.rt_send.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_uint8,
                ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32,
            ]
            lib.rt_send.restype = ctypes.c_int
            lib.rt_close_conn.argtypes = [ctypes.c_void_p, ctypes.c_long]
            lib.rt_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            lib.rt_next.restype = ctypes.c_int
            lib.rt_msg_free.argtypes = [ctypes.c_void_p]
            lib.rt_conn_debug.argtypes = [
                ctypes.c_void_p, ctypes.c_long,
                ctypes.POINTER(ctypes.c_longlong),
            ]
            lib.rt_conn_debug.restype = ctypes.c_int
            # --- native call table + exec fast lane (hot path, N18-N20) ---
            lib.rt_call_start.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p,
                ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
            ]
            lib.rt_call_start.restype = ctypes.c_uint64
            lib.rt_call_start_buf.argtypes = lib.rt_call_start.argtypes
            lib.rt_call_start_buf.restype = ctypes.c_uint64
            lib.rt_send_buf.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_uint8,
                ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32,
            ]
            lib.rt_send_buf.restype = ctypes.c_int
            lib.rt_exec_pending.argtypes = [ctypes.c_void_p]
            lib.rt_exec_pending.restype = ctypes.c_int
            lib.rt_conn_inflight.argtypes = [ctypes.c_void_p, ctypes.c_long]
            lib.rt_conn_inflight.restype = ctypes.c_int
            lib.rt_call_wait.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
                ctypes.c_void_p,
            ]
            lib.rt_call_wait.restype = ctypes.c_int
            lib.rt_call_poll.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ]
            lib.rt_call_poll.restype = ctypes.c_int
            lib.rt_call_abandon.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.rt_exec_filter.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.rt_exec_next.argtypes = [
                ctypes.c_void_p, ctypes.c_int, ctypes.c_void_p,
            ]
            lib.rt_exec_next.restype = ctypes.c_int
            lib.rt_exec_inject.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
            lib.rt_list_conns.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
                ctypes.c_int,
            ]
            lib.rt_list_conns.restype = ctypes.c_int
            # --- object-transfer plane (push manager, N16) ---
            lib.rt_push_object.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p,
                ctypes.c_void_p, ctypes.c_uint64,
            ]
            lib.rt_push_object.restype = ctypes.c_int
            lib.rt_transfer_take.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_void_p),
                ctypes.POINTER(ctypes.c_uint64),
            ]
            lib.rt_transfer_take.restype = ctypes.c_int
            lib.rt_transfer_free.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            # --- native lease lane (raylet grant path, N9/N10) ---
            lib.rt_lease_enable.argtypes = [ctypes.c_void_p, ctypes.c_int]
            lib.rt_lease_adjust.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
                ctypes.POINTER(ctypes.c_double), ctypes.c_int, ctypes.c_int,
            ]
            lib.rt_lease_adjust.restype = ctypes.c_int
            lib.rt_lease_pool_put.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_char_p, ctypes.c_int,
            ]
            lib.rt_lease_pool_pop.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_char_p,
                ctypes.c_int,
            ]
            lib.rt_lease_pool_pop.restype = ctypes.c_int
            lib.rt_lease_pool_remove.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.rt_lease_pool_remove.restype = ctypes.c_int
            lib.rt_lease_worker_ban.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.rt_lease_worker_unban.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p,
            ]
            lib.rt_lease_forget.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
            lib.rt_lease_forget.restype = ctypes.c_int
            lib.rt_lease_next_event.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ]
            lib.rt_lease_next_event.restype = ctypes.c_int
            lib.rt_lease_available_json.argtypes = [
                ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
            ]
            lib.rt_lease_available_json.restype = ctypes.c_int
            lib.rt_lease_stats.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ]
            lib.rt_engine_stats.argtypes = [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_longlong),
            ]
            _lib = lib
    return _lib


_pylib: "ctypes.PyDLL | None" = None


def load_nogilrelease() -> ctypes.PyDLL:
    """The same library loaded via PyDLL: calls KEEP the GIL.

    For microsecond-scale non-blocking entry points (rt_send on a
    non-blocking fd, rt_next, rt_next_msgid, rt_msg_free) the GIL
    release+reacquire of a normal CDLL call costs more than the call
    itself under thread contention (~150 us measured on a 1-core host vs
    ~10 us of actual work). Never use this handle for anything that can
    block."""
    global _pylib
    with _lock:
        if _pylib is None:
            path = build()
            lib = ctypes.PyDLL(path)
            lib.rt_send.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_uint8,
                ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32,
            ]
            lib.rt_send.restype = ctypes.c_int
            lib.rt_next_msgid.argtypes = [ctypes.c_void_p, ctypes.c_long]
            lib.rt_next_msgid.restype = ctypes.c_uint32
            lib.rt_next.argtypes = [ctypes.c_void_p, ctypes.c_void_p]
            lib.rt_next.restype = ctypes.c_int
            lib.rt_msg_free.argtypes = [ctypes.c_void_p]
            # Non-blocking fast-lane entry points (safe to keep the GIL:
            # rt_call_start's inline send is on a non-blocking fd).
            lib.rt_call_start.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_char_p,
                ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
            ]
            lib.rt_call_start.restype = ctypes.c_uint64
            lib.rt_call_start_buf.argtypes = lib.rt_call_start.argtypes
            lib.rt_call_start_buf.restype = ctypes.c_uint64
            lib.rt_send_buf.argtypes = [
                ctypes.c_void_p, ctypes.c_long, ctypes.c_uint8,
                ctypes.c_uint32, ctypes.c_char_p, ctypes.c_uint32,
                ctypes.c_char_p, ctypes.c_uint32,
            ]
            lib.rt_send_buf.restype = ctypes.c_int
            lib.rt_exec_pending.argtypes = [ctypes.c_void_p]
            lib.rt_exec_pending.restype = ctypes.c_int
            lib.rt_conn_inflight.argtypes = [ctypes.c_void_p, ctypes.c_long]
            lib.rt_conn_inflight.restype = ctypes.c_int
            lib.rt_call_poll.argtypes = [
                ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
            ]
            lib.rt_call_poll.restype = ctypes.c_int
            lib.rt_call_abandon.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
            lib.rt_exec_inject.argtypes = [ctypes.c_void_p, ctypes.c_uint32]
            _pylib = lib
    return _pylib


# ---------------------------------------------------------------------------
# _fastlane — CPython extension for the per-task hot path (src/pyext/).
# Built separately from libraytpu.so (it needs Python headers); it attaches
# to the SAME engine library at runtime via dlopen, so the two stay one
# native runtime. Failure to build/load degrades to the ctypes path.
# ---------------------------------------------------------------------------
_FASTLANE_SRC = os.path.join(_REPO, "src", "pyext", "fastlane.cc")
_FASTLANE_PATH = os.path.join(_HERE, "_fastlane.so")
_fastlane_mod = None
_fastlane_failed = False


def build_fastlane(force: bool = False) -> str:
    import sysconfig

    flags = [
        "-std=c++17", "-O2", "-fPIC", "-shared",
        f"-I{sysconfig.get_paths()['include']}",
    ]
    return _compile(_FASTLANE_PATH, flags, [_FASTLANE_SRC], force)


def load_fastlane():
    """Import the _fastlane extension, attached to the engine lib.
    Returns the module, or None when disabled/unbuildable."""
    global _fastlane_mod, _fastlane_failed
    if _fastlane_mod is not None:
        return _fastlane_mod
    if _fastlane_failed or os.environ.get("RAY_TPU_fastlane") == "0":
        return None
    with _lock:
        if _fastlane_mod is not None:
            return _fastlane_mod
        try:
            import importlib.util

            lib_path = build()
            ext_path = build_fastlane()
            spec = importlib.util.spec_from_file_location(
                "ray_tpu._native._fastlane", ext_path
            )
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
            mod.attach(lib_path)
            _fastlane_mod = mod
        except Exception:
            _fastlane_failed = True
            return None
    return _fastlane_mod


class RtMsgView(ctypes.Structure):
    """Mirror of rt_msg_view in src/rpc/transport.cc."""

    _fields_ = [
        ("conn", ctypes.c_long),
        ("kind", ctypes.c_uint8),
        ("msgid", ctypes.c_uint32),
        ("method", ctypes.c_void_p),
        ("mlen", ctypes.c_uint32),
        ("payload", ctypes.c_void_p),
        ("plen", ctypes.c_uint32),
        ("opaque", ctypes.c_void_p),
    ]
