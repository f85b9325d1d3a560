"""Finding this host's TPU chips, and who holds them, without JAX.

A chip belongs to one process at a time: the process that first
initialises the TPU backend opens the chip's device node and keeps it
until it exits; any other process that tries then fails or hangs. So the
driver, the controller and the node agent count chips from ``/dev`` and
never initialise JAX, and telemetry reads device state only in a process
that has already initialised a backend of its own accord.
"""

from __future__ import annotations

import os
import sys


def tpu_device_nodes() -> list[str]:
    """One device node per local chip, found without opening any.

    A TPU VM's kernel driver shows ``/dev/accel<N>`` (v4 and older); a
    host that hands its chips through VFIO (v5e, v6e) shows one numbered
    IOMMU group per chip, ``/dev/vfio/<N>``, beside the ``/dev/vfio/vfio``
    container node. The one-chip v5e host shows ``/dev/vfio/2`` and no
    ``/dev/accel*``."""
    try:
        accel = sorted(
            f"/dev/{name}"
            for name in os.listdir("/dev")
            if name.startswith("accel")
        )
    except OSError:
        accel = []
    if accel:
        return accel
    try:
        return sorted(
            f"/dev/vfio/{name}"
            for name in os.listdir("/dev/vfio")
            if name.isdigit()
        )
    except OSError:
        return []


def holds_tpu(pid: int | str = "self") -> bool:
    """Does process ``pid`` have a chip's device node open? Read from
    ``/proc/<pid>/fd``: it asks the OS, not JAX, so it can be put to any
    process of the cluster and takes nothing."""
    nodes = set(tpu_device_nodes())
    if not nodes:
        return False
    fd_dir = f"/proc/{pid}/fd"
    try:
        fds = os.listdir(fd_dir)
    except OSError:
        return False
    for fd in fds:
        try:
            if os.readlink(f"{fd_dir}/{fd}") in nodes:
                return True
        except OSError:
            continue
    return False


def place_compile_cache() -> str:
    """Where this process tree keeps jax's persistent compilation cache.

    Placed from outside: where ``JAX_COMPILATION_CACHE_DIR`` is set it is
    left alone. Where it is not, it is set (for this process and the
    children that inherit its environment) to ONE fixed path inside the
    checkout, ``<repo>/.jax_cache``. The path is part of the cache's key,
    so it is never a temp name, pid, session id or time. jax reads the
    variable when it is imported: call this before user code can import
    jax, and set no directory through ``jax.config``."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        repo = os.path.dirname(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        )
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(repo, ".jax_cache")
    return os.environ["JAX_COMPILATION_CACHE_DIR"]


def live_jax():
    """The ``jax`` module if THIS process has already initialised a
    backend, else None. ``jax`` being imported is not the test: import
    takes nothing, but the first ``jax.devices()`` / ``local_devices()``
    initialises the backend and, on a TPU host, takes the chip. Every
    backend lookup goes through ``jax.extend.backend.get_backend``, whose
    cache is empty until some code of this process has asked for one."""
    jax = sys.modules.get("jax")
    # Another thread (the train loop) may be half way through `import
    # jax` while this one runs a task's telemetry: a module that is still
    # initialising has no backend yet, and importing from it would fail.
    if jax is None or getattr(jax.__spec__, "_initializing", False):
        return None
    from jax.extend import backend

    if backend.get_backend.cache_info().currsize == 0:
        return None
    return jax


_annotation_cls = None


def trace_annotation_cls():
    """``jax.profiler.TraceAnnotation`` when the process already imported
    jax (never force a jax init for telemetry), else None. Cached after
    the first successful probe. The one guard for every host span the
    program writes into a live profile: ``step_stats.step_annotation``
    (train) and ``DataIterator`` (data, which may not import train)."""
    global _annotation_cls
    if _annotation_cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        try:
            _annotation_cls = jax.profiler.TraceAnnotation
        except Exception:  # rtlint: disable=swallowed-exception - ancient jax without profiler: annotations degrade to timers
            return None
    return _annotation_cls
