"""Driver/worker global runtime state and the implementation of the
top-level API (init/shutdown/get/put/wait/kill/...).

Role-equivalent of python/ray/_private/worker.py in the reference
(:: init, connect, get, put, wait, Worker global state, log listeners).
"""

from __future__ import annotations

import atexit
import json
import os
import sys
import threading
import time
from typing import Any, Sequence

from ray_tpu import exceptions
from ray_tpu._private import serialization
from ray_tpu._private.config import global_config, reset_config
from ray_tpu._private.core_context import CoreContext
from ray_tpu._private.ids import JobID
from ray_tpu._private.node import LocalCluster
from ray_tpu._private.object_ref import ObjectRef

_global_ctx: CoreContext | None = None
_local_cluster: LocalCluster | None = None
_autoscaler_monitor = None  # AutoscalerMonitor when init(autoscaling=...)
_is_driver = False
_lock = threading.RLock()
_runtime_context_extras: dict = {}
_boot_recorded = False  # driver.boot: once a process, at its first init


def set_global_context(ctx: CoreContext, is_driver: bool) -> None:
    global _global_ctx, _is_driver
    _global_ctx = ctx
    _is_driver = is_driver


def get_global_context() -> CoreContext:
    if _global_ctx is None:
        raise RuntimeError(
            "ray_tpu has not been initialized; call ray_tpu.init() first"
        )
    return _global_ctx


def is_initialized() -> bool:
    return _global_ctx is not None


def process_start_ns() -> int:
    """When the OS started this process, on ``time.time_ns()``'s clock:
    where a boot span (``driver.boot``, ``worker.boot``) starts. The
    process's age is read where the kernel keeps it, both ends on the
    clock that counts from boot: now, less the start in clock ticks of
    ``/proc/self/stat``; good to a tick (10 ms). ``psutil``'s
    ``create_time()`` adds the same ticks to ``/proc/stat``'s ``btime``,
    which is in WHOLE seconds: it reads early by a constant of the
    machine's boot, anything under a second (0.4 s on the builder's
    host), and importing psutil is 30 ms of every worker's start. It is
    the fallback where there is no ``/proc``."""
    try:
        with open("/proc/self/stat", "rb") as fh:
            ticks = int(fh.read().rsplit(b")", 1)[1].split()[19])
        age_ns = time.clock_gettime_ns(time.CLOCK_BOOTTIME) - (
            ticks * 1_000_000_000 // os.sysconf("SC_CLK_TCK")
        )
        return time.time_ns() - age_ns
    except (OSError, AttributeError, ValueError, IndexError):
        import psutil

        return int(psutil.Process().create_time() * 1e9)


def init(
    address: str | None = None,
    *,
    num_cpus: int | None = None,
    resources: dict | None = None,
    object_store_memory: int | None = None,
    log_to_driver: bool = True,
    namespace: str = "default",
    runtime_env: dict | None = None,
    autoscaling: "str | dict | None" = None,
    _system_config: dict | None = None,
    ignore_reinit_error: bool = False,
) -> dict:
    """Start (or connect to) a cluster and connect this process as driver.

    Like the reference's ray.init(): no address starts a local head
    (controller + node agent subprocesses + shm store); ``address`` of the
    form "host:port" (controller) connects to an existing cluster.
    Resources are *assertions* (resource lying is supported for tests, see
    SURVEY §4.4.3): pass ``resources={"TPU": 8}`` on a laptop and the
    scheduler will believe you.
    """
    global _local_cluster, _boot_recorded
    from ray_tpu.util import tracing as _tracing

    # Lifecycle span (docs/observability.md): recorded at the end, by when
    # the session directory is known. Ambient only around start_head: what
    # connect() starts on the io loop lives on and must not inherit it.
    init_span = _tracing.begin("ray_tpu.init")
    with _lock:
        if _global_ctx is not None:
            if ignore_reinit_error:
                return runtime_info()
            raise RuntimeError("ray_tpu.init() called twice")
        global_config().apply_system_config(_system_config)

        job_id = JobID.random()
        if address == "auto":
            # Reference's ray.init("auto"): resolve from the environment
            # (set for job-submission drivers and `ray_tpu start` shells).
            address = os.environ.get("RAYTPU_ADDRESS")
            if not address:
                raise ConnectionError(
                    'init("auto") needs RAYTPU_ADDRESS in the environment'
                )
        if address is None:
            custom = dict(resources or {})
            if num_cpus is not None:
                custom["CPU"] = num_cpus
            cluster = LocalCluster()
            # Before start_head, whose two subprocess starts are spans.
            _tracing.configure(cluster.session_dir)
            ambient = _tracing.set_current(init_span)
            try:
                cluster.start_head(
                    resources=custom,
                    store_capacity=object_store_memory or 0,
                )
            finally:
                _tracing.reset_current(ambient)
            _local_cluster = cluster
            # Driver-side tracing/profile exports land in the session dir
            # (workers inherit it via RAYTPU_SESSION_DIR at spawn).
            os.environ["RAYTPU_SESSION_DIR"] = cluster.session_dir
            controller_addr = cluster.controller_addr
            agent_addr = cluster.head_agent_addr
            store_info = cluster.head_store_info
            node_id = cluster.head_node_id
        else:
            host, port = address.rsplit(":", 1)
            controller_addr = (host, int(port))
            agent_addr, store_info, node_id = _discover_local_node(controller_addr)

        ctx = CoreContext(
            job_id=job_id,
            node_id=node_id,
            controller_addr=controller_addr,
            agent_addr=agent_addr,
            store_info=store_info,
            is_driver=True,
        )
        connect_start_ns = time.time_ns()
        ctx.connect()
        _tracing.emit(
            "init.connect", _tracing.context_of(init_span),
            start_ns=connect_start_ns, lifecycle=True,
        )
        if not _boot_recorded:
            # What came before this process's first init: the interpreter,
            # the imports, the user's own code up to here. A root span.
            _boot_recorded = True
            _tracing.emit(
                "driver.boot", start_ns=process_start_ns(),
                end_ns=init_span.start_ns, lifecycle=True,
            )
        set_global_context(ctx, is_driver=True)
        _runtime_context_extras["namespace"] = namespace
        _runtime_context_extras["runtime_env"] = runtime_env or {}
        if log_to_driver:
            _subscribe_logs(ctx, job_id)
        atexit.register(shutdown)
        if autoscaling is not None:
            # Bootstrap-launched monitor (autoscaler/_private/monitor.py
            # role): the cluster autoscales with NO user-side autoscaler
            # construction. "v2"/"v1" or a dict of monitor kwargs. A bad
            # config must not leak the just-started cluster processes.
            global _autoscaler_monitor
            from ray_tpu.autoscaler.monitor import start_monitor_from_config

            try:
                _autoscaler_monitor = start_monitor_from_config(
                    autoscaling, local_cluster=_local_cluster
                )
            except Exception:
                shutdown()  # RLock: safe to re-enter from init's lock
                raise
        _tracing.finish(init_span)
        return runtime_info()


def _discover_local_node(controller_addr: tuple) -> tuple:
    """Connect-to-existing: pick an agent (prefer one on this host)."""
    from ray_tpu._private.rpc import RpcClient

    probe = CoreContextProbe(controller_addr)
    nodes = probe.call("list_nodes", {})
    probe.close()
    alive = [n for n in nodes if n["alive"]]
    if not alive:
        raise RuntimeError("no alive nodes in cluster")
    node = alive[0]
    return tuple(node["agent_addr"]), node["store_info"], node["node_id"]


class CoreContextProbe:
    """Minimal one-shot RPC helper usable before the main context exists."""

    def __init__(self, addr: tuple):
        from ray_tpu._private.rpc import IoThread, RpcClient

        self.io = IoThread("probe-io")
        self.client = RpcClient(tuple(addr), name="probe")
        self.io.run(self.client.connect())

    def call(self, method: str, payload: Any, timeout: float | None = 30) -> Any:
        return self.io.run(self.client.call(method, payload), timeout)

    def close(self) -> None:
        try:
            self.io.run(self.client.close())
        except Exception:  # rtlint: disable=swallowed-exception - close of a dead controller conn at shutdown
            pass
        self.io.stop()


def _subscribe_logs(ctx: CoreContext, job_id: str) -> None:
    """Print worker stdout/stderr with (pid=) prefixes, like the reference's
    log monitor → driver pipeline."""

    def on_log(message):
        if message.get("job_id") not in ("", job_id):
            return
        stream = sys.stderr if message.get("kind") == "err" else sys.stdout
        print(f"(pid={message.get('pid')}) {message.get('line')}", file=stream)

    ctx.controller.on_push("logs", on_log)
    ctx.io.run(ctx.subscribe_channels(["logs", "error"]))


def shutdown() -> None:
    global _global_ctx, _local_cluster, _autoscaler_monitor
    with _lock:
        if _autoscaler_monitor is not None:
            try:
                _autoscaler_monitor.stop()
            except Exception:  # rtlint: disable=swallowed-exception - monitor already stopped
                pass
            _autoscaler_monitor = None
        if _global_ctx is not None:
            try:
                # Compiled DAGs hold resident worker loops and ring slots
                # — tear them down while the RPC plane is still up.
                from ray_tpu.dag import dag as dag_mod

                dag_mod.shutdown_all()
            except Exception:  # rtlint: disable=swallowed-exception - shutdown must not be blocked by a wedged graph
                pass
            _global_ctx.shutdown()
            _global_ctx = None
        if _local_cluster is not None:
            _local_cluster.shutdown()
            _local_cluster = None


def runtime_info() -> dict:
    ctx = get_global_context()
    return {
        "job_id": ctx.job_id,
        "node_id": ctx.node_id,
        "controller_address": f"{ctx.controller_addr[0]}:{ctx.controller_addr[1]}",
        "session_dir": (
            _local_cluster.session_dir if _local_cluster is not None else None
        ),
    }


# ---------------------------------------------------------------------------
# public API implementations
# ---------------------------------------------------------------------------
def put(value: Any) -> ObjectRef:
    return get_global_context().put(value)


def get(refs, timeout: float | None = None):
    return get_global_context().get(refs, timeout=timeout)


def wait(
    refs: Sequence[ObjectRef],
    *,
    num_returns: int = 1,
    timeout: float | None = None,
    fetch_local: bool = True,
):
    return get_global_context().wait(
        refs, num_returns=num_returns, timeout=timeout, fetch_local=fetch_local
    )


def kill(actor, *, no_restart: bool = True) -> None:
    ctx = get_global_context()
    ctx.io.run(
        ctx.controller.call(
            "kill_actor",
            {"actor_id": actor._actor_id, "no_restart": no_restart},
        )
    )


def cancel(ref: ObjectRef, *, force: bool = False) -> None:
    """Cancel the task that creates ``ref`` (reference: ray.cancel /
    test_cancel.py semantics). Queued tasks fail with TaskCancelledError;
    running tasks get KeyboardInterrupt (force=False) or their worker
    SIGKILLed (force=True -> WorkerCrashedError); finished tasks no-op."""
    get_global_context().cancel(ref, force=force)


def nodes() -> list[dict]:
    ctx = get_global_context()
    return ctx.io.run(ctx.controller.call("list_nodes", {}))


def cluster_resources() -> dict:
    ctx = get_global_context()
    return ctx.io.run(ctx.controller.call("cluster_resources", {}))


def available_resources() -> dict:
    ctx = get_global_context()
    return ctx.io.run(ctx.controller.call("available_resources", {}))


def timeline(filename: str | None = None) -> dict:
    """Chrome-trace JSON (Trace Event Format) for the whole session —
    spans, task events, and counter snapshots merged onto per-process
    tracks; loads directly in Perfetto / chrome://tracing."""
    from ray_tpu.util.timeline import build_chrome_trace

    ctx = get_global_context()
    events = ctx.io.run(
        ctx.controller.call("list_task_events", {"limit": 100_000})
    )
    session_dir = (
        _local_cluster.session_dir
        if _local_cluster is not None
        else os.environ.get("RAYTPU_SESSION_DIR", "")
    )
    trace = build_chrome_trace(session_dir, task_events=events)
    if filename:
        from ray_tpu._private.atomic_io import atomic_write_json

        atomic_write_json(filename, trace)
    return trace
