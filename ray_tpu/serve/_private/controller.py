"""ServeController — the singleton reconciliation actor.

Role-equivalent of python/ray/serve/_private/controller.py ::
ServeController + deployment_state.py :: DeploymentStateManager +
application_state.py (SURVEY §2.6, §3.4): holds target state (apps →
deployments), runs a reconcile loop that starts/stops replica actors to
match target counts, health-checks replicas, applies rolling updates on
version change, autoscales from replica queue metrics, and checkpoints
target state to the controller KV [N6] so a restarted controller replays
the reconcile.
"""

from __future__ import annotations

import hashlib
import logging
import os
import pickle
import threading
import time
import traceback
import uuid
from typing import Any, Optional

import ray_tpu
from ray_tpu.serve._private.autoscaling_policy import AutoscalingState
from ray_tpu.serve._private.common import (
    DeploymentConfig,
    DeploymentInfo,
    ReplicaInfo,
    new_replica_id,
)
from ray_tpu.serve._private.replica import Replica

RECONCILE_PERIOD_S = 0.25
# Proxy liveness + route-p99 + oom_risk scans ride a slower tick than the
# reconcile loop: each is an RPC or a file read, not a dict diff.
PROXY_CHECK_PERIOD_S = 1.0

logger = logging.getLogger(__name__)


def _inc_reliability(name: str, **tags) -> None:
    """Best-effort reliability counter bump (metric export must never take
    down the reconcile loop)."""
    try:
        from ray_tpu.util import metrics as metrics_mod

        metrics_mod.inc_serve_reliability(name, **tags)
    except Exception:  # rtlint: disable=swallowed-exception - metrics backend unavailable; reconcile continues
        pass


def _kv_call(method: str, payload: dict) -> Any:
    from ray_tpu._private import worker as worker_mod

    ctx = worker_mod.get_global_context()
    return ctx.io.run(ctx.controller.call(method, payload))


class ServeController:
    """Hosted in a detached named actor (max_concurrency > 1)."""

    def __init__(self):
        self._lock = threading.RLock()
        self._deployments: dict[str, DeploymentInfo] = {}  # qualified name →
        self._replicas: dict[str, list[ReplicaInfo]] = {}
        self._actor_handles: dict[str, Any] = {}
        self._autoscalers: dict[str, AutoscalingState] = {}
        self._autoscale_counts: dict[str, int] = {}
        self._routes: dict[str, str] = {}  # route_prefix → qualified name
        self._app_deployments: dict[str, list[str]] = {}
        self._app_status: dict[str, str] = {}
        self._applied_user_config: dict[str, Any] = {}
        self._stopped = False
        # Long-poll push state (reference: _private/long_poll.py host):
        # proxies/routers block in poll_update() until the membership
        # version advances instead of polling get_routes every second.
        self._config_version = 0
        self._config_cond = threading.Condition(self._lock)
        self._last_snapshot: dict | None = None
        self._pollers: set = set()  # (loop, asyncio.Event) of parked polls
        # Instance token: a restarted controller restarts versions at 0;
        # subscribers detect the epoch change and resync from scratch.
        self._instance = uuid.uuid4().hex
        # Keyed by qualified deployment name: a single controller-wide
        # timestamp would let the first deployment in iteration order
        # starve every other deployment's health checks.
        self._last_health_check: dict = {}
        # Ingress proxy registry (ISSUE 13): name → {"name", "protocol",
        # "host", "port"}. The reconcile loop health-checks each one and
        # restarts it under the same name/port on death; the set is
        # published in the membership snapshot so clients can fail over.
        self._proxies: dict[str, dict] = {}
        self._last_proxy_check = 0.0
        # Latest per-route p99 (ms) scraped from proxy SLO histograms,
        # fed into the autoscaler beside queue depth.
        self._route_p99: dict[str, float] = {}
        # oom_risk event high-water mark (the jax_trainer consumer
        # pattern): only events newer than this trigger drains.
        self._oom_seen = 0
        self._restore_checkpoint()
        self._thread = threading.Thread(target=self._reconcile_loop, daemon=True)
        self._thread.start()

    # ------------------------------------------------------------------
    # target-state API (called by serve.run / CLI)
    # ------------------------------------------------------------------
    def deploy_application(
        self, app_name: str, deployments: list[dict], route_prefix: Optional[str]
    ) -> str:
        with self._lock:
            new_names = []
            for spec in deployments:
                info = DeploymentInfo(
                    name=spec["name"],
                    app_name=app_name,
                    config=spec["config"],
                    cls_or_fn=spec["cls_or_fn"],
                    init_args=spec.get("init_args", ()),
                    init_kwargs=spec.get("init_kwargs", {}),
                    version=spec.get("version") or self._version_of(spec),
                    route_prefix=spec.get("route_prefix"),
                )
                qname = info.qualified_name()
                new_names.append(qname)
                self._deployments[qname] = info
                self._replicas.setdefault(qname, [])
                if info.config.autoscaling_config:
                    self._autoscalers[qname] = AutoscalingState(
                        info.config.autoscaling_config
                    )
                    self._autoscale_counts.setdefault(
                        qname, info.config.autoscaling_config.min_replicas
                    )
                # user_config change → in-place reconfigure of live replicas
                prev = self._applied_user_config.get(qname, object())
                if prev != info.config.user_config:
                    self._applied_user_config[qname] = info.config.user_config
                    for rep in self._replicas.get(qname, []):
                        actor = self._actor_handles.get(rep.actor_name)
                        if actor is not None and rep.state == "RUNNING":
                            try:
                                actor.reconfigure.remote(info.config.user_config)
                            except Exception:
                                # Replica death is handled by the health
                                # check; the new config lands on its
                                # replacement.
                                logger.debug(
                                    "reconfigure push to %s failed",
                                    rep.actor_name, exc_info=True,
                                )
            # Remove deployments dropped from the app.
            for qname in self._app_deployments.get(app_name, []):
                if qname not in new_names:
                    self._deployments.pop(qname, None)
                    self._last_health_check.pop(qname, None)
            self._app_deployments[app_name] = new_names
            self._app_status[app_name] = "DEPLOYING"
            if route_prefix is not None and deployments:
                ingress = deployments[-1]
                self._routes[route_prefix] = f"{app_name}_{ingress['name']}"
            self._bump_version_locked()
        self._save_checkpoint()
        return "ok"

    def delete_application(self, app_name: str) -> str:
        with self._lock:
            for qname in self._app_deployments.pop(app_name, []):
                self._deployments.pop(qname, None)
                self._last_health_check.pop(qname, None)
            self._routes = {
                r: d for r, d in self._routes.items()
                if not d.startswith(app_name + "_")
            }
            self._app_status.pop(app_name, None)
            self._bump_version_locked()
        self._save_checkpoint()
        return "ok"

    def shutdown(self) -> str:
        with self._lock:
            self._deployments.clear()
            self._routes.clear()
            self._app_deployments.clear()
            self._last_health_check.clear()
        # reconcile loop will drain replicas; mark stop after one pass
        time.sleep(2 * RECONCILE_PERIOD_S)
        self._stopped = True
        # Wake parked poll_update subscribers so they observe the stop now
        # instead of riding out their full long-poll timeout.
        self._notify_pollers()
        return "ok"

    # ------------------------------------------------------------------
    # introspection (routers, proxies, serve.status)
    # ------------------------------------------------------------------
    def get_deployment_replicas(self, qualified_name: str) -> dict:
        with self._lock:
            info = self._deployments.get(qualified_name)
            running = [
                r.actor_name
                for r in self._replicas.get(qualified_name, [])
                if r.state == "RUNNING"
            ]
            return {
                "actor_names": running,
                "max_ongoing_requests": (
                    info.config.max_ongoing_requests if info else 100
                ),
            }

    def get_routes(self) -> dict:
        with self._lock:
            return dict(self._routes)

    # ------------------------------------------------------------------
    # ingress proxy lifecycle (ISSUE 13)
    # ------------------------------------------------------------------
    def register_proxy(
        self, name: str, protocol: str, host: str, port: int
    ) -> str:
        """serve.start() reports each proxy it launched; from then on the
        controller owns its liveness (health-check + restart on death)."""
        with self._lock:
            self._proxies[name] = {
                "name": name, "protocol": protocol,
                "host": host, "port": int(port),
            }
            self._bump_version_locked()
        return "ok"

    def unregister_proxy(self, name: str) -> str:
        with self._lock:
            self._proxies.pop(name, None)
            self._bump_version_locked()
        return "ok"

    def get_proxies(self) -> list:
        with self._lock:
            return [dict(p) for p in self._proxies.values()]

    def _ensure_proxies(self) -> None:
        """Health-check every registered proxy; restart the dead ones under
        the same name/port so clients that pinned an address recover."""
        with self._lock:
            descriptors = [dict(p) for p in self._proxies.values()]
        for desc in descriptors:
            name = desc["name"]
            try:
                handle = ray_tpu.get_actor(name)
                ray_tpu.get(handle.get_num_requests.remote(), timeout=5)
                continue
            except Exception:  # rtlint: disable=swallowed-exception - dead/unreachable proxy detected; restart path follows
                pass
            logger.warning("proxy %s is down; restarting", name)
            try:
                if desc["protocol"] == "grpc":
                    from ray_tpu.serve._private.grpc_proxy import GRPCProxy

                    proxy_cls: Any = GRPCProxy
                else:
                    from ray_tpu.serve._private.proxy import HTTPProxy

                    proxy_cls = HTTPProxy
                ray_tpu.remote(proxy_cls).options(
                    name=name, lifetime="detached", max_concurrency=64
                ).remote(desc["host"], desc["port"])
                _inc_reliability("proxy_restarts", proxy=name)
            except Exception:
                # Name may still be registered while the old actor's death
                # propagates; the next tick retries.
                logger.warning("proxy %s restart failed", name, exc_info=True)

    def _scrape_route_p99(self) -> None:
        """Pull per-route p99 from each HTTP proxy's SLO histograms (ISSUE
        8) for the autoscaler; routes served by several proxies report the
        worst tail."""
        with self._lock:
            descriptors = [
                dict(p) for p in self._proxies.values()
                if p["protocol"] == "http"
            ]
        merged: dict[str, float] = {}
        for desc in descriptors:
            try:
                handle = ray_tpu.get_actor(desc["name"])
                stats = ray_tpu.get(handle.get_route_stats.remote(), timeout=5)
            except Exception:  # rtlint: disable=swallowed-exception - proxy down; _ensure_proxies handles it
                continue
            for route, snap in stats.items():
                p99 = snap.get("p99_ms")
                if p99 is not None:
                    merged[route] = max(merged.get(route, 0.0), p99)
        if merged:
            self._route_p99.update(merged)

    # ------------------------------------------------------------------
    # long-poll push (reference: long_poll.py LongPollHost)
    # ------------------------------------------------------------------
    def _bump_version_locked(self) -> None:
        self._config_version += 1
        self._last_snapshot = None  # recompute lazily at next poll
        self._notify_pollers()

    def _notify_pollers(self) -> None:
        """Wake every parked poll_update coroutine (they wait on per-call
        asyncio.Events; version bumps come from controller threads, so the
        wake crosses into each poller's loop threadsafely)."""
        for loop, event in list(self._pollers):
            try:
                loop.call_soon_threadsafe(event.set)
            except Exception:  # rtlint: disable=swallowed-exception - poller loop may be closed; next poll re-registers
                pass

    def _membership_snapshot(self) -> dict:
        with self._lock:
            replicas = {}
            for qname, info in self._deployments.items():
                running = sorted(
                    r.actor_name
                    for r in self._replicas.get(qname, [])
                    if r.state == "RUNNING"
                )
                replicas[qname] = {
                    "actor_names": running,
                    "max_ongoing_requests": info.config.max_ongoing_requests,
                    # Reliability policy (ISSUE 13): routers/proxies price
                    # deadlines, retries, and admission from deployment
                    # config instead of hardcoded constants.
                    "policy": info.config.policy_snapshot(),
                }
            return {
                "routes": dict(self._routes),
                "replicas": replicas,
                "proxies": [dict(p) for p in self._proxies.values()],
            }

    def _publish_if_changed(self) -> None:
        """End of each reconcile pass: if membership changed (replica went
        RUNNING/DEAD, routes changed), advance the version and wake every
        blocked poll_update."""
        snapshot = self._membership_snapshot()
        with self._config_cond:
            if snapshot != self._last_snapshot:
                self._config_version += 1
                self._last_snapshot = snapshot
                self._notify_pollers()

    async def poll_update(
        self, last_version: int = -1, timeout_s: float = 10.0
    ) -> dict:
        """Block until the membership version advances past last_version
        (or timeout); returns the fresh snapshot. Proxies and routers call
        this in a loop — push semantics over an actor call. async so each
        blocked subscriber is a coroutine on the actor's async lane, NOT a
        pinned concurrency slot (N subscribers would otherwise starve the
        control plane)."""
        import asyncio

        loop = asyncio.get_running_loop()
        event = asyncio.Event()
        entry = (loop, event)
        with self._lock:
            ready = self._config_version > last_version or self._stopped
            if not ready:
                self._pollers.add(entry)
        if not ready:
            try:
                await asyncio.wait_for(event.wait(), timeout_s)
            except asyncio.TimeoutError:
                pass
            finally:
                with self._lock:
                    self._pollers.discard(entry)
        # The membership as it is NOW, not as the last reconcile pass left
        # it: a replica turns RUNNING on its own thread (_await_ready) and
        # get_status says so at once, while a pass can be seconds away
        # (it health-checks every replica first).
        self._publish_if_changed()
        with self._config_cond:
            return {
                "version": self._config_version,
                "instance": self._instance,
                **self._last_snapshot,
            }

    def get_status(self) -> dict:
        with self._lock:
            apps = {}
            for app, qnames in self._app_deployments.items():
                deployments = {}
                for qname in qnames:
                    reps = self._replicas.get(qname, [])
                    info = self._deployments.get(qname)
                    target = self._target_count(qname, info) if info else 0
                    running = sum(1 for r in reps if r.state == "RUNNING")
                    deployments[qname.split("_", 1)[1]] = {
                        "target_replicas": target,
                        "running_replicas": running,
                        "states": [r.state for r in reps],
                    }
                all_ok = all(
                    d["running_replicas"] >= d["target_replicas"]
                    for d in deployments.values()
                )
                apps[app] = {
                    "status": "RUNNING" if all_ok else self._app_status.get(app, "DEPLOYING"),
                    "deployments": deployments,
                }
            return apps

    def get_metrics(self) -> dict:
        out = {}
        with self._lock:
            replicas = {
                q: [r for r in reps if r.state == "RUNNING"]
                for q, reps in self._replicas.items()
            }
        for qname, reps in replicas.items():
            metrics = []
            for rep in reps:
                try:
                    handle = self._actor_handles.get(rep.actor_name)
                    if handle:
                        metrics.append(
                            ray_tpu.get(handle.get_metrics.remote(), timeout=5)
                        )
                except Exception:  # rtlint: disable=swallowed-exception - metrics fetch from a dying replica; skip it
                    pass
            out[qname] = metrics
        return out

    def ping(self) -> str:
        return "ok"

    # ------------------------------------------------------------------
    # reconcile loop
    # ------------------------------------------------------------------
    def _reconcile_loop(self) -> None:
        while not self._stopped:
            try:
                self._reconcile_once()
            except Exception:
                traceback.print_exc()
            time.sleep(RECONCILE_PERIOD_S)

    def _target_count(self, qname: str, info: DeploymentInfo) -> int:
        if info.config.autoscaling_config:
            return self._autoscale_counts.get(
                qname, info.config.autoscaling_config.min_replicas
            )
        return info.config.num_replicas

    def _reconcile_once(self) -> None:
        with self._lock:
            targets = dict(self._deployments)
        # Slow tick: proxy liveness, route-p99 scrape, oom_risk scan (each
        # is an RPC or a file read — too heavy for every 0.25s pass).
        now = time.monotonic()
        if now - self._last_proxy_check >= PROXY_CHECK_PERIOD_S:
            self._last_proxy_check = now
            self._ensure_proxies()
            self._scrape_route_p99()
            self._drain_oom_flagged()
        # Drain replicas of deleted deployments.
        for qname in list(self._replicas):
            if qname not in targets:
                for rep in self._replicas.get(qname, []):
                    self._stop_replica(rep, trigger="app_delete")
                with self._lock:
                    self._replicas.pop(qname, None)
        for qname, info in targets.items():
            self._autoscale(qname, info)
            target = self._target_count(qname, info)
            replicas = self._replicas.setdefault(qname, [])
            # Rolling update: stop replicas of stale versions first.
            stale = [r for r in replicas if r.version != info.version]
            for rep in stale:
                self._stop_replica(
                    rep,
                    timeout_s=info.config.graceful_shutdown_timeout_s,
                    trigger="rolling_update",
                )
                replicas.remove(rep)
            alive = [r for r in replicas if r.state in ("STARTING", "RUNNING")]
            for _ in range(target - len(alive)):
                rep = self._start_replica(qname, info)
                if rep is not None:
                    replicas.append(rep)
            excess = len(alive) - target
            if excess > 0:
                # Scale-down prefers drains over kills: the replica leaves
                # the routing set first, finishes in-flight work, then dies.
                for rep in alive[-excess:]:
                    self._stop_replica(
                        rep,
                        timeout_s=info.config.graceful_shutdown_timeout_s,
                        trigger="scale_down",
                    )
                    replicas.remove(rep)
            self._health_check(qname, info, replicas)
        self._publish_if_changed()

    def _start_replica(self, qname: str, info: DeploymentInfo) -> ReplicaInfo | None:
        replica_id = new_replica_id(qname)
        actor_name = f"SERVE_REPLICA::{replica_id}"
        options = dict(
            name=actor_name,
            max_concurrency=max(8, info.config.max_ongoing_requests),
            num_cpus=info.config.ray_actor_options.get("num_cpus", 1),
        )
        if info.config.ray_actor_options.get("num_tpus"):
            options["num_tpus"] = info.config.ray_actor_options["num_tpus"]
        if info.config.ray_actor_options.get("resources"):
            options["resources"] = info.config.ray_actor_options["resources"]
        try:
            actor = ray_tpu.remote(Replica).options(**options).remote(
                replica_id,
                qname,
                info.cls_or_fn,
                info.init_args,
                info.init_kwargs,
                info.config.user_config,
                info.version,
                # Admission + drain knobs the replica enforces locally.
                limits=info.config.policy_snapshot(),
            )
        except Exception:
            traceback.print_exc()
            return None
        self._actor_handles[actor_name] = actor
        rep = ReplicaInfo(
            replica_id=replica_id,
            deployment=qname,
            actor_name=actor_name,
            state="STARTING",
            version=info.version,
        )
        # Async readiness probe: mark RUNNING when first health check lands.
        threading.Thread(
            target=self._await_ready, args=(rep, actor), daemon=True
        ).start()
        return rep

    def _await_ready(self, rep: ReplicaInfo, actor) -> None:
        try:
            ray_tpu.get(actor.check_health.remote(), timeout=120)
            try:
                rep.node_id = ray_tpu.get(
                    actor.get_node_id.remote(), timeout=10
                )
            except Exception:  # rtlint: disable=swallowed-exception - node id is only used for oom_risk targeting
                pass
            rep.state = "RUNNING"
        except Exception:
            rep.state = "DEAD"

    def _stop_replica(
        self,
        rep: ReplicaInfo,
        timeout_s: float = 20.0,
        trigger: str = "scale_down",
    ) -> None:
        """Drain-before-kill (ISSUE 13): flip the replica to DRAINING (the
        membership publish pulls it from every router), let in-flight
        requests finish up to the graceful timeout, then kill. The replica
        checkpoints its multiplexed models inside drain()."""
        rep.state = "DRAINING"
        actor = self._actor_handles.pop(rep.actor_name, None)
        if actor is None:
            rep.state = "DEAD"
            return
        _inc_reliability("drains", deployment=rep.deployment, trigger=trigger)

        def _drain():
            try:
                ray_tpu.get(actor.drain.remote(), timeout=10)
            except Exception:  # rtlint: disable=swallowed-exception - replica hung entering drain; the kill below still lands
                pass
            deadline = time.monotonic() + timeout_s
            while time.monotonic() < deadline:
                try:
                    ongoing = ray_tpu.get(
                        actor.get_num_ongoing.remote(), timeout=5
                    )
                except Exception:  # rtlint: disable=swallowed-exception - replica died mid-drain; nothing left to wait for
                    break
                if ongoing <= 0:
                    break
                time.sleep(0.25)
            try:
                ray_tpu.kill(actor)
            except Exception:  # rtlint: disable=swallowed-exception - actor already dead
                pass
            rep.state = "DEAD"

        threading.Thread(target=_drain, daemon=True).start()

    def _drain_oom_flagged(self) -> None:
        """Proactive drain on oom_risk telemetry (ISSUE 5 → ISSUE 13): the
        node agent projects a worker past its memory limit and publishes an
        oom_risk event; replicas on that node drain (checkpointing loaded
        models) before the OOM killer takes them mid-request. The reconcile
        pass starts replacements as soon as the drain drops them from the
        alive set."""
        session_dir = os.environ.get("RAYTPU_SESSION_DIR")
        if not session_dir:
            try:
                session_dir = ray_tpu.runtime_info().get("session_dir")
            except Exception:  # rtlint: disable=swallowed-exception - no cluster context: no events to read
                return
        if not session_dir:
            return
        try:
            from ray_tpu._private.event_export import read_events

            events = read_events(session_dir, "oom_risk")
        except Exception:  # rtlint: disable=swallowed-exception - unreadable events dir; retry next tick
            return
        fresh = events[self._oom_seen:]
        if not fresh:
            return
        self._oom_seen = len(events)
        nodes = {
            ev.get("data", {}).get("node_id") for ev in fresh
        } - {None, ""}
        if not nodes:
            return
        with self._lock:
            deployments = dict(self._deployments)
        for qname, info in deployments.items():
            replicas = self._replicas.get(qname, [])
            flagged = [
                r for r in replicas
                if r.state == "RUNNING" and r.node_id in nodes
            ]
            for rep in flagged:
                logger.warning(
                    "draining replica %s: oom_risk on node %s",
                    rep.replica_id, rep.node_id,
                )
                # Stay in the replicas list as DRAINING: the alive count
                # drops, so the same pass starts a replacement elsewhere.
                self._stop_replica(
                    rep,
                    timeout_s=info.config.graceful_shutdown_timeout_s,
                    trigger="oom_risk",
                )

    def _health_check(self, qname, info, replicas: list[ReplicaInfo]) -> None:
        now = time.monotonic()
        last = self._last_health_check.get(qname, 0.0)
        if now - last < info.config.health_check_period_s:
            return
        self._last_health_check[qname] = now
        for rep in [r for r in replicas if r.state == "RUNNING"]:
            actor = self._actor_handles.get(rep.actor_name)
            if actor is None:
                rep.state = "DEAD"
                continue
            try:
                result = ray_tpu.get(
                    actor.check_health.remote(),
                    timeout=info.config.health_check_timeout_s,
                )
            except Exception:
                rep.state = "DEAD"
                self._actor_handles.pop(rep.actor_name, None)
                try:
                    ray_tpu.kill(actor)
                except Exception:  # rtlint: disable=swallowed-exception - kill of an already-dead replica
                    pass
                continue
            if result == "draining":
                # The replica started draining on its own (SIGTERM from
                # the platform): honor it — pull it from routing, let
                # in-flight work finish, and let reconcile start a
                # replacement. _stop_replica's drain() call is idempotent.
                self._stop_replica(
                    rep,
                    timeout_s=info.config.graceful_shutdown_timeout_s,
                    trigger="sigterm",
                )
        self._replicas[qname] = [r for r in replicas if r.state != "DEAD"]

    def _autoscale(self, qname: str, info: DeploymentInfo) -> None:
        state = self._autoscalers.get(qname)
        if state is None:
            return
        running = [
            r for r in self._replicas.get(qname, []) if r.state == "RUNNING"
        ]
        total_ongoing = 0.0
        queue_depth = 0.0
        kv_free_frac: float | None = None
        for rep in running:
            actor = self._actor_handles.get(rep.actor_name)
            if actor is None:
                continue
            try:
                load = ray_tpu.get(actor.get_load.remote(), timeout=5)
                total_ongoing += load.get("ongoing", 0)
                queue_depth += load.get("queue_depth", 0)
                # Decode replicas report paged-KV headroom (ISSUE 17);
                # the pool scales on its WORST replica — one full pool
                # stalls that replica's admission even if siblings idle.
                frac = load.get("kv_free_frac")
                if frac is not None:
                    kv_free_frac = (
                        frac if kv_free_frac is None
                        else min(kv_free_frac, frac)
                    )
            except Exception:  # rtlint: disable=swallowed-exception - queue-depth probe failed; autoscale on what we have
                pass
        current = self._autoscale_counts.get(
            qname, info.config.autoscaling_config.min_replicas
        )
        # SLO input (ISSUE 13): the proxies' per-route p99 (scraped on the
        # slow tick) turns tail-latency breaches into upscale pressure.
        decision = state.decide(
            total_ongoing,
            current,
            queue_depth=queue_depth,
            p99_ms=self._route_p99.get(qname),
            kv_free_frac=kv_free_frac,
        )
        if decision != current:
            self._autoscale_counts[qname] = decision

    # ------------------------------------------------------------------
    # checkpoint/recovery via controller KV [N6]
    # ------------------------------------------------------------------
    def _save_checkpoint(self) -> None:
        with self._lock:
            state = {
                "deployments": self._deployments,
                "routes": self._routes,
                "app_deployments": self._app_deployments,
            }
        try:
            _kv_call(
                "kv_put",
                {
                    "namespace": "serve",
                    "key": "controller_checkpoint",
                    "value": pickle.dumps(state),
                    "overwrite": True,
                },
            )
        except Exception:
            # A lost checkpoint only bites on controller restart — which is
            # exactly when nobody is watching. Make the gap visible now.
            logger.warning("controller checkpoint save failed", exc_info=True)

    def _restore_checkpoint(self) -> None:
        try:
            resp = _kv_call(
                "kv_get", {"namespace": "serve", "key": "controller_checkpoint"}
            )
            if resp.get("status") == "ok" and resp.get("value"):
                state = pickle.loads(resp["value"])
                self._deployments = state["deployments"]
                self._routes = state["routes"]
                self._app_deployments = state["app_deployments"]
                for qname, info in self._deployments.items():
                    self._replicas.setdefault(qname, [])
                    if info.config.autoscaling_config:
                        self._autoscalers[qname] = AutoscalingState(
                            info.config.autoscaling_config
                        )
        except Exception:
            logger.warning(
                "controller checkpoint restore failed; starting with empty "
                "target state", exc_info=True,
            )

    @staticmethod
    def _version_of(spec: dict) -> str:
        """Code/arg identity only — scaling num_replicas or changing
        user_config must NOT roll replicas (user_config reconfigures in
        place, reference deployment_state semantics)."""
        import cloudpickle

        try:
            blob = cloudpickle.dumps(
                (spec["name"], spec["cls_or_fn"], spec.get("init_args"),
                 spec.get("init_kwargs"))
            )
        except Exception:
            blob = repr(spec).encode()
        return hashlib.sha1(blob).hexdigest()[:8]
