"""BackendExecutor — drives a WorkerGang through a training run.

Role-equivalent of python/ray/train/_internal/backend_executor.py ::
BackendExecutor + worker_group.py :: WorkerGroup, collapsed onto the core
WorkerGang primitive (gangs already do placement-group scheduling, collective
rendezvous, and correlated-failure semantics — SURVEY §7.0.2).

Lockstep protocol: every rank's session must produce one result before the
executor hands the round to the trainer (matching the reference, where
`ray.train.report` is a barrier across workers).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time
from typing import Any, Callable, Optional

from ray_tpu.train._internal.session import TrainContext, init_session
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import ScalingConfig
from ray_tpu.util import tracing
from ray_tpu.util.gang import WorkerGang


def _start_session_fn(
    gang_ctx,
    train_fn: Callable,
    train_loop_config: dict,
    experiment_name: str,
    trial_dir: str,
    latest_checkpoint: Optional[Checkpoint],
    dataset_shards_per_rank: list[dict],
    mesh_axes: dict,
    slice_topology=None,
    pipeline: dict | None = None,
    trace_parent: dict | None = None,
    leased_chips: float = 0,
) -> bool:
    if pipeline is not None:
        # MPMD stage assignment: gang rank r is stage r // gang_per_stage
        # (contiguous ranks form one stage's gang).
        num_stages = int(pipeline["num_stages"])
        per_stage = max(1, gang_ctx.world_size // num_stages)
        pipeline = {
            **pipeline,
            "stage": gang_ctx.rank // per_stage,
            "stage_rank": gang_ctx.rank % per_stage,
        }
    ctx = TrainContext(
        world_size=gang_ctx.world_size,
        world_rank=gang_ctx.rank,
        local_rank=0,
        node_id=gang_ctx.node_id,
        experiment_name=experiment_name,
        trial_dir=trial_dir,
        train_loop_config=dict(train_loop_config),
        latest_checkpoint=latest_checkpoint,
        dataset_shards=dataset_shards_per_rank[gang_ctx.rank],
        mesh=mesh_axes,
        slice_topology=slice_topology,
        collective_group=gang_ctx.group_name,
        pipeline=pipeline,
    )
    session = init_session(
        ctx, lambda: train_fn(dict(train_loop_config)), trace_parent,
        leased_chips,
    )
    gang_ctx.state["session"] = session
    session.start()
    return True


def _poll_fn(gang_ctx, poll_timeout: float) -> dict | None:
    return gang_ctx.state["session"].next_result(timeout=poll_timeout)


class TrainingFailedError(RuntimeError):
    pass


class BackendExecutor:
    def __init__(
        self,
        scaling_config: ScalingConfig,
        *,
        backend: str = "ring",
        experiment_name: str,
        trial_dir: str,
    ):
        self.scaling_config = scaling_config
        self.backend = backend
        self.selected_backend = self._resolve_backend(backend)
        self.experiment_name = experiment_name
        self.trial_dir = trial_dir
        self.gang: WorkerGang | None = None

    def _resolve_backend(self, backend: str) -> str:
        """Topology-aware default (ISSUE 7): a ring-backend gang whose
        workers each own >1 local device upgrades to the hierarchical
        group — tier-1 in-jit psum over the local devices, tier-2 DCN
        ring of per-host partials — so only one partial per host rides
        the slow tier. Plain host-level allreduce on the hierarchical
        group delegates to its inner ring, so existing user code is
        unchanged. RAY_TPU_COLLECTIVE_AUTO_HIER=0 is the kill switch."""
        if backend != "ring":
            return backend
        if os.environ.get("RAY_TPU_COLLECTIVE_AUTO_HIER", "1") == "0":
            return backend
        if self._worker_local_devices() > 1:
            return "hier"
        return backend

    def _worker_local_devices(self) -> int:
        """Local device count a gang WORKER will see, learnt without jax:
        the driver never initialises a backend (on a TPU host that would
        take the chip its worker needs). On the CPU twin it is the
        host-platform flag of the worker's environment (``worker_env``,
        else the environment workers inherit from this process); on a TPU
        host it is the ``TPU`` resource each worker is leased."""
        import re

        for flags in (
            dict(self.scaling_config.worker_env).get("XLA_FLAGS", ""),
            os.environ.get("XLA_FLAGS", ""),
        ):
            m = re.search(r"xla_force_host_platform_device_count=(\d+)", flags)
            if m:
                return int(m.group(1))
        return max(1, int(self.scaling_config.worker_resources().get("TPU", 1)))

    def start(
        self,
        train_fn: Callable,
        train_loop_config: dict,
        latest_checkpoint: Optional[Checkpoint],
        dataset_shards_per_rank: list[dict] | Callable[[int], list[dict]],
        attempt: int = 0,
    ) -> None:
        sc = self.scaling_config
        # Lifecycle spans, children of the caller's train.fit, whose
        # context also rides to the workers as train.loop's parent.
        fit_ctx = tracing.inject(lifecycle=True)
        with tracing.span(
            "train.form_gang", lifecycle=True, attempt=attempt
        ) as formed:
            self.gang = self._form_gang()
            formed.attributes["world_size"] = self.gang.num_workers
        if callable(dataset_shards_per_rank):
            # Elastic path: shards depend on the world size actually formed.
            with tracing.span("train.split_datasets", lifecycle=True):
                dataset_shards_per_rank = dataset_shards_per_rank(
                    self.gang.num_workers
                )
        with tracing.span("train.start_sessions", lifecycle=True):
            self.gang.run(
                _start_session_fn,
                train_fn=train_fn,
                train_loop_config=train_loop_config,
                experiment_name=self.experiment_name,
                trial_dir=self.trial_dir,
                latest_checkpoint=latest_checkpoint,
                dataset_shards_per_rank=dataset_shards_per_rank,
                mesh_axes=dict(sc.mesh_axes),
                # Above 0 the session reaches its devices itself, under
                # the lifecycle span train.reach_device.
                leased_chips=sc.worker_resources().get("TPU", 0),
                slice_topology=sc.slice_topology,
                pipeline=(
                    {
                        "num_stages": int(sc.pipeline_stages),
                        "microbatches": int(sc.microbatches),
                        "virtual": int(getattr(sc, "virtual_stages", 1)),
                        # Launch-attempt generation: the stage runner fences
                        # its p2p wire tags per attempt, so a re-formed gang
                        # never consumes a dead incarnation's frames.
                        "attempt": int(attempt),
                    }
                    if int(getattr(sc, "pipeline_stages", 1)) > 1
                    else None
                ),
                trace_parent=fit_ctx,
            )

    def _form_gang(self) -> WorkerGang:
        """Form the gang at the target size, stepping down to min_workers.

        Bounded elasticity (SURVEY §2.4 Train v2, §5.3): each size gets one
        formation attempt with a bounded placement timeout; a cluster that
        lost capacity re-forms at the largest world size it can still gang-
        schedule. Fixed-size configs keep the old behavior (one attempt,
        long timeout, hard failure).
        """
        from ray_tpu import exceptions

        sc = self.scaling_config
        # Multi-slice: the gang shares one jax.distributed runtime so the
        # training step is one XLA program over every slice's devices.
        coordinator = "auto" if sc.slice_topology is not None else None
        env_vars = dict(sc.worker_env) or None
        if not sc.elastic:
            return WorkerGang(
                sc.total_workers,
                resources_per_worker=sc.worker_resources(),
                backend=self.selected_backend,
                placement_strategy=sc.placement_strategy,
                coordinator=coordinator,
                env_vars=env_vars,
                collective_config=sc.collective_config,
            )
        last_exc: Exception | None = None
        for size in range(sc.total_workers, sc.min_workers - 1, -1):
            try:
                gang = WorkerGang(
                    size,
                    resources_per_worker=sc.worker_resources(),
                    backend=self.selected_backend,
                    placement_strategy=sc.placement_strategy,
                    ready_timeout=sc.elastic_formation_timeout_s,
                    coordinator=coordinator,
                    env_vars=env_vars,
                    collective_config=sc.collective_config,
                )
                if size < sc.total_workers:
                    print(
                        f"[train] elastic step-down: formed gang at "
                        f"world_size={size} (target {sc.total_workers})"
                    )
                return gang
            except (
                exceptions.PlacementGroupUnschedulableError,
                exceptions.GangDiedError,
            ) as exc:
                last_exc = exc
        raise TrainingFailedError(
            f"could not form a gang at any size in "
            f"[{sc.min_workers}, {sc.total_workers}]: {last_exc}"
        )

    def poll_round(self, timeout: float = 600.0) -> list[dict]:
        """Block until every rank produced one result (or finished/errored).

        Returns the per-rank result dicts. Raises GangDiedError if a member
        process dies (the trainer turns that into restart-from-checkpoint).
        """
        assert self.gang is not None
        import ray_tpu
        from ray_tpu import exceptions

        deadline = time.monotonic() + timeout
        results: dict[int, dict] = {}
        pending = set(range(self.gang.num_workers))
        while pending:
            if time.monotonic() > deadline:
                raise TrainingFailedError(
                    f"train workers stalled: only {len(results)}/"
                    f"{self.gang.num_workers} ranks reported within {timeout}s"
                )
            # Poll ONLY ranks still missing a result this round — polling a
            # rank that already reported would consume (and drop) its next
            # report, breaking the cross-rank lockstep.
            refs = {
                rank: self.gang.members[rank].run.remote(
                    _poll_fn, (), {"poll_timeout": 1.0}
                )
                for rank in sorted(pending)
            }
            for rank, ref in refs.items():
                # Per-rank get is bounded by BOTH the local liveness cap and
                # the caller's remaining round deadline — a 600s poll_round
                # must not block 120s per rank past its own budget.
                remaining = deadline - time.monotonic()
                per_get = max(1.0, min(120.0, remaining))
                try:
                    res = ray_tpu.get(ref, timeout=per_get)
                except exceptions.GetTimeoutError as exc:
                    missing = sorted(pending)
                    raise TrainingFailedError(
                        f"train workers stalled: ranks {missing} did not "
                        f"report within the {timeout}s round deadline"
                    ) from exc
                except (
                    exceptions.ActorDiedError,
                    exceptions.ActorUnavailableError,
                    exceptions.WorkerCrashedError,
                ) as exc:
                    raise exceptions.GangDiedError(
                        f"gang member rank={rank} died during training: {exc}"
                    ) from exc
                if res is not None:
                    results[rank] = res
                    pending.discard(rank)
        return [results[r] for r in range(self.gang.num_workers)]

    def merge_sharded_checkpoints(self, reported: list[Optional[Checkpoint]]) -> Optional[Checkpoint]:
        """Rank 0's checkpoint dir is canonical; other ranks' `shards/p*`
        subdirs and `DONE.p<rank>` commit markers (written by
        checkpoint.save_pytree(process_index=rank)) are merged in so a
        multi-host sharded save arrives whole.

        The merged manifest's `world_size` is rewritten to the number of
        commit markers actually present: a replicated save (only rank 0
        reports a checkpoint) verifies as a one-writer checkpoint, while a
        sharded save that lost a writer's marker fails inventory
        verification at persist time and the round is skipped — fail
        closed, never commit a partial save.
        """
        from ray_tpu.train import checkpoint as ckpt_mod

        base = reported[0]
        if base is None:
            return None
        for ckpt in reported[1:]:
            if ckpt is None or ckpt.path == base.path:
                continue
            src_shards = os.path.join(ckpt.path, "shards")
            if os.path.isdir(src_shards):
                for proc_dir in os.listdir(src_shards):
                    dst = os.path.join(base.path, "shards", proc_dir)
                    if not os.path.isdir(dst):
                        shutil.copytree(
                            os.path.join(src_shards, proc_dir), dst
                        )
            for name in os.listdir(ckpt.path):
                if name.startswith("DONE.p"):
                    dst = os.path.join(base.path, name)
                    if not os.path.exists(dst):
                        shutil.copy2(os.path.join(ckpt.path, name), dst)
            # Rank temp dir is merged — reclaim /tmp (multi-GB models would
            # otherwise leak a checkpoint per report round per rank).
            if ckpt.path.startswith(tempfile.gettempdir()):
                shutil.rmtree(ckpt.path, ignore_errors=True)
        manifest_path = os.path.join(base.path, "manifest.json")
        if os.path.exists(manifest_path):
            import json

            try:
                with open(manifest_path) as f:
                    manifest = json.load(f)
            except (OSError, ValueError):
                manifest = None
            if manifest is not None:
                markers = ckpt_mod._done_markers(base.path)
                manifest["world_size"] = max(1, len(markers))
                ckpt_mod._atomic_write_json(manifest_path, manifest)
        return base

    def shutdown(self) -> None:
        if self.gang is not None:
            self.gang.shutdown()
            self.gang = None
