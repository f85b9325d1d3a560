"""JaxTrainer / DataParallelTrainer — distributed training on gangs.

Role-equivalent of python/ray/train/data_parallel_trainer.py ::
DataParallelTrainer + torch/torch_trainer.py :: TorchTrainer, re-designed
TPU-first (SURVEY §3.3, §7.1 P6):

  * workers are gang members — one jax process per TPU host, gang-scheduled
    via a placement group; on real slices they share one jax.distributed
    runtime so the training step is ONE jitted XLA program whose psum /
    all_gather collectives ride ICI.
  * the "ring" backend is the CPU test twin (SURVEY §4.4.4): per-process
    jax + eager host-memory allreduce through ray_tpu.util.collective.
  * failure recovery is slice-granular (SURVEY §5.3): any member death ⇒
    GangDiedError ⇒ restart the whole gang from the latest persisted
    checkpoint, up to FailureConfig.max_failures.

Elasticity (ISSUE 6): with ``min_workers`` set the trainer *resizes
instead of restarting*. A gang death re-forms at the surviving size with
full-jitter backoff; a periodic capacity probe grows the gang back toward
``num_workers`` at the next checkpoint boundary; and (opt-in) an
``oom_risk`` telemetry event on a gang node triggers a preemptive
checkpoint-and-replace before the memory-monitor kill fires. Every
transition goes checkpoint → re-form → restore — XLA meshes are static —
and dataset ingest resumes from the per-rank iterator states stamped into
the committed checkpoint, re-split across the new world size.
"""

from __future__ import annotations

import logging
import os
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ray_tpu.train._internal.backend_executor import (
    BackendExecutor,
    TrainingFailedError,
)
from ray_tpu.train._internal.storage import StorageContext
from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train.config import RunConfig, ScalingConfig
from ray_tpu.util.backoff import Backoff

logger = logging.getLogger(__name__)


@dataclass
class Result:
    """What fit() returns — mirrors ray.train.Result."""

    metrics: dict = field(default_factory=dict)
    checkpoint: Optional[Checkpoint] = None
    path: str = ""
    error: Optional[Exception] = None
    metrics_history: list = field(default_factory=list)
    # Every world-size transition the run made: dicts of
    # {"reason": "gang_died"|"grow"|"oom_risk_drain", "from": k, "to": j}.
    resizes: list = field(default_factory=list)
    # Goodput accounting (ISSUE 8): the run's wall clock classified into
    # productive / checkpoint / restart / stalled buckets (they sum to
    # wall_s by construction) plus goodput_fraction.
    goodput: dict = field(default_factory=dict)

    @property
    def best_checkpoints(self) -> list:
        return [self.checkpoint] if self.checkpoint else []


def _split_datasets(
    datasets: dict, num_workers: int, ingest: dict | None = None
) -> list[dict]:
    """Per-rank dataset shards. A ray_tpu.data.Dataset splits via
    streaming_split (locality-aware iterators); plain sequences shard by
    striding; anything else is replicated.

    ``ingest`` is the per-rank iterator state stamped into the committed
    checkpoint being resumed ({"world_size": W, "datasets": {name:
    [state, ...]}}); Datasets then resume mid-epoch with the remaining
    sample space re-split across ``num_workers`` (which may differ from
    W). Striding of plain sequences is positionless and replays the
    epoch from the start — only Datasets get resume-exact semantics.
    """
    shards: list[dict] = [dict() for _ in range(num_workers)]
    per_ds_states = (ingest or {}).get("datasets", {})
    for name, ds in (datasets or {}).items():
        if hasattr(ds, "streaming_split"):
            resume_from = None
            if name in per_ds_states:
                resume_from = {
                    "world_size": (ingest or {}).get("world_size", 0),
                    "per_rank": per_ds_states[name],
                }
            for rank, it in enumerate(
                ds.streaming_split(num_workers, resume_from=resume_from)
            ):
                shards[rank][name] = it
        elif isinstance(ds, (list, tuple)):
            for rank in range(num_workers):
                shards[rank][name] = ds[rank::num_workers]
        else:
            for rank in range(num_workers):
                shards[rank][name] = ds
    return shards


def _session_events_dir_known() -> str | None:
    """The cluster session dir, when discoverable from this process."""
    sd = os.environ.get("RAYTPU_SESSION_DIR")
    if sd:
        return sd
    try:
        import ray_tpu

        return ray_tpu.runtime_info().get("session_dir")
    except Exception:  # rtlint: disable=swallowed-exception - no cluster context: no session dir
        return None


class DataParallelTrainer:
    """N workers × train_loop_per_worker(config), lockstep report rounds."""

    _default_backend = "ring"

    def __init__(
        self,
        train_loop_per_worker: Callable[[dict], Any],
        *,
        train_loop_config: dict | None = None,
        scaling_config: ScalingConfig | None = None,
        run_config: RunConfig | None = None,
        datasets: dict | None = None,
        resume_from_checkpoint: Checkpoint | None = None,
        backend: str | None = None,
    ):
        self.train_loop_per_worker = train_loop_per_worker
        self.train_loop_config = dict(train_loop_config or {})
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self.datasets = datasets or {}
        self.resume_from_checkpoint = resume_from_checkpoint
        self.backend = backend or self._default_backend

    # -- hooks for Tune integration (tune wraps fit() in a trial actor) --
    def _experiment_name(self) -> str:
        return self.run_config.name or type(self).__name__.lower()

    def fit(self) -> Result:
        from ray_tpu.util import tracing

        # Lifecycle span: the root of the run's start-up tree.
        with tracing.span(
            "train.fit", lifecycle=True, experiment=self._experiment_name()
        ):
            return self._fit()

    def _fit(self) -> Result:
        from ray_tpu._private import usage

        usage.record_feature("train")
        run_cfg = self.run_config
        storage = StorageContext(
            run_cfg.resolved_storage_path(),
            self._experiment_name(),
            checkpoint_config=run_cfg.checkpoint_config,
        )
        latest_ckpt = self.resume_from_checkpoint or storage.latest_checkpoint()
        failures = 0
        last_metrics: dict = {}
        history: list[dict] = []
        resizes: list[dict] = []
        error: Exception | None = None
        # Full-jitter restart backoff (shared Backoff helper): a node crash
        # that killed the gang often killed neighbours too — every trainer
        # re-forming on an identical schedule stampedes the controller.
        backoff = Backoff(initial_backoff_s=0.1, max_backoff_s=5.0)
        # oom_risk events are a monotone log; remember how many we have
        # already acted on so one event triggers one drain.
        oom_seen = 0
        # Workload flight recorder (ISSUE 8): per-round StepStats ingest +
        # goodput wall-clock buckets for this run.
        from ray_tpu.train._internal.step_stats import FlightRecorder

        recorder = FlightRecorder(self._experiment_name())

        while True:
            executor = BackendExecutor(
                self.scaling_config,
                backend=self.backend,
                experiment_name=self._experiment_name(),
                trial_dir=storage.trial_dir,
            )
            if executor.selected_backend != self.backend:
                logger.info(
                    "collective backend %r auto-upgraded to %r "
                    "(>1 local device per worker; set "
                    "RAY_TPU_COLLECTIVE_AUTO_HIER=0 to keep the flat ring)",
                    self.backend, executor.selected_backend,
                )
            resize: dict | None = None
            try:
                ingest = storage.latest_ingest() if latest_ckpt else None
                form_t0 = time.monotonic()
                try:
                    executor.start(
                        self.train_loop_per_worker,
                        self.train_loop_config,
                        latest_ckpt,
                        # Split AFTER gang formation: an elastic restart may
                        # come up at a smaller world size, and a resume re-splits
                        # the remaining sample space at whatever size formed.
                        lambda world_size: _split_datasets(
                            self.datasets, world_size, ingest=ingest
                        ),
                        # Launch-attempt generation — fences the pipeline
                        # p2p wire's tag namespace per gang incarnation.
                        attempt=failures,
                    )
                finally:
                    # Gang (re)formation is restart-resharding time whether
                    # it succeeded or died mid-form.
                    recorder.note_restart(time.monotonic() - form_t0)
                recorder.note_progress()
                backoff.reset()
                done, last_metrics, error, resize, oom_seen = self._drive(
                    executor, storage, history, last_metrics, oom_seen,
                    recorder,
                )
                if done:
                    break
            except Exception as exc:
                from ray_tpu import exceptions as core_exc

                recoverable = isinstance(
                    exc,
                    (
                        core_exc.GangDiedError,
                        core_exc.ActorDiedError,
                        core_exc.WorkerCrashedError,
                        TrainingFailedError,
                    ),
                )
                if not recoverable:
                    raise
                error = exc
            finally:
                prev_size = (
                    executor.gang.num_workers if executor.gang else None
                )
                executor.shutdown()

            if resize is not None:
                # Voluntary transition at a checkpoint boundary (grow-back
                # or preemptive drain): not a failure, not counted against
                # max_failures, no backoff.
                resizes.append(resize)
                latest_ckpt = storage.latest_checkpoint()
                continue
            if error is not None:
                # Wall clock since the last committed round is lost work +
                # detection latency: the "stalled" goodput bucket.
                recorder.note_stalled_since_progress()
                max_failures = run_cfg.failure_config.max_failures
                if run_cfg.failure_config.fail_fast or (
                    0 <= max_failures <= failures
                ):
                    break
                failures += 1
                resizes.append(
                    {"reason": "gang_died", "from": prev_size, "to": None}
                )
                latest_ckpt = storage.latest_checkpoint()
                error = None
                sleep_t0 = time.monotonic()
                backoff.sleep()
                recorder.note_restart(time.monotonic() - sleep_t0)
                continue
            break

        return Result(
            metrics=last_metrics,
            checkpoint=storage.best_checkpoint(),
            path=storage.trial_dir,
            error=error,
            metrics_history=history,
            resizes=resizes,
            goodput=recorder.finalize(),
        )

    # -- elasticity probes (evaluated at checkpoint boundaries) ----------
    def _want_grow(self, executor: BackendExecutor, state: dict) -> bool:
        """Capacity probe: can the gang grow back toward num_workers?

        Throttled to elastic_grow_probe_period_s; a positive answer is
        best-effort (the re-formed gang steps down again if the capacity
        evaporated) but only fires when the cluster-wide free resources
        cover every missing bundle.
        """
        sc = self.scaling_config
        if not sc.elastic or sc.elastic_grow_probe_period_s <= 0:
            return False
        current = executor.gang.num_workers if executor.gang else 0
        missing = sc.total_workers - current
        if missing <= 0:
            return False
        now = time.monotonic()
        if now - state.get("last_probe", 0.0) < sc.elastic_grow_probe_period_s:
            return False
        state["last_probe"] = now
        try:
            import ray_tpu

            avail = ray_tpu.available_resources()
        except Exception:  # rtlint: disable=swallowed-exception - resource probe failed: skip this grow attempt
            return False
        need = self.scaling_config.worker_resources()
        return all(
            avail.get(res, 0.0) >= amt * missing for res, amt in need.items()
        )

    def _oom_flagged_ranks(
        self, executor: BackendExecutor, oom_seen: int
    ) -> tuple[list[int], int]:
        """New oom_risk telemetry events matched against gang nodes.

        Returns (flagged ranks, new high-water event count).
        """
        if not self.scaling_config.drain_on_oom_risk:
            return [], oom_seen
        session_dir = _session_events_dir_known()
        if not session_dir:
            return [], oom_seen
        try:
            from ray_tpu._private.event_export import read_events

            events = read_events(session_dir, "oom_risk")
        except Exception:
            return [], oom_seen
        fresh = events[oom_seen:]
        if not fresh:
            return [], oom_seen
        try:
            infos = executor.gang.rank_infos()
        except Exception:
            return [], len(events)
        node_to_rank = {info["node_id"]: info["rank"] for info in infos}
        flagged = sorted(
            {
                node_to_rank[ev["data"]["node_id"]]
                for ev in fresh
                if ev.get("data", {}).get("node_id") in node_to_rank
            }
        )
        return flagged, len(events)

    def _drive(
        self,
        executor: BackendExecutor,
        storage: StorageContext,
        history: list,
        last_metrics: dict,
        oom_seen: int = 0,
        recorder=None,
    ) -> tuple[bool, dict, Exception | None, dict | None, int]:
        """Poll rounds until every rank is done, an error surfaces, a stop
        criterion is met, or a checkpoint boundary triggers a voluntary
        resize. Returns (done, last_metrics, error, resize, oom_seen)."""
        from ray_tpu.util import tracing

        stop = self.run_config.stop or {}
        probe_state: dict = {}
        # Lifecycle span: what the driver waits for while the workers set
        # up, from sessions started to the first round that returns.
        first_round = tracing.begin("train.first_round")
        while True:
            round_results = executor.poll_round()
            if first_round is not None:
                tracing.finish(first_round)
                first_round = None
            errors = [r for r in round_results if "error" in r]
            if errors:
                err = errors[0]["error"]
                err.worker_traceback = errors[0].get("traceback", "")  # type: ignore
                return True, last_metrics, err, None, oom_seen
            if all(r.get("done") for r in round_results):
                return True, last_metrics, None, None, oom_seen
            reports = [r for r in round_results if "metrics" in r]
            if not reports:
                continue
            metrics = dict(reports[0]["metrics"])
            # Flight recorder (ISSUE 8): fold every rank's StepStats into
            # the rolling gang view; surface throughput + stragglers in
            # the user-visible metrics stream.
            if recorder is not None:
                step_summary = recorder.on_round(round_results)
                if step_summary:
                    metrics.setdefault(
                        "tokens_per_s", step_summary["tokens_per_s"]
                    )
                    if step_summary.get("mfu") is not None:
                        metrics.setdefault("mfu", step_summary["mfu"])
                    if recorder.stragglers:
                        ranks = [s["rank"] for s in recorder.stragglers]
                        metrics["stragglers"] = ranks
                        if probe_state.get("stragglers_logged") != ranks:
                            probe_state["stragglers_logged"] = ranks
                            logger.warning(
                                "straggling ranks detected: %s",
                                recorder.stragglers,
                            )
            # Surface which collective backend the gang actually runs
            # (acceptance: the hier auto-upgrade must be observable from
            # Result.metrics without user code changes).
            metrics.setdefault(
                "collective_backend", executor.selected_backend
            )
            # Stamp the (dp, fsdp, tp, pp) factorization this run chose
            # (ISSUE 10). Worker loops that know better (e.g. a mesh
            # built over all local devices) report their own value and
            # win the setdefault.
            metrics.setdefault(
                "factorization", self.scaling_config.factorization()
            )
            ckpt = executor.merge_sharded_checkpoints(
                [r.get("checkpoint") for r in round_results]
            )
            committed = False
            if ckpt is not None:
                world = executor.gang.num_workers
                ingest_states = [r.get("ingest") for r in round_results]
                ingest = None
                if any(ingest_states):
                    names = {
                        n for s in ingest_states if s for n in s
                    }
                    ingest = {
                        "world_size": world,
                        "datasets": {
                            name: [
                                (s or {}).get(name) for s in ingest_states
                            ]
                            for name in names
                        },
                    }
                persist_t0 = time.monotonic()
                try:
                    persisted = storage.persist(ckpt, metrics, ingest=ingest)
                except IOError as exc:
                    # Torn sharded save (a writer's marker or inventory is
                    # missing): skip the commit, keep training — recovery
                    # falls back to the previous committed checkpoint.
                    logger.warning("skipping uncommittable checkpoint: %s", exc)
                else:
                    metrics["checkpoint_path"] = persisted.path
                    committed = True
                finally:
                    if recorder is not None:
                        # Driver-side commit time is the checkpoint goodput
                        # bucket (spent either way, committed or torn).
                        recorder.note_checkpoint(
                            time.monotonic() - persist_t0
                        )
            last_metrics = metrics
            history.append(metrics)
            for cb in self.run_config.callbacks:
                handler = getattr(cb, "on_result", None)
                if handler:
                    handler(metrics)
            if any(
                key in metrics and metrics[key] >= bound
                for key, bound in stop.items()
            ):
                return True, last_metrics, None, None, oom_seen
            if committed:
                # Checkpoint boundary: the only safe place for voluntary
                # transitions (nothing since the commit is lost).
                flagged, oom_seen = self._oom_flagged_ranks(
                    executor, oom_seen
                )
                cur = executor.gang.num_workers
                if flagged:
                    logger.warning(
                        "oom_risk flagged gang ranks %s; preemptive "
                        "checkpoint-and-replace", flagged,
                    )
                    return False, last_metrics, None, {
                        "reason": "oom_risk_drain",
                        "from": cur,
                        "to": None,
                        "ranks": flagged,
                    }, oom_seen
                if self._want_grow(executor, probe_state):
                    return False, last_metrics, None, {
                        "reason": "grow",
                        "from": cur,
                        "to": self.scaling_config.total_workers,
                    }, oom_seen


class JaxTrainer(DataParallelTrainer):
    """The flagship trainer. Same driver loop as DataParallelTrainer; the
    jax-specific machinery (mesh construction, param sharding, in-jit
    collectives, sharded checkpoints) lives in ray_tpu.train.jax_utils and
    runs inside train_loop_per_worker.

    backend="xla" (default on real slices) assumes gang members joined one
    jax.distributed runtime — collectives happen inside jit on ICI.
    backend="ring" (tests / CPU) gives eager host-memory collectives.

    ``topology=`` (a parallel.topology.SliceTopology) declares a
    multi-slice layout — cross-slice DCN axes composed with in-slice ICI
    axes; it reaches the workers via the train context
    (get_context().slice_topology → jax_utils.build_mesh(topology=...)).
    Implies the xla backend: the gang shares one jax.distributed runtime
    whose processes span the slices.
    """

    _default_backend = "ring"

    def __init__(self, *args, topology=None, **kwargs):
        super().__init__(*args, **kwargs)
        if topology is not None:
            self.scaling_config.slice_topology = topology
        if (
            self.scaling_config.use_tpu
            or self.scaling_config.slice_topology is not None
        ) and kwargs.get("backend") is None:
            self.backend = "xla"
