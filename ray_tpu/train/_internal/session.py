"""Per-worker train session.

Role-equivalent of python/ray/train/_internal/session.py :: _TrainSession —
the user's train loop runs on a background thread; `report(metrics,
checkpoint)` hands (metrics, checkpoint) to the trainer's polling loop and
blocks until consumed, which keeps every rank's loop in lockstep with the
driver the way the reference's session does.
"""

from __future__ import annotations

import queue
import threading
import time
import traceback
from dataclasses import dataclass, field
from typing import Any, Callable, Optional

from ray_tpu.train.checkpoint import Checkpoint
from ray_tpu.train._internal import step_stats as step_stats_mod
from ray_tpu.util import tracing


@dataclass
class TrainContext:
    """What `ray_tpu.train.get_context()` returns inside a worker."""

    world_size: int = 1
    world_rank: int = 0
    local_rank: int = 0
    node_id: str = ""
    experiment_name: str = ""
    trial_dir: str = ""
    train_loop_config: dict = field(default_factory=dict)
    latest_checkpoint: Optional[Checkpoint] = None
    dataset_shards: dict = field(default_factory=dict)
    mesh: Any = None
    # SliceTopology when the trainer runs multi-slice (DCN x ICI axes);
    # worker loops pass it to jax_utils.build_mesh(topology=...).
    slice_topology: Any = None
    collective_group: str = ""
    # MPMD pipeline assignment (ISSUE 10), set when
    # ScalingConfig.pipeline_stages > 1: {"stage": s, "num_stages": S,
    # "microbatches": M}. The stage runner
    # (train._internal.stage_runner.PipelineStageRunner) reads it; None
    # means no pipeline — the plain GSPMD path.
    pipeline: Any = None

    def get_world_size(self) -> int:
        return self.world_size

    def get_world_rank(self) -> int:
        return self.world_rank

    def get_local_rank(self) -> int:
        return self.local_rank

    def get_experiment_name(self) -> str:
        return self.experiment_name

    def get_trial_dir(self) -> str:
        return self.trial_dir


class _Session:
    def __init__(
        self,
        ctx: TrainContext,
        fn: Callable[[], Any],
        trace_parent: dict | None = None,
        leased_chips: float = 0,
    ):
        self.ctx = ctx
        # The driver's train.fit span: parent of this worker's train.loop.
        self._trace_parent = trace_parent
        # The TPU chips of this worker's lease: above 0 the session
        # reaches its devices itself, before fn (_reach_device).
        self._leased_chips = leased_chips
        # When fn started, until the first report has emitted
        # train.first_report from it; then None.
        self._loop_start_ns: int | None = None
        self._results: queue.Queue = queue.Queue(maxsize=1)
        self._consumed = threading.Event()
        self._consumed.set()
        self.error: Exception | None = None
        self.finished = threading.Event()
        # Workload flight recorder (ISSUE 8): one StepStats record per
        # report. Off → None, and the phase accumulator stays inactive.
        self._recorder = (
            step_stats_mod.StepRecorder(ctx)
            if step_stats_mod.enabled()
            else None
        )
        if self._recorder is not None:
            step_stats_mod.activate()
        self._thread = threading.Thread(
            target=self._run, args=(fn,), daemon=True
        )

    def start(self) -> None:
        self._thread.start()

    def _run(self, fn: Callable[[], Any]) -> None:
        step_stats_mod.begin_startup()
        try:
            # Lifecycle span, written when fn ends; what a reader of a
            # killed worker finds is train.first_report.
            with tracing.span(
                "train.loop", parent=self._trace_parent, lifecycle=True,
                rank=self.ctx.world_rank,
            ) as loop:
                self._loop_start_ns = loop.start_ns
                if self._leased_chips > 0:
                    _reach_device(self._leased_chips)
                fn()
        except Exception as exc:  # surfaced via next_result poll
            exc._traceback_str = traceback.format_exc()  # type: ignore[attr-defined]
            self.error = exc
        finally:
            self.finished.set()

    # -- called from the user thread ------------------------------------
    def report(
        self, metrics: dict, checkpoint: Checkpoint | None = None
    ) -> None:
        if self._loop_start_ns is not None:
            # The worker's own time to its first step, once.
            tracing.emit(
                "train.first_report", start_ns=self._loop_start_ns,
                lifecycle=True, rank=self.ctx.world_rank,
            )
            self._loop_start_ns = None
            step_stats_mod.end_startup()
        # Snapshot this rank's dataset-iterator positions alongside the
        # report: the driver stamps them into the committed checkpoint so a
        # restart (at any world size) resumes ingest exactly (ISSUE 6).
        ingest: dict[str, dict] = {}
        for name, shard in (self.ctx.dataset_shards or {}).items():
            if getattr(shard, "supports_state", False):
                try:
                    ingest[name] = shard.state_dict()
                except Exception:  # rtlint: disable=swallowed-exception - iterator snapshot is best-effort; resume falls back
                    pass
        # Cut the StepStats record BEFORE blocking on the driver: the
        # step interval must cover the user's work, not the driver's
        # poll latency (which would smear data/compute attribution).
        step_stats = (
            self._recorder.on_report(metrics)
            if self._recorder is not None
            else None
        )
        self._consumed.wait()
        self._consumed.clear()
        self._results.put(
            {
                "metrics": dict(metrics),
                "checkpoint": checkpoint,
                "ingest": ingest or None,
                "step_stats": step_stats,
            }
        )
        # Re-stamp the step clock AFTER the hand-off: the wait above is
        # the driver's rendezvous (every rank resumes on the same round
        # edge), and letting it bleed into the next record's wall makes
        # all ranks' walls equal the gang round period — hiding exactly
        # the per-rank dispersion the straggler detector keys on.
        if self._recorder is not None:
            self._recorder.mark_resume()

    # -- called from the actor (poll) -----------------------------------
    def next_result(self, timeout: float = 0.0) -> dict | None:
        """One reported result, or {'done': True}/{'error': ...} at the end."""
        try:
            item = self._results.get(timeout=timeout)
            self._consumed.set()
            return item
        except queue.Empty:
            pass
        if self.finished.is_set() and self._results.empty():
            if self.error is not None:
                return {
                    "error": self.error,
                    "traceback": getattr(self.error, "_traceback_str", ""),
                }
            return {"done": True}
        return None


def _reach_device(leased: float) -> None:
    """A worker whose lease holds chips reaches them here, on the loop's
    own thread before the user's function: ``import jax``, the compile
    watcher, the first ``jax.devices()`` (backend initialisation, on a TPU
    host the largest single part of a warm start: jax emits no monitoring
    event for it, so the program has to own the call). One lifecycle span,
    ``train.reach_device``; the user's first ``jax.devices()`` then finds
    a cached backend. A worker that was leased no chip never comes here:
    it must not import jax, let alone take a chip (accel.live_jax). What
    is found is an attribute, never an error: a lease may lie (tests lease
    fake chips on a CPU host), and a backend that fails to come up fails
    again, under its own name, where the user's function asks for it."""
    try:
        with tracing.span(
            "train.reach_device", lifecycle=True, leased=leased
        ) as reach:
            t0 = time.perf_counter()
            import jax

            reach.attributes["import_s"] = time.perf_counter() - t0
            from ray_tpu.train import jax_utils

            jax_utils._watch_compiles()
            devices = jax.devices()
            reach.attributes["platform"] = devices[0].platform
            reach.attributes["devices"] = len(devices)
    except Exception:  # rtlint: disable=swallowed-exception - recorded on the span (status error); the user's own first jax call raises it where it always did
        pass


_session: _Session | None = None


def init_session(
    ctx: TrainContext,
    fn: Callable[[], Any],
    trace_parent: dict | None = None,
    leased_chips: float = 0,
) -> _Session:
    global _session
    _session = _Session(ctx, fn, trace_parent, leased_chips)
    return _session


def get_session() -> _Session:
    if _session is None:
        raise RuntimeError(
            "ray_tpu.train.report()/get_context() called outside a train "
            "worker — they only work inside train_loop_per_worker."
        )
    return _session


def in_session() -> bool:
    return _session is not None


def shutdown_session() -> None:
    global _session
    step_stats_mod.deactivate()
    _session = None
