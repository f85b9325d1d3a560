"""StepStats recording — worker and driver halves of the flight recorder.

Worker half (runs inside each train worker process):
  * a per-process phase accumulator — the collective layer and the
    checkpoint writers call :func:`record_phase` with measured wall time;
    ``activate()``/``deactivate()`` gate it so a non-train worker pays a
    single bool check.
  * :class:`StepRecorder` — the session calls ``on_report()`` once per
    ``train.report()``; it cuts one StepStats record covering the
    interval since the previous report: wall time, data-wait (delta of
    the dataset iterators' fetch-wait clocks), collective + checkpoint
    time (drained from the accumulator), compute as the remainder, plus
    tokens/FLOPs when the user's metrics carry them (keys ``tokens`` and
    ``flops``, per rank per step), and ``compiles`` / ``compile_s`` when
    a program was compiled or loaded in the interval (the compile watcher
    below).

Driver half:
  * :class:`FlightRecorder` — one per ``fit()``. Ingests every rank's
    records each poll round into the
    :class:`~ray_tpu._private.workload.StepStatsAggregator`, pushes
    batched samples to the controller workload store (ONE throttled RPC,
    never per-record), and owns the goodput wall-clock buckets
    (checkpoint / restart / stalled; productive is the remainder, so the
    buckets always sum to wall).
"""

from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Any

from ray_tpu._private import accel
from ray_tpu._private import profiler as profiler_mod
from ray_tpu.util import tracing

logger = logging.getLogger(__name__)

_PUSH_INTERVAL_S = 1.0
_MAX_PENDING = 4096  # per-series driver-side buffer bound


def enabled() -> bool:
    try:
        from ray_tpu._private.config import global_config

        return bool(global_config().workload_stats_enabled)
    except Exception:  # rtlint: disable=swallowed-exception - config unreachable outside a cluster: default on
        return True


# -- worker-side phase accumulator --------------------------------------
_phase_lock = threading.Lock()
_phase_acc: dict[str, float] = {}
_active = False


def activate() -> None:
    global _active
    with _phase_lock:
        _phase_acc.clear()
    _active = True


def deactivate() -> None:
    global _active
    _active = False
    with _phase_lock:
        _phase_acc.clear()


def record_phase(phase: str, seconds: float) -> None:
    """Attribute ``seconds`` of the current step to ``phase``. Hot-path
    safe: outside an active train session this is one bool check."""
    if not _active:
        return
    if seconds <= 0:
        return
    with _phase_lock:
        _phase_acc[phase] = _phase_acc.get(phase, 0.0) + float(seconds)
    # Profile capture (ISSUE 20): phase totals during the capture window
    # feed the hot-phase attribution. One module-bool check when idle.
    profiler_mod.note_phase(phase, seconds)


@contextlib.contextmanager
def step_annotation(name: str, phase: str | None = None):
    """Named sub-step scope (ISSUE 20): times the block, opens a
    ``jax.profiler.TraceAnnotation`` so the device trace carries the same
    name, attributes the wall time to a StepStats ``phase`` (fwd/bwd/opt)
    when asked, and — only while a capture is live — buffers the slice
    for the merged Perfetto trace. Idle cost is one timer read pair plus
    a no-op TraceAnnotation."""
    cls = accel.trace_annotation_cls()
    ann = cls(name) if cls is not None else None
    wall0 = time.time()
    t0 = time.perf_counter()
    if ann is not None:
        ann.__enter__()
    try:
        yield
    finally:
        if ann is not None:
            ann.__exit__(None, None, None)
        dt = time.perf_counter() - t0
        if phase is not None:
            record_phase(phase, dt)
        profiler_mod.note_annotation(name, wall0, dt)


# -- worker-side compile watcher ----------------------------------------
# The jax.monitoring listeners that call these are registered where the
# session reaches its leased chips or the train loop first reaches jax
# (train/jax_utils.py::_watch_compiles): this module never imports jax.
# jax times three stages of a program on the thread that builds it, each
# with one event: the function to a jaxpr, the jaxpr to an MLIR module
# (Mosaic kernels' bodies included), and compile_or_get_cached (compile,
# or load from the persistent cache). Every inner jit and every lowering
# rule that traces fires the first two again, nested: thousands of events
# a program. So the thread keeps ONE depth over the three events together.
# A trace or a lowering that ends at depth 0 is a span (``jax.trace``,
# ``jax.lower``), one that ends deeper a count in the outermost span's
# ``inner``; a compile-or-load is a ``jax.compile`` span at ANY depth (an
# eager operation inside a traced function compiles there) and one count
# in the StepStats record of its interval: "which step recompiled". The
# spans' edges are jax's own ``time.time()`` readings around the stage,
# not this module's clock at the callback; their parent is the thread's
# current span.
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
STAGES = {TRACE: "jax.trace", LOWER: "jax.lower", COMPILE: "jax.compile"}
_compile_lock = threading.Lock()
_compile_acc = [0, 0.0]            # compiles, seconds since the last drain


class _Stages(threading.local):
    """One thread's place in jax's compile pipeline."""

    depth = 0               # stages entered and not left
    inner = 0               # stages entered under the outermost one
    cache_hit = False       # set by a compile's hit event, read at its end
    retrieval_s = 0.0       # and the seconds its cache read took

    def __init__(self):
        # (thread_time, process_time, inner) at the entry of every stage
        # that will be a span: the outermost one and any compile.
        self.entered = []


_stages = _Stages()
# From the start of a session's loop to its first train.report the compile
# spans are lifecycle spans (part of the time to the first step); after it
# they are per-step spans, gated by tracing.enabled() like any other.
_startup = False


def begin_startup() -> None:
    global _startup
    _startup = True


def end_startup() -> None:
    global _startup
    _startup = False


def note_cache_hit() -> None:
    """The persistent cache served the compile this thread is inside."""
    _stages.cache_hit = True


def note_cache_read(seconds: float) -> None:
    """... and reading and deserialising its entry took this long."""
    _stages.retrieval_s = seconds


def note_stage_entered(event: str) -> None:
    """This thread entered a trace, a lowering or a compile-or-load. A
    nested one costs two attribute stores; the CPU clocks are read only for
    a stage that will be a span."""
    here = _stages
    if here.depth:
        here.inner += 1
    else:
        here.inner = 0
    if not here.depth or event == COMPILE:
        here.entered.append((time.thread_time(), time.process_time(), here.inner))
    here.depth += 1


def note_stage_left(
    event: str, start: float, end: float, fun_name: str | None = None
) -> None:
    """... and left it, by return or by exception, ``start`` and ``end``
    being jax's own epoch seconds around it. ``cpu_s`` is what this thread
    held a CPU for meanwhile and ``proc_cpu_s`` what all threads of the
    process did: ``cpu_s`` well under ``seconds`` says the thread waited
    (the GIL, the disk), ``proc_cpu_s`` well over ``cpu_s`` that whoever it
    waited for is in this process. A compile that no cache served is a
    miss, whether or not one is set up: the program was built."""
    here = _stages
    here.depth = max(0, here.depth - 1)
    compiled = event == COMPILE
    if here.depth and not compiled:
        return
    seconds = end - start
    attributes: dict[str, Any] = {"seconds": seconds}
    if fun_name:
        attributes["fun_name"] = fun_name
    if here.entered:      # not so for a stage the watcher was registered inside
        thread_s, process_s, inner = here.entered.pop()
        attributes.update(
            inner=here.inner - inner,
            cpu_s=time.thread_time() - thread_s,
            proc_cpu_s=time.process_time() - process_s,
        )
    if compiled:
        attributes["cache"] = "hit" if here.cache_hit else "miss"
        if here.cache_hit:
            attributes["retrieval_s"] = here.retrieval_s
        here.cache_hit, here.retrieval_s = False, 0.0
        with _compile_lock:
            _compile_acc[0] += 1
            _compile_acc[1] += seconds
    tracing.emit(
        STAGES[event], start_ns=int(start * 1e9), end_ns=int(end * 1e9),
        lifecycle=_startup, thread=threading.get_native_id(), **attributes,
    )


def _drain_compiles() -> tuple[int, float]:
    with _compile_lock:
        out = (_compile_acc[0], _compile_acc[1])
        _compile_acc[:] = [0, 0.0]
    return out


def _drain_phases() -> dict[str, float]:
    with _phase_lock:
        out = dict(_phase_acc)
        _phase_acc.clear()
    return out


def _device_info() -> tuple[str, int]:
    """(device_kind, local device count) — read from jax only when the
    train loop has already initialised a backend itself (telemetry never
    takes the chip: accel.live_jax)."""
    jax = accel.live_jax()
    if jax is None:
        return "", 1
    try:
        devices = jax.local_devices()
        return devices[0].device_kind, len(devices)
    except Exception:
        return "", 1


class StepRecorder:
    """Cuts one StepStats record per ``train.report()`` on a worker."""

    def __init__(self, ctx: Any):
        self.ctx = ctx
        self.step = -1
        self._last = time.perf_counter()
        self._last_wait = 0.0
        self._device_kind: str | None = None
        self._devices = 1
        # The capture plane learns this worker's identity here so a
        # controller-armed profile can align on the step stream.
        profiler_mod.get_plane().set_meta(
            rank=ctx.world_rank, node_id=ctx.node_id
        )

    def _data_wait_total(self) -> float:
        """Both clocks of every dataset iterator: blocked on its producer
        (``fetch_wait_s``) and its own slicing and formatting on the
        loop's thread (``local_work_s``; a prefetching producer never
        blocks, and the loop still waits for its batch)."""
        total = 0.0
        for shard in (self.ctx.dataset_shards or {}).values():
            for clock in ("fetch_wait_s", "local_work_s"):
                wait = getattr(shard, clock, None)
                if isinstance(wait, (int, float)):
                    total += float(wait)
        return total

    def on_report(self, metrics: dict) -> dict:
        now = time.perf_counter()
        wall = max(0.0, now - self._last)
        self._last = now
        wait_total = self._data_wait_total()
        data_wait = min(wall, max(0.0, wait_total - self._last_wait))
        self._last_wait = wait_total
        phases = _drain_phases()
        collective = min(wall, phases.get("collective", 0.0))
        checkpoint = min(wall, phases.get("checkpoint", 0.0))
        # Pipeline-stage recv waits (stage_runner): schedule bubble, not
        # compute — subtracted from the remainder like the other phases.
        pp_bubble = min(wall, phases.get("pp_bubble", 0.0))
        # Overlapped gradient sync (ISSUE 11): collective keeps the TOTAL
        # op time (the work still happened, on background threads), but
        # only the fence-blocked slice stole wall clock from the step —
        # so when the overlap path ran, the compute remainder subtracts
        # the exposed time instead of the total.
        comm_exposed = min(wall, phases.get("comm_exposed", 0.0))
        comm_blocking = comm_exposed if "comm_exposed" in phases else collective
        compute = max(
            0.0, wall - data_wait - comm_blocking - checkpoint - pp_bubble
        )
        # Sub-step attribution (ISSUE 20): step_annotation() scopes split
        # the compute remainder into fwd/bwd/opt. The split is clamped so
        # fwd+bwd+opt never exceeds compute (annotation walls can overlap
        # phases already subtracted above); compute itself is UNCHANGED —
        # the split refines it, never redefines it.
        fwd = phases.get("fwd", 0.0)
        bwd = phases.get("bwd", 0.0)
        opt = phases.get("opt", 0.0)
        sub = fwd + bwd + opt
        if sub > compute > 0.0:
            scale = compute / sub
            fwd, bwd, opt = fwd * scale, bwd * scale, opt * scale
        elif sub > 0.0 and compute <= 0.0:
            fwd = bwd = opt = 0.0
            sub = 0.0
        if not self._device_kind:
            # Empty until the loop itself has initialised a jax backend.
            self._device_kind, self._devices = _device_info()
        self.step += 1
        rec = {
            "step": self.step,
            "ts": time.time(),
            "rank": self.ctx.world_rank,
            "node_id": self.ctx.node_id,
            "wall_s": wall,
            "data_wait_s": data_wait,
            "compute_s": compute,
            "collective_s": collective,
            "checkpoint_s": checkpoint,
            "pp_bubble_s": pp_bubble,
            "comm_exposed_s": comm_exposed,
        }
        if sub > 0.0:
            rec["fwd_s"] = fwd
            rec["bwd_s"] = bwd
            rec["opt_s"] = opt
        compiles, compile_s = _drain_compiles()
        if compiles:
            rec["compiles"] = compiles
            rec["compile_s"] = compile_s
        # Step boundary for the capture plane: this report ends step
        # `self.step` — an armed capture starts/stops exactly here, so
        # every selected rank cuts on the same global step edge.
        profiler_mod.on_step_boundary(self.step)
        tokens = metrics.get("tokens")
        if isinstance(tokens, (int, float)) and not isinstance(tokens, bool):
            rec["tokens"] = float(tokens)
        flops = metrics.get("flops")
        if isinstance(flops, (int, float)) and not isinstance(flops, bool):
            rec["flops"] = float(flops)
        if self._device_kind:
            rec["device_kind"] = self._device_kind
            rec["devices"] = self._devices
        return rec

    def mark_resume(self) -> None:
        """Exclude the driver's report rendezvous from the next wall.

        ``train.report()`` blocks until the trainer's poll loop consumes
        the previous result, so every rank resumes on the same round
        edge — gated by the slowest rank. Without this re-stamp that
        block lands in the NEXT step's wall and every rank's wall
        converges to the gang round period, which blinds the MAD
        straggler scan (a dragged rank reads as a uniform gang).
        ``session.report`` calls this after the hand-off so walls
        measure the rank's own step, not the driver's backpressure."""
        self._last = time.perf_counter()


async def _swallow(coro) -> None:
    """Await a fire-and-forget push; a failed push is a delayed snapshot,
    not an error (and must not leave 'exception never retrieved' noise)."""
    try:
        await coro
    except Exception:
        logger.debug("workload_ingest push failed", exc_info=True)


# -- driver side ---------------------------------------------------------
class FlightRecorder:
    """Driver-side aggregator + goodput accountant + store uplink."""

    def __init__(self, experiment: str, enabled_: bool | None = None):
        from ray_tpu._private.workload import StepStatsAggregator

        self.experiment = experiment
        self.enabled = enabled() if enabled_ is None else enabled_
        self.agg = StepStatsAggregator()
        self._t0 = time.monotonic()
        self.buckets = {
            "checkpoint_s": 0.0,
            "restart_s": 0.0,
            "stalled_s": 0.0,
        }
        self._last_progress: float | None = None
        self._pending: dict[str, list[dict]] = {}
        self._last_push = 0.0
        self._summary: dict | None = None
        self._last_summary = 0.0
        self.stragglers: list[dict] = []
        # Auto-profiling (ISSUE 20): ranks flagged straggler on
        # consecutive summary cuts debounce-trigger a bounded capture.
        self._straggler_streak: dict[int, int] = {}
        self._last_auto_req = 0.0

    # -- goodput wall-clock buckets -------------------------------------
    def note_restart(self, seconds: float) -> None:
        self.buckets["restart_s"] += max(0.0, seconds)

    def note_checkpoint(self, seconds: float) -> None:
        self.buckets["checkpoint_s"] += max(0.0, seconds)

    def note_progress(self) -> None:
        self._last_progress = time.monotonic()

    def note_stalled_since_progress(self) -> None:
        """The failure path: everything since the last committed round is
        lost work + detection time — the 'stalled' bucket."""
        if self._last_progress is not None:
            self.buckets["stalled_s"] += max(
                0.0, time.monotonic() - self._last_progress
            )
            self._last_progress = None

    def goodput(self) -> dict:
        from ray_tpu._private.workload import goodput_buckets

        return goodput_buckets(
            time.monotonic() - self._t0, **self.buckets
        )

    # -- per-round ingest -----------------------------------------------
    def on_round(self, round_results: list) -> dict | None:
        """Ingest one poll round's per-rank StepStats. Returns the rolling
        gang summary (tokens/s, MFU, phase fractions) or None when the
        recorder is off or the round carried no records."""
        self.note_progress()
        if not self.enabled:
            return None
        max_ckpt = 0.0
        saw = False
        for result in round_results:
            rec = result.get("step_stats") if isinstance(result, dict) else None
            if not isinstance(rec, dict):
                continue
            if self.agg.add(rec):
                saw = True
                if rec.get("compiles") and rec["step"] >= 2:
                    # Records 0 and 1 hold the step's own first compile
                    # (and a donated layout's second); later is a loop
                    # that changed a shape or a static value.
                    logger.warning(
                        "rank %s recompiled in step %s: %d program(s), %.3f s",
                        rec.get("rank"), rec["step"], rec["compiles"],
                        float(rec.get("compile_s") or 0.0),
                    )
                max_ckpt = max(max_ckpt, float(rec.get("checkpoint_s") or 0.0))
                rank = rec.get("rank", 0)
                self._queue(f"train/{self.experiment}/rank{rank}", rec)
        if not saw:
            return self._summary
        # Workers save sharded checkpoints inside the step; the slowest
        # rank's save time is wall clock the gang spent checkpointing.
        self.buckets["checkpoint_s"] += max_ckpt
        # The rolling summary + straggler scan walk the whole window
        # (O(window x ranks)); at ms-scale steps doing that every
        # lockstep round is measurable overhead, so it runs on the push
        # cadence and rounds in between reuse the cached (<=1s stale)
        # summary. Raw per-rank records are still queued every round.
        now = time.monotonic()
        if self._summary is None or now - self._last_summary >= _PUSH_INTERVAL_S:
            self._last_summary = now
            self._summary = self._cut_gang_sample()
            self._maybe_push()
        return self._summary

    def _cut_gang_sample(self) -> dict:
        """Compute the rolling gang summary + straggler scan and queue it
        as one ``train/<experiment>`` sample."""
        summary = self.agg.summary()
        self.stragglers = self.agg.straggler_report(k=self._mad_k())
        if self.stragglers:
            summary["stragglers"] = [s["rank"] for s in self.stragglers]
        self._maybe_auto_profile()
        self._queue(
            f"train/{self.experiment}",
            {"ts": time.time(), **summary},
        )
        return summary

    def _maybe_auto_profile(self) -> None:
        """Debounce straggler flags into ONE profile_capture request.

        A rank must stay flagged for RAY_TPU_PROFILE_AUTO_CONSECUTIVE
        summary cuts (MAD blips don't profile); the driver then
        fire-and-forgets one controller RPC. The controller is the
        authority on cooldown/concurrency — this side only rate-limits
        its own requests so a persistent straggler doesn't spam."""
        if not self.stragglers:
            self._straggler_streak.clear()
            return
        if not profiler_mod.knob_bool("AUTO", True):
            return
        flagged = {
            int(s["rank"]) for s in self.stragglers if "rank" in s
        }
        for rank in list(self._straggler_streak):
            if rank not in flagged:
                del self._straggler_streak[rank]
        need = profiler_mod.knob_int("AUTO_CONSECUTIVE", 2)
        ready = []
        for rank in sorted(flagged):
            streak = self._straggler_streak.get(rank, 0) + 1
            self._straggler_streak[rank] = streak
            if streak >= need:
                ready.append(rank)
        if not ready:
            return
        now = time.monotonic()
        cooldown = profiler_mod.knob_float("AUTO_COOLDOWN_S", 300.0)
        if self._last_auto_req and now - self._last_auto_req < cooldown:
            return
        self._last_auto_req = now
        for rank in ready:
            self._straggler_streak[rank] = 0
        try:
            from ray_tpu._private import worker as worker_mod

            ctx = worker_mod.get_global_context()
            call = ctx.controller.call(
                "profile_capture",
                {
                    "steps": profiler_mod.knob_int("AUTO_STEPS", 3),
                    "ranks": ready,
                    "reason": "straggler",
                },
                timeout=10.0,
            )
            ctx.io.spawn(_swallow(call))
        except Exception:
            logger.debug("auto-profile trigger failed", exc_info=True)

    @staticmethod
    def _mad_k() -> float:
        try:
            from ray_tpu._private.config import global_config

            return float(global_config().straggler_mad_k)
        except Exception:  # rtlint: disable=swallowed-exception - config unreachable: default MAD k
            return 3.0

    # -- controller uplink ----------------------------------------------
    def _queue(self, key: str, sample: dict) -> None:
        pending = self._pending.setdefault(key, [])
        pending.append(sample)
        if len(pending) > _MAX_PENDING:
            del pending[: len(pending) - _MAX_PENDING]

    def _maybe_push(self, force: bool = False) -> None:
        now = time.monotonic()
        if not force and now - self._last_push < _PUSH_INTERVAL_S:
            return
        if not self._pending:
            return
        series = [
            {"key": key, "samples": samples}
            for key, samples in self._pending.items()
        ]
        self._pending = {}
        self._last_push = now
        try:
            from ray_tpu._private import worker as worker_mod

            ctx = worker_mod.get_global_context()
            call = ctx.controller.call(
                "workload_ingest", {"series": series}, timeout=10.0
            )
            if force:
                # finalize(): the goodput sample must land before fit()
                # returns, so the last push is synchronous.
                ctx.io.run(call)
            else:
                # Steady state: fire-and-forget on the io loop — the
                # driver's poll round must not block on the controller
                # round trip (a lost push only delays the next snapshot).
                ctx.io.spawn(_swallow(call))
        except Exception:
            logger.debug("workload_ingest push failed", exc_info=True)

    def finalize(self) -> dict:
        """End of fit(): compute final goodput, push it + any pending
        samples, and return the goodput buckets for ``Result.goodput``."""
        g = self.goodput()
        if self.enabled:
            if self.agg.records_ingested:
                # One fresh gang sample: the throttled cadence may have
                # left the last <1s of steps out of the stored series.
                self._summary = self._cut_gang_sample()
            self._queue(
                f"train/{self.experiment}/goodput",
                {"ts": time.time(), **g},
            )
            self._maybe_push(force=True)
        return g
