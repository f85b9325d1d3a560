"""The time to the first step, told by the program: the lifecycle spans from
``ray_tpu.init`` to a train worker's first ``train.report`` (recorded with
tracing off), the train worker's compile watcher, and the data clocks of
the flight recorder."""

import logging
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private.config import global_config
from ray_tpu.train._internal import step_stats
from ray_tpu.train._internal.session import TrainContext
from ray_tpu.util import tracing

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEPS = 6

# span -> (its parent, the process that records it): docs/observability.md
TREE = {
    "ray_tpu.init": (None, "driver"),
    "init.start_controller": ("ray_tpu.init", "driver"),
    "init.start_agent": ("ray_tpu.init", "driver"),
    "init.connect": ("ray_tpu.init", "driver"),
    "train.fit": (None, "driver"),
    "train.form_gang": ("train.fit", "driver"),
    "train.split_datasets": ("train.fit", "driver"),
    "train.start_sessions": ("train.fit", "driver"),
    "train.first_round": ("train.fit", "driver"),
    "train.loop": ("train.fit", "worker"),
    "train.reach_device": ("train.loop", "worker"),
    "train.setup_state": ("train.loop", "worker"),
    "train.first_report": ("train.loop", "worker"),
}
# Once a PROCESS, not once a run: a boot span is not in TREE. The driver's
# is in the session of its process's first init (an earlier test file's,
# under xdist), and every worker process of the run writes its own.
BOOTS = {"driver.boot", "worker.boot"}
# The compile watcher's three spans a program, in the order jax runs them.
STAGES = ("jax.trace", "jax.lower", "jax.compile")
TICK = 0.01     # the CPU clocks are read a few listener calls after jax's own


def _loop(config):
    import jax
    import jax.numpy as jnp
    import optax

    from ray_tpu import train
    from ray_tpu.train import jax_utils

    def before_jax_utils(x):
        return x * 3 + 1

    @jax.jit
    def inner_jit(x):
        return jnp.sin(x) * 2

    def staged(x):
        # An eager operation on a concrete value: it compiles INSIDE the trace.
        with jax.ensure_compile_time_eval():
            scale = jnp.cumsum(jnp.arange(5.0))[-1]
        return inner_jit(x) * scale

    def raises(x):
        raise ValueError("while it is traced")

    def after_raise(x):
        return x - 7

    entered_ns = time.time_ns()
    # A program compiled before the loop first calls into jax_utils, as a
    # loop's own jax.random.PRNGKey(0) is.
    jax.jit(before_jax_utils)(jnp.ones(3)).block_until_ready()
    jax.jit(staged)(jnp.ones(3)).block_until_ready()
    with pytest.raises(ValueError):
        jax.jit(raises)(jnp.ones(3))
    jax.jit(after_raise)(jnp.ones(3)).block_until_ready()
    setup = jax_utils.setup_sharded_training(
        lambda: {"w": jnp.ones((8, 8))}, optax.sgd(0.1),
        mesh=jax_utils.build_mesh({"dp": 1}),
    )
    step = jax_utils.build_sharded_train_step(
        lambda p, b: ((b["x"] @ p["w"]) ** 2).mean(), optax.sgd(0.1), setup
    )
    params, opt_state = setup.params, setup.opt_state
    batches = train.get_dataset_shard("train").iter_batches(batch_size=4)
    for i in range(config["steps"]):
        rows = np.stack(next(batches)["x"]).astype(np.float32)
        if i == 3:      # a loop that changes a shape: one recompile
            rows = np.concatenate([rows, rows])
        params, opt_state, loss = step(params, opt_state, setup.shard_batch({"x": rows}))
        train.report({"loss": float(loss), "entered_ns": entered_ns})


def _loop_without_a_chip(config):
    from ray_tpu import train

    train.report({"jax_imported": "jax" in sys.modules})


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """One untraced one-worker ``JaxTrainer.fit`` on the CPU whose worker is
    leased one (asserted) chip, and one plain task: the session's spans,
    its timeline and rank 0's StepStats records. Then, beside it, a second
    fit whose worker is leased none: what it reported and the spans it
    added."""
    import ray_tpu.data
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.util import state

    assert not ray_tpu.is_initialized()
    os.environ.pop("RAY_TPU_tracing_enabled", None)
    global_config().tracing_enabled = False
    ray_tpu.init(num_cpus=4, resources={"TPU": 1})
    try:
        result = JaxTrainer(
            _loop,
            train_loop_config={"steps": STEPS},
            scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
            run_config=RunConfig(
                name="lifecycle", storage_path=str(tmp_path_factory.mktemp("run"))
            ),
            datasets={"train": ray_tpu.data.from_numpy(
                np.ones((64, 8), np.float32), column="x")},
        ).fit()
        assert result.error is None

        # After the fit: before it, the task's worker would idle in the
        # agent's pool and the gang's actor be handed it, warm, with its
        # boot behind it.
        @ray_tpu.remote
        def add(a, b):
            return a + b

        assert ray_tpu.get(add.remote(1, 2), timeout=60) == 3
        session_dir = os.environ["RAYTPU_SESSION_DIR"]
        deadline = time.monotonic() + 20
        records = []
        while time.monotonic() < deadline and len(records) < STEPS:
            records = state.get_workload_timeline(
                "train/lifecycle/rank0", "raw").get("raw") or []
            time.sleep(0.2)
        time.sleep(0.5)     # the worker's flusher tick
        spans = tracing.read_spans(session_dir)
        timeline = ray_tpu.timeline()
        no_chip = JaxTrainer(
            _loop_without_a_chip,
            scaling_config=ScalingConfig(num_workers=1),
            run_config=RunConfig(
                name="no-chip", storage_path=str(tmp_path_factory.mktemp("no-chip"))
            ),
        ).fit()
        assert no_chip.error is None
        time.sleep(0.5)
        seen = {s["span_id"] for s in spans}
        yield {
            "spans": spans,
            "timeline": timeline,
            "records": records,
            "entered_ns": result.metrics["entered_ns"],
            "no_chip": no_chip.metrics,
            "no_chip_spans": [
                s for s in tracing.read_spans(session_dir)
                if s["span_id"] not in seen
            ],
        }
    finally:
        ray_tpu.shutdown()


def _named(run, name):
    return [s for s in run["spans"] if s["name"] == name]


def test_lifecycle_spans_are_recorded_untraced_and_per_task_spans_are_not(run):
    names = {s["name"] for s in run["spans"]}
    assert set(TREE) <= names
    assert names <= set(TREE) | BOOTS | set(STAGES), (
        "a span gated by tracing.enabled() was recorded with tracing off"
    )


@pytest.mark.parametrize("name", sorted(TREE))
def test_each_span_of_the_table_once_under_its_parent(run, name):
    parent, process = TREE[name]
    found = _named(run, name)
    assert len(found) == 1
    span = found[0]
    driver_pid = _named(run, "ray_tpu.init")[0]["pid"]
    assert (span["pid"] == driver_pid) == (process == "driver")
    if parent is None:
        assert span["parent_id"] is None
        return
    above = _named(run, parent)[0]
    assert span["parent_id"] == above["span_id"]
    assert span["trace_id"] == above["trace_id"]
    # one host, one clock: a child lies inside its parent, across processes too
    slack = 5_000_000
    assert above["start_ns"] - slack <= span["start_ns"]
    assert span["end_ns"] <= above["end_ns"] + slack


def test_the_workers_loop_hangs_under_the_drivers_fit(run):
    fit, loop = _named(run, "train.fit")[0], _named(run, "train.loop")[0]
    assert loop["pid"] != fit["pid"]
    assert loop["parent_id"] == fit["span_id"]
    assert fit["attributes"]["experiment"] == "lifecycle"
    gang = _named(run, "train.form_gang")[0]["attributes"]
    assert gang == {"attempt": 0, "world_size": 1}
    # the first report ends where the driver's first round returns
    first_report = _named(run, "train.first_report")[0]
    first_round = _named(run, "train.first_round")[0]
    assert first_report["start_ns"] == loop["start_ns"]
    assert first_report["end_ns"] <= first_round["end_ns"]


def test_the_session_reaches_its_leased_chip_before_the_users_function(run):
    reach = _named(run, "train.reach_device")[0]
    loop = _named(run, "train.loop")[0]
    assert loop["start_ns"] <= reach["start_ns"]
    assert reach["end_ns"] <= run["entered_ns"]
    assert reach["status"] == "ok"
    found = reach["attributes"]
    assert set(found) == {"import_s", "platform", "devices", "leased"}
    assert found["platform"] == "cpu" and found["leased"] == 1
    # a lease may lie: what is found is an attribute, not an error
    assert found["devices"] == 8
    assert 0 <= found["import_s"] <= (reach["end_ns"] - reach["start_ns"]) / 1e9
    # the first report still counts from the loop's start
    assert _named(run, "train.first_report")[0]["start_ns"] == loop["start_ns"]


def test_a_worker_that_was_leased_no_chip_reaches_none_and_imports_no_jax(run):
    assert run["no_chip"]["jax_imported"] is False
    names = [s["name"] for s in run["no_chip_spans"]]
    assert "train.loop" in names and "train.first_report" in names
    assert "train.reach_device" not in names and "jax.compile" not in names


def test_the_gang_workers_boot_lies_inside_the_drivers_form_gang(run):
    worker_pid = _named(run, "train.first_report")[0]["pid"]
    boots = [s for s in _named(run, "worker.boot") if s["pid"] == worker_pid]
    assert len(boots) == 1
    boot, gang = boots[0], _named(run, "train.form_gang")[0]
    assert boot["parent_id"] is None
    slack = 20_000_000      # the OS counts a process's start in ticks of 10 ms
    assert gang["start_ns"] - slack <= boot["start_ns"]
    assert boot["end_ns"] <= gang["end_ns"]
    assert 0 < boot["attributes"]["imports_s"] < (boot["end_ns"] - boot["start_ns"]) / 1e9
    # every worker process of the run wrote one, its own
    pids = [s["pid"] for s in _named(run, "worker.boot")]
    assert len(pids) == len(set(pids)) > 1
    assert _named(run, "ray_tpu.init")[0]["pid"] not in pids


def test_start_up_compiles_are_spans_under_the_span_that_compiled(run):
    compiles = _named(run, "jax.compile")
    assert compiles
    loop = _named(run, "train.loop")[0]
    setup = _named(run, "train.setup_state")[0]
    first_report = _named(run, "train.first_report")[0]
    assert {s["pid"] for s in compiles} == {loop["pid"]}
    assert {s["parent_id"] for s in compiles} <= {loop["span_id"], setup["span_id"]}
    assert any(s["parent_id"] == setup["span_id"] for s in compiles)
    # the watcher is registered where the session reaches its chip: what the
    # loop compiles before it enters jax_utils is seen too
    early = [s for s in compiles if "before_jax_utils" in s["attributes"]["fun_name"]]
    assert len(early) == 1 and early[0]["parent_id"] == loop["span_id"]
    assert early[0]["end_ns"] <= setup["start_ns"]
    for s in compiles:
        assert s["attributes"]["cache"] in ("hit", "miss")
        assert s["attributes"]["seconds"] > 0 and s["attributes"]["fun_name"]
        # with tracing off they stop at the first report: step 3's recompile
        # is a counter of its record, not a span
        assert s["end_ns"] <= first_report["end_ns"]


def _stages_of(spans, function):
    """The watcher's spans of one jitted function: jax names the trace by
    the function and the other two ``jit(<function>)``."""
    return [s for s in spans if s["name"] in STAGES
            and s["attributes"].get("fun_name") in (function, f"jit({function})")]


@pytest.mark.parametrize("function", ["before_jax_utils", "staged", "after_raise"])
def test_a_program_is_three_spans_once_each_in_order_on_one_thread(run, function):
    found = sorted(_stages_of(run["spans"], function), key=lambda s: s["start_ns"])
    assert [s["name"] for s in found] == list(STAGES)
    trace, lower, compile_ = found
    assert trace["attributes"]["fun_name"] == function
    assert lower["attributes"]["fun_name"] == compile_["attributes"]["fun_name"] == f"jit({function})"
    assert len({s["pid"] for s in found}) == len({s["attributes"]["thread"] for s in found}) == 1
    assert trace["end_ns"] <= lower["start_ns"] and lower["end_ns"] <= compile_["start_ns"]
    assert len({s["parent_id"] for s in found}) == 1


def test_an_inner_jit_is_a_count_on_the_outermost_span_and_no_span_of_its_own(run):
    assert not _stages_of(run["spans"], "inner_jit")
    (trace,) = [s for s in _stages_of(run["spans"], "staged") if s["name"] == "jax.trace"]
    # inner_jit's trace, and the eager operations' traces, lowerings and compiles
    assert trace["attributes"]["inner"] > 3
    # jax.numpy's own operators are jits too: x - 7 is one
    (plain,) = [s for s in _stages_of(run["spans"], "after_raise") if s["name"] == "jax.trace"]
    assert 0 <= plain["attributes"]["inner"] < trace["attributes"]["inner"]


def test_an_eager_operation_inside_a_trace_still_writes_its_compile_span(run):
    (trace,) = [s for s in _stages_of(run["spans"], "staged") if s["name"] == "jax.trace"]
    eager = _stages_of(run["spans"], "cumsum")
    assert [s["name"] for s in eager] == ["jax.compile"]      # its trace and lowering: counts
    (compile_,) = eager
    assert trace["start_ns"] <= compile_["start_ns"] and compile_["end_ns"] <= trace["end_ns"]
    assert compile_["attributes"]["thread"] == trace["attributes"]["thread"]
    assert compile_["attributes"]["cache"] in ("hit", "miss")


def test_a_function_that_raises_in_its_trace_leaves_the_depth_at_zero(run):
    """jax's ``__exit__`` fires the event on the way out of an exception
    too: the trace is a span, nothing follows it, and the next program
    (``after_raise``, above) gets its three."""
    assert [s["name"] for s in _stages_of(run["spans"], "raises")] == ["jax.trace"]


@pytest.mark.parametrize("kind", STAGES)
def test_the_cpu_clocks_lie_beside_the_wall(run, kind):
    found = _named(run, kind)
    assert found
    for s in found:
        a = s["attributes"]
        assert a["seconds"] == pytest.approx((s["end_ns"] - s["start_ns"]) / 1e9, abs=1e-5)
        assert 0 <= a["cpu_s"] <= a["seconds"] + TICK
        assert a["proc_cpu_s"] >= a["cpu_s"] - TICK
        assert a["inner"] >= 0 and a["fun_name"]


@pytest.mark.parametrize("kind", ["jax.trace", "jax.lower"])
def test_no_span_of_a_kind_lies_inside_another_of_its_kind(run, kind):
    found = sorted(_named(run, kind), key=lambda s: s["start_ns"])
    assert len({s["attributes"]["thread"] for s in found}) == 1
    assert all(a["end_ns"] <= b["start_ns"] for a, b in zip(found, found[1:]))


@pytest.mark.parametrize("kind", STAGES)
def test_with_tracing_off_the_stages_stop_at_the_first_report(run, kind):
    """Step 3's recompile traced, lowered and compiled a program after it:
    counters of its record, no spans."""
    first_report = _named(run, "train.first_report")[0]
    assert all(s["end_ns"] <= first_report["end_ns"] for s in _named(run, kind))
    assert run["records"][3]["compiles"] >= 1


def test_a_stage_the_watcher_was_registered_inside_ends_without_its_clocks(tmp_path, monkeypatch):
    """A loop on a worker that was leased no chip may first call into
    ``jax_utils`` from INSIDE a traced function: that trace's end arrives
    with no entry before it. It must not raise into jax's ``__exit__``."""
    monkeypatch.setattr(tracing, "_dir", str(tmp_path / "tracing"))
    monkeypatch.setattr(step_stats, "_startup", True)
    seen = {}

    def unentered():
        now = time.time()
        step_stats.note_stage_left(step_stats.TRACE, now - 1.0, now, "outer")
        seen["depth"] = step_stats._stages.depth
        step_stats.note_stage_entered(step_stats.TRACE)
        step_stats.note_stage_left(step_stats.TRACE, now, now + 0.5, "next")

    worker = threading.Thread(target=unentered)
    worker.start()
    worker.join()
    tracing.flush()
    outer, following = tracing.read_spans(str(tmp_path))
    assert seen["depth"] == 0
    assert outer["attributes"] == {"seconds": pytest.approx(1.0), "fun_name": "outer",
                                   "thread": outer["attributes"]["thread"]}
    assert following["attributes"]["inner"] == 0 and "cpu_s" in following["attributes"]


_TRACED_FIT = """
import json, os, sys, time
import numpy as np
import ray_tpu
from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
from ray_tpu.util import state, tracing

def loop(config):
    import jax
    from ray_tpu import train
    def recompiled(x):
        return x * 2 + 1
    double = jax.jit(recompiled)
    for i in range(4):      # step 2 changes a shape
        double(np.ones(8 if i == 2 else 4, np.float32)).block_until_ready()
        train.report({"step": i})

ray_tpu.init(num_cpus=2, resources={"TPU": 1})
try:
    fit = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1, use_tpu=True),
                     run_config=RunConfig(name="traced", storage_path=sys.argv[1])).fit()
    assert fit.error is None, fit.error
    records, deadline = [], time.monotonic() + 20
    while time.monotonic() < deadline and len(records) < 4:
        records = state.get_workload_timeline("train/traced/rank0", "raw").get("raw") or []
        time.sleep(0.2)
    time.sleep(0.5)
    spans = tracing.read_spans(os.environ["RAYTPU_SESSION_DIR"])
finally:
    ray_tpu.shutdown()
(report,) = [s for s in spans if s["name"] == "train.first_report"]
print(json.dumps({
    "compiles": [r.get("compiles", 0) for r in records],
    "late": [[s["name"], s["attributes"]["fun_name"]] for s in spans
             if s["name"].startswith("jax.") and s["start_ns"] >= report["end_ns"]],
    "early": sorted({s["name"] for s in spans
                     if s["name"].startswith("jax.") and s["end_ns"] <= report["end_ns"]}),
}))
"""


def _run_script(script, *args, timeout=180, **extra):
    """A process of its own, off this session and untraced unless ``extra``
    says otherwise; what its last line of output holds."""
    import json

    env = dict(os.environ, PYTHONPATH=ROOT, JAX_PLATFORMS="cpu")
    env.pop("RAY_TPU_tracing_enabled", None)
    env.pop("RAYTPU_SESSION_DIR", None)
    env.update(extra)
    done = subprocess.run(
        [sys.executable, "-c", script, *args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )
    assert done.returncode == 0, done.stderr[-2000:]
    return json.loads(done.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def traced_fit(tmp_path_factory):
    """The one fit more: a cluster of its own with ``RAY_TPU_tracing_enabled=1``
    whose loop recompiles after its first report."""
    return _run_script(
        _TRACED_FIT, str(tmp_path_factory.mktemp("traced")), RAY_TPU_tracing_enabled="1"
    )


@pytest.mark.parametrize("kind, fun_name", [
    ("jax.trace", "recompiled"), ("jax.lower", "jit(recompiled)"), ("jax.compile", "jit(recompiled)"),
])
def test_with_tracing_on_a_recompile_after_the_first_report_is_a_span(traced_fit, kind, fun_name):
    assert [kind, fun_name] in traced_fit["late"]
    assert [name for name, _fun in traced_fit["late"]].count(kind) == 1
    assert kind in traced_fit["early"]


def test_stepstats_counts_the_recompile_traced_or_not(run, traced_fit):
    assert traced_fit["compiles"][2] == 1 and traced_fit["compiles"][3] == 0
    assert run["records"][3]["compiles"] >= 1


def test_the_timeline_shows_driver_and_worker_on_one_axis(run):
    events = run["timeline"]["traceEvents"]
    spans = {e["name"]: e for e in events if e.get("cat") == "span"}
    assert set(TREE) <= set(spans)
    tracks = {e["pid"]: e["args"]["name"] for e in events if e["name"] == "process_name"}
    assert tracks[spans["train.fit"]["pid"]].startswith("driver")
    assert tracks[spans["train.loop"]["pid"]].startswith("train_worker")
    fit, loop = spans["train.fit"], spans["train.loop"]
    assert fit["ts"] <= loop["ts"] and loop["ts"] + loop["dur"] <= fit["ts"] + fit["dur"] + 5e3


def test_the_record_of_the_step_that_recompiled_carries_compiles(run):
    records = run["records"]
    assert [r["step"] for r in records] == list(range(STEPS))
    with_compiles = [r["step"] for r in records if "compiles" in r]
    assert 3 in with_compiles and set(with_compiles) <= {0, 3}
    third = records[3]
    assert third["compiles"] >= 1 and 0 < third["compile_s"] <= third["wall_s"]
    assert all("compile_s" not in r for r in records if "compiles" not in r)


def test_data_wait_counts_the_iterators_own_work(run):
    """A prefetching producer never blocks the loop, and the loop still
    spends its time slicing and formatting: both clocks are data wait."""
    later = run["records"][1:]
    assert all(r["data_wait_s"] > 0 for r in later)
    assert all(r["data_wait_s"] <= r["wall_s"] for r in later)


class _Shard:
    fetch_wait_s = 0.25
    local_work_s = 0.5


def test_the_recorder_adds_both_clocks_and_a_toy_loop_recompiles_once():
    import jax
    import jax.numpy as jnp

    from ray_tpu.train import jax_utils

    jax_utils._watch_compiles()
    recorder = step_stats.StepRecorder(TrainContext(dataset_shards={"train": _Shard()}))
    assert recorder._data_wait_total() == pytest.approx(0.75)

    double = jax.jit(lambda x: x * 2 + 1)
    inputs = {n: np.ones(n, np.float32) for n in (4, 8)}
    double(inputs[4]).block_until_ready()
    step_stats._drain_compiles()
    records = []
    for i in range(5):
        double(inputs[8 if i == 3 else 4]).block_until_ready()
        records.append(recorder.on_report({}))
    assert [r["step"] for r in records if "compiles" in r] == [3]
    assert records[3]["compiles"] == 1 and records[3]["compile_s"] > 0


def test_the_driver_logs_the_step_that_recompiled(caplog):
    flight = step_stats.FlightRecorder("toy", enabled_=True)
    base = {"rank": 0, "wall_s": 0.1, "compute_s": 0.1}
    with caplog.at_level(logging.WARNING, logger=step_stats.__name__):
        for step in range(5):
            rec = dict(base, step=step)
            if step in (0, 3):
                rec.update(compiles=2, compile_s=0.05)
            flight.on_round([{"step_stats": rec}])
    lines = [r.getMessage() for r in caplog.records if "recompiled" in r.getMessage()]
    assert len(lines) == 1 and "step 3" in lines[0] and "2 program(s)" in lines[0]


def test_the_aggregator_carries_compiles_only_where_there_were_any():
    from ray_tpu._private.workload import StepStatsAggregator

    agg = StepStatsAggregator(window=4)
    base = {"rank": 0, "wall_s": 0.1, "compute_s": 0.1}
    for step in range(3):
        agg.add(dict(base, step=step))
    assert "compiles" not in agg.summary()
    agg.add(dict(base, step=3, compiles=2, compile_s=0.05))
    summary = agg.summary()
    assert summary["compiles"] == 2 and summary["compile_s"] == pytest.approx(0.05)
    for step in range(4, 8):        # the window moves past the recompile
        agg.add(dict(base, step=step))
    assert "compiles" not in agg.summary()


_TWICE = """
import os, sys, json
import jax, jax.numpy as jnp
from ray_tpu.train import jax_utils
from ray_tpu.train._internal import step_stats
from ray_tpu.util import tracing
tracing.configure(sys.argv[1])
jax_utils._watch_compiles()
step_stats.begin_startup()
x = jnp.ones((16, 16))
step_stats._drain_compiles()
for _ in range(2):
    jax.jit(lambda a: (a @ a).sum() * 3).lower(x).compile()
    jax.clear_caches()
count, seconds = step_stats._drain_compiles()
spans = [s for s in tracing.read_spans(sys.argv[1]) if s["name"] == "jax.compile"]
print(json.dumps({"count": count, "seconds": seconds,
                  "attributes": [s["attributes"] for s in spans][-2:]}))
"""


@pytest.fixture(scope="module")
def twice(tmp_path_factory):
    """One process with a cache directory placed that compiles one program,
    forgets it and compiles it again."""
    tmp_path = tmp_path_factory.mktemp("twice")
    return _run_script(
        _TWICE, str(tmp_path / "session"), timeout=120,
        JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
        JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
        JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
    )


def test_a_program_compiled_twice_is_a_miss_then_a_hit(twice):
    assert twice["count"] == 2 and twice["seconds"] > 0
    assert [a["cache"] for a in twice["attributes"]] == ["miss", "hit"]
    assert "lambda" in twice["attributes"][-1]["fun_name"]


def test_a_hit_carries_what_the_read_of_its_entry_took(twice):
    miss, hit = twice["attributes"]
    assert "retrieval_s" not in miss        # it read nothing
    assert 0 < hit["retrieval_s"] <= hit["seconds"]
    # the rest of a hit is the key: the module's and the options' hashing
    assert hit["cpu_s"] <= hit["seconds"] + TICK


_INIT_TWICE = """
import json, os
import psutil
import ray_tpu
from ray_tpu.util import tracing
sessions = []
for _ in range(2):
    ray_tpu.init(num_cpus=1)
    session = os.environ["RAYTPU_SESSION_DIR"]
    ray_tpu.shutdown()
    sessions.append([s for s in tracing.read_spans(session)
                     if s["name"] in ("driver.boot", "ray_tpu.init")])
print(json.dumps({"created": psutil.Process().create_time(), "pid": os.getpid(),
                  "sessions": sessions}))
"""


def test_the_drivers_boot_is_written_once_though_init_runs_twice():
    before_ns = time.time_ns()
    seen = _run_script(_INIT_TWICE)
    first, second = seen["sessions"]
    assert sorted(s["name"] for s in first) == ["driver.boot", "ray_tpu.init"]
    assert [s["name"] for s in second] == ["ray_tpu.init"]
    boot = next(s for s in first if s["name"] == "driver.boot")
    init = next(s for s in first if s["name"] == "ray_tpu.init")
    assert boot["pid"] == seen["pid"] and boot["parent_id"] is None
    # from the OS's start of the process, to a tick of 10 ms ...
    assert before_ns - 20_000_000 <= boot["start_ns"] < init["start_ns"]
    # ... where psutil's whole-second boot time reads up to a second early
    assert -0.02 <= boot["start_ns"] / 1e9 - seen["created"] < 1.02
    # to the entry of the first ray_tpu.init
    assert boot["end_ns"] == init["start_ns"]


def test_the_flusher_is_gone_a_second_after_the_last_span(tmp_path, monkeypatch):
    def flushers():
        return [t for t in threading.enumerate() if t.name == "raytpu-span-flusher"]

    deadline = time.monotonic() + 5
    while flushers() and time.monotonic() < deadline:
        time.sleep(0.1)         # an earlier test's, on its way out
    monkeypatch.setattr(tracing, "_dir", str(tmp_path / "tracing"))
    assert not tracing.enabled()
    with tracing.span("gated") as gated:
        assert gated is None
    with tracing.span("once.a.run", lifecycle=True, answer=42):
        pass
    assert len(flushers()) == 1
    started = time.monotonic()
    while flushers() and time.monotonic() - started < 3:
        time.sleep(0.05)
    assert not flushers(), "the flusher still ticks after its last span"
    assert time.monotonic() - started < 2.0
    written = tracing.read_spans(str(tmp_path))
    assert [s["name"] for s in written] == ["once.a.run"]
    assert written[0]["attributes"] == {"answer": 42}
    # the next span starts a new one
    tracing.emit("again", start_ns=time.time_ns(), lifecycle=True)
    assert len(flushers()) == 1
