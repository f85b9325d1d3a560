"""The readers of the compile watcher's three spans a program
(``harness/compile_spans.py`` and the four ``layer_metrics`` files on it),
each on a hand-written span list, and one CPU rehearsal through ``run.py``
whose traced result line carries all four and whose spans are one trace, one
lowering and one compile-or-load for every program the harness counted."""

import glob
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import compile_spans
from benchmarks.harness.manifest import Manifest
from benchmarks.layer_metrics import (
    cache_read_s, program_build_s, program_lower_s, program_trace_s, trace_lower_cpu_pct,
)
from benchmarks.tests.test_discovery import CASES, ROOT
from benchmarks.tests.test_program_spans import DRIVER, WORKER, run_of, span

NEW = ("program_trace_s", "program_lower_s", "cache_read_s", "trace_lower_cpu_pct")
READERS = (program_trace_s, program_lower_s, cache_read_s, trace_lower_cpu_pct)


def program(start, trace, lower, build, name, pid=WORKER, **compile_attributes):
    """One program's three spans, back to back from ``start``; the thread
    ran for half of the trace and all of the lowering."""
    lowered, compiled = start + trace, start + trace + lower
    return [
        span("jax.trace", start, lowered, pid, fun_name=name, seconds=trace, inner=3,
             cpu_s=trace / 2, proc_cpu_s=trace),
        span("jax.lower", lowered, compiled, pid, fun_name=f"jit({name})", seconds=lower, inner=0,
             cpu_s=lower, proc_cpu_s=lower),
        span("jax.compile", compiled, compiled + build, pid, fun_name=f"jit({name})",
             seconds=build, inner=0, cpu_s=build, proc_cpu_s=build, **compile_attributes),
    ]


def spans():
    return [
        span("train.first_report", 6.4, 29.0, WORKER),
        *program(10.0, 2.0, 1.0, 4.0, "init", cache="hit", retrieval_s=3.0),
        *program(20.0, 4.0, 2.0, 1.0, "fused", cache="hit", retrieval_s=0.5),
        *program(30.0, 1.0, 0.5, 2.0, "built", cache="miss"),
        # inside the window (a recompile): no part of the start-up
        *program(41.0, 8.0, 8.0, 8.0, "late", cache="hit", retrieval_s=8.0),
        # its compile ends after the window starts: the trace and the lowering count
        *program(38.0, 1.0, 0.5, 1.0, "straddles", cache="hit", retrieval_s=0.75),
        # another process's programs are not the worker's
        *program(5.0, 16.0, 16.0, 16.0, "elsewhere", DRIVER, cache="hit", retrieval_s=16.0),
    ]


def test_each_reader_on_a_hand_written_span_list():
    run = run_of(spans())
    assert program_trace_s.read(run) == pytest.approx(2.0 + 4.0 + 1.0 + 1.0)
    assert program_lower_s.read(run) == pytest.approx(1.0 + 2.0 + 0.5 + 0.5)
    assert cache_read_s.read(run) == pytest.approx(3.0 + 0.5)        # a miss read nothing
    # half of 8 s of tracing and all of 4 s of lowering
    assert trace_lower_cpu_pct.read(run) == pytest.approx(100 * (4.0 + 4.0) / 12.0)
    # a part of program_build_s, which reads what it read before the two spans were
    assert program_build_s.read(run) == pytest.approx(4.0 + 1.0 + 2.0)
    assert cache_read_s.read(run) <= program_build_s.read(run)


def test_the_readers_are_the_gang_workers_and_clip_at_the_window():
    found = compile_spans.stages(run_of(spans()))
    assert {s["pid"] for kind in found.values() for s in kind} == {WORKER}
    names = {kind: [s["attributes"]["fun_name"] for s in found[kind]] for kind in found}
    assert names["jax.trace"] == ["init", "fused", "built", "straddles"]
    assert names["jax.compile"] == ["jit(init)", "jit(fused)", "jit(built)"]
    # a later window takes the straddling compile and the late trace in
    later = run_of(spans())
    later["facts"]["marks"]["window_start"] += 10.0
    assert cache_read_s.read(later) == pytest.approx(3.0 + 0.5 + 0.75)
    assert program_trace_s.read(later) == pytest.approx(8.0 + 8.0)


@pytest.mark.parametrize("reader", READERS, ids=NEW)
def test_a_program_without_the_spans_reads_none(reader):
    """The parent of the PR that added them writes ``jax.compile`` spans and
    neither of the others: a sum of ``retrieval_s`` over those would be 0, a
    number, and has to be nothing. So has a run with no worker."""
    older = [dict(s, attributes={k: v for k, v in s["attributes"].items()
                                 if k in ("seconds", "cache", "fun_name")})
             for s in spans() if s["name"] not in ("jax.trace", "jax.lower")]
    assert program_build_s.read(run_of(older)) == pytest.approx(7.0)
    assert reader.read(run_of(older)) is None
    assert reader.read(run_of([])) is None
    assert reader.read(run_of([s for s in spans() if s["name"] != "train.first_report"])) is None


def test_the_manifest_validates_with_the_four_entries_at_its_end():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    entries = manifest.data["per_layer"]
    assert [e["name"] for e in entries[-4:]] == list(NEW)
    for entry in entries[-4:]:
        share = entry["name"] == "trace_lower_cpu_pct"
        assert entry == {"name": entry["name"], "unit": "%" if share else "s",
                         "better": "higher" if share else "lower", "source": "program_span",
                         "layer": "Step", "moves": "setup_s"}      # every cell: no list
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layer_metrics", entry["name"] + ".py"))


def test_a_traced_rehearsal_reports_all_four_and_three_spans_a_program(tmp_path):
    """``run.py --platform cpu --trace 1`` on a tiny ingest cell, as
    ``test_startup_spans.py`` runs it: the real cluster, trainer, worker and
    watcher. Times from a CPU say nothing; that every program the harness's
    own listener counted has its three spans, in order, does."""
    config, traffic = CASES["ingest"]
    copy = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "benchmarks"), copy / "benchmarks",
                    ignore=shutil.ignore_patterns("__pycache__", ".*"))
    bench = copy / "benchmarks"
    (bench / "configs" / "tiny.json").write_text(json.dumps(dict(config, name="tiny")))
    (bench / "traffic" / "tiny-ingest.json").write_text(json.dumps(dict(traffic, name="tiny-ingest")))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append({"name": "tiny", "source": "a test", "reduced": [],
                                "file": "benchmarks/configs/tiny.json", "why": "a test"})
    manifest["workloads"].append({"name": "tiny.ingest", "config": "tiny", "traffic": "tiny-ingest",
                                  "chips": 1, "why": "a test"})
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    sessions = tmp_path / "tmp"
    sessions.mkdir()
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT, TMPDIR=str(sessions),
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"))
    for name in ("XLA_FLAGS", "RAY_TPU_tracing_enabled", "RAYTPU_SESSION_DIR"):
        env.pop(name, None)
    done = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "tiny.ingest", "--seed", "2147484067",
         "--seconds", "2", "--trace", "1", "--platform", "cpu"],
        cwd=str(copy), env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(metrics)
    assert metrics["program_trace_s"] > 0 and metrics["program_lower_s"] > 0
    # a first run built every program: nothing was read from the cache
    assert metrics["cache_read_s"] == 0 and metrics["programs_built"] > 0
    assert 0 < metrics["trace_lower_cpu_pct"] <= 101

    from ray_tpu.util import tracing

    (session,) = glob.glob(str(sessions / "raytpu" / "session_*"))
    found = tracing.read_spans(session)
    (report,) = [s for s in found if s["name"] == "train.first_report"]
    run = {"program_spans": found, "facts": {"marks": {
        "window_start": max(s["end_ns"] for s in found if s["name"] == "jax.compile") / 1e9}}}
    stages = compile_spans.stages(run)
    counted = facts["setup"]["backend_compiles_in_setup"]
    assert [len(stages[kind]) for kind in ("jax.lower", "jax.compile")] == [counted] * 2
    assert counted == facts["setup"]["cache_hits"] + facts["setup"]["cache_misses"] > 0
    assert metrics["program_build_s"] == pytest.approx(
        sum((s["end_ns"] - s["start_ns"]) / 1e9 for s in stages["jax.compile"]))
    # hundreds of lines a start, not thousands: the nested events are counts
    assert sum(s["attributes"]["inner"] for s in stages["jax.trace"]) > 10 * counted
    # every program: its trace, then its lowering, then its compile, on one
    # thread. A trace that nothing follows is a ``jax.eval_shape`` (the
    # harness's of the initialiser, ``setup_sharded_training``'s): traced
    # for its shapes, never lowered.
    ordered = sorted((s for kind in stages.values() for s in kind), key=lambda s: s["start_ns"])
    assert len({s["attributes"]["thread"] for s in ordered}) == 1
    assert all(a["end_ns"] <= b["start_ns"] for a, b in zip(ordered, ordered[1:]))
    names = [s["name"] for s in ordered]
    lowered = [i for i, name in enumerate(names) if name == "jax.lower"]
    assert all(names[i - 1:i + 2] == list(compile_spans.STAGES) for i in lowered)
    for i in lowered:
        trace, lower, compile_ = ordered[i - 1:i + 2]
        assert lower["attributes"]["fun_name"] == compile_["attributes"]["fun_name"]
        assert lower["attributes"]["fun_name"] == f"jit({trace['attributes']['fun_name']})"
    shapes_only = len(stages["jax.trace"]) - counted
    assert 0 < shapes_only < 6
