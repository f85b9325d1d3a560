"""The readers of the program's own spans (``harness/program_spans.py`` and
the eight ``layer_metrics`` files on it), each on a hand-written span list,
and one CPU rehearsal through ``run.py`` whose traced result line carries
all eight."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.harness import program_spans
from benchmarks.harness.manifest import Manifest
from benchmarks.layer_metrics import (
    batch_format_ms, cluster_start_s, gang_start_s, program_build_s,
    programs_built, setup_coverage_pct, shard_batch_ms, state_init_s,
)
from benchmarks.tests.test_discovery import CASES, ROOT

NEW = ("cluster_start_s", "gang_start_s", "state_init_s", "program_build_s",
       "programs_built", "setup_coverage_pct", "batch_format_ms", "shard_batch_ms")
T0 = 1_790_000_000          # epoch seconds of process_start
DRIVER, WORKER = 100, 200


def span(name, start, end, pid=DRIVER, **attributes):
    """Seconds after T0 -> a span as ``tracing.read_spans`` returns it."""
    return {"name": name, "pid": pid, "attributes": attributes,
            "start_ns": int((T0 + start) * 1e9), "end_ns": int((T0 + end) * 1e9)}


def spans():
    return [
        span("ray_tpu.init", 2.0, 4.0),
        span("init.start_controller", 2.0, 2.8),
        span("train.fit", 4.0, 60.0, experiment="cell"),
        span("train.form_gang", 4.1, 6.1, world_size=1, attempt=0),
        span("train.split_datasets", 6.1, 6.2),
        span("train.start_sessions", 6.2, 6.5),
        span("train.first_round", 6.5, 30.0),
        # the worker, on the same clock: its spans overlap the driver's wait
        span("train.first_report", 6.4, 29.0, WORKER),
        span("train.setup_state", 16.0, 20.0, WORKER),
        span("jax.compile", 17.0, 18.0, WORKER, cache="hit", seconds=1.0),
        span("jax.compile", 18.5, 19.5, WORKER, cache="miss", seconds=1.0),
        span("jax.compile", 24.0, 27.0, WORKER, cache="hit", seconds=3.0),
        # inside the window (a recompile): no part of the start-up
        span("jax.compile", 41.0, 42.0, WORKER, cache="miss", seconds=1.0),
        # another process's compile is not the worker's
        span("jax.compile", 5.0, 5.5, DRIVER, cache="miss", seconds=0.5),
    ]


def run_of(found):
    return {"program_spans": found, "process_start": float(T0),
            "facts": {"marks": {"window_start": T0 + 40.0}, "trace": None}}


def test_each_reader_on_a_hand_written_span_list():
    run = run_of(spans())
    assert cluster_start_s.read(run) == pytest.approx(2.0)
    assert gang_start_s.read(run) == pytest.approx(2.4)      # 4.0 -> 6.4
    assert state_init_s.read(run) == pytest.approx(4.0)
    assert program_build_s.read(run) == pytest.approx(5.0)   # 1 + 1 + 3, not the window's
    assert programs_built.read(run) == 1
    # init 2.0-4.0, the gang's three 4.1-6.5, the driver's stray compile
    # inside them, setup_state 16-20 with two compiles inside, one compile
    # 24-27: 2.0 + 2.4 + 4.0 + 3.0 of 40 s, each second once
    assert setup_coverage_pct.read(run) == pytest.approx(100 * 11.4 / 40.0)


@pytest.mark.parametrize("missing, reader", [
    ("ray_tpu.init", cluster_start_s), ("train.fit", gang_start_s),
    ("train.first_report", gang_start_s), ("train.setup_state", state_init_s),
    ("jax.compile", program_build_s), ("jax.compile", programs_built),
    ("train.first_report", programs_built),
])
def test_a_missing_span_reads_none(missing, reader):
    assert reader.read(run_of([s for s in spans() if s["name"] != missing])) is None


def test_a_program_without_spans_reads_none_everywhere():
    """The parent of the PR that added the spans: nothing to read, no raise."""
    for name in NEW:
        module = __import__(f"benchmarks.layer_metrics.{name}", fromlist=["read"])
        assert module.read(run_of([])) is None


def test_a_warm_run_built_nothing():
    hits = [dict(s, attributes=dict(s["attributes"], cache="hit"))
            if s["name"] == "jax.compile" else s for s in spans()]
    assert programs_built.read(run_of(hits)) == 0
    assert program_build_s.read(run_of(hits)) == pytest.approx(5.0)


def test_overlapping_driver_and_worker_spans_count_once():
    found = [span("ray_tpu.init", 0.0, 10.0), span("train.setup_state", 5.0, 15.0, WORKER),
             span("jax.compile", 6.0, 7.0, WORKER, cache="hit"),
             span("train.first_round", 0.0, 40.0)]     # an envelope: not work
    assert setup_coverage_pct.read(run_of(found)) == pytest.approx(100 * 15.0 / 40.0)
    # work before the process started or after the window is clipped away
    found.append(span("train.form_gang", 38.0, 50.0))
    assert setup_coverage_pct.read(run_of(found)) == pytest.approx(100 * 17.0 / 40.0)


def test_the_host_spans_are_medians_over_the_traced_steps():
    run = run_of([])
    run["host_span_ms"] = {"data.next_batch": [0.5, 0.7, 9.0], "data.shard_batch": [0.2, 0.4]}
    assert batch_format_ms.read(run) == pytest.approx(0.7)
    assert shard_batch_ms.read(run) == pytest.approx(0.3)
    assert batch_format_ms.read(run_of([])) is None      # no trace, nothing to read


def test_the_entries_go_last_and_the_manifest_validates():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    entries = manifest.data["per_layer"][-len(NEW):]
    assert tuple(e["name"] for e in entries) == NEW
    assert {e["source"] for e in entries} == {"program_span", "program_counter"}
    assert all(e["moves"] == "setup_s" and "workloads" not in e for e in entries[:6])
    ingest = [w["name"] for w in manifest.data["workloads"] if w["traffic"].endswith("ingest")]
    assert all(e["workloads"] == ingest for e in entries[6:])


def test_a_traced_rehearsal_reports_all_eight(tmp_path):
    """``run.py --platform cpu --trace 1`` on a tiny ingest cell: the real
    cluster, trainer, worker and profiler, and the readers on what they
    wrote. Times from a CPU say nothing; that each is there, and how they
    nest, does."""
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
    for metric in manifest["per_layer"]:
        if metric["name"] in ("data_wait_ms", "batch_format_ms", "shard_batch_ms"):
            metric["workloads"] = metric["workloads"] + ["tiny.ingest"]
    (copy / "BENCHMARK.json").write_text(json.dumps(manifest))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT,
               JAX_COMPILATION_CACHE_DIR=str(copy / ".jax_cache"))
    env.pop("XLA_FLAGS", None)
    env.pop("RAY_TPU_tracing_enabled", None)
    done = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "tiny.ingest", "--seed", "2147483999",
         "--seconds", "2", "--trace", "1", "--platform", "cpu"],
        cwd=str(copy), env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(metrics)
    setup = facts["setup"]
    assert 0 < metrics["cluster_start_s"] + metrics["gang_start_s"] <= setup["process_to_worker_s"]
    assert 0 < metrics["state_init_s"] <= setup["state_s"]
    assert 0 < metrics["program_build_s"] < setup["setup_s"]
    # a first run against an empty cache builds programs, and the watcher
    # saw every compile the benchmark's own listener counted after it
    assert 0 < metrics["programs_built"] <= setup["backend_compiles_in_setup"]
    assert 0 < metrics["setup_coverage_pct"] < 100
    # medians of the five traced steps, under the profiler; data_wait_ms is
    # the median of the untraced ones, so on a CPU neither bounds the other
    assert metrics["batch_format_ms"] > 0 and metrics["shard_batch_ms"] > 0
    assert metrics["data_wait_ms"] > 0
