"""The readers of the program's boot spans (``harness/startup_spans.py`` and
the four ``layer_metrics`` files on it), each on a hand-written span list,
and one CPU rehearsal through ``run.py`` whose traced result line carries
all four and whose named and unnamed seconds add up to ``setup_s``."""

import glob
import json
import os
import shutil
import subprocess
import sys

import psutil
import pytest

from benchmarks.harness import startup_spans, xplane
from benchmarks.harness.manifest import Manifest
from benchmarks.layer_metrics import (
    driver_boot_s, reach_device_s, setup_coverage_pct, setup_unnamed_s, worker_boot_s,
)
from benchmarks.tests.test_discovery import CASES, ROOT
from benchmarks.tests.test_program_spans import DRIVER, T0, WORKER, run_of, span

NEW = ("reach_device_s", "worker_boot_s", "driver_boot_s", "setup_unnamed_s")
READERS = (reach_device_s, worker_boot_s, driver_boot_s, setup_unnamed_s)
OTHER = 300             # a data task's worker


def spans():
    return [
        span("driver.boot", 0.4, 2.0),
        span("ray_tpu.init", 2.0, 4.0),
        span("worker.boot", 3.5, 5.5, OTHER, imports_s=1.5),
        span("train.fit", 4.0, 60.0, experiment="cell"),
        span("train.form_gang", 4.1, 7.5, world_size=1, attempt=0),
        span("worker.boot", 4.2, 7.0, WORKER, imports_s=2.0),
        span("train.start_sessions", 7.5, 7.6),
        span("train.first_round", 7.6, 30.0),
        span("train.first_report", 7.6, 29.0, WORKER),
        span("train.reach_device", 7.6, 15.6, WORKER, import_s=0.0, platform="tpu",
             devices=1, leased=1),
        span("train.setup_state", 16.0, 20.0, WORKER),
        span("jax.compile", 17.0, 18.0, WORKER, cache="hit", seconds=1.0),
        span("jax.compile", 24.0, 27.0, WORKER, cache="hit", seconds=3.0),
        span("jax.compile", 41.0, 42.0, WORKER, cache="miss", seconds=1.0),
    ]


def test_each_reader_on_a_hand_written_span_list():
    run = run_of(spans())
    assert reach_device_s.read(run) == pytest.approx(8.0)
    assert worker_boot_s.read(run) == pytest.approx(2.8)     # the gang worker's, not pid 300's
    assert driver_boot_s.read(run) == pytest.approx(1.6)
    # 0.4 -> 7.6 without a gap (boot, init, the other worker's boot, the
    # gang), reach_device to 15.6, setup_state 16-20, one compile 24-27
    assert startup_spans.named_s(run) == pytest.approx(7.2 + 8.0 + 4.0 + 3.0)
    assert setup_unnamed_s.read(run) == pytest.approx(40.0 - 22.2)
    # the older share still counts its own six names and nothing else
    assert setup_coverage_pct.read(run) == pytest.approx(100 * (2.0 + 3.5 + 4.0 + 3.0) / 40.0)


@pytest.mark.parametrize("missing, reader", [
    ("train.reach_device", reach_device_s), ("train.first_report", reach_device_s),
    ("worker.boot", worker_boot_s), ("train.first_report", worker_boot_s),
    ("driver.boot", driver_boot_s),
])
def test_a_missing_span_reads_none(missing, reader):
    assert reader.read(run_of([s for s in spans() if s["name"] != missing])) is None


def test_a_program_without_the_boot_spans_reads_none_everywhere():
    """The parent of the PR that added them has every older span, and a
    sum over those alone would be a number: it has to be nothing."""
    older = [s for s in spans() if s["name"] not in startup_spans.BOOTS]
    assert setup_coverage_pct.read(run_of(older)) is not None
    for found in (older, []):
        assert [reader.read(run_of(found)) for reader in READERS] == [None] * 4


def test_the_gang_workers_boot_is_told_by_the_pid_of_the_first_report():
    warm = [dict(s, start_ns=s["start_ns"] - int(3e9), end_ns=s["end_ns"] - int(3e9))
            if s["name"] == "worker.boot" and s["pid"] == WORKER else s for s in spans()]
    # a warm worker from the agent's pool booted before the gang formed
    assert worker_boot_s.read(run_of(warm)) == pytest.approx(2.8)
    assert startup_spans.of_gang_worker(run_of(warm), "worker.boot")["pid"] == WORKER


def test_spans_of_two_processes_that_overlap_count_once():
    found = [span("driver.boot", 0.0, 3.0), span("ray_tpu.init", 3.0, 10.0),
             span("worker.boot", 8.0, 12.0, WORKER), span("train.reach_device", 11.0, 21.0, WORKER),
             span("train.first_report", 11.0, 39.0, WORKER),      # an envelope: not work
             span("train.first_round", 0.0, 40.0)]
    assert setup_unnamed_s.read(run_of(found)) == pytest.approx(40.0 - 21.0)
    # what lies before the process started or after the window is clipped away
    found += [span("worker.boot", -2.0, 1.0, OTHER), span("train.form_gang", 38.0, 50.0)]
    assert setup_unnamed_s.read(run_of(found)) == pytest.approx(40.0 - 23.0)
    assert startup_spans.named_s(run_of(found)) + setup_unnamed_s.read(run_of(found)) == pytest.approx(40.0)


def test_the_manifest_validates_with_the_four_entries_in_it():
    manifest = Manifest(ROOT)
    assert manifest.problems() == []
    entries = {e["name"]: e for e in manifest.data["per_layer"]}
    layers = {"reach_device_s": "Gang worker", "worker_boot_s": "Gang worker",
              "driver_boot_s": "Driver + Cluster", "setup_unnamed_s": "Driver + Cluster"}
    for name in NEW:
        entry = entries[name]
        assert entry == {"name": name, "unit": "s", "better": "lower", "source": "program_span",
                         "layer": layers[name], "moves": "setup_s"}      # every cell: no list
        assert os.path.isfile(os.path.join(ROOT, "benchmarks", "layer_metrics", name + ".py"))


def test_a_traced_rehearsal_reports_all_four_and_the_parts_add_up(tmp_path):
    """``run.py --platform cpu --trace 1`` on a tiny ingest cell: the real
    cluster, trainer, worker and profiler, and the readers on what they
    wrote. Times from a CPU say nothing; that each is there, how they nest
    and that named and unnamed seconds make ``setup_s`` does."""
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
    env.pop("XLA_FLAGS", None)
    env.pop("RAY_TPU_tracing_enabled", None)
    env.pop("RAYTPU_SESSION_DIR", None)
    done = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", "tiny.ingest", "--seed", "2147484051",
         "--seconds", "2", "--trace", "1", "--platform", "cpu"],
        cwd=str(copy), env=env, capture_output=True, text=True, timeout=300,
    )
    assert done.returncode == 0, done.stderr[-3000:]
    out = [json.loads(l) for l in done.stdout.splitlines() if l.startswith("{")]
    line, facts = out[-1], {l["fact"]: l for l in out[:-1]}
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(metrics)
    setup = facts["setup"]

    # The run's spans, read again from its session, and its window: run.py's
    # process_start is psutil's create_time(), which lies before the kernel's
    # own reading by a constant of this machine's boot (this process's too).
    from ray_tpu._private import worker
    from ray_tpu.util import tracing

    (session,) = glob.glob(str(sessions / "raytpu" / "session_*"))
    found = tracing.read_spans(session)
    early_ns = worker.process_start_ns() - psutil.Process().create_time() * 1e9
    assert -0.02e9 <= early_ns < 1.02e9
    (boot,) = [s for s in found if s["name"] == "driver.boot"]
    start_ns = boot["start_ns"] - early_ns
    window = (start_ns, start_ns + setup["setup_s"] * 1e9)
    named = xplane.length(xplane.clip(xplane.merge(
        (s["start_ns"], s["end_ns"]) for s in found if s["name"] in startup_spans.NAMED), window)) / 1e9
    assert named + metrics["setup_unnamed_s"] == pytest.approx(setup["setup_s"], abs=0.1)
    assert 0 < metrics["setup_unnamed_s"] < setup["setup_s"]
    assert 100 * named / setup["setup_s"] > metrics["setup_coverage_pct"]

    # the driver's boot ends where ray_tpu.init starts; the three spans nest
    # in the harness's own marks
    (init,) = [s for s in found if s["name"] == "ray_tpu.init"]
    assert boot["end_ns"] == init["start_ns"] and boot["pid"] == init["pid"]
    assert metrics["driver_boot_s"] == pytest.approx((boot["end_ns"] - boot["start_ns"]) / 1e9)
    (reach,) = [s for s in found if s["name"] == "train.reach_device"]
    assert reach["attributes"]["platform"] == "cpu" and reach["attributes"]["leased"] == 1
    assert 0 < metrics["driver_boot_s"] + metrics["cluster_start_s"] < setup["process_to_worker_s"]
    assert 0 < metrics["reach_device_s"] < setup["process_to_worker_s"]
    # the session reached the device: the loop's own first jax.devices() is a lookup
    assert setup["reach_device_s"] < 0.05
    assert 0 < metrics["worker_boot_s"] < metrics["gang_start_s"]
    # every compile-or-load the harness's listeners counted before the window
    # is a jax.compile span, the two before the loop enters jax_utils too
    worker_pid = reach["pid"]
    compiles = [s for s in found if s["name"] == "jax.compile" and s["pid"] == worker_pid
                and s["end_ns"] <= window[1]]
    assert len(compiles) == setup["cache_hits"] + setup["cache_misses"] > 0
