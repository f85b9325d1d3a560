"""Workload flight recorder (ISSUE 8): StepStats aggregation math under
chaos, MAD straggler detection, MFU agreement with the 6 * params * tokens formula,
goodput bucket accounting, serve latency histograms, the diagnose rule
set, and a live end-to-end run (train -> workload series -> goodput ->
dashboard /api/workload -> `ray_tpu diagnose`).
"""

import asyncio
import json
import time

import pytest

import ray_tpu
from ray_tpu._private import workload
from ray_tpu._private.workload import (
    LatencyHistogram,
    StepStatsAggregator,
    diagnose,
    flops_for_tokens,
    goodput_buckets,
    peak_flops_per_chip,
)


def _rec(step, rank, wall, *, tokens=0.0, flops=0.0, node="", kind=None,
         data_wait=0.0, collective=0.0, checkpoint=0.0, devices=1):
    rec = {
        "step": step,
        "ts": 1000.0 + step + rank * 1e-3,
        "rank": rank,
        "wall_s": wall,
        "data_wait_s": data_wait,
        "collective_s": collective,
        "checkpoint_s": checkpoint,
        "compute_s": max(0.0, wall - data_wait - collective - checkpoint),
        "tokens": tokens,
        "flops": flops,
    }
    if node:
        rec["node_id"] = node
    if kind:
        rec["device_kind"] = kind
        rec["devices"] = devices
    return rec


# ---------------------------------------------------------------------------
# aggregator math + chaos safety
# ---------------------------------------------------------------------------

def test_aggregator_drops_duplicate_and_replayed_records():
    """Chaos can re-deliver whole poll rounds: a replayed step index must
    not double-count tokens or steps (satellite 4)."""
    agg = StepStatsAggregator()
    batch = [_rec(s, r, 1.0, tokens=50.0) for s in range(4) for r in range(2)]
    assert all(agg.add(rec) for rec in batch)
    # Exact duplicate round + partial replay: all dropped.
    assert not any(agg.add(rec) for rec in batch)
    assert not agg.add(_rec(2, 0, 1.0, tokens=50.0))
    summary = agg.summary()
    assert summary["steps"] == 4
    assert summary["records"] == 8
    assert summary["dropped_stale"] == 9
    # tokens/s unchanged by the replay: 8 * 50 tokens over 4 s gang wall.
    assert summary["tokens_per_s"] == pytest.approx(100.0)


def test_aggregator_clamps_negative_durations():
    """A clock step backwards mid-run must never produce negative phase
    durations or negative throughput (satellite 4)."""
    agg = StepStatsAggregator()
    agg.add(_rec(0, 0, 1.0, tokens=10.0))
    bad = _rec(1, 0, -5.0, tokens=10.0)
    bad["data_wait_s"] = -1.0
    assert agg.add(bad)
    summary = agg.summary()
    assert summary["clamped_negative"] == 2
    assert summary["tokens_per_s"] >= 0.0
    for frac in ("data_wait_frac", "compute_frac", "collective_frac",
                 "checkpoint_frac"):
        assert summary[frac] >= 0.0


def test_aggregator_window_bounds_memory():
    agg = StepStatsAggregator(window=8)
    for step in range(1000):
        agg.add(_rec(step, 0, 1.0))
    assert len(agg._by_step) == 8
    assert agg.summary()["steps"] == 1000
    assert agg.summary()["window_steps"] == 8


def test_phase_fractions_sum_to_one():
    agg = StepStatsAggregator()
    for step in range(10):
        agg.add(_rec(step, 0, 2.0, data_wait=0.5, collective=0.3,
                     checkpoint=0.2))
    s = agg.summary()
    total = (s["data_wait_frac"] + s["compute_frac"] + s["collective_frac"]
             + s["checkpoint_frac"])
    assert total == pytest.approx(1.0)
    assert s["data_wait_frac"] == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# straggler detection
# ---------------------------------------------------------------------------

def test_straggler_detector_names_injected_slow_rank():
    """Deterministic acceptance case: rank 2 runs 1.8x the gang median on
    a slow node; the detector must name exactly that rank and node."""
    agg = StepStatsAggregator()
    for step in range(12):
        for rank in range(4):
            wall = 1.8 if rank == 2 else 1.0
            agg.add(_rec(step, rank, wall, node=f"node-{rank % 2}"))
    report = agg.straggler_report()
    assert [s["rank"] for s in report] == [2]
    assert report[0]["node_id"] == "node-0"
    assert report[0]["flagged_steps"] == 12
    assert report[0]["excess_ratio"] == pytest.approx(1.8, rel=0.01)


def test_straggler_detector_quiet_on_uniform_gang_and_noise():
    # Uniform gang with float jitter: the MAD floor (2% of median) must
    # keep it silent.
    agg = StepStatsAggregator()
    for step in range(16):
        for rank in range(4):
            agg.add(_rec(step, rank, 1.0 + 1e-4 * ((step + rank) % 3)))
    assert agg.straggler_report() == []
    # One slow step is noise, not a straggler (persistence threshold).
    agg2 = StepStatsAggregator()
    for step in range(16):
        for rank in range(4):
            wall = 3.0 if (rank == 1 and step == 7) else 1.0
            agg2.add(_rec(step, rank, wall))
    assert agg2.straggler_report() == []


def test_straggler_detector_needs_min_multi_rank_steps():
    agg = StepStatsAggregator()
    for step in range(4):  # < min_steps
        for rank in range(2):
            agg.add(_rec(step, rank, 5.0 if rank else 1.0))
    assert agg.straggler_report(min_steps=8) == []


# ---------------------------------------------------------------------------
# MFU / tokens-per-s vs the 6 * params * tokens formula (within 2%)
# ---------------------------------------------------------------------------

def test_peaks_table_matches_benchmark_peaks_file():
    """The program keeps one peak table; for every device kind the
    benchmark's own table lists, the two agree."""
    import os

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(repo, "benchmarks", "harness", "peaks.json")) as f:
        peaks = json.load(f)
    kinds = [k for k in peaks if not k.startswith("_")]
    assert kinds
    for kind in kinds:
        assert workload.PEAK_FLOPS_BY_KIND[kind] == peaks[kind]["bf16_flops_per_s"]
    assert peak_flops_per_chip("TPU v5p slice") == 459e12
    assert peak_flops_per_chip("TPU v6 lite x4") == 918e12
    assert peak_flops_per_chip("cpu") is None
    assert peak_flops_per_chip(None) is None


def test_mfu_agrees_with_formula_within_2pct():
    """Feed the aggregator one gang's step records; the in-framework MFU
    must match 6*p*tokens_per_s/peak within 2%."""
    params = 124_000_000
    tokens_per_step = 8 * 2048.0
    step_wall = 0.5
    agg = StepStatsAggregator()
    for step in range(20):
        agg.add(_rec(
            step, 0, step_wall,
            tokens=tokens_per_step,
            flops=flops_for_tokens(params, tokens_per_step),
            kind="TPU v4", devices=4,
        ))
    summary = agg.summary()
    tokens_per_s = tokens_per_step / step_wall
    expected_mfu = (6.0 * params * tokens_per_s) / (275e12 * 4)
    assert summary["tokens_per_s"] == pytest.approx(tokens_per_s, rel=0.02)
    assert summary["mfu"] == pytest.approx(expected_mfu, rel=0.02)
    # Unknown chip kind: MFU is absent, never wrong.
    agg2 = StepStatsAggregator()
    agg2.add(_rec(0, 0, 1.0, tokens=100.0, flops=1e12))
    assert agg2.summary()["mfu"] is None


# ---------------------------------------------------------------------------
# goodput buckets
# ---------------------------------------------------------------------------

def test_goodput_buckets_sum_to_wall_exactly():
    for wall, ckpt, restart, stalled in [
        (100.0, 5.0, 11.0, 3.0),
        (100.0, 0.0, 0.0, 0.0),
        (10.0, 4.0, 4.0, 4.0),    # over-subscribed: clamped in order
        (0.0, 1.0, 1.0, 1.0),
        (7.3, 0.1, 0.0, 9.9),
    ]:
        g = goodput_buckets(wall, ckpt, restart, stalled)
        total = (g["productive_s"] + g["checkpoint_s"] + g["restart_s"]
                 + g["stalled_s"])
        assert total == pytest.approx(g["wall_s"], abs=1e-9)
        assert all(v >= 0 for k, v in g.items() if k.endswith("_s"))
        assert 0.0 <= g["goodput_fraction"] <= 1.0
    g = goodput_buckets(100.0, 5.0, 11.0, 3.0)
    assert g["productive_s"] == pytest.approx(81.0)
    assert g["goodput_fraction"] == pytest.approx(0.81)


# ---------------------------------------------------------------------------
# latency histogram
# ---------------------------------------------------------------------------

def test_latency_histogram_percentiles_and_bounds():
    hist = LatencyHistogram()
    assert hist.snapshot()["p99_ms"] == 0.0
    for _ in range(95):
        hist.observe(0.010)
    for _ in range(5):
        hist.observe(0.800)
    snap = hist.snapshot()
    assert snap["count"] == 100
    # Log-bucketed: percentile lands in the right decade, not exact.
    assert 8.0 <= snap["p50_ms"] <= 20.0
    assert snap["p99_ms"] >= 500.0
    assert snap["max_ms"] == pytest.approx(800.0)
    assert snap["mean_ms"] == pytest.approx(1e3 * (95 * 0.01 + 5 * 0.8) / 100)
    # Memory is fixed regardless of volume; negatives clamp.
    hist.observe(-5.0)
    assert len(hist.counts) == len(LatencyHistogram._BOUNDS) + 1
    # Beyond the last bound lands in the overflow bucket.
    hist.observe(120.0)
    assert hist.counts[-1] == 1


# ---------------------------------------------------------------------------
# diagnose rule set (pure snapshot -> findings)
# ---------------------------------------------------------------------------

def _snapshot(**over):
    snap = {
        "latency": {},
        "comm": {},
        "resources": {"nodes": {}},
        "goodput": {"runs": {}},
        "workload": {"series": {}},
        "rank_records": {},
    }
    snap.update(over)
    return snap


def test_diagnose_empty_snapshot_returns_no_data():
    findings = diagnose(_snapshot())
    assert len(findings) == 1
    assert findings[0]["kind"] == "no_data"
    assert findings[0]["severity"] == "info"


def test_diagnose_flags_data_bound_run():
    snap = _snapshot(workload={"series": {
        "train/exp1": {"latest": {
            "data_wait_frac": 0.41, "compute_frac": 0.5,
            "collective_frac": 0.05, "checkpoint_frac": 0.04,
            "tokens_per_s": 1234.0, "mfu": None,
        }},
    }})
    findings = diagnose(snap)
    kinds = [f["kind"] for f in findings]
    assert "data_bound" in kinds
    f = findings[kinds.index("data_bound")]
    assert "41%" in f["message"] and "data-wait" in f["message"]
    assert f["severity"] == "warn"


def test_diagnose_straggler_names_saturated_node():
    records = []
    for step in range(12):
        for rank in range(4):
            records.append(_rec(
                step, rank, 2.0 if rank == 3 else 1.0,
                node="node-2-full-id" if rank == 3 else "node-1-full-id",
            ))
    snap = _snapshot(
        rank_records={"exp1": records},
        resources={"nodes": {
            "node-2-full-id": {"latest": {"cpu_percent": 97.0}},
        }},
    )
    findings = diagnose(snap)
    straggler = next(f for f in findings if f["kind"] == "straggler")
    assert straggler["severity"] == "crit"
    assert "rank 3" in straggler["message"]
    assert "CPU saturated" in straggler["message"]
    # crit sorts above info findings.
    assert findings[0]["kind"] == "straggler"


def test_diagnose_goodput_and_serve_rules():
    snap = _snapshot(
        goodput={"runs": {"exp1": goodput_buckets(100.0, 2.0, 11.0, 4.0)}},
        workload={"series": {
            "serve/app_model": {"latest": {
                "p50_ms": 40.0, "p99_ms": 612.0, "qps": 12.0,
                "errors": 3.0, "count": 500,
            }},
        }},
    )
    findings = diagnose(snap)
    kinds = {f["kind"] for f in findings}
    assert {"goodput", "serve_slo", "serve_errors"} <= kinds
    good = next(f for f in findings if f["kind"] == "goodput")
    assert "83%" in good["message"] and "restart" in good["message"]
    slo = next(f for f in findings if f["kind"] == "serve_slo")
    assert "612" in slo["message"]
    # Healthy goodput is an info line, not a warning.
    healthy = diagnose(_snapshot(
        goodput={"runs": {"exp2": goodput_buckets(100.0, 1.0, 1.0, 0.0)}},
    ))
    g = next(f for f in healthy if f["kind"] == "goodput")
    assert g["severity"] == "info"


def test_diagnose_findings_ranked_by_score():
    snap = _snapshot(workload={"series": {
        "train/a": {"latest": {"data_wait_frac": 0.9, "tokens_per_s": 1.0}},
        "train/b": {"latest": {"data_wait_frac": 0.3, "tokens_per_s": 1.0}},
    }})
    findings = [f for f in diagnose(snap) if f["kind"] == "data_bound"]
    assert len(findings) == 2
    assert findings[0]["data"]["experiment"] == "a"
    scores = [f["score"] for f in diagnose(snap)]
    assert scores == sorted(scores, reverse=True)


# ---------------------------------------------------------------------------
# serve-side pieces without a cluster: replica histogram + batching stats
# ---------------------------------------------------------------------------

def test_replica_metrics_histogram_and_queue_gauges():
    from ray_tpu.serve._private.replica import Replica

    class Model:
        def __call__(self, x):
            return x * 2

    replica = Replica("r1", "dep", Model, (), {}, None, "v1")

    async def run():
        for i in range(20):
            assert await replica.handle_request({}, (i,), {}) == i * 2

    asyncio.run(run())
    metrics = replica.get_metrics()
    assert metrics["total"] == 20
    for key in ("p50_ms", "p95_ms", "p99_ms", "queue_depth",
                "batch_occupancy", "rss_bytes"):
        assert key in metrics
    assert metrics["p50_ms"] >= 0.0
    assert metrics["p95_ms"] >= metrics["p50_ms"] - 1e-9
    assert metrics["ongoing"] == 0


def test_batching_occupancy_tracks_bucket_padding():
    from ray_tpu.serve import batching

    @batching.batch(max_batch_size=4, batch_wait_timeout_s=0.01,
                    bucket_sizes=[8])
    async def infer(items):
        return [x + 1 for x in items]

    async def run():
        return await asyncio.gather(*(infer(i) for i in range(4)))

    assert asyncio.run(run()) == [1, 2, 3, 4]
    stats = batching.queue_stats()
    assert stats["batches"] >= 1
    # 4 real items padded to the 8-bucket: occupancy ~0.5 for a full
    # flush (timeout flushes may split it, so bound rather than pin).
    assert stats["items_padded"] >= stats["items_real"]
    assert stats["batch_occupancy"] is not None
    assert 0.0 < stats["batch_occupancy"] <= 1.0


# ---------------------------------------------------------------------------
# workload series through the telemetry store (controller side)
# ---------------------------------------------------------------------------

def test_workload_store_monotonic_and_bounded():
    from ray_tpu._private.telemetry import TelemetryStore

    store = TelemetryStore(raw_capacity=16, max_workload_series=3)
    batch = [{"ts": 100.0 + i, "tokens_per_s": 10.0 * i} for i in range(5)]
    assert store.add_workload_many("train/exp", batch) == 5
    # Replay (chaos / driver retry): all dropped, counters move.
    assert store.add_workload_many("train/exp", batch) == 0
    assert store.workload_timeline("train/exp", "raw")["raw"][-1][
        "tokens_per_s"] == 40.0
    # Series cap: the 4th distinct key is refused, not unbounded.
    for i in range(5):
        store.add_workload(f"serve/route{i}", {"ts": 1.0})
    stats = store.stats()
    assert stats["workload_series"] == 3
    assert stats["workload_ingested"] == 5 + 2
    assert stats["workload_dropped"] >= 3 + 5
    # Malformed keys/samples are counted drops, not exceptions.
    assert not store.add_workload("", {"ts": 1.0})
    assert not store.add_workload("k", "not-a-dict")
    assert store.workload_timeline("unknown/key") == {}
    summary = store.workload_summary()
    assert "train/exp" in summary["series"]
    assert summary["series"]["train/exp"]["latest"]["tokens_per_s"] == 40.0


# ---------------------------------------------------------------------------
# live end-to-end: train run -> series -> goodput -> dashboard -> diagnose
# ---------------------------------------------------------------------------

def _poll(fn, timeout=30.0, period=0.25):
    deadline = time.time() + timeout
    value = fn()
    while not value and time.time() < deadline:
        time.sleep(period)
        value = fn()
    return value


def _token_loop(config):
    from ray_tpu import train

    for step in range(config["steps"]):
        time.sleep(0.02)
        train.report({
            "step": step,
            "tokens": 1000.0,
            "flops": 6.0 * 1e6 * 1000.0,
        })


@pytest.fixture()
def workload_cluster():
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=8)
    yield
    ray_tpu.shutdown()


def test_live_flight_recorder_end_to_end(workload_cluster, tmp_path):
    from ray_tpu import scripts
    from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
    from ray_tpu.util import state

    # Fresh cluster, nothing trained yet: every summary degrades to an
    # empty structure, never an exception (satellite 1).
    assert state.summarize_goodput() == {"runs": {}}
    assert state.summarize_workload()["series"] == {}
    assert isinstance(state.summarize_latency(), dict)
    assert isinstance(state.summarize_comm(), dict)
    assert state.get_workload_timeline("train/nothing") == {}

    wall_t0 = time.monotonic()
    trainer = JaxTrainer(
        _token_loop,
        train_loop_config={"steps": 12},
        scaling_config=ScalingConfig(num_workers=2),
        run_config=RunConfig(name="flight", storage_path=str(tmp_path)),
    )
    result = trainer.fit()
    wall = time.monotonic() - wall_t0
    assert result.error is None

    # Result.goodput: buckets sum to wall within 1% (acceptance), and the
    # recorder's wall clock matches the fit() wall clock.
    g = result.goodput
    total = (g["productive_s"] + g["checkpoint_s"] + g["restart_s"]
             + g["stalled_s"])
    assert total == pytest.approx(g["wall_s"], rel=0.01)
    assert g["wall_s"] == pytest.approx(wall, rel=0.25, abs=1.0)
    assert g["productive_s"] > 0

    # tokens/s + per-rank series reached the controller workload store.
    def series_ready():
        s = state.summarize_workload()["series"]
        return s if "train/flight" in s and "train/flight/goodput" in s \
            else None

    series = _poll(series_ready, timeout=20)
    assert series, f"workload series never landed: "\
        f"{sorted(state.summarize_workload()['series'])}"
    gang_latest = series["train/flight"]["latest"]
    assert gang_latest["tokens_per_s"] > 0
    assert gang_latest["world_size"] == 2
    rank_keys = [k for k in series if k.startswith("train/flight/rank")]
    assert len(rank_keys) == 2
    rank_tl = state.get_workload_timeline(rank_keys[0], "raw")["raw"]
    assert all(
        rec["wall_s"] >= rec["data_wait_s"] + rec["collective_s"]
        + rec["checkpoint_s"] - 1e-6 for rec in rank_tl
    )
    # tokens/s surfaced into the user-visible metrics stream too.
    assert result.metrics.get("tokens_per_s", 0) > 0

    runs = state.summarize_goodput()["runs"]
    assert "flight" in runs
    assert runs["flight"]["goodput_fraction"] == pytest.approx(
        g["goodput_fraction"], abs=0.05
    )

    # diagnose over the live snapshot: well-formed ranked findings.
    snapshot = state.collect_diagnose_snapshot()
    assert "flight" in snapshot["rank_records"]
    findings = workload.diagnose(snapshot)
    assert findings
    for f in findings:
        assert f["severity"] in ("crit", "warn", "info")
        assert f["kind"] and f["message"]
        assert isinstance(f["score"], float)
    scores = [f["score"] for f in findings]
    assert scores == sorted(scores, reverse=True)

    # Dashboard: /api/workload 200, unknown key/tier/node -> 404 JSON.
    import urllib.error
    import urllib.request

    from ray_tpu.dashboard.head import DashboardHead

    dash = DashboardHead(port=0)
    try:
        base = f"http://127.0.0.1:{dash.bound_port}"

        def get(path):
            try:
                with urllib.request.urlopen(base + path, timeout=30) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as exc:
                return exc.code, json.loads(exc.read())

        status, body = get("/api/workload")
        assert status == 200 and "train/flight" in body["series"]
        status, body = get("/api/workload?key=train%2Fflight&tier=raw")
        assert status == 200 and body["raw"]
        status, body = get("/api/workload?key=train%2Fnope")
        assert status == 404 and "error" in body
        status, body = get("/api/workload?key=train%2Fflight&tier=bogus")
        assert status == 404 and "error" in body
        status, body = get("/api/timeseries?node_id=not-a-node")
        assert status == 404 and "error" in body
        status, body = get("/api/timeseries?node_id=x&tier=bogus")
        assert status == 404 and "error" in body
    finally:
        dash.stop()

    # CLI surfaces (already connected; bypass _connect).
    import unittest.mock

    with unittest.mock.patch.object(scripts, "_connect"):
        import io
        from contextlib import redirect_stdout

        buf = io.StringIO()
        with redirect_stdout(buf):
            scripts.main(["diagnose", "--json"])
        payload = json.loads(buf.getvalue())
        assert payload["findings"]
        assert all("message" in f for f in payload["findings"])

        buf = io.StringIO()
        with redirect_stdout(buf):
            scripts.main(["diagnose"])
        text = buf.getvalue()
        assert "finding(s)" in text

        buf = io.StringIO()
        with redirect_stdout(buf):
            scripts.main(["top", "--json"])
        top = json.loads(buf.getvalue())
        assert "resources" in top and "workload" in top
        assert "train/flight" in top["workload"]["series"]
        assert "flight" in top["goodput"]["runs"]


def test_chaos_duplicated_rounds_do_not_double_count(monkeypatch, tmp_path):
    """Dup/replay RPC chaos on the driver<->controller channel: workload
    series must stay ts-monotonic and step counts exact (satellite 4)."""
    from ray_tpu._private import chaos as chaos_core

    monkeypatch.setenv("RAY_TPU_chaos", json.dumps({
        "seed": 1234,
        "dup_request": 0.25,
        "dup_reply": 0.15,
    }))
    chaos_core.reset()
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=4)
    try:
        from ray_tpu.train import JaxTrainer, RunConfig, ScalingConfig
        from ray_tpu.util import state

        trainer = JaxTrainer(
            _token_loop,
            train_loop_config={"steps": 10},
            scaling_config=ScalingConfig(num_workers=2),
            run_config=RunConfig(name="chaosrun", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.error is None
        g = result.goodput
        total = (g["productive_s"] + g["checkpoint_s"] + g["restart_s"]
                 + g["stalled_s"])
        assert total == pytest.approx(g["wall_s"], rel=0.01)
        assert all(v >= 0 for k, v in g.items() if k.endswith("_s"))

        def landed():
            series = state.summarize_workload()["series"]
            return series if "train/chaosrun" in series else None

        series = _poll(landed, timeout=20)
        assert series, "workload series lost under chaos"
        for key in series:
            if not key.startswith("train/chaosrun"):
                continue
            tl = state.get_workload_timeline(key, "raw").get("raw") or []
            ts = [p["ts"] for p in tl]
            assert ts == sorted(set(ts)), f"{key} not strictly monotonic"
        rank0 = state.get_workload_timeline(
            "train/chaosrun/rank0", "raw").get("raw") or []
        steps = [p["step"] for p in rank0]
        assert steps == sorted(set(steps)), "duplicated steps double-counted"
    finally:
        ray_tpu.shutdown()
        monkeypatch.delenv("RAY_TPU_chaos", raising=False)
        chaos_core.reset()
