"""Resource telemetry (ISSUE 5): tiered ring-buffer store math, the
end-to-end sampler → heartbeat → controller path, per-task resource
attribution, the trend-aware ``oom_risk`` early warning, and a chaos run
(dup/drop RPC frames) proving the time-series store stays monotonic and
bounded.
"""

import asyncio
import json
import os
import time

import pytest

import ray_tpu
from ray_tpu._private import chaos as chaos_core
from ray_tpu._private.telemetry import TelemetryStore, project_rss


# ---------------------------------------------------------------------------
# store math (pure, no cluster)
# ---------------------------------------------------------------------------

def _sample(ts: float, **fields) -> dict:
    out = {"ts": ts, "cpu_percent": 10.0, "mem_used": 100}
    out.update(fields)
    return out


def test_downsampling_tier_boundaries():
    """1 Hz samples over 125 s: the 10s tier closes one bucket per full
    10 s of data, the 60s tier one per minute; the trailing open buckets
    surface as ``partial`` in the timeline."""
    store = TelemetryStore(raw_capacity=1000, cap_10s=100, cap_60s=100)
    t0 = 1200.0  # aligned on both bucket widths (1200 % 10 == 1200 % 60 == 0)
    n = 125
    for i in range(n):
        assert store.add("n1", _sample(t0 + i))
    tl = store.timeline("n1")
    closed_10s = [b for b in tl["10s"] if not b.get("partial")]
    closed_60s = [b for b in tl["60s"] if not b.get("partial")]
    # Samples at t0..t0+124 span buckets [1200,1210).. — the bucket
    # holding t0+124 is still open, so 12 closed 10s and 2 closed 60s.
    assert len(closed_10s) == 12
    assert len(closed_60s) == 2
    assert tl["10s"][-1].get("partial") and tl["60s"][-1].get("partial")
    assert len(tl["raw"]) == n
    # Bucket boundaries are aligned to the tier width.
    assert [b["bucket_start"] for b in closed_10s] == [
        1200.0 + 10 * i for i in range(12)
    ]
    assert all(b["samples"] == 10 for b in closed_10s)
    assert all(b["samples"] == 60 for b in closed_60s)


def test_downsampling_aggregation_mean_vs_max():
    """Rate-like fields average inside a bucket; footprint fields keep
    the in-bucket peak (a 1-sample RSS spike must survive downsampling)."""
    store = TelemetryStore()
    t0 = 2000.0
    for i in range(10):
        store.add(
            "n1",
            _sample(
                t0 + i,
                cpu_percent=float(i),          # mean field: 0..9 -> 4.5
                mem_used=(1 << 20) * (i + 1),  # max field: 10 MiB
            ),
        )
    store.add("n1", _sample(t0 + 10))  # closes the first 10s bucket
    closed = [b for b in store.timeline("n1", "10s")["10s"]
              if not b.get("partial")]
    assert len(closed) == 1
    assert closed[0]["cpu_percent"] == pytest.approx(4.5)
    assert closed[0]["mem_used"] == 10 * (1 << 20)


def test_ring_eviction_keeps_store_bounded():
    store = TelemetryStore(raw_capacity=16, cap_10s=4, cap_60s=2)
    t0 = 3000.0
    for i in range(1000):
        store.add("n1", _sample(t0 + i))
    tl = store.timeline("n1")
    assert len(tl["raw"]) == 16
    # +1 for the trailing partial bucket each.
    assert len(tl["10s"]) <= 5 and len(tl["60s"]) <= 3
    stats = store.stats()
    assert stats["telemetry_ingested"] == 1000
    assert stats["telemetry_points"] <= 16 + 4 + 2
    # Eviction keeps the NEWEST data.
    assert tl["raw"][-1]["ts"] == t0 + 999


def test_monotonic_guard_drops_dup_and_replayed_samples():
    """Chaos can duplicate or replay whole heartbeat payloads; the store
    must stay strictly monotonic per node and count the drops."""
    store = TelemetryStore()
    batch = [_sample(100.0 + i) for i in range(5)]
    assert store.add_many("n1", batch) == 5
    assert store.add_many("n1", batch) == 0          # exact duplicate
    assert store.add_many("n1", batch[2:4]) == 0     # partial replay
    assert not store.add("n1", _sample(104.0))       # equal ts
    assert store.add("n1", _sample(105.0))           # fresh advances
    raw = store.timeline("n1", "raw")["raw"]
    ts = [s["ts"] for s in raw]
    assert ts == sorted(set(ts))
    assert store.total_dropped == 8
    assert store.stats()["telemetry_dropped"] == 8


def test_store_rejects_malformed_and_isolates_nodes():
    store = TelemetryStore()
    assert not store.add("n1", {"cpu_percent": 1.0})      # no ts
    assert not store.add("n1", {"ts": "yesterday"})       # non-numeric
    store.add("n1", _sample(10.0))
    store.add("n2", _sample(5.0))  # older than n1's clock: separate node
    assert store.node_ids() == ["n1", "n2"]
    assert store.timeline("n2", "raw")["raw"][0]["ts"] == 5.0
    store.forget("n1")
    assert store.node_ids() == ["n2"]


def test_workload_series_tiered_and_guarded_like_node_series():
    """The flight-recorder workload series (ISSUE 8) ride the same tiered
    rings + ts-monotonic guard as node telemetry, keyed by series name."""
    store = TelemetryStore(raw_capacity=16, cap_10s=4, cap_60s=2)
    t0 = 5000.0
    batch = [{"ts": t0 + i, "tokens_per_s": 100.0 + i} for i in range(30)]
    assert store.add_workload_many("train/exp", batch) == 30
    assert store.add_workload_many("train/exp", batch) == 0  # replay
    tl = store.workload_timeline("train/exp")
    assert len(tl["raw"]) == 16  # bounded, newest kept
    assert tl["raw"][-1]["tokens_per_s"] == 129.0
    ts = [p["ts"] for p in tl["raw"]]
    assert ts == sorted(set(ts))
    # Downsampling applies to workload series too.
    assert any(not b.get("partial") for b in tl["10s"])
    stats = store.stats()
    assert stats["workload_series"] == 1
    assert stats["workload_ingested"] == 30
    assert stats["workload_dropped"] == 30
    assert stats["workload_points"] <= 16 + 4 + 2
    # Node counters are untouched by workload traffic.
    assert stats["telemetry_ingested"] == 0
    assert store.workload_keys() == ["train/exp"]
    assert store.workload_summary()["series"]["train/exp"]["latest"][
        "tokens_per_s"] == 129.0


def test_project_rss_slope_math():
    # 10 MB/s ramp: projection 10 s out lands ~100 MB above the last point.
    hist = [(float(t), 10e6 * t) for t in range(5)]
    proj = project_rss(hist, 10.0)
    assert proj == pytest.approx(10e6 * 4 + 10e6 * 10, rel=1e-6)
    # Flat history projects no growth.
    flat = [(float(t), 5e6) for t in range(5)]
    assert project_rss(flat, 10.0) == pytest.approx(5e6)
    # Too little data -> None (a 2-point slope is noise).
    assert project_rss(hist[:2], 10.0) is None
    assert project_rss([(1.0, 5.0), (1.0, 6.0), (1.0, 7.0)], 10.0) is None


# ---------------------------------------------------------------------------
# live cluster: sampler -> heartbeat -> store -> state API
# ---------------------------------------------------------------------------

@pytest.fixture()
def telemetry_cluster(monkeypatch):
    # Env before init: agent/worker processes inherit it.
    monkeypatch.setenv("RAY_TPU_telemetry_sample_interval_s", "0.3")
    monkeypatch.setenv("RAY_TPU_memory_monitor_interval_s", "0.1")
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=4)
    yield
    ray_tpu.shutdown()


def _poll(fn, timeout=30.0, period=0.25):
    deadline = time.time() + timeout
    value = fn()
    while not value and time.time() < deadline:
        time.sleep(period)
        value = fn()
    return value


def test_live_samples_reach_summary_and_timeline(telemetry_cluster):
    from ray_tpu.util import state

    @ray_tpu.remote
    def noop():
        return 1

    assert ray_tpu.get([noop.remote() for _ in range(8)], timeout=60) == [1] * 8

    def ready():
        summary = state.summarize_resources()
        nodes = summary.get("nodes") or {}
        return nodes if any(
            (e.get("points") or {}).get("raw", 0) >= 2 for e in nodes.values()
        ) else None

    nodes = _poll(ready)
    assert nodes, "no telemetry samples reached the controller"
    node_id, entry = next(iter(nodes.items()))
    assert entry["alive"]
    latest = entry["latest"]
    for field in ("ts", "cpu_percent", "mem_used", "mem_total",
                  "workers_rss_total", "object_store_bytes"):
        assert field in latest, f"sample missing {field}: {latest}"
    assert latest["mem_total"] > latest["mem_used"] > 0
    # Workers exist and report real RSS.
    assert latest["num_workers"] >= 1
    assert latest["workers_rss_max"] > 1 << 20
    tl = state.get_node_timeline(node_id)
    assert {"raw", "10s", "60s"} <= set(tl)
    assert len(tl["raw"]) >= 2
    # Open buckets surface as trailing partials, so coarser tiers are
    # non-empty well before a full bucket width elapses.
    assert tl["10s"] and tl["60s"]
    single = state.get_node_timeline(node_id, "raw")
    assert set(single) == {"raw"}
    # /metrics exposition renders the current sample set.
    from ray_tpu.util import metrics as metrics_mod

    text = metrics_mod.collect_prometheus_text()
    assert "ray_tpu_node_cpu_percent" in text
    assert "ray_tpu_worker_rss_bytes" in text


def test_per_task_rss_attribution(telemetry_cluster):
    from ray_tpu.util import state

    @ray_tpu.remote
    def eat(mb):
        # ru_maxrss is a high-water mark, and a worker's start-up peak can
        # stand far above what it holds now: fill that gap too, so that the
        # ballast raises the mark by its own size whatever the start was.
        import resource

        import psutil

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss << 10
        gap = max(0, peak - psutil.Process().memory_info().rss)
        ballast = b"x" * (gap + (mb << 20))  # touched pages
        return len(ballast) - gap

    assert ray_tpu.get(eat.remote(192), timeout=60) == 192 << 20

    def attributed():
        # The worker is idle from here on: its batched events come of
        # themselves, with no later task to push them out.
        rows = [r for r in state.summarize_task_memory()
                if r.get("name") == "eat"]
        return rows or None

    rows = _poll(attributed)
    assert rows, "eat task never showed up with attribution"
    row = rows[0]
    assert row["state"] == "FINISHED"
    # 192 MiB of touched pages above the old mark (less what the worker
    # freed meanwhile) must raise it by well over 64 MiB.
    assert row["rss_delta"] >= 64 << 20
    assert row["peak_rss"] >= row["rss_delta"]
    # The ranking helper puts the hog first.
    assert state.summarize_task_memory()[0]["name"] == "eat"


def test_oom_risk_event_fires_before_kill(monkeypatch):
    """A worker ramping toward the limit (but never crossing it) emits
    the structured oom_risk event + metric, and is NOT killed."""
    monkeypatch.setenv("RAY_TPU_memory_worker_rss_limit_mb", "400")
    monkeypatch.setenv("RAY_TPU_memory_monitor_interval_s", "0.1")
    monkeypatch.setenv("RAY_TPU_oom_risk_horizon_s", "15")
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu._private import worker as worker_mod
        from ray_tpu._private.event_export import read_events
        from ray_tpu.util import state

        session_dir = worker_mod._local_cluster.session_dir

        @ray_tpu.remote(max_retries=0)
        def ramp():
            # ~25 MB/s toward ~250 MB: the slope projects past 400 MiB
            # within the 15 s horizon long before RSS approaches it.
            chunks = []
            for _ in range(10):
                block = bytearray(25 << 20)
                block[::4096] = b"x" * len(block[::4096])
                chunks.append(block)
                time.sleep(1.0)
            return sum(len(c) for c in chunks)

        # Completes: the early warning must never kill the worker itself.
        assert ray_tpu.get(ramp.remote(), timeout=120) == 250 << 20

        def risk_seen():
            stats = state._call("controller_stats")
            return (stats["counters"].get("oom_risk_events") or 0) >= 1

        assert _poll(risk_seen, timeout=20), "no oom_risk event recorded"
        events = _poll(
            lambda: read_events(session_dir, "oom_risk") or None, timeout=20
        )
        assert events, "oom_risk not exported to events_oom_risk.jsonl"
        data = events[-1]["data"]
        assert data["projected_rss"] >= 400 << 20
        assert data["rss"] < 400 << 20
        assert data["worker_id"] and data["node_id"]
    finally:
        ray_tpu.shutdown()


def test_chaos_dup_drop_heartbeats_store_monotonic_and_bounded(monkeypatch):
    """Seeded dup/drop RPC chaos on the agent<->controller channel: the
    telemetry store must stay strictly monotonic per node (replayed
    heartbeats dedup) and bounded, while still ingesting fresh samples."""
    monkeypatch.setenv("RAY_TPU_telemetry_sample_interval_s", "0.2")
    monkeypatch.setenv("RAY_TPU_memory_monitor_interval_s", "0.1")
    monkeypatch.setenv("RAY_TPU_chaos", json.dumps({
        "seed": 777,
        "drop_request": 0.05,
        "dup_request": 0.25,
        "dup_reply": 0.15,
    }))
    chaos_core.reset()
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=2)
    try:
        from ray_tpu.util import state

        @ray_tpu.remote
        def spin(i):
            return i * 2

        for _ in range(3):
            assert ray_tpu.get(
                [spin.remote(i) for i in range(10)], timeout=120
            ) == [i * 2 for i in range(10)]
            time.sleep(0.5)

        def sampled():
            s = state.summarize_resources()
            return s if s.get("total_ingested", 0) >= 3 else None

        # 0.2 s samples through 5 % drop: seconds alone, longer with five
        # other pytest workers each running a cluster of their own.
        summary = _poll(sampled, timeout=120)
        assert summary, "telemetry never flowed under chaos"
        cfg_caps = 360 + 360 + 1440
        for node_id in summary["nodes"]:
            tl = state.get_node_timeline(node_id)
            ts = [p["ts"] for p in tl["raw"]]
            assert ts == sorted(set(ts)), "raw series not strictly monotonic"
        stats = state._call("controller_stats")["telemetry"]
        assert stats["telemetry_points"] <= cfg_caps * len(summary["nodes"])
    finally:
        ray_tpu.shutdown()
        monkeypatch.delenv("RAY_TPU_chaos", raising=False)
        chaos_core.reset()


# ---------------------------------------------------------------------------
# 2-node FakeScaleCluster (acceptance shape) + `top` rendering
# ---------------------------------------------------------------------------

def test_fake_scale_cluster_summary_and_top_render():
    from ray_tpu.cluster_utils import FakeScaleCluster
    from ray_tpu.scripts import _render_top

    async def run():
        cluster = FakeScaleCluster(
            num_nodes=2, cpus_per_node=8, heartbeat_period_s=0.2
        )
        await cluster.start()
        try:
            async def beats():
                summary = await cluster.driver.call("resource_summary", {})
                nodes = summary.get("nodes") or {}
                ok = len(nodes) == 2 and all(
                    (e.get("points") or {}).get("raw", 0) >= 2
                    for e in nodes.values()
                )
                return summary if ok else None

            deadline = asyncio.get_event_loop().time() + 20
            summary = await beats()
            while summary is None and (
                asyncio.get_event_loop().time() < deadline
            ):
                await asyncio.sleep(0.2)
                summary = await beats()
            assert summary, "2-node telemetry never accumulated"
            for entry in summary["nodes"].values():
                latest = entry["latest"]
                assert "cpu_percent" in latest
                assert latest["mem_used"] > 0
                assert "workers_rss_total" in latest
                assert "object_store_bytes" in latest
            node_id = next(iter(summary["nodes"]))
            tl = await cluster.driver.call(
                "resource_timeline", {"node_id": node_id}
            )
            populated = [t for t in ("raw", "10s", "60s") if tl.get(t)]
            assert len(populated) >= 2, f"tiers populated: {populated}"
            frame = _render_top(summary)
            assert "NODE" in frame and "CPU%" in frame
            assert all(
                nid[-12:] in frame for nid in summary["nodes"]
            )
        finally:
            await cluster.stop()

    asyncio.run(run())
