"""Serve-plane chaos tests (ISSUE 13) — test_chaos.py-style fixtures.

Layers covered:
  * the windowed fail-point form ({"count", "start_s", "duration_s"}),
    which bounds process-kill points so replacement processes spawned
    after the window survive (an unwindowed kill point with a
    per-process budget would fell every successor too),
  * latency-point injection (slow-replica emulation),
  * ChaosMonkey's named-actor kill target against a live serve replica
    mid-load (tier-1: the budgeted-retry + controller-replacement path),
  * slow: the "serve.replica.mid_request" fail point under load (zero
    lost requests through a crash window),
  * slow: the "serve.proxy.kill" fail point with two proxies — client
    failover to the sibling, controller restart of the corpse,
  * slow: injected replica latency visible end to end.

The slow scenarios run via ci/run_serve_chaos.sh (and the serve_chaos
release benchmark drives the same fail points at benchmark scale).
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu._private import chaos as chaos_core
from ray_tpu.util.chaos import (
    ChaosMonkey,
    FaultSchedule,
    read_event_log,
)


@pytest.fixture(autouse=True)
def _clean_chaos_state(monkeypatch):
    """Every test starts and ends with no injector and no chaos env."""
    for var in ("RAY_TPU_chaos", "RAY_TPU_chaos_identity",
                "RAY_TPU_chaos_log_dir"):
        monkeypatch.delenv(var, raising=False)
    chaos_core.reset()
    yield
    chaos_core.reset()


# ---------------------------------------------------------------------------
# decision core: windowed fail points + latency points (pure)
# ---------------------------------------------------------------------------

def test_windowed_failpoint_budget():
    import json

    schedule = FaultSchedule(
        seed=1,
        fail_points={
            "w.open": {"count": 2, "start_s": 0.0, "duration_s": 3600.0},
            "w.later": {"count": -1, "start_s": 7200.0, "duration_s": 5.0},
            "plain": 1,
        },
    )
    injector = chaos_core.ChaosInjector(schedule, identity="t")
    fired = 0
    for _ in range(5):
        try:
            injector.failpoint("w.open")
        except chaos_core.ChaosFault:
            fired += 1
    assert fired == 2  # in-window hits honor the count budget
    for _ in range(3):
        injector.failpoint("w.later")  # window not open yet: no-op
    with pytest.raises(chaos_core.ChaosFault):
        injector.failpoint("plain")  # int form unchanged
    injector.failpoint("plain")

    # The dict form survives the env/JSON wire (replacement processes
    # reconstruct the same window from the shared epoch).
    clone = FaultSchedule.from_json(schedule.to_json())
    assert clone.fail_points == schedule.fail_points
    assert clone.epoch == schedule.epoch
    raw = json.loads(schedule.to_json())
    assert raw["fail_points"]["w.open"]["duration_s"] == 3600.0


def test_latency_point_and_proxy_kill_arming():
    schedule = FaultSchedule(
        seed=2,
        latency_points={"serve.replica.request": 300.0},
        fail_points={"serve.proxy.kill": -1},
    )
    chaos_core.install(schedule, identity="t", export_env=False)
    try:
        assert chaos_core.latency_delay("serve.replica.request") == pytest.approx(0.3)
        assert chaos_core.latency_delay("serve.replica.unarmed") == 0.0
        # The proxy's ingress fail point trips through the module-level
        # convenience (the proxy turns ChaosFault into os._exit).
        with pytest.raises(chaos_core.ChaosFault):
            chaos_core.failpoint("serve.proxy.kill")
    finally:
        chaos_core.reset()


# ---------------------------------------------------------------------------
# ChaosMonkey: named-actor kill against a live replica  (tier-1)
# ---------------------------------------------------------------------------

def test_chaosmonkey_actor_kill_replica_midload():
    """The monkey SIGKILLs a serve replica BY NAME mid-load: every request
    still succeeds (budgeted retry onto the survivor) and the controller
    replaces the corpse."""
    from ray_tpu import serve
    from ray_tpu.serve._private.long_poll import get_subscriber

    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=8)
    try:
        serve.start()

        @serve.deployment(num_replicas=2, health_check_period_s=1.0)
        class Pid:
            def __call__(self, x):
                return (os.getpid(), x)

        handle = serve.run(Pid.bind(), name="monkeyed",
                           route_prefix="/monkeyed")
        assert handle.remote(0).result(timeout=30)[1] == 0

        sub = get_subscriber()
        sub.force_refresh()
        names = sorted(sub.get_replicas("monkeyed_Pid")["actor_names"])
        assert len(names) == 2
        schedule = FaultSchedule(
            seed=0,
            kills=[{"at_s": 0.2, "target": "actor", "name": names[0]}],
        )
        # The monkey's "actor" target only needs the actor registry, not a
        # Cluster handle.
        monkey = ChaosMonkey(None, schedule).start()
        # Load before, while and after the kill: requests go on until the
        # monkey has recorded it and twelve more have been answered.
        deadline = time.monotonic() + 120
        sent = after_kill = 0
        while after_kill < 12:
            assert time.monotonic() < deadline, (
                f"{sent} answered, the monkey's events: {monkey.events}"
            )
            assert handle.remote(sent).result(timeout=60)[1] == sent
            sent += 1
            after_kill += bool(monkey.events)
        assert monkey.events[0]["status"] == "ok"
        assert monkey.events[0]["actor_name"] == names[0]

        # The controller notices the corpse and brings the deployment back
        # to two RUNNING replicas.
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            status = serve.status().get("monkeyed", {})
            running = (
                status.get("deployments", {})
                .get("Pid", {})
                .get("running_replicas", 0)
            )
            if running == 2:
                break
            time.sleep(0.5)
        assert running == 2, f"replica never replaced: {serve.status()}"
    finally:
        ray_tpu.shutdown()


# ---------------------------------------------------------------------------
# fail points under real load  (slow; ci/run_serve_chaos.sh)
# ---------------------------------------------------------------------------

def _sleep_until_window(epoch: float, start_s: float) -> None:
    remaining = (epoch + start_s) - time.time()
    if remaining > 0:
        time.sleep(remaining)


@pytest.mark.slow
def test_replica_mid_request_kill_window_zero_lost(monkeypatch, tmp_path):
    """Arm a windowed mid-request kill: replicas handling requests inside
    the window die holding them (their replacements die too, once each,
    while the window is open), yet zero requests are lost — budgeted
    retries ride out the crash window and land on post-window survivors."""
    from ray_tpu import serve

    log_dir = str(tmp_path / "chaos-log")
    # The window opens well after init + deploy finish and closes 4s
    # later; the test sleeps to the window edge before sending load.
    schedule = FaultSchedule(
        seed=3,
        fail_points={
            "serve.replica.mid_request": {
                "count": 1, "start_s": 25.0, "duration_s": 4.0,
            },
        },
    )
    monkeypatch.setenv("RAY_TPU_chaos", schedule.to_json())
    monkeypatch.setenv("RAY_TPU_chaos_log_dir", log_dir)
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=8)
    try:
        serve.start()

        @serve.deployment(
            num_replicas=2,
            health_check_period_s=1.0,
            request_timeout_s=60.0,
            retry_policy={"max_attempts": 10},
        )
        class Echo:
            def __call__(self, x):
                return x * 3

        handle = serve.run(Echo.bind(), name="chaosecho",
                           route_prefix="/chaosecho")
        assert handle.remote(1).result(timeout=30) == 3
        _sleep_until_window(schedule.epoch, 25.0)
        answers = [handle.remote(i).result(timeout=90) for i in range(6)]
        assert answers == [i * 3 for i in range(6)]
    finally:
        ray_tpu.shutdown()
    kills = [
        e for e in read_event_log(log_dir)
        if e.get("point") == "failpoint"
        and e.get("method") == "serve.replica.mid_request"
    ]
    assert kills, "the mid-request fail point never fired"


@pytest.mark.slow
def test_proxy_kill_failover_and_restart(monkeypatch, tmp_path):
    """Two proxies, a windowed ingress kill: the client fails over to the
    sibling proxy (zero lost requests), and the controller health check
    restarts the corpse — both ports serve again after the window."""
    import httpx

    from ray_tpu import serve

    log_dir = str(tmp_path / "chaos-log")
    schedule = FaultSchedule(
        seed=4,
        fail_points={
            "serve.proxy.kill": {
                "count": 1, "start_s": 25.0, "duration_s": 4.0,
            },
        },
    )
    monkeypatch.setenv("RAY_TPU_chaos", schedule.to_json())
    monkeypatch.setenv("RAY_TPU_chaos_log_dir", log_dir)
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=8)
    ports = (8197, 8198)
    try:
        serve.start(http_port=ports[0], num_proxies=2)

        @serve.deployment(num_replicas=1)
        class Echo:
            def __call__(self, body):
                return {"v": body.get("v") if isinstance(body, dict) else body}

        serve.run(Echo.bind(), name="pecho", route_prefix="/pecho",
                  http_port=ports[0])
        assert httpx.post(
            f"http://127.0.0.1:{ports[0]}/pecho", json={"v": 1}, timeout=30
        ).status_code == 200

        def failover_post(value):
            """One logical request: alternate proxies until a 2xx, as a
            real multi-ingress client would. 5xx counts as lost."""
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                for port in ports:
                    try:
                        resp = httpx.post(
                            f"http://127.0.0.1:{port}/pecho",
                            json={"v": value}, timeout=10,
                        )
                    except httpx.HTTPError:
                        continue  # proxy down: fail over / retry
                    if resp.status_code == 200:
                        return resp.json()["v"]
                    if resp.status_code == 503:
                        time.sleep(
                            float(resp.headers.get("Retry-After", 0.2))
                        )
                        continue
                    raise AssertionError(
                        f"lost request: HTTP {resp.status_code} {resp.text}"
                    )
                time.sleep(0.2)
            raise AssertionError(f"request {value} never completed")

        _sleep_until_window(schedule.epoch, 25.0)
        assert [failover_post(i) for i in range(10)] == list(range(10))

        # Past the window: the controller restarts dead proxies and both
        # ports answer health checks again.
        for port in ports:
            ok = False
            deadline = time.monotonic() + 60
            while time.monotonic() < deadline:
                try:
                    if httpx.get(
                        f"http://127.0.0.1:{port}/-/healthz", timeout=5
                    ).text == "ok":
                        ok = True
                        break
                except httpx.HTTPError:
                    time.sleep(0.5)
            assert ok, f"proxy on port {port} never came back"
    finally:
        ray_tpu.shutdown()
    kills = [
        e for e in read_event_log(log_dir)
        if e.get("point") == "failpoint"
        and e.get("method") == "serve.proxy.kill"
    ]
    assert kills, "the proxy kill fail point never fired"


@pytest.mark.slow
def test_slow_replica_latency_injection(monkeypatch):
    """An armed latency point stretches every replica request by the
    configured delay — the knob the SLO autoscaler and hedging tests
    use to fake a degraded replica."""
    from ray_tpu import serve

    schedule = FaultSchedule(
        seed=5, latency_points={"serve.replica.request": 400.0}
    )
    monkeypatch.setenv("RAY_TPU_chaos", schedule.to_json())
    assert not ray_tpu.is_initialized()
    ray_tpu.init(num_cpus=8)
    try:
        serve.start()

        @serve.deployment(num_replicas=1)
        class Quick:
            def __call__(self, x):
                return x

        handle = serve.run(Quick.bind(), name="slowed",
                           route_prefix="/slowed")
        handle.remote(0).result(timeout=30)  # warm (deploy + compile)
        t0 = time.monotonic()
        for i in range(3):
            assert handle.remote(i).result(timeout=30) == i
        elapsed = time.monotonic() - t0
        assert elapsed >= 3 * 0.35, (
            f"injected 400ms/request latency not observed: {elapsed:.3f}s "
            f"for 3 requests"
        )
    finally:
        ray_tpu.shutdown()
