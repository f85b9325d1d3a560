"""Self-healing serve-plane tests (ISSUE 13).

Mirrors the test_serve.py strategy: the reliability primitives (Deadline,
RetryPolicy, CircuitBreaker, admission math, header parsing) are tested
pure, then the end-to-end contracts — deadline expiry surfaces typed,
replica death mid-request is retried invisibly, saturated routes shed
with 503 + Retry-After, draining replicas bounce traffic without caller
errors — run against a real controller + replicas + proxy on the shared
cluster fixture.
"""

import os
import time
from concurrent.futures import ThreadPoolExecutor

import pytest

import ray_tpu
from ray_tpu import exceptions, serve
from ray_tpu.serve._private.common import (
    Deadline,
    DeploymentConfig,
    RetryPolicy,
    current_deadline,
    reset_current_deadline,
    set_current_deadline,
)
from ray_tpu.serve.handle import CircuitBreaker
from ray_tpu.serve._private.proxy import admission_limit, parse_deadline_header


# ---------- pure: Deadline ----------

def test_deadline_basics():
    d = Deadline.after(0.5)
    assert not d.expired()
    assert 0.0 < d.remaining() <= 0.5
    assert d.remaining(cap=0.1) <= 0.1
    assert d.budget() is not None and d.budget() <= 0.5

    gone = Deadline.after(0.0)
    assert gone.expired()
    assert gone.remaining() == 0.0


def test_deadline_unbounded():
    forever = Deadline.never()
    assert forever.is_unbounded()
    assert not forever.expired()
    assert forever.budget() is None  # nothing to put on the wire
    assert forever.remaining(cap=7.0) == 7.0  # cap still derives timeouts
    # after(None) is the unbounded spelling used for absent budgets.
    assert Deadline.after(None).is_unbounded()


def test_deadline_budget_reanchors_across_hops():
    """The wire carries a relative budget; the receiving hop re-anchors it
    on its own monotonic clock and the result is never longer than the
    sender's remaining time."""
    sender = Deadline.after(2.0)
    wire = sender.budget()
    receiver = Deadline.after(wire)
    assert receiver.remaining() <= 2.0
    assert receiver.remaining() > 1.5


def test_deadline_contextvar_roundtrip():
    assert current_deadline() is None
    d = Deadline.after(1.0)
    token = set_current_deadline(d)
    try:
        assert current_deadline() is d
    finally:
        reset_current_deadline(token)
    assert current_deadline() is None


# ---------- pure: RetryPolicy ----------

def test_retry_policy_from_dict_filters_unknown_keys():
    pol = RetryPolicy.from_dict(
        {"max_attempts": 5, "hedge": True, "from_the_future": 1}
    )
    assert pol.max_attempts == 5
    assert pol.hedge is True
    assert pol.hedge_after_s is None
    assert RetryPolicy.from_dict({}).max_attempts == RetryPolicy().max_attempts


def test_policy_snapshot_carries_reliability_knobs():
    cfg = DeploymentConfig(
        max_ongoing_requests=4,
        request_timeout_s=9.0,
        health_probe_timeout_s=2.0,
        max_queued_requests=3,
        retry_policy=RetryPolicy(max_attempts=7),
    )
    snap = cfg.policy_snapshot()
    assert snap["max_ongoing_requests"] == 4
    assert snap["request_timeout_s"] == 9.0
    assert snap["health_probe_timeout_s"] == 2.0
    assert snap["max_queued_requests"] == 3
    assert snap["graceful_shutdown_timeout_s"] == 20.0
    assert snap["retry_policy"]["max_attempts"] == 7
    # The snapshot must survive the long-poll wire (plain data only).
    import json

    json.dumps(snap)


# ---------- pure: circuit breaker ----------

def test_circuit_breaker_transitions():
    br = CircuitBreaker(failure_threshold=3, cooldown_s=0.2)
    assert br.state == CircuitBreaker.CLOSED
    br.record_failure()
    br.record_failure()
    assert br.can_route()  # under threshold: still closed
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert not br.can_route()
    # Cooldown elapses: half-open, a probe is allowed through.
    time.sleep(0.25)
    assert br.can_route()
    assert br.state == CircuitBreaker.HALF_OPEN
    # A single failure in half-open slams it shut again immediately.
    br.record_failure()
    assert br.state == CircuitBreaker.OPEN
    assert not br.can_route()
    time.sleep(0.25)
    assert br.can_route()
    br.record_success()
    assert br.state == CircuitBreaker.CLOSED
    assert br.can_route()


# ---------- pure: proxy admission + ingress header ----------

def test_admission_limit_formula():
    # capacity = replicas x max_ongoing; -1 queue allowance = 1x capacity.
    assert admission_limit(2, 8, -1) == 32
    assert admission_limit(2, 8, 0) == 16  # queueing disabled
    assert admission_limit(2, 8, 5) == 21
    # Scale-to-zero routes still admit one capacity's worth of traffic
    # (requests wait on the deadline for the first replica).
    assert admission_limit(0, 8, 0) == 8


def test_parse_deadline_header():
    d = parse_deadline_header("2.5", default_s=60.0)
    assert d.remaining() <= 2.5
    # Absent or malformed: the route's default request timeout seeds it.
    assert parse_deadline_header(None, default_s=1.0).remaining() <= 1.0
    assert parse_deadline_header("soon", default_s=1.0).remaining() <= 1.0
    assert parse_deadline_header("-3", default_s=60.0).expired()


# ---------- end-to-end ----------

@pytest.fixture(scope="module")
def serve_instance(ray_start_shared):
    yield
    serve.shutdown()


def test_deadline_expiry_is_typed(serve_instance):
    """result(timeout=...) tightens the propagated deadline; a replica
    still working when it lapses surfaces DeadlineExceededError, not a
    bare GetTimeoutError."""

    import asyncio

    @serve.deployment
    class Slow:
        # async so the replica's event loop stays free: the handle's
        # liveness probe at expiry must see "alive", making the typed
        # outcome DeadlineExceededError, not ReplicaDiedError.
        async def __call__(self, x):
            await asyncio.sleep(5.0)
            return x

    handle = serve.run(Slow.bind(), name="slowapp", route_prefix="/slowapp")
    t0 = time.monotonic()
    with pytest.raises(exceptions.DeadlineExceededError):
        handle.remote(1).result(timeout=0.4)
    # The error arrived promptly at expiry, not after the 5s handler.
    assert time.monotonic() - t0 < 4.0


def test_request_timeout_config_seeds_deadline(serve_instance):
    """With no ambient deadline and no result(timeout), the deployment's
    request_timeout_s is the ingress budget."""

    import asyncio

    @serve.deployment(request_timeout_s=0.4)
    class SlowDefault:
        async def __call__(self, x):
            await asyncio.sleep(5.0)
            return x

    handle = serve.run(
        SlowDefault.bind(), name="slowdef", route_prefix="/slowdef"
    )
    t0 = time.monotonic()
    with pytest.raises(exceptions.DeadlineExceededError):
        handle.remote(1).result()
    assert time.monotonic() - t0 < 4.0


def test_budgeted_retry_within_one_request(serve_instance, tmp_path):
    """A replica that dies mid-request is invisible to the caller: the
    SAME request re-dispatches onto a healthy replica under the retry
    budget (the tentpole contract replacing the old retry-once handoff)."""
    marker = str(tmp_path / "died_once")

    @serve.deployment(num_replicas=2, health_check_period_s=30.0)
    class DiesOnce:
        def __call__(self, payload):
            if payload == "poison" and not os.path.exists(marker):
                with open(marker, "w") as fh:
                    fh.write(str(os.getpid()))
                os._exit(1)
            return f"ok:{payload}"

    handle = serve.run(
        DiesOnce.bind(), name="diesonce", route_prefix="/diesonce"
    )
    assert handle.remote("warm").result(timeout=30) == "ok:warm"
    # First dispatch lands on some replica, which kills itself holding the
    # request; the retry must land elsewhere and succeed.
    assert handle.remote("poison").result(timeout=60) == "ok:poison"
    assert os.path.exists(marker), "the victim replica never died"


def test_admission_shed_http_503_with_retry_after(serve_instance):
    """Past capacity + queue allowance the proxy sheds fast: 503 with a
    Retry-After header, while admitted requests still complete."""
    import httpx

    @serve.deployment(
        max_ongoing_requests=1, max_queued_requests=0, num_replicas=1
    )
    class OneAtATime:
        async def __call__(self, body):
            import asyncio

            await asyncio.sleep(1.0)
            return {"done": True}

    serve.start(http_port=8183)
    serve.run(
        OneAtATime.bind(), name="shedme", route_prefix="/shedme",
        http_port=8183,
    )

    def post(_):
        return httpx.post(
            "http://127.0.0.1:8183/shedme", json={}, timeout=60
        )

    with ThreadPoolExecutor(max_workers=6) as pool:
        responses = list(pool.map(post, range(6)))
    codes = [r.status_code for r in responses]
    assert 200 in codes, codes
    shed = [r for r in responses if r.status_code == 503]
    assert shed, f"saturated route never shed: {codes}"
    for r in shed:
        assert "Retry-After" in r.headers
        assert "shed" in r.text


def test_deadline_header_rides_http(serve_instance, tmp_path):
    """An X-RayTPU-Deadline header bounds the whole request: a handler
    that is still working at the client's budget turns into a 504."""
    import httpx

    from ray_tpu.serve._private.common import DEADLINE_HEADER

    import asyncio

    @serve.deployment
    class HeldHttp:
        async def __call__(self, body):
            # Holds the request until the test lets go of it, so nothing
            # but the deadline can answer a held request before then.
            while body["hold"] and not os.path.exists(body["hold"]):
                await asyncio.sleep(0.05)
            return {}

    serve.start(http_port=8184)
    serve.run(
        HeldHttp.bind(), name="heldhttp", route_prefix="/heldhttp",
        http_port=8184,
    )
    url = "http://127.0.0.1:8184/heldhttp"
    # The route answers end to end first: a proxy that knows of no replica
    # yet sheds (503) what it cannot place within half a second.
    assert httpx.post(url, json={"hold": ""}, timeout=60).status_code == 200
    release = tmp_path / "release"
    try:
        resp = httpx.post(
            url, json={"hold": str(release)},
            headers={DEADLINE_HEADER: "0.5"}, timeout=60,
        )
        assert resp.status_code == 504, resp.text
    finally:
        release.touch()


def test_drain_bounces_traffic_without_errors(serve_instance):
    """Draining one of two replicas is caller-invisible: the handle
    bounces dispatches that hit the draining replica onto the survivor
    (no charge against breaker or retry budget), and drain() reports the
    replica quiesced."""
    from ray_tpu.serve._private.long_poll import get_subscriber

    @serve.deployment(num_replicas=2, health_check_period_s=30.0)
    class Steady:
        def __call__(self, x):
            return x + 1

    handle = serve.run(Steady.bind(), name="steady", route_prefix="/steady")
    assert handle.remote(0).result(timeout=30) == 1

    sub = get_subscriber()
    sub.force_refresh()
    names = sub.get_replicas("steady_Steady")["actor_names"]
    assert len(names) == 2
    victim = ray_tpu.get_actor(sorted(names)[0])
    report = ray_tpu.get(victim.drain.remote(), timeout=30)
    assert report["draining"] is True
    assert report["ongoing"] == 0
    # Every request still succeeds while one replica refuses new work.
    assert [
        handle.remote(i).result(timeout=30) for i in range(8)
    ] == [i + 1 for i in range(8)]
