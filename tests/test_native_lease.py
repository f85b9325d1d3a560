"""Native lease lane (SURVEY N9/N10: raylet local_task_manager.cc /
cluster_resource_scheduler.cc grant path in C++).

The node agent's engine grants simple worker leases (default runtime
env, no bundle) and accepts reusable returns ON THE ENGINE THREAD —
resource accounting, job-keyed idle-pool pop, reply encode — with zero
asyncio involvement per lease; Python keeps the policy and every slow
path (spawn, bundles, custom envs, kills) and adjusts the SAME native
counters, so there is one source of truth.
"""

import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu._private import worker as worker_mod


def _agent_stats():
    ctx = worker_mod.get_global_context()

    async def call():
        client = await ctx._client_for(tuple(ctx.agent_addr))
        return await client.call("store_stats", {})

    return ctx.io.run(call())


def test_native_lease_grants_on_engine_thread(ray_start_shared):
    @ray_tpu.remote
    def f(x):
        return x * 2

    # warm: first leases spawn workers through the Python path; returned
    # reusable workers land in the ENGINE's pool
    ray_tpu.get([f.remote(i) for i in range(20)], timeout=120)
    stats = _agent_stats()
    assert "native_lease" in stats, "native lease lane not enabled"
    before = stats["native_lease"]

    # Lease churn against the warm pool, one task at a time, each submitted
    # once the agent has every lease back and a warm worker pooled: such a
    # task needs a lease RPC, and that grant rides the engine. A task served
    # by a lease the warm-up still had on its way (its request was spawning a
    # worker when the queue drained) asks for none, so the churn goes on
    # until a grant is counted. The deadline only bounds the failure.
    deadline = time.monotonic() + 120
    i = 0
    while stats["native_lease"]["grants"] == before["grants"]:
        assert time.monotonic() < deadline, (
            f"no lease was granted natively despite a warm default-env pool: {stats}"
        )
        if (
            stats["native_lease"]["idle_workers"] > 0
            and stats.get("leases_outstanding", 0) == 0
        ):
            assert ray_tpu.get(f.remote(i), timeout=60) == 2 * i
            i += 1
        else:
            time.sleep(0.1)
        stats = _agent_stats()
    # ... and goes back the same way once the driver's reuse grace is over.
    while stats["native_lease"]["returns"] == before["returns"]:
        assert time.monotonic() < deadline, f"the native lease never came back: {stats}"
        time.sleep(0.1)
        stats = _agent_stats()


def test_native_lease_resource_accounting_consistent(ray_start_shared):
    """Custom-resource tasks (bounced to Python) and plain tasks (native)
    share one availability table — total CPU never goes negative and
    returns restore it."""
    @ray_tpu.remote(resources={"TPU": 1})
    def tpu_task():
        return "tpu"

    @ray_tpu.remote
    def plain(x):
        return x

    results = ray_tpu.get(
        [tpu_task.remote() for _ in range(4)]
        + [plain.remote(i) for i in range(20)],
        timeout=120,
    )
    assert results[:4] == ["tpu"] * 4
    # all leases eventually return; availability recovers to total
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline:
        avail = ray_tpu.available_resources()
        total = ray_tpu.cluster_resources()
        if (
            avail.get("CPU", -1) == total.get("CPU")
            and avail.get("TPU", -1) == total.get("TPU")
        ):
            break
        time.sleep(1.0)
    assert avail.get("CPU") == total.get("CPU"), (avail, total)
    assert avail.get("TPU") == total.get("TPU"), (avail, total)


def test_lease_request_sent_again_joins_its_grant(ray_start_shared):
    """A caller sends a lease request again when the reply is slow (a spawn
    on a loaded host) or lost, and a lossy link can deliver it twice: every
    copy gets the one grant. A second grant would lease a worker and a CPU
    that nobody holds and nobody returns."""
    import asyncio

    ctx = worker_mod.get_global_context()
    request = {
        "resources": {"CPU": 1},
        # Not the default env: no pooled worker fits, so the agent's Python
        # path spawns one, and both copies arrive while it does.
        "runtime_env": {"env_vars": {"LEASE_SENT_TWICE": "1"}},
        "job_id": ctx.job_id,
        "bundle": None,
        "mutation_token": "lease:sent-twice",
    }

    async def send():
        agent = await ctx._client_for(tuple(ctx.agent_addr))
        first, second = await asyncio.gather(
            agent.call("lease_worker", request),
            agent.call("lease_worker", request),
        )
        late = await agent.call("lease_worker", request)  # after a lost reply
        await agent.call(
            "return_worker", {"lease_id": first["lease_id"], "reusable": False}
        )
        return first, second, late

    first, second, late = ctx.io.run(send(), timeout=120)
    assert first["status"] == "ok", first
    assert second == first and late == first
