"""Compiled-DAG failure semantics (ISSUE 15).

Killing an actor mid-execute on a device-channel DAG must surface a
TYPED death error (DAGActorDiedError naming the dead actor and its
device-plane rank) from DAGRef.get() instead of a bare timeout, and the
comm-plane hang doctor must independently blame the dead rank: the
driver's blocked out-edge pop publishes the stall, the surviving
workers' in-flight short-slice pops are harvested as waiting-rank
evidence on the SAME folded channel skeleton (``dagch:e{}:{}:{}``), and
the frontier analysis names the rank with no record at the frontier.

Own module: the watchdog env must be set BEFORE ray_tpu.init and the
shared cluster fixture is module-scoped.
"""

import os
import time

import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.dag import InputNode

_WATCHDOG_ENV = {
    "RAY_TPU_COMM_WATCHDOG_TICK_S": "0.1",
    "RAY_TPU_COMM_WATCHDOG_MIN_S": "1.0",
    "RAY_TPU_COMM_WATCHDOG_K": "4.0",
    "RAY_TPU_COMM_WATCHDOG_MIN_SAMPLES": "4",
    "RAY_TPU_COMM_WATCHDOG_STARTUP_S": "3.0",
    "RAY_TPU_COMM_WATCHDOG_COOLDOWN_S": "1.0",
    "RAY_TPU_HANG_HARVEST_COOLDOWN_S": "1",
}


@pytest.fixture()
def dag_cluster():
    assert not ray_tpu.is_initialized()
    for key, value in _WATCHDOG_ENV.items():
        os.environ[key] = value
    ray_tpu.init(num_cpus=8)
    try:
        yield
    finally:
        ray_tpu.shutdown()
        for key in _WATCHDOG_ENV:
            os.environ.pop(key, None)


@ray_tpu.remote
class Relay:
    def add(self, x):
        return x + 1


def test_killed_dag_actor_raises_typed_error_and_hang_report(dag_cluster):
    from ray_tpu.util import state

    a, b, c = Relay.remote(), Relay.remote(), Relay.remote()
    with InputNode() as inp:
        out = c.add.bind(b.add.bind(a.add.bind(inp)))
    dag = out.experimental_compile(channel="device")
    victim_rank = dag._plan.rank_of(b._actor_id)
    try:
        # Warm: channels open AND the watchdog's per-channel p95 window
        # gets enough samples to arm the adaptive deadline.
        for i in range(4):
            assert dag.execute(i).get(timeout=60) == i + 3

        ray_tpu.kill(b, no_restart=True)
        ref = dag.execute(99)
        with pytest.raises(exceptions.DAGActorDiedError) as excinfo:
            ref.get(timeout=12.0)
        err = excinfo.value
        assert err.dag_id == dag.dag_id
        assert err.actor_id == b._actor_id
        assert err.rank == victim_rank
        assert isinstance(err, exceptions.ActorDiedError)

        # The driver's blocked full-timeout out-edge pop published a
        # stall; the controller harvested a report while it was live.
        deadline = time.time() + 30.0
        summary = state.summarize_commflight()
        while (
            summary["stall_total"] < 1 or summary["hang_reports"] < 1
        ) and time.time() < deadline:
            time.sleep(0.5)
            summary = state.summarize_commflight()
        assert summary["stall_total"] >= 1, summary
        assert summary["hang_reports"] >= 1, summary

        # The report blames the dead rank: it is the one with no record
        # at the stalled channel's frontier.
        report = state.get_hang_report()
        assert report.get("channels"), report.get("summary")
        blamed = set()
        for chan in report["channels"]:
            blamed.update(chan.get("suspect_ranks", ()))
            blamed.update(chan.get("missing_ranks", ()))
        assert victim_rank in blamed, (victim_rank, report["summary"])
    finally:
        dag.close(timeout=5.0)
