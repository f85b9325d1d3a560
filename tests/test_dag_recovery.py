"""Self-healing compiled-DAG recovery (ISSUE 16).

A supervised CompiledDAG must survive an actor kill mid-stream: the
driver-side supervisor restarts the victim through the controller lease
path, re-opens every channel under a bumped epoch, replays retained
inputs, and the caller's execute()/get() stream completes exactly-once
(no lost seqs, no duplicates). Unsupervised graphs keep the PR-15
contract — a typed DAGActorDiedError (now carrying edge evidence) plus
full failure-path cleanup. Epoch fencing discards stale pre-crash
frames loudly instead of desequencing re-opened rings, and a
slow-but-alive wire must never trigger a false-positive recovery.

Own module: the watchdog env (and for the slow-wire test, the chaos
schedule) must be set BEFORE ray_tpu.init, so each test owns its
cluster fixture.
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
def recovery_cluster():
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


@pytest.fixture()
def slow_wire_cluster():
    """Cluster whose device-channel pops all sleep a windowed chaos
    latency (`latency_points` dict form): installed and env-exported
    BEFORE init so every worker process inherits the schedule."""
    from ray_tpu._private import chaos as chaos_core

    assert not ray_tpu.is_initialized()
    for key, value in _WATCHDOG_ENV.items():
        os.environ[key] = value
    schedule = chaos_core.FaultSchedule(
        0,
        latency_points={
            "dag.device.pop": {
                "extra_ms": 600.0, "start_s": 0.0, "duration_s": 120.0,
            }
        },
    )
    chaos_core.install(schedule, export_env=True)
    ray_tpu.init(num_cpus=8)
    try:
        yield
    finally:
        ray_tpu.shutdown()
        chaos_core.install(None)  # uninstall + clear the env export
        chaos_core.reset()
        for key in _WATCHDOG_ENV:
            os.environ.pop(key, None)


@ray_tpu.remote
class Stage:
    def __init__(self, offset):
        self.offset = offset

    def add(self, x):
        return x + self.offset


@ray_tpu.remote
class Accumulator:
    """Stateful stage with the __dag_snapshot__/__dag_restore__ hooks."""

    def __init__(self):
        self.total = 0

    def accum(self, x):
        self.total += x
        return self.total

    def __dag_snapshot__(self):
        return {"total": self.total}

    def __dag_restore__(self, state):
        self.total = state["total"]


def test_supervised_dag_survives_kill_exactly_once(recovery_cluster):
    """Tentpole e2e: kill a mid-chain actor with executions in flight —
    the supervised stream completes with exactly-once results, the
    supervisor records the victim's rank and the epoch bump, and the
    recovered graph is back to zero-controller-RPC steady state."""
    from ray_tpu._private.worker import get_global_context

    a, b, c = Stage.remote(1), Stage.remote(1), Stage.remote(1)
    with InputNode() as inp:
        out = c.add.bind(b.add.bind(a.add.bind(inp)))
    dag = out.experimental_compile(supervise=True)
    victim_rank = dag._plan.rank_of(b._actor_id)
    try:
        for i in range(3):
            assert dag.execute(i).get(timeout=60) == i + 3

        refs = [dag.execute(i) for i in range(3, 7)]
        ray_tpu.kill(b, no_restart=True)  # returns once the process is gone
        # Those four race the kill, and one that is past `b` when it dies
        # needs no recovery. These two cannot complete without one.
        refs += [dag.execute(i) for i in range(7, 9)]
        # Every in-flight seq arrives exactly once across the kill.
        assert [r.get(timeout=120) for r in refs] == [
            i + 3 for i in range(3, 9)
        ]
        assert dag.recoveries == 1
        assert dag._epoch == 1
        rec = dag.last_recovery
        assert rec is not None
        assert b._actor_id in rec["victims"]
        assert victim_rank in rec["victim_ranks"]
        assert rec["epoch"] == 1
        assert rec["duration_s"] > 0

        # Post-recovery steady state: epoch-1 channels are pre-opened,
        # so executes issue no per-step controller RPCs. This cluster
        # arms a 0.1s-tick comm watchdog, whose background thread may
        # publish one late stall report (from the kill window) or one
        # liveness probe during the loop — allow strictly less than one
        # RPC per step; the exact-zero gate lives in test_dag.py and
        # the dag_chaos_recovery benchmark, which run unarmed.
        assert dag.execute(100).get(timeout=60) == 103
        ctrl = get_global_context().controller
        time.sleep(1.5)  # let kill-era watchdog publishes land
        before = ctrl.calls_total
        steps = 5
        for i in range(steps):
            assert dag.execute(i).get(timeout=60) == i + 3
        delta = ctrl.calls_total - before
        assert delta < steps, (
            f"recovered steady state issued {delta} controller RPC(s) "
            f"over {steps} steps — per-step control-plane traffic"
        )
    finally:
        dag.close(timeout=5.0)


def test_stateful_actor_resumes_from_snapshot(recovery_cluster):
    """A killed stateful actor comes back at its last __dag_snapshot__
    commit: the driver replays retained seqs from the commit, replayed
    results below the reader cursor are deduplicated, and the resumed
    stream continues from the committed state (not from scratch)."""
    acc = Accumulator.remote()
    with InputNode() as inp:
        out = acc.accum.bind(inp)
    dag = out.experimental_compile(supervise=True)
    try:
        for i in range(4):
            assert dag.execute(1).get(timeout=60) == i + 1
        assert dag.snapshot() == 4  # commit at total=4
        assert dag.execute(1).get(timeout=60) == 5

        ray_tpu.kill(acc, no_restart=True)
        # Detection + recovery happen inside get(): the replacement
        # restores total=4 from the commit, seq 4 (retained above the
        # snapshot floor) replays into a deduplicated result, and the
        # new seq lands on the restored state. From-scratch restart
        # would yield 2 here.
        assert dag.execute(1).get(timeout=120) == 6
        assert dag.recoveries == 1
        assert dag.replay_discards >= 1
        assert dag.execute(1).get(timeout=60) == 7
    finally:
        dag.close(timeout=5.0)


def test_unsupervised_failure_cleans_up_and_carries_evidence(
    recovery_cluster,
):
    """Unsupervised graphs keep the typed-failure contract, now with
    edge evidence on the error, and the failure path itself releases
    every ring slot and parks no loop — WITHOUT a close() call. It
    tears down the living actors only: the one the controller has
    declared DEAD is never dialled (a redial backs off for seconds)."""
    from ray_tpu._private.worker import get_global_context

    ctx = get_global_context()
    a, b = Stage.remote(1), Stage.remote(2)
    with InputNode() as inp:
        out = b.add.bind(a.add.bind(inp))
    dag = out.experimental_compile()  # NOT supervised
    dag_id = dag.dag_id
    assert dag.execute(0).get(timeout=60) == 3
    addr_of = {aid: ctx._actor_addr_cache[aid] for aid in (a._actor_id, b._actor_id)}

    ray_tpu.kill(b, no_restart=True)
    ref = dag.execute(1)
    dialled = []
    client_for = ctx._client_for
    ctx._client_for = lambda addr: dialled.append(tuple(addr)) or client_for(addr)
    try:
        with pytest.raises(exceptions.DAGActorDiedError) as excinfo:
            # An unsupervised reader blocks for the whole timeout and probes
            # liveness only then, so this is time the test always spends.
            ref.get(timeout=15.0)
    finally:
        del ctx._client_for
    err = excinfo.value
    assert addr_of[a._actor_id] in dialled, "the survivor was not torn down"
    assert addr_of[b._actor_id] not in dialled, "teardown dialled the dead actor"
    # The error names the edge it was detected on, not just the actor.
    assert err.actor_id == b._actor_id
    assert err.family == "shm"
    assert err.channel and err.channel.startswith(f"dagch-{dag_id}")
    assert err.epoch == 0
    assert err.seq == 1

    # Failure-path cleanup: graph torn down, zero leaked slots.
    assert dag._torn_down
    store = get_global_context().store
    leftovers = [
        name for name in store.list()
        if name.startswith(f"dagch-{dag_id}")
    ]
    assert not leftovers, f"leaked channel slots after failure: {leftovers}"
    with pytest.raises(RuntimeError, match="torn down"):
        dag.execute(9)
    dag.close()  # no-op after failure teardown


def test_epoch_fencing_discards_stale_frame(recovery_cluster):
    """A pre-crash (old-epoch) frame surviving into a re-opened shm
    channel is discarded loudly — counter bump, slot freed for the
    replaying producer — not surfaced as a seq-desync RuntimeError.
    A frame AHEAD of the consumer's epoch is a hard error."""
    from ray_tpu._private import serialization
    from ray_tpu._private.worker import get_global_context
    from ray_tpu.dag import channel as shm

    store = get_global_context().store
    name = "fence-test-slot-0"
    parts, total, _ = serialization.serialize_parts({"v": 1})
    assert shm.try_write_seq(store, name, 7, parts, total, epoch=0)

    before = shm.stale_frame_count()
    assert shm.read_seq_consume(store, name, 7, epoch=1) is shm.NOT_READY
    assert shm.stale_frame_count() == before + 1

    # The discard freed the slot: the epoch-1 producer claims it and
    # the epoch-1 consumer reads it normally.
    parts2, total2, _ = serialization.serialize_parts({"v": 2})
    assert shm.try_write_seq(store, name, 7, parts2, total2, epoch=1)
    assert shm.read_seq_consume(store, name, 7, epoch=1) == {"v": 2}

    assert shm.try_write_seq(store, name, 8, parts, total, epoch=2)
    with pytest.raises(RuntimeError, match="ahead"):
        shm.read_seq_consume(store, name, 8, epoch=1)
    shm._free_slot(store, name)


def test_slow_wire_does_not_trigger_false_restart(slow_wire_cluster):
    """Satellite 3: every DeviceChannel pop (the workers' watchdog-sliced
    short pops AND the driver's supervised sliced pops) sleeps the
    windowed chaos latency, so the whole wire is uniformly slow but
    every actor is ALIVE. Liveness probes between pop slices must keep
    waiting — the stream completes slowly with ZERO recoveries."""
    a, b = Stage.remote(1), Stage.remote(2)
    with InputNode() as inp:
        out = b.add.bind(a.add.bind(inp))
    dag = out.experimental_compile(channel="device", supervise=True)
    try:
        for i in range(3):
            assert dag.execute(i).get(timeout=90) == i + 3
        assert dag.recoveries == 0
        assert dag.replay_discards == 0
        assert dag._epoch == 0
    finally:
        dag.close(timeout=10.0)
