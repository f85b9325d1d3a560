"""Compiled-graph (aDAG-equiv) tests — linear chains, fan-in joins,
pipelining, and error propagation (SURVEY §2.2)."""

import time

import pytest

import ray_tpu
from ray_tpu.dag import InputNode


@ray_tpu.remote
class Stage:
    def __init__(self, offset):
        self.offset = offset

    def add(self, x):
        return x + self.offset

    def slow_add(self, x):
        time.sleep(0.3)
        return x + self.offset

    def join(self, a, b):
        return a + b

    def boom(self, x):
        raise RuntimeError("stage exploded")


def test_interpreted_dag(ray_start_shared):
    a, b = Stage.remote(1), Stage.remote(10)
    with InputNode() as inp:
        x = a.add.bind(inp)
        out = b.add.bind(x)
    assert out.execute(5) == 16


def test_compiled_linear_chain(ray_start_shared):
    a, b, c = Stage.remote(1), Stage.remote(10), Stage.remote(100)
    with InputNode() as inp:
        out = c.add.bind(b.add.bind(a.add.bind(inp)))
    dag = out.experimental_compile()
    assert dag.execute(0).get(timeout=60) == 111
    # Repeated executes reuse the channels.
    results = [dag.execute(i) for i in range(5)]
    assert [r.get(timeout=60) for r in results] == [111 + i for i in range(5)]


def test_compiled_fan_in_join(ray_start_shared):
    a, b, j = Stage.remote(1), Stage.remote(2), Stage.remote(0)
    with InputNode() as inp:
        out = j.join.bind(a.add.bind(inp), b.add.bind(inp))
    dag = out.experimental_compile()
    assert dag.execute(10).get(timeout=60) == 23  # (10+1) + (10+2)


def test_compiled_pipeline_overlaps(ray_start_shared):
    """Two slow stages; pipelined executes take ~(n+1)*t, not 2n*t."""
    a, b = Stage.remote(0), Stage.remote(0)
    with InputNode() as inp:
        out = b.slow_add.bind(a.slow_add.bind(inp))
    dag = out.experimental_compile()
    n = 4
    start = time.perf_counter()
    refs = [dag.execute(i) for i in range(n)]
    values = [r.get(timeout=60) for r in refs]
    elapsed = time.perf_counter() - start
    assert values == list(range(n))
    sequential = 2 * n * 0.3
    assert elapsed < sequential * 0.85, (
        f"no pipelining: {elapsed:.2f}s vs sequential {sequential:.2f}s"
    )


def test_compiled_dag_error_propagates(ray_start_shared):
    a, b = Stage.remote(1), Stage.remote(0)
    with InputNode() as inp:
        out = b.boom.bind(a.add.bind(inp))
    dag = out.experimental_compile()
    with pytest.raises(Exception, match="stage exploded"):
        dag.execute(1).get(timeout=60)


def test_compiled_channels_beat_actor_hops_at_1mib(ray_start_shared):
    """v2 shm channels: a 4-stage 1 MiB pipeline through pre-allocated
    ring channels makes no driver round trip and sends no payload over the
    socket path, where the per-hop actor-call path makes one round trip and
    sends the payload once a hop. That is what "beat" stood for, read from
    the native engine's own counters (frames and bytes this process sent)
    over ``n`` executions of each path: counts, not a speed, on a CPU host
    (the ratio of the two paths' times is release/'s to report, and gates
    nothing)."""
    import numpy as np

    from ray_tpu.util.metrics import local_engine_points

    @ray_tpu.remote
    class Echo:
        def f(self, x):
            return x

    def sent():
        """(frames, bytes) every native engine of this process has sent so far."""
        points = local_engine_points()
        return np.array([
            sum(value for name, _tags, value, _kind in points if name == f"native_engine_{field}")
            for field in ("frames_sent", "bytes_sent")
        ])

    stages = [Echo.remote() for _ in range(4)]
    payload = np.ones(1024 * 1024 // 4, dtype=np.float32)  # 1 MiB
    with InputNode() as inp:
        node = inp
        for s in stages:
            node = s.f.bind(node)
    dag = node.experimental_compile()
    try:
        # channels registered (pre-allocated at compile)
        assert all(t["channel"] for t in dag._input_targets)
        assert dag._out_channel

        def run_actor(n):
            for _ in range(n):
                mid = payload
                for s in stages:
                    mid = ray_tpu.get(s.f.remote(mid), timeout=60)
            assert np.array_equal(mid, payload)

        def run_dag(n):
            for _ in range(n):
                out = dag.execute(payload).get(timeout=60)
                assert out.nbytes == payload.nbytes
            assert np.array_equal(out, payload)

        run_actor(2), run_dag(2)  # warm both paths
        n, hops = 10, len(stages)
        before = sent()
        run_actor(n)
        actor_frames, actor_bytes = sent() - before
        before = sent()
        run_dag(n)
        dag_frames, dag_bytes = sent() - before
        # a hop of the actor path: a call out, its payload with it
        assert actor_frames >= n * hops and actor_bytes >= n * hops * payload.nbytes
        # the compiled path: fewer frames than hops and less than ONE payload
        # over all n executions (whatever else this process sent meanwhile)
        assert dag_frames < n * hops and dag_bytes < payload.nbytes, (
            f"channels went over the socket path: {dag_frames} frames, {dag_bytes} bytes "
            f"in {n} executions (actor hops: {actor_frames} frames, {actor_bytes} bytes)"
        )
    finally:
        dag.teardown()


def test_compiled_dag_teardown_frees_channel_slots(ray_start_shared):
    import numpy as np

    @ray_tpu.remote
    class Echo:
        def f(self, x):
            return x

    a = Echo.remote()
    with InputNode() as inp:
        out = a.f.bind(inp)
    dag = out.experimental_compile()
    dag.execute(np.ones(300_000, dtype=np.uint8)).get(timeout=60)
    dag_id = dag.dag_id
    dag.teardown()
    # torn-down DAGs refuse new work
    with pytest.raises(RuntimeError):
        dag.execute(1)
    # channel slots are gone from the shared store
    from ray_tpu._private.worker import get_global_context

    store = get_global_context().store
    leftovers = [
        name for name in store.list() if name.startswith(f"dagch-{dag_id}")
    ]
    assert not leftovers, f"leaked channel slots: {leftovers}"


def test_compiled_multi_stage_actor(ray_start_shared):
    """v2: one actor may host several stages (the reference's
    multi-method compiled graphs); same-actor edges deliver in-process."""
    a, b = Stage.remote(1), Stage.remote(10)
    with InputNode() as inp:
        h1 = a.add.bind(inp)          # +1
        h2 = a.add.bind(h1)           # +1 again, SAME actor
        out = b.add.bind(h2)          # +10
    dag = out.experimental_compile()
    try:
        assert dag.execute(0).get(timeout=120) == 12
        assert dag.execute(5).get(timeout=120) == 17
    finally:
        dag.teardown()


# ---------------------------------------------------------------------------
# rtdag (ISSUE 15): MultiOutputNode, backpressure, channel families,
# close() semantics, zero-controller-RPC steady state
# ---------------------------------------------------------------------------

def test_multi_output_fan_out_fan_in_ordering(ray_start_shared):
    """Fan-out from one upstream into two branches; MultiOutputNode
    returns both leaves in declaration order, and out-of-order get()s
    drain the channels without reordering seqs."""
    from ray_tpu.dag import MultiOutputNode

    a, b, c = Stage.remote(1), Stage.remote(10), Stage.remote(100)
    with InputNode() as inp:
        h = a.add.bind(inp)
        out = MultiOutputNode([b.add.bind(h), c.add.bind(h)])
    # Interpreted parity first: shared upstream runs ONCE per execute.
    assert out.execute(0) == [11, 101]
    dag = out.experimental_compile()
    try:
        assert dag.execute(0).get(timeout=60) == [11, 101]
        refs = [dag.execute(i) for i in range(1, 5)]
        # Out-of-order consumption: later seqs first.
        assert refs[2].get(timeout=60) == [14, 104]
        assert refs[0].get(timeout=60) == [12, 102]
        assert refs[3].get(timeout=60) == [15, 105]
        assert refs[1].get(timeout=60) == [13, 103]
    finally:
        dag.close()


def test_execute_backpressure_at_ring_depth(ray_start_shared):
    """Admission is bounded by the channel ring depth: the (depth+1)-th
    un-popped execute is refused instead of wedging a producer."""
    a = Stage.remote(1)
    with InputNode() as inp:
        out = a.add.bind(inp)
    dag = out.experimental_compile()
    try:
        depth = dag.CHANNEL_DEPTH
        refs = [dag.execute(i) for i in range(depth)]
        with pytest.raises(RuntimeError, match="in flight"):
            dag.execute(99)
        assert [r.get(timeout=60) for r in refs] == [
            i + 1 for i in range(depth)
        ]
        # Draining reopens admission.
        assert dag.execute(0).get(timeout=60) == 1
    finally:
        dag.close()


def test_device_channel_parity_and_flight_records(ray_start_shared):
    """channel="device" routes every edge over the collective p2p plane
    (driver = rank 0 of the per-DAG group) with identical results to the
    shm family, and both families leave site="dag" flight records."""
    import numpy as np

    from ray_tpu.util.collective import flight

    a, b = Stage.remote(1), Stage.remote(10)
    with InputNode() as inp:
        out = b.add.bind(a.add.bind(inp))
    shm_dag = out.experimental_compile()
    with InputNode() as inp:
        out2 = b.add.bind(a.add.bind(inp))
    dev_dag = out2.experimental_compile(channel="device")
    try:
        for i in range(3):
            got_shm = shm_dag.execute(i).get(timeout=60)
            got_dev = dev_dag.execute(i).get(timeout=60)
            assert got_shm == got_dev == i + 11
        arr = np.arange(8, dtype=np.float32)
        np.testing.assert_allclose(
            dev_dag.execute(arr).get(timeout=60), arr + 11
        )
        snap = flight.snapshot(512)
        dag_recs = [r for r in snap if r.get("site") == "dag"]
        # Device edges: real p2p send/recv records under certified tags.
        assert any(
            r["kind"] == "send" and r["tag"].startswith("dagch:")
            for r in dag_recs
        ), "no device-edge send recorded under site=dag"
        assert any(
            r["kind"] == "recv" and r["tag"].startswith("dagch:")
            for r in dag_recs
        ), "no device-edge recv recorded under site=dag"
        # Shm edges: chan_push/chan_pop notes (exempt from static
        # send/recv reconciliation, still visible to the ring).
        assert any(r["kind"] == "chan_push" for r in dag_recs)
        assert any(r["kind"] == "chan_pop" for r in dag_recs)
    finally:
        shm_dag.close()
        dev_dag.close()


def test_close_drains_inflight_and_frees_slots(ray_start_shared):
    """close() with executions still in flight drains them, then frees
    every ring slot and refuses new work."""
    a = Stage.remote(5)
    with InputNode() as inp:
        out = a.slow_add.bind(inp)
    dag = out.experimental_compile()
    refs = [dag.execute(i) for i in range(3)]
    del refs  # deliberately un-popped
    dag.close()
    with pytest.raises(RuntimeError, match="torn down"):
        dag.execute(9)
    from ray_tpu._private.worker import get_global_context

    store = get_global_context().store
    leftovers = [
        name for name in store.list()
        if name.startswith(f"dagch-{dag.dag_id}")
    ]
    assert not leftovers, f"leaked channel slots: {leftovers}"
    # Idempotent.
    dag.close()


def test_steady_state_has_zero_controller_rpcs(ray_start_shared):
    """The rtdag contract: after compile, a steady-state execute()/get()
    cycle issues ZERO controller RPCs — payloads move over pre-opened
    channels only."""
    from ray_tpu._private.worker import get_global_context

    a, b = Stage.remote(1), Stage.remote(2)
    with InputNode() as inp:
        out = b.add.bind(a.add.bind(inp))
    dag = out.experimental_compile()
    try:
        dag.execute(0).get(timeout=60)  # warm every channel
        ctrl = get_global_context().controller
        before = ctrl.calls_total
        for i in range(10):
            assert dag.execute(i).get(timeout=60) == i + 3
        assert ctrl.calls_total == before, (
            f"steady-state executes issued "
            f"{ctrl.calls_total - before} controller RPC(s)"
        )
    finally:
        dag.close()


def test_constant_args_still_rejected(ray_start_shared):
    a = Stage.remote(1)
    with InputNode() as inp:
        out = a.join.bind(inp, 7)
    with pytest.raises(ValueError, match="constant"):
        out.experimental_compile()


def test_placement_plan_pins_actors_and_ranks(ray_start_shared):
    """Compile resolves an explicit placement plan: every actor is
    pinned to a live node with a stable device-plane rank (driver=0),
    in graph order."""
    a, b = Stage.remote(1), Stage.remote(2)
    with InputNode() as inp:
        out = b.add.bind(a.add.bind(inp))
    dag = out.experimental_compile()
    try:
        plan = dag._plan
        assert plan.rank_of(None) == 0
        assert plan.rank_of(a._actor_id) == 1
        assert plan.rank_of(b._actor_id) == 2
        assert plan.world_size == 3
        assert plan.node_of(a._actor_id)
        assert plan.colocated(a._actor_id, b._actor_id)  # single node
    finally:
        dag.close()
