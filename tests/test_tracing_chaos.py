"""Tracing under chaos + the disabled-path smoke (ISSUE 4 satellites).

A dup/drop RPC fault schedule must not corrupt the span store: every
span file stays valid JSONL and span_ids stay globally unique (spans are
recorded process-locally, so duplicated/dropped RPC frames must never
duplicate a record). And with ``tracing_enabled=0`` the whole layer is
free: no tracing dir, no span files, no injected context.
"""

import glob
import json
import os
import time

import pytest

import ray_tpu
from ray_tpu._private import chaos as chaos_core
from ray_tpu._private.config import global_config


@pytest.fixture()
def chaos_traced_cluster():
    assert not ray_tpu.is_initialized()
    os.environ["RAY_TPU_tracing_enabled"] = "1"
    os.environ["RAY_TPU_chaos"] = json.dumps({
        "seed": 4242,
        "drop_request": 0.03,
        "drop_reply": 0.03,
        "dup_request": 0.1,
        "dup_reply": 0.2,
    })
    # The injector is a process singleton cached on first use: any test
    # that booted a cluster earlier in this pytest process cached the
    # inactive one. Without this reset the DRIVER would run chaos-blind
    # (no per-attempt call timeouts) against cluster processes that DO
    # drop replies — a dropped create_actor reply then hangs the client
    # forever.
    chaos_core.reset()
    global_config().tracing_enabled = True
    ray_tpu.init(num_cpus=4)
    from ray_tpu._private import worker as worker_mod

    yield worker_mod._local_cluster.session_dir
    ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_chaos", None)
    os.environ.pop("RAY_TPU_tracing_enabled", None)
    chaos_core.reset()  # drop the chaos injector for later tests
    global_config().tracing_enabled = False


@pytest.fixture()
def untraced_cluster():
    assert not ray_tpu.is_initialized()
    os.environ.pop("RAY_TPU_tracing_enabled", None)
    global_config().tracing_enabled = False
    ray_tpu.init(num_cpus=4)
    from ray_tpu._private import worker as worker_mod

    yield worker_mod._local_cluster.session_dir
    ray_tpu.shutdown()


def test_chaos_dup_drop_keeps_span_store_consistent(chaos_traced_cluster):
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def chaotic_add(a, b):
        return a + b

    @ray_tpu.remote
    class ChaoticCounter:
        def __init__(self):
            self.n = 0

        def bump(self):
            self.n += 1
            return self.n

    refs = [chaotic_add.remote(i, i) for i in range(30)]
    assert ray_tpu.get(refs, timeout=120) == [2 * i for i in range(30)]
    counter = ChaoticCounter.remote()
    for _ in range(10):
        ray_tpu.get(counter.bump.remote(), timeout=120)

    # Every execution has reached the span store: each process's buffered
    # exporter writes on its own clock, so wait for the records themselves.
    def executed(spans, what):
        return sum(
            s["name"].startswith("execute ") and s["name"].endswith(what)
            for s in spans
        )

    deadline = time.monotonic() + 60
    while True:
        spans = tracing.read_spans(chaos_traced_cluster)
        if executed(spans, "chaotic_add") >= 30 and executed(spans, ".bump") >= 10:
            break
        assert time.monotonic() < deadline, (
            f"{executed(spans, 'chaotic_add')} of 30 tasks and "
            f"{executed(spans, '.bump')} of 10 calls in the store"
        )
        time.sleep(0.2)

    span_ids = []
    files = glob.glob(
        os.path.join(chaos_traced_cluster, "tracing", "spans-*.jsonl")
    )
    assert files, "no span files written under chaos"
    for path in files:
        with open(path) as fh:
            # A record ends with its newline: a tail without one is an
            # append still on its way, not a record.
            records = fh.read().split("\n")[:-1]
        for lineno, line in enumerate(records, 1):
            # Every line must parse: dup'd/dropped RPCs must never
            # tear or repeat a JSONL record.
            span = json.loads(line)
            assert span["span_id"], f"{path}:{lineno}"
            span_ids.append(span["span_id"])
    assert len(span_ids) == len(set(span_ids)), "duplicate span_ids"


def test_tracing_disabled_path_is_free(untraced_cluster):
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def untraced_noop(x):
        return x

    refs = [untraced_noop.remote(i) for i in range(20)]
    assert ray_tpu.get(refs, timeout=60) == list(range(20))

    @ray_tpu.remote
    class Quiet:
        def m(self):
            return 1

    actor = Quiet.remote()
    assert ray_tpu.get(actor.m.remote(), timeout=60) == 1

    # Disabled means NO per-task span plumbing anywhere: no context to
    # inject, no span objects, and nothing in the session but the
    # once-a-run lifecycle spans (recorded with tracing off too:
    # docs/observability.md, "Lifecycle spans"): the driver's of
    # ray_tpu.init (and its boot, where this is its process's first
    # init), and ONE of each worker process, its boot.
    assert tracing.inject() is None
    with tracing.span("nope") as s:
        assert s is None
    time.sleep(1.0)
    spans = tracing.read_spans(untraced_cluster)
    driver = {s["name"] for s in spans if s["pid"] == os.getpid()}
    assert driver - {"driver.boot"} == {
        "ray_tpu.init", "init.start_controller", "init.start_agent",
        "init.connect",
    }
    workers = [s for s in spans if s["pid"] != os.getpid()]
    assert workers and {s["name"] for s in workers} == {"worker.boot"}
    assert len({s["pid"] for s in workers}) == len(workers)
    assert len(glob.glob(
        os.path.join(untraced_cluster, "tracing", "spans-*.jsonl")
    )) == 1 + len(workers)


def test_read_spans_leaves_an_append_in_flight_alone(tmp_path):
    """A reader may open a span file while another process is writing its
    next batch: the record whose newline has not landed yet is skipped, not
    parsed as a torn one."""
    from ray_tpu.util import tracing

    record = json.dumps({"name": "execute f", "span_id": "00000001"})
    (tmp_path / "tracing").mkdir()
    (tmp_path / "tracing" / "spans-1.jsonl").write_text(
        f"{record}\n{record}\n{record[:17]}"
    )
    assert tracing.read_spans(str(tmp_path)) == [json.loads(record)] * 2
