"""Object recovery + borrowed-reference protocol tests.

Reference roles (SURVEY §7.3.1, N21/N23): lineage reconstruction
(object_recovery_manager.cc — `test_reconstruction*.py` behavior) and
reference_count_test.cc-style table tests over the borrow protocol
(local / submitted / borrower counts and their release orderings).
"""

import gc
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import exceptions
from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy


def _ctx():
    from ray_tpu._private.worker import get_global_context

    return get_global_context()


def _poll(predicate, timeout=30.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if predicate():
            return
        time.sleep(0.1)
    pytest.fail(f"timed out waiting for {msg}")


# ---------------------------------------------------------------------------
# lineage reconstruction (N23)
# ---------------------------------------------------------------------------

def test_lineage_reconstruction_after_node_death(ray_start_cluster, tmp_path):
    """Kill the node holding the ONLY copy of a task output: get() must
    re-execute the creating task through lineage and return the value."""
    cluster = ray_start_cluster
    node2 = cluster.add_node(resources={"prod": 1}, num_cpus=2)
    cluster.wait_for_nodes(2)
    tally = str(tmp_path / "executions.log")

    # Soft affinity: first execution lands on node2; the reconstruction
    # re-execution falls back to the surviving node.
    @ray_tpu.remote(
        num_cpus=1,
        max_retries=2,
        scheduling_strategy=NodeAffinitySchedulingStrategy(node2, soft=True),
    )
    def produce():
        with open(tally, "a") as fh:
            fh.write(f"{os.getpid()}\n")
        return np.arange(500_000, dtype=np.float32)  # ~2MB: shm, not inline

    ref = produce.remote()
    ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=120)
    assert ready, "first execution never finished"
    # wait() does not fetch: the only copy lives in node2's store.
    with open(tally) as fh:
        assert len(fh.read().splitlines()) == 1
    state = _ctx()._objects[ref.id]
    assert state.status == "shm"
    assert all(loc["node_id"] == node2 for loc in state.locations)

    cluster.remove_node(node2)
    value = ray_tpu.get(ref, timeout=180)
    assert value.shape == (500_000,)
    assert float(value[123]) == 123.0
    with open(tally) as fh:
        assert len(fh.read().splitlines()) == 2, "task was not re-executed"


def test_reconstruction_disabled_raises_object_lost(
    ray_start_cluster, monkeypatch
):
    """With lineage pinning off, losing every copy surfaces
    ObjectLostError (no silent hang, no bogus value)."""
    from ray_tpu._private.config import global_config

    monkeypatch.setattr(global_config(), "lineage_pinning_enabled", False)
    cluster = ray_start_cluster
    node2 = cluster.add_node(resources={"prod2": 1}, num_cpus=2)
    cluster.wait_for_nodes(2)

    @ray_tpu.remote(
        num_cpus=1,
        scheduling_strategy=NodeAffinitySchedulingStrategy(node2, soft=True),
    )
    def produce():
        return np.ones(500_000, dtype=np.float32)

    ref = produce.remote()
    ready, _ = ray_tpu.wait([ref], num_returns=1, timeout=120)
    assert ready
    cluster.remove_node(node2)
    with pytest.raises(exceptions.ObjectLostError):
        ray_tpu.get(ref, timeout=120)


# ---------------------------------------------------------------------------
# borrowed-reference protocol table tests (N21)
# ---------------------------------------------------------------------------

@ray_tpu.remote
class _Holder:
    """Borrower actor: receives ObjectRefs NESTED in a list so the ref
    itself (not the resolved value) crosses the wire."""

    def __init__(self):
        self.held = None

    def hold(self, boxed):
        self.held = boxed[0]
        return True

    def peek(self):
        return float(ray_tpu.get(self.held).sum())

    def drop(self):
        self.held = None
        gc.collect()
        return True


def _shm_ref():
    # > max_direct_call_object_size so the value lives in the store and
    # freeing is observable.
    return ray_tpu.put(np.ones(300_000, dtype=np.uint8))


def test_borrow_keeps_object_alive_after_owner_drop(ray_start_shared):
    """Ordering: borrow registered -> owner drops -> borrower reads ->
    borrower drops -> object freed."""
    ctx = _ctx()
    holder = _Holder.remote()
    ref = _shm_ref()
    rid = ref.id
    assert ray_tpu.get(holder.hold.remote([ref]), timeout=60)
    _poll(lambda: ctx._borrowers.get(rid), msg="borrow registration")

    del ref
    gc.collect()
    time.sleep(0.5)
    # Borrower keeps it alive despite zero owner-local references.
    assert rid in ctx._objects
    assert ray_tpu.get(holder.peek.remote(), timeout=60) == 300_000.0

    assert ray_tpu.get(holder.drop.remote(), timeout=60)
    _poll(
        lambda: rid not in ctx._objects,
        msg="free after last borrower released",
    )
    ray_tpu.kill(holder)


def test_borrower_drop_first_then_owner(ray_start_shared):
    """Ordering: borrower drops while the owner still holds -> object
    survives; owner drop then frees it."""
    ctx = _ctx()
    holder = _Holder.remote()
    ref = _shm_ref()
    rid = ref.id
    ray_tpu.get(holder.hold.remote([ref]), timeout=60)
    _poll(lambda: ctx._borrowers.get(rid), msg="borrow registration")

    ray_tpu.get(holder.drop.remote(), timeout=60)
    _poll(lambda: not ctx._borrowers.get(rid), msg="borrower deregistration")
    time.sleep(0.2)
    assert rid in ctx._objects  # owner's local ref still pins it
    assert float(ray_tpu.get(ref, timeout=60).sum()) == 300_000.0

    del ref
    gc.collect()
    _poll(lambda: rid not in ctx._objects, msg="free after owner drop")
    ray_tpu.kill(holder)


def test_submitted_ref_pins_args_until_task_done(ray_start_shared):
    """A ref passed as a task arg stays alive through execution even if
    the caller drops it right after submission (submitted-ref count)."""

    @ray_tpu.remote
    def slow_sum(arr):
        time.sleep(1.0)
        return float(arr.sum())

    ctx = _ctx()
    ref = _shm_ref()
    rid = ref.id
    out = slow_sum.remote(ref)
    del ref
    gc.collect()
    time.sleep(0.2)
    assert rid in ctx._objects, "submitted-ref count failed to pin the arg"
    assert ray_tpu.get(out, timeout=60) == 300_000.0
    _poll(lambda: rid not in ctx._objects, msg="free after task completion")


def test_ref_finalized_while_the_refs_lock_is_held_does_not_wait(ray_start_shared):
    """The collector runs ObjectRef.__del__ between any two bytecodes of the
    thread it interrupts, also inside a block of that thread that holds the
    refs lock. A finalizer that waited for the lock there would wait for
    its own thread, for ever: the drop is queued, and applied by the next
    ref that is made or dropped."""
    import threading

    ctx = _ctx()
    ref = ray_tpu.put("held")
    rid = ref.id
    ref._runtime = None  # the drop below stands in for this handle's finalizer
    with ctx._refs_lock:  # what the interrupted block holds
        dropper = threading.Thread(target=ctx.remove_local_ref, args=(rid,))
        dropper.start()
        dropper.join(timeout=30)
        assert not dropper.is_alive(), "a finalizer waited for the refs lock"
        assert rid in ctx._objects
    ray_tpu.put("next")  # any later ref applies what was queued
    assert rid not in ctx._objects


def test_nested_ref_inside_put_value(ray_start_shared):
    """put([inner_ref]): the outer value pins the inner object; dropping
    the outer frees the chain (contained-borrow handling)."""
    ctx = _ctx()
    inner = _shm_ref()
    inner_id = inner.id
    outer = ray_tpu.put([inner, "tag"])
    del inner
    gc.collect()
    time.sleep(0.3)
    assert inner_id in ctx._objects, "outer value failed to pin nested ref"
    got_inner, tag = ray_tpu.get(outer, timeout=60)
    assert tag == "tag"
    assert float(ray_tpu.get(got_inner, timeout=60).sum()) == 300_000.0


def test_borrower_sees_value_after_owner_worker_count_table(ray_start_shared):
    """Table run: every release ordering of (owner, borrower_a,
    borrower_b) keeps the object alive exactly until the last holder."""
    ctx = _ctx()
    orderings = [
        ("owner", "a", "b"),
        ("a", "owner", "b"),
        ("a", "b", "owner"),
    ]
    for ordering in orderings:
        ref = _shm_ref()
        rid = ref.id
        a = _Holder.remote()
        b = _Holder.remote()
        ray_tpu.get([a.hold.remote([ref]), b.hold.remote([ref])], timeout=60)
        _poll(
            lambda: len(ctx._borrowers.get(rid, ())) >= 2,
            msg=f"two borrows registered ({ordering})",
        )
        holders = {"owner": None, "a": a, "b": b}
        live = dict(holders)
        for who in ordering:
            if who == "owner":
                del ref
                gc.collect()
            else:
                ray_tpu.get(live[who].drop.remote(), timeout=60)
            live.pop(who)
            if live:
                time.sleep(0.3)
                assert rid in ctx._objects, (
                    f"object freed early: ordering={ordering}, "
                    f"released={who}, live={sorted(live)}"
                )
                # any remaining borrower can still read it
                reader = next(
                    (h for name, h in live.items() if name != "owner"), None
                )
                if reader is not None:
                    assert ray_tpu.get(
                        reader.peek.remote(), timeout=60
                    ) == 300_000.0
        _poll(
            lambda: rid not in ctx._objects,
            msg=f"free after last holder ({ordering})",
        )
        ray_tpu.kill(a)
        ray_tpu.kill(b)
