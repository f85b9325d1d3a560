"""Native shared-memory object store tests.

Models the reference's plasma tests (src/ray/object_manager/plasma/ test
coverage): create/seal/get semantics, blocking gets, eviction under
pressure, spill + transparent restore, connection-drop cleanup.
"""

import os
import threading
import time

import pytest

from ray_tpu._private.object_store import (
    ObjectStoreClient,
    ObjectStoreFull,
    ObjectStoreServer,
)


@pytest.fixture
def store(tmp_path):
    sock = str(tmp_path / "store.sock")
    shm = f"/dev/shm/raytpu-test-{os.getpid()}-{time.monotonic_ns()}"
    capacity = 1 << 20
    server = ObjectStoreServer(sock, shm, capacity, spill_dir=str(tmp_path / "spill"))
    client = ObjectStoreClient(sock, shm, capacity)
    yield client, sock, shm, capacity
    client.close()
    server.stop()


def test_put_get_roundtrip(store):
    client, *_ = store
    client.put("a", b"hello")
    view = client.get("a")
    assert bytes(view) == b"hello"
    client.release("a")
    assert client.contains("a")
    assert not client.contains("nope")


def test_get_is_zero_copy_view(store):
    client, *_ = store
    data = os.urandom(4096)
    client.put("z", data)
    view = client.get("z")
    assert isinstance(view, memoryview)
    assert view.readonly
    assert bytes(view) == data
    client.release("z")


def test_blocking_get_wakes_on_seal(store):
    client, sock, shm, capacity = store
    other = ObjectStoreClient(sock, shm, capacity)
    result = []
    thread = threading.Thread(
        target=lambda: result.append(bytes(other.get("late", timeout_ms=5000)))
    )
    thread.start()
    time.sleep(0.05)
    client.put("late", b"worth-the-wait")
    thread.join(timeout=5)
    assert result == [b"worth-the-wait"]
    other.close()


def test_get_timeout(store):
    client, *_ = store
    start = time.monotonic()
    assert client.get("missing", timeout_ms=100) is None
    assert time.monotonic() - start < 2.0


def test_eviction_spills_and_restores(store):
    client, *_ = store
    # 10 x 200KB into a 1MB arena forces eviction+spill.
    blobs = {f"big-{i}": bytes([i]) * (200 * 1024) for i in range(10)}
    for key, blob in blobs.items():
        client.put(key, blob)
    stats = client.stats()
    assert stats["evictions"] > 0
    assert stats["spilled_bytes"] > 0
    # Everything still readable (spilled copies restore transparently).
    for key, blob in blobs.items():
        view = client.get(key, timeout_ms=0)
        assert view is not None and bytes(view[:1]) == blob[:1]
        client.release(key)
    assert client.stats()["restores"] > 0


def test_pinned_objects_survive_pressure(store):
    client, *_ = store
    client.put("pinned", b"p" * (100 * 1024))
    client.pin("pinned")
    for i in range(12):
        client.put(f"filler-{i}", bytes(150 * 1024))
    info = client.list()["pinned"]
    assert not info["spilled"]
    client.unpin("pinned")


def test_delete(store):
    client, *_ = store
    client.put("d", b"x")
    assert client.delete("d")
    assert not client.contains("d")
    assert not client.delete("d")


def test_store_full_without_spill(tmp_path):
    sock = str(tmp_path / "s2.sock")
    shm = f"/dev/shm/raytpu-test2-{os.getpid()}-{time.monotonic_ns()}"
    server = ObjectStoreServer(sock, shm, 256 * 1024, spill_dir=None)
    client = ObjectStoreClient(sock, shm, 256 * 1024)
    try:
        client.put("keep", bytes(100 * 1024))
        client.pin("keep")
        with pytest.raises(ObjectStoreFull):
            client.put("toobig", bytes(400 * 1024))
    finally:
        client.close()
        server.stop()


def test_native_rebuild_is_keyed_on_source_hash_not_mtime(tmp_path):
    """The libraries are not in git; what decides a rebuild is the hash of
    the sources recorded beside the library. mtimes say nothing after a
    checkout or a copy to another machine: a touched source with the same
    bytes is not rebuilt, and changed bytes are — even under a source
    mtime OLDER than the library's."""
    from ray_tpu import _native

    src = tmp_path / "a.cc"
    src.write_text('extern "C" int f() { return 1; }\n')
    lib = str(tmp_path / "liba.so")
    flags = ["-shared", "-fPIC"]

    def built():
        _native._compile(lib, flags, [str(src)], False)
        with open(lib + ".srchash") as fh:
            return os.stat(lib).st_ino, fh.read()

    first = built()
    os.utime(src, (time.time() + 3600, time.time() + 3600))
    assert built() == first  # newer mtime, same bytes: kept
    src.write_text('extern "C" int f() { return 2; }\n')
    os.utime(src, (0, 0))
    rebuilt = built()  # older mtime, other bytes: rebuilt
    assert rebuilt[0] != first[0] and rebuilt[1] != first[1]
    os.remove(lib)
    assert built()[1] == rebuilt[1]  # library gone, hash file left: rebuilt
    assert os.path.exists(lib)
