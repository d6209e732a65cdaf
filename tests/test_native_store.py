"""Native arena store: allocator, eviction, spill, client-ref protection
(reference: plasma `store.cc`, `eviction_policy.h`, `plasma_allocator.h`)."""

import asyncio
import os

import pytest

from ray_tpu._private.native_store import ArenaStore, load
from ray_tpu._private.object_store import NodeObjectStore, ObjectStoreFullError

pytestmark = pytest.mark.skipif(load() is None,
                                reason="native toolchain unavailable")


def oid(i: int) -> bytes:
    return i.to_bytes(4, "little") + b"\x00" * 24


@pytest.fixture
def arena(tmp_path):
    store = ArenaStore(str(tmp_path / "arena"), 1 << 20)
    yield store
    store.close()


def test_create_seal_get_roundtrip(arena):
    off = arena.create(oid(1), 1000)
    assert off is not None
    assert arena.get(oid(1)) is None        # unsealed: not visible
    arena.seal(oid(1))
    assert arena.get(oid(1)) == (off, 1000)
    assert arena.contains(oid(1))


def test_alloc_reuse_after_delete(arena):
    offs = [arena.create(oid(i), 4096) for i in range(10)]
    for i in range(10):
        arena.seal(oid(i))
    for i in range(10):
        arena.delete(oid(i))
    # Freed extents coalesce: one allocation spanning several old ones.
    big = arena.create(oid(100), 30_000)
    assert big is not None


def test_eviction_lru_order(arena):
    i = 0
    while arena.create(oid(i), 4000) is not None:  # fill to capacity
        arena.seal(oid(i))
        i += 1
    # touch object 0 so it is MRU
    arena.get(oid(0))
    evicted = arena.evict_for(4000)
    assert evicted and oid(0) not in evicted  # LRU victims, not the MRU


def test_pinned_and_referenced_not_evicted(arena):
    arena.create(oid(1), 4000)
    arena.seal(oid(1))
    arena.pin(oid(1), True)
    arena.create(oid(2), 4000)
    arena.seal(oid(2))
    arena.addref(oid(2), 1)
    # Fill the rest
    i = 3
    while arena.create(oid(i), 4000) is not None:
        arena.seal(oid(i))
        i += 1
    evicted = arena.evict_for(4000)
    assert oid(1) not in evicted
    assert oid(2) not in evicted
    assert arena.contains(oid(1)) and arena.contains(oid(2))


def test_node_store_spills_pinned_under_pressure(tmp_path):
    store = NodeObjectStore(1 << 20, str(tmp_path), str(tmp_path / "spill"),
                            "ab" * 14)
    assert store.backend == "native"
    # Pinned primaries fill the store completely...
    i = 0
    while store.used + 61 * 1024 <= store.capacity:
        store.create(oid(i), 60 * 1024)
        store.seal(oid(i))
        store.pin(oid(i))
        i += 1
    # ...a new allocation forces a spill, not a failure.
    store.create(oid(1000), 60 * 1024)
    store.seal(oid(1000))
    assert store.num_spills >= 1
    # Spilled object restores transparently on get.
    spilled = [e for e in store._entries.values()
               if e.spilled_path is not None]
    assert spilled
    victim = spilled[0].object_id
    # asyncio.run, not get_event_loop(): an earlier test in the same
    # xdist worker may have closed the thread's default loop.
    path, size, offset = asyncio.run(store.get(victim, timeout=5))
    assert size == 60 * 1024
    assert store.num_restores >= 1
    store.cleanup()


def test_node_store_full_when_everything_referenced(tmp_path):
    store = NodeObjectStore(1 << 20, str(tmp_path), str(tmp_path / "spill"),
                            "cd" * 14)
    i = 0
    while store.used + 61 * 1024 <= store.capacity:  # fill completely
        store.create(oid(i), 60 * 1024)
        store.seal(oid(i))
        store.pin(oid(i))
        store.addref_client(oid(i))  # live client mappings: unspillable
        i += 1
    with pytest.raises(ObjectStoreFullError):
        store.create(oid(1000), 60 * 1024)
    store.cleanup()


def test_sanitizer_harness_builds_and_passes():
    """ASan+UBSan over the full store ABI from 4 threads (reference
    analogue: the sanitizer CI jobs over plasma). Compiles the harness
    fresh so the sanitized build is exercised, not the cached .so."""
    import shutil
    import subprocess

    if shutil.which("g++") is None:
        import pytest as _pytest

        _pytest.skip("no g++ in this environment")
    native = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native")
    out = subprocess.run(["make", "sanitize"], cwd=native,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout[-1500:] + out.stderr[-1500:]
    assert "SANITIZE-OK" in out.stdout
