"""Paged KV-cache bookkeeping (serve/llm/kv_cache.py): the fixed-pool
block allocator (alloc/free, copy-on-write refcounts, exhaustion, byte
accounting), the prefix cache (hit/miss accounting, LRU eviction, block
ownership, spill hook), and the KV memory hierarchy below HBM
(KVTierManager spill/lookup/pop, budget demotion, PromoteCostModel).

Pure host-side data structures — no JAX, no model; everything here runs
in milliseconds.
"""

import numpy as np
import pytest

from ray_tpu.serve.llm.kv_cache import (
    BlockAllocator, KVPrefix, KVTierManager, PrefixCache,
    PromoteCostModel, hash_prefix, stable_hash_prefix,
)


class TestBlockAllocator:
    def test_alloc_free_roundtrip(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        assert a.free_blocks == 8 and a.used_blocks == 0
        blocks = a.alloc(3)
        assert blocks is not None and len(set(blocks)) == 3
        assert all(0 <= b < 8 for b in blocks)
        assert a.free_blocks == 5 and a.used_blocks == 3
        assert all(a.refcount(b) == 1 for b in blocks)
        a.free(blocks)
        assert a.free_blocks == 8 and a.used_blocks == 0

    def test_alloc_is_all_or_nothing(self):
        a = BlockAllocator(num_blocks=4, block_size=4)
        held = a.alloc(3)
        assert a.alloc(2) is None            # only 1 left: nothing taken
        assert a.free_blocks == 1
        assert a.alloc(1) is not None        # the remainder still works
        a.free(held)

    def test_refcount_free_decrements_before_releasing(self):
        a = BlockAllocator(num_blocks=4, block_size=4)
        (b,) = a.alloc(1)
        a.incref([b])
        assert a.refcount(b) == 2
        a.free([b])                          # 2 -> 1: still allocated
        assert a.refcount(b) == 1 and a.used_blocks == 1
        a.free([b])                          # 1 -> 0: back in the pool
        assert a.used_blocks == 0

    def test_double_free_raises(self):
        a = BlockAllocator(num_blocks=4, block_size=4)
        (b,) = a.alloc(1)
        a.free([b])
        with pytest.raises(ValueError):
            a.free([b])

    def test_fork_shares_blocks(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        blocks = a.alloc(3)
        child = a.fork(blocks)
        assert child == blocks               # same physical blocks
        assert all(a.refcount(b) == 2 for b in blocks)
        a.free(child)
        assert all(a.refcount(b) == 1 for b in blocks)

    def test_copy_on_write(self):
        a = BlockAllocator(num_blocks=8, block_size=4)
        (b,) = a.alloc(1)
        # Sole owner: write in place, no copy.
        nb, needs_copy = a.copy_on_write(b)
        assert nb == b and not needs_copy
        # Shared: writer gets a fresh block, sharer keeps the old one.
        a.incref([b])
        nb, needs_copy = a.copy_on_write(b)
        assert nb != b and needs_copy
        assert a.refcount(b) == 1 and a.refcount(nb) == 1

    def test_copy_on_write_exhaustion_raises(self):
        a = BlockAllocator(num_blocks=1, block_size=4)
        (b,) = a.alloc(1)
        a.incref([b])
        with pytest.raises(MemoryError):
            a.copy_on_write(b)               # shared, but pool is empty


class TestPrefixCache:
    def _setup(self, num_blocks=16, bs=4, max_blocks=None):
        a = BlockAllocator(num_blocks=num_blocks, block_size=bs)
        return a, PrefixCache(a, max_blocks=max_blocks)

    def test_hash_prefix_is_deterministic(self):
        assert hash_prefix([1, 2, 3]) == hash_prefix((1, 2, 3))
        assert hash_prefix([1, 2, 3]) != hash_prefix([1, 2, 4])

    def test_miss_then_hit(self):
        a, pc = self._setup()
        tokens = list(range(12))             # 3 full blocks
        assert pc.match(tokens) == []
        blocks = a.alloc(3)
        pc.insert(tokens, blocks)
        hit = pc.match(tokens)
        assert hit == blocks                 # deepest chain, in order
        st = pc.stats()
        assert st["hits"] >= 1 and st["misses"] >= 1
        assert st["hit_tokens"] == 12
        # The hit incref'd for the caller: cache ref + caller ref.
        assert all(a.refcount(b) == 3 for b in blocks)

    def test_partial_prefix_hit_and_cap(self):
        a, pc = self._setup()
        tokens = list(range(12))
        blocks = a.alloc(3)
        pc.insert(tokens, blocks)
        # A longer prompt sharing the first 8 tokens hits 2 blocks.
        hit = pc.match(tokens[:8] + [99, 98, 97, 96])
        assert hit == blocks[:2]
        a.free(hit)
        # max_blocks caps the walk depth.
        hit = pc.match(tokens, max_blocks=1)
        assert hit == blocks[:1]
        a.free(hit)

    def test_lru_eviction_frees_blocks(self):
        a, pc = self._setup(num_blocks=16, max_blocks=2)
        t1, t2 = list(range(8)), list(range(100, 108))
        b1, b2 = a.alloc(2), a.alloc(2)
        pc.insert(t1, b1)
        pc.insert(t2, b2)                    # overflow: t1 is coldest
        assert pc.stats()["evictions"] == 2
        assert pc.match(t1) == []            # evicted
        hit = pc.match(t2)
        assert hit == b2                     # survivor intact
        a.free(hit)
        # Engine refs remain: eviction dropped only the CACHE's refs.
        assert all(a.refcount(b) == 1 for b in b1)

    def test_explicit_evict_and_clear(self):
        a, pc = self._setup()
        used_before = a.used_blocks
        blocks = a.alloc(2)
        pc.insert(list(range(8)), blocks)
        a.free(blocks)                       # engine done; cache holds on
        assert a.used_blocks == used_before + 2
        pc.evict(1)
        assert a.used_blocks == used_before + 1
        pc.clear()
        assert a.used_blocks == used_before
        assert pc.stats()["entries"] == 0

    def test_byte_accounting(self):
        a = BlockAllocator(num_blocks=8, block_size=4, block_bytes=1024)
        pc = PrefixCache(a)
        assert a.free_bytes == 8 * 1024 and a.used_bytes == 0
        blocks = a.alloc(2)
        assert a.used_bytes == 2048
        assert a.stats()["block_bytes"] == 1024
        pc.insert(list(range(8)), blocks)
        hit = pc.match(list(range(8)))
        a.free(hit)
        st = pc.stats()
        assert st["hit_bytes"] == 2 * 1024
        pc.clear()
        assert pc.stats()["evicted_bytes"] == 2 * 1024
        a.free(blocks)
        assert a.used_bytes == 0

    def test_spill_hook_sees_victims_before_free(self):
        """The spill hook fires while the cache still owns the victim
        blocks (refcount alive — HBM rows still valid), in eviction
        order, with the covered token prefix attached; a hook that
        raises is counted and never blocks the eviction."""
        a = BlockAllocator(num_blocks=8, block_size=4, block_bytes=64)
        pc = PrefixCache(a)
        tokens = list(range(8))
        blocks = a.alloc(2)
        pc.insert(tokens, blocks)
        a.free(blocks)                       # cache is the only owner
        seen = []

        def hook(victims):
            for e in victims:
                # cache ref still held: the block is NOT free yet
                assert a.refcount(e.block) >= 1
                seen.append((e.depth, tuple(e.tokens)))
            return len(victims)

        pc.spill_fn = hook
        assert pc.evict(2) == 2
        assert (1, tuple(tokens[:4])) in seen
        assert (2, tuple(tokens)) in seen
        st = pc.stats()
        assert st["spilled"] == 2 and st["spilled_bytes"] == 2 * 64
        assert a.used_blocks == 0            # eviction still freed them

        # A raising hook: counted, eviction proceeds.
        blocks = a.alloc(2)
        pc.insert(list(range(100, 108)), blocks)
        a.free(blocks)
        pc.spill_fn = lambda victims: 1 / 0
        assert pc.evict(2) == 2
        assert pc.stats()["spill_errors"] == 1
        assert a.used_blocks == 0

    def test_snapshot_heads_stable_and_hot_first(self):
        a, pc = self._setup()
        t1, t2 = list(range(8)), list(range(50, 58))
        b1, b2 = a.alloc(2), a.alloc(2)
        pc.insert(t1, b1)
        pc.insert(t2, b2)
        a.free(pc.match(t1))                 # t1 most recently matched
        heads = pc.snapshot_heads()
        assert heads[0] == (stable_hash_prefix(t1), 2)
        assert (stable_hash_prefix(t2[:4]), 1) in heads
        assert pc.snapshot_heads(max_heads=1) == heads[:1]
        a.free(b1), a.free(b2)


def _prefix(tokens, bs=4, n_blocks=None, fill=1.0):
    """A KVPrefix covering ``tokens`` whose payload is the LAST
    ``n_blocks`` blocks (default: the final chain link only)."""
    tokens = tuple(tokens)
    nb = 1 if n_blocks is None else n_blocks
    kb = np.full((2, nb, bs, 1, 2), fill, np.float32)
    return KVPrefix(tokens=tokens, block_size=bs,
                    blocks={"k": kb, "v": kb * 2})


class TestKVTierManager:
    def test_spill_lookup_pop_roundtrip(self):
        tm = KVTierManager(host_budget_bytes=1 << 20, block_size=4)
        tokens = list(range(12))             # 3 chain links
        chain = [_prefix(tokens[: (j + 1) * 4], fill=float(j))
                 for j in range(3)]
        assert tm.spill(chain) == 3
        hits = tm.lookup(tokens + [99], 4)
        assert [h.tier for h in hits] == ["host"] * 3
        assert [len(h.prefix.tokens) for h in hits] == [4, 8, 12]
        # payloads come back bitwise
        assert np.array_equal(hits[1].prefix.blocks["k"],
                              chain[1].blocks["k"])
        # lookup is non-destructive; pop commits consumption
        assert len(tm) == 3
        tm.pop(hits[:2])
        assert len(tm) == 1
        st = tm.stats()
        assert st["host"]["spills"] == 3
        assert st["host"]["promotes"] == 2
        assert st["host"]["hits"] == 3

    def test_lookup_continues_from_hbm_depth_and_caps(self):
        tm = KVTierManager(host_budget_bytes=1 << 20, block_size=4)
        tokens = list(range(16))
        tm.spill([_prefix(tokens[: (j + 1) * 4]) for j in range(4)])
        hits = tm.lookup(tokens, 4, start_depth=2)
        assert [len(h.prefix.tokens) for h in hits] == [12, 16]
        hits = tm.lookup(tokens, 4, start_depth=1, max_blocks=1)
        assert [len(h.prefix.tokens) for h in hits] == [8]

    def test_hash_collision_verified_against_tokens(self):
        """A tier hit must match the real tokens, not just the key —
        plant a colliding entry and the lookup rejects it."""
        tm = KVTierManager(host_budget_bytes=1 << 20, block_size=4)
        tokens = list(range(8))
        evil = _prefix([7, 7, 7, 7, 7, 7, 7, 7])
        tm._host[hash_prefix(tuple(tokens))] = evil  # forged key
        assert tm.lookup(tokens, 4) == []
        assert tm.stats()["host"]["misses"] >= 1

    def test_budget_demotes_to_store_and_promotes_back(self):
        store = {}

        def put_fn(p):
            ref = f"ref{len(store)}"
            store[ref] = p
            return ref

        one = _prefix(list(range(4))).payload_bytes
        tm = KVTierManager(host_budget_bytes=one, block_size=4,
                           put_fn=put_fn, get_fn=store.get)
        t1, t2 = list(range(4)), list(range(40, 44))
        tm.spill([_prefix(t1)])
        tm.spill([_prefix(t2)])              # over budget: t1 demotes
        st = tm.stats()
        assert st["host"]["blocks"] == 1 and st["store"]["blocks"] == 1
        assert st["store"]["spills"] == 1
        (hit,) = tm.lookup(t1, 4)
        assert hit.tier == "store"
        assert tuple(hit.prefix.tokens) == tuple(t1)
        tm.pop([hit])
        assert tm.stats()["store"]["promotes"] == 1

    def test_no_store_fn_drops_and_counts(self):
        one = _prefix(list(range(4))).payload_bytes
        tm = KVTierManager(host_budget_bytes=one, block_size=4)
        tm.spill([_prefix(list(range(4)))])
        tm.spill([_prefix(list(range(40, 44)))])
        st = tm.stats()
        assert st["host"]["blocks"] == 1
        assert tm.dropped_blocks == 1 and tm.dropped_bytes == one

    def test_invalid_prefix_rejected(self):
        tm = KVTierManager(host_budget_bytes=1 << 20, block_size=4)
        bad = _prefix(list(range(6)))        # not whole blocks
        assert tm.spill([bad]) == 0
        assert len(tm) == 0

    def test_stable_heads(self):
        tm = KVTierManager(host_budget_bytes=1 << 20, block_size=4)
        tokens = list(range(8))
        tm.spill([_prefix(tokens[:4]), _prefix(tokens)])
        heads = tm.stable_heads()
        assert (stable_hash_prefix(tokens[:4]), 1) in heads
        assert heads[0] == (stable_hash_prefix(tokens), 2)  # hottest


class TestPromoteCostModel:
    def test_default_crossover(self):
        """With the TPU-default costs (2ms fixed adopt + 0.1ms/block vs
        0.05ms/token prefill at bs=16), recompute wins short chains and
        the scatter wins from 3 blocks on — and once promotion wins it
        keeps winning (both costs are linear)."""
        cm = PromoteCostModel()
        assert not cm.should_promote(1, 16)
        assert not cm.should_promote(2, 16)
        assert cm.should_promote(3, 16)
        assert all(cm.should_promote(n, 16) for n in range(3, 64))

    def test_costs_scale(self):
        cm = PromoteCostModel(adopt_fixed_s=1.0, adopt_per_block_s=0.1,
                              prefill_per_token_s=0.0)
        assert cm.promote_cost_s(5) == pytest.approx(1.5)
        assert cm.recompute_cost_s(100) == 0.0
        assert not cm.should_promote(50, 16)  # free recompute never loses


def test_stable_hash_crosses_processes_and_types():
    """The wire hash must not depend on PYTHONHASHSEED or container
    type, and must see token VALUES (crc32 over the int64 stream)."""
    assert stable_hash_prefix([1, 2, 3]) == stable_hash_prefix((1, 2, 3))
    assert stable_hash_prefix(np.asarray([1, 2, 3])) \
        == stable_hash_prefix([1, 2, 3])
    assert stable_hash_prefix([1, 2, 3]) != stable_hash_prefix([1, 2, 4])


def test_kv_prefix_validation():
    good = _prefix(list(range(8)), n_blocks=2)
    good.validate()
    with pytest.raises(ValueError):
        _prefix(list(range(6))).validate()          # partial block
    with pytest.raises(ValueError):
        _prefix(list(range(4)), n_blocks=2).validate()  # blocks > prefix
