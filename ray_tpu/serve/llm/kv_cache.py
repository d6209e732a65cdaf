"""Paged KV-cache bookkeeping: block allocator + token-prefix cache.

The HBM side lives with the model (`config.serving().init_pool`
allocates one fixed pool of ``[block_size]``-row blocks per layer, a
small dict of leaves the model names; its paged decode / prefill read
and write it through per-sequence *block tables*:
:mod:`ray_tpu.models.serving`). This module is the host side: which
physical block belongs to whom, and which prompt prefixes are already
resident so admission can skip their prefill entirely.

Two pieces, both pure host-Python (no jax imports — unit-testable
without a device):

- :class:`BlockAllocator` — a fixed pool of ``num_blocks`` block ids
  with per-block reference counts. ``alloc`` hands out free ids or
  reports exhaustion (the engine *queues* the request — never crashes);
  ``incref``/``free`` implement copy-on-write sharing: a block reaching
  refcount 0 returns to the free list, a shared block stays resident
  until its last reader releases it. ``copy_on_write`` gives a private
  copy id for a shared block about to be mutated (the engine's sharing
  is block-aligned — only *full* prompt blocks are ever shared, and
  sequences write strictly past them — so the engine never triggers the
  copy; the primitive is here, and tested, for sub-block sharing).

- :class:`PrefixCache` — RadixAttention-style reuse keyed on the hash
  of the token prefix at every block boundary (a hash chain rather than
  a radix tree: block-granular lookups need only exact block-boundary
  matches). ``match`` walks the chain and increfs every hit block for
  the caller; ``insert`` registers a finished prompt's full blocks,
  taking cache-owned refs so blocks outlive the sequence that produced
  them; LRU eviction frees the coldest tails when the allocator runs
  dry (vLLM: "Efficient Memory Management for LLM Serving with
  PagedAttention"; SGLang: RadixAttention).

- :class:`KVTierManager` — the memory hierarchy below HBM. An evicted
  prefix block no longer vanishes: the engine's spill hook gathers its
  rows off the pool (one `_export_fn` dispatch per eviction batch) and
  parks them here, first in host RAM (bounded by
  ``EngineConfig.kv_host_tier_bytes``), demoting LRU entries to the object
  store when the host tier overflows (``put_fn``/``get_fn`` — wired to
  ``ray_tpu.put``/``get`` by the deployment; absent a cluster, cold
  overflow is dropped and counted). A re-admitted prompt that misses
  HBM but hits a tier re-adopts the blocks through the engine's
  `_adopt_fn` scatter instead of re-prefilling — when the
  :class:`PromoteCostModel` says the scatter beats recompute.
"""

from __future__ import annotations

import threading
import zlib
from collections import OrderedDict, deque
from dataclasses import dataclass, field
from typing import (Any, Callable, Dict, List, Optional, Sequence, Tuple)

__all__ = [
    "BlockAllocator", "KVPrefix", "KVState", "KVTierManager",
    "PrefixCache", "PromoteCostModel", "TierHit", "WindowRing",
    "hash_prefix", "stable_hash_prefix",
]


@dataclass
class KVState:
    """A sequence's paged KV checkpoint, detached from any engine.

    The unit of KV migration: `LLMEngine._export_state` densifies the
    slot's live blocks into plain ndarrays (one per leaf of the model's
    pool, [L, n_valid, bs, ...]: `k` and `v` for the dense decoder, one
    `latent` row for latent attention; zero-copy through the object
    store), and `LLMEngine.submit_adopted`
    scatters them into another engine's pool. Produced by the
    disaggregated prefill tier (serve/llm/disagg) and by batch-lane
    preemption (the checkpoint that lets a preempted decode resume).

    ``pos`` is the number of CONSUMED tokens — rows [0, pos) of the
    dense view are valid; ``next_tok`` is the last sampled token, not
    yet consumed (the engine's device ``tok`` at export time).
    ``tokens`` are the tokens already emitted to the caller (the first
    sampled token onward), so an adopting engine resumes max_tokens /
    stop accounting exactly where the exporter left off.
    """

    prompt: List[int]
    tokens: List[int]
    next_tok: int
    pos: int
    temperature: float
    block_size: int
    blocks: Dict[str, Any]  # leaf name -> np [L, n_valid, bs, ...]

    @property
    def n_blocks(self) -> int:
        return _n_blocks(self.blocks)

    @property
    def payload_bytes(self) -> int:
        return sum(int(x.nbytes) for x in self.blocks.values())

    def validate(self) -> None:
        bs = self.block_size
        need = -(-self.pos // bs)
        if self.n_blocks != need:
            raise ValueError(
                f"KVState holds {self.n_blocks} blocks but pos="
                f"{self.pos} at block_size={bs} needs {need}")
        _check_leaves(self.blocks, bs)
        if not self.tokens or self.tokens[-1] != self.next_tok:
            raise ValueError(
                "next_tok must be the last emitted token (sampled but "
                "not yet consumed)")
        if self.pos != len(self.prompt) + len(self.tokens) - 1:
            raise ValueError(
                f"pos={self.pos} inconsistent with prompt "
                f"{len(self.prompt)} + emitted {len(self.tokens)} "
                f"(expected prompt + emitted - 1 consumed tokens)")


def _n_blocks(blocks: Dict[str, Any]) -> int:
    return int(next(iter(blocks.values())).shape[1])


def _check_leaves(blocks: Dict[str, Any], block_size: int) -> None:
    """Every leaf holds the same blocks of `block_size` rows."""
    lead = {tuple(x.shape[1:3]) for x in blocks.values()}
    if len(lead) != 1 or next(iter(lead))[1] != block_size:
        raise ValueError(
            f"pool leaves disagree on blocks x rows (block_size "
            f"{block_size}): "
            f"{ {k: tuple(x.shape) for k, x in blocks.items()} }")


def hash_prefix(tokens: Sequence[int]) -> int:
    """Fast key for a token prefix. Python's tuple hash is salted per
    process (PYTHONHASHSEED) which is fine *locally* — each replica
    owns its pool, so its prefix cache is process-local. Anything that
    crosses processes (the cluster-wide prefix index, the GCS
    ``report/lookup_prefix_index`` RPCs) must use
    :func:`stable_hash_prefix` instead."""
    return hash(tuple(tokens))


def stable_hash_prefix(tokens: Sequence[int]) -> int:
    """Process-independent key for a token prefix — the hash that may
    cross the wire. crc32 over the little-endian token stream: cheap,
    deterministic everywhere, and collisions only cost a wasted peer
    probe (every consumer re-verifies against real tokens before
    trusting a match)."""
    import numpy as np

    return int(zlib.crc32(
        np.asarray(tokens, np.int64).tobytes()))


class BlockAllocator:
    """Fixed pool of ``num_blocks`` KV blocks with refcounts.

    Thread-safe: the engine's scheduler thread allocates while the
    dashboard thread reads stats. All ops are O(1) amortized.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 block_bytes: int = 0):
        if num_blocks <= 0 or block_size <= 0:
            raise ValueError(
                f"need positive num_blocks/block_size, got "
                f"{num_blocks}/{block_size}")
        self.num_blocks = num_blocks
        self.block_size = block_size
        # HBM bytes per block (k + v rows across all layers); 0 when
        # the caller doesn't care about byte-level accounting.
        self.block_bytes = int(block_bytes)
        self._free: deque = deque(range(num_blocks))
        self._refs: List[int] = [0] * num_blocks
        self._lock = threading.Lock()

    # -- core ------------------------------------------------------------
    def alloc(self, n: int) -> Optional[List[int]]:
        """n fresh blocks at refcount 1, or None if the pool can't cover
        it (caller queues / evicts; nothing is partially allocated)."""
        with self._lock:
            if n > len(self._free):
                return None
            out = [self._free.popleft() for _ in range(n)]
            for b in out:
                self._refs[b] = 1
            return out

    def incref(self, blocks: Sequence[int]) -> None:
        with self._lock:
            for b in blocks:
                if self._refs[b] <= 0:
                    raise ValueError(f"incref on free block {b}")
                self._refs[b] += 1

    def free(self, blocks: Sequence[int]) -> None:
        """Drop one reference per id; blocks hitting 0 rejoin the pool."""
        with self._lock:
            for b in blocks:
                r = self._refs[b] - 1
                if r < 0:
                    raise ValueError(f"double free of block {b}")
                self._refs[b] = r
                if r == 0:
                    self._free.append(b)

    def fork(self, blocks: Sequence[int]) -> List[int]:
        """Share an existing table (copy-on-write fork): the child holds
        the same physical ids, each with one more reference."""
        self.incref(blocks)
        return list(blocks)

    def copy_on_write(self, block: int) -> Tuple[int, bool]:
        """Prepare ``block`` for mutation. Uniquely-owned blocks are
        returned as-is; shared ones release one ref and return a fresh
        private id (returns (id, needs_copy) — the caller must copy the
        HBM rows when needs_copy). None is never returned: raises on
        exhaustion so callers treat COW pressure as a hard signal."""
        with self._lock:
            if self._refs[block] <= 0:
                raise ValueError(f"copy_on_write on free block {block}")
            if self._refs[block] == 1:
                return block, False
            if not self._free:
                raise MemoryError(
                    "copy_on_write: pool exhausted (free a sequence or "
                    "evict prefix-cache entries first)")
            new = self._free.popleft()
            self._refs[new] = 1
            self._refs[block] -= 1
            return new, True

    # -- migration -------------------------------------------------------
    def adopt(self, n: int,
              prefix_cache: Optional["PrefixCache"] = None
              ) -> Optional[List[int]]:
        """All-or-nothing allocation for an imported/resumed sequence:
        like :meth:`alloc`, but under pressure it first evicts cold
        prefix-cache entries to make room (the same fallback admission
        uses). Returns None — nothing allocated, nothing evicted beyond
        the attempt — when the pool still can't cover ``n``; the caller
        requeues the import and retries as running sequences finish."""
        blocks = self.alloc(n)
        if blocks is None and prefix_cache is not None:
            prefix_cache.evict(n - self.free_blocks)
            blocks = self.alloc(n)
        return blocks

    def donate(self, blocks: Sequence[int]) -> None:
        """Release a live sequence's block refs after its KV has been
        exported (the ownership hand-off half of a migration: the rows
        now live in a :class:`KVState` / another engine's pool, so this
        engine's copies may be recycled). Identical accounting to
        :meth:`free` — the name records intent at export sites, and the
        liveness check catches exporting an already-freed slot."""
        for b in blocks:
            if self.refcount(b) <= 0:
                raise ValueError(
                    f"donate of free block {b}: export must happen "
                    f"before the slot is torn down")
        self.free(blocks)

    # -- introspection ---------------------------------------------------
    def refcount(self, block: int) -> int:
        with self._lock:
            return self._refs[block]

    @property
    def free_blocks(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used_blocks(self) -> int:
        return self.num_blocks - self.free_blocks

    @property
    def used_bytes(self) -> int:
        return self.used_blocks * self.block_bytes

    @property
    def free_bytes(self) -> int:
        return self.free_blocks * self.block_bytes

    def stats(self) -> Dict[str, int]:
        return {
            "num_blocks": self.num_blocks,
            "used_blocks": self.used_blocks,
            "free_blocks": self.free_blocks,
            "block_bytes": self.block_bytes,
            "used_bytes": self.used_bytes,
            "free_bytes": self.free_bytes,
        }


class WindowRing:
    """Host side of a model's WINDOW kind of pool leaves
    (models/serving.py): blocks of their own (`allocator`), and a table
    a slot that is a ring, `ring` blocks wide, in which the block of
    position t is entry `(t // block_size) % ring`.  A sequence takes
    `min(blocks, ring)` entries up front, as it takes the full kind's:
    a chunk's insert finds the window before it and room for its own
    rows.  Once its prompt is in, a sequence writes `lookahead`
    positions a dispatch and reads `window` keys back from the first of
    them: `keep` blocks at a time, whatever its length.  `cover` gives
    the others back before the sequence's first decode dispatch and
    from then on hands the block that fell out of the window to the
    position that comes into it.  Scheduler thread only (the allocator
    has its own lock)."""

    def __init__(self, window: int, ring: int, allocator: BlockAllocator,
                 num_slots: int, lookahead: int = 1):
        import numpy as np

        self.window, self.ring, self.allocator = window, ring, allocator
        # blocks that positions first - window + 1 .. first + lookahead
        # - 1 can straddle
        self.keep = (window + lookahead - 2) // allocator.block_size + 2
        self.tables = np.zeros((num_slots, ring), np.int32)
        self.slot_blocks: List[List[int]] = [[] for _ in range(num_slots)]
        # [lowest, highest] block, by position, of a slot `cover` trimmed
        self._held: List[Optional[List[int]]] = [None] * num_slots

    def blocks_for(self, n_blocks: int) -> int:
        """Of a sequence that holds `n_blocks` of the full kind, at
        admission."""
        return min(n_blocks, self.ring)

    def take(self, slot: int, n_blocks: int) -> bool:
        """`blocks_for(n_blocks)` blocks into `slot`'s ring; False, and
        nothing taken, when the allocator cannot cover them."""
        blocks = self.allocator.alloc(self.blocks_for(n_blocks))
        if blocks is None:
            return False
        self.tables[slot] = 0
        self.tables[slot, :len(blocks)] = blocks
        self.slot_blocks[slot] = blocks
        self._held[slot] = None
        return True

    def cover(self, slot: int, first: int, last: int) -> None:
        """Before a decode dispatch that writes `slot`'s positions
        first .. last (at most `lookahead` of them; the prompt is in)
        and reads the keys first - window + 1 .. last.  A slot that
        holds more than `keep` blocks gives the others back, once; then
        each block a dispatch newly writes is the one that fell out of
        the window `keep` blocks before it."""
        import numpy as np

        bs, ring = self.allocator.block_size, self.ring
        held = self._held[slot]
        if held is None:
            blocks = self.slot_blocks[slot]
            if len(blocks) <= self.keep:
                return          # a block for every position it will write
            lo = max(first - self.window + 1, 0) // bs
            if len(blocks) < ring:      # entries past them hold no block
                lo = min(lo, len(blocks) - self.keep)
            held = self._held[slot] = [lo, lo + self.keep - 1]
            kept = self.tables[
                slot, np.arange(lo, lo + self.keep) % ring].tolist()
            self.slot_blocks[slot] = kept
            self.allocator.free(sorted(set(blocks) - set(kept)))
        while held[1] < last // bs:
            block = self.tables[slot, held[0] % ring]
            held[0] += 1
            held[1] += 1
            self.tables[slot, held[1] % ring] = block

    def release(self, slot: int) -> None:
        self.allocator.free(self.slot_blocks[slot])
        self.slot_blocks[slot] = []

    def block_ids(self, slot: int, start: int, n_rows: int):
        """Physical blocks of positions start .. start + n_rows - 1
        (whole blocks), through the ring (before `cover` trimmed it)."""
        import numpy as np

        bs = self.allocator.block_size
        at = (start // bs + np.arange(n_rows // bs)) % self.ring
        return self.tables[slot, at]

    def stats(self) -> Dict[str, int]:
        return dict(self.allocator.stats(), window=self.window,
                    ring_blocks=self.ring, keep_blocks=self.keep)


@dataclass
class _Entry:
    """One full block of one cached prefix: the chain link at block
    boundary ``depth`` (prefix length = depth * block_size).

    ``tokens`` is the full covered prefix — needed so an evicted entry
    can be spilled down a tier under a key the next admission (or a
    peer replica, via the stable hash) can still resolve, and so tier
    hits verify against real tokens instead of trusting a hash."""
    block: int
    depth: int
    tokens: Tuple[int, ...] = ()
    _stable: Optional[int] = None

    @property
    def stable(self) -> int:
        if self._stable is None:
            self._stable = stable_hash_prefix(self.tokens)
        return self._stable


class PrefixCache:
    """Block-granular prompt-prefix reuse over a :class:`BlockAllocator`.

    Entries are keyed ``hash(tokens[: j * block_size])`` for j = 1..;
    each holds exactly one cache-owned reference on one block. ``match``
    walks j upward until the first miss — the hit blocks cover positions
    ``[0, hits * block_size)`` and arrive *increffed for the caller*
    (the engine later frees them with the rest of the sequence's table,
    no special-casing). Eviction pops least-recently-matched entries;
    an entry's block only truly returns to the pool once every sequence
    still reading it has also released it — refcounts make eviction safe
    mid-flight.
    """

    def __init__(self, allocator: BlockAllocator,
                 max_blocks: Optional[int] = None):
        self.allocator = allocator
        self.max_blocks = (allocator.num_blocks if max_blocks is None
                           else max_blocks)
        self._entries: "OrderedDict[int, _Entry]" = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0            # match() calls that found >= 1 block
        self.misses = 0
        self.hit_tokens = 0      # positions whose prefill was skipped
        self.hit_bytes = 0       # HBM bytes those positions occupy
        self.evictions = 0       # entries evicted (≈ blocks released)
        self.evicted_bytes = 0
        self.spilled = 0         # evicted blocks handed to spill_fn
        self.spilled_bytes = 0
        self.spill_errors = 0
        # Engine-installed eviction hook: called with the victim
        # ``_Entry`` list while their blocks STILL hold the cache ref
        # (the HBM rows are valid until the ``allocator.free`` that
        # follows). Returns how many blocks it actually spilled.
        self.spill_fn: Optional[Callable[[List[_Entry]], int]] = None

    def __len__(self) -> int:
        return len(self._entries)

    # -- lookup ----------------------------------------------------------
    def match(self, tokens: Sequence[int],
              max_blocks: Optional[int] = None) -> List[int]:
        """Longest cached block-chain covering a prefix of ``tokens``.

        Returns the physical block ids (may be empty), each increffed on
        behalf of the caller. ``max_blocks`` caps the hit (the engine
        passes ``(len(prompt) - 1) // block_size`` so at least the last
        prompt token is always prefilled — its logits seed sampling)."""
        bs = self.allocator.block_size
        limit = len(tokens) // bs
        if max_blocks is not None:
            limit = min(limit, max_blocks)
        out: List[int] = []
        with self._lock:
            for j in range(1, limit + 1):
                e = self._entries.get(hash_prefix(tokens[: j * bs]))
                if e is None or e.depth != j:
                    break
                out.append(e.block)
                self._entries.move_to_end(hash_prefix(tokens[: j * bs]))
            if out:
                self.hits += 1
                self.hit_tokens += len(out) * bs
                self.hit_bytes += len(out) * self.allocator.block_bytes
            else:
                self.misses += 1
        if out:
            self.allocator.incref(out)
        return out

    # -- registration ----------------------------------------------------
    def insert(self, tokens: Sequence[int], blocks: Sequence[int]) -> None:
        """Register a prompt's resident full blocks. ``blocks[j]`` must
        hold the KV rows for positions ``[j*bs, (j+1)*bs)`` of
        ``tokens``. Already-cached depths are skipped (the shared block
        is already registered); new depths take one cache-owned ref."""
        bs = self.allocator.block_size
        n = min(len(tokens) // bs, len(blocks))
        fresh: List[Tuple[int, _Entry]] = []
        with self._lock:
            for j in range(1, n + 1):
                key = hash_prefix(tokens[: j * bs])
                if key in self._entries:
                    self._entries.move_to_end(key)
                    continue
                fresh.append((key, _Entry(block=blocks[j - 1], depth=j,
                                          tokens=tuple(tokens[: j * bs]))))
        if not fresh:
            return
        self.allocator.incref([e.block for _, e in fresh])
        with self._lock:
            for key, e in fresh:
                if key in self._entries:       # lost a race: drop our ref
                    self.allocator.free([e.block])
                    continue
                self._entries[key] = e
            overflow = len(self._entries) - self.max_blocks
        if overflow > 0:
            self.evict(overflow)

    # -- eviction --------------------------------------------------------
    def evict(self, n_blocks: int) -> int:
        """Release the ``n_blocks`` least-recently-matched entries'
        cache refs (deepest-first within equal recency, so a chain's
        tail goes before its root and surviving prefixes stay usable).
        Returns how many refs were dropped; the pool only grows by the
        blocks nobody else still reads.

        If the engine installed :attr:`spill_fn`, the victims are
        offered to it *before* their refs drop — at that point the
        cache still owns the blocks, so the hook may gather their HBM
        rows and park them in a lower tier (the engine's hook
        dispatches the gather here and lands its host copy later in
        the same step: the gather is ordered before whatever overwrites
        the freed blocks). Spill failures are counted and never block
        the eviction itself (the pool must grow)."""
        victims: List[_Entry] = []
        with self._lock:
            # LRU order with chain-tail preference: scan from coldest,
            # take deepest entries first among the same prefix family.
            while len(victims) < n_blocks and self._entries:
                # coldest key
                key = next(iter(self._entries))
                victims.append(self._entries.pop(key))
                self.evictions += 1
        if not victims:
            return 0
        self.evicted_bytes += len(victims) * self.allocator.block_bytes
        if self.spill_fn is not None:
            try:
                n = int(self.spill_fn(victims))
                self.spilled += n
                self.spilled_bytes += n * self.allocator.block_bytes
            except Exception:
                self.spill_errors += 1
        self.allocator.free([e.block for e in victims])
        return len(victims)

    def spill_failed(self, n_blocks: int) -> None:
        """A hook that defers its copy (the engine lands it behind the
        next tick) reports here what it had counted as spilled and
        then lost: the same one error a raising hook counts."""
        self.spill_errors += 1
        self.spilled -= n_blocks
        self.spilled_bytes -= n_blocks * self.allocator.block_bytes

    def clear(self) -> None:
        self.evict(len(self._entries))

    def snapshot_heads(self, max_heads: int = 512) -> List[Tuple[int, int]]:
        """Hottest cached chain links as ``(stable_hash, depth)`` pairs,
        most-recently-matched first — what a replica publishes to the
        cluster-wide prefix index. Uses :func:`stable_hash_prefix` so
        peers can compare against their own prompts; entries inserted
        without tokens (pre-tiering callers) are skipped."""
        with self._lock:
            entries = [e for e in reversed(self._entries.values())
                       if e.tokens][:max_heads]
        return [(e.stable, e.depth) for e in entries]

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {
                "entries": len(self._entries),
                "hits": self.hits,
                "misses": self.misses,
                "hit_tokens": self.hit_tokens,
                "hit_bytes": self.hit_bytes,
                "evictions": self.evictions,
                "evicted_bytes": self.evicted_bytes,
                "spilled": self.spilled,
                "spilled_bytes": self.spilled_bytes,
                "spill_errors": self.spill_errors,
            }


@dataclass
class KVPrefix:
    """One spilled prefix block, detached from any pool.

    The tier-resident sibling of :class:`KVState`: where KVState
    checkpoints a *live sequence* (sampling state, emitted tokens),
    KVPrefix carries only the KV rows of full prompt blocks — no
    ``next_tok``/``pos`` semantics, because a promoted prefix re-enters
    through admission, not through resume. ``tokens`` is the full
    covered prefix; the payload holds its LAST ``n_blocks`` blocks
    (spilled chain links carry one block each — the earlier links are
    their own entries), and doubles as the collision check for
    hash-keyed tier lookups. Plain ndarrays so the object-store tier
    holds it zero-copy.
    """

    tokens: Tuple[int, ...]
    block_size: int
    blocks: Dict[str, Any]  # leaf name -> np [L, n_blocks, bs, ...]

    @property
    def n_blocks(self) -> int:
        return _n_blocks(self.blocks)

    @property
    def payload_bytes(self) -> int:
        return sum(int(x.nbytes) for x in self.blocks.values())

    def validate(self) -> None:
        if not self.tokens or len(self.tokens) % self.block_size:
            raise ValueError(
                f"KVPrefix must cover whole blocks, got "
                f"{len(self.tokens)} tokens at block_size "
                f"{self.block_size}")
        if self.n_blocks * self.block_size > len(self.tokens):
            raise ValueError(
                f"KVPrefix holds {self.n_blocks} blocks but the "
                f"covered prefix is only {len(self.tokens)} tokens")
        _check_leaves(self.blocks, self.block_size)


@dataclass
class PromoteCostModel:
    """Is the scatter cheaper than the recompute?

    Promoting n tier blocks back into HBM costs a fixed dispatch (host
    staging + one `_adopt_fn` launch) plus a per-block transfer;
    recomputing costs prefill over the covered tokens. Short suffixes
    lose to recompute — prefill is one fused program and the fixed
    adopt cost dominates — so admission only promotes when the model
    says the crossover is passed. The engine builds it from
    ``EngineConfig.kv_adopt_cost_*`` / ``kv_prefill_cost_per_token_ms``;
    benches overwrite them with measured numbers.
    """

    adopt_fixed_s: float = 2e-3
    adopt_per_block_s: float = 1e-4
    prefill_per_token_s: float = 5e-5

    def promote_cost_s(self, n_blocks: int) -> float:
        return self.adopt_fixed_s + n_blocks * self.adopt_per_block_s

    def recompute_cost_s(self, n_tokens: int) -> float:
        return n_tokens * self.prefill_per_token_s

    def should_promote(self, n_blocks: int, block_size: int) -> bool:
        return (self.promote_cost_s(n_blocks)
                < self.recompute_cost_s(n_blocks * block_size))


@dataclass
class TierHit:
    """One tier-lookup result: where ``prefix`` was found and under
    which key, so a successful promote can :meth:`KVTierManager.pop`
    exactly what it consumed (all-or-nothing: nothing is popped until
    the scatter landed)."""
    key: int
    tier: str
    prefix: KVPrefix


class KVTierManager:
    """Host-RAM + object-store tiers below the HBM block pool.

    Spilled blocks land in an LRU host dict bounded by
    ``host_budget_bytes``; overflow demotes the coldest entries to the
    object store via ``put_fn`` (→ ``ray_tpu.put``) when a cluster is
    attached, else drops them (counted — a dropped block just means a
    future recompute, never an error). ``lookup`` extends an HBM
    partial hit with the longest contiguous tier run; ``pop`` commits
    consumption after the engine's scatter succeeded.

    Keys are process-local :func:`hash_prefix` values — the manager
    lives and dies with its engine. What crosses processes is the
    *stable* hash (:meth:`stable_heads`, the cluster index) and the
    KVPrefix payloads themselves (peer pull), both of which re-verify
    against real tokens here before anything is trusted.

    Thread-safe: the engine scheduler spills/promotes while dashboard
    and publisher threads read stats/heads.
    """

    TIERS = ("host", "store")

    def __init__(self, host_budget_bytes: int, block_size: int = 16,
                 put_fn: Optional[Callable[[Any], Any]] = None,
                 get_fn: Optional[Callable[[Any], Any]] = None):
        self.host_budget_bytes = int(host_budget_bytes)
        self.block_size = int(block_size)
        self.put_fn = put_fn
        self.get_fn = get_fn
        self._host: "OrderedDict[int, KVPrefix]" = OrderedDict()
        self._store: "OrderedDict[int, Tuple[Any, Tuple[int, ...], int]]" \
            = OrderedDict()          # key -> (ref, tokens, payload_bytes)
        self._host_bytes = 0
        self._store_bytes = 0
        self._lock = threading.Lock()
        self._c = {t: {"hits": 0, "misses": 0, "spills": 0,
                       "promotes": 0} for t in self.TIERS}
        self.dropped_blocks = 0
        self.dropped_bytes = 0

    def __len__(self) -> int:
        with self._lock:
            return len(self._host) + len(self._store)

    # -- spill (HBM -> host -> store) ------------------------------------
    def spill(self, prefixes: Sequence[KVPrefix]) -> int:
        """Park evicted blocks in the host tier (newest hottest),
        demoting over-budget cold entries downward. Returns how many of
        ``prefixes`` were accepted (all, unless a prefix fails
        validation)."""
        n = 0
        for p in prefixes:
            try:
                p.validate()
            except (ValueError, AttributeError):
                continue
            key = hash_prefix(p.tokens)
            with self._lock:
                old = self._host.pop(key, None)
                if old is not None:
                    self._host_bytes -= old.payload_bytes
                self._host[key] = p
                self._host_bytes += p.payload_bytes
                self._c["host"]["spills"] += 1
                n += 1
        self._demote_overflow()
        return n

    def _demote_overflow(self) -> None:
        """Push the coldest host entries down until under budget."""
        while True:
            with self._lock:
                if self._host_bytes <= self.host_budget_bytes \
                        or not self._host:
                    return
                key, p = self._host.popitem(last=False)
                self._host_bytes -= p.payload_bytes
            if self.put_fn is None:
                with self._lock:
                    self.dropped_blocks += p.n_blocks
                    self.dropped_bytes += p.payload_bytes
                continue
            try:
                ref = self.put_fn(p)
            except Exception:
                with self._lock:
                    self.dropped_blocks += p.n_blocks
                    self.dropped_bytes += p.payload_bytes
                continue
            with self._lock:
                self._store[key] = (ref, p.tokens, p.payload_bytes)
                self._store_bytes += p.payload_bytes
                self._c["store"]["spills"] += 1

    # -- lookup (promote candidates) -------------------------------------
    def lookup(self, tokens: Sequence[int], block_size: int,
               start_depth: int = 0,
               max_blocks: Optional[int] = None) -> List[TierHit]:
        """Longest contiguous tier run continuing ``tokens`` from block
        boundary ``start_depth`` (the HBM hit depth). Walks depths
        upward, host tier first, resolving store refs through
        ``get_fn``; every hit is token-verified. Entries stay resident —
        call :meth:`pop` only after the promote scatter landed."""
        limit = len(tokens) // block_size
        if max_blocks is not None:
            limit = min(limit, start_depth + max_blocks)
        hits: List[TierHit] = []
        for j in range(start_depth + 1, limit + 1):
            want = tuple(tokens[: j * block_size])
            key = hash_prefix(want)
            hit = self._lookup_one(key, want)
            if hit is None:
                break
            hits.append(hit)
        return hits

    def _lookup_one(self, key: int,
                    want: Tuple[int, ...]) -> Optional[TierHit]:
        with self._lock:
            p = self._host.get(key)
            if p is not None and p.tokens == want:
                self._host.move_to_end(key)
                self._c["host"]["hits"] += 1
                return TierHit(key=key, tier="host", prefix=p)
            self._c["host"]["misses"] += 1
            entry = self._store.get(key)
        if entry is None or self.get_fn is None:
            with self._lock:
                self._c["store"]["misses"] += 1
            return None
        ref, tok, _ = entry
        if tok != want:
            with self._lock:
                self._c["store"]["misses"] += 1
            return None
        try:
            p = self.get_fn(ref)
        except Exception:
            p = None
        if p is None or tuple(p.tokens) != want:
            with self._lock:
                self._c["store"]["misses"] += 1
            return None
        with self._lock:
            self._c["store"]["hits"] += 1
        return TierHit(key=key, tier="store", prefix=p)

    def pop(self, hits: Sequence[TierHit]) -> None:
        """Commit consumption of promoted entries: drop them from their
        tier (a promoted block is HBM-resident again and re-enters the
        PrefixCache via the normal insert path — keeping the tier copy
        would double-count the budget)."""
        with self._lock:
            for h in hits:
                p = self._host.pop(h.key, None)
                if p is not None:
                    self._host_bytes -= p.payload_bytes
                    self._c["host"]["promotes"] += 1
                    continue
                entry = self._store.pop(h.key, None)
                if entry is not None:
                    self._store_bytes -= entry[2]
                    self._c["store"]["promotes"] += 1

    # -- cluster index ---------------------------------------------------
    def stable_heads(self, max_heads: int = 512) -> List[Tuple[int, int]]:
        """Tier-resident chain links as ``(stable_hash, depth)`` pairs,
        hottest first — merged with :meth:`PrefixCache.snapshot_heads`
        into the replica's published index entry."""
        toks: List[Tuple[int, ...]] = []
        with self._lock:
            for p in reversed(self._host.values()):
                if len(toks) >= max_heads:
                    break
                toks.append(p.tokens)
            for _, tok, _ in reversed(self._store.values()):
                if len(toks) >= max_heads:
                    break
                toks.append(tok)
        return [(stable_hash_prefix(t), len(t) // self.block_size)
                for t in toks]

    def clear(self) -> None:
        with self._lock:
            self._host.clear()
            self._store.clear()
            self._host_bytes = self._store_bytes = 0

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {
                "host": dict(self._c["host"], blocks=len(self._host),
                             bytes=self._host_bytes,
                             budget_bytes=self.host_budget_bytes),
                "store": dict(self._c["store"], blocks=len(self._store),
                              bytes=self._store_bytes),
                "dropped_blocks": self.dropped_blocks,
                "dropped_bytes": self.dropped_bytes,
            }
