"""Cluster-wide KV memory hierarchy (serve/llm): spill -> promote
bitwise parity through the host tier, all-or-nothing promotes under
pool exhaustion, the promote cost model at engine level, the GCS
cluster prefix index (publish / lookup / head cap / TTL expiry), and
cache-aware p2c routing beating plain queue-depth p2c on a skewed
prefix workload.

Compile budget: same (slots, buckets, S, block) geometry as the disagg
suite, model params memoized per module; each engine re-jits only its
touched buckets plus the shared export/adopt programs.
"""

import random
import threading
import time

import pytest

_CACHE = {}

_GEO = dict(num_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
            kv_layout="paged", kv_block_size=8, decode_block=1)

# 28 tokens = 3 full blocks of history + a 4-token suffix, so a full
# tier promote leaves real prefill work (the last block + logits).
_PROMPT = [5 + (i * 11) % 190 for i in range(28)]


def _model():
    if "model" not in _CACHE:
        import jax

        from ray_tpu.models.llama import LlamaConfig, init_params

        config = LlamaConfig.tiny()
        _CACHE["model"] = (config, init_params(config, jax.random.key(0)))
    return _CACHE["model"]


def _engine(**overrides):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    config, params = _model()
    return LLMEngine(params, config,
                     EngineConfig(**{**_GEO, **overrides}))


def _reference(prompt, n):
    key = (tuple(prompt), n)
    if key not in _CACHE.setdefault("refs", {}):
        if "ref_engine" not in _CACHE:
            _CACHE["ref_engine"] = _engine()
        from ray_tpu.serve.llm.engine import Request

        e = _CACHE["ref_engine"]
        h = e.submit(Request(prompt=list(prompt), max_tokens=n))
        e.drain()
        _CACHE["refs"][key] = list(h.tokens)
    return _CACHE["refs"][key]


def _run(eng, prompt, n):
    from ray_tpu.serve.llm.engine import Request

    h = eng.submit(Request(prompt=list(prompt), max_tokens=n))
    eng.drain()
    return h


def _spill_all(eng):
    """Evict the whole prefix cache; with kv_spill on, every evicted
    chain link lands in the host tier (the engine is idle between
    drains, so driving the spill gather from the test thread is the
    single-threaded scheduler)."""
    n = len(eng._prefix)
    assert eng._prefix.evict(n) == n
    return n


class TestTieredPromote:
    def test_spill_promote_bitwise_parity(self):
        """The tentpole invariant: prefill once, spill the chain to the
        host tier, re-admit the same prompt — the promote path scatters
        the spilled rows back and the token stream is bitwise identical,
        with only the suffix actually prefilled."""
        ref = _reference(_PROMPT, 12)
        # Prefill "costs" 50ms/token -> the cost model always promotes.
        eng = _engine(kv_prefill_cost_per_token_ms=50.0)
        h1 = _run(eng, _PROMPT, 12)
        assert h1.tokens == ref
        assert h1.prefilled_tokens == len(_PROMPT)

        assert _spill_all(eng) == 3
        st = eng.stats()["kv_tiers"]
        assert st["host"]["blocks"] == 3
        assert eng._prefix.stats()["spilled"] == 3

        h2 = _run(eng, _PROMPT, 12)
        assert h2.tokens == ref
        st = eng.stats()["kv_tiers"]
        assert st["promoted_blocks"] == 3
        assert st["host"]["blocks"] == 0        # pop committed
        # Only the 4-token suffix was prefilled the second time.
        assert h2.prefilled_tokens == len(_PROMPT) - 3 * 8
        # Trace budget: tick + per-bucket inserts + the two migration
        # programs the hierarchy reuses (export gather for the spill,
        # one trace per row length of `export_rows`; adopt scatter for
        # the promote) — nothing per-request.
        assert eng.trace_count <= (len(_GEO["prefill_buckets"]) + 2
                                   + len(eng.config.export_rows))
        assert eng.stats()["traces"]["export"] == 1     # one row used

    def test_promote_all_or_nothing_under_exhaustion(self):
        """A promote the pool cannot cover is dropped ENTIRELY — tier
        entries stay banked, no partial scatter — and the request lands
        as a plain recompute with bitwise parity."""
        ref = _reference(_PROMPT, 12)
        eng = _engine(kv_prefill_cost_per_token_ms=50.0)
        _run(eng, _PROMPT, 12)
        _spill_all(eng)

        real = eng._allocator.alloc
        calls = {"n": 0}

        def flaky(n):
            # Starve the promote attempt (first alloc + post-evict
            # retry); the recompute retry that follows sees the real
            # pool.
            calls["n"] += 1
            return None if calls["n"] <= 2 else real(n)

        eng._allocator.alloc = flaky
        try:
            h2 = _run(eng, _PROMPT, 12)
        finally:
            eng._allocator.alloc = real
        assert calls["n"] >= 3
        assert h2.tokens == ref
        st = eng.stats()["kv_tiers"]
        assert st["promoted_blocks"] == 0
        assert st["host"]["blocks"] == 3        # lookup never commits
        assert h2.prefilled_tokens == len(_PROMPT)  # full recompute

    def test_cost_model_prefers_free_recompute(self):
        """With recompute priced at zero the cost model must never pay
        for the adopt scatter: tier hits are counted as skips, entries
        stay banked, and the plain path still reaches parity."""
        ref = _reference(_PROMPT, 12)
        eng = _engine(kv_prefill_cost_per_token_ms=0.0)
        _run(eng, _PROMPT, 12)
        _spill_all(eng)
        h2 = _run(eng, _PROMPT, 12)
        assert h2.tokens == ref
        st = eng.stats()["kv_tiers"]
        assert st["promoted_blocks"] == 0
        assert st["promote_skips"] == 3
        assert st["host"]["blocks"] == 3
        assert h2.prefilled_tokens == len(_PROMPT)

    def test_cost_model_default_crossover_unit(self):
        from ray_tpu.serve.llm.kv_cache import PromoteCostModel

        cm = PromoteCostModel()
        cross = next(n for n in range(1, 65) if cm.should_promote(n, 16))
        assert cross == 3
        assert all(cm.should_promote(n, 16) for n in range(cross, 65))


# ------------------------------------------ the spill off the critical path
#
# PR 27: `_spill_evicted` exports the smallest row of `export_rows` that
# holds the victims, starts its copy to the host and keeps it pending;
# `_step` lands it behind the dispatched tick. 40 blocks a slot give the
# rows (16, 32, 40).

_ROWS_GEO = dict(num_slots=2, max_seq_len=320, prefill_buckets=(16,),
                 kv_layout="paged", kv_block_size=8, decode_block=1)


def _model_of(kind):
    if kind == "dense":
        return _model()
    if "latent" not in _CACHE:
        import jax

        from ray_tpu.models.latent_moe import LatentMoEConfig

        config = LatentMoEConfig.tiny()
        _CACHE["latent"] = (config, config.serving().init_params(
            config, jax.random.key(0)))
    return _CACHE["latent"]


def _engine_of(kind, **geo):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    config, params = _model_of(kind)
    return LLMEngine(params, config, EngineConfig(**geo))


def _random_pool(eng, seed=0):
    """Every row of the pool distinct, so a block read from the wrong
    place or at the wrong time cannot compare equal."""
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.RandomState(seed)
    eng._programs._cache = {name: jnp.asarray(rng.standard_normal(x.shape), x.dtype)
                  for name, x in eng._programs._cache.items()}
    return {name: np.asarray(x) for name, x in eng._programs._cache.items()}


class _Spans:
    """Stands in for `trace_span` in the engine module: every span as a
    dict of its name and arguments, in the order they opened."""

    def __init__(self, monkeypatch):
        from ray_tpu.serve.llm import engine as E

        self.rows = []
        monkeypatch.setattr(E, "trace_span", self._open)

    def _open(self, name, **args):
        rows = self.rows

        class _Span:
            def __enter__(self):
                self.row = dict(args, name=name)
                rows.append(self.row)
                return self

            def __exit__(self, *exc):
                return False

            def set_metadata(self, **kw):
                self.row.update(kw)

        return _Span()

    def named(self, name):
        return [r for r in self.rows if r["name"] == "llm_engine." + name]


def _tier_blocks(eng, tokens):
    """{leaf: [L, n, bs, ...]} of the chain links of `tokens` that the
    tier holds, in depth order (lookup stops at the first miss)."""
    import numpy as np

    hits = eng._tiers.lookup(tokens, eng.config.kv_block_size)
    if not hits:
        return 0, {}
    return len(hits), {name: np.concatenate(
        [h.prefix.blocks[name] for h in hits], axis=1)
        for name in hits[0].prefix.blocks}


@pytest.mark.parametrize("kind", ["dense", "latent"])
@pytest.mark.parametrize("victims, row", [(1, 16), (16, 16), (17, 32),
                                          (40, 40)])
def test_spill_exports_smallest_row_and_lands_victims_bitwise(
        monkeypatch, kind, victims, row):
    """1, a ladder edge, an edge + 1 and `max_blocks_per_slot` victims:
    the export row is the smallest that holds them, `llm_engine.spill`'s
    bytes are that row's, and the tier holds exactly the victims, each
    leaf bitwise what the pool held."""
    import numpy as np

    eng = _engine_of(kind, **_ROWS_GEO)
    assert eng.config.export_rows == (16, 32, 40)
    spans = _Spans(monkeypatch)
    pool = _random_pool(eng, seed=victims)
    bs = eng.config.kv_block_size
    # one chain of 40 links in a cache of 80 blocks; evict() takes the
    # coldest first, which is the chain's head
    tokens = [1 + (i * 7) % 250 for i in range(40 * bs)]
    blocks = eng._allocator.alloc(40)
    eng._prefix.insert(tokens, blocks)
    eng._allocator.free(blocks)

    assert eng._prefix.evict(victims) == victims    # outside a step:
    assert eng._pending_spills == []                # landed at once
    row_bytes = row * eng._allocator.block_bytes
    (sp,), (ld,) = spans.named("spill"), spans.named("spill_land")
    assert (sp["evicted_blocks"], sp["bytes"]) == (victims, row_bytes)
    assert (ld["blocks"], ld["bytes"]) == (victims, row_bytes)
    assert ld["ready"] in (0, 1)
    n, got = _tier_blocks(eng, tokens)
    assert n == victims == eng.stats()["kv_tiers"]["host"]["blocks"]
    assert set(got) == set(pool)
    for name, x in pool.items():
        assert got[name].dtype == x.dtype
        assert np.array_equal(got[name], x[:, blocks[:victims]]), name
    st = eng.stats()
    assert st["traces"]["export"] == 1
    assert st["prefix_cache"]["spilled"] == victims
    assert st["kv_tiers"]["spill_lands"] == 1


# A pool of 9 blocks in which four served prompts leave 8 cached links
# (two each) and one free block: the fifth admission (3 blocks) has to
# evict two, and writes where the victims were.
_TIGHT_GEO = dict(num_slots=2, max_seq_len=64, prefill_buckets=(16,),
                  kv_layout="paged", kv_block_size=8, num_kv_blocks=9,
                  decode_block=1)


def _prompt16(i):
    return [1 + (37 * i + 3 * j) % 250 for j in range(16)]


def _fill_tight(eng):
    from ray_tpu.serve.llm.engine import Request

    for i in range(4):
        eng.submit(Request(prompt=_prompt16(i), max_tokens=2))
        eng.drain()
    assert len(eng._prefix) == 8 and eng._allocator.free_blocks == 1


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_insert_over_evicted_blocks_lands_what_they_held(monkeypatch, kind):
    """The admission that evicts frees the victims' blocks and its own
    insert (and the tick) write into them in the same step, before the
    landing reads the row: the tier still gets what the blocks held at
    eviction, for every leaf."""
    import numpy as np

    from ray_tpu.serve.llm.engine import Request

    eng = _engine_of(kind, **_TIGHT_GEO)
    _fill_tight(eng)
    before = {name: np.asarray(x) for name, x in eng._programs._cache.items()}
    held = {e.tokens: e.block for e in eng._prefix._entries.values()}
    spans = _Spans(monkeypatch)
    eng.submit(Request(prompt=_prompt16(9), max_tokens=4))
    assert eng.step()
    assert eng._pending_spills == []
    order = [r["name"].split(".", 1)[1] for r in spans.rows]
    # (no `tick_wait`: the first tick of a wave is read back a step on)
    assert [n for n in order if n in (
        "spill", "insert_dispatch", "tick_dispatch", "spill_land",
        "tick_wait")] == ["spill", "insert_dispatch", "tick_dispatch",
                          "spill_land"]
    after = {name: np.asarray(x) for name, x in eng._programs._cache.items()}
    # 3 blocks for 16 + 4 tokens, one free: both links of prompt 0 go
    victims = [t for t in held if t not in
               {e.tokens for e in eng._prefix._entries.values()}]
    assert len(victims) == 2 == spans.named("spill_land")[0]["blocks"]
    overwritten = 0
    for tokens in victims:
        hit = eng._tiers.lookup(tokens, 8, start_depth=len(tokens) // 8 - 1)
        assert len(hit) == 1
        for name, x in before.items():
            assert np.array_equal(hit[0].prefix.blocks[name][:, 0],
                                  x[:, held[tokens]]), name
            overwritten += not np.array_equal(after[name][:, held[tokens]],
                                              x[:, held[tokens]])
    assert overwritten >= len(before)        # a victim's block, each leaf


def test_lookup_in_the_evicting_step_finds_and_promotes(monkeypatch):
    """Two admissions in ONE step: the first evicts the whole chain of
    `_PROMPT` (and writes over its blocks), the second is `_PROMPT`
    again: its lookup lands the pending spill first, finds the three
    links and promotes them. Both streams are bitwise the reference's."""
    from ray_tpu.serve.llm.engine import Request

    other = [[9 + (i * 5 + 17 * k) % 180 for i in range(28)]
             for k in range(3)]
    big = other[2]
    refs = [_reference(_PROMPT, 12), _reference(big, 52)]
    eng = _engine(num_kv_blocks=16, kv_prefill_cost_per_token_ms=50.0)
    for p in (_PROMPT, other[0], other[1]):     # 3 cached links each
        _run(eng, p, 12)
    assert len(eng._prefix) == 9 and eng._allocator.free_blocks == 7
    spans = _Spans(monkeypatch)
    h_big = eng.submit(Request(prompt=big, max_tokens=52))   # 10 blocks
    h_again = eng.submit(Request(prompt=list(_PROMPT), max_tokens=12))
    assert eng.step()
    assert spans.named("admit")[0]["admitted"] == 2
    lands = spans.named("spill_land")
    # the first landing stands before the second admission's promote,
    # the second (what that admission evicted) behind the tick
    order = [r["name"].split(".", 1)[1] for r in spans.rows]
    assert [lands[0]["blocks"], lands[1]["blocks"]] == [3, 5]
    assert order.index("spill_land") < order.index("promote") \
        < order.index("tick_dispatch") < len(order) - 1 - order[::-1].index(
            "spill_land")
    assert eng._pending_spills == []
    eng.drain()
    assert [h_again.tokens, h_big.tokens] == refs
    st = eng.stats()["kv_tiers"]
    assert st["promoted_blocks"] == 3
    assert h_again.prefilled_tokens == len(_PROMPT) - 3 * 8
    assert st["spill_lands"] == 2


@pytest.mark.parametrize("max_tokens, ticks", [(4, True), (1, False)])
def test_no_pending_spill_outlives_its_step(monkeypatch, max_tokens, ticks):
    """A step that ticks lands behind the dispatched tick; one whose
    only request ends at its first token dispatches no tick and lands
    before it returns. Either way the tier holds the victims when
    `step()` is back, and a step that admitted nothing lands nothing."""
    from ray_tpu.serve.llm.engine import Request

    eng = _engine_of("dense", **_TIGHT_GEO)
    _fill_tight(eng)
    spans = _Spans(monkeypatch)
    h = eng.submit(Request(prompt=_prompt16(9), max_tokens=max_tokens))
    assert eng.step()
    assert ("tick_dispatch" in {r["name"].split(".", 1)[1]
                                for r in spans.rows}) == ticks
    assert eng._pending_spills == []
    evicted = spans.named("spill")[0]["evicted_blocks"]
    st = eng.stats()
    assert st["kv_tiers"]["host"]["blocks"] == evicted > 0
    assert st["kv_tiers"]["spill_lands"] == 1
    assert st["prefix_cache"]["spilled"] == evicted
    eng.drain()
    assert h.finish_reason == "length"
    assert len(spans.named("spill_land")) == 1
    assert eng.stats()["kv_tiers"]["spill_lands"] == 1


@pytest.mark.parametrize("threshold, waited", [(-1.0, 1), (1e9, 0)])
def test_spill_lands_counts_the_landings_that_waited(monkeypatch,
                                                     threshold, waited):
    """`spill_lands` counts landings, `spill_lands_waited` those whose
    read of the row blocked longer than the engine's threshold (the
    transfer was still under way); the span says the same as `ready`."""
    from ray_tpu.serve.llm import engine as E
    from ray_tpu.serve.llm.engine import Request

    monkeypatch.setattr(E, "_SPILL_READY_S", threshold)
    eng = _engine_of("dense", **_TIGHT_GEO)
    _fill_tight(eng)
    spans = _Spans(monkeypatch)
    for i in (9, 10):
        eng.submit(Request(prompt=_prompt16(i), max_tokens=2))
        eng.drain()
    st = eng.stats()["kv_tiers"]
    assert st["spill_lands"] == len(spans.named("spill_land")) == 2
    assert st["spill_lands_waited"] == 2 * waited
    assert {r["ready"] for r in spans.named("spill_land")} == {1 - waited}


def test_failed_landing_counts_and_never_blocks_the_eviction():
    """A landing that fails is a spill that failed: `spill_errors`
    counts it, `spilled` does not count its blocks, the blocks were
    freed and the request is served."""
    from ray_tpu.serve.llm.engine import Request

    eng = _engine_of("dense", **_TIGHT_GEO)
    _fill_tight(eng)

    def broken(prefixes):
        raise MemoryError("host tier out of memory")

    eng._tiers.spill = broken
    h = eng.submit(Request(prompt=_prompt16(9), max_tokens=4))
    eng.drain()
    assert h.finish_reason == "length" and len(h.tokens) == 4
    st = eng.stats()
    assert st["prefix_cache"]["spill_errors"] == 1
    assert st["prefix_cache"]["spilled"] == 0
    assert st["prefix_cache"]["evictions"] == 2
    assert st["kv_tiers"]["host"]["blocks"] == 0
    assert st["kv_tiers"]["spill_lands"] == 1
    assert eng._pending_spills == []


@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_warmup_compiles_every_export_row(kind):
    """`warmup()` traces the export at every row length, so evicting
    traffic, a checkpoint and a peer pull after it add no trace."""
    from ray_tpu.serve.llm.engine import Request

    eng = _engine_of(kind, **dict(_ROWS_GEO, num_kv_blocks=48))
    eng.warmup()
    st = eng.stats()
    assert st["traces"] == {"tick": 1, "insert": 1, "export": 3, "adopt": 0}
    assert st["trace_count"] == 2 + len(eng.config.export_rows)
    # 20 cached links a prompt in a pool of 48: the third prompt evicts
    # (rows of 16 and 32 by the count), as do the ones after it
    hs = []
    for i in range(5):
        prompt = [1 + (i * 31 + j * 3) % 250 for j in range(160 + 8 * i)]
        hs.append(eng.submit(Request(prompt=prompt, max_tokens=3,
                                     chunked_prefill=True)))
        eng.drain()
    assert all(h.finish_reason == "length" for h in hs)
    assert eng.stats()["prefix_cache"]["spilled"] > 40
    eng.submit(Request(prompt=[7] * 12, max_tokens=4))
    eng.step()
    eng.preempt(int(eng._active.nonzero()[0][0]))          # checkpoint
    eng.drain()
    assert eng.export_prefix(prompt)                        # peer pull
    st = eng.stats()
    assert st["traces"] == {"tick": 1, "insert": 1, "export": 3, "adopt": 1}


_LONG = [5 + (i * 11) % 190 for i in range(40)]     # pieces of 32 and 8


def _chunked(**fields):
    from ray_tpu.serve.llm.engine import Request

    return Request(**{"prompt": _LONG, "max_tokens": 8,
                      "chunked_prefill": True, **fields})


def _unchunked(kind, **fields):
    """What an engine whose largest bucket holds the whole prompt makes
    of the request: the handle, memoized a kind."""
    from ray_tpu.serve.llm.engine import Request

    key = (kind, tuple(fields.get("prompt", _LONG)),
           fields.get("prefill_only", False))
    if key not in _CACHE:
        if ("big", kind) not in _CACHE:
            _CACHE["big", kind] = _engine_of(
                kind, **{**_GEO, "prefill_buckets": (16, 48)})
        big = _CACHE["big", kind]
        _CACHE[key] = big.submit(Request(**{
            "prompt": _LONG, "max_tokens": 8, **fields}))
        big.drain()
    return _CACHE[key]


@pytest.mark.parametrize("case", [
    "no_prefix_cache", "starts_at_the_hit", "a_sharer_hits_its_blocks",
    "cancel_between_pieces", "prefill_only_exports"])
@pytest.mark.parametrize("kind", ["dense", "latent"])
def test_chunked_prompt_goes_into_the_slot_it_keeps(kind, case):
    """A prompt longer than the largest bucket goes in piece by piece
    into ONE slot, whatever the model: without a prefix cache; from a
    cached prefix on; leaving its blocks for a sharer to hit; giving
    every block back when cancelled between two pieces; and exporting
    the KVState an unchunked prefill exports."""
    import numpy as np

    from ray_tpu.serve.llm.engine import Request

    bs = _GEO["kv_block_size"]
    want = list(_unchunked(kind).tokens)
    if case == "no_prefix_cache":
        eng = _engine_of(kind, **_GEO, prefix_cache=False)
        h = eng.submit(_chunked())
        assert eng.step() and list(eng._chunking) == [0]
        assert not eng._active.any()        # inactive until the last piece
        eng.drain()
        assert h.tokens == want and h.prefilled_tokens == len(_LONG)
    elif case == "starts_at_the_hit":
        eng = _engine_of(kind, **_GEO)
        eng.submit(Request(prompt=_LONG[:2 * bs + 4], max_tokens=2))
        eng.drain()
        h = eng.submit(_chunked())
        eng.drain()
        assert h.tokens == want
        assert h.prefilled_tokens == len(_LONG) - 2 * bs
        assert eng.stats()["prefix_cache"]["hit_tokens"] == 2 * bs
    elif case == "a_sharer_hits_its_blocks":
        eng = _engine_of(kind, **_GEO)
        eng.submit(_chunked())
        eng.drain()
        sharer = _LONG[:36] + [3, 1, 4, 1, 5, 9, 2, 6]
        h = eng.submit(_chunked(prompt=sharer))
        eng.drain()
        assert h.prefilled_tokens == len(sharer) - 4 * bs
        assert h.tokens == _unchunked(kind, prompt=sharer).tokens
    elif case == "cancel_between_pieces":
        eng = _engine_of(kind, **_GEO, kv_spill=False)
        free = eng._allocator.free_blocks
        h = eng.submit(_chunked())
        assert eng.step() and list(eng._chunking) == [0]
        assert h.cancel() and eng.step()
        assert h.finish_reason == "cancelled" and not eng._chunking
        assert len(eng._free) == _GEO["num_slots"]
        # the first piece's full blocks are the cache's alone now
        assert eng._allocator.free_blocks == free - 32 // bs
        eng._prefix.clear()
        assert eng._allocator.free_blocks == free
    else:
        eng = _engine_of(kind, **_GEO)
        h = eng.submit(_chunked(prefill_only=True))
        eng.drain()
        got, ref = h.kv_state, _unchunked(kind, prefill_only=True).kv_state
        assert h.finish_reason == "prefill" and h.tokens == want[:1]
        assert (got.prompt, got.tokens, got.next_tok, got.pos) == (
            ref.prompt, ref.tokens, ref.next_tok, ref.pos)
        assert got.blocks.keys() == ref.blocks.keys()
        for name, x in got.blocks.items():
            np.testing.assert_allclose(
                np.asarray(x, np.float32),
                np.asarray(ref.blocks[name], np.float32), atol=2e-2)


def test_cluster_prefix_index_gcs():
    """report_prefix_index / lookup_prefix_index: roundtrip,
    last-write-wins per replica, the serve_prefix_index_max_heads cap,
    and lazy TTL expiry at lookup. Own cluster: the TTL is read inside
    the GCS daemon, so it must arrive via _system_config (the same
    head-to-every-process propagation production overrides use)."""
    import ray_tpu
    from ray_tpu._private.config import GlobalConfig
    from ray_tpu._private.worker import global_worker

    ray_tpu.init(num_cpus=2, num_tpus=0,
                 object_store_memory=128 * 1024 * 1024,
                 _system_config={"serve_prefix_index_ttl_s": 0.5})
    try:
        w = global_worker()
        assert w.gcs.call(
            "report_prefix_index", timeout=10, replica="repA",
            heads=[(11, 1), (22, 2)],
            tiers={"block_size": 8, "host_blocks": 3})
        idx = w.gcs.call("lookup_prefix_index", timeout=10)
        rec = idx["repA"]
        assert [(int(h), int(d)) for h, d in rec["heads"]] \
            == [(11, 1), (22, 2)]
        assert rec["tiers"]["block_size"] == 8
        assert rec["age_s"] >= 0.0

        # Last write wins, hottest-first heads capped at the limit.
        cap = int(GlobalConfig.serve_prefix_index_max_heads)
        w.gcs.call("report_prefix_index", timeout=10, replica="repA",
                   heads=[(i, i + 1) for i in range(cap + 100)],
                   tiers={})
        idx = w.gcs.call("lookup_prefix_index", timeout=10)
        assert len(idx["repA"]["heads"]) == cap
        assert idx["repA"]["tiers"] == {}

        # Publish IS the heartbeat: a silent replica ages out lazily.
        time.sleep(0.7)
        assert "repA" not in w.gcs.call("lookup_prefix_index",
                                        timeout=10)
    finally:
        ray_tpu.shutdown()


# --------------------------------------------------------------- routing
_BS = 8


def _family(seed):
    rng = random.Random(seed)
    return [rng.randrange(1, 200) for _ in range(3 * _BS)]


def _heads_for(tokens):
    from ray_tpu.serve.llm.kv_cache import stable_hash_prefix

    return [(stable_hash_prefix(tokens[:j * _BS]), j)
            for j in range(1, len(tokens) // _BS + 1)]


def _bare_router(index, index_id, weight, ttl=60.0):
    """An LLMRouter with only the routing-policy state populated — the
    pure decision path (_score/_expected_hits/_pick_cached), no actor
    plumbing, no probe threads."""
    from ray_tpu.serve.llm.router import LLMRouter

    r = object.__new__(LLMRouter)
    r._lock = threading.Lock()
    r._index = dict(index)
    r._index_at = time.monotonic()
    r._index_id = dict(index_id)
    r._cache_weight = weight
    r._index_ttl = ttl
    r._replicas = list(index_id)
    r._inflight = {h: 0 for h in index_id}
    r._depth = {h: 0.0 for h in index_id}
    r._pre_replicas = []
    r._pre_inflight = {}
    r._pre_depth = {}
    return r


class TestCacheAwareRouting:
    def _setup(self):
        fams = [_family(s) for s in range(4)]
        index = {f"iid{i}": {"heads": _heads_for(f),
                             "tiers": {"block_size": _BS},
                             "age_s": 0.1}
                 for i, f in enumerate(fams)}
        index_id = {f"rep{i}": f"iid{i}" for i in range(4)}
        return fams, index, index_id

    def test_expected_hits_longest_boundary_run(self):
        fams, index, index_id = self._setup()
        router = _bare_router(index, index_id, weight=0.25)
        # Full family + tail: every replica scores its own chain only.
        exp = router._expected_hits(fams[1] + [7])
        assert exp["iid1"] == 3
        assert all(exp[f"iid{i}"] == 0 for i in (0, 2, 3))
        # A diverging second block stops the run after one hit.
        mutant = fams[1][:_BS] + [0] * _BS + fams[1][2 * _BS:] + [7]
        assert router._expected_hits(mutant)["iid1"] == 1
        # The last token is always prefilled: a prompt of exactly 3
        # blocks can only ever hit 2 (same cap as admission).
        assert router._expected_hits(fams[1])["iid1"] == 2

    def test_cache_aware_beats_plain_p2c(self):
        """On a Zipf-skewed family mix, scoring p2c with the published
        index must route substantially more expected-hit blocks to
        their owners than load-only p2c — with weight 0.25, i.e. as a
        tie-break between idle replicas, not a load override."""
        from ray_tpu.serve.llm.router import p2c_pick

        fams, index, index_id = self._setup()
        router = _bare_router(index, index_id, weight=0.25)
        rng = random.Random(42)
        random.seed(7)                       # p2c_pick's default rng
        weights = [1.0 / (i + 1) ** 1.3 for i in range(4)]
        plain = aware = 0
        for _ in range(200):
            fam = rng.choices(range(4), weights=weights)[0]
            prompt = fams[fam] + [rng.randrange(1, 200)]
            exp = router._expected_hits(prompt)
            chosen, expected, outcome = router._pick_cached(prompt)
            assert outcome == "scored" and expected == exp
            aware += exp.get(index_id[chosen], 0)
            load = {r: 0.0 for r in index_id}
            plain += exp.get(index_id[p2c_pick(list(index_id), load)], 0)
        assert aware >= plain * 1.3
        assert aware >= 200                  # owners actually chosen

    def test_stale_index_holds_to_plain_p2c(self):
        """PR-7 staleness discipline: an index view older than the TTL
        must NOT steer routing — outcome 'held', no expected map."""
        _, index, index_id = self._setup()
        router = _bare_router(index, index_id, weight=0.25, ttl=0.05)
        router._index_at = time.monotonic() - 1.0
        chosen, expected, outcome = router._pick_cached([1] * 25)
        assert outcome == "held" and expected == {}
        assert chosen in index_id
        # weight 0 disables scoring outright, fresh index or not.
        router = _bare_router(index, index_id, weight=0.0)
        _, expected, outcome = router._pick_cached([1] * 25)
        assert outcome == "held" and expected == {}
