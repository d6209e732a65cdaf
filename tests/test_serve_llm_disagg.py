"""Disaggregated serving (serve/llm/disagg): KV export→import parity,
all-or-nothing adoption, cancel/preempt block accounting, SLO lanes +
Hysteresis-gated preemption, speculative-decode greedy parity, and
chunked long-prompt prefill.

Compile budget: every engine here is paged with the same
(slots, buckets, S, block) geometry wherever possible, and the module
caches the target params plus ONE monolithic reference engine — each
extra LLMEngine re-jits its tick + touched insert buckets, so tests
share engines unless the scenario needs special geometry.
"""

import numpy as np
import pytest

_CACHE = {}

_GEO = dict(num_slots=4, max_seq_len=128, prefill_buckets=(16, 32),
            kv_layout="paged", kv_block_size=8, decode_block=1)


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
    """Monolithic greedy tokens for (prompt, n), memoized; ONE shared
    paged engine produces every reference."""
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


_PROMPT = [3 + (i * 7) % 200 for i in range(14)]


def test_export_import_roundtrip_parity():
    """The tentpole invariant: prefill on engine A, adopt on engine B,
    and the token stream is bitwise what one engine would produce —
    including the first token, which crosses inside the KVState."""
    from ray_tpu.serve.llm.engine import Request

    ref = _reference(_PROMPT, 12)
    pe = _engine()
    h = pe.submit(Request(prompt=_PROMPT, max_tokens=12,
                          prefill_only=True))
    pe.drain()
    assert h.finish_reason == "prefill"
    assert h.tokens == ref[:1]
    state = h.kv_state
    assert state is not None
    state.validate()
    assert state.payload_bytes == state.blocks["k"].nbytes * 2

    de = _engine()
    h2 = de.submit_adopted(Request(prompt=_PROMPT, max_tokens=12), state)
    de.drain()
    assert h2.tokens == ref
    assert h2.finish_reason is not None
    mig = de.stats()["migration"]
    assert mig["blocks"] == state.n_blocks
    assert mig["bytes"] == state.payload_bytes
    # Exporter freed the slot; importer returns its blocks at finish.
    assert pe.stats()["active_slots"] == 0
    assert de.stats()["kv"]["used_blocks"] <= state.n_blocks  # prefix refs


def test_adopt_prefix_cache_hit_parity():
    """Adoption registers the migrated prompt in the decode engine's
    prefix cache, so a lookalike prompt prefix-hits the migrated blocks
    — and still decodes to the monolithic reference."""
    from ray_tpu.serve.llm.engine import Request

    ref = _reference(_PROMPT, 12)
    pe = _engine()
    h = pe.submit(Request(prompt=_PROMPT, max_tokens=12,
                          prefill_only=True))
    pe.drain()
    de = _engine()
    de.submit_adopted(Request(prompt=_PROMPT, max_tokens=12), h.kv_state)
    de.drain()
    before = de._prefix.stats()["hits"]
    h3 = de.submit(Request(prompt=list(_PROMPT), max_tokens=12))
    de.drain()
    assert de._prefix.stats()["hits"] == before + 1
    assert h3.tokens == ref


def test_adopt_all_or_nothing_under_exhaustion():
    """An adoption the pool cannot cover allocates NOTHING and the
    request queues until blocks free; when capacity returns it lands
    and decodes to parity."""
    from ray_tpu.serve.llm.engine import Request

    ref = _reference(_PROMPT, 12)
    pe = _engine()
    h = pe.submit(Request(prompt=_PROMPT, max_tokens=12,
                          prefill_only=True))
    pe.drain()
    # Decode pool with barely enough blocks for ONE sequence at a time.
    de = _engine(num_slots=2, num_kv_blocks=6, prefix_cache=False)
    blocker = de.submit(Request(prompt=_PROMPT, max_tokens=30))
    de.step()                      # blocker takes the pool
    used_before = de.stats()["kv"]["used_blocks"]
    h2 = de.submit_adopted(Request(prompt=_PROMPT, max_tokens=12),
                           h.kv_state)
    de.step()
    # Nothing allocated for the queued adoption.
    assert not h2.done()
    assert de.stats()["kv"]["used_blocks"] == used_before
    assert de.stats()["queued"] == 1
    de.drain()                     # blocker finishes -> adoption lands
    assert blocker.done() and h2.done()
    assert h2.tokens == ref


def test_cancel_restores_block_accounting():
    """cancel() on a live request frees its slot, paged blocks, and
    prefix refs at the next step boundary; a queued cancel finishes
    immediately without touching the pool."""
    from ray_tpu.serve.llm.engine import Request

    e = _engine(prefix_cache=False)
    free0 = e._allocator.free_blocks
    h = e.submit(Request(prompt=_PROMPT, max_tokens=50))
    for _ in range(3):
        e.step()
    assert not h.done()
    assert e._allocator.free_blocks < free0
    assert h.cancel()
    e.step()
    assert h.done() and h.finish_reason == "cancelled"
    assert not h.cancel()          # already finished
    assert e._allocator.free_blocks == free0
    # Queued cancel: fill all slots first.
    fillers = [e.submit(Request(prompt=_PROMPT, max_tokens=40))
               for _ in range(4)]
    e.step()
    queued = e.submit(Request(prompt=_PROMPT, max_tokens=4))
    assert queued.cancel()
    assert queued.done() and queued.finish_reason == "cancelled"
    for f in fillers:
        f.cancel()
    e.drain()
    assert e._allocator.free_blocks == free0


def test_preempt_resume_continuity():
    """preempt() mid-decode checkpoints the sequence; readmission
    resumes it with zero token divergence from the uninterrupted run."""
    from ray_tpu.serve.llm.engine import Request

    ref = _reference(_PROMPT, 12)
    e = _engine()
    h = e.submit(Request(prompt=_PROMPT, max_tokens=12, slo="batch"))
    for _ in range(4):
        e.step()
    assert 0 < len(h.tokens) < 12
    slot = next(s for s in range(4) if e._slots[s].handle is h)
    free_before = e._allocator.free_blocks
    e.preempt(slot)
    assert h.kv_state is not None
    assert e._allocator.free_blocks > free_before   # blocks came back
    assert e.stats()["preempted"] == 1
    e.drain()
    assert h.tokens == ref
    assert h.kv_state is None      # consumed at readmission


def test_interactive_pressure_preempts_batch():
    """The scheduling policy end to end: with every slot held by batch
    decodes, a waiting interactive request trips the Hysteresis gate
    (hold 0, cooldown 0 here) and evicts the newest batch decode."""
    from ray_tpu.serve.llm.engine import Request

    e = _engine(num_slots=2, preempt_hold_s=0.0,
                preempt_cooldown_s=0.0)
    batch = [e.submit(Request(prompt=_PROMPT, max_tokens=60,
                              slo="batch"))
             for _ in range(2)]
    e.step()
    assert e.stats()["active_slots"] == 2
    inter = e.submit(Request(prompt=_PROMPT, max_tokens=2))
    e.step()                       # pressure observed -> preempt
    e.step()                       # interactive admitted
    assert inter.done() or any(
        e._slots[s].handle is inter for s in range(2))
    e.drain()
    assert e.stats()["preempted"] >= 1
    assert inter.tokens == _reference(_PROMPT, 2)
    for b in batch:                # preempted batch work still exact
        assert b.tokens == _reference(_PROMPT, 60)[:len(b.tokens)]
        assert b.finish_reason in ("length", "eos", "stop")


def test_spec_decode_greedy_parity():
    """Speculative decoding is token-invisible: a self-draft accepts
    ~everything, a mismatched random draft accepts ~nothing (the
    zero-accept worst case), and both emit the monolithic stream."""
    import jax

    from ray_tpu.models.llama import init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    config, params = _model()
    ref = _reference(_PROMPT, 12)
    econf = EngineConfig(**_GEO, spec_k=3)
    # Self-draft: proposals always agree with the verifier.
    se = LLMEngine(params, config, econf, draft_params=params,
                   draft_config=config)
    h = se.submit(Request(prompt=_PROMPT, max_tokens=12))
    se.drain()
    assert h.tokens == ref
    spec = se.stats()["spec"]
    assert spec["rounds"] > 0
    # Not exactly 1.0: the draft decodes on a dense cache, the verify
    # on the paged pool, and bf16 reduction-order differences can flip
    # an argmax on a near-tie. Parity (above) is exact regardless.
    assert spec["accept_ratio"] > 0.7
    # Random draft: near-zero acceptance, identical tokens.
    drafts = init_params(config, jax.random.key(123))
    se2 = LLMEngine(params, config, econf, draft_params=drafts,
                    draft_config=config)
    h2 = se2.submit(Request(prompt=_PROMPT, max_tokens=12))
    se2.drain()
    assert h2.tokens == ref
    assert se2.stats()["spec"]["rounds"] >= spec["rounds"]


def test_spec_with_adopted_checkpoint():
    """Migration composes with speculation: the decode engine re-seeds
    its draft cache from the adopted prompt + prior tokens and the
    resumed stream still matches the monolithic reference."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    config, params = _model()
    ref = _reference(_PROMPT, 12)
    pe = _engine()
    h = pe.submit(Request(prompt=_PROMPT, max_tokens=12,
                          prefill_only=True))
    pe.drain()
    de = LLMEngine(params, config, EngineConfig(**_GEO, spec_k=3),
                   draft_params=params, draft_config=config)
    h2 = de.submit_adopted(Request(prompt=_PROMPT, max_tokens=12),
                           h.kv_state)
    de.drain()
    assert h2.tokens == ref
    assert de.stats()["spec"]["rounds"] > 0


def test_chunked_prefill_long_prompt_parity():
    """A prompt past the largest bucket is admitted in bucket-sized
    chunks through the prefix cache — and decodes exactly like the same
    prompt on an engine whose buckets DO fit it."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    config, params = _model()
    long_prompt = [5 + (i * 11) % 190 for i in range(40)]
    big = LLMEngine(params, config, EngineConfig(
        **{**_GEO, "prefill_buckets": (16, 48)}))
    r = big.submit(Request(prompt=long_prompt, max_tokens=8))
    big.drain()
    ref = list(r.tokens)

    e = _engine()                  # buckets top out at 32 < 40
    with pytest.raises(ValueError):
        e.submit(Request(prompt=long_prompt, max_tokens=8))
    h = e.submit(Request(prompt=long_prompt, max_tokens=8,
                         chunked_prefill=True))
    e.drain()
    assert h.tokens == ref


def test_lane_queue_priority():
    """Interactive submissions admitted ahead of earlier-queued batch
    work when slots free up."""
    from ray_tpu.serve.llm.engine import Request

    e = _engine(num_slots=1)
    running = e.submit(Request(prompt=_PROMPT, max_tokens=2))
    e.step()
    b = e.submit(Request(prompt=_PROMPT, max_tokens=2, slo="batch"))
    i = e.submit(Request(prompt=_PROMPT, max_tokens=2))
    by_lane = e.stats()["queued_by_lane"]
    assert by_lane == {"interactive": 1, "batch": 1}
    e.drain()
    assert running.done() and b.done() and i.done()
    # Interactive finished before batch was even admitted.
    assert i.finished_at <= b.admitted_at
