"""Kimi-Linear through `LLMEngine`: chunked prompts and recycled slots,
the slot's state, a cancel between chunks, what the engine refuses, and
the decode tick's kernel paths (latent attention, the KDA step) against
the plain ones.  `test_kimi_linear.py` has the model's mathematics and
the tolerances' reasons; `kimi_linear_tiny.py` what the two files share.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kimi_linear_tiny import (     # noqa: F401  (`model`: a fixture)
    BS, C, KERNEL_BS, _drawn_at_a_tenth, _tiling, _tokens, model,
)


# ------------------------------------------------------- (g) the engine

def _engine(mc, params, **over):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    cfg = dict(num_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
               kv_block_size=BS, num_kv_blocks=40, prefix_cache=False)
    cfg.update(over)
    return LLMEngine(params, mc, EngineConfig(**cfg), rng_seed=3)


@pytest.fixture
def engine(model, shared_engine):
    """The module's one engine at `_engine`'s own configuration, warmed
    up: drained when a case takes it and when it leaves it."""
    _, mc, _, params = model

    def build():
        engine = _engine(mc, params)
        engine.warmup()
        return engine

    return shared_engine("two slots", build)


def test_engine_serves_chunked_prompts_and_recycles_slots(model, engine):
    """Seven requests through two slots, prompts from one token to three
    chunks: every slot is freed and re-admitted, every served token is
    the reference's choice given the served prefix (so a re-admitted
    slot started from a zero state: a leak would change its logits),
    and a prompt under way keeps its slot inactive until its last
    chunk."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    assert engine.stats()["traces"] == {"tick": 1, "insert": 2,
                                        "export": 0, "adopt": 0}
    reuses_before = engine.stats()["slot_reuses"]
    prompts = [_tokens(n, seed=20 + n) for n in (1, 16, 37, 9, 45, 17, 3)]
    handles = [engine.submit(Request(
        prompt=p, max_tokens=6, chunked_prefill=len(p) > 16))
        for p in prompts]
    seen_under_way = 0
    while engine.has_work():
        engine.step()
        for slot in engine._chunking:
            seen_under_way += 1
            assert not engine._active[slot]
            assert engine._slots[slot].handle is not None
    assert seen_under_way > 0
    st = engine.stats()
    assert st["slot_reuses"] - reuses_before >= 5
    assert st["trace_count"] == 3
    assert st["slot_state"]["prompts_under_way"] == 0
    assert st["kv"]["used_blocks"] == 0
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length" and len(h.tokens) == 6
        assert h.prefilled_tokens == len(p)
        d = R.served_token_deficits(weights, C, p, h.tokens)
        assert d.max() < 1e-4, (len(p), d)
    ctr = st["counters"]
    assert int(ctr["ticks"]) > 0
    assert int(ctr["live_slots"]) <= 2 * int(ctr["ticks"])
    assert int(ctr["pairs_local"]) == int(ctr["expert_tokens"].sum())
    assert int(ctr["pairs_total"]) == int(ctr["live_slots"]) * 2 * 4


def test_slot_state_is_what_the_reference_carries(model, engine):
    """`LLMEngine.slot_state`: after a chunked prompt and six tokens the
    slot's recurrent state is the reference recurrence's over the prompt
    and the first five, and a model without per-slot state has none."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    p = _tokens(37, seed=9)
    h = engine.submit(Request(prompt=p, max_tokens=6, chunked_prefill=True))
    engine.step()
    slot, = (i for i, s in enumerate(engine._slots) if s.handle is h)
    while engine.has_work():
        engine.step()
    got = engine.slot_state(slot)
    assert got["S"].shape == (4, 4, 16, 16) and got["S"].dtype == np.float32
    assert got["conv"].shape == (4, 3 * 3 * 64)     # taps on lanes
    want = R.kda_states(weights, C, p + h.tokens[:-1])
    # states of 8e-3 at these sizes; float32 sums in another order read
    # 1e-6 of that, a state kept in bf16 between tokens 4e-3 of it
    assert np.abs(want).max() > 1e-3
    assert np.abs(got["S"] - want).max() < 1e-4 * np.abs(want).max()

    from ray_tpu.models.latent_moe import LatentMoEConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    lc = LatentMoEConfig.tiny()
    plain = LLMEngine(init_params(lc, jax.random.key(0)), lc, EngineConfig(
        num_slots=1, max_seq_len=64, prefill_buckets=(8,), kv_block_size=BS))
    assert plain.slot_state(0) is None


def test_cancel_between_chunks_frees_the_slot(engine):
    from ray_tpu.serve.llm.engine import Request

    # what the engine serves for `p` with no cancel before it
    p = _tokens(20, seed=9)
    h3 = engine.submit(Request(prompt=p, max_tokens=3, chunked_prefill=True))
    engine.drain()
    h = engine.submit(Request(prompt=_tokens(45), max_tokens=4,
                              chunked_prefill=True))
    engine.step()                       # first chunk: slot and blocks taken
    assert len(engine._chunking) == 1 and not engine._active.any()
    assert engine.stats()["kv"]["used_blocks"] > 0
    assert h.cancel()
    engine.drain()
    assert h.finish_reason == "cancelled"
    assert not engine._chunking and len(engine._free) == 2
    assert engine.stats()["kv"]["used_blocks"] == 0
    # and the slot serves the next request from a zero state
    h2 = engine.submit(Request(prompt=p, max_tokens=3, chunked_prefill=True))
    engine.drain()
    assert h2.tokens == h3.tokens and len(h2.tokens) == 3


@pytest.mark.parametrize("what", ["prefix_cache", "export_prefix",
                                  "adopt", "prefill_only", "preempt",
                                  "speculative_verify"])
def test_engine_refuses_by_name_what_would_lose_the_state(model, what):
    """Whatever moves rows without the recurrent state is refused, and
    the refusal names the model."""
    from ray_tpu.serve.llm.engine import (EngineConfig, LLMEngine, Request)
    from ray_tpu.serve.llm.kv_cache import KVState

    _, mc, _, params = model
    name = "models/kimi_linear.py"
    with pytest.raises(ValueError, match=name):
        if what == "prefix_cache":
            _engine(mc, params, prefix_cache=True)
        elif what == "speculative_verify":
            LLMEngine(params, mc, EngineConfig(
                num_slots=2, max_seq_len=64, prefill_buckets=(8,),
                kv_block_size=BS, prefix_cache=False),
                draft_params=params, draft_config=mc)
        else:
            engine = _engine(mc, params)
            if what == "export_prefix":
                engine.export_prefix(_tokens(8))
            elif what == "prefill_only":
                engine.submit(Request(prompt=_tokens(5), max_tokens=2,
                                      prefill_only=True))
            elif what == "preempt":
                engine.submit(Request(prompt=_tokens(5), max_tokens=4))
                engine.step()
                engine.preempt(0)
            else:
                engine.submit_adopted(
                    Request(prompt=[1, 2], max_tokens=4),
                    KVState(prompt=[1, 2], tokens=[3], next_tok=3, pos=2,
                            temperature=0.0, block_size=BS, blocks={}))


# ------------------------- (h) the decode tick's two attention paths

@pytest.mark.parametrize("seed", [0, 3])
def test_engine_serves_the_same_greedy_tokens_on_both_attention_paths(
        monkeypatch, shared_engine, seed):
    """Three prompts of different lengths beside each other, a free
    slot: the tokens through the kernel equal the gather path's, and
    `stats()` names the path.  The two paths' logits differ by a
    hundredth of their size, each as far from float32 throughout as the
    other (the kernel keeps scores in float32, the gather path rounds
    them), so the seeds are ones at which no served token's best two
    logits lie closer than that: a flip at another seed is that
    rounding, which `test_decode_step_agrees_on_both_attention_paths`
    bounds, and not a wrong row.  One engine a path serves both seeds:
    the programs take the parameters as an argument."""
    from ray_tpu.ops import attention
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    KL, c = _tiling()
    params = _drawn_at_a_tenth(KL, c, seed)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, c.vocab_size, n).tolist() for n in (5, 19, 40)]

    def serve(path):
        eng = shared_engine(("whole tiles", path), lambda: LLMEngine(
            params, c, EngineConfig(
                num_slots=4, max_seq_len=128, prefill_buckets=(16, 32, 64),
                kv_block_size=KERNEL_BS, prefix_cache=False)))
        eng.params = params
        handles = [eng.submit(Request(prompt=p, max_tokens=6))
                   for p in prompts]
        eng.drain()
        return [h.tokens for h in handles], eng.stats()

    gather_tokens, gather_stats = serve("gather")
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    kernel_tokens, kernel_stats = serve("kernel")
    assert kernel_tokens == gather_tokens
    assert all(len(t) == 6 for t in kernel_tokens)
    assert kernel_stats["paged_attention"] == "kernel"
    assert gather_stats["paged_attention"] == "gather"
    assert 0 < kernel_stats["live_rows"] == gather_stats["live_rows"] \
        < kernel_stats["padded_rows"]


# -------------------- (i) the recurrence's live step through the engine

def test_engine_steps_live_states_in_place_on_both_kda_paths(monkeypatch):
    """A float32 model with KDA heads of 128 through `LLMEngine`, two
    slots: a prompt in one bucket, one in three chunks, ticks, the first
    slot freed, left free while the other ticks on, then taken by a
    third request.  With `ops.kda.kda_step_live` forced through the
    interpreter against the plain `kda_step` path: the same tokens, the
    slots' states within 2e-6 of their size, a freed slot's rows the
    same BITS after the ticks that follow (the kernel never writes a
    dead slot; the plain form writes back what it read), and
    `kda_rows_stepped / (live_slots x KDA layers)` 1.0 against 0.0."""
    from ray_tpu.models import kimi_linear as KL
    from ray_tpu.ops import attention
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    c = KL.KimiLinearConfig.tiny(kda_head_dim=128, kda_heads=2,
                                 dtype=jnp.float32,
                                 param_dtype=jnp.float32)
    params = _drawn_at_a_tenth(KL, c, 2)
    first, long, third = (_tokens(n, seed=70 + n) for n in (9, 41, 20))

    def serve():
        eng = LLMEngine(params, c, EngineConfig(
            num_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
            kv_block_size=BS, num_kv_blocks=40, prefix_cache=False),
            rng_seed=3)
        a = eng.submit(Request(prompt=first, max_tokens=3,
                               chunked_prefill=True))
        b = eng.submit(Request(prompt=long, max_tokens=16,
                               chunked_prefill=True))
        eng.step()
        slot, = (i for i, s in enumerate(eng._slots) if s.handle is a)
        while a.finished_at is None:
            eng.step()
        freed = eng.slot_state(slot)
        assert np.abs(freed["S"]).max() > 1e-3
        for _ in range(4):                      # the other slot ticks on
            eng.step()
        assert b.finished_at is None and len(b.tokens) >= 4
        kept = eng.slot_state(slot)
        for leaf in freed:
            np.testing.assert_array_equal(kept[leaf], freed[leaf])
        d = eng.submit(Request(prompt=third, max_tokens=5,
                               chunked_prefill=True))
        while eng.has_work():
            eng.step()
        assert eng.stats()["slot_reuses"] >= 1
        ctr = eng.stats()["counters"]
        return ([h.tokens for h in (a, b, d)],
                [eng.slot_state(s)["S"] for s in range(2)],
                int(ctr["kda_rows_stepped"])
                / (int(ctr["live_slots"]) * c.n_kda_layers))

    plain_tokens, plain_states, plain_share = serve()
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert KL.kda.engages(c.kda_head_dim, c.kda_head_dim, c.state_dtype)
    kernel_tokens, kernel_states, kernel_share = serve()
    assert kernel_tokens == plain_tokens
    assert [len(t) for t in kernel_tokens] == [3, 16, 5]
    for got, want in zip(kernel_states, plain_states):
        assert np.abs(want).max() > 1e-3
        assert np.abs(got - want).max() <= 2e-6 * np.abs(want).max()
    assert (plain_share, kernel_share) == (0.0, 1.0)
