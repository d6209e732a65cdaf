"""Generation by diffusion over blocks (`models/blockdiff_moe.py`, the
block form of `serve/llm/engine.py`'s tick and insert, `ops/attention.py`
and `models/window_moe.py::blockwise_attention` under a block-causal
mask, `ops/paged_attention.py` at L queries a sequence) against the
plain float32 reference of `benchmarks/reference/blockdiff_moe_decoder.py`
on seeded random weights at a tiny size: hidden 64, 3 layers, 4 of 8
experts held, blocks of 4, a vocabulary of 512.

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit) on logits, float32
  against float32 on the CPU: the program and the reference differ in
  the ORDER of float32 sums (sorted expert groups against blocks of
  experts, grouped against repeated KV heads, an online softmax against
  one softmax over a masked row); that reads 1e-6 relative.
* The engine tests serve greedy tokens in float32 and judge them as the
  benchmark does, by `served_token_deficits` (token and place): a token
  may flip on a tie, a deficit may not exceed rounding, 1e-4 x the
  largest logit (about 3).  Every mutilated program is a hundred times
  that away in its worst token.
* The weights are drawn at 0.1, not the 0.02 of the published widths:
  at hidden 64 a 0.02 draw leaves every softmax flat.
"""

import os
import sys
import types

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

RTOL = 1e-4
TOL = 3e-4          # a deficit: 1e-4 x the largest logit
L = 4
C = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
         head_dim=16, moe_intermediate_size=32, num_experts=4,
         num_experts_per_tok=2, num_hidden_layers=3, rms_norm_eps=1e-6,
         rope_theta=1000000, rope_scaling=None, vocab_size=512,
         norm_topk_prob=True, tie_word_embeddings=False,
         initializer_range=0.1, block_length=L, denoising_steps=4,
         remasking_strategy="low_confidence_dynamic",
         confidence_threshold=0.9, mask_token_id=511,
         deployment=dict(num_experts=8, rank=0))
BS = 8              # rows a pool block
RULES = ("low_confidence_dynamic", "low_confidence_static", "sequential")


def _modules():
    from families import blockdiff_moe_decoder as F
    from reference import blockdiff_moe_decoder as R

    return F, R


def _build(c, seed=7, max_seq_len=128):
    F, R = _modules()
    w = R.init_weights(c, seed, jnp.float32)
    mc = F.model_config(c, max_seq_len=max_seq_len,
                        compute_dtype="float32", param_dtype="float32")
    return w, F.program_params(w), mc


@pytest.fixture(scope="module")
def built():
    return _build(C)


def _engine(params, mc, slots=2, **over):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    ec = dict(num_slots=slots, max_seq_len=128, prefill_buckets=(16, 32),
              kv_block_size=BS, prefix_cache=False)
    return LLMEngine(params, mc, EngineConfig(**{**ec, **over}))


def _prompt(n, seed=None):
    return [int(t) for t in np.random.RandomState(
        n if seed is None else seed).randint(0, 512, size=(n,))]


def _serve(engine, jobs, **kw):
    from ray_tpu.serve.llm.engine import Request

    hs = [engine.submit(Request(prompt=p, max_tokens=m, **kw))
          for p, m in jobs]
    engine.drain()
    return hs


# --------------------------------------------------------------- (a), (d)

def test_forward_under_the_block_causal_mask_is_the_references(built):
    from ray_tpu.models import blockdiff_moe as M

    _, R = _modules()
    w, params, mc = built
    toks = np.asarray(_prompt(23))
    got = M.forward(params, jnp.asarray(toks)[None], mc)[0]
    want = R.forward(w, C, toks)
    assert float(jnp.abs(got - want).max()) \
        <= RTOL * float(jnp.abs(want).max())
    # and the mask is what moves them: a causal reading is far away
    causal = R.forward(w, dict(C, block_length=1), toks)
    assert float(jnp.abs(causal - want).max()) \
        > 100 * RTOL * float(jnp.abs(want).max())


def test_the_four_ranks_parts_add_up_to_the_uncut_layer():
    """The held experts' part of one layer, summed over the four ranks
    that share it, is the whole layer's routed sum."""
    from ray_tpu.models import blockdiff_moe as M

    F, R = _modules()
    c4 = dict(C, num_experts=2)            # 2 of 8 held: four ranks
    h = jax.random.normal(jax.random.key(3), (24, 64), jnp.float32)
    total, keys = 0.0, []
    for rank in range(4):
        c = dict(c4, deployment=dict(num_experts=8, rank=rank))
        w, params, mc = _build(c)
        y, sizes = M.routed_experts(mc, params["layers"][1], h)
        assert sizes.shape == (2,)
        total = total + y
        keys.append(w["layers"][1]["experts"]["keys"])
    whole = dict(C, num_experts=8, deployment={})
    experts = dict(w["layers"][1]["experts"], keys=jnp.concatenate(keys))
    with jax.default_matmul_precision("highest"):
        want = R.routed(whole, h, dict(w["layers"][1], experts=experts))
    assert float(jnp.abs(total - want).max()) \
        <= RTOL * float(jnp.abs(want).max())
    assert float(jnp.abs(y - want).max()) \
        > 100 * RTOL * float(jnp.abs(want).max())   # one rank is a part


# -------------------------------------------------------------------- (b)

@pytest.mark.parametrize("tail", [0, 1, 2, 3])
def test_prefill_then_denoise_through_the_pool_is_the_references(built,
                                                                 tail):
    """The prompt's whole blocks through `prefill_paged`, then every
    state of three blocks through `denoise_paged` over the paged pool
    (the second block crosses into a new pool block of 8 rows): the
    block's hidden rows times the head are the reference's full forward
    of [prompt ‖ committed blocks ‖ state] at the block's rows."""
    from ray_tpu.models import blockdiff_moe as M

    _, R = _modules()
    w, params, mc = built
    M_ID = C["mask_token_id"]
    prompt = _prompt(12 + tail, seed=40 + tail)
    P = len(prompt)
    Pw = P - tail
    nb, Pb = 6, 16
    pools = M.init_paged_pool(mc, 10, BS)
    table = jnp.asarray([[7, 2, 5, 0, 9, 1]], jnp.int32)
    padded = np.zeros((Pb,), np.int32)
    padded[:Pw] = prompt[:Pw]
    hist = {k: jnp.zeros((p.shape[0], nb * BS, p.shape[-1]), p.dtype)
            for k, p in pools.items()}
    _, rows = M.prefill_paged(params, jnp.asarray(padded)[None], 0, hist,
                              mc, Pw)
    pools = {k: p.at[:, table[0, :Pb // BS]].set(
        rows[k].reshape(p.shape[0], Pb // BS, BS, -1))
        for k, p in pools.items()}
    final = list(prompt[:Pw])
    toks = list(prompt[Pw:]) + [M_ID] * (L - tail)
    masked = np.arange(L) >= tail
    active = jnp.ones((1,), bool)
    scale = None
    for _ in range(3):
        b = len(final) // L
        while True:
            x, pools, _ = M.denoise_paged(
                params, pools, table, jnp.asarray([toks], jnp.int32),
                jnp.asarray([b * L], jnp.int32), mc, active)
            lg = x @ M.lm_head_weight(params, mc)   # the engine's part
            ids, pos, blk, state = R._rows_of(final, L, [(b, toks)])
            want = R.logits_of_rows(w, C, ids, pos, blk, state, len(final),
                                    L)
            scale = scale or float(jnp.abs(want).max())
            assert float(jnp.abs(lg[0] - want).max()) <= RTOL * scale
            if not masked.any():
                break                       # that forward was the commit
            i = int(np.nonzero(masked)[0][-1])      # any order will do
            toks[i], masked[i] = int(jnp.argmax(want[i, :M_ID])), False
        final += toks
        toks, masked = [M_ID] * L, np.ones((L,), bool)


def test_block_causal_kernel_is_the_loop(monkeypatch):
    """`flash_prefill(causal_block=4)` (interpreted) against the loop of
    `blockwise_attention` at shapes where the kernel engages."""
    from ray_tpu.models import window_moe
    from ray_tpu.ops import attention

    ks = jax.random.split(jax.random.key(5), 3)
    Q, S, H, kvh, hd = 1024, 2048, 4, 2, 128
    q = jax.random.normal(ks[0], (Q, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (S, kvh, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (S, kvh, hd), jnp.bfloat16)
    start = 0       # the first queries see a key or four: far apart
    qpos = start + jnp.arange(Q)
    args = (q, k, v, qpos, 0, 0, (start + Q) // 1024, None, 1024)
    loop = window_moe.blockwise_attention(*args, causal_block=4)
    causal = window_moe.blockwise_attention(*args)
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert attention.prefill_engages(Q, hd, S)
    kernel = window_moe.blockwise_attention(*args, causal_block=4)
    err = float(jnp.abs(kernel.astype(jnp.float32)
                        - loop.astype(jnp.float32)).max())
    assert err <= 2 ** -6, err
    assert float(jnp.abs(causal.astype(jnp.float32)
                         - loop.astype(jnp.float32)).max()) > 0.5
    with pytest.raises(ValueError, match="power of two"):
        attention.flash_prefill(q, k, v, 0, 0, S, causal_block=3)


# -------------------------------------------------------------------- (c)

JOBS = ((9, 10), (16, 7), (3, 13), (30, 5), (18, 16), (23, 9))


@pytest.fixture(scope="module", params=RULES)
def served(request):
    """Six requests through two slots under one rule: prompts with every
    P mod 4, one shorter than a block, `max_tokens` off the block's
    grid, slots released and taken again."""
    c = dict(C, remasking_strategy=request.param)
    w, params, mc = _build(c)
    engine = _engine(params, mc)
    jobs = [(_prompt(n), m) for n, m in JOBS]
    return types.SimpleNamespace(c=c, w=w, engine=engine, jobs=jobs,
                                 handles=_serve(engine, jobs))


def test_engine_serves_what_the_reference_generates(served):
    _, R = _modules()
    for h, (prompt, m) in zip(served.handles, served.jobs):
        assert h.finish_reason == "length" and len(h.tokens) == m
        d = R.served_token_deficits(served.w, served.c, prompt, h.tokens)
        assert d.shape == (m,) and float(d.max()) <= TOL, d
        assert h.tokens == R.generate(served.w, served.c, prompt, m)


def test_rows_forwarded_and_tokens_emitted_are_counted_apart(served):
    st = served.engine.stats()
    blk, ctr = st["block"], st["counters"]
    assert blk["tokens_emitted"] == sum(m for _, m in JOBS)
    assert blk["rows_forwarded"] == L * blk["slot_forwards"]
    assert blk["slot_forwards"] == int(ctr["block_forwards"])
    assert blk["slot_forwards"] > blk["tokens_emitted"]
    assert int(ctr["block_threshold_fixes"]) == 0
    assert st["slot_reuses"] >= len(JOBS) - 2
    assert st["traces"]["tick"] == 1 and st["traces"]["insert"] <= 2
    # a block of 4 takes 4 steps and a commit; a first block whose
    # prompt's tail fixed t positions takes 4 - t and a commit
    assert 0.7 < int(ctr["block_tokens_fixed"]) / blk["slot_forwards"] <= 0.8


def test_an_end_token_inside_a_block_drops_the_blocks_rest(built):
    _, R = _modules()
    w, params, mc = built
    prompt = _prompt(10)
    whole = R.generate(w, C, prompt, 12)
    eos = whole[3]                  # the second token of the second block
    first = whole.index(eos)
    engine = _engine(params, mc, eos_id=eos)
    h, = _serve(engine, [(prompt, 12)])
    assert h.finish_reason == "eos"
    assert h.tokens == whole[:first + 1] == R.generate(w, C, prompt, 12, eos)
    g, = _serve(engine, [(prompt, 12)], stop=(whole[5],))
    assert g.finish_reason in ("stop", "eos")
    assert g.tokens == h.tokens or g.tokens == whole[:whole.index(whole[5])]


def test_two_slots_at_different_steps_share_one_tick(built):
    """A request admitted while another is mid-block: the two slots sit
    at different steps of their blocks in the same ticks, one program."""
    from ray_tpu.serve.llm.engine import Request

    _, R = _modules()
    w, params, mc = built
    engine = _engine(params, mc)
    a, b = _prompt(9), _prompt(18)      # tails of 1 and of 2
    ha = engine.submit(Request(prompt=a, max_tokens=16))
    for _ in range(3):
        engine.step()                   # a is two ticks into its block
    hb = engine.submit(Request(prompt=b, max_tokens=12))
    engine.step()
    engine._settle("ctrl")
    steps = np.asarray(engine._programs._blk["step"])
    assert steps[0] != steps[1], steps
    engine.drain()
    assert engine.stats()["traces"]["tick"] == 1
    for h, p in ((ha, a), (hb, b)):
        d = R.served_token_deficits(w, C, p, h.tokens)
        assert float(d.max()) <= TOL, d


def test_sampling_at_a_temperature_serves_whole_requests(built):
    w, params, mc = built
    engine = _engine(params, mc)
    hot, cold = _serve(engine, [(_prompt(9), 10)], temperature=0.8) \
        + _serve(engine, [(_prompt(9), 10)])
    assert len(hot.tokens) == len(cold.tokens) == 10
    assert C["mask_token_id"] not in hot.tokens
    assert hot.tokens != cold.tokens


# -------------------------------------------------------------------- (e)

def _worst_deficit(c, monkeypatch, patch):
    """The largest deficit of what a program mutilated by `patch` serves,
    and the sound program's for the same requests."""
    _, R = _modules()
    w, params, mc = _build(c)
    jobs = [(_prompt(n), m) for n, m in ((9, 12), (18, 16), (16, 12))]
    out = []
    for mutilated in (False, True):
        with monkeypatch.context() as mp:
            if mutilated:
                patch(mp)
            hs = _serve(_engine(params, mc), jobs)
        out.append(max(float(R.served_token_deficits(
            w, c, p, h.tokens).max()) for h, (p, _) in zip(hs, jobs)))
    return out


def _causal_inside_the_block(mp):
    from ray_tpu.models import blockdiff_moe as M

    mp.setattr(M, "_block_end", lambda pos, L: pos)


def _commit_keeps_the_last_steps_rows(mp):
    from ray_tpu.serve.llm import programs as E

    mp.setattr(E, "_block_writes", lambda active, is_open: active & is_open)


def _rule_ignores_confidence(mp):
    from ray_tpu.serve.llm import programs as E

    choose = E._block_choose
    mp.setattr(E, "_block_choose", lambda conf, *a: choose(
        jnp.zeros_like(conf), *a))


def _rotary_from_the_blocks_start(mp):
    from ray_tpu.models import blockdiff_moe as M

    mp.setattr(M, "_rope_positions", lambda qpos: qpos % L)


@pytest.mark.parametrize("patch", [
    _causal_inside_the_block, _commit_keeps_the_last_steps_rows,
    _rule_ignores_confidence, _rotary_from_the_blocks_start],
    ids=lambda f: f.__name__.strip("_"))
def test_a_mutilated_program_fails_the_reference(monkeypatch, patch):
    rule = "low_confidence_static"      # a rule that reads confidence
    sound, broken = _worst_deficit(dict(C, remasking_strategy=rule),
                                   monkeypatch, patch)
    assert sound <= TOL
    assert broken > 100 * TOL, (patch.__name__, broken)


# --------------------------------------------------------------- (f), (g)

def test_a_threshold_of_zero_commits_every_block_after_one_step():
    _, R = _modules()
    c = dict(C, confidence_threshold=0.0)
    w, params, mc = _build(c)
    engine = _engine(params, mc)
    jobs = [(_prompt(16), 16), (_prompt(32), 12)]     # no tail: whole blocks
    for h, (p, m) in zip(_serve(engine, jobs), jobs):
        d = R.served_token_deficits(w, c, p, h.tokens)
        assert float(d.max()) <= TOL, d
        assert h.tokens == R.generate(w, c, p, m)
    ctr = engine.stats()["counters"]
    fixed, forwards = int(ctr["block_tokens_fixed"]), \
        int(ctr["block_forwards"])
    # every block: one step fixes all 4 (3 of them beyond the step's
    # share), one commit; the commit behind each request's last block is
    # dispatched before that block's landing ends the request
    assert fixed == 28 and int(ctr["block_threshold_fixes"]) == 21
    assert int(ctr["block_commits"]) == 7 and fixed / forwards == 2.0


@pytest.mark.parametrize("what", ["decode_block", "draft", "prefix_cache",
                                  "kv_block_size", "prefill_only",
                                  "adopt", "preempt", "export_prefix"])
def test_what_a_block_model_does_not_offer_is_refused_by_name(built, what):
    from ray_tpu.serve.llm.engine import Request
    from ray_tpu.serve.llm.kv_cache import KVState

    w, params, mc = built
    name = "generates by blocks"
    if what == "decode_block":
        with pytest.raises(ValueError, match=name):
            _engine(params, mc, decode_block=2)
    elif what == "kv_block_size":
        with pytest.raises(ValueError, match="whole"):
            _engine(params, mc, kv_block_size=2, max_seq_len=128,
                    prefill_buckets=(16,))
    elif what == "prefix_cache":
        with pytest.raises(ValueError, match=name):
            _engine(params, mc, prefix_cache=True)
    elif what == "draft":
        from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

        with pytest.raises(ValueError, match="draft|speculative"):
            LLMEngine(params, mc, EngineConfig(
                num_slots=2, max_seq_len=128, prefill_buckets=(16,),
                kv_block_size=BS, prefix_cache=False),
                draft_params=params, draft_config=mc)
    else:
        engine = _engine(params, mc)
        if what == "prefill_only":
            with pytest.raises(ValueError, match=name):
                engine.submit(Request(prompt=[1, 2, 3], prefill_only=True))
        elif what == "adopt":
            with pytest.raises(ValueError, match=name):
                engine.submit_adopted(Request(prompt=[1, 2, 3]),
                                      KVState.__new__(KVState))
        elif what == "preempt":
            with pytest.raises(ValueError, match=name):
                engine.preempt(0)
        else:
            with pytest.raises(ValueError, match=name):
                engine.export_prefix([1] * 32)


# ------------------------------------------------- the head over compact rows

WIDE = 256          # slots: 1,024 rows a tick, so R = 384 and up to 3 passes
R = 384


@pytest.fixture(scope="module")
def wide(built):
    """An engine of 256 slots, its tick jitted as a plain function (no
    donation: the cases hand it states of their own), a pool of random
    rows and a table of its own blocks a slot."""
    from ray_tpu.serve.llm import programs as E

    _, params, mc = built
    engine = _engine(params, mc, slots=WIDE)
    assert E._block_pass_rows(WIDE, L) == R
    pools = {k: 0.1 * jax.random.normal(jax.random.key(i), p.shape, p.dtype)
             for i, (k, p) in enumerate(sorted(engine._programs._cache.items()))}
    nb = engine.config.max_blocks_per_slot
    tables = jnp.arange(WIDE * nb, dtype=jnp.int32).reshape(WIDE, nb)
    return types.SimpleNamespace(
        engine=engine, params=params, mc=mc, pools=pools, tables=tables,
        tick=jax.jit(engine._programs._block_tick_fn))


def _state(n_fixed, seed=0):
    """The open blocks of 256 slots, slot i with its `n_fixed[i]` of
    lowest index fixed (any set will do: the rule fixes by confidence),
    at the step that many fixes make and at a position of its own."""
    rng = np.random.RandomState(seed)
    fixed = np.arange(L)[None, :] < np.asarray(n_fixed)[:, None]
    tok = np.where(fixed, rng.randint(0, 511, size=(WIDE, L)),
                   C["mask_token_id"])
    return {"tok": jnp.asarray(tok, jnp.int32), "fixed": jnp.asarray(fixed),
            "step": jnp.asarray(n_fixed, jnp.int32),
            "pos0": jnp.asarray(L * (2 + rng.randint(0, 6, size=(WIDE,))),
                                jnp.int32)}


def _counters(engine):
    return jax.tree.map(jnp.zeros_like, engine._programs._counters)


def _dense_tick(w, blk, active, temp):
    """What the tick leaves, with the head over ALL slots x L rows and
    the softmax over all of its logits: (pools, blk, out, done)."""
    from ray_tpu.models import blockdiff_moe as M
    from ray_tpu.serve.llm import programs as E

    spec = w.engine._block
    tok, fixed, step, pos0 = (blk[k] for k in ("tok", "fixed", "step",
                                                "pos0"))
    masked = ~fixed
    is_open = masked.any(-1)
    hidden, pools, _ = M.denoise_paged(
        w.params, w.pools, w.tables, tok, pos0, w.mc, active, active)
    logits = jnp.dot(hidden, M.lm_head_weight(w.params, w.mc),
                     preferred_element_type=jnp.float32)
    logits = logits.at[..., spec.mask_token_id].set(-jnp.inf)
    x0 = jnp.argmax(logits, -1).astype(jnp.int32)
    conf = jnp.max(jax.nn.softmax(logits, -1), -1)
    fixing, committing = active & is_open, active & ~is_open
    pick = E._block_choose(conf, masked, E._block_share(step, spec), spec) \
        & fixing[:, None]
    tok = jnp.where(pick, x0, tok)
    fixed = fixed | pick
    S = w.engine.config.max_seq_len
    mine = committing[:, None]
    return pools, {
        "tok": jnp.where(mine, spec.mask_token_id, tok),
        "fixed": jnp.where(mine, False, fixed),
        "step": jnp.where(committing, 0, step + fixing),
        "pos0": jnp.where(committing, jnp.minimum(pos0 + L, S - L), pos0),
    }, tok, fixing & fixed.all(-1), x0


# name -> (positions fixed a slot, live slots, passes of R = 384 rows)
_EVERY = np.ones((WIDE,), bool)
_AT = np.arange(WIDE)
_MIX = _AT % 5                  # steps 0..4 side by side: 2 rows a slot
_MIX_LIVE = _AT % 7 != 3        # a dead slot between live ones: 439 rows
STATES = {
    "every_position_masked": (np.zeros(WIDE, int), _EVERY, 3),
    "all_committing": (np.full(WIDE, L), _EVERY, 0),
    "all_dead": (np.zeros(WIDE, int), ~_EVERY, 0),
    # 96 slots at step 0: 384 rows; one more slot with one masked: 385
    "exactly_a_pass": (np.where(_AT % 8 < 3, 0, L), _EVERY, 1),
    "a_pass_and_a_row": (np.where(_AT % 8 < 3, 0,
                                  np.where(_AT == 77, 3, L)), _EVERY, 2),
    "steps_mixed_dead_between": (_MIX, _MIX_LIVE, 2),
}


@pytest.mark.parametrize("name", list(STATES))
def test_the_tick_over_compact_rows_is_the_dense_evaluation(wide, name):
    """`_block_tick_fn` multiplies by the head only the rows still
    masked in a live slot whose block is open, 384 a pass: tokens,
    flags, steps, positions, `done`, the emitted block and the pool are
    what a head over all 1,024 rows and a softmax over all of its
    logits leave, and the passes are as many as the needed rows fill
    (with every row masked, the slots x L / R there are, rounded up)."""
    n_fixed, live, passes = STATES[name]
    blk, active = _state(n_fixed, seed=len(name)), jnp.asarray(live)
    needed = int(((L - n_fixed) * live).sum())
    assert passes == -(-needed // R)
    temp = jnp.zeros((WIDE,), jnp.float32)
    pools, got, _, out, done, ctr = wide.tick(
        wide.params, wide.pools, wide.tables, blk, active, temp,
        jax.random.key(1), _counters(wide.engine))
    want_pools, want, want_out, want_done, _ = _dense_tick(
        wide, blk, active, temp)
    for k in ("tok", "fixed", "step", "pos0"):
        assert np.array_equal(got[k], want[k]), k
    assert np.array_equal(out, want_out) and np.array_equal(done, want_done)
    for k in pools:     # two compilations of one forward: float32 rounding
        assert np.allclose(pools[k], want_pools[k], rtol=0, atol=1e-5), k
    assert int(ctr["head_passes"]) == passes
    assert int(ctr["head_rows_walked"]) == R * passes
    assert int(ctr["head_rows_dense"]) == WIDE * L
    if needed:          # something was fixed, and only where it was live
        moved = np.asarray(got["fixed"] != blk["fixed"]).any(-1)
        assert moved.any() and not (moved & ~live).any()


def test_a_greedy_slot_beside_a_sampling_one_gets_its_argmax(wide):
    """Every other slot samples at a high temperature in the SAME tick:
    a pass gathers each row's temperature by the row's slot, so the
    greedy slots fix the dense argmax and the others do not."""
    n_fixed = np.zeros(WIDE, int)
    blk = _state(n_fixed, seed=3)
    hot = np.arange(WIDE) % 2 == 0
    temp = jnp.asarray(np.where(hot, 50.0, 0.0), jnp.float32)
    active = jnp.ones((WIDE,), bool)
    _, got, _, _, _, _ = wide.tick(
        wide.params, wide.pools, wide.tables, blk, active, temp,
        jax.random.key(2), _counters(wide.engine))
    _, want, _, _, x0 = _dense_tick(wide, blk, active, jnp.zeros_like(temp))
    got_fixed, got_tok = np.asarray(got["fixed"]), np.asarray(got["tok"])
    assert (got_fixed.sum(-1) == 1).all()       # a step's share: one of 4
    cold = ~hot
    assert np.array_equal(got_fixed[cold], np.asarray(want["fixed"])[cold])
    assert np.array_equal(got_tok[cold], np.asarray(want["tok"])[cold])
    # at a temperature of 50 a draw is all but uniform over 511 tokens
    drawn = got_tok[hot][got_fixed[hot]]
    assert (drawn != np.asarray(x0)[hot][got_fixed[hot]]).mean() > 0.9
    assert C["mask_token_id"] not in drawn


def test_the_heads_rows_are_counted(wide):
    """Three requests through 256 slots: a tick's head walks one pass
    of 384 rows (none where all three commit) of the 1,024 it forwards."""
    engine = wide.engine
    before = {k: int(v) for k, v in engine.stats()["counters"].items()
              if np.ndim(v) == 0}
    hs = _serve(engine, [(_prompt(n), m) for n, m in JOBS[:3]])
    assert all(len(h.tokens) == m for h, (_, m) in zip(hs, JOBS[:3]))
    ctr = {k: int(v) - before[k] for k, v in
           engine.stats()["counters"].items() if k in before}
    assert ctr["head_rows_walked"] == R * ctr["head_passes"] > 0
    assert ctr["head_rows_dense"] == ctr["ticks"] * WIDE * L
    assert ctr["head_passes"] <= ctr["ticks"]
    assert ctr["head_rows_walked"] < ctr["head_rows_dense"] / 2


# ------------------------------------------------------------ the rule alone

@pytest.mark.parametrize("rule", RULES)
def test_the_rule_fixes_what_the_family_says(rule):
    from ray_tpu.models.serving import BlockSpec
    from ray_tpu.serve.llm import programs as E

    spec = BlockSpec(4, 2, rule, 0.5, 511)      # two steps of two
    conf = jnp.asarray([[0.1, 0.4, 0.3, 0.2],
                        [0.6, 0.1, 0.7, 0.9],
                        [0.2, 0.9, 0.1, 0.3]], jnp.float32)
    masked = jnp.asarray([[1, 1, 1, 1], [1, 1, 1, 1], [0, 1, 1, 1]], bool)
    share = E._block_share(jnp.asarray([0, 1, 5]), spec)
    assert share.tolist() == [2, 2, 2]
    got = np.asarray(E._block_choose(conf, masked, share, spec)).tolist()
    want = {"sequential": [[1, 1, 0, 0], [1, 1, 0, 0], [0, 1, 1, 0]],
            "low_confidence_static": [[0, 1, 1, 0], [0, 0, 1, 1],
                                      [0, 1, 0, 1]],
            # row 1: three pass 0.5, at least the share: all three
            "low_confidence_dynamic": [[0, 1, 1, 0], [1, 0, 1, 1],
                                       [0, 1, 0, 1]]}[rule]
    assert got == [[bool(x) for x in r] for r in want]
    odd = BlockSpec(4, 3, rule, 0.5, 511)       # 4 over 3 steps: 2, 1, 1
    assert E._block_share(jnp.arange(4), odd).tolist() == [2, 1, 1, 1]


@pytest.mark.parametrize("heads,kv_heads", [(32, 4), (8, 4)])
def test_denoise_agrees_on_both_paged_paths(monkeypatch, heads, kv_heads):
    """A tick's forward in bf16 at heads of 128 over a pool block of 16
    (shapes at which the paged kernel engages), through the gather and
    through the kernel under the interpreter: at 4 x 32 heads over 4 KV
    heads the call walks its KV groups (32 query rows a group, SDAR's
    own), at 8 heads it lays every row as wide as the pool's; a dead
    slot between live ones, a slot in its first block and one further
    in.  The same rows in the pool but for what the first layer's
    attention rounds; hidden rows a bf16 rounding or two of an O(1)
    value apart."""
    from ray_tpu.models import blockdiff_moe as M
    from ray_tpu.ops import attention, paged_attention as paged

    mc = M.BlockDiffMoEConfig(
        vocab_size=512, dim=64, n_layers=2, n_heads=heads,
        n_kv_heads=kv_heads, head_dim=128, expert_hidden_dim=32,
        n_experts=4, top_k=2, max_seq_len=128, mask_token_id=511)
    assert paged.walks_groups(L, heads, kv_heads) == (heads == 32)
    params = M.init_params(mc, jax.random.key(2), std=0.1)
    pools = jax.tree.map(
        lambda x: jax.random.normal(jax.random.key(3), x.shape, x.dtype),
        M.init_paged_pool(mc, 24, 16))
    tables = jnp.asarray(np.random.RandomState(4).permutation(24)
                         .reshape(3, 8), jnp.int32)
    tok = jnp.asarray(np.random.RandomState(5).randint(0, 511, (3, L)),
                      jnp.int32)
    pos0 = jnp.asarray([0, 40, 100], jnp.int32)
    active = jnp.asarray([True, False, True])

    def run():
        return jax.jit(lambda *a: M.denoise_paged(*a, mc, active))(
            params, pools, tables, tok, pos0)

    assert M._paged_attention(pools) == "gather"
    want, want_pools, _ = run()
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert M._paged_attention(pools) == "kernel"
    got, got_pools, _ = run()
    for name in ("k", "v"):     # layer 0's rows come before any attention
        a, b = (np.asarray(x[name], np.float32)
                for x in (got_pools, want_pools))
        np.testing.assert_array_equal(a[0], b[0])
        np.testing.assert_allclose(a[1], b[1], atol=2 ** -4, rtol=0)
    live = np.asarray(active)
    np.testing.assert_allclose(
        np.asarray(got, np.float32)[live], np.asarray(want, np.float32)[live],
        atol=2 ** -5, rtol=0)


def test_queries_of_a_block_cut_the_slots_to_fit_vector_memory():
    from ray_tpu.ops import paged_attention as paged

    assert paged.slot_parts(256, 192) == 1
    # 4 queries x 32 heads x 512 lanes of bf16 a sequence, as every row
    # was laid until PR 58; 128 lanes wide, walked a KV group: one call
    assert paged.slot_parts(256, 192, query_bytes=4 * 32 * 512 * 2) == 4
    assert paged.slot_parts(
        256, 192, query_bytes=paged.query_bytes(4, 32, 4, 128)) == 1
    assert paged.slot_parts(384, 768) == 2      # scalar memory, as before
