"""Mamba-1 layers with RMSNorms on Delta, B and C and their state by slot
beside attention layers of ONE K/V head in a paged pool, a SwiGLU a
layer (`models/jamba.py`, with the mixer body of `models/sambay.py`,
`ops/selective_scan.py`, `ops/paged_attention.py`,
`serve/llm/engine.py`), against the plain float32 reference of
`benchmarks/reference/mamba_mqa_decoder.py` on seeded random weights at
a tiny size.  Logits are compared, never sampled tokens (but for the
engine tests, which judge served tokens by their reference logits, as
the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit, about 3 here) on
  logits, float32 against float32 on the CPU: the program's blockwise
  online softmax, its scan over folded channels and its norms inside a
  loop body against the reference's plain softmax and token-by-token
  scan differ in the ORDER of float32 sums; that reads 2e-6 relative.
  Every mutilated program reads 100 x the tolerance and more: with
  Delta, B and C normed to unit size the scan is a large share of every
  logit, so a norm left out (either way: out of the reference, or out of
  the PROGRAM) moves logits by a tenth of their size.
* 1e-5 relative on the state and the tails handed on: the same sums in
  another order, one layer deep.
* The weights are drawn at 0.1, not the 0.02 of the published widths,
  and every bias and norm vector is drawn too (the family's draws are
  zeros and ones, which would hide a norm's weight left out): with a
  tied head, hidden 64 and 0.02 the model echoes its input token
  whatever the layers do.
* 2e-2 of the logits' size between the kernel path and the gather path
  in bf16: bf16 operands either way, the kernel keeps float32 scores
  where the gather rounds them.  That case draws its weights at 0.05:
  at 0.1 and heads of 128 the scores are large enough that rounding
  them to bf16 alone moves a logit by 2.3% of the largest.
"""

import dataclasses
import functools
import json
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

RTOL = 1e-4
# `M*MM*M`: attention at layers 1 and 4, four query heads over the one
# K/V head, d_inner one lane row
C = dict(model_type="jamba", hidden_size=64, num_attention_heads=4,
         num_key_value_heads=1, intermediate_size=128, hidden_act="silu",
         num_hidden_layers=6, attn_layer_period=3, attn_layer_offset=1,
         expert_layer_period=2, expert_layer_offset=1, num_experts=1,
         num_experts_per_tok=1, sliding_window=None, rms_norm_eps=1e-6,
         tie_word_embeddings=True, vocab_size=512, mamba_d_state=4,
         mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=8,
         mamba_conv_bias=True, mamba_proj_bias=False,
         initializer_range=0.1, precision=dict(recurrent_state="float32"))
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


def _drawn(weights):
    """Every norm's weight and the convolution's bias drawn, so that
    each is seen."""
    def leaf(path, x):
        name = path[-1].key
        if "norm" not in name and name != "conv_b":
            return x
        key = jax.random.key(sum(map(ord, jax.tree_util.keystr(path))))
        return (x + 0.1 * jax.random.normal(key, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, weights)


def _build(c, max_seq_len=64, **overrides):
    from families import mamba_mqa_decoder as F
    from reference import mamba_mqa_decoder as R

    mc = F.model_config(c, max_seq_len=max_seq_len,
                        compute_dtype="float32", param_dtype="float32",
                        prefill_key_block=8, **overrides)
    weights = _drawn(R.init_weights(c, 11, jnp.float32))
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


@functools.cache
def _jitted(name):
    """A program function of `models/jamba.py` under `jax.jit`, its
    configuration static: one compile a shape for the whole module."""
    from ray_tpu.models import jamba as M

    return jax.jit(getattr(M, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C, **kw):
    return np.asarray(R.logits_for_positions(weights, c, toks, start, n,
                                             **{"pad_to": 16, **kw}))


def _off(got, want):
    scale = np.abs(want).max()
    assert scale > 0.3
    return np.abs(np.asarray(got) - want).max() / scale


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert (mc.kinds, mc.mamba_runs, mc.n_ssm_layers, mc.n_attn_layers,
            mc.head_dim, mc.d_inner, mc.dt_rank) \
        == ("M*MM*M", [1, 2, 1], 4, 2, 16, 128, 8)
    assert params is weights            # one copy of the model
    toks = _tokens(50)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 50)) < RTOL


def test_published_layers_attend_at_7_and_21_and_count_to_3_billion():
    """The configuration file as the family reads it: 28 layers,
    attention at 7 and 21 alone, runs of 7, 13 and 6 Mamba layers; the
    reference's shapes, the counts module and the file's `constants`
    agree on the published total, kind by kind."""
    import counts_mamba_mqa as K
    from families import mamba_mqa_decoder as F
    from reference import mamba_mqa_decoder as R

    with open(os.path.join(BENCH, "configs",
                           "ai21-jamba2-3b-serve.json")) as f:
        c = json.load(f)
    mc = F.model_config(c, max_seq_len=8192, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    assert [i for i, k in enumerate(mc.kinds) if k == "*"] == [7, 21]
    assert mc.mamba_runs == [7, 13, 6] and mc.n_ssm_layers == 26
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.d_inner, mc.dt_rank,
            mc.hidden_dim, mc.norm_eps) == (20, 1, 128, 5120, 160, 8192, 1e-6)
    assert [k == "attention" for k in R.layer_kinds(c)] \
        == [k == "attn" for k in K.layer_kinds(c)] \
        == [k == "*" for k in mc.kinds]
    got = R.param_counts(c)
    assert got["total"] == K.total_params(c) == 3_029_337_472
    assert (got["mamba_layer"], got["attn_layer"]) \
        == (104_161_472, 76_682_240)
    assert c["constants"] == K.constants(c) and c["reduced"] == {}
    assert (c["constants"]["state_bytes_per_slot_f32"],
            c["constants"]["tail_bytes_per_slot_bf16"],
            c["constants"]["kv_row_bytes_bf16"]) == (8_519_680, 798_720, 1024)


# ---------------------- (b) prefill + decode: the pool and the slot state

def _prefill(mc, params, pools, state, slot, table, toks, start,
             bucket=BUCKET):
    """One bucket-padded chunk of `toks` at `start` into the blocks of
    `table` and the state row of `slot`, as the engine's insert program
    does it.  Returns the hidden of the last REAL row."""
    bs = pools["k"].shape[2]
    hist = {k: v[:, table].reshape((v.shape[0], -1) + v.shape[3:])
            for k, v in pools.items()}
    padded = np.zeros((bucket,), np.int32)
    padded[:len(toks)] = toks
    mine = {k: jnp.where(start > 0, v[:, slot], 0) for k, v in state.items()}
    x, rows, mine = _jitted("prefill_paged")(
        params, jnp.asarray(padded)[None], jnp.int32(start), hist, mc,
        jnp.int32(len(toks)), mine)
    ids = table[start // bs + np.arange(bucket // bs)]
    pools = {k: v.at[:, ids].set(rows[k].reshape(
        (v.shape[0], bucket // bs, bs) + v.shape[3:]))
        for k, v in pools.items()}
    state = {k: v.at[:, slot].set(mine[k]) for k, v in state.items()}
    return x[0, len(toks) - 1:len(toks)], pools, state


def _fresh(mc, n_blocks, slots=3, bs=BS):
    from ray_tpu.models.jamba import init_paged_pool, init_slot_state

    pools = init_paged_pool(mc, n_blocks + 9, bs)
    return pools, init_slot_state(mc, slots), \
        np.arange(n_blocks, dtype=np.int32)[::-1] + 5


def _served_logits(mc, params, toks, n_prompt=None, slots=3, slot=2):
    """Logits at the LAST row of every chunk of the prompt and at every
    later position of `toks` through the serving path: the prompt in
    chunks of BUCKET (state, tails and rows handed on in the slot), the
    rest a decode step a token with dead slots beside the live one.
    Returns (positions, [len(positions), V]); checks that the dead
    slots' state stands."""
    from ray_tpu.models.jamba import _head

    n_prompt = n_prompt or len(toks) - 10
    n_blocks = -(-len(toks) // BUCKET) * BUCKET // BS
    pools, state, table = _fresh(mc, n_blocks, slots)
    # the slot holds another sequence's garbage: admission must clear it
    state = jax.tree.map(lambda x: x.at[:, slot].set(1.0), state)
    at, got = [], []
    for start in range(0, n_prompt, BUCKET):
        end = min(start + BUCKET, n_prompt)
        x, pools, state = _prefill(mc, params, pools, state, slot, table,
                                   toks[start:end], start)
        at.append(end - 1)
        got.append(np.asarray(_head(mc, params, x)))
    tables = np.zeros((slots, len(table)), np.int32)
    tables[slot] = table
    tables = jnp.asarray(tables)
    active = jnp.arange(slots) == slot
    dead = np.arange(slots) != slot
    before = jax.tree.map(lambda x: np.asarray(x[:, dead]), state)
    step = _jitted("decode_step_paged")
    for t in range(n_prompt, len(toks)):
        tok = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        tok[slot], pos[slot] = toks[t], t
        logits, pools, counts, state = step(
            params, pools, tables, jnp.asarray(tok), jnp.asarray(pos), mc,
            active, state)
        at.append(t)
        got.append(np.asarray(logits[slot:slot + 1]))
    for k, v in before.items():
        assert np.array_equal(np.asarray(state[k][:, dead]), v)
    assert int(counts["live_slots"]) == 1 and int(counts["ticks"]) == 1
    assert int(counts["ssm_live_steps"]) == 0         # `ssm_step` ran
    # the last tick read len(toks) rows in each attention layer
    assert int(counts["mqa_rows_read"]) == 2 * len(toks)
    return np.asarray(at), np.concatenate(got)


@pytest.mark.parametrize("case", ["one_bucket", "chunked"])
def test_paged_prefill_and_decode_match_reference(model, case):
    """Prefill (one bucket; three chunks, each over the rows, state and
    tails before it) and then 10 decode steps through the pool and the
    slot's state, dead slots beside the live one: logits at every served
    position against the reference's full forward."""
    R, mc, weights, params = model
    pools, state, _ = _fresh(mc, 8)
    assert pools["k"].shape == pools["v"].shape == (2, 17, BS, 16)
    assert state["h"].shape == (4, 3, 4, 1, 128)    # channels on lanes
    assert state["tail"].shape == (4, 3, 3 * 128)   # taps on lanes
    n_prompt = {"one_bucket": 13, "chunked": 43}[case]
    toks = _tokens(n_prompt + 10, seed=3)
    at, got = _served_logits(mc, params, toks, n_prompt)
    want = _reference_logits(R, weights, toks, 0, len(toks))[at]
    assert _off(got, want) < RTOL


def test_slot_state_and_tails_are_what_the_reference_carries(model):
    """After a chunked prompt the slot's `h` is the reference's state
    after the last REAL token (a chunk of 11 in a bucket of 16), and the
    tail the last three REAL rows of the first layer's `xs`."""
    from ray_tpu.ops.selective_scan import unfold

    R, mc, weights, params = model
    toks = _tokens(27, seed=6)
    pools, state, table = _fresh(mc, 8, slots=2)
    for a, b in ((0, 16), (16, 27)):
        _, pools, state = _prefill(mc, params, pools, state, 1, table,
                                   toks[a:b], a)
    got = np.asarray(unfold(state["h"][:, 1]))
    want = R.states(weights, C, toks)
    assert want.shape == got.shape == (4, 4, 128)
    assert np.abs(want).max() > 1e-3
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    # layer 0 is a Mamba and its input the embedding, whatever follows
    p = jax.tree.map(lambda a: np.asarray(a[0]), params["mamba"])
    x = np.asarray(params["embed"])[np.asarray(toks)]
    u = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-6) * p["norm_in"]
    rows = (u @ p["w_in"])[:, :128]
    assert np.abs(rows[24:27]).max() > 0.1
    np.testing.assert_allclose(np.asarray(state["tail"][0, 1]),
                               rows[24:27].reshape(-1), atol=1e-5)


@pytest.mark.parametrize("case", ["chunked_equals_whole",
                                  "padded_equals_unpadded"])
def test_prefill_hand_off(model, case):
    """A prompt prefilled in chunks leaves the served row, the pool's
    rows, the scan's state and the tail that the same prompt prefilled
    whole leaves; padding advances nothing."""
    _, mc, _, params = model
    toks = _tokens(32, seed=5)
    plans = {"chunked_equals_whole": ((((0, 29),), 32),
                                      (((0, 16), (16, 29)), 16)),
             "padded_equals_unpadded": ((((0, 16),), 32),
                                        (((0, 16),), 16))}[case]
    out = []
    for chunks, bucket in plans:
        pools, state, table = _fresh(mc, 8, slots=2)
        for a, b in chunks:
            x, pools, state = _prefill(mc, params, pools, state, 1, table,
                                       toks[a:b], a, bucket)
        n = chunks[-1][1]
        rows = [np.asarray(pools[k][:, table]).reshape(2, -1, 16)[:, :n]
                for k in ("k", "v")]
        out.append((np.asarray(x), rows,
                    {k: np.asarray(v[:, 1]) for k, v in state.items()}))
    (xa, ra, sa), (xb, rb, sb) = out
    assert xa.shape == xb.shape and np.abs(xa).max() > 0.5
    close = lambda a, b: np.abs(a - b).max() < 1e-5 * max(1, np.abs(a).max())
    assert close(xa, xb) and all(close(a, b) for a, b in zip(ra, rb))
    assert np.abs(sa["h"]).max() > 1e-3 and np.abs(sa["tail"]).max() > 1e-3
    for k in sa:
        assert close(sa[k], sb[k]), k


# ------------- (c) the mutilated programs fail the same comparison

def _tail_after_padding(self, st, j, xs, w):
    from ray_tpu.ops import short_conv

    y, tail = short_conv.short_conv(
        xs, w, short_conv.rows(st["tail"][j], w), xs.shape[1])
    return y, dict(st, tail=st["tail"].at[j].set(short_conv.flat(tail)))


def _norms_left_out(c, p, dbc):
    return dbc


def _only_dt_normed(c, p, dbc):
    from ray_tpu.models.llama import rms_norm

    R = c.dt_rank
    return jnp.concatenate([rms_norm(dbc[..., :R], p["dt_norm"].astype(
        jnp.float32), c.norm_eps), dbc[..., R:]], axis=-1)


# what the reference leaves out or changes, and the program then has
# that it has not; a callable is patched into the PROGRAM, which then
# has not what the sound reference has
MUTILATIONS = {
    "the_reference_without_the_norm_on_dt": "dt_norm",
    "the_reference_without_the_norms_on_b_and_c": "bc_norms",
    "dskip_dropped": "dskip",
    "delta_without_its_bias": "dt_bias",
    "rotary_added": "no_rotary",
    "the_program_without_its_three_norms": ("_normed", _norms_left_out),
    "the_program_norming_dt_alone": ("_normed", _only_dt_normed),
    "a_tail_taken_after_padded_rows": ("conv", _tail_after_padding),
}


@pytest.mark.parametrize("what", sorted(MUTILATIONS))
def test_a_mutilated_program_fails(model, what, monkeypatch):
    """The served logits (a chunked prompt, then decode steps) are far
    from each mutilated reference by 100 x the tolerance, and the
    program patched to leave its three norms out (or B's and C's, or to
    take its tail after a bucket's padding) is as far from the sound
    one: THE THREE NORMS ARE NOT OPTIONAL."""
    from ray_tpu.models import jamba as M
    from ray_tpu.models import sambay

    R, mc, weights, params = model
    toks = _tokens(27 + 6, seed=12)
    piece = MUTILATIONS[what]
    if not isinstance(piece, str):
        name, patch = piece
        monkeypatch.setattr(*((sambay._Sequences, name) if name == "conv"
                              else (M, name)), patch)
        # jitted anew (a partial is a function of its own): the
        # mutilation is there as it traces
        monkeypatch.setitem(globals(), "_jitted", lambda name: jax.jit(
            functools.partial(getattr(M, name)),
            static_argnames=("config",)))
        piece = None
    at, got = _served_logits(mc, params, toks, 27)
    want = _reference_logits(R, weights, toks, 0, len(toks),
                             without=(piece,) if piece else ())[at]
    assert _off(got, want) > 100 * RTOL


def test_a_bf16_state_fails_over_a_few_hundred_tokens(model):
    """The state rounded to bf16 between tokens: the family refuses the
    file that asks for it, and the program made to keep one (the config
    field, past the family) fails through the serving path, where the
    state is rounded between chunks and every token: the state itself
    by 100 x its tolerance, as the reference rounding its own the same
    way does, and the logits by 100 x what the sound program reads."""
    from ray_tpu.ops.selective_scan import unfold

    R, mc, weights, params = model
    toks = _tokens(300, seed=2)
    want = _reference_logits(R, weights, toks, 0, 300)
    at, sound = _served_logits(mc, params, toks, 100)
    assert _off(sound, want[at]) < RTOL
    rounding = dataclasses.replace(mc, state_dtype=jnp.bfloat16)
    _, bf16 = _served_logits(rounding, params, toks, 100)
    assert _off(bf16, want[at]) > 100 * _off(sound, want[at])
    h = R.states(weights, C, toks)
    off = {}
    for name, cfg in (("sound", mc), ("bf16", rounding)):
        pools, state, table = _fresh(cfg, 76, slots=2)
        for a in range(0, 300, BUCKET):
            _, pools, state = _prefill(cfg, params, pools, state, 1, table,
                                       toks[a:a + BUCKET], a)
        got = np.asarray(unfold(state["h"][:, 1]).astype(jnp.float32))
        off[name] = np.abs(got - h).max() / np.abs(h).max()
    assert off["sound"] < 1e-5 and off["bf16"] > 1e-3, off
    rounded = R.states(weights, C, toks, state_dtype=jnp.bfloat16)
    assert np.abs(rounded - h).max() / np.abs(h).max() > 1e-3


@pytest.mark.parametrize("refused, change", [
    ("recurrent state kept in bfloat16",
     {"precision": {"recurrent_state": "bfloat16"}}),
    ("num_experts 2", {"num_experts": 2}),
    ("a sliding_window of 4096", {"sliding_window": 4096}),
    ("an untied head", {"tie_word_embeddings": False}),
    ("mamba_proj_bias", {"mamba_proj_bias": True}),
    ("a convolution without its bias", {"mamba_conv_bias": False}),
    ("query heads that do not divide", {"num_key_value_heads": 3}),
    ("hidden_act gelu", {"hidden_act": "gelu"}),
])
def test_the_family_refuses_what_the_program_does_not_compute(refused,
                                                              change):
    from families import mamba_mqa_decoder as F

    with pytest.raises(ValueError, match=refused):
        F.model_config(dict(C, **change), max_seq_len=64,
                       compute_dtype="float32", param_dtype="float32")


def test_the_reference_trains_nothing():
    from reference import mamba_mqa_decoder as R

    for fn in (R.init_as_trainer, R.adamw_trajectory):
        with pytest.raises(NotImplementedError, match="no train cell"):
            fn()


def test_the_control_rounds_the_matrices_and_nothing_else(model):
    """`quantize_int8` (the benchmark's control) changes every matmul
    weight, a stacked leaf's layers each by their own scales, and hands
    back the table, the taps, the vectors and the decays untouched."""
    from ray_tpu.models.jamba import quantize_int8

    _, _, weights, _ = model
    rounded = jax.jit(quantize_int8)(weights)
    flat = jax.tree_util.tree_flatten_with_path(weights)[0]
    for (path, w), r in zip(flat, jax.tree.leaves(rounded)):
        name = path[-1].key
        matrix = name.startswith("w") and name != "conv_w"
        assert bool(jnp.any(w != r)) == matrix, jax.tree_util.keystr(path)
        if matrix:
            levels = np.unique(np.asarray(
                (r / jnp.max(jnp.abs(r), axis=-2, keepdims=True) * 127)
                .round(3)))
            assert len(levels) <= 255


# --------------------------------------------- the engine, end to end

def _engine(mc, params, **over):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    cfg = dict(num_slots=3, max_seq_len=64, prefill_buckets=(8, 16),
               kv_block_size=BS, num_kv_blocks=40, decode_block=1,
               prefix_cache=False)
    return LLMEngine(params, mc, EngineConfig(**{**cfg, **over}), rng_seed=0)


@pytest.fixture
def engine(model, shared_engine):
    """The module's one engine at `_engine`'s own configuration, every
    selector answering as on the CPU: drained when a case takes it and
    when it leaves it."""
    _, mc, _, params = model
    return shared_engine("three slots", lambda: _engine(mc, params))


LENGTHS = (5, 16, 23, 45)


def _serve(engine, which):
    from ray_tpu.serve.llm.engine import Request

    handles = [engine.submit(Request(
        prompt=_tokens(LENGTHS[i], seed=20 + i), max_tokens=6,
        temperature=0.0, chunked_prefill=LENGTHS[i] > 16)) for i in which]
    while engine.has_work():
        engine.step()
    assert all(h.finish_reason == "length" for h in handles)
    return [list(h.tokens) for h in handles]


def test_engine_serves_four_unequal_requests_as_it_serves_each_alone(
        model, engine):
    """Four requests of unequal length through three slots (a slot is
    reused with its state cleared), prompts shorter and longer than the
    top bucket: every served token's reference logit lies within the
    tolerance of the reference maximum, and each request's tokens are
    those it gets served ALONE (no slot's state, tail or rows leak into
    its neighbour's)."""
    R, mc, weights, params = model
    assert engine._stateful and engine._ring is None
    live_before = int(engine.stats()["counters"]["live_slots"])
    together = _serve(engine, range(4))
    stats = engine.stats()
    assert stats["paged_attention"] == "gather"
    assert stats["counters"]["ssm_live_steps"] == 0
    assert stats["counters"]["live_slots"] - live_before >= 4 * 5
    assert stats["counters"]["mqa_rows_read"] > 0
    assert stats["slot_state"]["bytes"] == 4 * 3 * (4 * 128 * 4 + 3 * 128 * 4)
    assert stats["kv"]["used_blocks"] == 0
    for i, tokens in enumerate(together):
        assert len(tokens) == 6
        d = R.served_token_deficits(weights, C, _tokens(
            LENGTHS[i], seed=20 + i), tokens)
        assert d.max() < RTOL * 3, (LENGTHS[i], d)
        assert _serve(engine, [i]) == [tokens]


@pytest.mark.parametrize("what", ["prefix_cache", "export_prefix",
                                  "prefill_only", "preempt", "speculation"])
def test_engine_refuses_by_name_what_would_lose_state(model, what):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    _, mc, _, params = model
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix"):
            _engine(mc, params, prefix_cache=True)
        return
    if what == "speculation":
        with pytest.raises(ValueError, match="no speculative verify"):
            LLMEngine(params, mc, EngineConfig(
                num_slots=2, max_seq_len=64, prefill_buckets=(16,),
                kv_block_size=BS, prefix_cache=False),
                draft_params=params, draft_config=mc)
        return
    engine = _engine(mc, params)
    with pytest.raises((ValueError, NotImplementedError),
                       match="state by slot"):
        if what == "export_prefix":
            engine.export_prefix(_tokens(8), max_blocks=1)
        elif what == "prefill_only":
            engine.submit(Request(prompt=_tokens(8), max_tokens=1,
                                  prefill_only=True))
        else:
            engine.preempt(0)


# ------------------------------------------------ the kernels, interpreted

def test_step_kernel_over_26_layers_leaves_a_dead_slot_untouched(
        monkeypatch):
    """`ssm_step_live` (interpreted) on every layer of a 26-layer stack,
    one after another through the aliased buffer as the tick's loops
    run it: the live slots' rows are `ssm_step`'s in every layer, a
    dead slot's stand TO THE BIT in all 26, its output is zeros."""
    from ray_tpu.ops import attention, kda
    from ray_tpu.ops import selective_scan as S

    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    L, B, N, R = 26, 5, 4, 8
    k = jax.random.split(jax.random.key(1), 6)
    H = jax.random.normal(k[0], (L, B, N, R, 128))
    z = dict(delta=0.1 * jax.nn.softplus(jax.random.normal(k[1], (B, R, 128))),
             x=jax.random.normal(k[2], (B, R, 128)),
             b=jax.random.normal(k[3], (B, N)),
             c=jax.random.normal(k[4], (B, N)),
             a=-jnp.exp(jax.random.normal(k[5], (N, R, 128))))
    live = jnp.array([1, 0, 1, 1, 0], bool)
    assert S.engages(H)

    @jax.jit
    def every_layer(H):
        plan = kda.live_plan(live, B)

        def layer(j, carry):
            H, ys = carry
            y, H = S.ssm_step_live(H, j, **z, plan=plan)
            return H, ys.at[j].set(y)

        return jax.lax.fori_loop(0, L, layer,
                                 (H, jnp.zeros((L, B, R, 128))))

    H1, ys = every_layer(H)
    m = np.asarray(live)
    for j in (0, 7, 25):
        want_y, want_h = S.ssm_step(H[j], **z)
        assert np.abs(np.asarray(ys[j])[m] - np.asarray(want_y)[m]).max() \
            < 1e-5
        assert np.abs(np.asarray(H1[j])[m] - np.asarray(want_h)[m]).max() \
            < 1e-5
    assert np.array_equal(np.asarray(H1)[:, ~m], np.asarray(H)[:, ~m])
    assert not np.asarray(ys)[:, ~m].any()
    assert np.abs(np.asarray(H1)[:, m] - np.asarray(H)[:, m]).max() > 0.1


@pytest.mark.parametrize("bs", [16, 64])
def test_paged_attention_at_one_kv_head_and_20_query_heads(bs, monkeypatch):
    """`ops.paged_attention.paged_attention` (interpreted) over pools
    `[2, NB, bs, 128]`, one K/V head that 20 query heads read, at 16
    rows a block and at the cell's 64: the whole-row form (no groups to
    walk, no mask, no zero lane), against dense causal attention over
    each live sequence's own rows; a dead sequence reads zeros."""
    from ray_tpu.ops import attention
    from ray_tpu.ops import paged_attention as pa

    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    B, H, D, nb = 3, 20, 128, 6
    NB = B * nb + 2
    k = jax.random.split(jax.random.key(bs), 3)
    pools = [jax.random.normal(kk, (2, NB, bs, D), jnp.bfloat16)
             for kk in k[:2]]
    assert pa.engages(pools[0]) and not pa.walks_groups(1, H, 1)
    q = jax.random.normal(k[2], (B, 1, H, D), jnp.bfloat16)
    tables = jnp.asarray(np.random.RandomState(0).permutation(NB)[
        :B * nb].reshape(B, nb).astype(np.int32))
    qpos = jnp.array([nb * bs - 1, 5, 2 * bs + 3], jnp.int32)
    active = jnp.array([True, False, True])
    plan = pa.plan(tables, qpos, active, bs)
    f32 = lambda a: np.asarray(a.astype(jnp.float32), np.float64)
    for layer in (0, 1):
        out = pa.paged_attention(q, *pools, layer, plan)
        assert out.shape == (B, 1, H, D) and not f32(out[1]).any()
        for b in (0, 2):
            n = int(qpos[b]) + 1
            rows = [f32(p[layer][tables[b]].reshape(nb * bs, D)[:n])
                    for p in pools]
            s = f32(q[b, 0]) @ rows[0].T / np.sqrt(D)
            p = np.exp(s - s.max(-1, keepdims=True))
            want = (p / p.sum(-1, keepdims=True)) @ rows[1]
            # bf16 output: 2^-8 of its size
            assert np.abs(f32(out[b, 0]) - want).max() \
                < 1e-2 * np.abs(want).max()


def test_decode_step_agrees_on_both_paths(monkeypatch):
    """A decode step at sizes where `engages` answers yes (heads of 128,
    blocks of 64 rows, bf16 pools, `d_inner` 1024 in whole tiles) with
    the interpreter forced goes through `ops.paged_attention`'s kernel
    and the scan step's, in all three loops of Mamba layers, and lands
    on the gather path's logits and states."""
    from ray_tpu.models import jamba as M
    from ray_tpu.ops import attention

    mc = M.JambaConfig.tiny(dim=512, n_heads=4, head_dim=128, hidden_dim=64,
                            vocab_size=256, dt_rank=32, max_seq_len=256)
    assert (mc.d_inner, mc.n_ssm_layers, mc.n_attn_layers) == (1024, 4, 2)
    params = _drawn(M.init_params(mc, jax.random.key(3), std=0.05))
    B, bs = 3, 64
    pools = M.init_paged_pool(mc, 14, bs)
    pools = jax.tree.map(lambda p: jax.random.normal(
        jax.random.key(p.shape[1]), p.shape, p.dtype), pools)
    state = jax.tree.map(lambda s: 0.1 * jax.random.normal(
        jax.random.key(7), s.shape).astype(s.dtype),
        M.init_slot_state(mc, B))
    tables = jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4) + 1
    tok = jnp.array([5, 6, 7], jnp.int32)
    pos = jnp.array([141, 3, 250], jnp.int32)
    active = jnp.array([True, False, True])
    out = {}
    for force in (False, True):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert M._paged_attention(pools) == ("kernel" if force else "gather")
        # a partial is a function of its own: traced under this path
        logits, kv, counts, st = jax.jit(
            functools.partial(M.decode_step_paged),
            static_argnames=("config",))(
                params, pools, tables, tok, pos, mc, active, state)
        assert int(counts["ssm_live_steps"]) == (2 * 4 if force else 0)
        assert int(counts["mqa_rows_read"]) == 2 * (142 + 251)
        assert np.array_equal(np.asarray(st["h"][:, 1]),
                              np.asarray(state["h"][:, 1]))
        out[force] = (np.asarray(logits)[[0, 2]], np.asarray(st["h"]))
    scale = np.abs(out[False][0]).max()
    assert scale > 0.3
    assert np.abs(out[True][0] - out[False][0]).max() < 2e-2 * scale
    assert np.abs(out[True][1] - out[False][1]).max() < 1e-2
