"""Gated delta-rule layers with their state by slot beside full
multi-head attention over two paged pools, in post-norm blocks
(`models/gdn_hybrid.py`, `ops/kda.py` with one decay a head and keys of
another size than values, `ops/short_conv.py`, `serve/llm/engine.py`),
against the plain float32 reference of
`benchmarks/reference/gdn_hybrid_decoder.py` on seeded random weights at
a tiny size.  Logits are compared, never sampled tokens (but for the
engine test, which judges served tokens by their reference logits, as
the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit, about 1.5 here) on
  logits, float32 against float32 on the CPU: the program's chunkwise
  form and one-token step against the reference's token-by-token scan
  differ in the ORDER of float32 sums (a chunk's solve, grouped against
  repeated K/V heads); that reads 2e-6 relative.  Every mutilated
  program reads 100 x the tolerance and more, but the state kept in
  bf16, which over 300 tokens reads 20 x.
* The weights are drawn at 0.1, not the 0.02 of the published widths:
  at hidden 64 a 0.02 draw leaves every sub-layer's output near zero
  BEFORE its post-norm, and the norm then amplifies rounding.
"""

import dataclasses
import functools
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
_KINDS = ["linear_attention"] * 3 + ["full_attention"]
# a period and a half: delta delta delta attention delta delta; keys of
# 32 against values of 64, two heads a row of the state's stack
C = dict(model_type="olmo_hybrid", hidden_size=64, num_attention_heads=4,
         num_key_value_heads=4, head_dim=None, intermediate_size=128,
         hidden_act="silu", num_hidden_layers=6, layer_types=_KINDS * 2,
         linear_num_key_heads=2, linear_num_value_heads=2,
         linear_key_head_dim=32, linear_value_head_dim=64,
         linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
         attention_bias=False, tie_word_embeddings=False,
         rms_norm_eps=1e-6, vocab_size=512,
         rope_parameters={"rope_theta": None}, initializer_range=0.1,
         precision=dict(recurrent_state="float32"))
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


def _build(c, **overrides):
    from families import gdn_hybrid_decoder as F
    from reference import gdn_hybrid_decoder as R

    mc = F.model_config(c, max_seq_len=64, compute_dtype="float32",
                        param_dtype="float32", **overrides)
    weights = R.init_weights(c, 11, jnp.float32)
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


@functools.cache
def _jitted(name):
    """A program function of `models/gdn_hybrid.py` under `jax.jit`, its
    configuration static: one compile a shape for the whole module where
    op-by-op dispatch compiled every primitive of every layer."""
    from ray_tpu.models import gdn_hybrid as M

    return jax.jit(getattr(M, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C, **kw):
    return np.asarray(R.logits_for_positions(weights, c, toks, start, n,
                                             pad_to=64, **kw))


def _off(got, want):
    scale = np.abs(want).max()
    assert scale > 0.3
    return np.abs(np.asarray(got) - want).max() / scale


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert (mc.n_gdn_layers, mc.n_attn_layers, mc.attn_layers,
            mc.heads_a_row, mc.rope_theta) == (5, 1, (3,), 2, None)
    toks = _tokens(50)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 50)) < RTOL


def test_a_rope_theta_in_the_file_rotates_half(model):
    """A number under `rope_parameters.rope_theta` is a data change: the
    program and the reference both rotate, and differ from no rotation."""
    c = dict(C, rope_parameters={"rope_theta": 10000.0})
    R, mc, weights, params = _build(c)
    assert mc.rope_theta == 10000.0
    toks = _tokens(50)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 50, c=c)) < RTOL
    assert _off(got, _reference_logits(R, weights, toks, 0, 50)) > 100 * RTOL


# ------------- (c) the mutilated programs fail the same comparison

def _pre_norm(x, sub_layer, w, eps):
    from ray_tpu.models.llama import rms_norm

    return x + sub_layer(rms_norm(x, w, eps))


def _norm_a_head(x, w, eps):
    from ray_tpu.models.llama import rms_norm

    heads = x.reshape(x.shape[:-1] + (4, 16))
    return (rms_norm(heads, jnp.ones((16,), x.dtype), eps)
            * w.reshape(4, 16)).reshape(x.shape)


MUTILATIONS = {
    # what is patched in the program, the piece the reference leaves
    # out to match it
    "beta_without_its_factor_2": (
        "_write_strength", jax.nn.sigmoid, "beta_x2"),
    "a_pre_norm_block": ("_block_half", _pre_norm, "post_norm"),
    "the_output_norms_gate_dropped": (
        "_gated_norm", lambda o, w, gate, eps: __import__(
            "ray_tpu.models.llama", fromlist=["rms_norm"]).rms_norm(
                o, w, eps), "gate"),
    "qk_norm_a_head": ("_qk_norm", _norm_a_head, "qk_norm_over_the_width"),
}


@pytest.mark.parametrize("what", sorted(MUTILATIONS))
def test_a_mutilated_program_fails(model, what, monkeypatch):
    """Each mutilated program is far from the reference, and is exactly
    the reference mutilated the same way: the patch did what its name
    says and nothing else."""
    from ray_tpu.models import gdn_hybrid as M

    R, mc, weights, params = model
    name, fn, piece = MUTILATIONS[what]
    monkeypatch.setattr(M, name, fn)
    toks = _tokens(48)
    # jitted anew: the mutilation is there as it traces
    got = jax.jit(lambda p, t: M.forward(p, t, mc))(
        params, jnp.asarray(toks)[None])[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 48)) \
        > 100 * RTOL
    assert _off(got, _reference_logits(R, weights, toks, 0, 48,
                                       without=(piece,))) < RTOL


def test_a_bf16_state_fails_over_a_few_hundred_tokens():
    """The state rounded to bf16 between tokens: the family refuses the
    file that asks for it, and the program made to keep one (the config
    field, past the family) fails the comparison through the serving
    path, where the state is rounded between chunks and every token."""
    from families import gdn_hybrid_decoder as F

    with pytest.raises(ValueError, match="recurrent state kept in bfloat16"):
        F.model_config(dict(C, precision=dict(recurrent_state="bfloat16")),
                       max_seq_len=64, compute_dtype="float32",
                       param_dtype="float32")
    R, mc, weights, params = _build(C)
    toks = _tokens(300, seed=2)
    want = _reference_logits(R, weights, toks, 0, 300)
    sound = _served_logits(mc, params, toks)
    assert _off(sound, want) < RTOL
    bf16 = _served_logits(dataclasses.replace(mc, state_dtype=jnp.bfloat16),
                          params, toks)
    assert _off(bf16, want) > 10 * RTOL
    # and it is the state: the reference rounding its own the same way
    # lands beside it
    rounded = _reference_logits(R, weights, toks, 0, 300,
                                state_dtype=jnp.bfloat16)
    assert _off(rounded, want) > 10 * RTOL


@pytest.mark.parametrize("refused, change", [
    ("neither linear_attention nor full_attention",
     {"layer_types": ["sliding_attention"] * 8}),
    ("linear_num_key_heads != linear_num_value_heads",
     {"linear_num_key_heads": 1}),
    ("attention_bias", {"attention_bias": True}),
    ("a tied head", {"tie_word_embeddings": True}),
    ("without its factor 2", {"linear_allow_neg_eigval": False}),
])
def test_the_family_refuses_what_the_program_does_not_compute(refused,
                                                              change):
    from families import gdn_hybrid_decoder as F

    with pytest.raises(ValueError, match=refused):
        F.model_config(dict(C, **change), max_seq_len=64,
                       compute_dtype="float32", param_dtype="float32")


# ---------------------- (b) prefill + decode: paged rows and slot state

def _prefill(mc, params, pools, state, slot, table, toks, start,
             bucket=BUCKET):
    """One bucket-padded chunk of `toks` at `start` into the blocks of
    `table` and the state row of `slot`, as the engine's insert program
    does it."""
    S_pad = table.shape[0] * BS
    hist = {k: v[:, table].reshape((v.shape[0], S_pad) + v.shape[3:])
            for k, v in pools.items()}
    padded = np.zeros((bucket,), np.int32)
    padded[:len(toks)] = toks
    mine = {k: jnp.where(start > 0, v[:, slot], 0) for k, v in state.items()}
    x, rows, mine = _jitted("prefill_paged")(
        params, jnp.asarray(padded)[None], jnp.int32(start), hist, mc,
        jnp.int32(len(toks)), mine)
    ids = table[start // BS: start // BS + bucket // BS]
    pools = {k: v.at[:, ids].set(rows[k].reshape(
        (v.shape[0], bucket // BS, BS) + v.shape[3:]))
        for k, v in pools.items()}
    state = {k: v.at[:, slot].set(mine[k]) for k, v in state.items()}
    return x[0, :len(toks)], pools, state


def _served_logits(mc, params, toks, n_prompt=None, slots=3, slot=2):
    """Logits at every position of `toks` through the serving path: the
    prompt in chunks of BUCKET (state and tail handed on in the slot),
    the rest a decode step a token with dead slots beside the live one.
    Returns [len(toks), V]; checks that the dead slots' state stands."""
    from ray_tpu.models.gdn_hybrid import (LM, init_paged_pool,
                                           init_slot_state)

    n_prompt = n_prompt or len(toks) - 10
    n_blocks = -(-len(toks) // BUCKET) * BUCKET // BS
    pools = init_paged_pool(mc, n_blocks + 9, BS)
    state = init_slot_state(mc, slots)
    # the slot holds another sequence's garbage: admission must clear it
    state = jax.tree.map(lambda x: x.at[:, slot].set(1.0), state)
    table = np.arange(n_blocks, dtype=np.int32) + 5
    hidden = []
    for start in range(0, n_prompt, BUCKET):
        x, pools, state = _prefill(mc, params, pools, state, slot, table,
                                   toks[start:min(start + BUCKET, n_prompt)],
                                   start)
        hidden.append(x)
    got = [np.asarray(LM._head(mc, params, jnp.concatenate(hidden)))]
    tables = np.zeros((slots, n_blocks), np.int32)
    tables[slot] = table
    active = jnp.arange(slots) == slot
    dead = np.arange(slots) != slot
    before = jax.tree.map(lambda x: np.asarray(x[:, dead]), state)
    step = _jitted("decode_step_paged")
    for t in range(n_prompt, len(toks)):
        tok = np.zeros(slots, np.int32)
        pos = np.zeros(slots, np.int32)
        tok[slot], pos[slot] = toks[t], t
        logits, pools, counts, state = step(
            params, pools, jnp.asarray(tables), jnp.asarray(tok),
            jnp.asarray(pos), mc, active, state)
        got.append(np.asarray(logits[slot:slot + 1]))
    for k, v in before.items():
        assert np.array_equal(np.asarray(state[k][:, dead]), v)
    assert int(counts["live_slots"]) == 1 and int(counts["ticks"]) == 1
    assert int(counts["gdn_rows_stepped"]) == 0       # `kda_step` ran
    return np.concatenate(got)


@pytest.mark.parametrize("case", ["one_bucket", "chunked"])
def test_paged_prefill_and_decode_match_reference(model, case):
    """Prefill (one bucket; two chunks, the second over the first's rows,
    state and tail) and then 10 decode steps through the two paged pools
    and the slot's recurrent state, dead slots beside the live one:
    logits at every position against the reference's full forward."""
    from ray_tpu.models.gdn_hybrid import init_paged_pool, init_slot_state

    R, mc, weights, params = model
    pools, state = init_paged_pool(mc, 8, BS), init_slot_state(mc, 3)
    assert pools["k"].shape == pools["v"].shape == (1, 8, BS, 4, 16)
    assert state["S"].shape == (5, 3, 1, 32, 128)   # two heads a row
    assert state["conv"].shape == (5, 3, 3 * (2 * 64 + 128))
    n_prompt = {"one_bucket": 13, "chunked": 27}[case]
    toks = _tokens(n_prompt + 10, seed=3)
    got = _served_logits(mc, params, toks, n_prompt)
    want = _reference_logits(R, weights, toks, 0, len(toks))
    assert _off(got, want) < RTOL


def test_slot_state_is_what_the_reference_carries(model):
    """After a chunked prompt the slot's `S`, unpacked, is the
    reference's state after the last REAL token."""
    from ray_tpu.models.gdn_hybrid import init_paged_pool, init_slot_state
    from ray_tpu.ops import kda

    R, mc, weights, params = model
    toks = _tokens(27, seed=6)
    pools, state = init_paged_pool(mc, 30, BS), init_slot_state(mc, 2)
    table = np.arange(16, dtype=np.int32) + 2
    for a, b in ((0, 16), (16, 27)):
        _, pools, state = _prefill(mc, params, pools, state, 1, table,
                                   toks[a:b], a)
    got = np.asarray(kda.unpack(state["S"][:, 1], mc.heads_a_row))
    want = R.states(weights, C, toks)
    assert want.shape == got.shape == (5, 2, 32, 64)
    assert np.abs(want).max() > 1e-2
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max() + 1e-7


# ----------- (d) the tail after a chunk whose real tokens end mid-bucket

def test_conv_tail_is_the_last_three_real_rows(model):
    """A chunk of 11 real tokens in a bucket of 16: the tail the slot
    keeps is rows 8, 9, 10 of the layer's pre-activation q ‖ k ‖ v, not
    the bucket's last three (padding)."""
    from ray_tpu.models.gdn_hybrid import init_paged_pool, init_slot_state

    _, mc, _, params = model
    toks = _tokens(16, seed=8)
    table = np.arange(16, dtype=np.int32) + 2
    out = {}
    for name, n in (("padded", 11), ("whole", 16)):
        pools, state = init_paged_pool(mc, 30, BS), init_slot_state(mc, 2)
        _, _, state = _prefill(mc, params, pools, state, 1, table,
                               toks[:n], 0)
        out[name] = np.asarray(state["conv"][:, 1])
    # layer 0's input is the embedding, whatever comes after: its rows
    # are x[t] @ [wq ‖ wk ‖ wv]
    p = params["layers"][0]
    x = np.asarray(params["embed"])[np.asarray(toks)]
    rows = x @ np.concatenate([np.asarray(p[k]) for k in ("wq", "wk", "wv")],
                              -1)
    assert np.abs(rows[8:11]).max() > 0.1
    # a slot's three rows lie side by side in the lanes of one
    flat = lambda r: r.reshape(-1)
    np.testing.assert_allclose(out["padded"][0], flat(rows[8:11]), atol=1e-5)
    np.testing.assert_allclose(out["whole"][0], flat(rows[13:16]), atol=1e-5)
    assert np.abs(out["padded"][0] - flat(rows[13:16])).max() > 0.1


@pytest.mark.parametrize("case", ["chunked_equals_whole",
                                  "padded_equals_unpadded"])
def test_prefill_hand_off(model, case):
    """A prompt prefilled in chunks leaves the rows, the recurrent state
    and the convolution's tail that the same prompt prefilled whole
    leaves; padding advances nothing."""
    from ray_tpu.models.gdn_hybrid import init_paged_pool, init_slot_state

    _, mc, _, params = model
    toks = _tokens(32, seed=5)
    table = np.arange(16, dtype=np.int32) + 2
    plans = {"chunked_equals_whole": ((((0, 29),), 32),
                                      (((0, 16), (16, 29)), 16)),
             "padded_equals_unpadded": ((((0, 16),), 32),
                                        (((0, 16),), 16))}[case]
    out = []
    for chunks, bucket in plans:
        pools, state = init_paged_pool(mc, 30, BS), init_slot_state(mc, 2)
        xs = []
        for a, b in chunks:
            x, pools, state = _prefill(mc, params, pools, state, 1, table,
                                       toks[a:b], a, bucket)
            xs.append(np.asarray(x))
        n = sum(len(x) for x in xs)
        rows = np.concatenate([np.asarray(pools[k][:, table]).reshape(
            1, -1, 4 * 16)[:, :n] for k in ("k", "v")], -1)
        out.append((np.concatenate(xs), rows,
                    {k: np.asarray(v[:, 1]) for k, v in state.items()}))
    (xa, ra, sa), (xb, rb, sb) = out
    assert xa.shape == xb.shape and np.abs(xa).max() > 0.5
    close = lambda a, b: np.abs(a - b).max() < 1e-5 * max(1, np.abs(a).max())
    assert close(xa, xb) and close(ra, rb)
    assert np.abs(sa["S"]).max() > 1e-3 and np.abs(sa["conv"]).max() > 1e-3
    for k in sa:
        assert close(sa[k], sb[k]), k


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


def test_engine_serves_chunked_prompts_and_recycles_slots(model, engine):
    """Five requests through three slots (a slot is reused with its
    state cleared), prompts shorter and longer than the top bucket:
    every served token's reference logit lies within the tolerance of
    the reference maximum."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    live_before = int(engine.stats()["counters"]["live_slots"])
    lengths = (5, 16, 23, 37, 9)
    handles = [engine.submit(Request(
        prompt=_tokens(n, seed=20 + i), max_tokens=6, temperature=0.0,
        chunked_prefill=n > 16)) for i, n in enumerate(lengths)]
    while engine.has_work():
        engine.step()
    stats = engine.stats()
    assert stats["paged_attention"] == "gather"
    assert stats["counters"]["gdn_rows_stepped"] == 0
    assert stats["counters"]["live_slots"] - live_before >= 5 * 5
    for i, (n, h) in enumerate(zip(lengths, handles)):
        assert h.finish_reason == "length" and len(h.tokens) == 6
        d = R.served_token_deficits(weights, C, _tokens(n, seed=20 + i),
                                    h.tokens)
        assert d.max() < RTOL * 1.5, (n, d)


@pytest.mark.parametrize("what", ["prefix_cache", "export_prefix",
                                  "prefill_only", "preempt"])
def test_engine_refuses_by_name_what_would_lose_the_state(model, what):
    from ray_tpu.serve.llm.engine import Request

    _, mc, _, params = model
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix"):
            _engine(mc, params, prefix_cache=True)
        return
    engine = _engine(mc, params)
    with pytest.raises((ValueError, NotImplementedError)):
        if what == "export_prefix":
            engine.export_prefix(_tokens(8), max_blocks=1)
        elif what == "prefill_only":
            engine.submit(Request(prompt=_tokens(8), max_tokens=1,
                                  prefill_only=True))
        else:
            engine.preempt(0)


@pytest.mark.parametrize("when", ["in_flight", "drained"])
def test_engine_stats_count_the_ticks_read_back(engine, when):
    """`stats()["counters"]` are the model's counters as the last tick
    READ BACK left them: with a tick in flight they agree with the
    tokens emitted, one tick behind the device (and the caller, which
    may be another thread, waits for no tick); after a drain they hold
    every tick."""
    from ray_tpu.serve.llm.engine import Request

    before = engine.stats()         # drained: every tick read back
    h = engine.submit(Request(prompt=_tokens(7, seed=3), max_tokens=6,
                              temperature=0.0))
    if when == "in_flight":
        while len(h.tokens) < 3:
            engine.step()
        assert len(engine._flying) == 1
    else:
        engine.drain()
    loop, counters = engine.stats()["loop"], engine.stats()["counters"]
    # a token at the insert, then one a tick read back
    assert int(counters["ticks"]) - int(before["counters"]["ticks"]) \
        == len(h.tokens) - 1 \
        == loop["ticks"] - before["loop"]["ticks"] - len(engine._flying)
    engine.drain()


def test_engine_steps_live_states_through_the_kernel(model, engine,
                                                     monkeypatch):
    """With the interpreter forced, at heads that tile (keys of 8
    sublanes' worth, two heads of 64 a 128-lane row), the tick steps the
    packed stack through `kda_step_live` and counts it; the tokens are
    the plain path's."""
    from ray_tpu.ops import attention
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    served = {}
    for force in (False, True):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        # the plain path's is the module's engine; the kernel's is built
        # and traced under the forced interpreter
        eng = _engine(mc, params) if force else engine
        hs = [eng.submit(Request(prompt=_tokens(n, seed=40 + n),
                                 max_tokens=5, temperature=0.0,
                                 chunked_prefill=n > 16))
              for n in (7, 21)]
        eng.drain()
        counters = eng.stats()["counters"]
        assert counters["gdn_rows_stepped"] == (
            counters["live_slots"] * mc.n_gdn_layers if force else 0)
        served[force] = [list(h.tokens) for h in hs]
        for n, h in zip((7, 21), hs):
            d = R.served_token_deficits(weights, C, _tokens(n, seed=40 + n),
                                        h.tokens)
            assert d.max() < RTOL * 1.5
    assert served[True] == served[False]
