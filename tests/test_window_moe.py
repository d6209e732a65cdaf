"""Sliding-window and full attention layers over two kinds of paged pool
(a table by position for the full layers, a ring for the window
layers), a gated attention output, QK-norm, rotary on the window layers
only, four norms, a scaled embedding and routed experts beside a shared
one (`models/window_moe.py`, `ops/paged_attention.py`'s window form,
`serve/llm/engine.py`, `serve/llm/kv_cache.py::WindowRing`), against the
plain float32 reference of `benchmarks/reference/window_moe_decoder.py`
on seeded random weights at a tiny size.  Logits are compared, never
sampled tokens (but for the engine tests, which judge served tokens by
their reference logits, as the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit) on logits, float32
  against float32 on the CPU: the program and the reference differ in
  the ORDER of float32 sums (sorted expert groups against blocks of
  experts, grouped against repeated KV heads, an online softmax a block
  of keys at a time against one softmax over a masked row); that reads
  1e-6 relative.  Every mutilated program (the window ignored, rotary on
  the full layer) and every mutilated reference (no gate, a norm short,
  the embedding unscaled, no shared expert) is 100 times the tolerance
  away and more; int8-rounded matrices twice and more.
* The weights are drawn at 0.1, not the 0.02 of the published widths:
  at hidden 64 a 0.02 draw leaves q . k so small that every softmax is
  flat and a window changes nothing that a tolerance could see.
* The paged kernel's window form against the masked gather: 2 ulp of a
  bf16 output of size 1 (2 ** -6), as `tests/test_paged_attention.py`
  argues.
* The engine tests serve greedy tokens in float32; each served token's
  reference logit lies within 1e-4 relative of the reference maximum.
"""

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
# held: published layers 1-5 = a dense window layer, then window, FULL,
# window, window; a window of 8 keys
C = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
         head_dim=16, intermediate_size=128, moe_intermediate_size=32,
         num_experts=8, num_experts_per_tok=2, num_shared_experts=1,
         num_dense_layers=1, num_hidden_layers=5, first_layer=1,
         layer_types=["sliding_attention", "sliding_attention",
                      "sliding_attention", "full_attention"] * 2,
         sliding_window=8, rms_norm_eps=1e-5, rope_theta=10000,
         rope_scaling=None, route_norm=True, route_scale=2.826,
         score_func="sigmoid", n_group=1, topk_group=1, mup_enabled=True,
         tie_word_embeddings=False, vocab_size=512, router_bias_scale=0.02,
         initializer_range=0.1)
BS = 4            # rows a block
BUCKET = 16       # the largest prefill bucket
RING = (8 + BUCKET) // BS   # blocks: the window before a chunk + the chunk
N_TOK = 70        # longer than ring + window (24 + 8 rows)


# The same layers at shapes where an insert's attention goes through the
# kernel (`ops.attention.prefill_engages`: heads of 128, pieces and key
# rows in whole tiles of 128): a window of 128, buckets of 128, blocks
# of 16, a ring of 256 rows
C_KERNEL = dict(C, head_dim=128, sliding_window=128)
BS_K, BUCKET_K = 16, 128
RING_K = (128 + BUCKET_K) // BS_K


def _build(c, dtype="float32", max_seq_len=96, **overrides):
    from families import window_moe_decoder as F
    from reference import window_moe_decoder as R

    mc = F.model_config(c, max_seq_len=max_seq_len, compute_dtype=dtype,
                        param_dtype=dtype, prefill_key_block=8, **overrides)
    weights = R.init_weights(c, 11, getattr(jnp, dtype))
    # norms that are not all ones, so that a missing one shows
    rng = np.random.RandomState(5)
    weights["layers"] = [
        {k: (v * jnp.asarray(rng.uniform(0.5, 1.5, v.shape), v.dtype)
             if k.endswith("norm") else v) for k, v in w.items()}
        for w in weights["layers"]]
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


@functools.cache
def _jitted(name):
    """A program function of `models/window_moe.py` under `jax.jit`, its
    configuration static: one compile a shape for the whole module where
    op-by-op dispatch compiled every primitive of every layer."""
    from ray_tpu.models import window_moe

    return jax.jit(getattr(window_moe, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C, without=()):
    # whole blocks of the reference's 256 queries, past one block
    pad = 16 if len(toks) <= 256 else 256
    Tp = -(-len(toks) // pad) * pad
    return np.asarray(R.logits_for_positions(
        weights, c, toks, start, n, pad_to=Tp, without=without))


def _off(got, want):
    """The largest deviation, relative to the largest reference logit."""
    scale = np.abs(want).max()
    assert scale > 0.3
    return np.abs(np.asarray(got) - want).max() / scale


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert (mc.n_window_layers, mc.n_full_layers, mc.n_moe_layers,
            mc.full_layers, mc.window) == (4, 1, 4, (2,), 8)
    assert [mc.kind(i) for i in range(5)] == [
        "window", "window", "full", "window", "window"]
    toks = _tokens(48)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 48)) < RTOL


# ------------- (c) the mutilated programs fail the same comparison

@pytest.mark.parametrize("what", ["window_ignored", "rotary_on_full"])
def test_a_mutilated_program_fails(model, what, monkeypatch):
    import dataclasses

    from ray_tpu.models import window_moe as M

    R, mc, weights, params = model
    toks = _tokens(48)
    if what == "window_ignored":
        mc = dataclasses.replace(mc, window=10 ** 6)
    else:
        monkeypatch.setattr(M, "ROTARY_KINDS", ("window", "full"))
    # jitted anew: the mutilation is there as it traces
    got = jax.jit(lambda p, t: M.forward(p, t, mc))(
        params, jnp.asarray(toks)[None])[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 48)) \
        > 100 * RTOL


# ------------- (d) each piece is in the program: without it, far off

@pytest.mark.parametrize("piece", ["gate", "post_attn_norm",
                                   "post_ffn_norm", "qk_norm",
                                   "embed_scale", "shared", "window",
                                   "rope"])
def test_the_program_has_each_piece(model, piece):
    """The program equals the whole reference (above); a reference
    WITHOUT the piece is far from it."""
    R, mc, weights, params = model
    toks = _tokens(48)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    short = _reference_logits(R, weights, toks, 0, 48, without=(piece,))
    assert _off(got, short) > 100 * RTOL


def test_lower_precision_is_caught(model):
    """The tolerance is tight enough: matrices rounded to int8 (the
    cell's control) fail it by a factor of two at least."""
    from families import window_moe_decoder as F

    R, mc, weights, _ = model
    toks = _tokens(48)
    want = _reference_logits(R, weights, toks, 0, 48)
    # the control deletes the bank of experts `program_params` made
    # last: make that one this test's own, not the fixture's
    _, _, mine, _ = _build(C)
    params = jax.jit(F.lower_precision_params)(mine)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    assert _off(got, want) > 2 * RTOL


# --------- (b) prefill in chunks, then decode, through both kinds of pool

def _prefill(mc, params, pools, table, ring, toks, start, bucket=BUCKET):
    """One bucket-padded chunk of `toks` at `start`, as the engine's
    insert program does it: the full kind's history by position, the
    window kind's as its ring; rows scattered through each table."""
    from ray_tpu.models.window_moe import WINDOW_LEAVES

    BS = pools["k"].shape[2]

    def row(name):
        return ring if name in WINDOW_LEAVES else table

    hist = {k: v[:, row(k)].reshape(
        (v.shape[0], len(row(k)) * BS) + v.shape[3:])
        for k, v in pools.items()}
    padded = np.zeros((bucket,), np.int32)
    padded[:len(toks)] = toks
    x, rows = _jitted("prefill_paged")(
        params, jnp.asarray(padded)[None], jnp.int32(start), hist, mc,
        jnp.int32(len(toks)))
    at = start // BS + np.arange(bucket // BS)
    ids = {k: (ring[at % len(ring)] if k in WINDOW_LEAVES else table[at])
           for k in pools}
    pools = {k: v.at[:, ids[k]].set(rows[k].reshape(
        (v.shape[0], bucket // BS, BS) + v.shape[3:]))
        for k, v in pools.items()}
    return x[0, :len(toks)], pools


@functools.cache
def _kernel_model():
    return _build(C_KERNEL, max_seq_len=640)


@pytest.mark.parametrize("path, n_prompt", [
    ("loop", 13), ("loop", 27), ("loop", 55),
    ("kernel", 100), ("kernel", 250), ("kernel", 500)])
def test_paged_prefill_and_decode_match_reference(model, path, n_prompt,
                                                  monkeypatch):
    """Prefill (one bucket; two chunks; four, the ring wrapping inside
    the prefill) and then decode to 70 tokens, past ring + window rows so
    that the ring wraps in decode too: logits at every position against
    the reference's full forward; the blocks no table names stand as
    they were.  `kernel`: the same at heads of 128 and pieces of 128
    rows with the interpreter forced, so that every piece attends
    through `ops.attention.flash_prefill` (the pools are float32: the
    tick keeps its gathers), then 20 decode steps."""
    from ray_tpu.models.window_moe import (
        _head, init_paged_pool, insert_attention,
    )
    from ray_tpu.ops import attention

    if path == "kernel":
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
        # from 128 queries and keys on, not the chip's 1024 and 2048
        monkeypatch.setattr(attention, "PREFILL_MIN_Q", 128)
        monkeypatch.setattr(attention, "PREFILL_MIN_K", 128)
        R, mc, weights, params = _kernel_model()
        c, bs, bucket, n_ring, n_tok = (C_KERNEL, BS_K, BUCKET_K, RING_K,
                                        n_prompt + 20)
        ring = np.asarray([17, 3, 11, 8, 14, 2, 0, 19, 5, 9, 1, 16, 7, 12,
                           4, 18], np.int32)
    else:
        R, mc, weights, params = model
        c, bs, bucket, n_ring, n_tok = C, BS, BUCKET, RING, N_TOK
        ring = np.asarray([17, 3, 11, 8, 14, 2], np.int32)
    n_table = mc.max_seq_len // bs
    assert insert_attention(mc, 0, bucket, mc.max_seq_len)[0] == path
    toks = _tokens(n_tok, seed=3)
    pools = init_paged_pool(mc, n_table + 16, bs, window_blocks=20)
    row = 2 * mc.head_dim
    assert pools["k"].shape == (1, n_table + 16, bs, row)   # the full layer
    assert pools["v_w"].shape == (4, 20, bs, row)       # the window layers
    pools = jax.tree.map(lambda x: x + 7.0, pools)
    table = np.arange(n_table, dtype=np.int32) + 5
    assert len(ring) == n_ring
    # the longest prompt of each path wraps its ring
    assert max(n_tok, {"loop": N_TOK, "kernel": 500}[path]) \
        > n_ring * bs + 8
    hidden = []
    for start in range(0, n_prompt, bucket):
        x, pools = _prefill(mc, params, pools, table, ring,
                            toks[start:min(start + bucket, n_prompt)], start,
                            bucket)
        hidden.append(x)
    got = [np.asarray(_head(mc, params, jnp.concatenate(hidden)))]
    tables = {"full": np.zeros((3, n_table), np.int32),
              "window": np.zeros((3, n_ring), np.int32)}
    tables["full"][2], tables["window"][2] = table, ring
    tables = jax.tree.map(jnp.asarray, tables)
    active = jnp.asarray([False, False, True])
    for t in range(n_prompt, n_tok):
        logits, pools, counts = _jitted("decode_step_paged")(
            params, pools, tables, jnp.asarray([0, 0, toks[t]]),
            jnp.asarray([0, 0, t]), mc, active)
        got.append(np.asarray(logits[2:3]))
    want = _reference_logits(R, weights, toks, 0, n_tok, c=c)
    assert _off(np.concatenate(got), want) < RTOL
    assert np.all(np.asarray(pools["k"][:, np.setdiff1d(
        np.arange(n_table + 16), table)]) == 7.0)
    assert np.all(np.asarray(pools["k_w"][:, np.setdiff1d(
        np.arange(20), ring)]) == 7.0)
    assert int(counts["ticks"]) == 1
    assert counts["expert_tokens"].shape == (4, 8)
    assert int(counts["expert_tokens"].sum()) == mc.top_k * mc.n_moe_layers


def test_decode_step_agrees_on_both_paths(monkeypatch):
    """Heads of 128 at 4 KV heads, bf16, blocks of 16: the tick through
    the kernel's two forms (interpreter) against the tick through the
    masked gathers, sequences under and over the window and past the
    ring."""
    from ray_tpu.models import window_moe as M
    from ray_tpu.ops import attention

    mc = M.WindowMoEConfig.tiny(dim=128, n_heads=8, n_kv_heads=4,
                                head_dim=128, window=32, max_seq_len=256)
    params = M.init_params(mc, jax.random.key(1))
    rng = np.random.default_rng(0)
    pools = {k: jnp.asarray(rng.standard_normal(v.shape), jnp.bfloat16)
             for k, v in M.init_paged_pool(mc, 40, 16,
                                           window_blocks=20).items()}
    ring = 4
    tables = {"full": jnp.asarray(rng.permutation(40)[:32].reshape(2, 16),
                                  jnp.int32),
              "window": jnp.asarray(rng.permutation(20)[:2 * ring].reshape(
                  2, ring), jnp.int32)}
    tok = jnp.asarray([5, 9], jnp.int32)
    pos = jnp.asarray([20, 201], jnp.int32)
    # jitted anew: the path is chosen as it traces
    step = lambda: jax.jit(lambda: M.decode_step_paged(       # noqa: E731
        params, pools, tables, tok, pos, mc))()
    assert M._paged_attention(pools) == "gather"
    want, pools_g, _ = step()
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert M._paged_attention(pools) == "kernel"
    got, pools_k, _ = step()
    for name in M.WINDOW_LEAVES:    # the first layer's rows: the same
        assert jnp.array_equal(pools_g[name][0], pools_k[name][0]), name
    scale = float(jnp.abs(want).max())
    assert float(jnp.abs(got - want).max()) < 0.05 * scale


# --------------- (e) the shares of an expert layer add up to the layer

def test_eight_shares_and_the_shared_expert_once_make_the_layer(model):
    from ray_tpu.models.window_moe import routed_experts, shared_expert

    _, mc, _, params = model
    p = params["layers"][2]
    h = jnp.asarray(np.random.RandomState(2).randn(24, 64), jnp.float32)
    whole, sizes = routed_experts(mc, p, h)
    parts, counts = [], []
    for r in range(8):
        held = dict(p, **{k: p[k][r:r + 1]
                          for k in ("w_gate", "w_up", "w_down")})
        y, n = routed_experts(mc, held, h, share=(r, 8))
        parts.append(y)
        counts.append(int(n[0]))
    assert counts == [int(x) for x in sizes]
    assert sum(counts) == 24 * mc.top_k
    shared = shared_expert(mc, p, h)
    assert float(jnp.abs(shared).max()) > 1e-3
    np.testing.assert_allclose(
        np.asarray(sum(parts) + shared), np.asarray(whole + shared),
        atol=1e-5 * float(jnp.abs(whole).max()))


@pytest.mark.parametrize("what", ["rope_scaling", "n_group", "route_norm",
                                  "mup_enabled", "tied_head", "layer_kind"])
def test_family_refuses_by_name_what_the_program_has_not(what):
    from families import window_moe_decoder as F

    c = dict(C)
    c.update({"rope_scaling": {"rope_scaling": {"type": "yarn"}},
              "n_group": {"n_group": 4}, "route_norm": {"route_norm": False},
              "mup_enabled": {"mup_enabled": False},
              "tied_head": {"tie_word_embeddings": True},
              "layer_kind": {"layer_types": ["conv"] * 8}}[what])
    with pytest.raises(ValueError, match="has no"):
        F.model_config(c, max_seq_len=64, compute_dtype="float32",
                       param_dtype="float32")


# ------------------------------------------------------- (f) the engine

def _engine(mc, params, **over):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    cfg = dict(num_slots=3, max_seq_len=96, prefill_buckets=(8, 16),
               kv_block_size=BS, num_kv_blocks=60, num_window_blocks=14,
               prefix_cache=False)
    cfg.update(over)
    return LLMEngine(params, mc, EngineConfig(**cfg), rng_seed=3)


@pytest.fixture
def engine(model, shared_engine):
    """The module's one engine at `_engine`'s own configuration: drained
    when a case takes it and when it leaves it."""
    _, mc, _, params = model
    return shared_engine("three slots", lambda: _engine(mc, params))


def test_engine_serves_through_both_kinds_past_a_ring_wrap(model, engine):
    """Six requests through three slots and a window pool that holds
    two rings and a little: prompts from three tokens to four chunks,
    answers that take the longer streams past ring + window rows.  Every
    served token is the reference's choice given the served prefix; a
    stream never holds more than a ring of window blocks; every block
    of both kinds is given back.  `stats()` says which form the inserts'
    attention compiled to and sums, over the admitted pieces, the
    (query, key) tiles the kernel's bounds would let through beside
    those of the rectangles the loop multiplies."""
    from ray_tpu.models.window_moe import insert_attention
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    before = engine.stats()
    assert before["kv"]["window"]["ring_blocks"] == RING
    assert before["insert_attention"] == "loop"         # heads of 16
    prompts = [_tokens(n, seed=n) for n in (3, 40, 16, 55, 9, 30)]
    handles = [engine.submit(Request(prompt=p, max_tokens=30,
                                     chunked_prefill=len(p) > BUCKET))
               for p in prompts]
    most = 0
    while engine.has_work():
        engine.step()
        most = max([most] + [len(b) for b in engine._ring.slot_blocks])
        assert engine._ring.allocator.used_blocks == sum(
            len(b) for b in engine._ring.slot_blocks)
    assert most == RING
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length" and len(h.tokens) == 30
        lg = _reference_logits(R, weights, p + h.tokens[:-1], len(p) - 1, 30)
        chosen = lg[np.arange(30), h.tokens]
        assert np.all(lg.max(-1) - chosen <= RTOL * np.abs(lg).max())
    stats = engine.stats()
    kv = stats["kv"]
    assert kv["used_blocks"] == 0 and kv["window"]["used_blocks"] == 0
    # a piece a bucket of 16, the last in the bucket that holds it
    pieces = [(start, 8 if min(n - start, BUCKET) <= 8 else BUCKET)
              for n in map(len, prompts) for start in range(0, n, BUCKET)]
    tiles = [insert_attention(mc, start, bucket, 96)[1:]
             for start, bucket in pieces]
    run, dense = (stats[k] - before[k] for k in (
        "insert_attn_tiles_run", "insert_attn_tiles_dense"))
    assert (run, dense) == tuple(map(sum, zip(*tiles)))
    assert 0 < run <= dense     # tiles of 16 x 32: nothing to skip here


class _RecordingTick:
    """Stands in for the engine's `Programs._jit_tick`: lays each
    dispatch's live slots and window table aside, then calls through."""

    def __init__(self, engine):
        self.engine, self.seen = engine, []
        self.tick = engine._programs._jit_tick

    def __call__(self, params, pools, tables, tok, pos, active, *rest):
        e = self.engine
        self.seen.append((
            [(s, e._slots[s].handle) for s in np.nonzero(active)[0]],
            tables["window"].copy(), [list(b) for b in e._ring.slot_blocks],
            len(e._flying)))
        return self.tick(params, pools, tables, tok, pos, active, *rest)

    def __getattr__(self, name):        # `take_sample`, `record_wall`
        return getattr(self.tick, name)


def test_ring_covers_the_row_a_tick_behind_one_in_flight_writes(
        engine, monkeypatch):
    """One tick in flight (PR 41): when tick k goes out the handles are
    one token short of the rows dispatched, and the ring still has to
    hold, owned and distinct, the block of the row tick k WRITES (a
    handle's j-th tick writes position prompt + j - 1, counted here by
    the dispatches themselves) and of the window before it."""
    from ray_tpu.serve.llm.engine import Request

    rec = _RecordingTick(engine)
    monkeypatch.setattr(engine._programs, "_jit_tick", rec)
    ring, W = engine._ring, C["sliding_window"]
    prompts = [_tokens(n, seed=n) for n in (5, 40, 21)]
    handles = [engine.submit(Request(prompt=p, max_tokens=40,
                                     chunked_prefill=len(p) > BUCKET))
               for p in prompts]
    engine.drain()
    assert all(len(h.tokens) == 40 for h in handles)
    ticks_of = {}
    overlapped = 0
    for live, table, owned, in_flight in rec.seen:
        overlapped += in_flight
        for slot, h in live:
            j = ticks_of[h] = ticks_of.get(h, 0) + 1
            row = len(h.request.prompt) + j - 1
            blocks = [int(table[slot, b % ring.ring])
                      for b in range(max(row - W + 1, 0) // BS, row // BS + 1)]
            assert len(set(blocks)) == len(blocks), (row, blocks)
            assert set(blocks) <= set(owned[slot]), (row, blocks)
    assert set(ticks_of.values()) == {39}       # the insert gave the first
    assert overlapped > len(rec.seen) * 0.8
