"""Mamba layers with their state by slot beside differential attention in
a window's ring, one full layer's K and V read by every cross layer, and
gated memory units (`models/sambay.py`, `ops/selective_scan.py`,
`ops/short_conv.py`, `ops/paged_attention.py`, `serve/llm/engine.py`),
against the plain float32 reference of
`benchmarks/reference/sambay_decoder.py` on seeded random weights at a
tiny size.  Logits are compared, never sampled tokens (but for the
engine tests, which judge served tokens by their reference logits, as
the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit, about 3 here) on
  logits, float32 against float32 on the CPU: the program's grouped
  attention over zero-padded query rows, its blockwise online softmax
  and its scan over folded channels against the reference's two plain
  softmaxes and token-by-token scan differ in the ORDER of float32
  sums; that reads 2e-6 relative.  Every mutilated program reads 100 x
  the tolerance and more, but the state kept in bf16: over 300 tokens
  the STATE is off by 100 x its own tolerance (1e-5 relative), the
  logits by 40 x what the sound program reads yet under 1e-4, because
  at this size the scan's share of a logit is small beside `Dskip`'s.
* The weights are drawn at 0.1, not the 0.02 of the published widths,
  and every bias and norm vector is drawn too (the family's draws are
  zeros and ones, which would hide a bias left out): with a tied head,
  hidden 64 and 0.02 the model echoes its input token whatever the
  layers do.
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
# two (Mamba, window) pairs, the middle pair, one (GMU, cross) pair; a
# window of 8 keys, 4 query pairs over 2 K/V pairs, d_inner one lane row
C = dict(model_type="phi4flash", hidden_size=64, num_attention_heads=8,
         num_key_value_heads=4, intermediate_size=128, hidden_act="silu",
         num_hidden_layers=8, mb_per_layer=2, sliding_window=8,
         layer_norm_eps=1e-5, tie_word_embeddings=True, mlp_bias=False,
         lm_head_bias=False, vocab_size=512, mamba_d_state=4,
         mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=4,
         initializer_range=0.1, precision=dict(recurrent_state="float32"))
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket
RING = (8 + BUCKET) // BS       # blocks of a slot's ring


def _drawn(weights):
    """Every bias and norm vector drawn, so that each is seen."""
    def leaf(path, x):
        name = path[-1].key
        if not (name.startswith(("b_", "ln_")) or name in (
                "conv_b", "sub_w", "norm_f_w", "norm_f_b")):
            return x
        key = jax.random.key(sum(map(ord, jax.tree_util.keystr(path))))
        return (x + 0.1 * jax.random.normal(key, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, weights)


# The same layers at shapes where a window layer's piece goes through
# the kernel (`ops.attention.prefill_engages`: pairs of heads of 64 laid
# 128 lanes wide, pieces and key rows in whole tiles of 128): a window
# of 128, buckets of 128, blocks of 16, a ring of 256 rows; `d_inner`
# stays under the scan kernels' whole tiles, so that the interpreter
# forced changes the insert's attention and nothing else
C_KERNEL = dict(C, hidden_size=512, sliding_window=128, mamba_expand=1,
                mamba_dt_rank=32)
GEOMETRY = {"loop": (BS, BUCKET, RING),
            "kernel": (16, 128, (128 + 128) // 16)}


def _build(c, max_seq_len=64, **overrides):
    from families import sambay_decoder as F
    from reference import sambay_decoder as R

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
    """A program function of `models/sambay.py` under `jax.jit`, its
    configuration static: one compile a shape for the whole module."""
    from ray_tpu.models import sambay as M

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
    assert (mc.n_self_pairs, mc.n_cross_pairs, mc.n_ssm_layers,
            mc.head_dim, mc.n_kv_pairs, mc.d_inner, mc.dt_rank) \
        == (2, 1, 3, 8, 2, 128, 4)
    assert params is weights            # one copy of the model
    toks = _tokens(50)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    assert _off(got, _reference_logits(R, weights, toks, 0, 50)) < RTOL


def test_published_sizes_count_to_the_published_total():
    """The reference's shapes and the counts module agree on the
    published 3.8 B, kind by kind."""
    import json

    import counts_sambay as K
    from reference import sambay_decoder as R

    with open(os.path.join(
            BENCH, "configs", "phi-4-mini-flash-reasoning-serve.json")) as f:
        c = json.load(f)
    got = R.param_counts(c)
    assert got["total"] == K.total_params(c) == 3_852_562_944
    assert (got["mamba_layer"], got["attn_layer"], got["gmu_layer"],
            got["cross_layer"]) == (119_895_040, 98_322_304, 104_867_840,
                                    91_766_144)


# ---------------------- (b) prefill + decode: both pools and slot state

def _prefill(mc, params, pools, state, slot, tables, toks, start,
             bucket=BUCKET):
    """One bucket-padded chunk of `toks` at `start` into the blocks of
    `tables` (the full kind's by position, the window kind's a ring) and
    the state row of `slot`, as the engine's insert program does it.
    Returns the ONE row the model hands back."""
    from ray_tpu.models.window_moe import WINDOW_LEAVES

    BS = pools["k"].shape[2]

    kind = lambda name: "window" if name in WINDOW_LEAVES else "full"
    hist = {k: v[:, tables[kind(k)]].reshape((v.shape[0], -1) + v.shape[3:])
            for k, v in pools.items()}
    padded = np.zeros((bucket,), np.int32)
    padded[:len(toks)] = toks
    mine = {k: jnp.where(start > 0, v[:, slot], 0) for k, v in state.items()}
    x, rows, mine = _jitted("prefill_paged")(
        params, jnp.asarray(padded)[None], jnp.int32(start), hist, mc,
        jnp.int32(len(toks)), mine)
    assert x.shape == (1, 1, mc.dim)
    at = start // BS + np.arange(bucket // BS)
    ids = {"full": tables["full"][at],
           "window": tables["window"][at % len(tables["window"])]}
    pools = {k: v.at[:, ids[kind(k)]].set(rows[k].reshape(
        (v.shape[0], bucket // BS, BS) + v.shape[3:]))
        for k, v in pools.items()}
    state = {k: v.at[:, slot].set(mine[k]) for k, v in state.items()}
    return x[0], pools, state


def _fresh(mc, n_blocks, slots=3, geometry="loop"):
    from ray_tpu.models.sambay import init_paged_pool, init_slot_state

    BS, _, RING = GEOMETRY[geometry]
    pools = init_paged_pool(mc, n_blocks + 9, BS, window_blocks=RING + 5)
    tables = {"full": np.arange(n_blocks, dtype=np.int32) + 5,
              "window": np.arange(RING, dtype=np.int32)[::-1] + 2}
    return pools, init_slot_state(mc, slots), tables


def _served_logits(mc, params, toks, n_prompt=None, slots=3, slot=2,
                   geometry="loop"):
    """Logits at the LAST row of every chunk of the prompt and at every
    later position of `toks` through the serving path: the prompt in
    chunks of BUCKET (state, tails and ring handed on in the slot), the
    rest a decode step a token with dead slots beside the live one.
    Returns (positions, [len(positions), V]); checks that the dead
    slots' state stands."""
    from ray_tpu.models.sambay import _head

    BS, BUCKET, _ = GEOMETRY[geometry]
    n_prompt = n_prompt or len(toks) - 10
    n_blocks = -(-len(toks) // BUCKET) * BUCKET // BS
    pools, state, table = _fresh(mc, n_blocks, slots, geometry)
    # the slot holds another sequence's garbage: admission must clear it
    state = jax.tree.map(lambda x: x.at[:, slot].set(1.0), state)
    at, got = [], []
    for start in range(0, n_prompt, BUCKET):
        end = min(start + BUCKET, n_prompt)
        x, pools, state = _prefill(mc, params, pools, state, slot, table,
                                   toks[start:end], start, BUCKET)
        at.append(end - 1)
        got.append(np.asarray(_head(mc, params, x)))
    tables = {k: np.zeros((slots, len(v)), np.int32)
              for k, v in table.items()}
    for k, v in table.items():
        tables[k][slot] = v
    tables = jax.tree.map(jnp.asarray, tables)
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
    # the last tick read len(toks) rows of the shared layer twice: the
    # full layer and the one cross layer
    assert int(counts["shared_kv_rows_read"]) == 2 * len(toks)
    return np.asarray(at), np.concatenate(got)


@functools.cache
def _kernel_model():
    return _build(C_KERNEL, max_seq_len=640)


@pytest.mark.parametrize("path, case", [
    ("loop", "one_bucket"), ("loop", "chunked"),
    ("kernel", "one_bucket"), ("kernel", "chunked")])
def test_paged_prefill_and_decode_match_reference(model, path, case,
                                                  monkeypatch):
    """Prefill (one bucket; three chunks across the window and round the
    ring of 24 rows, each over the rows, ring, state and tails before
    it) and then 10 decode steps through both kinds of pool and the
    slot's state, dead slots beside the live one: logits at every served
    position against the reference's full forward.  `kernel`: the same
    at pairs of 128 lanes and pieces of 128 rows (one bucket; five
    chunks round a ring of 256 rows) with the interpreter forced, so
    that every window layer's piece attends through
    `ops.attention.flash_prefill` and nothing else changes (float32
    pools, `d_inner` of 512: the tick keeps its gathers and the scan its
    plain forms)."""
    from ray_tpu.models.sambay import insert_attention
    from ray_tpu.ops import attention

    if path == "kernel":
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
        # from 128 queries and keys on, not the chip's 1024 and 2048
        monkeypatch.setattr(attention, "PREFILL_MIN_Q", 128)
        monkeypatch.setattr(attention, "PREFILL_MIN_K", 128)
        R, mc, weights, params = _kernel_model()
        c, n_prompt = C_KERNEL, {"one_bucket": 100, "chunked": 530}[case]
    else:
        R, mc, weights, params = model
        c, n_prompt = C, {"one_bucket": 13, "chunked": 43}[case]
        pools, state, _ = _fresh(mc, 8)
        assert pools["k"].shape == pools["v"].shape == (1, 17, BS, 32)
        assert pools["k_w"].shape == pools["v_w"].shape \
            == (2, RING + 5, BS, 32)
        assert state["h"].shape == (3, 3, 4, 1, 128)    # channels on lanes
        assert state["tail"].shape == (3, 3, 3 * 128)   # taps on lanes
    assert insert_attention(mc, 0, GEOMETRY[path][1], mc.max_seq_len)[0] \
        == path
    toks = _tokens(n_prompt + 10, seed=3)
    at, got = _served_logits(mc, params, toks, n_prompt, geometry=path)
    want = _reference_logits(R, weights, toks, 0, len(toks), c=c)[at]
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
    assert want.shape == got.shape == (3, 4, 128)
    assert np.abs(want).max() > 1e-3
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    # layer 0's input is the embedding, whatever comes after
    p = jax.tree.map(lambda a: np.asarray(a[0]), params["self"]["a"])
    x = np.asarray(params["embed"])[np.asarray(toks)]
    mu = x.mean(-1, keepdims=True)
    u = (x - mu) / np.sqrt(((x - mu) ** 2).mean(-1, keepdims=True) + 1e-5) \
        * p["ln_in_w"] + p["ln_in_b"]
    rows = (u @ p["w_in"])[:, :128]
    assert np.abs(rows[24:27]).max() > 0.1
    np.testing.assert_allclose(np.asarray(state["tail"][0, 1]),
                               rows[24:27].reshape(-1), atol=1e-5)


@pytest.mark.parametrize("case", ["chunked_equals_whole",
                                  "padded_equals_unpadded"])
def test_prefill_hand_off(model, case):
    """A prompt prefilled in chunks leaves the served row, the rows of
    both kinds, the scan's state and the tail that the same prompt
    prefilled whole leaves; padding advances nothing."""
    _, mc, _, params = model
    toks = _tokens(32, seed=5)
    plans = {"chunked_equals_whole": ((((0, 29),), 32),
                                      (((0, 16), (16, 29)), 16)),
             "padded_equals_unpadded": ((((0, 16),), 32),
                                        (((0, 16),), 16))}[case]
    out = []
    for chunks, bucket in plans:
        pools, state, table = _fresh(mc, 8, slots=2)
        # a ring as wide as the larger bucket needs, for both plans
        table["window"] = np.arange(10, dtype=np.int32) + 1
        pools = jax.tree.map(
            lambda v: jnp.zeros(v.shape[:1] + (17,) + v.shape[2:], v.dtype),
            pools)
        for a, b in chunks:
            x, pools, state = _prefill(mc, params, pools, state, 1, table,
                                       toks[a:b], a, bucket)
        n = chunks[-1][1]
        rows = [np.asarray(pools[k][:, table["full"]]).reshape(
            1, -1, 32)[:, :n] for k in ("k", "v")]
        # the window's rows that a later query can still see
        rows += [np.asarray(pools[k][:, table["window"]]).reshape(
            2, -1, 32)[:, n - 8:n] for k in ("k_w", "v_w")]
        out.append((np.asarray(x), rows,
                    {k: np.asarray(v[:, 1]) for k, v in state.items()}))
    (xa, ra, sa), (xb, rb, sb) = out
    assert xa.shape == xb.shape and np.abs(xa).max() > 0.5
    close = lambda a, b: np.abs(a - b).max() < 1e-5 * max(1, np.abs(a).max())
    assert close(xa, xb) and all(close(a, b) for a, b in zip(ra, rb))
    assert np.abs(sa["h"]).max() > 1e-3 and np.abs(sa["tail"]).max() > 1e-3
    for k in sa:
        assert close(sa[k], sb[k]), k


def test_insert_runs_its_second_half_for_one_row(model):
    """The insert's one served row is the reference's at the last REAL
    position, its full-kind rows are the reference's K and V of the one
    full layer at EVERY row, and nothing in the compiled insert has the
    cross-decoder's weights multiplied by a whole bucket of rows."""
    R, mc, weights, params = model
    toks = _tokens(13, seed=9)
    pools, state, table = _fresh(mc, 4, slots=2)
    x, pools, state = _prefill(mc, params, pools, state, 1, table, toks, 0)
    from ray_tpu.models.sambay import _head

    want = _reference_logits(R, weights, toks, 12, 1)
    assert _off(np.asarray(_head(mc, params, x)), want) < RTOL
    # the reference's own K and V of layer L/2 + 1, every row
    key = R._cfg_key(C)
    h = weights["embed"][jnp.asarray(toks + [0] * 3)].astype(jnp.float32)
    for i in range(2):
        h, _ = R._self_pair_jit(h, weights["self"], i, key)
    _, _, k, v, _ = R._mid_jit(h, weights["mid"], key)
    got = [np.asarray(pools[n][0, table["full"]]).reshape(-1, 32)[:13]
           for n in ("k", "v")]
    for g, w in zip(got, (k, v)):
        w = np.asarray(w).reshape(16, 32)[:13]
        assert np.abs(w).max() > 0.1
        assert np.abs(g - w).max() < 1e-5 * np.abs(w).max()
    # [16, .] x the cross pairs' W1 [64, 256] appears nowhere: one row
    from ray_tpu.models import sambay as M

    hist = {n: jnp.zeros((p.shape[0], 64) + p.shape[3:])
            for n, p in pools.items()}
    hist["k_w"] = hist["v_w"] = jnp.zeros((2, RING * BS, 32))
    text = jax.jit(M.prefill_paged, static_argnames=("config",)).lower(
        params, jnp.zeros((1, 16), jnp.int32), jnp.int32(0), hist, mc,
        jnp.int32(13), {n: s[:, 1] for n, s in state.items()}).as_text()
    assert "tensor<1x1x256xf32>" in text        # the cross pair's one row
    dots = [l for l in text.splitlines() if "dot_general" in l]
    # 16 rows meet a [64, 256] matrix (W_in, W1) in the self pairs' scan
    # body (W_in, W1, W1) and in the middle Mamba (W_in, W1) alone
    assert sum("x16x256xf32>" in l.split("->")[-1] for l in dots) == 5


# ------------- (c) the mutilated programs fail the same comparison

def _tail_after_padding(self, st, j, xs, w):
    from ray_tpu.ops import short_conv

    y, tail = short_conv.short_conv(
        xs, w, short_conv.rows(st["tail"][j], w), xs.shape[1])
    return y, dict(st, tail=st["tail"].at[j].set(short_conv.flat(tail)))


# what the reference leaves out or changes, and the program then has
# that it has not; the last is patched into the PROGRAM
MUTILATIONS = {
    "the_second_softmax_dropped": "second_softmax",
    "v1_v2_swapped": "v_order",
    "lam0_of_another_depth": "depth",
    "no_sub_norm": "sub_norm",
    "no_one_minus_lam0": "one_minus_lam0",
    "the_memory_taken_after_the_gate": "memory_before_gate",
    "a_cross_layer_reading_its_own_kv": "shared_kv",
    "a_window_of_513": "window",
    "dskip_dropped": "dskip",
    "delta_without_its_bias": "dt_bias",
    "rotary_added": "no_rotary",
    "a_tail_taken_after_padded_rows": None,
}


@pytest.mark.parametrize("what", sorted(MUTILATIONS))
def test_a_mutilated_program_fails(model, what, monkeypatch):
    """The served logits (a chunked prompt, then decode steps) are far
    from each mutilated reference by 100 x the tolerance; the program
    patched to take its tail after a bucket's padding is as far from
    the sound one."""
    from ray_tpu.models import sambay as M

    R, mc, weights, params = model
    toks = _tokens(27 + 6, seed=12)
    piece = MUTILATIONS[what]
    if piece is None:
        monkeypatch.setattr(M._Sequences, "conv", _tail_after_padding)
        # jitted anew (a partial is a function of its own): the
        # mutilation is there as it traces
        monkeypatch.setitem(globals(), "_jitted", lambda name: jax.jit(
            functools.partial(getattr(M, name)),
            static_argnames=("config",)))
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
    way does, and the logits by 10 x what the sound program reads (at
    this size the scan's share of a logit is small beside `Dskip`'s)."""
    from families import sambay_decoder as F
    from ray_tpu.ops.selective_scan import unfold

    with pytest.raises(ValueError, match="recurrent state kept in bfloat16"):
        F.model_config(dict(C, precision=dict(recurrent_state="bfloat16")),
                       max_seq_len=64, compute_dtype="float32",
                       param_dtype="float32")
    R, mc, weights, params = model
    toks = _tokens(300, seed=2)
    want = _reference_logits(R, weights, toks, 0, 300)
    at, sound = _served_logits(mc, params, toks, 100)
    assert _off(sound, want[at]) < RTOL
    rounding = dataclasses.replace(mc, state_dtype=jnp.bfloat16)
    _, bf16 = _served_logits(rounding, params, toks, 100)
    assert _off(bf16, want[at]) > 10 * _off(sound, want[at])
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
    ("mb_per_layer 4", {"mb_per_layer": 4}),
    ("a depth of 6", {"num_hidden_layers": 6}),
    ("an untied head", {"tie_word_embeddings": False}),
    ("mlp_bias", {"mlp_bias": True}),
    ("lm_head_bias", {"lm_head_bias": True}),
    ("query pairs that do not divide", {"num_key_value_heads": 3}),
    ("a dt_rank other than", {"mamba_dt_rank": 8}),
    ("hidden_act gelu", {"hidden_act": "gelu"}),
])
def test_the_family_refuses_what_the_program_does_not_compute(refused,
                                                              change):
    from families import sambay_decoder as F

    with pytest.raises(ValueError, match=refused):
        F.model_config(dict(C, **change), max_seq_len=64,
                       compute_dtype="float32", param_dtype="float32")


def test_the_control_rounds_the_matrices_and_nothing_else(model):
    """`quantize_int8` (the benchmark's control) changes every matmul
    weight, a stacked leaf's layers each by their own scales, and hands
    back the table, the taps, the vectors and the decays untouched."""
    from ray_tpu.models.sambay import quantize_int8

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
               kv_block_size=BS, num_kv_blocks=40, num_window_blocks=16,
               decode_block=1, prefix_cache=False)
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
    state cleared and its ring given back), prompts shorter and longer
    than the top bucket, one past the ring's 24 rows: every served
    token's reference logit lies within the tolerance of the reference
    maximum."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    assert engine._ring is not None and engine._stateful
    assert engine._ring.ring == RING
    live_before = int(engine.stats()["counters"]["live_slots"])
    lengths = (5, 16, 23, 45, 9)
    handles = [engine.submit(Request(
        prompt=_tokens(n, seed=20 + i), max_tokens=6, temperature=0.0,
        chunked_prefill=n > 16)) for i, n in enumerate(lengths)]
    while engine.has_work():
        engine.step()
    stats = engine.stats()
    assert stats["paged_attention"] == "gather"
    assert stats["counters"]["ssm_live_steps"] == 0
    assert stats["counters"]["live_slots"] - live_before >= 5 * 5
    assert stats["counters"]["shared_kv_rows_read"] > 0
    assert stats["slot_state"]["bytes"] == 3 * 3 * (4 * 128 * 4 + 3 * 128 * 4)
    assert stats["kv"]["window"]["used_blocks"] == 0    # rings given back
    assert stats["kv"]["used_blocks"] == 0
    for i, (n, h) in enumerate(zip(lengths, handles)):
        assert h.finish_reason == "length" and len(h.tokens) == 6
        d = R.served_token_deficits(weights, C, _tokens(n, seed=20 + i),
                                    h.tokens)
        assert d.max() < RTOL * 3, (n, d)


@pytest.mark.parametrize("what", ["prefix_cache", "export_prefix",
                                  "prefill_only", "preempt", "speculation"])
def test_engine_refuses_by_name_what_would_lose_state_or_ring(model, what):
    from ray_tpu.serve.llm.engine import Request

    _, mc, _, params = model
    if what == "prefix_cache":
        with pytest.raises(ValueError, match="prefix"):
            _engine(mc, params, prefix_cache=True)
        return
    if what == "speculation":
        from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

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

def _scan_inputs(T, N, R, seed=0):
    k = jax.random.split(jax.random.key(seed), 6)
    return dict(
        h0=jax.random.normal(k[0], (N, R, 128)),
        delta=0.1 * jax.nn.softplus(jax.random.normal(k[1], (T, R, 128))),
        x=jax.random.normal(k[2], (T, R, 128)),
        b=jax.random.normal(k[3], (T, N)), c=jax.random.normal(k[4], (T, N)),
        a=-jnp.exp(jax.random.normal(k[5], (N, R, 128))))


def _recurrence(h0, delta, x, b, c, a, n_real):
    """The equations, a token at a time in numpy float64."""
    h, ys = np.asarray(h0, np.float64), []
    f = lambda t: np.asarray(t, np.float64)
    delta, x, b, c, a = f(delta), f(x), f(b), f(c), f(a)
    for t in range(delta.shape[0]):
        if t < n_real:
            h = np.exp(delta[t] * a) * h \
                + (delta[t] * x[t]) * b[t][:, None, None]
        ys.append((h * c[t][:, None, None]).sum(0))
    return np.stack(ys), h


@pytest.mark.parametrize("form", ["plain", "kernel"])
def test_scan_over_a_sequence_is_the_recurrence(form, monkeypatch):
    """`ssm_scan`, token by token under `lax.scan` and as the Pallas
    kernel (interpreted: 16 channel rows, tiles of 8 rows of the
    sequence, a state handed in), stops the state at `n_real`."""
    from ray_tpu.ops import attention
    from ray_tpu.ops import selective_scan as S

    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET",
                        form == "kernel")
    monkeypatch.setattr(S, "SCAN_ROWS", 8)
    z = _scan_inputs(32, 4, 16)
    assert S.engages(z["h0"]) == (form == "kernel")
    y, h = S.ssm_scan(**z, n_real=21)
    want_y, want_h = _recurrence(**z, n_real=21)
    assert np.abs(np.asarray(y)[:21] - want_y[:21]).max() < 1e-5
    assert np.abs(np.asarray(h) - want_h).max() < 1e-5
    assert np.abs(want_h - np.asarray(z["h0"])).max() > 0.1


def test_step_kernel_steps_the_live_slots_where_they_lie(monkeypatch):
    """`ssm_step_live` (interpreted) on one layer of a stack: the live
    slots' rows are `ssm_step`'s, a dead slot's and the other layers'
    stand to the bit, a dead slot's output is zeros."""
    from ray_tpu.ops import attention, kda
    from ray_tpu.ops import selective_scan as S

    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    k = jax.random.split(jax.random.key(1), 2)
    L, B, N, R = 3, 6, 4, 8
    H = jax.random.normal(k[0], (L, B, N, R, 128))
    z = _scan_inputs(B, N, R, seed=2)
    z.pop("h0")
    live = jnp.array([1, 0, 1, 1, 0, 1], bool)
    assert S.engages(H) and not S.engages(H.astype(jnp.bfloat16)) \
        and not S.engages(H[..., :4, :])
    want_y, want_h = S.ssm_step(H[1], **z)
    y, H1 = S.ssm_step_live(H, 1, **z, plan=kda.live_plan(live, B))
    m = np.asarray(live)
    assert np.abs(np.asarray(y)[m] - np.asarray(want_y)[m]).max() < 1e-5
    assert not np.asarray(y)[~m].any()
    assert np.abs(np.asarray(H1[1])[m] - np.asarray(want_h)[m]).max() < 1e-5
    assert np.array_equal(np.asarray(H1[1])[~m], np.asarray(H[1])[~m])
    assert np.array_equal(np.asarray(H1)[[0, 2]], np.asarray(H)[[0, 2]])


def test_differential_layout_agrees_on_both_paged_paths(monkeypatch):
    """A decode step at heads that tile (pairs of 128 lanes, blocks of
    16 rows, bf16) with the interpreter forced goes through
    `ops.paged_attention`'s kernel in both its forms, the scan step
    through its kernel, and lands on the gather path's logits."""
    from ray_tpu.models import sambay as M
    from ray_tpu.ops import attention

    mc = M.SambaYConfig.tiny(dim=512, n_heads=8, n_kv_heads=4, window=24,
                             hidden_dim=64, vocab_size=256)
    assert (mc.head_dim, mc.kv_width, mc.d_inner) == (64, 256, 1024)
    params = _drawn(M.init_params(mc, jax.random.key(3), std=0.1))
    B, bs, ring = 3, 16, 4
    pools = M.init_paged_pool(mc, 12, bs, window_blocks=14)
    pools = jax.tree.map(lambda p: jax.random.normal(
        jax.random.key(p.shape[1]), p.shape, p.dtype), pools)
    state = jax.tree.map(lambda s: 0.1 * jax.random.normal(
        jax.random.key(7), s.shape).astype(s.dtype),
        M.init_slot_state(mc, B))
    tables = {"full": jnp.arange(B * 4, dtype=jnp.int32).reshape(B, 4),
              "window": jnp.arange(B * ring, dtype=jnp.int32).reshape(
                  B, ring) + 1}
    tok = jnp.array([5, 6, 7], jnp.int32)
    pos = jnp.array([41, 3, 60], jnp.int32)
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
        assert int(counts["ssm_live_steps"]) == (2 * 3 if force else 0)
        out[force] = (np.asarray(logits)[[0, 2]], np.asarray(st["h"]))
    # bf16 operands either way; the kernel keeps float32 scores where
    # the gather rounds them: a hundredth of the logits' size
    scale = np.abs(out[False][0]).max()
    assert scale > 0.3
    assert np.abs(out[True][0] - out[False][0]).max() < 2e-2 * scale
    assert np.abs(out[True][1] - out[False][1]).max() < 1e-2
