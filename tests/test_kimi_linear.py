"""Recurrent delta-rule layers (KDA) with their state by slot beside
latent attention over the paged latent cache, and an expert layer that
holds a share of the experts (`models/kimi_linear.py`, `ops/kda.py`,
`models/moe.py::dropless_moe(share=)`, `serve/llm/engine.py`), against
the plain float32 reference of `benchmarks/reference/kda_hybrid_decoder.py`
on seeded random weights at a tiny size.  Logits are compared, never
sampled tokens (but for the engine tests, which judge served tokens by
their reference logits, as the benchmark does).

Tolerances and their reasons
----------------------------
* 5e-6 on logits of magnitude 0.6, float32 against float32 on the CPU:
  the program and the reference differ in the ORDER of float32 sums
  (the chunkwise form against the token-by-token recurrence, sorted
  expert groups against blocks, absorbed against expanded attention);
  that reads 2e-7 to 6e-7 here.  A recurrent state kept in bf16 between
  tokens reads 1e-3 and int8-rounded matrices 2e-2:
  `test_lower_precision_is_caught` holds the tolerance to half of both.
* `kda_chunked` against `kda_step` applied token by token: 2e-6 on
  outputs and states of magnitude 1 to 3, for decays within 1e-4 of 1
  (where nothing is forgotten and sums grow) and for decays of e^-12 a
  token (where a factored exp(-G) would overflow after 8 tokens), at
  chunks of 16 (one sub-block) and of 64 (four, the terms between them
  matrix products around a reference row); 2e-5 where strong and weak
  tokens alternate inside a chunk of 64 (reads 6.4e-6 on outputs and
  1.5e-5 on a state, with the `[C, C, dk]` reduction as with the
  products and with either solve: the float32 `cumsum` of the
  log-decay, see the test's own words).
* The engine tests (`test_kimi_linear_engine.py`, a file of its own so
  that `--dist loadfile` can give it another worker) serve greedy tokens
  in float32; each served token's reference logit lies within 1e-4 of
  the reference maximum (0 unless two logits tie to within the sums'
  reordering).
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import latent_walk
from kimi_linear_tiny import (     # noqa: F401  (`model`: a fixture)
    BS, BUCKET, C, KERNEL_BS, TOL, _build, _drawn_at_a_tenth,
    _reference_logits, _tiling, _tokens, model,
)


@functools.cache
def _jitted(name):
    """A program function of `models/kimi_linear.py` under `jax.jit`,
    its configuration static: one compile a shape for the whole module
    where op-by-op dispatch compiled every primitive of every layer."""
    from ray_tpu.models import kimi_linear as KL

    return jax.jit(getattr(KL, name), static_argnames=("config",))


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert (mc.n_kda_layers, mc.n_mla_layers, mc.n_held_experts,
            mc.expert_rank, mc.expert_shards) == (4, 1, 2, 1, 4)
    toks = _tokens(50)
    got = np.asarray(_jitted("forward")(params, jnp.asarray(toks)[None],
                                        mc)[0])
    want = _reference_logits(R, weights, toks, 0, 50)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < TOL


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


@pytest.mark.parametrize("case", ["one_bucket", "chunked"])
def test_paged_prefill_and_decode_match_reference(model, case):
    """Prefill (one bucket; two chunks, the second over the first's rows
    and state) and then 10 decode steps through the paged latent pool
    and the slot's recurrent state: logits at every position against
    the reference's full forward."""
    from ray_tpu.models.kimi_linear import (LM, init_paged_pool,
                                            init_slot_state)

    R, mc, weights, params = model
    n_prompt = {"one_bucket": 13, "chunked": 27}[case]
    toks = _tokens(n_prompt + 10, seed=3)
    pools = init_paged_pool(mc, 40, BS)
    assert pools["latent"].shape[0] == 1            # MLA layers only
    state = init_slot_state(mc, 3)
    # slot 2 holds another sequence's garbage: admission must clear it
    state = jax.tree.map(lambda x: x.at[:, 2].set(1.0), state)
    table = np.arange(16, dtype=np.int32) + 5
    hidden = []
    for start in range(0, n_prompt, BUCKET):
        x, pools, state = _prefill(mc, params, pools, state, 2, table,
                                   toks[start:min(start + BUCKET, n_prompt)],
                                   start)
        hidden.append(x)
    got = [np.asarray(LM._head(mc, params, jnp.concatenate(hidden)))]
    tables = np.zeros((3, 16), np.int32)
    tables[2] = table
    active = jnp.asarray([False, False, True])
    before = jax.tree.map(lambda x: np.asarray(x[:, :2]), state)
    for t in range(n_prompt, n_prompt + 10):
        logits, pools, counts, state = _jitted("decode_step_paged")(
            params, pools, jnp.asarray(tables),
            jnp.asarray([0, 0, toks[t]]), jnp.asarray([0, 0, t]), mc,
            active, state)
        got.append(np.asarray(logits[2:3]))
    want = _reference_logits(R, weights, toks, 0, len(toks))
    assert np.abs(np.concatenate(got) - want).max() < TOL
    # dead slots: their state stands as it was
    for k, v in before.items():
        assert np.array_equal(np.asarray(state[k][:, :2]), v)
    assert int(counts["live_slots"]) == 1 and int(counts["ticks"]) == 1
    assert int(counts["pairs_total"]) == mc.top_k * mc.n_moe_layers
    assert int(counts["pairs_local"]) == int(counts["expert_tokens"].sum())
    assert counts["expert_tokens"].shape == (4, 2)   # held experts only


# --------------------- (c) what the chunks of one prompt hand each other

@pytest.mark.parametrize("case", sorted(latent_walk.CASES))
def test_insert_walks_the_history_it_has(model, case, monkeypatch):
    """A whole insert (the latent layers with no rotary beside the
    recurrent ones, which read no history) by `_History`'s walk of the
    history up to `start + Pb` and by `attend_expanded` over all of the
    padded history: the normed hidden states of every query, the padded
    ones included, agree to 1e-5 and are finite.  In float32: between
    two bf16 forms a routing flip moves a hidden state by half its
    size; the walk's bf16 rounding is held to the plain form's where no
    router follows it, `tests/test_latent_moe.py::
    test_history_walk_equals_the_plain_form`."""
    from ray_tpu.models.kimi_linear import init_slot_state, prefill_paged

    _, mc, _, params = model
    state = {k: v[:, 0] for k, v in init_slot_state(mc, 1).items()}
    assert latent_walk.insert_walk_error(
        monkeypatch, prefill_paged, mc, params, mc.n_mla_layers, case,
        state) < 1e-5


@pytest.mark.parametrize("case", ["chunked_equals_whole",
                                  "padded_equals_unpadded"])
def test_prefill_hand_off(model, case):
    """A prompt prefilled in chunks leaves the rows, the recurrent state
    and the convolution's tail that the same prompt prefilled whole
    leaves; a prompt in a larger (padded) bucket leaves what it leaves
    in one it fills exactly: padding advances nothing."""
    from ray_tpu.models.kimi_linear import init_paged_pool, init_slot_state

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
        rows = np.asarray(pools["latent"][:, table]).reshape(
            1, -1, pools["latent"].shape[-1])[:, :n]
        out.append((np.concatenate(xs), rows,
                    {k: np.asarray(v[:, 1]) for k, v in state.items()}))
    (xa, ra, sa), (xb, rb, sb) = out
    assert xa.shape == xb.shape and np.abs(xa).max() > 0.5
    assert np.abs(xa - xb).max() < TOL
    assert np.abs(ra - rb).max() < TOL
    assert np.abs(sa["S"]).max() > 1e-3 and np.abs(sa["conv"]).max() > 1e-3
    for k in sa:
        assert np.abs(sa[k] - sb[k]).max() < TOL, k


# ---------------- (d) the chunkwise form against the one-token recurrence

def _kda_chunked(*args, chunk):
    """`ops.kda.kda_chunked` under `jax.jit`, the chunk static."""
    from ray_tpu.ops.kda import kda_chunked

    return jax.jit(kda_chunked, static_argnames="chunk")(*args, chunk=chunk)


def _token_by_token(q, k, v, g, beta, S):
    """`kda_step` over the sequence: (outputs [B, T, H, dv], the state
    after every token)."""
    from ray_tpu.ops.kda import kda_step

    step = jax.jit(kda_step)
    outs, states = [], []
    for t in range(q.shape[1]):
        o, S = step(S, q[:, t], k[:, t], v[:, t], g[:, t], beta[:, t])
        outs.append(o)
        states.append(S)
    return jnp.stack(outs, 1), states


@pytest.mark.parametrize("lo,hi", [(-1e-4, -1e-6), (-12.0, -3.0),
                                   (-2.0, -0.01)],
                         ids=["decay_near_1", "decay_near_0", "mixed"])
@pytest.mark.parametrize("width", ["a_channel", "a_head"])
def test_chunkwise_kda_equals_recurrence(lo, hi, width):
    """`width` a_head: `g` [.., H, 1], one decay a head, keys of another
    size than values and write strengths up to 2 (the gated delta rule
    of `models/gdn_hybrid.py`)."""
    B, T, H, dk, dv = 2, 50, 3, 16, 8
    if width == "a_head":
        dk, dv = 12, 24
    ks = jax.random.split(jax.random.key(0), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk)))
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
    g = jax.random.uniform(ks[4], (B, T, H, dk), minval=lo, maxval=hi)
    if width == "a_head":
        beta, g = 2 * beta, g[..., :1]
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))
    n_real = jnp.asarray([50, 37])
    want, states = _token_by_token(q, k, v, g, beta, S0)
    got, S_got = _kda_chunked(q, k, v, g, beta, S0, n_real, chunk=16)
    assert jnp.abs(want).max() > 0.5
    assert jnp.abs(got[0] - want[0]).max() < 2e-6
    assert jnp.abs(got[1, :37] - want[1, :37]).max() < 2e-6
    # the state after the last REAL token: padding did not advance it
    S_want = jnp.stack([states[49][0], states[36][1]])
    assert jnp.abs(S_got - S_want).max() < 2e-6


def _decays(case, key, shape):
    """Log-decays a token a channel for the sub-block cases: `near_1`
    within 1e-4 of no decay, `near_0` e^-12 to e^-3 a token, `mixed`
    strong and weak tokens alternating inside every sub-block of 16 (in
    row 0; row 1 the other way round, by threes)."""
    weak = jax.random.uniform(key, shape, minval=-1e-4, maxval=-1e-6)
    strong = jax.random.uniform(jax.random.fold_in(key, 1), shape,
                                minval=-12.0, maxval=-3.0)
    if case != "mixed":
        return {"near_1": weak, "near_0": strong}[case]
    t = jnp.arange(shape[1])
    pick = jnp.stack([t % 2 == 0, t % 3 != 0])[:, :, None, None]
    return jnp.where(pick, strong, weak)


@pytest.mark.parametrize("T, n_real", [(150, (150, 101)), (192, (192, 64))],
                         ids=["ragged_last_chunk", "whole_chunks"])
@pytest.mark.parametrize("case, tol", [("near_1", 2e-6), ("near_0", 2e-6),
                                       ("mixed", 2e-5)],
                         ids=["near_1", "near_0", "mixed"])
def test_chunkwise_kda_by_sub_blocks_equals_recurrence(case, tol, T, n_real):
    """One decay a channel at `chunk=64`: four sub-blocks of 16 a chunk,
    so the in-chunk terms between sub-blocks are the matrix products
    around a reference row (`ops.kda._decayed_products`), several chunks
    from a non-zero state, the last one ragged, one row short of T.
    (`mixed` reads 6.4e-6 on the outputs and 1.5e-5 on the state, with
    the products as with the `[C, C, dk]` reduction they replace and
    with either form of the solve, to the last digit; 1.1e-6 at chunks
    of 16.  It is the cumulative log-decay `G` in float32: by a chunk's
    64th row it reaches -365, where float32 steps by 3e-5, and a
    difference `G_r - G_i` between two weak rows, itself 1e-6 to 1e-4,
    carries that step into its `exp`.  With the `cumsum` alone taken in
    float64 the case reads 1.7e-7 / 4.9e-8 against a float64 recurrence,
    with the solve alone in float64 the same 9.9e-6 / 6.7e-6 as with
    nothing (PR 50).  The products themselves are held to float64 in
    the next test, the solve in the one after.)"""
    B, H, dk, dv = 2, 3, 16, 8
    ks = jax.random.split(jax.random.key(1), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (B, T, H, dk)))
    k = unit(jax.random.normal(ks[1], (B, T, H, dk)))
    v = jax.random.normal(ks[2], (B, T, H, dv))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (B, T, H)))
    g = _decays(case, ks[4], (B, T, H, dk))
    S0 = jax.random.normal(ks[5], (B, H, dk, dv))
    want, states = _token_by_token(q, k, v, g, beta, S0)
    got, S_got = _kda_chunked(q, k, v, g, beta, S0, jnp.asarray(n_real),
                              chunk=64)
    assert jnp.abs(want).max() > 0.5
    for row, n in enumerate(n_real):
        assert jnp.abs(got[row, :n] - want[row, :n]).max() < tol
        # the state after the last REAL token: padding did not advance it
        assert jnp.abs(S_got[row] - states[n - 1][row]).max() < tol


@pytest.mark.parametrize("per_token", [-20.0, -1e-5, (-20.0, -1e-5)],
                         ids=["e-20_a_token", "no_decay", "alternating"])
def test_decayed_products_neither_overflow_nor_lose_a_term(per_token):
    """`_decayed_products` against the sum it stands for, taken in
    float64 from the decay differences themselves: at e^-20 a token
    (`exp(-G_i)` from the chunk's start would pass float32 after five
    rows) every entry is finite, the entries beside the diagonal are not
    lost, and nothing lies above the diagonal."""
    from ray_tpu.ops.kda import _decayed_products

    C, dk = 64, 16
    ks = jax.random.split(jax.random.key(2), 4)
    x = jax.random.normal(ks[0], (2, 3, C, dk))
    k = jax.random.normal(ks[1], (3, C, dk))
    per_token = jnp.resize(jnp.asarray(per_token), C)[:, None]
    g = per_token * jax.random.uniform(ks[2], (3, C, dk), minval=0.5,
                                       maxval=1.0)
    # a few channels that hardly decay, so that far entries survive
    g = jnp.where(jnp.arange(dk) < 3, 1e-3 * g, g)
    G = jnp.cumsum(g, -2)
    got = np.asarray(_decayed_products(x, k, G))
    assert np.isfinite(got).all()
    G64 = np.asarray(G, np.float64)
    diff = G64[:, :, None, :] - G64[:, None, :, :]           # [.., r, i, c]
    lower = np.tril(np.ones((C, C), bool))
    want = np.einsum("xhrc,hic,hric->xhri", np.asarray(x, np.float64),
                     np.asarray(k, np.float64),
                     np.exp(np.where(lower[..., None], diff, -np.inf)))
    assert np.abs(want[..., 40:, :16]).max() > 1e-2     # across sub-blocks
    assert np.abs(got - want).max() < 1e-5 * np.abs(want).max()
    assert not got[..., ~lower].any()


def _repeated_keys(key, C, strength):
    """`A Diag(beta)` of a chunk whose keys all but repeat (one direction
    a head, 5% of noise a row) at write strengths of 0.9-1.0 times
    `strength`: entries of 0.85-1.0 (times `strength`) down whole
    columns."""
    ks = jax.random.split(key, 3)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    k = unit(unit(jax.random.normal(ks[0], (3, 1, 16)))
             + 0.05 * jax.random.normal(ks[1], (3, C, 16)))
    beta = strength * jax.random.uniform(ks[2], (3, C), minval=0.9,
                                         maxval=1.0)
    N = jnp.tril(jnp.einsum("hrc,hic->hri", k, k) * beta[:, None, :], -1)
    assert N[:, 1:, 0].min() > 0.85 * strength
    return N


@pytest.mark.parametrize("keys, C, W", [
    ("random", 64, 256), ("random", 64, 288), ("random", 16, 256),
    ("random", 16, 288), ("repeated", 64, 256), ("repeated_twice", 64, 288),
    ("repeated", 16, 288)])
def test_unit_lower_solve_is_forward_substitution(keys, C, W):
    """`_unit_lower_solve` against forward substitution in float64, row
    by row: at a chunk of 64 (four diagonal blocks inverted together,
    then the block recurrence) and of 16 (one block, no recurrence), at
    the two delta-rule cells' right-hand sides (`dv + dk` = 128 + 128,
    192 + 96: two sides through the same inverses),
    on random strictly-lower matrices and on the hard ones: keys that
    repeat, at write strengths near 1 and (`repeated_twice`, the gated
    delta rule's) near 2, where the product of `I + N^(2^j)` that is
    the same inverse on paper cancels to nothing in float32."""
    from ray_tpu.ops.kda import _unit_lower_solve

    ks = jax.random.split(jax.random.key(3), 3)
    if keys == "random":
        N = jnp.tril(0.3 * jax.random.normal(ks[0], (2, 3, C, C)), -1)
    else:
        N = _repeated_keys(ks[0], C, 2.0 if keys == "repeated_twice" else 1.0)
    tol = 4e-6 if keys == "repeated_twice" else 1e-6
    rhs = jax.random.normal(ks[1], N.shape[:-1] + (W,))
    want = np.array(rhs, np.float64)
    N64 = np.asarray(N, np.float64)
    for i in range(1, C):
        want[..., i, :] -= np.einsum("...m,...mw->...w", N64[..., i, :i],
                                     want[..., :i, :])
    dv = {256: 128, 288: 192}[W]        # the values' side, then the keys'
    got = np.concatenate(jax.jit(_unit_lower_solve)(
        N, rhs[..., :dv], rhs[..., dv:]), -1)
    assert np.abs(got - want).max() < tol * np.abs(want).max()
    if keys != "random" and C == 64:
        eye, hi = jnp.eye(C), jax.lax.Precision.HIGHEST
        series, power = eye - N, N
        for _ in range(5):              # (I - N)(I + N^2) .. (I + N^32)
            power = jnp.matmul(power, power, precision=hi)
            series = jnp.matmul(series, eye + power, precision=hi)
        lost = np.asarray(jnp.matmul(series, rhs, precision=hi)) - want
        assert np.abs(lost).max() > 1e3 * np.abs(want).max()


# ----------------------------------- (e) the shares add up to the layer

def test_expert_shares_sum_to_the_uncut_layer():
    """The parts of an expert layer's result that the four shares give
    (each routes over all 8 experts, holds 2 and sums those), with the
    shared expert counted once, add up to what the reference gives for
    the whole layer with all 8 experts held."""
    from reference import kda_hybrid_decoder as R

    from ray_tpu.models import latent_moe as LM
    from ray_tpu.models.moe import dropless_moe, sigmoid_bias_top_k

    uncut = dict(C, num_experts=8, deployment=dict(num_experts=8, rank=0))
    w_all = R.init_weights(uncut, 11, jnp.float32)["layers"][1]
    # wide inputs: the scores then spread past the selection bias, so
    # the tokens' choices differ and fall on every share
    h = 8.0 * jax.random.normal(jax.random.key(4), (24, 64), jnp.float32)
    with jax.default_matmul_precision("highest"):
        routed = R.held_experts(uncut, h, R.route(
            uncut, h, w_all["router"], w_all["router_bias"]),
            w_all["experts"])
        want = routed + R._swiglu(h, w_all["ws_gate"], w_all["ws_up"],
                                  w_all["ws_down"])
    assert jnp.abs(routed).max() > 1e-3
    total, seen, busy = 0.0, 0, 0
    for rank in range(4):
        share = dict(C, deployment=dict(num_experts=8, rank=rank))
        w = R.init_weights(share, 11, jnp.float32)["layers"][1]
        assert jnp.array_equal(w["router"], w_all["router"])
        bank = R.expert_bank(w["experts"])
        assert bank["w_gate"].shape[0] == 2
        y, sizes = dropless_moe(
            h, dict(w, **bank), sigmoid_bias_top_k(2, 2.446),
            share=(rank, 4))
        total, seen = total + y, seen + int(sizes.sum())
        busy += int(sizes.sum() > 0)
    assert seen == 24 * 2                   # every assignment, once
    assert busy >= 3                        # and not all on one share
    shared = LM._swiglu(h, w_all["ws_gate"], w_all["ws_up"],
                        w_all["ws_down"], jnp.float32)
    assert jnp.abs(total + shared - want).max() < TOL


@pytest.mark.parametrize("rank", [0, 3])
def test_a_share_agrees_on_both_grouped_paths(rank, monkeypatch):
    """`dropless_moe(share=)` at widths that tile (bf16, 128 -> 128, 2
    of 8 experts held, dead rows besides): three quarters of the
    assignments sort last, in no group.  `ops.grouped_matmul` through
    the Pallas interpreter against `lax.ragged_dot`: the held experts'
    counts to the row, outputs to 2 ulp of bf16 at their size."""
    from ray_tpu.models import kimi_linear as KL, moe
    from ray_tpu.ops import attention

    c = KL.KimiLinearConfig.tiny(dim=128, expert_hidden_dim=128,
                                 expert_shards=4, expert_rank=rank)
    p = KL.init_params(c, jax.random.key(5))["layers"][1]
    assert p["w_gate"].shape == (2, 128, 128)
    x = 4.0 * jax.random.normal(jax.random.key(6), (64, 128), c.dtype)
    live = jnp.arange(64) % 4 != 2
    routing = moe.sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor)
    out = {}
    for path, force in (("xla", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert KL._SERVING.grouped_matmul(c, 32) == path
        out[path] = moe.dropless_moe(x, p, routing, live=live,
                                     share=(rank, 4))
    (yx, sx), (yk, sk) = out["xla"], out["kernel"]
    assert sx.shape == (2,) and sx.tolist() == sk.tolist()
    assert 0 < int(sx.sum()) < 48 * 2
    yx, yk = np.asarray(yx, np.float32), np.asarray(yk, np.float32)
    scale = np.abs(yx).max()
    assert scale > 1e-3 and np.abs(yx - yk).max() <= 2 ** -7 * scale
    assert not yk[~np.asarray(live)].any()


# --------------------------------------------------- (f) lower precision

@pytest.mark.parametrize("what", ["state_bf16", "int8"])
def test_lower_precision_is_caught(model, what):
    """The tolerance is tight enough: a recurrent state kept in bf16
    between tokens (the cell's second control), or matrices rounded to
    int8 (its first), fails it."""
    from families import kda_hybrid_decoder as F

    R, mc, weights, params = model
    toks = _tokens(50)
    want = _reference_logits(R, weights, toks, 0, 50)
    if what == "int8":
        # the control deletes the bank of experts `program_params` made
        # last: make that one this test's own, not the fixture's
        _, _, weights, _ = _build(C)
        params = jax.jit(F.lower_precision_params)(weights)
        got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    else:
        c = dict(C, precision=dict(recurrent_state="bfloat16"))
        _, mc16, _, _ = _build(c)
        assert mc16.state_dtype == jnp.bfloat16
        got = _served_logits(mc16, params, toks)
    assert np.abs(np.asarray(got) - want).max() > 2 * TOL


def _served_logits(mc, params, toks):
    """Logits of every position through the serving path: the first
    bucket prefilled, the rest decoded a token at a time."""
    from ray_tpu.models.kimi_linear import (LM, init_paged_pool,
                                            init_slot_state)

    pools, state = init_paged_pool(mc, 20, BS), init_slot_state(mc, 1)
    table = np.arange(16, dtype=np.int32) + 1
    x, pools, state = _prefill(mc, params, pools, state, 0, table,
                               toks[:BUCKET], 0)
    got = [LM._head(mc, params, x)]
    for t in range(BUCKET, len(toks)):
        logits, pools, _, state = _jitted("decode_step_paged")(
            params, pools, jnp.asarray(table[None]), jnp.asarray([toks[t]]),
            jnp.asarray([t]), mc, jnp.asarray([True]), state)
        got.append(logits)
    return jnp.concatenate(got)


def test_float32_state_through_the_serving_path(model):
    """`_served_logits` with the state as the file states it is inside
    the tolerance (so what `state_bf16` shows is the state's precision)."""
    R, mc, weights, params = model
    toks = _tokens(50)
    got = np.asarray(_served_logits(mc, params, toks))
    assert np.abs(got - _reference_logits(R, weights, toks, 0, 50)).max() \
        < TOL


# ------------------------- (h) the decode tick's two attention paths


def test_decode_step_agrees_on_both_attention_paths(monkeypatch):
    """`decode_step_paged` over a latent pool with history and slots
    with a state, one slot dead: the kernel's logits against the gather
    path's, the same greedy tokens, the same rows written at the same
    places, the dead slot's state kept on both."""
    from ray_tpu.ops import attention, paged_attention

    KL, c = _tiling()
    params = _drawn_at_a_tenth(KL, c, 0)
    rng = np.random.default_rng(5)
    B, nb, NB = 3, c.max_seq_len // KERNEL_BS, 30
    draw = lambda x: jnp.asarray(                             # noqa: E731
        rng.standard_normal(x.shape) * 0.5, x.dtype)
    pools = jax.tree.map(draw, KL.init_paged_pool(c, NB, KERNEL_BS))
    state = jax.tree.map(draw, KL.init_slot_state(c, B))
    tables = jnp.asarray(rng.permutation(NB)[:B * nb].reshape(B, nb),
                         jnp.int32)
    pos = jnp.asarray([37, 0, 90], jnp.int32)
    active = jnp.asarray([True, False, True])
    tok = jnp.asarray(rng.integers(0, c.vocab_size, B), jnp.int32)
    step = lambda: jax.jit(lambda: KL.decode_step_paged(      # noqa: E731
        params, pools, tables, tok, pos, c, active, state))()
    out = {}
    for path, force in (("gather", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert paged_attention.engages(pools["latent"]) == force
        assert KL._SERVING.paged_attention(pools) == path
        out[path] = step()
    live = np.asarray(active)
    a, b = (np.asarray(out[path][0], np.float32)[live]
            for path in ("kernel", "gather"))
    assert np.abs(a - b).max() <= 0.05 * np.abs(b).max()
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    wrote = {path: np.asarray(out[path][1]["latent"], np.float32)
             for path in out}
    before = np.asarray(pools["latent"], np.float32)
    for path in wrote:          # one row a live slot an MLA layer, no more
        changed = (wrote[path] != before).any(-1)
        assert changed.sum() == c.n_mla_layers * live.sum()
    np.testing.assert_allclose(wrote["kernel"], wrote["gather"], atol=0.05)
    for path in out:
        for leaf, x in out[path][3].items():
            np.testing.assert_array_equal(
                np.asarray(x[:, 1], np.float32),
                np.asarray(state[leaf][:, 1], np.float32))
