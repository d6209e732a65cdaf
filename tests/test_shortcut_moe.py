"""Two latent-attention sublayers and two dense feed-forwards a layer
with one expert layer on a shortcut across them, zero-compute experts in
a router wider than the routed experts (`models/shortcut_moe.py`,
`models/latent_moe.py::latent_attention` with a compressed, scaled
query, `models/moe.py::dropless_moe(n_zero=, share=)`), against the
plain float32 reference of
`benchmarks/reference/shortcut_moe_decoder.py` on seeded random weights
at a tiny size.  Logits are compared, never sampled tokens (but for the
engine test, which judges served tokens by their reference logits, as
the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-5 on logits of magnitude 3, float32 against float32 on the CPU:
  the program and the reference differ in the ORDER of float32 sums only
  (blocked attention, experts summed in blocks against sorted groups,
  absorbed against expanded weights, the zero picks' weights summed
  before or after they meet the token), which reads 3e-6 here.
  Weights are drawn at 0.1 (not 0.02) so that the expert layer and the
  two scales move the logits by far more than that: a model without the
  two low-rank scales reads 2.8, a router through bf16 3e-3, and
  `test_what_the_tolerance_catches` holds the tolerance to a hundredth
  of each.
* Layer-level sums (shares, all-zero picks) 2e-5 on results of
  magnitude 3 to 30, for the same reason.
* The engine test serves greedy tokens in float32; each served token's
  reference logit lies within 1e-4 of the reference maximum.
"""

import functools
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import latent_walk

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TOL = 1e-5
# this chip: rank 1 of 2 that share 8 routed experts; 4 zero-compute
C = dict(hidden_size=64, num_attention_heads=4, q_lora_rank=24,
         kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
         v_head_dim=16, ffn_hidden_size=128, expert_ffn_hidden_size=32,
         n_routed_experts=4, zero_expert_num=4, zero_expert_type="identity",
         moe_topk=3, routed_scaling_factor=6, mla_scale_q_lora=True,
         mla_scale_kv_lora=True, attention_method="MLA", vocab_size=512,
         num_layers=2, rms_norm_eps=1e-5, rope_theta=1e7,
         router_bias_scale=0.01, initializer_range=0.1,
         deployment=dict(n_routed_experts=8, rank=1))
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


def _build(c):
    from families import shortcut_moe_decoder as F
    from reference import shortcut_moe_decoder as R

    mc = F.model_config(c, max_seq_len=64, compute_dtype="float32",
                        param_dtype="float32")
    weights = R.init_weights(c, 11, jnp.float32)
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


@functools.cache
def _jitted(name):
    """A program function of `models/shortcut_moe.py` under `jax.jit`,
    its configuration static: one compile a shape for the whole module
    where op-by-op dispatch compiled every primitive of every layer."""
    from ray_tpu.models import shortcut_moe

    return jax.jit(getattr(shortcut_moe, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C):
    return np.asarray(R.logits_for_positions(weights, c, toks, start, n,
                                             pad_to=64))


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert (mc.n_held_experts, mc.expert_rank, mc.expert_shards,
            mc.router_width) == (4, 1, 2, 12)
    toks = _tokens(50)
    got = np.asarray(_jitted("forward")(params, jnp.asarray(toks)[None],
                                        mc)[0])
    want = _reference_logits(R, weights, toks, 0, 50)
    assert np.abs(want).max() > 1.0
    assert np.abs(got - want).max() < TOL


@pytest.mark.parametrize("what", ["no_scales", "router_bf16", "zero_dropped",
                                  "shortcut_early"])
def test_what_the_tolerance_catches(model, what, monkeypatch):
    """The comparison is tight enough: a reference without the two
    low-rank scales, a router whose logits pass through bf16, a layer
    that drops its zero-compute picks, and an expert layer whose result
    joins the stream BEFORE the second sublayer all differ from the
    program by a hundred tolerances."""
    from ray_tpu.models import shortcut_moe as M

    R, mc, weights, params = model
    c = C
    if what == "no_scales":
        c = dict(C, mla_scale_q_lora=False, mla_scale_kv_lora=False)
    elif what == "router_bf16":
        bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa
        real = M.dropless_moe
        monkeypatch.setattr(
            M, "dropless_moe", lambda x, p, routing, **kw: real(
                x, p, lambda lg, pp: routing(bf16(lg), pp), **kw))
    elif what == "zero_dropped":
        real = M.dropless_moe

        def without(x, p, routing, **kw):
            y, sizes = real(x, p, routing, **kw)
            idx, w = routing(x @ p["router"], p)
            wz = jnp.where(idx >= 8, w, 0.0).sum(-1)    # 8 routed columns
            return y - wz[:, None] * x, sizes

        monkeypatch.setattr(M, "dropless_moe", without)
    else:
        real = M.LM.latent_attention
        pending = []

        def early(cfg, l, p, x, *a):
            # the second sublayer's attention sees the stream with the
            # shortcut already added
            return real(cfg, l, p, x + pending.pop() if pending and l % 2
                        else x, *a)

        real_experts = M.shortcut_experts

        def experts(cfg, p, h, live=None):
            y, sizes = real_experts(cfg, p, h, live)
            pending.append(y)
            return jnp.zeros_like(y), sizes

        monkeypatch.setattr(M.LM, "latent_attention", early)
        monkeypatch.setattr(M, "shortcut_experts", experts)
    toks = _tokens(50)
    # jitted anew: what was taken away is away as it traces
    got = np.asarray(jax.jit(lambda p, t: M.forward(p, t, mc))(
        params, jnp.asarray(toks)[None])[0])
    want = _reference_logits(R, weights, toks, 0, 50, c)
    assert np.abs(got - want).max() > 100 * TOL


# ------------------------------------- (b) prefill + decode, paged cache

def _prefill(mc, params, pools, table, toks, start):
    """One bucket-padded chunk of `toks` at `start` into the blocks of
    `table`, as the engine's insert program does it."""
    S_pad = table.shape[0] * BS
    hist = {k: v[:, table].reshape((v.shape[0], S_pad) + v.shape[3:])
            for k, v in pools.items()}
    padded = np.zeros((BUCKET,), np.int32)
    padded[:len(toks)] = toks
    x, rows = _jitted("prefill_paged")(
        params, jnp.asarray(padded)[None], jnp.int32(start), hist, mc,
        jnp.int32(len(toks)))
    ids = table[start // BS: start // BS + BUCKET // BS]
    pools = {k: v.at[:, ids].set(rows[k].reshape(
        (v.shape[0], BUCKET // BS, BS) + v.shape[3:]))
        for k, v in pools.items()}
    return x[0, :len(toks)], pools


@pytest.mark.parametrize("case", ["one_bucket", "chunked"])
def test_paged_prefill_and_decode_match_reference(model, case):
    """Prefill (one bucket; two chunks, the second over the first's
    rows) and then 10 decode steps through the paged latent pool, TWO
    pool layers a layer: logits at every position against the
    reference's full forward, and the tick's counters."""
    from ray_tpu.models.shortcut_moe import LM, init_paged_pool

    R, mc, weights, params = model
    n_prompt = {"one_bucket": 13, "chunked": 27}[case]
    toks = _tokens(n_prompt + 10, seed=3)
    pools = init_paged_pool(mc, 40, BS)
    assert pools["latent"].shape == (4, 40, BS, mc.cache_row)
    table = np.arange(16, dtype=np.int32) + 5
    for start in range(0, n_prompt, BUCKET):
        x, pools = _prefill(mc, params, pools, table,
                            toks[start:min(start + BUCKET, n_prompt)], start)
        want = _reference_logits(R, weights, toks, start, x.shape[0])
        assert np.abs(np.asarray(LM._head(mc, params, x)) - want).max() < TOL
    # a second, dead slot rides along: it must change and count nothing
    tables = jnp.asarray(np.stack([table, np.zeros_like(table)]))
    want = _reference_logits(R, weights, toks, n_prompt, 10)
    for i in range(10):
        pos = n_prompt + i
        logits, pools, counts = _jitted("decode_step_paged")(
            params, pools, tables, jnp.asarray([toks[pos], 7]),
            jnp.asarray([pos, 0]), mc, active=jnp.asarray([True, False]))
        assert np.abs(np.asarray(logits[0]) - want[i]).max() < TOL
        # one live token: top_k picks in each of the two layers
        assert counts["expert_tokens"].shape == (2, 4)
        assert int(counts["zero_picks"]) + int(counts["real_picks"]) == 2 * 3
        assert int(counts["held_picks"]) == int(
            counts["expert_tokens"].sum()) <= int(counts["real_picks"])
        assert int(counts["experts_touched"]) == int(
            (np.asarray(counts["expert_tokens"]) > 0).sum())


@pytest.mark.parametrize("case", sorted(latent_walk.CASES))
def test_insert_walks_the_history_it_has(model, case, monkeypatch):
    """A whole insert (both sublayers of both layers, the query through
    its low rank, both low-rank paths scaled) by `_History`'s walk of
    the history up to `start + Pb` and by `attend_expanded` over all of
    the padded history: the normed hidden states of every query, the
    padded ones included, agree to 1e-5 and are finite.  In float32:
    between two bf16 forms a routing flip moves a hidden state by half
    its size; the walk's bf16 rounding is held to the plain form's
    where no router follows it, `tests/test_latent_moe.py::
    test_history_walk_equals_the_plain_form`."""
    from ray_tpu.models.shortcut_moe import prefill_paged

    _, mc, _, params = model
    assert latent_walk.insert_walk_error(
        monkeypatch, prefill_paged, mc, params, 2 * mc.n_layers, case) < TOL


# ----------------------------------- (c) the expert layer, piece by piece

def _layer_params(E, Z, held=None, D=16, F=8, seed=1, bias=None):
    """An expert layer's parameters: a router E + Z wide over `held`
    (default all E) experts' weights."""
    ks = jax.random.split(jax.random.key(seed), 5)
    p = {"router": jax.random.normal(ks[0], (D, E + Z)),
         "router_bias": jnp.zeros((E + Z,)) if bias is None else bias,
         "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.3,
         "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.3,
         "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.3}
    if held is not None:
        p.update({k: p[k][held] for k in ("w_gate", "w_up", "w_down")})
    return p


def _plain_layer(x, p, k, scale, E):
    """Softmax over the router's whole width, top k of score + bias,
    weights the scores x scale; a dense mask over experts, the columns
    past E identities."""
    s = jax.nn.softmax(x @ p["router"], -1)
    _, idx = jax.lax.top_k(s + p["router_bias"], k)
    T = x.shape[0]
    dense = jnp.zeros_like(s).at[jnp.arange(T)[:, None], idx].set(
        jnp.take_along_axis(s, idx, -1) * scale)
    each = jnp.einsum(
        "etf,efd->etd", jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"]))
        * jnp.einsum("td,edf->etf", x, p["w_up"]), p["w_down"])
    zero = dense[:, E:].sum(-1, keepdims=True) * x
    return jnp.einsum("etd,te->td", each, dense[:, :E]), zero, dense


def test_shares_and_the_zero_picks_once_add_up_to_the_whole_layer():
    """4 shares of 8 routed experts beside 4 zero-compute ones: each
    share's result holds its two experts' part AND the zero picks' part
    (computed where the token is); the four results less three of the
    zero parts are the uncut layer, and the counts are each share's
    own beside the same zero picks."""
    from ray_tpu.models.moe import dropless_moe, softmax_bias_top_k

    E, Z, k, T = 8, 4, 5, 40
    p = _layer_params(E, Z, bias=jax.random.normal(
        jax.random.key(7), (E + Z,)) * 0.05)
    x = jax.random.normal(jax.random.key(2), (T, 16))
    routed, zero, dense = _plain_layer(x, p, k, 6.0, E)
    assert float(jnp.abs(zero).max()) > 1.0 and float(
        jnp.abs(routed).max()) > 1.0
    total, picks = 0.0, np.asarray((dense > 0).sum(0))
    for r in range(4):
        held = slice(2 * r, 2 * r + 2)
        y, sizes = dropless_moe(
            x, _layer_params(E, Z, held=held, bias=p["router_bias"]),
            softmax_bias_top_k(k, 6.0), share=(r, 4), n_zero=Z)
        assert sizes.tolist() == picks[held].tolist() + [picks[E:].sum()]
        total = total + y
    assert float(jnp.abs(total - 3 * zero - (routed + zero)).max()) < 2e-5
    # all experts held: the same layer in one piece
    y, sizes = dropless_moe(x, p, softmax_bias_top_k(k, 6.0), n_zero=Z)
    assert float(jnp.abs(y - (routed + zero)).max()) < 2e-5
    assert int(sizes.sum()) == T * k


@pytest.mark.parametrize("rows", ["all", "live"])
def test_all_zero_picks_touch_no_expert(rows):
    """A selection bias that sends every pick of every token to
    zero-compute experts: y = (sum of the picks' weights) x the token,
    no expert has a row, and the zero picks are counted (the live
    tokens' only)."""
    from ray_tpu.models.moe import dropless_moe, softmax_bias_top_k

    E, Z, k, T = 4, 6, 3, 20
    p = _layer_params(E, Z, bias=jnp.zeros((E + Z,)).at[E:].set(10.0))
    x = jax.random.normal(jax.random.key(3), (T, 16))
    live = None if rows == "all" else jnp.arange(T) % 3 != 1
    y, sizes = dropless_moe(x, p, softmax_bias_top_k(k, 6.0), live=live,
                            n_zero=Z)
    s = jax.nn.softmax(x @ p["router"], -1)
    w = 6.0 * jax.lax.top_k(s[:, E:], k)[0].sum(-1, keepdims=True)
    want = w * x if live is None else jnp.where(live[:, None], w * x, 0.0)
    assert float(jnp.abs(want).max()) > 0.5
    assert float(jnp.abs(y - want).max()) < 2e-5
    n_live = T if live is None else int(live.sum())
    assert sizes.tolist() == [0] * E + [n_live * k]


@pytest.mark.parametrize("width", [11, 13])
def test_a_router_of_another_width_is_refused_by_name(width):
    """8 held experts x 1 share + 4 zero-compute ones is 12 columns: a
    router one narrower or wider is refused."""
    from ray_tpu.models.moe import dropless_moe, softmax_bias_top_k

    p = _layer_params(8, 4)
    p["router"] = jnp.zeros((16, width))
    p["router_bias"] = jnp.zeros((width,))
    with pytest.raises(ValueError, match=f"router {width} wide"):
        dropless_moe(jnp.zeros((3, 16)), p, softmax_bias_top_k(2),
                     n_zero=4)


@pytest.mark.parametrize("rank", [0, 5, 31])
def test_share_of_a_768_wide_router_holds_its_sixteen_of_the_512(rank):
    """The published router: 512 routed + 256 zero-compute columns,
    32 shares of 16.  A bias that sends every token's twelve picks to
    routed experts [16 r, 16 r + 12): share r counts them all, its
    neighbour none, and the share is taken of the 512 (of the router's
    768 the range would be [24 r, 24 r + 24))."""
    from ray_tpu.models.moe import dropless_moe, softmax_bias_top_k

    T, k = 6, 12
    bias = jnp.zeros((768,)).at[16 * rank:16 * rank + k].set(10.0)
    p = _layer_params(512, 256, held=slice(0, 16), D=8, F=4, bias=bias)
    x = jax.random.normal(jax.random.key(4), (T, 8))
    for r, want in ((rank, [T] * k + [0] * 4), ((rank + 1) % 32, [0] * 16)):
        _, sizes = dropless_moe(x, p, softmax_bias_top_k(k, 6.0),
                                share=(r, 32), n_zero=256)
        assert sizes.tolist() == want + [0]


@pytest.mark.parametrize("rows", ["all", "live"])
def test_zero_compute_layer_agrees_on_both_grouped_paths(rows, monkeypatch):
    """An expert layer of this model at widths that tile (bf16, 128 ->
    128, 4 of 8 routed experts held, 4 zero-compute, top 3):
    `ops.grouped_matmul` through the Pallas interpreter against
    `lax.ragged_dot`, with rows that vary from token to token.  The same
    counts to the row; outputs to 2 ulp of bf16 at their size."""
    from ray_tpu.models import moe, shortcut_moe as M
    from ray_tpu.ops import attention

    c = M.ShortcutMoEConfig.tiny(dim=128, expert_hidden_dim=128,
                                 expert_rank=1, expert_shards=2)
    p = M.init_params(c, jax.random.key(5), bias_scale=0.01)["layers"][1]
    x = jax.random.normal(jax.random.key(6), (40, 128), c.dtype)
    live = None if rows == "all" else jnp.arange(40) % 3 != 1
    out = {}
    for path, force in (("xla", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert M._SERVING.grouped_matmul(c, 20) == path
        out[path] = moe.dropless_moe(
            x, p["moe"], moe.softmax_bias_top_k(c.top_k, 6.0), live=live,
            share=(1, 2), n_zero=c.n_zero_experts)
    (yx, sx), (yk, sk) = out["xla"], out["kernel"]
    assert sx.tolist() == sk.tolist()
    n_live = 40 if live is None else int(live.sum())
    assert 0 < int(sx[:-1].sum()) < n_live * 3 and int(sx[-1]) > 0
    yx, yk = np.asarray(yx, np.float32), np.asarray(yk, np.float32)
    scale = np.abs(yx).max()
    assert scale > 1e-3 and np.abs(yx - yk).max() <= 2 ** -7 * scale
    if live is not None:
        assert not yk[~np.asarray(live)].any()


# ------------------------------------------------------- (d) the engine

def test_engine_serves_chunked_prompts_and_counts_its_picks(model):
    """Through `LLMEngine` by `config.serving()` alone, prefix cache on
    (a `full`-kind pool and no state by slot: nothing is refused): ten
    prompts, one in two chunks, through a pool that evicts; every served
    token's reference logit lies at the reference's maximum, and the
    device's counters add up."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    R, mc, weights, params = model
    engine = LLMEngine(params, mc, EngineConfig(
        num_slots=2, max_seq_len=64, prefill_buckets=(BUCKET,),
        kv_layout="paged", kv_block_size=BS, num_kv_blocks=20))
    assert engine._model is mc.serving()
    prompts = [_tokens(12 + (i % 4), seed=20 + i) for i in range(9)]
    prompts.insert(3, _tokens(27, seed=40))           # two chunks
    hs = [engine.submit(Request(prompt=p, max_tokens=6,
                                chunked_prefill=len(p) > BUCKET))
          for p in prompts]
    engine.drain()
    assert all(h.finish_reason == "length" for h in hs)
    st = engine.stats()
    assert st["prefix_cache"]["evictions"] > 0
    deficits = np.concatenate([
        R.served_token_deficits(weights, C, p, list(h.tokens))
        for p, h in zip(prompts, hs)])
    assert deficits.size == 10 * 6
    assert deficits.mean() < 1e-4, deficits.max()
    ctr = st["counters"]
    decoded = sum(len(h.tokens) - 1 for h in hs)
    assert ctr["expert_tokens"].shape == (2, 4)
    assert int(ctr["zero_picks"]) + int(ctr["real_picks"]) \
        == decoded * mc.top_k * mc.n_layers
    assert int(ctr["held_picks"]) == int(ctr["expert_tokens"].sum())
    assert 0 < int(ctr["zero_picks"]) and 0 < int(ctr["held_picks"]) \
        < int(ctr["real_picks"])
    # the walk of the held picks (`moe.walk_counts`): two slots x top 3
    # are under a row tile, so a pass is all six rows and never a second
    assert int(ctr["moe_rows_dense"]) \
        == int(ctr["ticks"]) * 2 * mc.top_k * mc.n_layers
    assert 0 < int(ctr["moe_rows_walked"]) <= int(ctr["moe_rows_dense"])
    assert int(ctr["moe_extra_passes"]) == 0
    assert set(ctr) == {"expert_tokens", "experts_touched", "ticks",
                        "zero_picks", "real_picks", "held_picks",
                        "moe_rows_walked", "moe_rows_dense",
                        "moe_extra_passes"}
