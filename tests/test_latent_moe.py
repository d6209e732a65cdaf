"""Latent attention over a paged latent cache and dropless routed experts
(`models/latent_moe.py`, `models/moe.py::dropless_moe`) against the plain
float32 reference of `benchmarks/reference/latent_moe_decoder.py`, on
seeded random weights at a tiny size.  Logits are compared, never sampled
tokens (but for the engine test, which judges served tokens by their
reference logits, as the benchmark does).

Tolerances and their reasons
----------------------------
* 3e-6 on logits of magnitude 0.7, float32 against float32 on the CPU:
  the program and the reference differ in the ORDER of float32 sums only
  (blocked attention, experts summed in blocks against sorted groups,
  absorbed against expanded weights), which reads 1e-7 to 5e-7 here.
  Router logits rounded to bf16 read 1.7e-5 at this size and softmax
  scores rounded to bf16 8.9e-6 (weights of 0.02 make flat scores):
  `test_lower_precision_is_caught` holds the tolerance to half of both.
* The engine test serves greedy tokens in float32; each served token's
  reference logit lies within 1e-4 of the reference maximum (0 unless
  two logits tie to within the sums' reordering).
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

TOL = 3e-6
C = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
         intermediate_size=128, moe_intermediate_size=32,
         n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
         routed_scaling_factor=2.448, norm_topk_prob=True, vocab_size=512,
         num_hidden_layers=3, first_k_dense_replace=1, rms_norm_eps=1e-6,
         rope_theta=1e6, router_bias_scale=0.1, initializer_range=0.02)
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


@pytest.fixture(scope="module")
def model():
    from families import latent_moe_decoder as F
    from reference import latent_moe_decoder as R

    mc = F.model_config(C, max_seq_len=64, compute_dtype="float32",
                        param_dtype="float32")
    weights = R.init_weights(C, 11, jnp.float32)
    return R, mc, weights, F.program_params(weights)


@functools.cache
def _jitted(name):
    """A program function of `models/latent_moe.py` under `jax.jit`, its
    configuration static: one compile a shape for the whole module where
    op-by-op dispatch compiled every primitive of every layer."""
    from ray_tpu.models import latent_moe

    return jax.jit(getattr(latent_moe, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n):
    return np.asarray(R.logits_for_positions(weights, C, toks, start, n,
                                             pad_to=64))


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    toks = _tokens(50)
    got = np.asarray(_jitted("forward")(params, jnp.asarray(toks)[None],
                                        mc)[0])
    want = _reference_logits(R, weights, toks, 0, 50)
    assert np.abs(want).max() > 0.3
    assert np.abs(got - want).max() < TOL


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


@pytest.mark.parametrize("case", ["one_bucket", "chunked", "prefix_hit"])
def test_paged_prefill_and_decode_match_reference(model, case):
    """Prefill (one bucket; two chunks, the second over the first's
    history; a suffix over ANOTHER sequence's cached blocks) and then 10
    decode steps through the paged latent pool: logits at every position
    against the reference's full forward."""
    from ray_tpu.models.latent_moe import _head, init_paged_pool

    R, mc, weights, params = model
    n_prompt = {"one_bucket": 13, "chunked": 27, "prefix_hit": 24}[case]
    toks = _tokens(n_prompt + 10, seed=3)
    pools = init_paged_pool(mc, 40, BS)
    table = np.arange(16, dtype=np.int32) + 5
    hidden = []
    if case == "prefix_hit":
        # another sequence left the first 16 rows in blocks 1..4
        other = np.arange(16, dtype=np.int32) + 1
        _, pools = _prefill(mc, params, pools, other, toks[:16], 0)
        table[:4] = other[:4]
        first = 16
    else:
        first = 0
    for start in range(first, n_prompt, BUCKET):
        x, pools = _prefill(mc, params, pools, table,
                            toks[start:min(start + BUCKET, n_prompt)], start)
        hidden.append((start, x))
    for start, x in hidden:
        want = _reference_logits(R, weights, toks, start, x.shape[0])
        assert np.abs(np.asarray(_head(mc, params, x)) - want).max() < TOL
    # a second, dead slot rides along: it must change nothing
    tables = jnp.asarray(np.stack([table, np.zeros_like(table)]))
    want = _reference_logits(R, weights, toks, n_prompt, 10)
    for i in range(10):
        pos = n_prompt + i
        logits, pools, counts = _jitted("decode_step_paged")(
            params, pools, tables, jnp.asarray([toks[pos], 7]),
            jnp.asarray([pos, 0]), mc, active=jnp.asarray([True, False]))
        assert np.abs(np.asarray(logits[0]) - want[i]).max() < TOL
        # one live token: top_k experts in each of the two expert layers
        assert counts["expert_tokens"].shape == (2, 8)
        assert int(counts["expert_tokens"].sum()) == 2 * 2
        assert int(counts["experts_touched"]) == 2 * 2


# ---------------------------------------------- (c) two forms, one function

def test_absorbed_attention_equals_expanded(model):
    from ray_tpu.models.latent_moe import attend_absorbed, attend_expanded

    _, mc, _, params = model
    ks = jax.random.split(jax.random.key(5), 3)
    B, Q, K = 2, 3, 20
    q_nope = jax.random.normal(ks[0], (B, Q, 4, 16))
    q_rope = jax.random.normal(ks[1], (B, Q, 4, 8))
    rows = jax.random.normal(ks[2], (B, K, mc.cache_row))
    qpos = jnp.asarray([[5, 6, 7], [17, 18, 19]])
    wkv_b = params["layers"][1]["wkv_b"] * 10.0
    a = attend_absorbed(mc, wkv_b, q_nope, q_rope, rows, qpos)
    b = attend_expanded(mc, wkv_b, q_nope, q_rope, rows, qpos)
    assert float(jnp.abs(b).max()) > 0.5
    assert float(jnp.abs(a - b).max()) < TOL


# ----------------------------- (c2) the insert walks the history it has

def _walk_and_plain(mc, S_pad, start, Q, dtype, n_real):
    """`_History.attend` and `attend_expanded` over the same updated
    history, whose rows past `start + Q` are loud and stale; inputs are
    bf16 numbers in either dtype, so float32 is bf16's truth."""
    from ray_tpu.models import latent_moe as LM

    def drawn(key, *shape, scale=1.0):
        x = jax.random.normal(key, shape) * scale
        return x.astype(jnp.bfloat16).astype(dtype)

    ks = jax.random.split(jax.random.key(S_pad + start), 5)
    H, n, v = mc.n_heads, mc.qk_nope_head_dim, mc.v_head_dim
    q_nope = drawn(ks[0], 1, Q, H, n)
    q_rope = drawn(ks[1], 1, Q, H, mc.qk_rope_head_dim)
    hist = drawn(ks[2], 1, S_pad, mc.cache_row, scale=3.0)
    new = drawn(ks[3], 1, Q, mc.cache_row)
    new = new.at[:, n_real:].set(0)
    wkv_b = drawn(ks[4], mc.kv_lora_rank, H * (n + v), scale=0.1)

    @jax.jit
    def both(start):        # traced, as the insert program has it
        qpos = (start + jnp.arange(Q))[None]
        cache = LM._History(hist, start)
        rows = cache.update(0, new)
        return (cache.attend(mc, wkv_b, q_nope, q_rope, rows, qpos),
                LM.attend_expanded(mc, wkv_b, q_nope, q_rope, rows, qpos))

    return [np.asarray(x, np.float32) for x in both(jnp.int32(start))]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(latent_walk.CASES))
def test_history_walk_equals_the_plain_form(model, case, dtype):
    """The insert's attention over tiles of the history up to `start +
    Q` against `attend_expanded` over all of it.  float32: 1e-5 of the
    output's size (the order of float32 sums differs, as between any
    two blockings).  bf16: both forms round their probabilities to bf16
    at different scales (the walk's relative to the running maximum,
    the plain form's normalised), so they are held to the float32 plain
    form: the walk is no further from it than the plain bf16 form is,
    with a quarter of room.  Every output is finite, the padded
    queries' too."""
    _, mc, _, _ = model
    S_pad, start, Q, n_real = latent_walk.geometry(case)
    walk, truth = _walk_and_plain(mc, S_pad, start, Q, jnp.float32, n_real)
    size = np.abs(truth).max()
    assert size > 0.1
    if dtype == "float32":
        assert np.abs(walk - truth).max() < 1e-5 * size
    else:
        walk, plain = _walk_and_plain(mc, S_pad, start, Q, jnp.bfloat16,
                                      n_real)
        assert np.abs(walk - truth).max() \
            < 1.25 * np.abs(plain - truth).max() + 1e-3 * size
    assert np.isfinite(walk).all()


# --------------------------------------------------- (d) nothing is dropped

def test_routing_drops_nothing_under_total_imbalance(model):
    """A selection bias that sends EVERY token to experts 0 and 1 (a
    capacity-dispatch layer at any capacity factor under E / k = 4 would
    drop most of them): the model still equals the reference."""
    from ray_tpu.models.moe import dropless_moe, sigmoid_bias_top_k

    R, mc, weights, params = model
    bias = jnp.zeros((8,)).at[:2].set(10.0)

    def skew(tree):
        return dict(tree, layers=[
            dict(w, router_bias=bias) if "router_bias" in w else w
            for w in tree["layers"]])

    toks = _tokens(40, seed=9)
    got = np.asarray(_jitted("forward")(
        skew(params), jnp.asarray(toks)[None], mc)[0])
    want = _reference_logits(R, skew(weights), toks, 0, 40)
    assert np.abs(got - want).max() < TOL
    x = jax.random.normal(jax.random.key(2), (40, 64))
    _, sizes = dropless_moe(x, skew(params)["layers"][1],
                            sigmoid_bias_top_k(2, 2.448))
    assert sizes.tolist() == [40, 40, 0, 0, 0, 0, 0, 0]
    # rows taken out are in no group and get nothing
    live = jnp.arange(40) < 25
    y, sizes = dropless_moe(x, skew(params)["layers"][1],
                            sigmoid_bias_top_k(2, 2.448), live=live)
    assert sizes.tolist() == [25, 25, 0, 0, 0, 0, 0, 0]
    assert float(jnp.abs(y[25:]).max()) == 0.0


@pytest.mark.parametrize("what", ["router", "softmax"])
def test_lower_precision_is_caught(model, what, monkeypatch):
    """The tolerance is tight enough: a router whose logits, or a
    softmax whose scores, pass through bf16 fails it."""
    from ray_tpu.models import latent_moe

    R, mc, weights, params = model
    bf16 = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if what == "router":
        real = latent_moe.dropless_moe
        monkeypatch.setattr(
            latent_moe, "dropless_moe",
            lambda x, p, routing, **kw: real(
                x, p, lambda lg, pp: routing(bf16(lg), pp), **kw))
    else:
        real = latent_moe._masked_softmax
        monkeypatch.setattr(
            latent_moe, "_masked_softmax",
            lambda s, qpos, n, dt: real(bf16(s), qpos, n, dt))
    toks = _tokens(50)
    # jitted anew: the rounding is there as it traces
    got = np.asarray(jax.jit(lambda p, t: latent_moe.forward(p, t, mc))(
        params, jnp.asarray(toks)[None])[0])
    want = _reference_logits(R, weights, toks, 0, 50)
    assert np.abs(got - want).max() > 2 * TOL


def test_softmax_top_k_routing_is_dropless_too():
    """The layer with the other routing rule, against a plain masked sum
    over experts."""
    from ray_tpu.models.moe import dropless_moe, softmax_top_k

    ks = jax.random.split(jax.random.key(1), 5)
    T, D, F, E, k = 30, 16, 8, 4, 2
    p = {"router": jax.random.normal(ks[0], (D, E)),
         "w_gate": jax.random.normal(ks[1], (E, D, F)) * 0.3,
         "w_up": jax.random.normal(ks[2], (E, D, F)) * 0.3,
         "w_down": jax.random.normal(ks[3], (E, F, D)) * 0.3}
    x = jax.random.normal(ks[4], (T, D))
    y, sizes = dropless_moe(x, p, softmax_top_k(k))
    probs = jax.nn.softmax(x @ p["router"], -1)
    w, idx = jax.lax.top_k(probs, k)
    dense = jnp.zeros((T, E)).at[jnp.arange(T)[:, None], idx].set(w)
    each = jnp.einsum(
        "etf,efd->etd", jax.nn.silu(jnp.einsum("td,edf->etf", x, p["w_gate"]))
        * jnp.einsum("td,edf->etf", x, p["w_up"]), p["w_down"])
    want = jnp.einsum("etd,te->td", each, dense)
    assert int(sizes.sum()) == T * k
    assert float(jnp.abs(y - want).max()) < TOL


@pytest.mark.parametrize("rows", ["all", "live"])
def test_dropless_moe_agrees_on_both_grouped_paths(rows, monkeypatch):
    """An expert layer of this model at widths that tile (bf16, 128 ->
    128, 8 experts, top 2): `ops.grouped_matmul` through the Pallas
    interpreter against `lax.ragged_dot`.  The same counts to the row;
    outputs to 2 ulp of bf16 at their size (both accumulate in float32
    and round once; XLA:CPU sums K in another order)."""
    from ray_tpu.models import latent_moe as LM, moe
    from ray_tpu.ops import attention

    c = LM.LatentMoEConfig.tiny(dim=128, expert_hidden_dim=128)
    p = LM.init_params(c, jax.random.key(5))["layers"][1]
    x = jax.random.normal(jax.random.key(6), (40, 128), c.dtype)
    live = None if rows == "all" else jnp.arange(40) % 3 != 1
    routing = moe.sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor)
    out = {}
    for path, force in (("xla", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert LM._SERVING.grouped_matmul(c, 20) == path
        out[path] = moe.dropless_moe(x, p, routing, live=live)
    (yx, sx), (yk, sk) = out["xla"], out["kernel"]
    assert sx.tolist() == sk.tolist()
    assert int(sx.sum()) == (80 if live is None else 2 * int(live.sum()))
    yx, yk = np.asarray(yx, np.float32), np.asarray(yk, np.float32)
    scale = np.abs(yx).max()
    assert scale > 1e-3 and np.abs(yx - yk).max() <= 2 ** -7 * scale
    if live is not None:
        assert not yk[~np.asarray(live)].any()


# ------------------------------------------------------- (e) the engine

@pytest.fixture(scope="module")
def engine(model):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    _, mc, _, params = model
    return LLMEngine(params, mc, EngineConfig(
        num_slots=2, max_seq_len=64, prefill_buckets=(BUCKET,),
        kv_layout="paged", kv_block_size=BS, num_kv_blocks=20,
        # promote whenever a tier holds the blocks: the path under test
        kv_adopt_cost_fixed_ms=0.0, kv_adopt_cost_per_block_ms=0.0,
        kv_prefill_cost_per_token_ms=1.0))


def test_engine_serves_through_eviction_spill_and_promotion(model, engine):
    """Ten distinct prompts (one chunked) through a pool of 20 blocks:
    admissions evict and spill latent blocks; the first prompts come
    back and are promoted from the host tier.  Every served token's
    reference logit lies at the reference's maximum."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, _ = model
    prompts = [_tokens(12 + (i % 4), seed=20 + i) for i in range(9)]
    prompts.insert(3, _tokens(27, seed=40))           # two chunks
    served = []
    for batch in (prompts, prompts[:3]):
        hs = [engine.submit(Request(prompt=p, max_tokens=6,
                                    chunked_prefill=len(p) > BUCKET))
              for p in batch]
        engine.drain()
        assert all(h.finish_reason == "length" for h in hs)
        served += [(p, list(h.tokens)) for p, h in zip(batch, hs)]
    st = engine.stats()
    assert st["prefix_cache"]["evictions"] > 0
    assert st["kv_tiers"]["host"]["spills"] > 0
    assert st["kv_tiers"]["promoted_blocks"] > 0
    # 16 blocks a slot: `export_rows` is the one length, so the spill's
    # gather is one trace whatever it evicted
    assert engine.config.export_rows == (16,)
    assert st["traces"] == {"tick": 1, "insert": 1, "export": 1, "adopt": 1}
    assert st["kv_tiers"]["spill_lands"] > 0 and not engine._pending_spills
    deficits = np.concatenate([
        R.served_token_deficits(weights, C, p, t) for p, t in served])
    assert deficits.size == 13 * 6
    assert deficits.mean() < 1e-4, deficits.max()
    # the device's counters, read once: every decode token of a live
    # slot went to top_k experts in each of the two expert layers
    ctr = st["counters"]
    decoded = sum(len(t) - 1 for _, t in served)
    assert ctr["expert_tokens"].shape == (2, 8)
    assert int(ctr["expert_tokens"].sum()) == decoded * 2 * 2
    assert 0 < int(ctr["experts_touched"]) <= int(ctr["ticks"]) * 2 * 4
    assert int(ctr["ticks"]) >= 5 * 7


def test_engine_counts_the_keys_its_inserts_walk(model):
    """One short prompt and one prompt in three pieces through an
    engine whose rows are four tiles long: `stats()` sums, over the
    inserts, history + bucket rounded up to the tile beside the padded
    history's rows.  The third piece's keys cross a tile edge in the
    engine's own program, and every served token's reference logit
    still lies at the reference's maximum."""
    from ray_tpu.models.serving import HISTORY_TILE as T
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    R, mc, weights, params = model
    S, top = 4 * T, T // 2
    engine = LLMEngine(params, mc, EngineConfig(
        num_slots=2, max_seq_len=S, prefill_buckets=(BUCKET, top // 2, top),
        kv_layout="paged", kv_block_size=BS, num_kv_blocks=2 * S // BS,
        prefix_cache=False))
    assert engine.stats()["insert_keys_walked"] == 0
    prompts = [_tokens(13, seed=50), _tokens(2 * top + 44, seed=51)]
    hs = [engine.submit(Request(prompt=p, max_tokens=4,
                                chunked_prefill=len(p) > top))
          for p in prompts]
    engine.drain()
    st = engine.stats()
    # 13 in the 16 bucket; [0, top), [top, 2 top), then 44 in top / 2
    ends = [BUCKET, top, 2 * top, 2 * top + top // 2]
    assert st["insert_keys_walked"] \
        == sum(-(-e // T) * T for e in ends) == 5 * T
    assert st["insert_keys_padded"] == len(ends) * S \
        >= st["insert_keys_walked"]
    assert st["traces"]["insert"] == 3         # a program a bucket
    # no tiles to count: this model's inserts attend by one form
    assert (st["insert_attention"], st["insert_attn_tiles_dense"]) \
        == ("plain", 0)
    deficits = np.concatenate([
        R.served_token_deficits(weights, C, p, list(h.tokens))
        for p, h in zip(prompts, hs)])
    assert deficits.size == 8 and deficits.mean() < 1e-4, deficits.max()


def test_engine_exports_and_adopts_latent_blocks(model, engine):
    """Disaggregated prefill on the latent pool: the exported state has
    the model's one leaf, a row of latent ‖ rotary key, and another
    request adopts it."""
    from ray_tpu.serve.llm.engine import Request

    R, _, weights, _ = model
    p = _tokens(14, seed=77)
    h = engine.submit(Request(prompt=p, max_tokens=5, prefill_only=True))
    engine.drain()
    state = h.kv_state
    assert sorted(state.blocks) == ["latent"]
    assert state.blocks["latent"].shape == (3, 4, BS, 128)   # 32 + 8, padded
    assert state.payload_bytes == 3 * 4 * BS * 128 * 4
    h2 = engine.submit_adopted(Request(prompt=p, max_tokens=5), state)
    engine.drain()
    assert h2.finish_reason == "length" and len(h2.tokens) == 5
    assert R.served_token_deficits(weights, C, p, h2.tokens).max() < 1e-4


@pytest.mark.parametrize("what, refusal", [
    ("dense_layout", "removed at PR 28"),       # for every model
    ("speculation", "latent attention"),
    ("int8", "latent attention")])
def test_unsupported_paths_refuse_by_name(model, what, refusal):
    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    _, mc, _, params = model
    ec = dict(num_slots=2, max_seq_len=64, prefill_buckets=(BUCKET,),
              kv_block_size=BS)
    with pytest.raises(ValueError, match=refusal):
        if what == "dense_layout":
            LLMEngine(params, mc, EngineConfig(kv_layout="dense", **ec))
        elif what == "speculation":
            dc = LlamaConfig.tiny(vocab_size=512)
            LLMEngine(params, mc, EngineConfig(**ec),
                      draft_params=init_params(dc, jax.random.key(0)),
                      draft_config=dc)
        else:
            from ray_tpu.serve.llm.deployment import LLMServer

            cls = getattr(LLMServer, "func_or_class", LLMServer)
            cls(model_config=mc, engine_config=EngineConfig(**ec),
                quantize="int8")


# ------------------------- (f) the decode tick's two attention paths

KERNEL_BS = 16    # rows a block: a whole packed tile, so the kernel engages


def _tiling():
    """bf16, a latent of one lane tile in a row of two (128 ‖ 8 ‖ zeros
    to 256): the shapes `ops.paged_attention.engages` asks for."""
    from ray_tpu.models import latent_moe as LM

    c = LM.LatentMoEConfig.tiny(kv_lora_rank=128)
    assert c.cache_row == 256 and c.dtype == jnp.bfloat16
    return LM, c


def _drawn_at_a_tenth(LM, c, seed):
    """Matrices at 0.1, not `init_params`' 0.02: at 0.02 the tiny
    model's best two logits lie closer than bf16 rounding moves them
    and greedy tokens say nothing about the path."""
    return jax.tree.map(lambda x: 5 * x if x.ndim >= 2 else x,
                        LM.init_params(c, jax.random.key(seed)))


def test_decode_step_agrees_on_both_attention_paths(monkeypatch):
    """`decode_step_paged` over a pool with history, one slot dead: the
    kernel's logits against the gather path's, the same greedy tokens,
    the same rows written at the same places."""
    from ray_tpu.ops import attention, paged_attention

    LM, c = _tiling()
    params = _drawn_at_a_tenth(LM, c, 0)
    rng = np.random.default_rng(5)
    B, nb, NB = 3, c.max_seq_len // KERNEL_BS, 30
    pools = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape) * 0.5, x.dtype),
        LM.init_paged_pool(c, NB, KERNEL_BS))
    tables = jnp.asarray(rng.permutation(NB)[:B * nb].reshape(B, nb),
                         jnp.int32)
    pos = jnp.asarray([37, 0, 90], jnp.int32)
    active = jnp.asarray([True, False, True])
    tok = jnp.asarray(rng.integers(0, c.vocab_size, B), jnp.int32)
    step = lambda: jax.jit(lambda: LM.decode_step_paged(      # noqa: E731
        params, pools, tables, tok, pos, c, active))()
    out = {}
    for path, force in (("gather", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert paged_attention.engages(pools["latent"]) == force
        assert LM._SERVING.paged_attention(pools) == path
        out[path] = step()
    live = np.asarray(active)
    a, b = (np.asarray(out[path][0], np.float32)[live]
            for path in ("kernel", "gather"))
    assert np.abs(a - b).max() <= 0.05 * np.abs(b).max()
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    wrote = {path: np.asarray(out[path][1]["latent"], np.float32)
             for path in out}
    before = np.asarray(pools["latent"], np.float32)
    for path in wrote:              # one row a live slot a layer, no more
        changed = (wrote[path] != before).any(-1)
        assert changed.sum() == c.n_layers * live.sum()
    # layer 0 writes the same rows on both paths; deeper layers' rows
    # carry the attention's rounding
    np.testing.assert_array_equal(wrote["kernel"][0], wrote["gather"][0])
    np.testing.assert_allclose(wrote["kernel"], wrote["gather"], atol=0.05)
    assert out["kernel"][2]["expert_tokens"].sum() \
        == out["gather"][2]["expert_tokens"].sum()


@pytest.mark.parametrize("seed", [0, 1])
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

    LM, c = _tiling()
    params = _drawn_at_a_tenth(LM, c, seed)
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
