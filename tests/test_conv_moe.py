"""Gated short-convolution layers with a two-row tail by slot beside
grouped-query attention over a paged K ‖ V pool, and a whole bank of
routed experts (`models/conv_moe.py`, `ops/short_conv.py`,
`ops/paged_attention.py` with `v_pool=None`, `serve/llm/engine.py`),
against the plain float32 reference of
`benchmarks/reference/conv_moe_decoder.py` on seeded random weights at a
tiny size.  Logits are compared, never sampled tokens (but for the
engine tests, which judge served tokens by their reference logits, as
the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit, about 3 here) on
  logits, float32 against float32 on the CPU: the program and the
  reference differ in the ORDER of float32 sums (sorted expert groups
  against blocks of experts, grouped against repeated KV heads, the
  `[V, D]` table contracted as it lies against its transpose); that
  reads 1e-6 relative.  Int8-rounded matrices read 0.2 (a rounded
  router picks other experts): `test_lower_precision_is_caught` holds
  the tolerance to half of it and more.
* bf16 program against the float32 reference on the same bf16-rounded
  weights: the MEAN deviation under 0.08 on logits of magnitude 3.3
  (read 0.014-0.037 over four seeds).  bf16 keeps 8 bits: a hidden
  state of size 1 carries 4e-3 of rounding an operation, and 8 layers
  and the head compound to about 1e-2; a token whose expert choice
  flips under that rounding moves by 0.5-1.0, so the MAX is not held
  (read 0.54-1.0) and the mean is what the flips leave of it.
* The weights are drawn at 0.1, not the 0.02 of the published widths:
  at hidden 64 a 0.02 draw leaves the layers' outputs far under the
  embedding in the residual stream, the TIED head then scores the
  input token itself highest by a wide margin, and every greedy token
  repeats its input whatever the layers do (a test of nothing).
* The paged kernel against the gather path at heads of 64: 2 ulp of a
  bf16 output of size 1 (2 ** -6), as `tests/test_paged_attention.py`
  argues for heads of 128.
* The engine tests serve greedy tokens in float32; each served token's
  reference logit lies within 1e-4 of the reference maximum.
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
# two dense conv layers, then attention conv conv conv attention conv
C = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
         head_dim=16, conv_L_cache=3, conv_bias=False,
         intermediate_size=128, moe_intermediate_size=32, num_experts=8,
         num_experts_per_tok=2, num_dense_layers=2, num_hidden_layers=8,
         layer_types=["conv", "conv", "full_attention", "conv", "conv",
                      "conv", "full_attention", "conv", "conv", "conv"],
         norm_eps=1e-5, norm_topk_prob=True, use_expert_bias=True,
         rope_theta=1000000, routed_scaling_factor=1, vocab_size=512,
         router_bias_scale=0.02, initializer_range=0.1)
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


def _build(c, dtype="float32", **overrides):
    from families import conv_moe_decoder as F
    from reference import conv_moe_decoder as R

    mc = F.model_config(c, max_seq_len=64, compute_dtype=dtype,
                        param_dtype=dtype, **overrides)
    weights = R.init_weights(c, 11, getattr(jnp, dtype))
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


@functools.cache
def _jitted(name):
    """A program function of `models/conv_moe.py` under `jax.jit`, its
    configuration static: one compile a shape for the whole module where
    op-by-op dispatch compiled every primitive of every layer."""
    from ray_tpu.models import conv_moe

    return jax.jit(getattr(conv_moe, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C):
    return np.asarray(R.logits_for_positions(weights, c, toks, start, n,
                                             pad_to=64))


def _close(got, want):
    scale = np.abs(want).max()
    assert scale > 0.3
    assert np.abs(np.asarray(got) - want).max() < RTOL * scale


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert (mc.n_conv_layers, mc.n_attn_layers, mc.n_moe_layers,
            mc.attn_layers, mc.conv_size) == (6, 2, 6, (2, 6), 3)
    assert "lm_head" not in params                  # tied
    toks = _tokens(50)
    got = _jitted("forward")(params, jnp.asarray(toks)[None], mc)[0]
    _close(got, _reference_logits(R, weights, toks, 0, 50))


@pytest.mark.parametrize("seed", [11, 12, 13, 14])
def test_bf16_forward_is_near_the_reference(seed):
    """The bf16 program (bf16 weights, the reference reads the same
    rounded weights in float32): the tolerance argued in the docstring."""
    from families import conv_moe_decoder as F
    from reference import conv_moe_decoder as R

    mc = F.model_config(C, max_seq_len=64, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    weights = R.init_weights(C, seed, jnp.bfloat16)
    toks = _tokens(50, seed)
    got = np.asarray(_jitted("forward")(F.program_params(weights),
                                        jnp.asarray(toks)[None], mc)[0])
    want = _reference_logits(R, weights, toks, 0, 50)
    assert np.abs(want).max() > 2.0
    assert np.abs(got - want).mean() < 0.08
    # the layers do the work: the reference does not echo its input
    assert (want.argmax(-1) == np.asarray(toks)).mean() < 0.2


# ---------------------- (b) prefill + decode: paged rows and slot tails

def _prefill(mc, params, pools, state, slot, table, toks, start,
             bucket=BUCKET):
    """One bucket-padded chunk of `toks` at `start` into the blocks of
    `table` and the tail row of `slot`, as the engine's insert program
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


@pytest.mark.parametrize("case", ["one_bucket", "two_chunks",
                                  "three_chunks"])
def test_paged_prefill_and_decode_match_reference(model, case):
    """Prefill (one bucket; two and three chunks, each over the rows and
    the tail the one before left) and then 10 decode steps through the
    paged K ‖ V pool and the slot's tail: logits at every position
    against the reference's full forward; the dead slots' tails and the
    blocks no table names stand as they were."""
    from ray_tpu.models.conv_moe import (_head, init_paged_pool,
                                         init_slot_state)

    R, mc, weights, params = model
    n_prompt = {"one_bucket": 13, "two_chunks": 27, "three_chunks": 41}[case]
    toks = _tokens(n_prompt + 10, seed=3)
    pools = init_paged_pool(mc, 40, BS)
    assert pools["kv"].shape == (2, 40, BS, 2, 32)   # attention layers only
    state = init_slot_state(mc, 3)
    assert state["tail"].shape == (6, 3, 2 * 64)     # conv layers only
    # every slot holds another sequence's garbage: admission must clear
    # slot 2's, and nothing may touch the others'
    state = jax.tree.map(lambda x: x + 1.0, state)
    pools = jax.tree.map(lambda x: x + 7.0, pools)
    table = np.arange(16, dtype=np.int32) + 5
    hidden = []
    for start in range(0, n_prompt, BUCKET):
        x, pools, state = _prefill(mc, params, pools, state, 2, table,
                                   toks[start:min(start + BUCKET, n_prompt)],
                                   start)
        hidden.append(x)
    got = [np.asarray(_head(mc, params, jnp.concatenate(hidden)))]
    tables = np.zeros((3, 16), np.int32)
    tables[2] = table
    active = jnp.asarray([False, False, True])
    for t in range(n_prompt, n_prompt + 10):
        logits, pools, counts, state = _jitted("decode_step_paged")(
            params, pools, jnp.asarray(tables),
            jnp.asarray([0, 0, toks[t]]), jnp.asarray([0, 0, t]), mc,
            active, state)
        got.append(np.asarray(logits[2:3]))
    _close(np.concatenate(got),
           _reference_logits(R, weights, toks, 0, len(toks)))
    assert np.all(np.asarray(state["tail"][:, :2]) == 1.0)
    untouched = np.setdiff1d(np.arange(40), table)
    assert np.all(np.asarray(pools["kv"][:, untouched]) == 7.0)
    assert int(counts["ticks"]) == 1
    assert counts["expert_tokens"].shape == (6, 8)
    assert int(counts["expert_tokens"].sum()) == mc.top_k * mc.n_moe_layers
    assert int(counts["experts_touched"]) == mc.top_k * mc.n_moe_layers


# --------------------- (c) what the chunks of one prompt hand each other

@pytest.mark.parametrize("case", ["two_chunks_equal_whole",
                                  "three_chunks_equal_whole",
                                  "padded_equals_unpadded",
                                  "one_token_chunk"])
def test_prefill_hand_off(model, case):
    """A prompt prefilled in 2 and in 3 chunks leaves the rows and the
    tails that the same prompt prefilled whole leaves; a prompt in a
    larger (padded) bucket leaves what it leaves in one it fills
    exactly: the tail is taken after the last REAL token; a chunk of ONE
    token (shorter than the tail) keeps the older row."""
    from ray_tpu.models.conv_moe import init_paged_pool, init_slot_state

    _, mc, _, params = model
    toks = _tokens(48, seed=5)
    table = np.arange(16, dtype=np.int32) + 2
    plans = {
        "two_chunks_equal_whole": ((((0, 29),), 32),
                                   (((0, 16), (16, 29)), 16)),
        "three_chunks_equal_whole": ((((0, 41),), 48),
                                     (((0, 16), (16, 32), (32, 41)), 16)),
        "padded_equals_unpadded": ((((0, 16),), 32), (((0, 16),), 16)),
        "one_token_chunk": ((((0, 17),), 32), (((0, 16), (16, 17)), 16)),
    }[case]
    out = []
    for chunks, bucket in plans:
        pools, state = init_paged_pool(mc, 30, BS), init_slot_state(mc, 2)
        xs = []
        for a, b in chunks:
            x, pools, state = _prefill(mc, params, pools, state, 1, table,
                                       toks[a:b], a, bucket)
            xs.append(np.asarray(x))
        n = sum(len(x) for x in xs)
        rows = np.asarray(pools["kv"][:, table]).reshape(
            (2, -1) + pools["kv"].shape[3:])[:, :n]
        out.append((np.concatenate(xs), rows, np.asarray(state["tail"][:, 1])))
    (xa, ra, ta), (xb, rb, tb) = out
    assert xa.shape == xb.shape and np.abs(xa).max() > 0.5
    assert np.abs(xa - xb).max() < 1e-5
    assert np.abs(ra).max() > 1e-2 and np.abs(ra - rb).max() < 1e-5
    assert np.abs(ta).max() > 1e-4
    assert np.abs(ta - tb).max() < 1e-5 * max(1.0, np.abs(ta).max())


def test_short_conv_is_one_module_for_both_models():
    """`ops/short_conv.py`: the width is the weights' (4 for the KDA
    hybrid, 3 here); a step after a prefill equals the longer prefill."""
    from ray_tpu.models import conv_moe, kimi_linear
    from ray_tpu.ops import short_conv as sc

    assert kimi_linear.short_conv is sc and conv_moe.short_conv is sc
    for K in (3, 4):
        ks = jax.random.split(jax.random.key(K), 2)
        x = jax.random.normal(ks[0], (2, 9, 5))
        w = jax.random.normal(ks[1], (K, 5))
        zeros = jnp.zeros((2, K - 1, 5))
        y, tail = sc.short_conv(x, w, zeros, jnp.asarray([9, 9]))
        y8, tail8 = sc.short_conv(x[:, :8], w, zeros, jnp.asarray([8, 8]))
        y1, tail1 = sc.step_in_place(sc.flat(tail8)[None], 0, x[:, 8], w)
        assert jnp.abs(y1 - y[:, 8]).max() < 1e-6
        assert jnp.array_equal(sc.rows(tail1[0], w), tail)
        # written out: zeros before the sequence, w[K-1] on the current row
        want = sum(w[j] * jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))[:, j:j + 9]
                   for j in range(K))
        assert jnp.abs(y - want).max() < 1e-6


# ------------------------------------- (d) the kernel at heads of 64

D64, NB_ROW, LAYER, CHUNK = 64, 8, 1, 3
LENGTHS = (0, 1, 16, 17, 50, NB_ROW * 16)       # ragged, one dead


def _kv_case(heads, kv_heads, seed):
    rng = np.random.default_rng(seed)
    B, NB = len(LENGTHS), len(LENGTHS) * NB_ROW + 4
    pool = jnp.asarray(rng.standard_normal((2, NB, 16, kv_heads, 2 * D64)),
                       jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 1, heads, D64)), jnp.bfloat16)
    tables = rng.permutation(NB - 4)[:B * NB_ROW].reshape(
        B, NB_ROW).astype(np.int32)
    lengths = np.asarray(LENGTHS)
    qpos = np.maximum(lengths - 1, 0).astype(np.int32)
    return q, pool, tables, qpos, lengths


@pytest.mark.parametrize("heads,kv_heads", [(4, 1), (8, 4), (32, 8)],
                         ids=["kv1", "kv4", "kv8"])
def test_kernel_at_heads_of_64_matches_the_gather_path(heads, kv_heads):
    """The K ‖ V pool through the Pallas interpreter (`v_pool=None`)
    against the gather + `_decode_attention` over the same pool's two
    halves: every length class at once, a layer index that is not 0,
    chunks of 3 blocks; stale table entries past a length change
    nothing."""
    from ray_tpu.models.llama import _decode_attention
    from ray_tpu.ops import paged_attention as pa

    q, pool, tables, qpos, lengths = _kv_case(heads, kv_heads, seed=heads)
    active = lengths > 0
    B, nb = tables.shape

    def kernel(tab):
        scalars = pa.plan(jnp.asarray(tab), jnp.asarray(qpos),
                          jnp.asarray(active), 16, CHUNK)
        return np.asarray(pa.paged_attention(
            q, pool, None, jnp.int32(LAYER), scalars, chunk=CHUNK),
            np.float32)

    got = kernel(tables)
    assert got.shape == (B, 1, heads, D64)
    kv = pool[LAYER][tables].reshape(B, nb * 16, kv_heads, 2 * D64)
    want = np.asarray(_decode_attention(
        q, kv[..., :D64], kv[..., D64:], jnp.asarray(qpos)[:, None]),
        np.float32)
    assert not got[~active].any()
    assert np.abs(got[active] - want[active]).max() <= 2 ** -6
    dirty = tables.copy()
    for b, n in enumerate(lengths):
        dirty[b, -(-int(n) // 16):] = 2 ** 30
    np.testing.assert_array_equal(kernel(dirty), got)


def test_the_kernel_engages_at_this_pool_and_at_mistrals(monkeypatch):
    from ray_tpu.models.conv_moe import ConvMoEConfig, _SERVING
    from ray_tpu.ops import attention, paged_attention as pa

    c = ConvMoEConfig(n_layers=14, attn_layers=(2, 6, 10))
    pools = jax.eval_shape(lambda: c.serving().init_pool(c, 64, 16))
    assert pools["kv"].shape == (3, 64, 16, 8, 128)
    # 2048 B a token a layer, no padded lanes
    assert np.prod(pools["kv"].shape[3:]) * 2 == 2048
    mistral = jax.ShapeDtypeStruct((20, 64, 16, 8, 128), jnp.bfloat16)
    assert _SERVING.paged_attention(pools) == "gather"      # the CPU
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert pa.engages(pools["kv"]) and pa.engages(mistral)
    assert _SERVING.paged_attention(pools) == "kernel"


def test_decode_step_agrees_on_both_paths(monkeypatch):
    """`decode_step_paged` at heads of 64 (bf16) over a pool with
    history: the kernel's logits (interpreter) against the gather
    path's, the same rows written."""
    from ray_tpu.models import conv_moe as M
    from ray_tpu.ops import attention

    c = M.ConvMoEConfig.tiny(head_dim=64, n_heads=4, n_kv_heads=2, dim=128,
                             n_layers=4, attn_layers=(2,), n_dense_layers=2)
    params = M.init_params(c, jax.random.key(0))
    rng = np.random.default_rng(5)
    B, nb, NB = 3, c.max_seq_len // 16, 30
    pools = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape) * 0.5, x.dtype),
        M.init_paged_pool(c, NB, 16))
    state = M.init_slot_state(c, B)
    tables = jnp.asarray(rng.permutation(NB)[:B * nb].reshape(B, nb),
                         jnp.int32)
    pos = jnp.asarray([37, 0, 90], jnp.int32)
    tok = jnp.asarray([5, 6, 7], jnp.int32)
    active = jnp.asarray([True, False, True])
    out = {}
    for path, force in (("gather", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert M._paged_attention(pools) == path
        # jitted anew: the path is chosen as it traces
        out[path] = jax.jit(lambda: M.decode_step_paged(
            params, pools, tables, tok, pos, c, active, state))()
    (lg, pg, _, sg), (lk, pk, _, sk) = out["gather"], out["kernel"]
    live = np.asarray(active)
    assert np.abs(np.asarray(lg)).max() > 0.1
    assert np.abs(np.asarray(lg - lk))[live].max() < 0.02
    assert jnp.array_equal(pg["kv"], pk["kv"])
    # the convolutions before the attention layer saw the same input;
    # the one after it sees the two paths' rounding
    assert jnp.array_equal(sg["tail"][:2], sk["tail"][:2])
    assert jnp.abs(sg["tail"][2] - sk["tail"][2]).max() < 2e-3


def _tiling(**over):
    """The tiny model at expert widths that tile (bf16, 128 -> 128):
    `ops.grouped_matmul` engages wherever the interpreter is forced;
    heads of 16, so the paged-attention kernel does not."""
    from ray_tpu.models import conv_moe as M

    c = M.ConvMoEConfig.tiny(dim=128, expert_hidden_dim=128, **over)
    return M, c, M.init_params(c, jax.random.key(0))


@pytest.mark.parametrize("rows", ["all", "live"])
def test_dropless_moe_agrees_on_both_grouped_paths(rows, monkeypatch):
    """An expert layer of this model: `ops.grouped_matmul` through the
    Pallas interpreter against `lax.ragged_dot`, the same counts to the
    row and outputs to 2 ulp of bf16 at their size."""
    from ray_tpu.models import moe
    from ray_tpu.ops import attention

    M, c, params = _tiling()
    p = params["layers"][c.n_dense_layers]
    x = jax.random.normal(jax.random.key(6), (48, 128), c.dtype)
    live = None if rows == "all" else jnp.arange(48) % 3 != 1
    routing = moe.sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor,
                                     M.ROUTE_EPS)
    out = {}
    for path, force in (("xla", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        assert M._SERVING.grouped_matmul(c, 24) == path
        out[path] = moe.dropless_moe(x, p, routing, live=live)
    (yx, sx), (yk, sk) = out["xla"], out["kernel"]
    assert sx.tolist() == sk.tolist()
    assert int(sx.sum()) == (96 if live is None else 2 * int(live.sum()))
    yx, yk = np.asarray(yx, np.float32), np.asarray(yk, np.float32)
    scale = np.abs(yx).max()
    assert scale > 1e-3 and np.abs(yx - yk).max() <= 2 ** -7 * scale


def test_engine_serves_the_same_tokens_on_both_grouped_paths(monkeypatch):
    """One engine run a path over the same prompts (a chunked one, so
    inserts of two buckets and the tick all run their grouped products
    by the kernel): `engine.stats()` names the path, and the greedy
    tokens are the same."""
    from ray_tpu.ops import attention
    from ray_tpu.serve.llm.engine import Request

    M, c, params = _tiling(max_seq_len=64)
    prompts = [_tokens(n, seed=40 + n) for n in (5, 16, 30)]
    served = {}
    for path, force in (("xla", False), ("kernel", True)):
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", force)
        engine = _engine(c, params)
        handles = [engine.submit(Request(
            prompt=p, max_tokens=5, chunked_prefill=len(p) > 16))
            for p in prompts]
        while engine.has_work():
            engine.step()
        st = engine.stats()
        assert st["grouped_matmul"] == path
        assert st["paged_attention"] == "gather"
        served[path] = [h.tokens for h in handles]
        assert all(len(t) == 5 for t in served[path])
    assert served["kernel"] == served["xla"]


# --------------------------------------------------- (e) lower precision

def test_lower_precision_is_caught(model):
    """The tolerance is tight enough: matrices rounded to int8 (the
    cell's control) fail it by a factor of two at least."""
    from families import conv_moe_decoder as F

    R, mc, weights, _ = model
    toks = _tokens(50)
    want = _reference_logits(R, weights, toks, 0, 50)
    # the control deletes the bank of experts `program_params` made
    # last: make that one this test's own, not the fixture's
    _, _, mine, _ = _build(C)
    params = jax.jit(F.lower_precision_params)(mine)
    got = np.asarray(_jitted("forward")(params, jnp.asarray(toks)[None],
                                        mc)[0])
    assert np.abs(got - want).max() > 2 * RTOL * np.abs(want).max()


def test_routing_keeps_the_published_epsilon():
    """`sigmoid_bias_top_k(k, scale, eps)`: the default leaves the other
    models' rule as it was; this model's weights sum to s / (s + 1e-6)."""
    from ray_tpu.models.moe import sigmoid_bias_top_k

    logits = jnp.asarray([[-30.0, -31.0, -32.0, -40.0]])   # scores ~ 1e-13
    p = {"router_bias": jnp.zeros((4,))}
    _, w20 = sigmoid_bias_top_k(2)(logits, p)
    _, w6 = sigmoid_bias_top_k(2, 1.0, 1e-6)(logits, p)
    assert abs(float(w20.sum()) - 1.0) < 1e-5
    assert float(w6.sum()) < 1e-6


@pytest.mark.parametrize("what", ["conv_bias", "layer_kind",
                                  "norm_topk_prob", "untied_head"])
def test_family_refuses_by_name_what_the_program_has_not(what):
    from families import conv_moe_decoder as F

    c = dict(C)
    if what == "conv_bias":
        c["conv_bias"] = True
    elif what == "layer_kind":
        c["layer_types"] = ["conv", "sliding_attention"] + C["layer_types"][2:]
    elif what == "norm_topk_prob":
        c["norm_topk_prob"] = False
    else:
        c["tie_word_embeddings"] = False
    with pytest.raises(ValueError, match="has no"):
        F.model_config(c, max_seq_len=64, compute_dtype="float32",
                       param_dtype="float32")


# ------------------------------------------------------- (f) the engine

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
    slot started from a zero tail: a leak would change its logits), and
    a prompt under way keeps its slot inactive until its last chunk."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    st = engine.stats()
    reuses_before = st["slot_reuses"]
    assert st["traces"] == {"tick": 1, "insert": 2, "export": 0, "adopt": 0}
    assert st["paged_attention"] == "gather"         # the CPU
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
    assert seen_under_way > 0
    st = engine.stats()
    assert st["slot_reuses"] - reuses_before >= 5
    assert st["trace_count"] == 3
    assert st["kv"]["used_blocks"] == 0
    for p, h in zip(prompts, handles):
        assert h.finish_reason == "length" and len(h.tokens) == 6
        assert h.prefilled_tokens == len(p)
        d = R.served_token_deficits(weights, C, p, h.tokens)
        assert d.max() < 1e-4, (len(p), d)
    ctr = st["counters"]
    assert int(ctr["ticks"]) > 0
    assert ctr["expert_tokens"].shape == (6, 8)
    assert int(ctr["expert_tokens"].sum()) % (2 * 6) == 0


def test_slot_tail_is_the_last_two_rows(model, engine):
    """`LLMEngine.slot_state`: after a chunked prompt and six tokens the
    slot's tail in the first convolution layer is the reference's
    `B * X` of the last two tokens it has seen."""
    from reference import conv_moe_decoder as R
    from ray_tpu.serve.llm.engine import Request

    _, mc, weights, params = model
    p = _tokens(37, seed=9)
    h = engine.submit(Request(prompt=p, max_tokens=6, chunked_prefill=True))
    engine.step()
    slot, = (i for i, s in enumerate(engine._slots) if s.handle is h)
    while engine.has_work():
        engine.step()
    got = engine.slot_state(slot)["tail"]
    assert got.shape == (6, 2 * 64)             # taps on lanes
    seen = p + h.tokens[:-1]
    w = weights["layers"][0]
    with jax.default_matmul_precision("highest"):
        x = weights["embed"][jnp.asarray(seen[-2:])].astype(jnp.float32)
        u = R._rms(x, w["op_norm"], 1e-5)
        b, _, xin = jnp.split(u @ w["w_in"], 3, axis=-1)
    want = np.asarray(b * xin)
    assert np.abs(want).max() > 1e-6
    assert np.abs(np.asarray(got[0]).reshape(want.shape) - want).max() \
        < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("what", ["prefix_cache", "export_prefix",
                                  "adopt", "prefill_only", "preempt",
                                  "speculative_verify"])
def test_engine_refuses_by_name_what_would_lose_the_tail(model, what):
    """Whatever moves rows without the tail is refused, and the refusal
    names the model."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.serve.llm.kv_cache import KVState

    _, mc, _, params = model
    with pytest.raises(ValueError, match="models/conv_moe.py"):
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


# ------------------------------------------------------- (g) the names

@pytest.mark.parametrize("program", ["tick", "insert"])
def test_programs_carry_the_scopes_the_readers_read(model, engine, program,
                                                   monkeypatch):
    """`conv` > `in_proj`, `mix`, `out_proj`; `attn` > `qk_norm`,
    `kv_write` and (tick) `paged` on the kernel path, `kv_gather` on the
    other; `moe` > `router`, `experts`; `mlp`, `lm_head`; the engine's
    `sample`: by PATH in the compiled programs' `op_name`s, as
    `benchmarks/scope_paths.py` reads them."""
    import re

    from ray_tpu.ops import attention

    _, mc, _, params = model
    e = engine
    if program == "tick":
        lowered = e._programs.lower(e.params)
        want = ["conv/in_proj", "conv/mix", "conv/out_proj", "attn/qk_norm",
                "attn/kv_write", "attn/kv_gather", "moe/router",
                "moe/experts", "mlp", "lm_head", "sample"]
    else:
        from ray_tpu.models.conv_moe import (init_paged_pool,
                                             init_slot_state, prefill_paged)

        pools, state = init_paged_pool(mc, 20, BS), init_slot_state(mc, 1)
        hist = {k: v[:, :16].reshape((v.shape[0], 64) + v.shape[3:])
                for k, v in pools.items()}
        lowered = jax.jit(lambda p, t, h, s: prefill_paged(
            p, t, jnp.int32(0), h, mc, jnp.int32(9), s)).lower(
            params, jnp.zeros((1, 16), jnp.int32), hist,
            {k: v[:, 0] for k, v in state.items()})
        want = ["conv/in_proj", "conv/mix", "conv/out_proj", "attn/qk_norm",
                "moe/router", "moe/experts", "mlp"]
    names = set(re.findall(r'op_name="([^"]*)"', lowered.compile().as_text()))

    def has(path):
        parts = path.split("/")
        for n in names:
            comps = iter(re.split(r"[/()]", n))
            if all(p in comps for p in parts):
                return True
        return False

    for path in want:
        assert has(path), path
    if program == "tick":
        # the kernel path names `attn/paged` (interpreter: the CPU)
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
        from ray_tpu.models import conv_moe as M

        c64 = M.ConvMoEConfig.tiny(head_dim=64, n_heads=2, n_kv_heads=1,
                                   dim=128, n_layers=3, attn_layers=(2,))
        p64 = jax.eval_shape(lambda: M.init_params(c64, jax.random.key(0)))
        pool = jax.eval_shape(lambda: M.init_paged_pool(c64, 8, 16))
        st = jax.eval_shape(lambda: M.init_slot_state(c64, 2))
        i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
        text = jax.jit(lambda p, kv, t, tok, pos, s: M.decode_step_paged(
            p, kv, t, tok, pos, c64, None, s)).lower(
            p64, pool, i32(2, 8), i32(2), i32(2), st).as_text(
            debug_info=True)
        assert re.search(r"attn/paged", text)
