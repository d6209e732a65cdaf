"""Mamba-2 layers with their state by slot, non-gated experts beside a
shared one and grouped-query attention without positions, one mixer a
layer (`models/nemotron_h.py`, `ops/ssd.py`, `ops/short_conv.py`,
`ops/paged_attention.py`, `models/moe.py`, `serve/llm/engine.py`),
against the plain float32 reference of
`benchmarks/reference/ssd_moe_decoder.py` on seeded random weights at a
tiny size.  Logits are compared, never sampled tokens (but for the
engine tests, which judge served tokens by their reference logits, as
the benchmark does).

Tolerances and their reasons
----------------------------
* 1e-4 RELATIVE (to the largest reference logit, about 4 here) on
  logits, float32 against float32 on the CPU: the program's chunked
  matrix form, its sorted grouped products and its blockwise online
  softmax against the reference's token-by-token recurrence, per-expert
  loop and plain softmax differ in the ORDER of float32 sums; that
  reads 2e-6 relative.  Every mutilated program reads 30 x the
  tolerance and more.
* 3e-4 RELATIVE on the recurrent STATE of a slot (Frobenius, a layer)
  after a chunked prompt and served tokens: float32 against float32
  reads 1e-6; a state kept in bf16 between tokens reads 2e-3 and fails
  it (`test_a_bf16_state_fails`), whatever it does to a logit.
* The weights are drawn at 0.1, not the 0.02 of the published widths,
  and the norm vectors, `D` and the convolution's bias are drawn too
  (the family's draws are ones and zeros, which would hide one left
  out): at hidden 64 and 0.02 no mixer moves a logit by much.
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
STATE_RTOL = 3e-4
# `MEM*E` twice (the scan's two repeats), then `ME` unrolled; two groups
# of two Mamba heads; 2 query heads a K/V head; 4 of 8 experts held
C = dict(model_type="nemotron_h", hidden_size=64,
         hybrid_override_pattern="MEM*EMEM*EME", num_hidden_layers=12,
         mamba_num_heads=4, mamba_head_dim=16, n_groups=2, ssm_state_size=8,
         conv_kernel=4, chunk_size=128, num_attention_heads=4,
         num_key_value_heads=2, head_dim=16, moe_intermediate_size=32,
         moe_shared_expert_intermediate_size=64, n_shared_experts=1,
         n_routed_experts=4, num_experts_per_tok=2, routed_scaling_factor=2.5,
         norm_topk_prob=True, n_group=1, topk_group=1, vocab_size=512,
         layer_norm_epsilon=1e-5, norm_eps=1e-5, mlp_hidden_act="relu2",
         mamba_hidden_act="silu", use_conv_bias=True, use_bias=False,
         mlp_bias=False, attention_bias=False, mamba_proj_bias=False,
         residual_in_fp32=False, tie_word_embeddings=False,
         sliding_window=None, time_step_min=1e-3, time_step_max=0.1,
         time_step_floor=1e-4, initializer_range=0.1, router_bias_scale=0.02,
         deployment=dict(n_routed_experts=8, rank=1),
         precision=dict(recurrent_state="float32"))
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


def _drawn(weights):
    """Every norm vector, `D` and the convolution's bias drawn, so that
    each is seen."""
    def leaf(path, x):
        name = path[-1].key
        if name not in ("norm", "gate_norm", "norm_f", "D", "conv_b"):
            return x
        key = jax.random.key(sum(map(ord, jax.tree_util.keystr(path))))
        return (x + 0.3 * jax.random.normal(key, x.shape)).astype(x.dtype)

    return jax.tree_util.tree_map_with_path(leaf, weights)


def _build(c, max_seq_len=64, **overrides):
    from families import ssd_moe_decoder as F
    from reference import ssd_moe_decoder as R

    mc = F.model_config(c, max_seq_len=max_seq_len,
                        compute_dtype="float32", param_dtype="float32",
                        prefill_key_block=8, **overrides)
    weights = _drawn(R.init_weights(c, 11, jnp.float32))
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


@functools.cache
def _jitted(name):
    from ray_tpu.models import nemotron_h as M

    return jax.jit(getattr(M, name), static_argnames=("config",))


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C):
    return np.asarray(R.logits_for_positions(weights, c, toks, start, n,
                                             pad_to=16))


def _off(got, want):
    scale = np.abs(want).max()
    assert scale > 0.3
    return np.abs(np.asarray(got) - want).max() / scale


# ------------------------------------------------ (a) no cache, whole model

def test_forward_matches_reference(model):
    R, mc, weights, params = model
    assert mc.layout == ("MEM*E", 2, "ME")
    assert mc.n_held_experts == 4 and mc.expert_rank == 1
    toks = _tokens(40)
    got = _jitted("forward")(params, jnp.asarray([toks]), mc)
    assert _off(got[0], _reference_logits(R, weights, toks, 0, 40)) < RTOL


@pytest.mark.parametrize("pattern, layout", [
    ("MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
     ("MEMEM*E", 5, "MEMEMEM*EMEMEMEME")),      # as published
    ("MEMEM*EMEMEM*E", ("MEMEM*E", 2, "")),     # the benchmark's cut
    ("MEM*E", ("", 0, "MEM*E")),                # nothing repeats
    ("MEMEME*", ("ME", 3, "*")),
])
def test_the_pattern_is_cut_into_repeats_and_a_tail(pattern, layout):
    from ray_tpu.models.nemotron_h import NemotronHConfig
    from reference import ssd_moe_decoder as R

    mc = NemotronHConfig(pattern=pattern)
    assert mc.layout == layout == R.layout(pattern)
    assert mc.n_ssm_layers + mc.n_attn_layers + mc.n_moe_layers \
        == len(pattern)


def test_an_unrolled_pattern_is_the_same_model(model):
    """A pattern that does not repeat from layer 0 (`*MEMEM`: nothing is
    stacked, every layer is unrolled) agrees with the reference as the
    scanned one does."""
    c = dict(C, hybrid_override_pattern="*MEMEM", num_hidden_layers=6)
    R, mc, weights, params = _build(c)
    assert mc.layout == ("", 0, "*MEMEM")
    toks = _tokens(24, seed=3)
    got = _jitted("forward")(params, jnp.asarray([toks]), mc)
    assert _off(got[0], _reference_logits(R, weights, toks, 0, 24, c)) < RTOL


def test_published_sizes_count_to_the_published_total():
    """31.6 B parameters as published, 3.2 B of them active a token."""
    from ray_tpu.models.nemotron_h import NemotronHConfig, init_params

    mc = NemotronHConfig()
    tree = jax.eval_shape(lambda: init_params(mc, jax.random.key(0)))
    total = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(tree))
    assert round(total / 1e9, 1) == 31.6
    expert = 2 * mc.dim * mc.expert_hidden_dim
    active = total - mc.n_moe_layers * (mc.n_experts - mc.top_k) * expert \
        - mc.vocab_size * mc.dim            # the table is a gather
    assert round(active / 1e9, 1) == 3.2


# ------------------------------------------------ (b) through pool and state

def _prefill(mc, params, pools, state, slot, table, toks, start):
    """One bucket-padded chunk of `toks` at `start` into the blocks of
    `table` and the state row of `slot`, as the engine's insert program
    does it."""
    hist = {k: v[:, table].reshape((v.shape[0], -1) + v.shape[3:])
            for k, v in pools.items()}
    padded = np.zeros((BUCKET,), np.int32)
    padded[:len(toks)] = toks
    mine = {k: jnp.where(start > 0, v[:, slot], 0) for k, v in state.items()}
    x, rows, mine = _jitted("prefill_paged")(
        params, jnp.asarray(padded)[None], jnp.int32(start), hist, mc,
        jnp.int32(len(toks)), mine)
    ids = table[start // BS + np.arange(BUCKET // BS)]
    pools = {k: v.at[:, ids].set(rows[k].reshape(
        (v.shape[0], BUCKET // BS, BS) + v.shape[3:]))
        for k, v in pools.items()}
    state = {k: v.at[:, slot].set(mine[k]) for k, v in state.items()}
    return x[0, len(toks) - 1], pools, state


def _served_logits(mc, params, toks, n_prompt, slots=3, slot=2):
    """Logits at the LAST row of every chunk of the prompt and at every
    later position of `toks` through the serving path: the prompt in
    chunks of BUCKET (state and tail handed on in the slot), the rest a
    decode step a token with dead slots beside the live one.  Returns
    (positions, [len(positions), V], the state); checks that the dead
    slots' state stands."""
    from ray_tpu.models import nemotron_h as M

    n_blocks = -(-len(toks) // BUCKET) * BUCKET // BS
    pools = M.init_paged_pool(mc, n_blocks + 9, BS)
    table = np.arange(n_blocks, dtype=np.int32)[::-1] + 5
    # the slot holds another sequence's garbage: admission must clear it
    state = jax.tree.map(lambda x: x.at[:, slot].set(1.0),
                         M.init_slot_state(mc, slots))
    at, got = [], []
    for start in range(0, n_prompt, BUCKET):
        end = min(start + BUCKET, n_prompt)
        x, pools, state = _prefill(mc, params, pools, state, slot, table,
                                   toks[start:end], start)
        at.append(end - 1)
        got.append(np.asarray(M._head(mc, params, x[None])))
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
        at.append(t)
        got.append(np.asarray(logits[slot:slot + 1]))
    for k, v in before.items():
        assert np.array_equal(np.asarray(state[k][:, dead]), v)
    assert int(counts["live_slots"]) == 1 and int(counts["ticks"]) == 1
    assert int(counts["expert_tokens"].sum()) <= mc.top_k * mc.n_moe_layers
    return np.asarray(at), np.concatenate(got), state


@pytest.mark.parametrize("n_prompt", [13, 16, 41],
                         ids=["one_piece", "a_whole_bucket", "three_chunks"])
def test_paged_prefill_and_decode_match_reference(model, n_prompt):
    R, mc, weights, params = model
    toks = _tokens(n_prompt + 9, seed=n_prompt)
    at, got, _ = _served_logits(mc, params, toks, n_prompt)
    want = _reference_logits(R, weights, toks, 0, len(toks))[at]
    assert _off(got, want) < RTOL


def _state_off(R, mc, weights, state, toks, slot=2, c=C):
    """The largest relative distance, over the Mamba-2 layers, of the
    slot's state from the reference's after `toks`."""
    from ray_tpu.ops import kda

    want = R.states_after(weights, c, toks)                 # [Lm,H,P,N]
    got = np.swapaxes(np.asarray(kda.unpack(
        state["S"][:, slot].astype(jnp.float32), mc.heads_a_row)), 2, 3)
    norm = lambda a: np.sqrt((a.astype(np.float64) ** 2).sum((1, 2, 3)))
    return float(np.max(norm(got - want) / norm(want)))


def test_slot_state_is_what_the_reference_carries(model):
    R, mc, weights, params = model
    toks = _tokens(50, seed=5)
    _, _, state = _served_logits(mc, params, toks, 41)
    assert _state_off(R, mc, weights, state, toks) < STATE_RTOL / 30


def test_a_bf16_state_fails(model):
    """The state kept in bf16 between tokens and chunks: the slot's
    state is off by several times the state's tolerance (the logits
    need not show it at this size)."""
    R, mc, weights, params = model
    low = dataclasses.replace(mc, state_dtype=jnp.bfloat16)
    toks = _tokens(50, seed=5)
    _, _, state = _served_logits(low, params, toks, 41)
    assert state["S"].dtype == jnp.bfloat16
    assert _state_off(R, low, weights, state, toks) > 3 * STATE_RTOL


# ------------------------------------------------ (c) mutilated programs

def _norm_after_gate(c, y, z, w):
    from jax import lax

    g = y.reshape(y.shape[:-1] + (c.ssm_groups, -1))
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + c.norm_eps)
    return g.reshape(y.shape) * w.astype(jnp.float32) * jax.nn.silu(z)


def _one_group_norm(c, y, z, w):
    from jax import lax

    y = y * jax.nn.silu(z)
    return y * lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + c.norm_eps) \
        * w.astype(jnp.float32)


def _rotated(self, kv, l, q, k, v):
    from ray_tpu.models.llama import apply_rope
    from ray_tpu.models.window_moe import _masked_attention, _seen

    hd = q.shape[-1]
    inv = 1.0 / (1e4 ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = self.qpos.astype(jnp.float32)[..., None] * inv
    q, k = apply_rope(q, jnp.cos(f), jnp.sin(f)), \
        apply_rope(k, jnp.cos(f), jnp.sin(f))
    return _masked_attention(q, k, v, _seen(self.qpos, self.qpos, None)), kv


def _silu_experts(h, w_up, w_down, dt_):
    return jax.nn.silu(h @ w_up.astype(dt_)) @ w_down.astype(dt_)


MUTILATIONS = {
    "the gate after the norm": ("_gated_group_norm", _norm_after_gate),
    "the norm over the whole width": ("_gated_group_norm", _one_group_norm),
    "rotary positions": ("_NoCache.attend", _rotated),
    "a silu shared expert": ("_relu2", _silu_experts),
}


@pytest.mark.parametrize("what", sorted(MUTILATIONS) + [
    "no D skip", "no convolution bias", "weights not renormalised",
    "every head its own B and C"])
def test_a_mutilated_program_fails(model, what, monkeypatch):
    """Each departure from the layers' equations is seen by the
    tolerance: 30 times over and more."""
    from ray_tpu.models import moe, nemotron_h as M

    R, mc, weights, params = model
    if what in MUTILATIONS:
        name, fn = MUTILATIONS[what]
        owner = M
        for part in name.split(".")[:-1]:
            owner = getattr(owner, part)
        monkeypatch.setattr(owner, name.split(".")[-1], fn)
    elif what == "weights not renormalised":
        def plain(k, scale=1.0, eps=1e-20):
            def route(logits, p):
                s = jax.nn.sigmoid(logits)
                _, idx = jax.lax.top_k(s + p["router_bias"], k)
                return idx, jnp.take_along_axis(s, idx, -1) * scale
            return route
        monkeypatch.setattr(M, "sigmoid_bias_top_k", plain)
    elif what == "every head its own B and C":
        # the groups' rows read in another order: head h reads h % G
        from ray_tpu.ops import ssd
        monkeypatch.setattr(ssd, "ssd_chunked", functools.partial(
            _regrouped, ssd.ssd_chunked))
    else:
        zero = {"no D skip": "D", "no convolution bias": "conv_b"}[what]
        params = jax.tree_util.tree_map_with_path(
            lambda path, x: jnp.zeros_like(x)
            if path[-1].key == zero else x, params)
    toks = _tokens(40)
    # a partial of it: `jax.jit` caches a trace by the FUNCTION
    got = jax.jit(functools.partial(M.forward, config=mc))(
        params, jnp.asarray([toks]))
    assert _off(got[0], _reference_logits(R, weights, toks, 0, 40)) \
        > 30 * RTOL, what


def _regrouped(chunked, x, dt, A, Bm, Cm, S0, n_real=None, **kw):
    return chunked(x, dt, A, Bm[..., ::-1, :], Cm[..., ::-1, :], S0, n_real,
                   **kw)


# ------------------------------------------------ (d) the family, the control

@pytest.mark.parametrize("refused, change", [
    ("recurrent state kept in bfloat16",
     {"precision": {"recurrent_state": "bfloat16"}}),
    ("mlp_hidden_act silu", {"mlp_hidden_act": "silu"}),
    ("n_group / topk_group", {"n_group": 2}),
    ("a bias on a projection", {"mamba_proj_bias": True}),
    ("a convolution without its bias", {"use_conv_bias": False}),
    ("tie_word_embeddings", {"tie_word_embeddings": True}),
    ("a pattern of 12 layers at num_hidden_layers 14",
     {"num_hidden_layers": 14}),
    ("a dense feed-forward layer", {"hybrid_override_pattern": "M-M*EMEM*EME"}),
])
def test_the_family_refuses_what_the_program_does_not_compute(refused,
                                                              change):
    from families import ssd_moe_decoder as F

    with pytest.raises(ValueError, match=refused.replace("*", r"\*")):
        F.model_config(dict(C, **change), max_seq_len=64,
                       compute_dtype="float32", param_dtype="float32")


def test_the_control_rounds_the_matrices_and_nothing_else(model):
    """The family's control and the model's `quantize_int8` round the
    same leaves to the same values; taps, norms, biases, decays and the
    embedding table stand."""
    from families import ssd_moe_decoder as F
    from ray_tpu.models.nemotron_h import quantize_int8

    _, mc, weights, _ = model
    # tracing the control deletes the sound bank made last: one of its
    # own, not the module's
    F.program_params(weights)
    low = jax.jit(F.lower_precision_params)(weights)
    params = F.program_params(weights)
    mine = quantize_int8(params)
    kept = ("norm", "gate_norm", "norm_f", "conv_w", "conv_b", "A_log",
            "dt_bias", "D", "router_bias", "embed")
    flat = lambda t: {jax.tree_util.keystr(p): x for p, x in
                      jax.tree_util.tree_flatten_with_path(t)[0]}
    sound, low, mine = flat(params), flat(low), flat(mine)
    assert sound.keys() == low.keys() == mine.keys()
    for path, x in sound.items():
        name = path.split("'")[-2]
        same = np.array_equal(np.asarray(low[path]), np.asarray(x))
        assert same == (name in kept), path
        assert np.abs(np.asarray(low[path] - mine[path])).max() < 1e-6, path
        if not same:
            assert len(np.unique(np.asarray(low[path]).ravel())) \
                <= 255 * max(x.shape), path


# ------------------------------------------------ (e) through LLMEngine

def _engine(mc, params, **over):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    cfg = dict(num_slots=3, max_seq_len=64, prefill_buckets=(8, 16),
               kv_block_size=BS, num_kv_blocks=40, decode_block=1,
               prefix_cache=False)
    return LLMEngine(params, mc, EngineConfig(**{**cfg, **over}), rng_seed=0)


@pytest.fixture
def engine(model, shared_engine):
    _, mc, _, params = model
    return shared_engine("three slots", lambda: _engine(mc, params))


def test_engine_serves_chunked_prompts_and_recycles_slots(model, engine):
    """Five requests through three slots (a slot is reused with its
    state cleared), prompts shorter and longer than the top bucket, two
    and three slots live at different positions: every served token's
    reference logit lies within the tolerance of the reference
    maximum."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    assert engine._stateful and engine._ring is None
    lengths = (5, 16, 23, 45, 9)
    handles = [engine.submit(Request(
        prompt=_tokens(n, seed=20 + i), max_tokens=6, temperature=0.0,
        chunked_prefill=n > 16)) for i, n in enumerate(lengths)]
    while engine.has_work():
        engine.step()
    stats = engine.stats()
    assert stats["paged_attention"] == "gather"
    assert stats["grouped_matmul"] == "xla"
    assert stats["counters"]["ssd_live_steps"] == 0     # `ssd_step` ran
    assert stats["counters"]["live_slots"] >= 5 * 5
    assert stats["counters"]["experts_touched"] > 0
    assert stats["counters"]["ticks"] > 0
    assert stats["counters"]["expert_tokens"].shape == (5, 4)
    # 5 Mamba-2 layers x 3 slots x (2 x 8 x 32 float32 + 3 x 96 float32)
    assert stats["slot_state"]["bytes"] == 5 * 3 * (2 * 8 * 32 + 3 * 96) * 4
    assert stats["kv"]["used_blocks"] == 0
    for i, (n, h) in enumerate(zip(lengths, handles)):
        assert h.finish_reason == "length" and len(h.tokens) == 6
        d = R.served_token_deficits(weights, C, _tokens(n, seed=20 + i),
                                    h.tokens)
        assert d.max() < RTOL * 4, (n, d)


def test_engine_holds_the_references_state_in_the_slot(model, engine):
    """A prompt in three chunks, then five ticks: the slot's state is
    the reference's after the prompt and every served token but the
    last."""
    from ray_tpu.serve.llm.engine import Request

    R, mc, weights, params = model
    prompt = _tokens(37, seed=31)
    h = engine.submit(Request(prompt=prompt, max_tokens=6, temperature=0.0,
                              chunked_prefill=True))
    while engine.has_work():
        engine.step()
    # a released slot keeps its rows until the next admission clears
    # them: one of the three holds this request's
    offs = [_state_off(R, mc, weights, {
        k: jnp.asarray(v)[:, None] for k, v in engine.slot_state(s).items()},
        prompt + h.tokens[:-1], slot=0) for s in range(3)]
    assert min(offs) < STATE_RTOL / 30, offs


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
    with pytest.raises((ValueError, NotImplementedError),
                       match="state by slot"):
        if what == "export_prefix":
            engine.export_prefix(_tokens(8), max_blocks=1)
        elif what == "prefill_only":
            engine.submit(Request(prompt=_tokens(8), max_tokens=1,
                                  prefill_only=True))
        else:
            engine.preempt(0)


# ------------------------------------------------ (f) the kernels' paths

# The same layers at shapes where every kernel engages under the
# interpreter: pairs of Mamba heads of 64 over a state of 16 rows, K/V
# heads of 128 in blocks of 16, a model width and an expert width of
# whole and of HALF lane rows (192 = 3 x 64), bf16 weights and compute.
C_KERNEL = dict(C, hidden_size=256, mamba_num_heads=4, mamba_head_dim=64,
                ssm_state_size=16, num_attention_heads=4,
                num_key_value_heads=2, head_dim=128,
                moe_intermediate_size=192,
                moe_shared_expert_intermediate_size=128,
                hybrid_override_pattern="MEM*EMEM*E", num_hidden_layers=10)


def test_decode_step_agrees_on_both_paths(monkeypatch):
    """One tick of three slots (one dead) with the Pallas state step,
    the paged-attention kernel and the grouped kernel at a width of
    half lane rows (reading a repeat's bank through the scan's stack)
    all engaged under the interpreter, against the same
    tick by `ssd_step`, the gather and `lax.ragged_dot`: bf16 compute,
    so logits to 2e-2 of their size (the paths round in other places),
    the float32 state of the first Mamba-2 layer to 1e-5."""
    from families import ssd_moe_decoder as F
    from reference import ssd_moe_decoder as R
    from ray_tpu.models import nemotron_h as M
    from ray_tpu.ops import attention

    mc = F.model_config(C_KERNEL, max_seq_len=64, compute_dtype="bfloat16",
                        param_dtype="bfloat16", prefill_key_block=16)
    params = F.program_params(R.init_weights(C_KERNEL, 4, jnp.bfloat16))
    slots, nb = 3, 4
    pools = jax.tree.map(
        lambda x: jax.random.normal(jax.random.key(1), x.shape, x.dtype),
        M.init_paged_pool(mc, slots * nb + 2, 16))
    state = M.init_slot_state(mc, slots)
    state = dict(state, S=jax.random.normal(jax.random.key(2),
                                            state["S"].shape))
    tables = jnp.arange(slots * nb, dtype=jnp.int32).reshape(slots, nb) + 1
    tok = jnp.array([5, 7, 9], jnp.int32)
    pos = jnp.array([37, 3, 50], jnp.int32)
    active = jnp.array([True, False, True])

    def tick():
        return jax.jit(functools.partial(M.decode_step_paged, config=mc))(
            params, pools, tables, tok, pos, active=active, state=state)

    plain = tick()
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert M._paged_attention(pools) == "kernel"
    assert M.serving_grouped_path(mc, slots) == "kernel"
    forced = tick()
    assert int(forced[2]["ssd_live_steps"]) == 2 * mc.n_ssm_layers
    assert int(plain[2]["ssd_live_steps"]) == 0
    live = np.asarray(active)
    lg = [np.asarray(o[0], np.float32)[live] for o in (plain, forced)]
    assert np.abs(lg[0] - lg[1]).max() < 2e-2 * np.abs(lg[0]).max()
    # the first Mamba-2 layer reads the same rows on both paths; the
    # later ones' inputs have been through the other kernels' roundings
    off = np.abs(np.asarray(plain[3]["S"] - forced[3]["S"])).max(
        (1, 2, 3, 4)) / float(jnp.abs(plain[3]["S"]).max())
    assert off[0] < 1e-5 and off.max() < 2e-2, off
    assert np.array_equal(np.asarray(plain[2]["expert_tokens"]),
                          np.asarray(forced[2]["expert_tokens"]))
