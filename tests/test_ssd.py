"""The state-space duality operator (`ops/ssd.py`), the grouped product at
a width of half lane rows (`ops/grouped_matmul.py`) and the non-gated
form of the dropless expert layer (`models/moe.py`), each against its
plain form on seeded random inputs, float32 on the CPU.

Tolerances and their reasons
----------------------------
* 2e-5 ABSOLUTE between the step applied T times, the chunked matrix
  form and the reference's token-by-token recurrence (values of order
  1): the same float32 sums in another order (a chunk's decays are
  `exp` of a difference of cumulative sums where the recurrence
  multiplies step by step); reads 1e-5 and less.  A decay taken to the
  wrong row, a hand-off without its decay or a padded row that writes
  reads 1e-2 and more.
* The Pallas step against `ssd_step`: 1e-5, the same operations on the
  same operands in the interpreter, the `y` sum in another order.
* The grouped kernel against `lax.ragged_dot` in bf16: both accumulate
  in float32 and round once; 2e-2 relative to the largest entry covers
  the rounding of a sum of 192 products to bf16 (2^-8).
"""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from ray_tpu.models import moe
from ray_tpu.ops import attention, grouped_matmul as gm, kda, ssd

ATOL = 2e-5


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def _inputs(B, T, H, P, G, N, seed=0):
    ks = jax.random.split(jax.random.key(seed), 6)
    return dict(
        x=jax.random.normal(ks[0], (B, T, H, P)),
        dt=jax.nn.softplus(jax.random.normal(ks[1], (B, T, H)) - 1.0),
        A=-jnp.exp(jax.random.normal(ks[2], (H,))),
        Bm=jax.random.normal(ks[3], (B, T, G, N)),
        Cm=jax.random.normal(ks[4], (B, T, G, N)),
        S0=jax.random.normal(ks[5], (B, H, N, P)))


def _stepped(i, n_real, S0=None):
    """`ssd_step` a row at a time over the first `n_real[b]` rows."""
    B, T = i["dt"].shape[:2]
    S = i["S0"] if S0 is None else S0
    ys = []
    for t in range(T):
        y, new = ssd.ssd_step(S, i["x"][:, t], i["dt"][:, t], i["A"],
                              i["Bm"][:, t], i["Cm"][:, t])
        S = jnp.where((t < n_real)[:, None, None, None], new, S)
        ys.append(y)
    return jnp.stack(ys, 1), S


@pytest.mark.parametrize("chunk", [128, 16, 8])
def test_chunked_form_is_the_step_applied_row_by_row(chunk):
    """A padded bucket: 40 rows of which 40 and 29 are real; the state
    handed back is the one after the last REAL row."""
    i = _inputs(2, 40, 4, 8, 2, 16)
    n_real = jnp.array([40, 29])
    ys, S = _stepped(i, n_real)
    y, S2 = ssd.ssd_chunked(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"],
                            i["S0"], n_real, chunk=chunk)
    assert np.abs(np.asarray(y[0] - ys[0])).max() < ATOL
    assert np.abs(np.asarray(y[1, :29] - ys[1, :29])).max() < ATOL
    assert np.abs(np.asarray(S2 - S)).max() < ATOL


def test_both_forms_are_the_references_recurrence():
    """The reference keeps the state as published, [P, N], and starts
    from zero."""
    from reference import ssd_moe_decoder as R

    i = _inputs(1, 24, 4, 8, 2, 16, seed=1)
    zero = jnp.zeros_like(i["S0"])
    want_y, want_S = R.ssd_recurrence(i["x"][0], i["dt"][0], i["A"],
                                      i["Bm"][0], i["Cm"][0])
    ys, S = _stepped(i, jnp.array([24]), zero)
    y, S2 = ssd.ssd_chunked(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"], zero,
                            chunk=8)
    for got_y, got_S in ((ys, S), (y, S2)):
        assert np.abs(np.asarray(got_y[0] - want_y)).max() < ATOL
        assert np.abs(np.asarray(jnp.swapaxes(got_S[0], 1, 2)
                                 - want_S)).max() < ATOL


@pytest.mark.parametrize("cut", [21, 16, 37])
def test_a_hand_off_between_calls_at_any_row(cut):
    """Two calls, the second from the first's state, at a row that is
    no multiple of the chunk (21, 37) and at one that is: the whole
    sequence's outputs and state."""
    i = _inputs(1, 40, 4, 8, 2, 16, seed=2)
    whole_y, whole_S = ssd.ssd_chunked(
        i["x"], i["dt"], i["A"], i["Bm"], i["Cm"], i["S0"], chunk=16)
    part = lambda a, lo, hi: a[:, lo:hi]
    # the first call in a padded bucket of 40 rows, `cut` real
    y1, S1 = ssd.ssd_chunked(i["x"], i["dt"], i["A"], i["Bm"], i["Cm"],
                             i["S0"], jnp.array(cut), chunk=16)
    y2, S2 = ssd.ssd_chunked(
        part(i["x"], cut, 40), part(i["dt"], cut, 40), i["A"],
        part(i["Bm"], cut, 40), part(i["Cm"], cut, 40), S1, chunk=16)
    assert np.abs(np.asarray(y1[:, :cut] - whole_y[:, :cut])).max() < ATOL
    assert np.abs(np.asarray(y2 - whole_y[:, cut:])).max() < ATOL
    assert np.abs(np.asarray(S2 - whole_S)).max() < ATOL


def test_step_kernel_steps_the_live_slots_where_they_lie(monkeypatch):
    """Layer 1 of a stack of three, slots 0, 2 and 3 of five live: the
    kernel's rows are `ssd_step`'s, a dead slot's rows and the other
    layers' are bit for bit what they were, a dead slot's `y` is 0."""
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    L, B, H, P, N, G = 3, 5, 8, 64, 16, 2
    i = _inputs(B, 1, H, P, G, N, seed=3)
    stack = jax.random.normal(jax.random.key(9), (L, B, H, N, P))
    p = ssd.heads_a_row(H, P)
    assert p == 2 and ssd.engages(kda.pack(stack, p))
    active = jnp.array([True, False, True, True, False])
    now = (i["x"][:, 0], i["dt"][:, 0], i["A"], i["Bm"][:, 0], i["Cm"][:, 0])
    y, new = ssd.ssd_step_live(kda.pack(stack, p), 1, *now,
                               kda.live_plan(active, B))
    new = kda.unpack(new, p)
    want_y, want_S = ssd.ssd_step(stack[1], *now)
    live = np.asarray(active)
    assert np.abs(np.asarray(y - want_y))[live].max() < 1e-5
    assert np.abs(np.asarray(new[1] - want_S))[live].max() < 1e-5
    assert not np.asarray(y)[~live].any()
    assert np.array_equal(np.asarray(new[1])[~live],
                          np.asarray(stack[1])[~live])
    assert np.array_equal(np.asarray(new[0]), np.asarray(stack[0]))
    assert np.array_equal(np.asarray(new[2]), np.asarray(stack[2]))


@pytest.mark.parametrize("shape, dtype, engages", [
    ((32, 128, 128), jnp.float32, True),    # the published state's pair
    ((32, 128, 128), jnp.bfloat16, False),  # a state kept in bf16
    ((2, 8, 32), jnp.float32, False),       # the tiny model's
])
def test_step_kernel_engages_by_dtype_and_shape_alone(monkeypatch, shape,
                                                      dtype, engages):
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert ssd.engages(jnp.zeros((1, 1) + shape, dtype)) is engages


# ------------------------------------ the grouped product at 64 x odd

def _groups(m, g, seed):
    """`g` group sizes that sum to less than `m`, one of them empty."""
    sizes = np.random.RandomState(seed).multinomial(m - 7, np.ones(g) / g)
    sizes[1] = 0
    return jnp.asarray(sizes, jnp.int32)


@pytest.mark.parametrize("k, n, by_rows", [
    (256, 192, True),       # up: the width 3 x 64 is the OUTPUT, w [G, N, K]
    (192, 256, False),      # down: it is the contraction, w [G, K, N]
    (256, 192, False),      # the padded-in-HBM form still multiplies right
])
def test_grouped_product_at_a_width_of_half_lane_rows(monkeypatch, k, n,
                                                      by_rows):
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    m, g = 80, 6
    assert gm.engages(m, g, k, n, jnp.bfloat16)
    assert gm.col_tile(k, n) == n
    ks = jax.random.split(jax.random.key(4), 2)
    xs = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    w = jax.random.normal(ks[1], (g, n, k) if by_rows else (g, k, n),
                          jnp.bfloat16)
    sizes = _groups(m, g, 5)
    want = lax.ragged_dot(xs, jnp.swapaxes(w, 1, 2) if by_rows else w, sizes)
    got = gm.grouped_matmul(xs, w, sizes, by_rows=by_rows)
    rows = int(sizes.sum())
    off = np.abs(np.asarray(got[:rows] - want[:rows], np.float32)).max()
    assert off <= 2e-2 * np.abs(np.asarray(want, np.float32)).max()


@pytest.mark.parametrize("by_rows", [True, False])
def test_grouped_product_reads_a_bank_through_its_stack(monkeypatch, by_rows):
    """`layer=`: w is a stack of three banks and the traced index picks
    one where it lies; the same rows as the product over that bank cut
    out."""
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    m, g, k, n = 48, 4, 128, 192
    ks = jax.random.split(jax.random.key(10), 2)
    xs = jax.random.normal(ks[0], (m, k), jnp.bfloat16)
    w = jax.random.normal(ks[1], (3, g, n, k) if by_rows else (3, g, k, n),
                          jnp.bfloat16)
    sizes = _groups(m, g, 11)
    rows = int(sizes.sum())
    for layer in (0, 2):
        got = jax.jit(lambda l: gm.grouped_matmul(
            xs, w, sizes, by_rows=by_rows, layer=l))(jnp.int32(layer))
        want = gm.grouped_matmul(xs, w[layer], sizes, by_rows=by_rows)
        assert np.array_equal(np.asarray(got[:rows], np.float32),
                              np.asarray(want[:rows], np.float32))


@pytest.mark.parametrize("k, n, engages", [
    (2688, 1856, True), (1856, 2688, True),     # the published expert
    (2048, 1024, True),                         # whole lane rows, as before
    (64, 32, False), (64, 64, False), (128, 64, False),   # the tiny models'
    (2688, 1850, False),
])
def test_grouped_kernel_engages_by_whole_half_lane_rows(monkeypatch, k, n,
                                                        engages):
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert gm.engages(384, 32, k, n, jnp.bfloat16) is engages
    assert not gm.engages(384, 32, k, n, jnp.float32)


# ------------------------------------ the expert layer without a gate

def _expert_layer(D=32, F=24, E=8, held=8, seed=6):
    ks = jax.random.split(jax.random.key(seed), 5)
    draw = lambda k, *s: 0.3 * jax.random.normal(k, s)
    return {"router": draw(ks[0], D, E),
            "router_bias": 0.02 * jax.random.normal(ks[1], (E,)),
            "w_up": draw(ks[2], held, F, D), "w_down": draw(ks[3], held, F, D)}


def _per_token(x, p, k, scale, lo=0):
    """The layer a token and an expert at a time, over the held experts
    [lo, lo + held)."""
    s = jax.nn.sigmoid(x @ p["router"])
    _, idx = lax.top_k(s + p["router_bias"], k)
    out = np.zeros(x.shape, np.float32)
    for t in range(x.shape[0]):
        chosen = np.asarray(idx[t])
        w = np.asarray(s[t])[chosen]
        w = w / w.sum() * scale
        for e, we in zip(chosen, w):
            if lo <= e < lo + p["w_up"].shape[0]:
                up = np.asarray(p["w_up"][e - lo]) @ np.asarray(x[t])
                out[t] += we * (np.square(np.maximum(up, 0))
                                @ np.asarray(p["w_down"][e - lo]))
    return out


def test_dropless_layer_without_a_gate_is_relu_squared():
    p = _expert_layer()
    x = jax.random.normal(jax.random.key(7), (12, 32))
    live = jnp.arange(12) < 10
    y, sizes = moe.dropless_moe(x, p, moe.sigmoid_bias_top_k(2, 2.5),
                                live=live)
    want = _per_token(x, p, 2, 2.5)
    assert np.abs(np.asarray(y)[:10] - want[:10]).max() < ATOL
    assert not np.asarray(y)[10:].any()
    assert int(sizes.sum()) == 10 * 2


def test_the_gated_layer_still_reads_its_gate():
    """The same bank with a `w_gate` beside it is SwiGLU, `w_up` [E, D,
    F]: what `params` holds decides the form."""
    p = _expert_layer()
    gated = dict(p, w_gate=jnp.swapaxes(p["w_up"], 1, 2),
                 w_up=jnp.swapaxes(p["w_up"], 1, 2))
    x = jax.random.normal(jax.random.key(7), (12, 32))
    rule = moe.sigmoid_bias_top_k(2, 2.5)
    y_plain, _ = moe.dropless_moe(x, p, rule)
    y_gated, _ = moe.dropless_moe(x, gated, rule)
    assert np.abs(np.asarray(y_plain - y_gated)).max() > 1e-3


def test_the_shares_add_up_to_the_whole_layer():
    """Four shares of two experts each: their routed parts, with the
    shared expert counted once, are the uncut reference's whole layer
    (`reference/ssd_moe_decoder.py::layer` with all 8 held)."""
    from reference import ssd_moe_decoder as R

    c = dict(hidden_size=32, mamba_num_heads=2, mamba_head_dim=8, n_groups=1,
             ssm_state_size=4, conv_kernel=4, num_attention_heads=2,
             num_key_value_heads=1, head_dim=16, moe_intermediate_size=24,
             moe_shared_expert_intermediate_size=16, n_shared_experts=1,
             n_routed_experts=8, num_experts_per_tok=3,
             routed_scaling_factor=2.5, vocab_size=64,
             layer_norm_epsilon=1e-5, hybrid_override_pattern="E",
             initializer_range=0.3, router_bias_scale=0.02)
    w = R._init_layer(c, "E", jax.random.key(8), jnp.float32)
    bank = R.expert_bank(w["experts"])
    x = jax.random.normal(jax.random.key(9), (10, 32))
    want = R.layer(c, "E", x, w)[0] - x
    h = R._rms(x, w["norm"], 1e-5)
    rule = moe.sigmoid_bias_top_k(3, 2.5)
    total = R._relu2(h, w["ws_up"], w["ws_down"])
    touched = 0
    for rank in range(4):
        held = {k: v[2 * rank:2 * rank + 2] for k, v in bank.items()}
        y, sizes = moe.dropless_moe(h, dict(w, **held), rule,
                                    share=(rank, 4))
        total = total + y
        touched += int(sizes.sum())
    assert touched == 10 * 3
    assert np.abs(np.asarray(total - want)).max() < ATOL
