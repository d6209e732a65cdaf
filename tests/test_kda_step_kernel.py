"""`ops.kda.kda_step_live` (the decode tick's Pallas step over the whole
stacked state, live slots only, in place) against the form it replaces:
`kda_step` on a layer's rows of all slots, `where(live, new, old)` and
`.at[layer].set`.

CPU, the kernel through the Pallas interpreter.  Tolerance: both forms
are float32 throughout and write the same operations; only the order of
the additions inside the two sums over dk is the compiler's, and that
is worth 1e-7 of a state's size a step (here, and on the chip at the
cell's shape, the two agree to the bit: PERF.md section 6, PR 39).  A
dead slot's state and the other layers' are not within a tolerance but
the SAME BITS: the kernel never writes them.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import attention, kda

LK, B, H, DK, DV = 2, 8, 2, 128, 128
LAYER = 1           # the layer stepped; layer 0 must come through as it was
STEPS = 64
REL = 2e-6

ACTIVE = {
    "all_live": [True] * B,
    "none_live": [False] * B,
    "one_live_in_the_last_slot": [False] * (B - 1) + [True],
    "a_scattered_half": [False, True, True, False, True, False, False, True],
    "no_mask": None,
}


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)


def _inputs():
    ks = jax.random.split(jax.random.key(39), 7)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (STEPS, B, H, DK))) * DK ** -0.5
    k = unit(jax.random.normal(ks[1], (STEPS, B, H, DK)))
    v = jax.random.normal(ks[2], (STEPS, B, H, DV))
    beta = jax.nn.sigmoid(jax.random.normal(ks[3], (STEPS, B, H)))
    # decays from e^-0.001 to e^-12 a token, log-uniform in the rate
    g = -jnp.exp(jax.random.uniform(
        ks[4], (STEPS, B, H, DK), minval=np.log(1e-3), maxval=np.log(12.0)))
    S0 = jax.random.normal(ks[5], (LK, B, H, DK, DV))
    return S0, (q, k, v, g, beta)


@functools.lru_cache(maxsize=None)
def _run(case):
    """(S0, the plain form's stack and outputs, the kernel's) after
    STEPS successive steps of layer LAYER; the interpreter is forced by
    the caller."""
    active = None if ACTIVE[case] is None else jnp.asarray(ACTIVE[case])
    S0, xs = _inputs()
    assert kda.engages(DK, DV, S0.dtype)

    def plain(S, x):
        old = S[LAYER]
        o, new = kda.kda_step(old, *x)
        if active is not None:
            live = active.reshape(-1, 1, 1, 1)
            new, o = jnp.where(live, new, old), jnp.where(live[..., 0], o, 0)
        return S.at[LAYER].set(new), o

    def kernel(S, x):
        o, S = kda.kda_step_live(S, LAYER, *x, kda.live_plan(active, B))
        return S, o

    got = {name: jax.jit(lambda S, xs, f=f: lax.scan(f, S, xs))(S0, xs)
           for name, f in (("plain", plain), ("kernel", kernel))}
    live = np.ones(B, bool) if active is None else np.asarray(active)
    return (np.asarray(S0), live,
            *(tuple(map(np.asarray, got[name]))
              for name in ("plain", "kernel")))


@pytest.mark.parametrize("case", sorted(ACTIVE))
def test_live_slots_step_as_kda_step_does(interpreter, case):
    S0, live, (S_want, o_want), (S_got, o_got) = _run(case)
    assert o_got.shape == (STEPS, B, H, DV) and S_got.shape == S0.shape
    if not live.any():
        return
    want, got = S_want[LAYER][live], S_got[LAYER][live]
    assert np.abs(want - S0[LAYER][live]).max() > 0.1       # it moved
    assert np.abs(got - want).max() <= REL * np.abs(want).max()
    want, got = o_want[:, live], o_got[:, live]
    assert np.abs(want).max() > 0.05
    assert np.abs(got - want).max() <= REL * np.abs(want).max()


@pytest.mark.parametrize("case", sorted(ACTIVE))
def test_dead_slots_and_other_layers_keep_their_bits(interpreter, case):
    S0, live, _, (S_got, o_got) = _run(case)
    np.testing.assert_array_equal(S_got[LAYER][~live], S0[LAYER][~live])
    np.testing.assert_array_equal(S_got[1 - LAYER], S0[1 - LAYER])
    assert not o_got[:, ~live].any()                        # zeros, no NaN


@pytest.mark.parametrize("case", sorted(ACTIVE))
def test_live_plan_lists_the_live_slots_first(case):
    active = ACTIVE[case]
    slots, count = kda.live_plan(
        None if active is None else jnp.asarray(active), B)
    live = np.flatnonzero(np.ones(B, bool) if active is None else active)
    assert slots.dtype == count.dtype == jnp.int32 and count.shape == (1,)
    assert int(count[0]) == len(live)
    np.testing.assert_array_equal(slots[:len(live)], live)
    # the dead ones after them, in slot order: a permutation
    np.testing.assert_array_equal(
        slots[len(live):], np.setdiff1d(np.arange(B), live))


@pytest.mark.parametrize("dk, dv, dtype, forced, want", [
    (128, 128, jnp.float32, True, True),
    (256, 128, jnp.float32, True, True),
    (16, 16, jnp.float32, True, False),         # the audit's heads
    (128, 64, jnp.float32, True, False),
    (96, 384, jnp.float32, True, True),         # two heads of 192 a row
    (96, 192, jnp.float32, True, False),        # unpacked: half a lane row
    (100, 128, jnp.float32, True, False),       # not whole sublanes
    (128, 128, jnp.bfloat16, True, False),      # a state kept in bf16
    (128, 128, jnp.float32, False, False),      # the CPU as it is
])
def test_engages_by_backend_shape_and_dtype_alone(monkeypatch, dk, dv,
                                                  dtype, forced, want):
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", forced)
    assert kda.engages(dk, dv, dtype) is want


# (heads, dk, dv): keys of 96 against values of 192 (two heads a row of
# the stack), the square heads of 128, a tiny odd pair (one head a row)
# and a tiny pair that packs.
SHAPES = {"96x192": (2, 96, 192), "128x128": (2, 128, 128),
          "5x3": (3, 5, 3), "24x64": (4, 24, 64)}
LIVE = {"none": [], "one": [2], "two": [0, 3], "three": [0, 1, 3],
        "all": [0, 1, 2, 3]}


@pytest.mark.parametrize("decay", ["a_head", "a_channel"])
@pytest.mark.parametrize("live", sorted(LIVE))
@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_packed_stack_steps_as_kda_step_does(interpreter, shape, live,
                                             decay):
    """The kernel over the stack as `pack` lays it, at key and value
    sizes that differ, with `g` one number a head or one a channel,
    against `kda_step` on the unpacked rows; write strengths up to 2.
    Dead slots and the other layer keep their bits."""
    h, dk, dv = SHAPES[shape]
    n, steps = 4, 6
    p = kda.heads_a_row(h, dv)
    assert p == {"96x192": 2, "24x64": 2}.get(shape, 1)
    ks = jax.random.split(jax.random.key(40), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (steps, n, h, dk))) * dk ** -0.5
    k = unit(jax.random.normal(ks[1], (steps, n, h, dk)))
    v = jax.random.normal(ks[2], (steps, n, h, dv))
    beta = 2 * jax.nn.sigmoid(jax.random.normal(ks[3], (steps, n, h)))
    g = -jnp.exp(jax.random.uniform(
        ks[4], (steps, n, h, 1 if decay == "a_head" else dk),
        minval=np.log(1e-3), maxval=np.log(4.0)))
    S0 = jax.random.normal(ks[5], (LK, n, h, dk, dv))
    active = jnp.zeros(n, bool).at[jnp.asarray(LIVE[live], int)].set(True)
    plan = kda.live_plan(active, n)

    def plain(S, x):
        o, new = kda.kda_step(S, *x)
        keep = active.reshape(-1, 1, 1, 1)
        return jnp.where(keep, new, S), jnp.where(keep[..., 0], o, 0)

    def kernel(S, x):
        o, S = kda.kda_step_live(S, LAYER, *x, plan)
        return S, o

    xs = (q, k, v, g, beta)
    S_want, o_want = jax.jit(lambda S: lax.scan(plain, S, xs))(S0[LAYER])
    stack, o_got = jax.jit(lambda S: lax.scan(kernel, S, xs))(
        kda.pack(S0, p))
    assert stack.shape == (LK, n, h // p, dk, p * dv)
    S_got = kda.unpack(stack, p)
    mask = np.asarray(active)
    np.testing.assert_array_equal(S_got[LAYER][~mask], S0[LAYER][~mask])
    np.testing.assert_array_equal(S_got[1 - LAYER], S0[1 - LAYER])
    assert not np.asarray(o_got)[:, ~mask].any()
    if mask.any():
        for want, got in ((S_want[mask], S_got[LAYER][mask]),
                          (o_want[:, mask], o_got[:, mask])):
            assert np.abs(got - want).max() <= REL * np.abs(want).max()
        assert np.abs(S_want[mask] - S0[LAYER][mask]).max() > 0.1


def test_pack_lays_heads_side_by_side_and_unpack_undoes_it():
    S = jnp.arange(2 * 4 * 3 * 5, dtype=jnp.float32).reshape(2, 4, 3, 5)
    packed = kda.pack(S, 2)
    assert packed.shape == (2, 2, 3, 10)
    np.testing.assert_array_equal(packed[1, 0, :, 5:], S[1, 1])
    np.testing.assert_array_equal(kda.unpack(packed, 2), S)
    assert kda.pack(S, 1) is S and kda.unpack(S, 1) is S
