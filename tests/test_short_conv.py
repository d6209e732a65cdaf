"""`ops/short_conv.py`: the one step every model with a convolution's
tail takes (`step_in_place` over the stack `[L', B, (K-1) C]`, a slot's
taps side by side in the lanes of its one row) against the sequence form
`short_conv`, which inserts take, and against the product it replaced."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import short_conv as sc

# the four families' (taps, channels) cut small: Mamba's `d_inner` and
# Mamba-2's `conv_width` (4 taps), the gated convolution's `dim` (3 taps),
# the delta rule's q ‖ k ‖ v (4 taps, three lane tiles)
SIZES = [(4, 256), (3, 128), (4, 384)]
L, B, LAYER, N = 3, 6, 1, 7


def _einsum_step(x, w, tail):
    """The step as it was written out in four models until PR 60: the
    tail `[B, K-1, C]`, a concatenate to `[B, K, C]` and a product."""
    xx = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    return jnp.einsum("bkc,kc->bc", xx, w.astype(x.dtype)), xx[:, 1:]


def _case(K, C, dtype, seed=0):
    """Rows whose products a float32 holds exactly (bf16 values), so
    that a fused multiply-add and a multiply and an add agree."""
    ks = jax.random.split(jax.random.key(seed + 10 * K + C), 3)
    bf = lambda k, *s: jax.random.normal(k, s, jnp.bfloat16).astype(dtype)
    return bf(ks[0], B, N, C), bf(ks[1], K, C), bf(ks[2], L, B, (K - 1) * C)


@pytest.mark.parametrize("K, C", SIZES)
def test_steps_from_a_zero_tail_are_the_sequence_form(K, C):
    """`N` steps of the live slots from a zero tail equal `short_conv`
    over the same `N` rows, outputs and final tail (re-laid); the other
    layers' rows and a dead slot's row are bit for bit what they were."""
    x, w, garbage = _case(K, C, jnp.float32)
    active = jnp.asarray([True, False, True, True, False, True])
    tails = garbage.at[LAYER].set(
        jnp.where(active[:, None], 0.0, garbage[LAYER]))
    step = jax.jit(sc.step_in_place)
    ys, now = [], tails
    for t in range(N):
        y, now = step(now, LAYER, x[:, t], w, active)
        ys.append(y)
    want_y, want_tail = sc.short_conv(
        x, w, jnp.zeros((B, K - 1, C)), jnp.full((B,), N))
    live = np.asarray(active)
    got_y = np.asarray(jnp.stack(ys, 1))
    assert np.abs(np.asarray(want_y)).max() > 1.0
    np.testing.assert_allclose(got_y[live], np.asarray(want_y)[live],
                               atol=1e-5)
    assert np.array_equal(np.asarray(sc.rows(now[LAYER], w))[live],
                          np.asarray(want_tail)[live])
    assert np.array_equal(np.asarray(now[LAYER])[~live],
                          np.asarray(tails[LAYER])[~live])
    others = np.arange(L) != LAYER
    assert np.array_equal(np.asarray(now)[others], np.asarray(tails)[others])
    assert now.shape == tails.shape and now.dtype == tails.dtype
    # `flat` and `rows` are each other's inverse
    assert np.array_equal(np.asarray(sc.flat(sc.rows(now, w))),
                          np.asarray(now))


@pytest.mark.parametrize("K, C", SIZES)
def test_traced_and_static_layer_index_agree(K, C):
    """`j` a Python int (`kimi_linear`, `conv_moe`: unrolled layers) or
    a traced scalar (`sambay`, `jamba`, `nemotron_h`: a `lax.scan` over
    layers) is the same step; no `active` means every slot is live."""
    x, w, tails = _case(K, C, jnp.bfloat16, seed=1)
    active = jnp.asarray([True, True, False, True, False, False])
    static = jax.jit(lambda t, x: sc.step_in_place(t, 2, x, w, active))
    traced = jax.jit(lambda t, j, x: sc.step_in_place(t, j, x, w, active))
    (ya, ta), (yb, tb) = static(tails, x[:, 0]), traced(tails, jnp.int32(2),
                                                        x[:, 0])
    assert jnp.array_equal(ya, yb) and jnp.array_equal(ta, tb)
    assert ya.dtype == jnp.bfloat16 and ta.dtype == tails.dtype
    yc, tc = sc.step_in_place(tails, 2, x[:, 0], w)
    yd, td = sc.step_in_place(tails, 2, x[:, 0], w, jnp.ones((B,), bool))
    assert jnp.array_equal(yc, yd) and jnp.array_equal(tc, td)
    assert jnp.array_equal(yc, ya)        # a dead slot's output is computed
    assert not jnp.array_equal(tc, ta)    # ... and its row is not shifted


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("K, C", SIZES)
def test_step_is_the_product_it_replaced(K, C, dtype):
    """K multiply-adds of lane slices, float32 and rounded once, against
    `einsum("bkc,kc->bc")` over the concatenated `[B, K, C]`: the same
    float32 to the last bit (on rows whose products float32 holds
    exactly: the CPU's product fuses its multiply-adds), within one bf16
    rounding in bf16; the shifted tail the same rows."""
    dtype = jnp.dtype(dtype)
    x, w, tails = _case(K, C, dtype, seed=2)
    want_y, want_tail = _einsum_step(x[:, 0], w, sc.rows(tails[LAYER], w))
    y, now = jax.jit(sc.step_in_place)(tails, LAYER, x[:, 0], w)
    assert y.dtype == dtype and np.abs(np.asarray(want_y, np.float32)).max() > 1
    assert jnp.array_equal(sc.rows(now[LAYER], w), want_tail)
    if dtype == jnp.float32:
        assert jnp.array_equal(y, want_y)
    else:
        got, want = (np.asarray(a, np.float32) for a in (y, want_y))
        assert np.all(np.abs(got - want) <= 2.0 ** -8 * np.abs(want))


def test_a_width_that_is_no_lane_tile_computes_the_same():
    """Channels that are not whole lane tiles (5, the sizes
    `tests/test_conv_moe.py` uses) take the same step, only slower on
    the chip."""
    K, C = 3, 5
    ks = jax.random.split(jax.random.key(3), 3)
    x = jax.random.normal(ks[0], (2, C))
    w = jax.random.normal(ks[1], (K, C))
    tail = jax.random.normal(ks[2], (2, K - 1, C))
    want_y, want_tail = _einsum_step(x, w, tail)
    y, now = sc.step_in_place(sc.flat(tail)[None], 0, x, w)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want_y), atol=1e-6)
    assert jnp.array_equal(sc.rows(now[0], w), want_tail)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("K, C", SIZES)
def test_kernel_is_the_plain_step(monkeypatch, K, C, dtype):
    """The Pallas form the chip takes (`engages`: taps on lane tiles,
    slots in whole sublane tiles), driven through the interpreter: bit
    for bit the plain step, outputs and stack, at a traced and a static
    layer, with dead slots and with none; the other layers' rows come
    back untouched through the aliased stack."""
    from ray_tpu.ops import attention

    dtype = jnp.dtype(dtype)
    slots = 48                    # three blocks of 16 rows (bf16), six of 8
    ks = jax.random.split(jax.random.key(K * C), 4)
    bf = lambda k, *s: jax.random.normal(k, s, jnp.bfloat16).astype(dtype)
    x, w = bf(ks[0], slots, C), bf(ks[1], K, C)      # exact products
    tails = bf(ks[2], L, slots, (K - 1) * C)
    active = jax.random.uniform(ks[3], (slots,)) < 0.6
    assert not sc.engages(tails, w)                 # the CPU's own form
    plain = [sc.step_in_place(tails, LAYER, x, w, a) for a in (active, None)]
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert sc.engages(tails, w)
    assert not sc.engages(tails[:, :5], w)          # slots: no whole tile
    assert not sc.engages(tails[..., :(K - 1) * 5], w[:, :5])   # nor lanes
    assert slots % sc._block_rows(slots, (K - 1) * C, dtype.itemsize) == 0
    for (want_y, want), a in zip(plain, (active, None)):
        step = lambda t, j: sc.step_in_place(t, j, x, w, a)
        for y, now in (jax.jit(step, static_argnums=1)(tails, LAYER),
                       jax.jit(step)(tails, jnp.int32(LAYER))):
            assert jnp.array_equal(y, want_y) and jnp.array_equal(now, want)
    assert not jnp.array_equal(plain[0][1], plain[1][1])


@pytest.mark.parametrize("slots, width, itemsize, rows", [
    (512, 3 * 5120, 2, 16),     # tutor: 30 KB a slot, 32 blocks a layer
    (256, 2 * 2048, 2, 32),     # compose: an eighth of the slots
    (96, 3 * 8192, 2, 16),      # think: one tile, however wide the row
    (24, 3 * 128, 4, 8)])
def test_kernel_blocks_are_whole_tiles_that_divide_the_slots(
        slots, width, itemsize, rows):
    assert sc._block_rows(slots, width, itemsize) == rows
