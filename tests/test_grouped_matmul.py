"""`ops.grouped_matmul` (the experts' grouped product) against the path
it replaces, `lax.ragged_dot`.

CPU, the kernel through the Pallas interpreter.  Tolerance: operands are
bf16, both paths accumulate in float32 and round once, to bf16; against
`lax.ragged_dot` on the same operands in float32 throughout, a bf16
output of size about 1 is within 1 ulp of a value in [1, 2) (2 ** -7),
and the two bf16 paths agree to the bit on the chip (PERF.md section 6,
PR 33) and to that ulp here (XLA:CPU sums K in another order).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops import attention, grouped_matmul as gm

ATOL = 2 ** -6


@pytest.fixture
def interpreter(monkeypatch):
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)


def _spread(rng, rows, groups, touched):
    """`rows` rows over `touched` of `groups` groups, each >= 1."""
    sizes = np.zeros(groups, np.int64)
    pick = rng.choice(groups, touched, replace=False)
    sizes[pick] = 1 + rng.multinomial(rows - touched,
                                      np.ones(touched) / touched)
    return sizes


# (M, G, K, N) of the three cells' ticks scaled down by 8 (K and N kept
# in whole lane rows), and what the walk has to get right
def _cases():
    rng = np.random.default_rng(33)
    return {
        # compose 1024 x 32, 2048 -> 1792: most rows live, every group
        "compose_tick": (128, 32, 256, 256, _spread(rng, 83, 32, 32)),
        # assistant 384 x 128, 2048 -> 768: half the groups empty, M not
        # a multiple of the 128-row tile it would take at full size
        "assistant_tick": (48, 16, 256, 128, _spread(rng, 24, 16, 8)),
        # agent 1024 x 64 with `share`: three quarters in no group
        "agent_tick": (256, 8, 384, 128, _spread(rng, 60, 8, 6)),
        "agent_back": (256, 8, 128, 384, _spread(rng, 60, 8, 6)),
        "one_group_holds_every_row": (256, 4, 128, 128, [0, 0, 256, 0]),
        "no_rows_at_all": (64, 4, 128, 128, [0, 0, 0, 0]),
        # 128-row tiles: group 1 spans three of them, group 3 starts
        # inside the tile group 1 ends in
        "straddles_row_tiles": (400, 4, 128, 256, [50, 230, 0, 100]),
        "m_not_a_multiple_of_the_tile": (200, 4, 128, 128,
                                         [17, 33, 100, 41]),
        # N wider than one 8 MiB block allows: two column tiles
        "two_column_tiles": (64, 2, 4096, 2048, [40, 20]),
    }


CASES = _cases()


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot(interpreter, case):
    M, G, K, N, sizes = CASES[case]
    rng = np.random.default_rng(len(case))
    xs = jnp.asarray(rng.standard_normal((M, K)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((G, K, N)) / np.sqrt(K),
                    jnp.bfloat16)
    sizes = jnp.asarray(sizes, jnp.int32)
    n = int(sizes.sum())
    assert gm.engages(M, G, K, N, xs.dtype)
    # the tail of x is poison: rows in no group reach no row in one
    xs = xs.at[n:].set(jnp.nan)
    got = np.asarray(gm.grouped_matmul(xs, w, sizes), np.float32)
    exact = np.asarray(lax.ragged_dot(
        jnp.nan_to_num(xs).astype(jnp.float32), w.astype(jnp.float32),
        sizes))
    same = np.asarray(lax.ragged_dot(jnp.nan_to_num(xs), w, sizes),
                      np.float32)
    assert got.shape == (M, N)
    assert np.isfinite(got[:n]).all()
    if n:
        assert np.abs(exact[:n]).max() > 1.0
        assert np.abs(got[:n] - exact[:n]).max() <= ATOL
        assert np.abs(got[:n] - same[:n]).max() <= ATOL
    if case == "two_column_tiles":
        assert gm.col_tile(K, N) == 1024


def test_plan_visits_every_live_tile_of_every_live_group_once():
    """The walk itself, with no kernel: visits in row order, a group
    once a tile it has rows in, an empty group never."""
    sizes = np.asarray([50, 230, 0, 100, 0, 3])
    tm, m = 128, 512
    n, group, tile, lo, hi = (np.asarray(a) for a in gm.plan(
        jnp.asarray(sizes, jnp.int32), m, tm))
    assert group.shape == (m // tm + len(sizes) - 1,)
    want = []
    start = 0
    for g, size in enumerate(sizes):
        for t in range(start // tm, -(-(start + size) // tm) if size else 0):
            want.append((g, t, start, start + size))
        start += size
    assert int(n[0]) == len(want) == 6
    got = list(zip(group, tile, lo, hi))[:len(want)]
    assert [tuple(int(a) for a in v) for v in got] == want
    # and with nothing to do
    n, group, tile, _, _ = gm.plan(jnp.zeros((4,), jnp.int32), 64, 16)
    assert int(n[0]) == 0 and not np.asarray(tile).any()


def test_engages_by_backend_dtype_and_shape(monkeypatch):
    bf16, f32 = jnp.bfloat16, jnp.float32
    assert not gm.engages(1024, 32, 2048, 1792, bf16)        # the CPU
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
    assert gm.engages(1024, 32, 2048, 1792, bf16)
    assert gm.engages(384, 128, 768, 2048, bf16)
    assert gm.engages(16384, 64, 2304, 1024, bf16)
    assert not gm.engages(1024, 32, 2048, 1792, f32)
    assert not gm.engages(64, 8, 128, 32, bf16)      # the tiny models'
    assert not gm.engages(64, 8, 64, 128, bf16)
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", False)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert gm.engages(1024, 32, 2048, 1792, bf16)
    assert not gm.engages(1024, 32, 2048, 1792, f32)


def test_the_tiles_come_from_the_shape_alone():
    assert gm.row_tile(1024) == gm.row_tile(16384) == 128
    assert gm.row_tile(384) == 128 and gm.row_tile(48) == 48
    assert gm.row_tile(40) == 48                 # whole packed tiles
    # every expert matrix of the three cells is one block
    for k, n in ((2048, 1792), (1792, 2048), (2048, 768), (768, 2048),
                 (2304, 1024), (1024, 2304)):
        assert gm.col_tile(k, n) == n
    assert gm.col_tile(8192, 4096) == 512


def test_a_derivative_is_ragged_dots(interpreter):
    """No caller differentiates the serving programs; one that did gets
    `lax.ragged_dot`'s derivative, not a silent zero or an error deep
    in Pallas."""
    rng = np.random.default_rng(2)
    xs = jnp.asarray(rng.standard_normal((64, 128)), jnp.bfloat16)
    w = jnp.asarray(rng.standard_normal((4, 128, 128)), jnp.bfloat16)
    sizes = jnp.asarray([10, 0, 30, 5], jnp.int32)
    live = (jnp.arange(64) < 45)[:, None]

    def loss(product):
        return lambda a, b: jnp.where(
            live, product(a, b, sizes), 0).astype(jnp.float32).sum()

    got = jax.grad(loss(gm.grouped_matmul), (0, 1))(xs, w)
    want = jax.grad(loss(lax.ragged_dot), (0, 1))(xs, w)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


@pytest.mark.parametrize("how", ["live", "share"])
def test_the_tail_never_reaches_a_token(interpreter, how):
    """`dropless_moe` with the kernel: rows in no group (dead tokens, or
    assignments to experts held elsewhere) sort last, their x is NaN
    here, and what the kernel leaves in their rows (tiles it never
    visited hold whatever was there) reaches no token."""
    from ray_tpu.models import moe

    rng = np.random.default_rng(4)
    T, D, F, E, k = 48, 128, 128, 8, 2
    held = E // 2 if how == "share" else E
    p = {"router": jnp.asarray(rng.standard_normal((D, E)), jnp.float32),
         "w_gate": jnp.asarray(rng.standard_normal((held, D, F)) * 0.1,
                               jnp.bfloat16),
         "w_up": jnp.asarray(rng.standard_normal((held, D, F)) * 0.1,
                             jnp.bfloat16),
         "w_down": jnp.asarray(rng.standard_normal((held, F, D)) * 0.1,
                               jnp.bfloat16)}
    x = jnp.asarray(rng.standard_normal((T, D)), jnp.bfloat16)
    live = jnp.asarray(rng.random(T) < 0.5)
    kw = dict(live=live, share=(1, 2) if how == "share" else None)
    assert moe.grouped_path(T * k, p["w_gate"].shape, x.dtype) == "kernel"
    y, sizes = moe.dropless_moe(
        jnp.where(live[:, None], x, jnp.nan), p, moe.softmax_top_k(k), **kw)
    attention.FORCE_PALLAS_INTERPRET = False     # the fixture restores it
    want, want_sizes = moe.dropless_moe(x, p, moe.softmax_top_k(k), **kw)
    np.testing.assert_array_equal(np.asarray(sizes), np.asarray(want_sizes))
    assert 0 < int(sizes.sum()) < T * k
    y, want = np.asarray(y, np.float32), np.asarray(want, np.float32)
    assert np.isfinite(y).all() and not y[~np.asarray(live)].any()
    assert np.abs(want).max() > 0.05
    assert np.abs(y - want).max() <= 2 ** -8 * max(1.0, np.abs(want).max())
