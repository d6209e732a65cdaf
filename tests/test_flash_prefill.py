"""`ops.attention.flash_prefill`, the insert's attention over a gathered
history as a Pallas kernel (interpreted here), against
`window_moe._masked_attention` under `_seen`'s mask: through
`blockwise_attention`, the one place that chooses between the kernel and
the XLA loop, with the bounds `piece_walk` gives a piece and keys laid
as `_History` lays them (the full kind's padded history by position, the
window kind's `W` rows out of a ring before the piece's own)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import window_moe as WM
from ray_tpu.ops import attention as A

HD = 128
KEY_BLOCK = 128             # the models' `prefill_key_block` here


@pytest.fixture
def kernel(monkeypatch):
    """The kernel engages (interpreter) from 128 queries and keys on, at
    tiles small enough that a piece of 256 rows is several of them: 128
    queries, 128 keys."""
    monkeypatch.setattr(A, "FORCE_PALLAS_INTERPRET", True)
    monkeypatch.setattr(A, "PREFILL_MIN_Q", 128)
    monkeypatch.setattr(A, "PREFILL_MIN_K", 128)
    monkeypatch.setattr(A, "PREFILL_BLOCK_Q", 128)
    monkeypatch.setattr(A, "PREFILL_BLOCK_K", 128)
    A._flash_prefill.clear_cache()      # its trace read the tiles
    yield
    A._flash_prefill.clear_cache()


def _draw(seed, n, heads, kv_heads):
    ks = jax.random.split(jax.random.key(seed), 3)
    return (jax.random.normal(ks[0], (n, heads, HD)),
            jax.random.normal(ks[1], (n, kv_heads, HD)),
            jax.random.normal(ks[2], (n, kv_heads, HD)))


def _piece(kind, start, Pb, *, W, rows, heads, kv_heads, scale=None):
    """A sequence of start + Pb positions whose last Pb are the piece:
    (what `blockwise_attention` gives for the piece over keys laid as
    `_History` lays them, what `_masked_attention` gives over the
    sequence's own rows).  `rows`: of the padded history (full kind) or
    of the ring (window kind); what they hold beside the sequence's
    rows is LARGE, so a key that leaks shows."""
    n = start + Pb
    q, k, v = _draw(start * 7 + Pb, n, heads, kv_heads)
    stale = jnp.full((rows, kv_heads, HD), 1e3)
    if kind == "full":
        keys = [stale.at[:n].set(x) for x in (k, v)]
        window = None
    else:
        at = np.arange(max(start - rows, 0), start)
        before = (start - W + np.arange(W)) % rows
        keys = [jnp.concatenate([stale.at[at % rows].set(x[at])[before],
                                 x[start:]]) for x in (k, v)]
        window = W
    qpos = start + jnp.arange(Pb)
    kpos0, lo, hi = WM.piece_walk(kind, jnp.int32(start), Pb,
                                  keys[0].shape[0], W, KEY_BLOCK,
                                  most=jnp.maximum)
    got = WM.blockwise_attention(q[start:], *keys, qpos, kpos0, lo, hi,
                                 window, KEY_BLOCK, scale)
    mask = WM._seen(qpos, jnp.arange(n), window)
    want = WM._masked_attention(q[None, start:], k[None], v[None],
                                mask[None], scale)[0]
    return got, want


CASES = {
    # the full kind: [rows] of padded history by position
    "full, start 0": ("full", 0, 256, dict(W=128, rows=1024)),
    "full, a start that is no multiple of a tile":
        ("full", 200, 256, dict(W=128, rows=1024)),
    "full, one query tile": ("full", 384, 128, dict(W=128, rows=512)),
    "full, the history's last rows": ("full", 768, 256,
                                      dict(W=128, rows=1024)),
    # the window kind: W rows out of a ring of [rows], then the piece
    "window, start 0 (every row before the piece masked)":
        ("window", 0, 256, dict(W=128, rows=384)),
    "window, start < W (positions before 0 masked)":
        ("window", 72, 256, dict(W=128, rows=384)),
    "window, start = W": ("window", 128, 256, dict(W=128, rows=384)),
    "window, start past a ring wrap": ("window", 1000, 256,
                                       dict(W=128, rows=384)),
    "window wider than the piece": ("window", 300, 128,
                                    dict(W=256, rows=512)),
}


@pytest.mark.parametrize("heads, kv_heads, scale", [
    (32, 4, None),          # 8 heads a KV head (Trinity-Mini)
    (8, 2, 0.125),          # pairs laid as heads of 128 (models/sambay.py)
], ids=["r8", "pairs"])
@pytest.mark.parametrize("case", list(CASES))
def test_kernel_is_masked_attention(kernel, case, heads, kv_heads, scale):
    kind, start, Pb, sizes = CASES[case]
    S = sizes["rows"] if kind == "full" else sizes["W"] + Pb
    assert A.prefill_engages(Pb, HD, S)
    got, want = _piece(kind, start, Pb, heads=heads, kv_heads=kv_heads,
                       scale=scale, **sizes)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("case", ["full, start 0",
                                  "window, start past a ring wrap"])
def test_loop_is_masked_attention(case):
    """The same pieces through the XLA loop, which nothing forces off."""
    kind, start, Pb, sizes = CASES[case]
    S = sizes["rows"] if kind == "full" else sizes["W"] + Pb
    assert not A.prefill_engages(Pb, HD, S)
    got, want = _piece(kind, start, Pb, heads=8, kv_heads=2, **sizes)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("window", [None, 128], ids=["full", "window"])
def test_tiles_no_query_sees_are_never_read(kernel, window):
    """Key rows outside lo .. hi, past every query's position and, under
    a window, behind every query's window are NaN, in whole tiles: the
    output is finite and what the finite rows alone give."""
    Q, S, off, lo, hi = 256, 1536, 640, 256, 1024
    q, _, _ = _draw(1, Q, 32, 4)
    _, k, v = _draw(2, S, 32, 4)
    rows = np.arange(S)
    # rows some query sees lie in lo .. hi, at or before the last query
    # and, under a window, after the first query's window's start
    first = lo if window is None else max(lo, off - window + 1)
    read = (rows >= first // 128 * 128) & (rows < min(hi, off + Q))
    assert 0 < read.sum() < S - 512
    poisoned = [jnp.where(read[:, None, None], x, jnp.nan) for x in (k, v)]
    got = A.flash_prefill(q, *poisoned, off, lo, hi, window=window)
    assert bool(jnp.isfinite(got).all())
    kpos = jnp.arange(S)
    mask = WM._seen(off + jnp.arange(Q), kpos, window) \
        & (kpos >= lo) & (kpos < hi)
    want = WM._masked_attention(q[None], k[None], v[None], mask[None])[0]
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # and the host's count of tiles is the kernel's: 2 query blocks of
    # 128, each over the key tiles of 128 its span holds
    run = A.prefill_tiles(Q, S, off, lo, hi, window)
    spans = [(max(first, off + b * 128 - (window or S) + 1) // 128,
              min(hi - 1, off + b * 128 + 127) // 128) for b in range(2)]
    assert run == sum(b - a + 1 for a, b in spans) < 2 * (S // 128)


def test_engages_by_backend_and_shape_alone(monkeypatch):
    """Off TPU nothing engages unless a test forces the interpreter;
    forced, Trinity-Mini's two large buckets do and its small ones,
    Phi-4-mini-flash's 1536 key rows, one query (a cross layer's last
    row), heads of 16 (the tiny models) and rows that are no whole
    tiles still take the loop."""
    assert not A.prefill_engages(2048, HD, 4096)
    monkeypatch.setattr(A, "FORCE_PALLAS_INTERPRET", True)
    for Q in (1024, 2048):
        assert A.prefill_engages(Q, HD, 2048 + Q)
        assert A.prefill_engages(Q, HD, 18432)
    for Q in (256, 512):
        assert not A.prefill_engages(Q, HD, 2048 + Q)
        assert not A.prefill_engages(Q, HD, 18432)
    assert not A.prefill_engages(1024, HD, 512 + 1024)
    assert not A.prefill_engages(1, HD, 4096)
    assert not A.prefill_engages(1024, 16, 4096)
    assert not A.prefill_engages(1024, HD, 4000)
    monkeypatch.setattr(A, "PREFILL_MIN_Q", 128)
    monkeypatch.setattr(A, "PREFILL_MIN_K", 128)

    def refuse(*a, **k):
        raise AssertionError("the kernel was called")

    monkeypatch.setattr(A, "flash_prefill", refuse)
    q, k, v = _draw(3, 512, 8, 2)
    for n_q, hd in ((1, HD), (128, 16)):
        qpos = 511 - n_q + 1 + jnp.arange(n_q)
        got = WM.blockwise_attention(
            q[-n_q:, :, :hd], k[..., :hd], v[..., :hd], qpos, 0, 0, 4,
            None, KEY_BLOCK)
        want = WM._masked_attention(
            q[None, -n_q:, :, :hd], k[None, ..., :hd], v[None, ..., :hd],
            WM._seen(qpos, jnp.arange(512), None)[None])[0]
        np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)


def test_walk_tiles_counts_what_the_bounds_let_through():
    """`window_moe.walk_tiles` at Trinity-Mini's sizes (1 full + 4 window
    layers, window 2048, a history of 18,432 rows, tiles of 512 queries
    x 1024 keys): a first piece of 2048 runs 6 of the full layer's 8
    dense pairs and 6 of each window layer's 8 (the rows before position
    0 are no tile's); a piece at 14,336 all 62 of the full layer's 64,
    and 12 of each window layer's 16."""
    layers = {"full": 1, "window": 4}
    assert WM.walk_tiles(layers, 0, 2048, 18432, 2048, 1024, HD) \
        == ("loop", 6 + 4 * 6, 8 + 4 * 8)
    assert WM.walk_tiles(layers, 14336, 2048, 18432, 2048, 1024, HD) \
        == ("loop", 62 + 4 * 12, 64 + 4 * 16)
