"""`ops.paged_attention` (the decode tick's kernel) against the path it
replaces: the block-table gather + `models.llama._decode_attention`.

CPU, the kernel through the Pallas interpreter.  Tolerance: operands
are bf16, the kernel keeps scores, softmax and accumulation in float32
and rounds once, to bf16, at the end; the gather path rounds the scores
to bf16 BEFORE the softmax and the probabilities after it.  On
unit-normal queries, keys and values the two differ by at most 2 ulp of
a bf16 output of size 1 (2 ** -6 = 0.0156); each is within 0.012 of the
same attention computed in float32 throughout, the kernel the closer.
"""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import kimi_linear, latent_moe as LM
from ray_tpu.models.llama import (
    LlamaConfig, _decode_attention, decode_step_paged, init_paged_kv_cache,
    init_params, verify_kv_paged,
)
from ray_tpu.ops import attention, paged_attention as pa

ATOL = 2 ** -6          # 2 ulp of a bf16 value in [1, 2)
D, BS, NB_ROW, L, LAYER, CHUNK = 128, 16, 8, 3, 2, 3
# dead, one row, a whole block, a block and a row, a full table row
LENGTHS = (0, 1, 16, 17, NB_ROW * BS)
# the last three: more than 4 KV heads that are not whole sublane tiles,
# whose blocks the kernel views head by head (as many K/V heads as query
# heads at 30 and at a tiny 6; groups of two at 12)
GROUPS = {"rep4": (8, 2), "rep1": (4, 4), "mqa": (4, 1),
          "mha30": (30, 30), "mha6": (6, 6), "rep2_kv12": (24, 12)}


def _case(heads, kv_heads, n_q, seed):
    """A pool, queries, a table of distinct blocks a sequence, and the
    queries' positions: the last query of a live sequence sits on its
    last row."""
    rng = np.random.default_rng(seed)
    B, NB = len(LENGTHS), len(LENGTHS) * NB_ROW + 4
    pool = lambda: jnp.asarray(                               # noqa: E731
        rng.standard_normal((L, NB, BS, kv_heads, D)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, n_q, heads, D)), jnp.bfloat16)
    tables = rng.permutation(NB - 4)[:B * NB_ROW].reshape(
        B, NB_ROW).astype(np.int32)
    lengths = np.maximum(np.asarray(LENGTHS), (np.asarray(LENGTHS) > 0)
                         * n_q)         # n_q queries need n_q rows
    last = np.maximum(lengths - 1, 0)
    qpos = np.maximum(last[:, None] - (n_q - 1) + np.arange(n_q)[None],
                      0).astype(np.int32)
    return q, pool(), pool(), tables, qpos, lengths


def _reference(q, k_pool, v_pool, tables, qpos, dtype=None):
    B, nb = tables.shape
    kvh = k_pool.shape[3]
    k = k_pool[LAYER][tables].reshape(B, nb * BS, kvh, D)
    v = v_pool[LAYER][tables].reshape(B, nb * BS, kvh, D)
    if dtype is not None:
        q, k, v = q.astype(dtype), k.astype(dtype), v.astype(dtype)
    return np.asarray(_decode_attention(q, k, v, jnp.asarray(qpos)),
                      np.float32)


def _kernel(q, k_pool, v_pool, tables, qpos, active, chunk=CHUNK):
    scalars = pa.plan(jnp.asarray(tables), jnp.asarray(qpos),
                      None if active is None else jnp.asarray(active),
                      BS, chunk)
    return np.asarray(pa.paged_attention(
        q, k_pool, v_pool, jnp.int32(LAYER), scalars, chunk=chunk),
        np.float32)


def _spoil(tables, lengths, how, nb_total):
    """Every table entry the kernel has no business with: entries past
    a sequence's last live block, and every entry of a dead row."""
    dirty = tables.copy()
    for b, n in enumerate(lengths):
        live = -(-int(n) // BS)
        if how == "out_of_range":
            dirty[b, live:] = [nb_total + 7, 2 ** 30, -1][b % 3]
        elif how == "other_sequences":
            dirty[b, live:] = tables[(b + 1) % len(lengths), 0]
        elif how == "poisoned":
            dirty[b, live:] = nb_total - 1      # a block of NaN
    return dirty


@pytest.mark.parametrize("n_q", [1, 3])
@pytest.mark.parametrize("group", sorted(GROUPS))
def test_kernel_matches_the_gather_path(group, n_q):
    """Every length class at once (dead, 1, 16, 17, a full row), a layer
    index that is not 0, chunks of 3 blocks so that a sequence ends
    inside a chunk, at a chunk's end and after several."""
    q, kp, vp, tables, qpos, lengths = _case(*GROUPS[group], n_q, seed=n_q)
    active = lengths > 0
    got = _kernel(q, kp, vp, tables, qpos, active)
    want = _reference(q, kp, vp, tables, qpos)
    exact = _reference(q, kp, vp, tables, qpos, jnp.float32)
    assert not got[~active].any()               # a dead slot: zeros
    assert np.abs(got[active] - want[active]).max() <= ATOL
    # no further from float32 throughout than the path it replaces
    assert (np.abs(got[active] - exact[active]).max()
            <= np.abs(want[active] - exact[active]).max() + 2 ** -8)


@pytest.mark.parametrize("how", ["out_of_range", "other_sequences",
                                 "poisoned"])
@pytest.mark.parametrize("n_q", [1, 3])
def test_entries_past_a_length_change_nothing(n_q, how):
    """`engine.py` never clears a freed slot's table row, so entries
    past a length and in dead rows hold stale and out-of-range ids: the
    output is the clean table's to the bit, also when they name a block
    of NaN (no such row reaches a sum, masked or not)."""
    q, kp, vp, tables, qpos, lengths = _case(8, 2, n_q, seed=7)
    nb_total = kp.shape[1]
    kp, vp = (x.at[:, nb_total - 1].set(jnp.nan) for x in (kp, vp))
    active = lengths > 0
    clean = _kernel(q, kp, vp, tables, qpos, active)
    dirty = _kernel(q, kp, vp, _spoil(tables, lengths, how, nb_total),
                    qpos, active)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


@pytest.mark.parametrize("n_q", [1, 3])
def test_only_live_blocks_are_copied(monkeypatch, n_q):
    """Every copy the kernel starts, recorded: exactly the live blocks
    of the live sequences at the layer asked for, K and V once each,
    and no table entry beyond them is ever dereferenced."""
    q, kp, vp, tables, qpos, lengths = _case(8, 2, n_q, seed=11)
    dirty = _spoil(tables, lengths, "out_of_range", kp.shape[1])
    seen = []
    block_copy = pa._block_copy

    def recording(pool, layer, phys, *rest):
        if not isinstance(phys, int):           # a start, not a wait
            jax.debug.callback(
                lambda l, p: seen.append((int(l), int(p))), layer, phys)
        return block_copy(pool, layer, phys, *rest)

    monkeypatch.setattr(pa, "_block_copy", recording)
    _kernel(q, kp, vp, dirty, qpos, lengths > 0)
    jax.effects_barrier()
    want = sorted((LAYER, int(tables[b, j]))
                  for b, n in enumerate(lengths)
                  for j in range(-(-int(n) // BS))) * 2
    assert sorted(seen) == sorted(want)


def test_all_live_without_a_mask_and_one_chunk_a_row():
    """`active=None` is every slot live; a chunk as long as the table
    row is one item a sequence."""
    q, kp, vp, tables, qpos, lengths = _case(8, 2, 1, seed=3)
    lengths = np.where(lengths > 0, lengths, 5)
    qpos = (lengths - 1)[:, None].astype(np.int32)
    got = _kernel(q, kp, vp, tables, qpos, None, chunk=64)
    assert np.abs(got - _reference(q, kp, vp, tables, qpos)).max() <= ATOL


# ------------------------------------------------- through the model's steps

def _small(**kw):
    return LlamaConfig.tiny(
        vocab_size=128, dim=1024, n_layers=2, n_heads=8, n_kv_heads=8,
        hidden_dim=256, max_seq_len=128, param_dtype=jnp.bfloat16, **kw)


@pytest.fixture
def forced(monkeypatch):
    """The test hook `ops.attention` has: run the kernels through the
    interpreter off TPU."""
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)


def test_the_selector_is_backend_and_shape_alone(monkeypatch):
    pool = lambda kvh, d, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 8, 16, kvh, d), dt)
    assert not pa.engages(pool(8, 128))             # the CPU, not forced
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert pa.engages(pool(8, 128)) and pa.engages(pool(32, 256))
    assert pa.engages(pool(1, 128))                 # MQA: 16 rows a block
    assert not pa.engages(pool(8, 16))              # the rehearsal's heads
    assert not pa.engages(pool(8, 128, jnp.float32))
    assert not pa.engages(jax.ShapeDtypeStruct(     # 8 rows: half a tile
        (2, 8, 4, 2, 128), jnp.bfloat16))


@pytest.mark.parametrize("step", ["decode", "verify"])
def test_model_steps_agree_on_both_paths(forced, monkeypatch, step):
    """`decode_step_paged` and `verify_kv_paged` (K = 3) over a pool
    with history: the kernel's logits against the gather path's, the
    same pools written, the same greedy tokens."""
    c = _small()
    params = init_params(c, jax.random.key(0))
    rng = np.random.default_rng(5)
    B, nb, NB = 3, c.max_seq_len // BS, 30
    pools = jax.tree.map(
        lambda x: jnp.asarray(rng.standard_normal(x.shape) * 0.5, x.dtype),
        init_paged_kv_cache(c, NB, BS))
    tables = jnp.asarray(rng.permutation(NB)[:B * nb].reshape(B, nb),
                         jnp.int32)
    pos = jnp.asarray([37, 0, 90], jnp.int32)
    active = jnp.asarray([True, False, True])
    if step == "decode":
        tok = jnp.asarray(rng.integers(0, c.vocab_size, B), jnp.int32)
        fn = lambda: decode_step_paged(                       # noqa: E731
            params, pools, tables, tok, pos, c, active)
    else:
        tok = jnp.asarray(rng.integers(0, c.vocab_size, (B, 3)), jnp.int32)
        fn = lambda: verify_kv_paged(                         # noqa: E731
            params, pools, tables, tok, pos, c, active)
    assert pa.engages(pools["k"])
    kernel_logits, kernel_pools = jax.jit(fn)()
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", False)
    assert not pa.engages(pools["k"])
    gather_logits, gather_pools = jax.jit(fn)()
    live = np.asarray(active)
    a, b = (np.asarray(x, np.float32)[live]
            for x in (kernel_logits, gather_logits))
    assert np.abs(a - b).max() <= 0.05 * np.abs(b).max()
    np.testing.assert_array_equal(a.argmax(-1), b.argmax(-1))
    # layer 0 writes the same rows on both paths; deeper layers' rows
    # carry the attention's rounding
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(
            np.asarray(kernel_pools[leaf][0], np.float32),
            np.asarray(gather_pools[leaf][0], np.float32))


@pytest.mark.parametrize("seed", [0, 1])
def test_engine_serves_the_same_greedy_tokens_on_both_paths(
        forced, monkeypatch, seed):
    """A small `LLMEngine` (heads of 128, 8 KV heads: shapes that tile)
    serves three prompts of different lengths beside each other; the
    tokens through the kernel equal the gather path's, and `stats()`
    names the path and counts the live rows."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    c = _small()
    params = init_params(c, jax.random.key(seed))
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(1, c.vocab_size, n).tolist() for n in (5, 19, 40)]

    def serve():
        eng = LLMEngine(params, c, EngineConfig(
            num_slots=4, max_seq_len=128, prefill_buckets=(16, 32, 64),
            kv_block_size=BS, prefix_cache=False))
        handles = [eng.submit(Request(prompt=p, max_tokens=6))
                   for p in prompts]
        for _ in range(200):
            if all(h.finished_at is not None for h in handles):
                break
            eng.step()
        return [h.tokens for h in handles], eng.stats()

    kernel_tokens, kernel_stats = serve()
    monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", False)
    gather_tokens, gather_stats = serve()
    assert kernel_tokens == gather_tokens
    assert all(len(t) == 6 for t in kernel_tokens)
    assert kernel_stats["paged_attention"] == "kernel"
    assert gather_stats["paged_attention"] == "gather"
    for stats in (kernel_stats, gather_stats):
        assert 0 < stats["live_rows"] < stats["padded_rows"]
        assert stats["padded_rows"] % (4 * 128) == 0
    assert kernel_stats["live_rows"] == gather_stats["live_rows"]


# ----------------------------------------------------- the latent pool's form

# 32 heads on one row a token: latent 512 ‖ shared key 64 ‖ zeros to 640
LAT = LM.LatentMoEConfig.tiny(
    n_heads=32, kv_lora_rank=512, qk_nope_head_dim=16, qk_rope_head_dim=64,
    v_head_dim=16, dtype=jnp.bfloat16)
# dead; ends inside a block; inside a chunk; past one chunk; a full row
LAT_LENGTHS = (0, 5, 2 * BS + 3, CHUNK * BS + 9, NB_ROW * BS)


def _latent_case(seed, c=LAT):
    """A latent pool whose pad lanes are zero as the model writes them,
    one query a sequence on its last row, `wkv_b`, distinct blocks."""
    rng = np.random.default_rng(seed)
    B, NB = len(LAT_LENGTHS), len(LAT_LENGTHS) * NB_ROW + 4
    real = c.kv_lora_rank + c.qk_rope_head_dim
    pool = rng.standard_normal((L, NB, BS, c.cache_row))
    pool[..., real:] = 0
    n = c.qk_nope_head_dim
    q_nope = jnp.asarray(rng.standard_normal((B, 1, c.n_heads, n)),
                         jnp.bfloat16)
    q_rope = jnp.asarray(
        rng.standard_normal((B, 1, c.n_heads, c.qk_rope_head_dim)),
        jnp.bfloat16)
    wkv_b = jnp.asarray(rng.standard_normal(
        (c.kv_lora_rank, c.n_heads * (n + c.v_head_dim)))
        * c.kv_lora_rank ** -0.5, jnp.bfloat16)
    tables = rng.permutation(NB - 4)[:B * NB_ROW].reshape(
        B, NB_ROW).astype(np.int32)
    lengths = np.asarray(LAT_LENGTHS)
    qpos = np.maximum(lengths - 1, 0).astype(np.int32)
    return (jnp.asarray(pool, jnp.bfloat16), q_nope, q_rope, wkv_b, tables,
            qpos, lengths)


def _latent_reference(pool, q_nope, q_rope, wkv_b, tables, qpos, c=LAT,
                      dtype=jnp.bfloat16):
    B, nb = tables.shape
    rows = pool[LAYER][tables].reshape(B, nb * BS, c.cache_row)
    return np.asarray(LM.attend_absorbed(
        c, wkv_b.astype(dtype), q_nope.astype(dtype), q_rope.astype(dtype),
        rows.astype(dtype), jnp.asarray(qpos)[:, None]), np.float32)


def _latent_kernel(pool, q_nope, q_rope, wkv_b, tables, qpos, active,
                   c=LAT, chunk=CHUNK):
    scalars = pa.plan(jnp.asarray(tables), jnp.asarray(qpos),
                      None if active is None else jnp.asarray(active),
                      BS, chunk)
    w = wkv_b.reshape(c.kv_lora_rank, c.n_heads, -1)
    q_row = LM._absorbed_query(c, w, q_nope, q_rope, c.cache_row)
    o_lat = pa.paged_latent_attention(
        q_row[:, 0], pool, jnp.int32(LAYER), scalars,
        scale=c.qk_head_dim ** -0.5, rank=c.kv_lora_rank, chunk=chunk)
    return np.asarray(LM._absorbed_output(c, w, o_lat[:, None]),
                      np.float32)


@pytest.mark.parametrize("rank", [512, 96])
def test_latent_kernel_matches_gather_and_attend_absorbed(rank):
    """32 heads on rows of 640 (rank 512, shared key 64) and, with a
    latent that is no whole number of lane tiles (96 + 64 in a row of
    256), the value product over the whole row: every length class at
    once, the score scaled by 1/sqrt(qk_head_dim), not by the row."""
    c = LAT if rank == 512 else dataclasses.replace(LAT, kv_lora_rank=rank)
    assert c.cache_row == (640 if rank == 512 else 256)
    case = _latent_case(rank, c)
    lengths = case[-1]
    active = lengths > 0
    got = _latent_kernel(*case[:-1], active, c=c)
    want = _latent_reference(*case[:-1], c=c)
    exact = _latent_reference(*case[:-1], c=c, dtype=jnp.float32)
    assert got.shape == want.shape == (len(lengths), 1,
                                       c.n_heads * c.v_head_dim)
    assert not got[~active].any()               # a dead slot: zeros
    assert np.abs(got[active] - want[active]).max() <= ATOL
    assert (np.abs(got[active] - exact[active]).max()
            <= np.abs(want[active] - exact[active]).max() + 2 ** -8)


@pytest.mark.parametrize("how", ["out_of_range", "other_sequences",
                                 "poisoned"])
def test_latent_entries_past_a_length_change_nothing(how):
    pool, *rest, tables, qpos, lengths = _latent_case(7)
    nb_total = pool.shape[1]
    pool = pool.at[:, nb_total - 1].set(jnp.nan)
    active = lengths > 0
    clean = _latent_kernel(pool, *rest, tables, qpos, active)
    dirty = _latent_kernel(
        pool, *rest, _spoil(tables, lengths, how, nb_total), qpos, active)
    assert np.isfinite(dirty).all()
    np.testing.assert_array_equal(dirty, clean)


def test_latent_kernel_copies_only_live_blocks_once(monkeypatch):
    """One copy a live block of a live sequence, at the layer asked
    for; a dead slot copies nothing."""
    pool, *rest, tables, qpos, lengths = _latent_case(11)
    dirty = _spoil(tables, lengths, "out_of_range", pool.shape[1])
    seen = []
    block_copy = pa._block_copy

    def recording(src, layer, phys, *more):
        if not isinstance(phys, int):           # a start, not a wait
            jax.debug.callback(
                lambda l, p: seen.append((int(l), int(p))), layer, phys)
        return block_copy(src, layer, phys, *more)

    monkeypatch.setattr(pa, "_block_copy", recording)
    # the entry is jitted: trace it anew with the recorder in, and
    # leave no such trace behind for the next test of these shapes
    pa.paged_latent_attention.clear_cache()
    try:
        _latent_kernel(pool, *rest, dirty, qpos, lengths > 0)
        jax.effects_barrier()
    finally:
        pa.paged_latent_attention.clear_cache()
    assert sorted(seen) == sorted(
        (LAYER, int(tables[b, j])) for b, n in enumerate(lengths)
        for j in range(-(-int(n) // BS)))


def test_the_selector_reads_a_latent_pool_as_one_kv_head(monkeypatch):
    pool = lambda bs, row, dt=jnp.bfloat16: jax.ShapeDtypeStruct(  # noqa: E731
        (2, 8, bs, row), dt)
    assert not pa.engages(pool(16, 640))            # the CPU, not forced
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert pa.engages(pool(16, 640)) and pa.engages(pool(32, 128))
    assert not pa.engages(pool(16, 576))            # no whole lane rows
    assert not pa.engages(pool(8, 640))             # half a packed tile
    assert not pa.engages(pool(16, 640, jnp.float32))
    for fns in (LM._SERVING, kimi_linear._SERVING):
        assert fns.paged_attention({"latent": pool(16, 640)}) == "kernel"
        assert fns.paged_attention({"latent": pool(4, 128)}) == "gather"


# ---------------------------------------------------------------------------
# The window form: a ring of a table, the keys p - W + 1 .. p
# ---------------------------------------------------------------------------

W_KEYS, RING = 48, 5        # 3 blocks of window in a ring of 5 (80 rows)
# dead; under, at and just over the window; past the ring, where the
# table wraps; past it twice over, at a block's first and last row
W_LENGTHS = (0, 1, 47, 48, 49, 64, 81, 96, 177, 192)


def _window_case(heads, kv_heads, seed):
    """Pools whose ring of a sequence holds what a stream of that length
    leaves there: position t in block `table[(t // BS) % RING]`, older
    rows overwritten, rows the stream never reached random."""
    rng = np.random.default_rng(seed)
    B, NB = len(W_LENGTHS), len(W_LENGTHS) * RING + 3
    pool = lambda: jnp.asarray(                               # noqa: E731
        rng.standard_normal((L, NB, BS, kv_heads, D)), jnp.bfloat16)
    q = jnp.asarray(rng.standard_normal((B, 1, heads, D)), jnp.bfloat16)
    tables = rng.permutation(NB - 3)[:B * RING].reshape(
        B, RING).astype(np.int32)
    lengths = np.asarray(W_LENGTHS)
    qpos = np.maximum(lengths - 1, 0).astype(np.int32)[:, None]
    return q, pool(), pool(), tables, qpos, lengths


def _window_reference(q, k_pool, v_pool, tables, qpos, window):
    """The masked gather (`models/window_moe.py::_Paged`'s other path):
    row r of the gathered ring is the last position <= p that is r
    modulo the ring's rows."""
    from ray_tpu.models.window_moe import _masked_attention, _seen

    B, ring = tables.shape
    kvh = k_pool.shape[3]
    rows = ring * BS
    k = k_pool[LAYER][tables].reshape(B, rows, kvh, D)
    v = v_pool[LAYER][tables].reshape(B, rows, kvh, D)
    pos = jnp.asarray(qpos)
    kpos = pos - (pos - jnp.arange(rows)[None]) % rows
    return np.asarray(_masked_attention(q, k, v, _seen(pos, kpos, window)),
                      np.float32)


def _window_kernel(q, k_pool, v_pool, tables, qpos, active, window, chunk):
    scalars = pa.plan(jnp.asarray(tables), jnp.asarray(qpos),
                      jnp.asarray(active), BS, chunk, window=window)
    return np.asarray(pa.paged_attention(
        q, k_pool, v_pool, jnp.int32(LAYER), scalars, chunk=chunk,
        window=window), np.float32)


@pytest.mark.parametrize("chunk", [2, 5])
@pytest.mark.parametrize("heads,kv_heads", [(8, 4), (16, 8)])
def test_window_form_matches_the_masked_gather(heads, kv_heads, chunk):
    """Lengths under, at and over the window and past the ring, at 4 and
    8 KV heads, the chunk smaller than the window and as wide as the
    ring."""
    q, kp, vp, tables, qpos, lengths = _window_case(heads, kv_heads, 11)
    active = lengths > 0
    got = _window_kernel(q, kp, vp, tables, qpos, active, W_KEYS, chunk)
    want = _window_reference(q, kp, vp, tables, qpos, W_KEYS)
    assert np.all(got[~active] == 0.0)
    np.testing.assert_allclose(got[active], want[active], atol=ATOL, rtol=0)
    # and the window matters: without it the longer streams read more
    whole = _window_reference(q, kp, vp, tables, qpos, None)
    assert np.abs(whole - want)[lengths > W_KEYS].max() > 10 * ATOL


def test_window_form_copies_the_windows_blocks_alone(monkeypatch):
    """Every copy the window form starts, recorded: exactly the blocks
    from the one of a stream's first visible key to its last, through
    the ring, K and V once each: at most window / BS + 1 blocks a
    sequence a pool whatever its length."""
    q, kp, vp, tables, qpos, lengths = _window_case(8, 4, 12)
    seen = []
    block_copy = pa._block_copy

    def recording(pool, layer, phys, *rest):
        if not isinstance(phys, int):           # a start, not a wait
            jax.debug.callback(
                lambda l, p: seen.append((int(l), int(p))), layer, phys)
        return block_copy(pool, layer, phys, *rest)

    monkeypatch.setattr(pa, "_block_copy", recording)
    _window_kernel(q, kp, vp, tables, qpos, lengths > 0, W_KEYS, 2)
    jax.effects_barrier()
    want = sorted((LAYER, int(tables[b, j % RING]))
                  for b, n in enumerate(lengths)
                  for j in range(max(int(n) - W_KEYS, 0) // BS,
                                 -(-int(n) // BS))) * 2
    assert sorted(seen) == sorted(want)
    assert len(want) <= 2 * (lengths > 0).sum() * (W_KEYS // BS + 1)


def test_window_plan_is_bounded_by_the_window_not_the_length():
    tables = jnp.zeros((3, 256), jnp.int32)
    qpos = jnp.asarray([5, 2047, 18000], jnp.int32)
    n, seq, chunk, lengths, _, _ = pa.plan(tables, qpos, None, 16,
                                           window=2048)
    assert seq.shape[0] == 3 * 5        # (2048 - 2) // 512 + 2 a sequence
    # 1 chunk, 4 chunks, and 18001 - 2048 = 15953 -> chunks 31 .. 35
    assert int(n[0]) == 1 + 4 + 5
    assert list(np.asarray(chunk[:10])) == [0, 0, 1, 2, 3, 31, 32, 33, 34,
                                            35]
    with pytest.raises(ValueError, match="one query"):
        pa.plan(tables, jnp.zeros((3, 2), jnp.int32), None, 16, window=2048)


# (queries a sequence, heads, KV heads): 2 to 32 query rows a KV group,
# both sides of `pa.walks_groups`; the window form takes one query
SIDE_BY_SIDE = [(n_q, heads, kv_heads, window)
                for n_q, heads, kv_heads in (
                    (1, 8, 4), (1, 8, 2), (1, 16, 2), (1, 32, 2),
                    (1, 64, 2), (1, 128, 4), (4, 4, 4), (4, 8, 4),
                    (4, 16, 4), (4, 32, 4), (4, 16, 2))
                for window in (None, W_KEYS) if n_q == 1 or window is None]


@pytest.mark.parametrize("n_q,heads,kv_heads,window", SIDE_BY_SIDE)
def test_kv_heads_side_by_side_in_a_row(monkeypatch, n_q, heads, kv_heads,
                                        window):
    """Pools of four axes, a token's KV heads side by side in one row
    [L, NB, bs, kvH * D] (few KV heads: `ops/paged_attention.py`), give
    what the same rows give as [L, NB, bs, kvH, D], in BOTH forms of
    the call (every query row as wide as the pool's row; a KV group's
    rows against that group's lanes), whichever of them the rule takes
    at this geometry: both forms of the walk, against the masked
    gather, and one against the other."""
    if n_q == 1:
        q, kp, vp, tables, qpos, lengths = _window_case(heads, kv_heads, 13)
        chunk = 2
        if window is None:              # a table by position: no wrap
            lengths = np.minimum(lengths, RING * BS)
            qpos = np.maximum(lengths - 1, 0).astype(np.int32)[:, None]
        want = _window_reference(q, kp, vp, tables, qpos, window)
    else:                               # query j sees the keys up to its own
        q, kp, vp, tables, qpos, lengths = _case(heads, kv_heads, n_q, 13)
        chunk = CHUNK
        want = _reference(q, kp, vp, tables, qpos)
    active = lengths > 0
    flat = [x.reshape(x.shape[:3] + (-1,)) for x in (kp, vp)]
    scalars = pa.plan(jnp.asarray(tables), jnp.asarray(qpos),
                      jnp.asarray(active), BS, chunk, window=window)
    rule = pa.walks_groups(n_q, heads, kv_heads)
    assert rule == (n_q * heads // kv_heads >= 32)
    got = {}
    for grouped in (False, True):
        monkeypatch.setattr(pa, "walks_groups", lambda *_: grouped)
        got[grouped] = np.asarray(pa.paged_attention(
            q, *flat, jnp.int32(LAYER), scalars, chunk=chunk,
            window=window), np.float32)
        assert np.all(got[grouped][~active] == 0.0)
        np.testing.assert_allclose(got[grouped][active], want[active],
                                   atol=ATOL, rtol=0)
    # the same float32 sums over the same bf16 operands, but for the
    # zeros of the other groups' lanes: a rounding of the result apart
    np.testing.assert_allclose(got[True], got[False], atol=2 ** -8, rtol=0)


@pytest.mark.parametrize("cell,shape,grouped", [
    ("solve-decode-blockdiff-moe", (4, 32, 4), True),       # 32 rows a group
    ("swarm-decode-ssd-moe", (1, 32, 2), False),            # 16
    ("mixed-decode-window-moe", (1, 32, 4), False),         # 8
    ("think-decode-ssm-yoco", (1, 40, 10), False),          # 4 (K/V pairs)
    ("tutor-decode-mamba-mqa", (1, 20, 1), False),          # ONE K/V head
])
def test_which_cells_walk_their_kv_groups(cell, shape, grouped):
    """`walks_groups` on the (queries a sequence, heads, KV heads) of
    the five benchmark cells whose pools hold KV heads side by side: a
    function of shapes, static a program; the blocks a chunk go with
    it (one K/V head has no groups to walk, whatever its query rows)."""
    assert pa.walks_groups(*shape) is grouped
    assert pa.chunk_blocks(*shape) == (64 if grouped else pa.CHUNK_BLOCKS)
    # and what a sequence's queries take follows it
    n_q, heads, kv_heads = shape
    lanes = 128 if grouped else kv_heads * 128
    assert pa.query_bytes(n_q, heads, kv_heads, 128) \
        == n_q * heads * lanes * 2


@pytest.mark.parametrize("form,parts", [("groups", 1), ("whole_rows", 4)])
def test_slots_a_call_by_the_queries_it_holds(form, parts):
    """`solve`'s 256 slots x 192 blocks of table at 4 queries of 32 heads
    over 4 KV heads of 128: walked by groups a sequence's queries are 32
    KiB (16 MiB of query + output a call: one part); laid as wide as the
    pool's row they would be 128 KiB (4 parts).  Host arithmetic."""
    a_slot = {"groups": pa.query_bytes(4, 32, 4, 128),
              "whole_rows": 4 * 32 * 4 * 128 * 2}[form]
    assert a_slot == {"groups": 32, "whole_rows": 128}[form] * 2 ** 10
    assert pa.slot_parts(256, 192, query_bytes=a_slot) == parts
    assert 2 * 256 // parts * a_slot <= pa._VMEM_QUERY_BUDGET
