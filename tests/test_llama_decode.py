"""Llama KV-cache decode + generation: incremental decode must reproduce
the full-sequence forward, and jitted generation must be deterministic."""

import numpy as np
import pytest


@pytest.mark.parametrize("weights", ["float32", "int8"])
def test_decode_matches_full_forward(weights):
    """Token by token through the cache is the full-sequence forward,
    on the float tree and on the int8 one: every path reads its weights
    through `_weight`, so `forward` over an int8 tree is bitwise
    `forward` over the same tree dequantised."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (
        LlamaConfig, decode_step, forward, init_kv_cache, init_params,
        quantize_weights_int8,
    )

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(0))
    rng = np.random.RandomState(0)
    tokens = jnp.asarray(rng.randint(0, config.vocab_size, (2, 12)),
                         jnp.int32)
    if weights == "int8":
        params = quantize_weights_int8(params)

        def dequantised(tree):
            out = {k: v for k, v in tree.items()
                   if not k.endswith(("_q", "_s")) and k != "layers"}
            for k in tree:
                if k.endswith("_q"):
                    out[k[:-2]] = (tree[k].astype(config.dtype)
                                   * tree[k[:-2] + "_s"].astype(config.dtype))
            return out

        plain = dequantised(params)
        plain["layers"] = dequantised(params["layers"])
        assert set(plain) == {"embed", "layers", "norm_f", "lm_head"}
        np.testing.assert_array_equal(
            np.asarray(forward(params, tokens, config)),
            np.asarray(forward(plain, tokens, config)))

    full_logits = forward(params, tokens, config)  # [B, S, V]

    cache = init_kv_cache(config, 2, max_len=16)
    step = jax.jit(lambda c, t, p: decode_step(params, c, t, p, config))
    for i in range(tokens.shape[1]):
        pos = jnp.full((2,), i, jnp.int32)
        logits, cache = step(cache, tokens[:, i], pos)
        np.testing.assert_allclose(
            np.asarray(logits), np.asarray(full_logits[:, i]),
            rtol=2e-2, atol=2e-2)


def test_generate_greedy_continuation():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (
        LlamaConfig, forward, generate, init_params,
    )

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(1))
    rng = np.random.RandomState(1)
    prompt = jnp.asarray(rng.randint(0, config.vocab_size, (2, 6)),
                         jnp.int32)

    out = generate(params, prompt, config, max_new_tokens=5)
    assert out.shape == (2, 5)
    # First generated token == argmax of the full forward's last position.
    full = forward(params, prompt, config)
    expect = np.argmax(np.asarray(full[:, -1]), axis=-1)
    np.testing.assert_array_equal(np.asarray(out[:, 0]), expect)

    # Deterministic under re-run (greedy).
    out2 = generate(params, prompt, config, max_new_tokens=5)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out2))


def test_generate_jits():
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig, generate, init_params

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(2))
    gen = jax.jit(lambda p, t: generate(p, t, config, max_new_tokens=4))
    prompt = jnp.ones((1, 3), jnp.int32)
    out = gen(params, prompt)
    assert out.shape == (1, 4)


def test_int8_quantized_decode_matches_bf16():
    """Weight-only int8 serving config (bench detail metric): projected
    logits stay highly correlated with bf16 and greedy argmax tokens are
    unchanged on a tiny config."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.models.llama import (
        LlamaConfig, decode_step, init_params, prefill,
        quantize_weights_int8,
    )

    cfg = LlamaConfig(vocab_size=256, dim=64, n_layers=2, n_heads=4,
                      n_kv_heads=2, hidden_dim=128, max_seq_len=64)
    params = init_params(cfg, jax.random.key(0))
    qp = quantize_weights_int8(params)
    # int8 payload is half the bytes for every quantized matrix.
    assert qp["layers"]["wq_q"].dtype == jnp.int8
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 256, (2, 8)), jnp.int32)

    logits, cache = jax.jit(
        lambda p, t: prefill(p, t, cfg, max_len=32))(params, toks)
    logits_q, cache_q = jax.jit(
        lambda p, t: prefill(p, t, cfg, max_len=32))(qp, toks)
    corr = np.corrcoef(np.asarray(logits).ravel(),
                       np.asarray(logits_q).ravel())[0, 1]
    assert corr > 0.999, corr

    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((2,), 8, jnp.int32)
    step = jax.jit(lambda p, c, t, q: decode_step(p, c, t, q, cfg))
    l2, _ = step(params, cache, tok, pos)
    l2q, _ = step(qp, cache_q, tok, pos)
    corr2 = np.corrcoef(np.asarray(l2).ravel(),
                        np.asarray(l2q).ravel())[0, 1]
    assert corr2 > 0.999, corr2
    # Random-init logits are near-uniform so exact argmax ties can flip
    # under ~0.4% quantization noise; the bf16 pick must stay in int8's
    # top-5 (trained-model greedy decode agreement was verified on the
    # bench geometry: identical greedy tokens at 1B params).
    top5 = np.argsort(np.asarray(l2q), axis=-1)[:, -5:]
    bf16_pick = np.argmax(np.asarray(l2), -1)
    assert all(bf16_pick[i] in top5[i] for i in range(len(bf16_pick)))


def test_paged_decode_matches_dense():
    """decode_step_paged (block-table indirection over the fixed pool)
    reproduces decode_step on the same greedy stream — including with
    rows scattered non-contiguously across the pool."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (
        LlamaConfig, decode_step, decode_step_paged, init_kv_cache,
        init_paged_kv_cache, init_params,
    )

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(0))
    B, bs, max_blocks = 2, 4, 4              # S_pad = 16
    cache = init_kv_cache(config, B, max_len=16)
    pools = init_paged_kv_cache(config, num_blocks=12, block_size=bs)
    tables = jnp.asarray([[3, 6, 1, 8], [0, 5, 9, 2]], jnp.int32)

    dense_step = jax.jit(
        lambda c, t, p: decode_step(params, c, t, p, config))
    paged_step = jax.jit(
        lambda pl, t, p: decode_step_paged(params, pl, tables, t, p,
                                           config))
    rng = np.random.RandomState(3)
    toks = jnp.asarray(rng.randint(0, config.vocab_size, (B,)), jnp.int32)
    for i in range(12):
        pos = jnp.full((B,), i, jnp.int32)
        dl, cache = dense_step(cache, toks, pos)
        pl_, pools = paged_step(pools, toks, pos)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(dl), -1),
            np.argmax(np.asarray(pl_), -1))
        np.testing.assert_allclose(np.asarray(dl), np.asarray(pl_),
                                   rtol=2e-2, atol=2e-2)
        toks = jnp.argmax(dl, -1).astype(jnp.int32)


# (n_heads, n_kv_heads): MHA, the tiny config's rep 2, the benchmark's
# rep 4, and MQA
GQA_SHAPES = {"mha": (4, 4), "rep2": (4, 2), "rep4": (8, 2), "mqa": (4, 1)}


@pytest.mark.parametrize("n_queries", [1, 3])
@pytest.mark.parametrize("heads", sorted(GQA_SHAPES))
def test_decode_attention_matches_explicit_repeat(heads, n_queries):
    """The grouped contraction of `_decode_attention` against the form it
    replaced, written out here: repeat K and V `rep`-fold along the head
    axis, then contract head by head. Rows sit at position 0 (every key
    but one masked), mid-sequence and S-1."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import _decode_attention

    H, KVH = GQA_SHAPES[heads]
    B, S, D, Q = 3, 32, 16, n_queries
    kq, kk, kv = jax.random.split(jax.random.key(H * 10 + KVH), 3)
    q = jax.random.normal(kq, (B, Q, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, S, KVH, D), jnp.bfloat16)
    v = jax.random.normal(kv, (B, S, KVH, D), jnp.bfloat16)
    # query j of a row sits at pos + j; the last row ends at S-1
    qpos = (jnp.asarray([0, S // 2, S - Q], jnp.int32)[:, None]
            + jnp.arange(Q)[None, :])

    def reference(q, k, v, qpos):
        kr = jnp.repeat(k, H // KVH, axis=2)
        vr = jnp.repeat(v, H // KVH, axis=2)
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, kr).astype(
            jnp.float32) / np.sqrt(D)
        mask = jnp.arange(S)[None, None, None, :] <= qpos[:, None, :, None]
        probs = jax.nn.softmax(jnp.where(mask, scores, -1e30), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs.astype(q.dtype), vr)

    got = jax.jit(_decode_attention)(q, k, v, qpos)
    want = jax.jit(reference)(q, k, v, qpos)
    assert got.shape == (B, Q, H, D) and got.dtype == jnp.bfloat16
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    np.testing.assert_allclose(got, want, rtol=2e-2, atol=2e-2)
    np.testing.assert_array_equal(got.argmax(-1), want.argmax(-1))
    # one live key: head h reads row 0 of its own group h // rep, exactly
    np.testing.assert_array_equal(
        got[0, 0], np.repeat(np.asarray(v[0, 0], np.float32),
                             H // KVH, axis=0))


@pytest.mark.parametrize("K", [4, 1])
def test_verify_kv_paged_matches_successive_decode_steps(K):
    """`verify_kv_paged` over K tokens gives, row by row, the logits that
    K successive `decode_step_paged` calls give on a GQA config (rep 4):
    the two run the same layer over the same paged cache at other query
    counts. At K = 1 they are one computation: logits and pools are
    bitwise equal."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (
        LlamaConfig, decode_step_paged, init_paged_kv_cache, init_params,
        verify_kv_paged,
    )

    config = LlamaConfig.tiny(n_heads=8, n_kv_heads=2)
    params = init_params(config, jax.random.key(4))
    B, bs, start = 2, 4, 5
    tables = jnp.asarray([[3, 6, 1, 8], [0, 5, 9, 2]], jnp.int32)
    step = jax.jit(lambda pl, t, p: decode_step_paged(
        params, pl, tables, t, p, config))
    verify = jax.jit(lambda pl, t, p: verify_kv_paged(
        params, pl, tables, t, p, config))
    rng = np.random.RandomState(5)
    toks = jnp.asarray(
        rng.randint(0, config.vocab_size, (B, start + K)), jnp.int32)
    pools = init_paged_kv_cache(config, num_blocks=12, block_size=bs)
    for i in range(start):              # rows start at different lengths
        pos = jnp.asarray([i, i + 2], jnp.int32)
        _, pools = step(pools, toks[:, i], pos)
    base = jnp.asarray([start, start + 2], jnp.int32)
    v_logits, v_pools = verify(pools, toks[:, start:], base)
    for j in range(K):
        s_logits, pools = step(pools, toks[:, start + j], base + j)
        np.testing.assert_array_equal(
            np.argmax(np.asarray(s_logits), -1),
            np.argmax(np.asarray(v_logits[:, j]), -1))
        np.testing.assert_allclose(np.asarray(s_logits),
                                   np.asarray(v_logits[:, j]),
                                   rtol=2e-2, atol=2e-2)
    for name in ("k", "v"):
        np.testing.assert_allclose(
            np.asarray(pools[name], np.float32),
            np.asarray(v_pools[name], np.float32), rtol=2e-2, atol=2e-2)
    if K == 1:
        np.testing.assert_array_equal(np.asarray(s_logits),
                                      np.asarray(v_logits[:, 0]))
        for name in ("k", "v"):
            np.testing.assert_array_equal(
                np.asarray(pools[name], np.float32),
                np.asarray(v_pools[name], np.float32))


def test_prefill_kv_paged_over_zero_history_is_prefill_kv():
    """`prefill_kv_paged` at start = 0 over an all-zero history is
    bitwise `prefill_kv`: hidden states and every layer's new K/V rows
    (the engine's one insert program serves a miss as a hit of length
    zero)."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (
        LlamaConfig, init_params, prefill_kv, prefill_kv_paged,
    )

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(6))
    P = 16
    toks = jnp.asarray(np.random.RandomState(6).randint(
        0, config.vocab_size, (1, P)), jnp.int32)
    hist = jnp.zeros((config.n_layers, P, config.n_kv_heads,
                      config.head_dim), config.dtype)
    want = jax.jit(lambda t: prefill_kv(params, t, config))(toks)
    got = jax.jit(lambda t, h: prefill_kv_paged(
        params, t, jnp.int32(0), h, h, config))(toks, hist)
    for g, w in zip(got, want):
        assert g.shape == w.shape and g.dtype == w.dtype
        np.testing.assert_array_equal(np.asarray(g, np.float32),
                                      np.asarray(w, np.float32))


@pytest.mark.parametrize("entry", [
    "decode_step", "decode_step_paged", "verify_kv_paged", "prefill_kv",
    "prefill_kv_paged"])
def test_cached_entry_points_refuse_experts(entry):
    """Experts are implemented for `forward` alone: every entry point
    that makes or reads a KV cache refuses `n_experts`, with the one
    message of the shared trunk."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import llama

    config = llama.LlamaConfig.tiny(n_experts=2)
    params = llama.init_params(config, jax.random.key(0))
    tok, pos = jnp.zeros((1,), jnp.int32), jnp.zeros((1,), jnp.int32)
    pools = llama.init_paged_kv_cache(config, num_blocks=2, block_size=4)
    tables = jnp.zeros((1, 2), jnp.int32)
    hist = pools["k"][:, 0]
    calls = {
        "decode_step": lambda: llama.decode_step(
            params, llama.init_kv_cache(config, 1, max_len=8), tok, pos,
            config),
        "decode_step_paged": lambda: llama.decode_step_paged(
            params, pools, tables, tok, pos, config),
        "verify_kv_paged": lambda: llama.verify_kv_paged(
            params, pools, tables, tok[:, None], pos, config),
        "prefill_kv": lambda: llama.prefill_kv(params, tok[None], config),
        "prefill_kv_paged": lambda: llama.prefill_kv_paged(
            params, tok[None], jnp.int32(0), hist, hist, config),
    }
    with pytest.raises(NotImplementedError,
                       match="not implemented for MoE configs; use "
                             "forward"):
        calls[entry]()
    logits = llama.forward(params, tok[None], config)
    assert logits.shape == (1, 1, config.vocab_size)
