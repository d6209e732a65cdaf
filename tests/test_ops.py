"""Pallas kernel parity tests (CPU, interpret mode).

The public ops fall back to XLA off-TPU, so these tests force the pallas
kernel bodies through `pl.pallas_call(..., interpret=True)` and check values
AND gradients against the reference `xla_attention`.  (An early review: the
hand-written backward had never executed before the bench.)
"""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from ray_tpu.models.llama import xla_attention  # noqa: E402
from ray_tpu.ops import attention as attn_mod  # noqa: E402
from ray_tpu.ops.attention import flash_attention  # noqa: E402


@pytest.fixture(autouse=True)
def _force_interpret():
    attn_mod.FORCE_PALLAS_INTERPRET = True
    yield
    attn_mod.FORCE_PALLAS_INTERPRET = False


def _rand_qkv(key, B, S, H, D, dtype=jnp.float32):
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (B, S, H, D), dtype)
    k = jax.random.normal(kk, (B, S, H, D), dtype)
    v = jax.random.normal(kv, (B, S, H, D), dtype)
    return q, k, v


@pytest.mark.parametrize("causal", [True, False])
def test_flash_forward_matches_xla(causal):
    q, k, v = _rand_qkv(jax.random.key(0), 2, 256, 2, 64)
    out = flash_attention(q, k, v, causal)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal", [True, False])
def test_flash_grads_match_xla(causal):
    q, k, v = _rand_qkv(jax.random.key(1), 1, 128, 2, 64)

    def mk_loss(f):
        def loss(q, k, v):
            o = f(q, k, v)
            # Non-uniform weighting so dq/dk/dv are all exercised.
            w = jnp.arange(o.size, dtype=o.dtype).reshape(o.shape) / o.size
            return jnp.sum(o * w)
        return loss

    gf = jax.grad(mk_loss(lambda q, k, v: flash_attention(q, k, v, causal)),
                  argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(mk_loss(
        lambda q, k, v: xla_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2))(q, k, v)
    for got, ref, name in zip(gf, gr, "q k v".split()):
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(ref), rtol=5e-4, atol=5e-4,
            err_msg=f"d{name} mismatch (causal={causal})")


def test_flash_uneven_seq_pads():
    # 200 is not a multiple of the 128 block; causal path pads internally.
    q, k, v = _rand_qkv(jax.random.key(2), 1, 200, 1, 64)
    out = flash_attention(q, k, v, True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


def test_flash_bf16_close_to_f32_reference():
    q, k, v = _rand_qkv(jax.random.key(3), 1, 128, 2, 64, jnp.bfloat16)
    out = flash_attention(q, k, v, True).astype(jnp.float32)
    ref = xla_attention(q.astype(jnp.float32), k.astype(jnp.float32),
                        v.astype(jnp.float32), causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=0.05, atol=0.05)


def test_short_seq_falls_back_to_xla():
    # Below the 128-token threshold the public API must still be exact.
    q, k, v = _rand_qkv(jax.random.key(4), 2, 64, 2, 64)
    out = flash_attention(q, k, v, True)
    ref = xla_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-6, atol=1e-6)


class TestFusedLoss:
    """ops/fused_loss.py: blockwise lm_head+xent vs materialized logits."""

    def _data(self, n=48, d=16, v=500):
        import numpy as np

        rng = np.random.default_rng(7)
        import jax.numpy as jnp

        return (jnp.asarray(rng.standard_normal((n, d)), jnp.float32),
                jnp.asarray(rng.standard_normal((d, v)), jnp.float32),
                jnp.asarray(rng.integers(0, v, n), jnp.int32))

    def test_forward_and_grads_match_reference(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.fused_loss import blockwise_xent

        h, head, t = self._data()

        def ref(h, hd):
            logits = h @ hd
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            return (lse - jnp.take_along_axis(
                logits, t[:, None], 1)[:, 0]).mean()

        def fus(h, hd):
            return blockwise_xent(h, hd, t, 128).mean()

        assert jnp.allclose(ref(h, head), fus(h, head), atol=1e-5)
        gr = jax.grad(ref, argnums=(0, 1))(h, head)
        gf = jax.grad(fus, argnums=(0, 1))(h, head)
        assert jnp.allclose(gr[0], gf[0], atol=1e-5)
        assert jnp.allclose(gr[1], gf[1], atol=1e-5)

    def test_non_divisible_vocab_under_jit(self):
        import jax
        import jax.numpy as jnp

        from ray_tpu.ops.fused_loss import blockwise_xent

        h, head, t = self._data(v=500)  # 500 % 96 != 0
        out = jax.jit(
            lambda h, hd, t: blockwise_xent(h, hd, t, 96))(h, head, t)
        logits = h @ head
        ref = (jax.scipy.special.logsumexp(logits, -1)
               - jnp.take_along_axis(logits, t[:, None], 1)[:, 0])
        assert jnp.allclose(out, ref, atol=1e-5)

    def test_llama_loss_fused_matches_unfused(self):
        import jax.numpy as jnp

        from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn

        cfg = LlamaConfig(dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
                          hidden_dim=128, vocab_size=211, max_seq_len=32,
                          attn_impl="xla", remat=False)
        import jax
        params = init_params(cfg, jax.random.PRNGKey(0))
        import numpy as np

        toks = jnp.asarray(
            np.random.default_rng(1).integers(0, 211, (2, 17)), jnp.int32)
        a = loss_fn(params, {"tokens": toks}, cfg, fused=False)
        b = loss_fn(params, {"tokens": toks}, cfg, fused=True)
        assert jnp.allclose(a, b, atol=2e-3), (float(a), float(b))
