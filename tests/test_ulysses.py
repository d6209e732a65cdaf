"""Ulysses sequence parallelism parity on the 8-device virtual CPU mesh
(reference: SURVEY §5 — all-to-all head/sequence resharding as the
config alternative to ring attention; the DeepSpeed-Ulysses pattern over
XLA collectives)."""

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from ray_tpu.models.llama import xla_attention  # noqa: E402
from ray_tpu.ops.ulysses import (  # noqa: E402
    ulysses_attention, ulysses_attention_global,
)


def _mesh(n=8, name="sp"):
    devs = jax.devices()
    if len(devs) < n:
        pytest.skip(f"need {n} devices, have {len(devs)}")
    return Mesh(np.array(devs[:n]), (name,))


def _rand(key, shape):
    return jax.random.normal(key, shape, jnp.float32)


@pytest.mark.parametrize("causal", [True, False])
def test_ulysses_matches_reference(causal):
    B, S, H, D = 2, 256, 8, 32
    ks = jax.random.split(jax.random.key(0), 3)
    q, k, v = (_rand(ks[i], (B, S, H, D)) for i in range(3))
    mesh = _mesh()
    out = ulysses_attention_global(q, k, v, mesh, causal=causal)
    ref = xla_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_ulysses_gqa_and_grads():
    """TRUE grouped-query attention (Hkv < H): the KV head shard expands
    to the query head count after the reshard; grads flow through both
    all-to-alls and the repeat."""
    B, S, H, Hkv, D = 1, 128, 16, 8, 16
    ks = jax.random.split(jax.random.key(1), 3)
    q = _rand(ks[0], (B, S, H, D))
    k = _rand(ks[1], (B, S, Hkv, D))
    v = _rand(ks[2], (B, S, Hkv, D))
    mesh = _mesh()

    def mk(f):
        def loss(q, k, v):
            o = f(q, k, v)
            w = jnp.arange(o.size, dtype=o.dtype).reshape(o.shape) / o.size
            return jnp.sum(o * w)
        return loss

    def ref_attn(q, k, v):
        rep = H // Hkv
        return xla_attention(q, jnp.repeat(k, rep, axis=2),
                             jnp.repeat(v, rep, axis=2), causal=True)

    out = ulysses_attention_global(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref_attn(q, k, v)),
                               rtol=2e-5, atol=2e-5)

    g_uly = jax.grad(mk(lambda q, k, v: ulysses_attention_global(
        q, k, v, mesh, causal=True)), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(mk(ref_attn), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_uly, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_ulysses_head_divisibility_enforced():
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    mesh = _mesh()
    B, S, H, D = 1, 256, 4, 16   # 4 heads on an 8-way axis: invalid
    q = _rand(jax.random.key(2), (B, S, H, D))
    spec = P(None, "sp", None, None)
    with pytest.raises(ValueError, match="must divide"):
        shard_map(lambda a, b, c: ulysses_attention(a, b, c,
                                                    axis_name="sp"),
                  mesh=mesh, in_specs=(spec,) * 3, out_specs=spec,
                  check_vma=False)(q, q, q)


def test_unbound_axis_falls_back_exact():
    B, S, H, D = 1, 128, 4, 16
    q = _rand(jax.random.key(3), (B, S, H, D))
    out = ulysses_attention(q, q, q, causal=True, axis_name="nope")
    ref = xla_attention(q, q, q, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_context_parallel_attention_impl_switch():
    """parallel.context_parallel_attention routes impl= to ring or
    ulysses and both train the model layer identically."""
    from ray_tpu.models.llama import LlamaConfig, forward, init_params
    from ray_tpu.parallel import context_parallel_attention

    mesh = _mesh(name="seq")
    cfg = LlamaConfig(vocab_size=64, dim=32, n_layers=1, n_heads=8,
                      n_kv_heads=8, hidden_dim=64, max_seq_len=256)
    params = init_params(cfg, jax.random.key(0))
    toks = jnp.asarray(
        np.random.RandomState(0).randint(0, 64, (1, 256)), jnp.int32)

    ref = forward(params, toks, cfg)
    for impl in ("ring", "ulysses"):
        attn = context_parallel_attention(mesh, seq_axis="seq", impl=impl)
        out = forward(params, toks, cfg, attn_impl=attn)
        # fp32 reassociation through norm+FFN amplifies attention's
        # reduction-order differences; logits tolerance reflects that.
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=0, atol=5e-3)
    with pytest.raises(ValueError, match="expected 'ring'"):
        context_parallel_attention(mesh, impl="bogus")
