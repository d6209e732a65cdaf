"""Compute/communication overlap (split-phase collectives).

Four planes of the overlap PR, all on the REAL ring kernels under the
Pallas interpreter with virtual CPU devices (tier-1 budget — shapes tiny):

- split-phase start/wait entry points are hop-schedule identical to the
  monolithic kernels (bitwise parity),
- the chunked-overlap ZeRO step matches the monolithic ZeRO step to
  float tolerance (per-chunk ring order differs, so not bitwise),
- int8 gradient exchange with error feedback tracks the f32 run where
  plain int8 visibly drifts,
- ring attention over the split-phase permute matches the lax ring.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax import shard_map
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ray_tpu.parallel.zero import build_zero_train_step, create_zero_state
from ray_tpu.util.collective.pallas import (
    local_quantization_residual, ring_allgather, ring_reduce_scatter,
    start_quantized_ring_reduce_scatter, start_ring_allgather,
    start_ring_permute, start_ring_reduce_scatter,
    wait_quantized_ring_reduce_scatter, wait_ring_allgather,
    wait_ring_permute, wait_ring_reduce_scatter,
)

IMPL = "pallas_interpret"


def _mesh(n) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]), ("data",))


def _copy(tree):
    # build_zero_train_step donates its state: every state needs its own
    # arrays or the second step invalidates the first state's buffers.
    return jax.tree.map(jnp.copy, tree)


class TestSplitPhaseParity:
    """start_* + wait_* must replay the monolithic kernels' hop schedule
    element-for-element — parity is bitwise, not approximate."""

    N = 4

    def _run(self, fn, x, out_specs=P("data")):
        g = jax.jit(shard_map(fn, mesh=_mesh(self.N), in_specs=P(),
                              out_specs=out_specs, check_vma=False))
        return np.asarray(g(x))

    def test_reduce_scatter_bitwise(self):
        n = self.N
        x = jnp.arange(n * 8 * 128, dtype=jnp.float32)
        x = x.reshape(n * 8, 128) / 100.0

        def mono(v):
            return ring_reduce_scatter(v, "data", n=n, impl=IMPL)

        def split(v):
            h = start_ring_reduce_scatter(v, "data", n=n, impl=IMPL)
            return wait_ring_reduce_scatter(h)

        np.testing.assert_array_equal(self._run(mono, x),
                                      self._run(split, x))

    def test_allgather_bitwise_and_roundtrip(self):
        n = self.N
        x = jnp.arange(n * 8 * 128, dtype=jnp.float32)
        x = x.reshape(n * 8, 128) / 100.0

        def mono(v):
            my = lax.axis_index("data")
            shard = lax.dynamic_slice(v, (my * 8, 0), (8, 128))
            return ring_allgather(shard, "data", n=n,
                                  impl=IMPL).reshape(n * 8, 128)

        def split(v):
            my = lax.axis_index("data")
            shard = lax.dynamic_slice(v, (my * 8, 0), (8, 128))
            h = start_ring_allgather(shard, "data", n=n, impl=IMPL)
            return wait_ring_allgather(h).reshape(n * 8, 128)

        a = self._run(mono, x, out_specs=P())
        b = self._run(split, x, out_specs=P())
        np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(b, np.asarray(x))  # gather(slice)=id

    def test_permute_rotates_one_hop(self):
        n = self.N

        def perm(v):
            my = lax.axis_index("data")
            shard = lax.dynamic_slice(v, (my * 8, 0), (8, 128))
            h = start_ring_permute(shard, "data", n=n, impl=IMPL)
            return wait_ring_permute(h)

        x = jnp.arange(n * 8 * 128, dtype=jnp.float32)
        x = x.reshape(n * 8, 128) / 100.0
        got = self._run(perm, x)
        expect = np.roll(np.asarray(x).reshape(n, 8, 128), 1,
                         axis=0).reshape(n * 8, 128)
        np.testing.assert_array_equal(got, expect)

    def test_quantized_rs_error_bound(self):
        n = self.N
        x = jnp.arange(n * 8 * 128, dtype=jnp.float32)
        x = x.reshape(n * 8, 128) / 100.0

        def exact(v):
            return ring_reduce_scatter(v, "data", n=n, impl=IMPL)

        def qsplit(v):
            h = start_quantized_ring_reduce_scatter(v, "data", n=n,
                                                    impl=IMPL)
            return wait_quantized_ring_reduce_scatter(h)

        ref = self._run(exact, x)
        got = self._run(qsplit, x)
        rel = np.abs(got - ref).max() / (np.abs(ref).max() + 1e-9)
        assert rel < 0.05, rel

    def test_residual_matches_quantizer(self):
        n = self.N
        x = jnp.arange(n * 8 * 128, dtype=jnp.float32)
        x = x.reshape(n * 8, 128) / 100.0
        r = local_quantization_residual(x, n)
        assert r.shape == x.shape and r.dtype == jnp.float32
        # Residual of a symmetric int8 quantizer is at most half a
        # quantum at the per-chunk scale (max|chunk|/127).
        bound = float(jnp.abs(x).max()) / 127.0
        assert float(jnp.abs(r).max()) <= bound


class TestChunkedOverlapZero:
    def test_parity_vs_monolithic(self):
        """Pipelined start/wait chunks must compute the same update as
        the monolithic RS -> adam -> AG step (float tolerance: per-chunk
        rings re-associate the adds)."""
        n = 8
        mesh = _mesh(n)
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (64, 40)) * 0.1,
                  "b": jnp.zeros((40,))}
        opt = optax.adam(1e-2)

        def loss_fn(p, batch):
            pred = batch["x"] @ p["w"] + p["b"]
            return jnp.mean((pred - batch["y"]) ** 2)

        bsh = NamedSharding(mesh, P("data"))
        batch = {
            "x": jax.device_put(
                jax.random.normal(jax.random.PRNGKey(1), (n * 4, 64)),
                bsh),
            "y": jax.device_put(
                jax.random.normal(jax.random.PRNGKey(2), (n * 4, 40)),
                bsh),
        }

        mono = build_zero_train_step(loss_fn, opt, mesh, collective=IMPL)
        over = build_zero_train_step(loss_fn, opt, mesh, collective=IMPL,
                                     overlap=True, n_chunks=3)
        s1 = create_zero_state(_copy(params), opt, mesh)
        s2 = create_zero_state(_copy(params), opt, mesh)
        for _ in range(3):
            s1, m1 = mono(s1, batch)
            s2, m2 = over(s2, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for k in params:
            np.testing.assert_allclose(np.asarray(s1.params[k]),
                                       np.asarray(s2.params[k]),
                                       atol=1e-5, rtol=1e-5)

    def test_n_chunks_validated(self):
        mesh = _mesh(2)
        with pytest.raises(ValueError, match="n_chunks"):
            build_zero_train_step(lambda p, b: jnp.sum(p["w"]),
                                  optax.sgd(0.1), mesh, n_chunks=0)


class TestErrorFeedback:
    def test_requires_quantized_grads(self):
        mesh = _mesh(2)
        with pytest.raises(ValueError, match="quantized_grads"):
            build_zero_train_step(lambda p, b: jnp.sum(p["w"]),
                                  optax.sgd(0.1), mesh,
                                  error_feedback=True)

    def test_state_must_carry_ef_buffer(self):
        mesh = _mesh(2)
        params = {"w": jnp.zeros((4, 128))}
        opt = optax.sgd(0.1)
        step = build_zero_train_step(
            lambda p, b: jnp.sum(p["w"] ** 2), opt, mesh,
            collective=IMPL, quantized_grads=True, error_feedback=True)
        state = create_zero_state(params, opt, mesh)  # no ef buffer
        with pytest.raises(ValueError, match="ef buffer"):
            step(state, {"x": jnp.zeros((2, 1))})

    def test_ef_buffer_shape_and_dtype(self):
        n = 2
        mesh = _mesh(n)
        params = {"w": jnp.zeros((4, 128))}
        state = create_zero_state(params, optax.sgd(0.1), mesh,
                                  error_feedback=True)
        assert state.ef is not None
        assert state.ef.dtype == jnp.float32  # EF must stay float
        assert state.ef.shape[0] == n
        assert state.ef.shape[1] % (n * 128) == 0
        assert float(jnp.abs(state.ef).max()) == 0.0

    def test_int8_ef_tracks_f32(self):
        """The convergence claim: over 60 sgd steps, plain int8 exchange
        visibly drifts from the f32 run while int8+EF stays close.

        The dummy "z" param contributes one constant outlier gradient
        (50.0) that sets the int8 scale for its ring chunk, so the mse
        gradients below ~scale/2 round to zero on the wire — exactly the
        regime error feedback exists for.  Seeds fixed; on the CPU
        interpreter the final mses are deterministic
        (f32 0.7358 / int8 0.8225 / int8+EF 0.7661)."""
        n = 2
        mesh = _mesh(n)
        params = {"w": jax.random.normal(jax.random.PRNGKey(0),
                                         (64, 40)) * 0.3,
                  "z": jnp.zeros((128,))}
        opt = optax.sgd(0.05)

        def loss_fn(p, batch):
            pred = batch["x"] @ p["w"]
            return (jnp.mean((pred - batch["y"]) ** 2)
                    + 50.0 * p["z"][0])

        x = jax.random.normal(jax.random.PRNGKey(1), (n * 8, 64)) * 0.3
        wstar = jax.random.normal(jax.random.PRNGKey(3), (64, 40)) * 0.3
        y = x @ wstar
        bsh = NamedSharding(mesh, P("data"))
        batch = {"x": jax.device_put(x, bsh), "y": jax.device_put(y, bsh)}

        f32_step = build_zero_train_step(loss_fn, opt, mesh,
                                         collective=IMPL)
        q_step = build_zero_train_step(loss_fn, opt, mesh,
                                       collective=IMPL,
                                       quantized_grads=True)
        ef_step = build_zero_train_step(loss_fn, opt, mesh,
                                        collective=IMPL,
                                        quantized_grads=True,
                                        error_feedback=True)
        s_f = create_zero_state(_copy(params), opt, mesh)
        s_q = create_zero_state(_copy(params), opt, mesh)
        s_e = create_zero_state(_copy(params), opt, mesh,
                                error_feedback=True)
        for _ in range(60):
            s_f, _ = f32_step(s_f, batch)
            s_q, _ = q_step(s_q, batch)
            s_e, _ = ef_step(s_e, batch)

        def mse(s):
            pred = np.asarray(x) @ np.asarray(s.params["w"])
            return float(np.mean((pred - np.asarray(y)) ** 2))

        mf, mq, me = mse(s_f), mse(s_q), mse(s_e)
        gap_q, gap_e = mq - mf, me - mf
        # Plain int8 must drift by a real margin for the comparison to
        # mean anything; EF must close most of that gap.
        assert gap_q > 0.04, (mf, mq, me)
        assert gap_e < 0.6 * gap_q, (mf, mq, me)
        assert me < mq
        # And the residual buffer is live, finite, and float.
        ef = np.asarray(s_e.ef)
        assert ef.dtype == np.float32
        assert np.isfinite(ef).all() and np.abs(ef).max() > 0.0


class TestRingAttentionOverlap:
    def test_pallas_permute_matches_lax_ring(self):
        """The split-phase Pallas KV rotation must reproduce the lax
        ppermute ring and the unsharded reference."""
        from ray_tpu.models.llama import xla_attention
        from ray_tpu.ops.ring_attention import ring_attention_global

        n = 4
        mesh = Mesh(np.asarray(jax.devices()[:n]), ("sp",))
        key = jax.random.PRNGKey(0)
        B, S, H, D = 1, 32, 2, 8
        q, k, v = (jax.random.normal(kk, (B, S, H, D), jnp.float32)
                   for kk in jax.random.split(key, 3))

        ref = xla_attention(q, k, v, causal=True)
        out_lax = ring_attention_global(q, k, v, mesh, causal=True,
                                        impl="lax")
        out_pl = ring_attention_global(q, k, v, mesh, causal=True,
                                       impl=IMPL)
        np.testing.assert_allclose(np.asarray(out_pl),
                                   np.asarray(out_lax),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(out_pl), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5)
