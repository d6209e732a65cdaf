"""Pallas ring collectives: CPU-interpret parity vs jax.lax, quantized
allreduce error bounds, ZeRO sharded-update parity, backend fallback.

Everything runs the REAL kernels (``pltpu.make_async_remote_copy`` rings)
under the Pallas interpreter on virtual CPU devices — the same code path a
TPU compiles, minus the hardware. Shapes are intentionally tiny: this file
is tier-1 and shares the suite's time budget.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from ray_tpu.util.collective.pallas import (
    quantized_ring_allreduce, ring_allgather, ring_allreduce,
    ring_reduce_scatter, select_impl,
)

N = 4
IMPL = "pallas_interpret"


def _mesh(n=N) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]), ("x",))


def _run(fn, x, n=N, out_specs=P("x")):
    g = jax.jit(shard_map(fn, mesh=_mesh(n), in_specs=P("x"),
                          out_specs=out_specs, check_vma=False))
    return np.asarray(g(x))


class TestRingParity:
    """Ring kernels vs the lax collectives they replace (interpret mode)."""

    def test_allreduce_sum(self):
        # 5x7 per rank: forces the LANES padding path.
        host = np.random.RandomState(0).randn(N, 5, 7).astype(np.float32)
        got = _run(lambda x: ring_allreduce(x, "x", n=N, impl=IMPL), host)
        ref = _run(lambda x: lax.psum(x, "x"), host)
        # Ring order vs XLA tree order: bitwise-different float sums.
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-6)

    def test_allreduce_max(self):
        host = np.random.RandomState(1).randn(N, 3, 9).astype(np.float32)
        got = _run(lambda x: ring_allreduce(x, "x", n=N, op="max",
                                            impl=IMPL), host)
        ref = _run(lambda x: lax.pmax(x, "x"), host)
        np.testing.assert_array_equal(got, ref)  # max is order-free

    def test_allgather(self):
        host = np.random.RandomState(2).randn(N, 2, 5).astype(np.float32)
        out_specs = P(None, "x")
        got = _run(lambda x: ring_allgather(x, "x", n=N, impl=IMPL),
                   host, out_specs=out_specs)
        ref = _run(lambda x: lax.all_gather(x, "x", tiled=False),
                   host, out_specs=out_specs)
        np.testing.assert_array_equal(got, ref)

    def test_reduce_scatter(self):
        # Each rank reduces a full (N*2, 5) array and keeps its slab.
        host = np.random.RandomState(3).randn(N, N * 2, 5).astype(
            np.float32)
        got = _run(
            lambda x: ring_reduce_scatter(x[0], "x", n=N, impl=IMPL)[None],
            host)
        ref = _run(
            lambda x: lax.psum_scatter(x[0], "x", scatter_dimension=0,
                                       tiled=True)[None], host)
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-6)


class TestQuantizedAllreduce:
    def test_int8_error_bound(self):
        # >= RAY_TPU_QAR_MIN_ELEMS elements per rank so the int8 path
        # (not the bf16 fallback) runs: per-hop requantization of
        # partial sums; error grows with hop count but stays small.
        host = np.random.RandomState(4).randn(N, 40, 32).astype(
            np.float32)
        got = _run(lambda x: quantized_ring_allreduce(x, "x", n=N,
                                                      impl=IMPL), host)
        ref = host.sum(axis=0, keepdims=True).repeat(N, axis=0)
        denom = np.abs(ref).max()
        assert np.abs(got - ref).max() / denom < 0.05

    def test_bf16_fallback_precision(self):
        host = np.random.RandomState(5).randn(N, 40, 32).astype(
            np.float32)
        got = _run(lambda x: quantized_ring_allreduce(
            x, "x", n=N, precision="bf16", impl=IMPL), host)
        ref = host.sum(axis=0, keepdims=True).repeat(N, axis=0)
        denom = np.abs(ref).max()
        assert np.abs(got - ref).max() / denom < 0.05

    def test_integer_grads_rejected(self):
        x = jnp.arange(2048, dtype=jnp.int32)
        with pytest.raises(TypeError):
            quantized_ring_allreduce(x, "x", n=N, impl=IMPL)


class TestBackendFallback:
    def test_select_impl_off_tpu_is_lax(self, monkeypatch):
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
        assert select_impl("auto") == "lax"

    def test_select_impl_interpret_env(self, monkeypatch):
        monkeypatch.setenv("RAY_TPU_PALLAS_INTERPRET", "1")
        assert select_impl("auto") == "pallas_interpret"

    def test_select_impl_rejects_unknown(self):
        with pytest.raises(ValueError):
            select_impl("nccl")

    def test_backend_registry_knows_pallas(self):
        from ray_tpu.util.collective.types import Backend

        assert Backend.validate("pallas") == Backend.PALLAS

    def test_auto_allreduce_matches_psum_off_tpu(self, monkeypatch):
        # impl="auto" without the interpret env: the lax fallback path a
        # `pallas` group takes on a CPU-only node.
        monkeypatch.delenv("RAY_TPU_PALLAS_INTERPRET", raising=False)
        host = np.random.RandomState(6).randn(N, 3, 4).astype(np.float32)
        got = _run(lambda x: ring_allreduce(x, "x", n=N, impl="auto"),
                   host)
        ref = _run(lambda x: lax.psum(x, "x"), host)
        np.testing.assert_array_equal(got, ref)


class TestZeroShardedUpdate:
    def test_parity_vs_replicated_adam_one_ulp_per_step(self):
        """reduce-scatter grads -> shard-local Adam -> allgather params
        against allreduce grads -> replicated Adam on a 2-way mesh.

        The gradient exchange is one commutative float add per element,
        so it is bitwise; step 0 (zero moments, nothing to contract)
        must therefore match bit for bit.  From step 1 on the moment
        update ``b*m + (1-b)*g`` is an FMA candidate, and XLA contracts
        it differently in the flat 128-lane shard program and in the
        (13, 7)-shaped reference (with FMA disabled via
        ``--xla_cpu_max_isa=SSE4_2`` the runs are bitwise equal).  One
        differently rounded product moves an update far below half a
        parameter ulp, so a parameter can flip by at most one ulp per
        such step: the bound asserted is steps-1 ulp."""
        import optax

        from ray_tpu.parallel.zero import (
            build_zero_train_step, create_zero_state,
        )

        n = 2
        mesh = Mesh(np.asarray(jax.devices()[:n]), ("data",))
        key = jax.random.PRNGKey(0)
        params = {"w": jax.random.normal(key, (13, 7)),
                  "b": jnp.zeros((7,))}
        opt = optax.adam(1e-2)

        def loss_fn(p, batch):
            pred = batch["x"] @ p["w"] + p["b"]
            return jnp.mean((pred - batch["y"]) ** 2)

        batch = {"x": jax.random.normal(jax.random.PRNGKey(1), (4, 13)),
                 "y": jax.random.normal(jax.random.PRNGKey(2), (4, 7))}

        # The zero step donates its state — give it copies so the
        # reference path below still owns live arrays.
        params0 = jax.tree.map(lambda x: jnp.array(np.asarray(x)), params)
        state = create_zero_state(params0, opt, mesh, "data")
        step = build_zero_train_step(loss_fn, opt, mesh, "data",
                                     collective=IMPL)
        steps = 3
        zero_params = []
        for _ in range(steps):
            state, metrics = step(state, batch)
            zero_params.append(
                jax.tree.map(lambda x: np.array(x), state.params))

        opt_shape = jax.eval_shape(lambda p: opt.init(p), params)

        def ref_step(p, o, b):
            loss, grads = jax.value_and_grad(loss_fn)(p, b)
            grads = jax.tree.map(lambda g: lax.psum(g, "data"), grads)
            updates, new_o = opt.update(grads, o, p)
            return optax.apply_updates(p, updates), new_o, loss

        ref_jit = jax.jit(shard_map(
            ref_step, mesh=mesh,
            in_specs=(P(), jax.tree.map(lambda _: P(), opt_shape),
                      {"x": P("data"), "y": P("data")}),
            out_specs=(P(), jax.tree.map(lambda _: P(), opt_shape), P()),
            check_vma=False))
        rp, ro = params, opt.init(params)
        for i in range(steps):
            rp, ro, _ = ref_jit(rp, ro, batch)
            for k in params:
                if i == 0:
                    np.testing.assert_array_equal(zero_params[i][k],
                                                  np.asarray(rp[k]))
                else:
                    np.testing.assert_array_max_ulp(
                        zero_params[i][k], np.asarray(rp[k]), maxulp=i)
        assert np.isfinite(float(metrics["loss"]))

    def test_weight_update_knob_validated(self):
        import optax

        from ray_tpu.parallel import (
            build_train_step, llama_param_shardings, make_mesh,
        )
        from ray_tpu.models.llama import LlamaConfig

        config = LlamaConfig.tiny()
        mesh = make_mesh({"data": -1})
        sh = llama_param_shardings(config, mesh)
        with pytest.raises(ValueError):
            build_train_step(lambda p, b: 0.0, optax.adam(1e-3), mesh,
                             sh, sh, weight_update="bogus")
