"""Model + parallel stack: llama forward/loss and dp/fsdp/tp parity on the
8-virtual-device CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from ray_tpu.models.llama import (
    LlamaConfig, forward, init_params, loss_fn,
)
from ray_tpu.parallel import (
    TrainState, batch_sharding, build_train_step, create_train_state,
    llama_param_shardings, make_mesh, shard_params,
)

CFG = LlamaConfig.tiny()


def _batch(bsz=8, seq=16, seed=0):
    rng = np.random.RandomState(seed)
    return {"tokens": jnp.asarray(
        rng.randint(0, CFG.vocab_size, (bsz, seq)), jnp.int32)}


class TestLlamaModel:
    def test_forward_shapes(self):
        params = init_params(CFG, jax.random.key(0))
        logits = forward(params, _batch()["tokens"], CFG)
        assert logits.shape == (8, 16, CFG.vocab_size)
        assert logits.dtype == jnp.float32

    def test_loss_finite_and_near_uniform(self):
        params = init_params(CFG, jax.random.key(0))
        loss = loss_fn(params, _batch(), CFG)
        assert np.isfinite(float(loss))
        # Random init => loss close to ln(vocab).
        assert abs(float(loss) - np.log(CFG.vocab_size)) < 1.0

    def test_causality(self):
        """Changing a future token must not affect earlier logits."""
        params = init_params(CFG, jax.random.key(0))
        toks = _batch(2, 16)["tokens"]
        logits1 = forward(params, toks, CFG)
        toks2 = toks.at[:, -1].set((toks[:, -1] + 1) % CFG.vocab_size)
        logits2 = forward(params, toks2, CFG)
        np.testing.assert_allclose(np.asarray(logits1[:, :-1]),
                                   np.asarray(logits2[:, :-1]),
                                   rtol=1e-4, atol=1e-4)

    def test_gqa_heads(self):
        cfg = LlamaConfig.tiny(n_heads=4, n_kv_heads=1)
        params = init_params(cfg, jax.random.key(0))
        logits = forward(params, _batch()["tokens"], cfg)
        assert logits.shape[-1] == cfg.vocab_size

    def test_remat_matches(self):
        cfg = LlamaConfig.tiny(remat=True)
        params = init_params(CFG, jax.random.key(0))
        l1 = loss_fn(params, _batch(), CFG)
        l2 = loss_fn(params, _batch(), cfg)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)

    def test_num_params_matches(self):
        params = init_params(CFG, jax.random.key(0))
        actual = sum(x.size for x in jax.tree.leaves(params))
        assert actual == CFG.num_params()


def _reference_step(params, batch, lr=0.01):
    loss, grads = jax.value_and_grad(
        lambda p, b: loss_fn(p, b, CFG))(params, batch)
    new = jax.tree.map(lambda a, g: a - lr * g, params, grads)
    return new, loss


class TestShardedTraining:
    @pytest.mark.parametrize("axes", [
        {"data": -1},                       # pure DP over 8
        {"fsdp": -1},                       # ZeRO-style over 8
        {"data": 2, "fsdp": 2, "tensor": 2},  # 3-way combo
        {"data": 4, "tensor": 2},           # DP x TP
    ])
    def test_parity_with_single_device(self, axes):
        """A sharded pjit step must produce the same loss trajectory as the
        unsharded single-device step (GSPMD correctness)."""
        mesh = make_mesh(axes)
        params = init_params(CFG, jax.random.key(0))
        sh = llama_param_shardings(CFG, mesh)
        bs = batch_sharding(mesh)
        opt = optax.sgd(0.01)
        sharded_params = shard_params(params, sh)
        state = create_train_state(sharded_params, opt)
        step = build_train_step(
            lambda p, b: loss_fn(p, b, CFG), opt, mesh, sh, bs)

        # Fresh tree: device_put may alias buffers that donation later
        # invalidates, so the reference must not share storage.
        ref_params = init_params(CFG, jax.random.key(0))
        for i in range(3):
            batch = _batch(seed=i)
            gbatch = jax.device_put(batch, bs)
            state, metrics = step(state, gbatch)
            ref_params, ref_loss = _reference_step(ref_params, batch)
            np.testing.assert_allclose(float(metrics["loss"]),
                                       float(ref_loss), rtol=2e-2, atol=2e-2)

    def test_grad_accum(self):
        mesh = make_mesh({"data": -1})
        params = init_params(CFG, jax.random.key(0))
        sh = llama_param_shardings(CFG, mesh)
        bs = batch_sharding(mesh)
        opt = optax.sgd(0.01)
        state = create_train_state(shard_params(params, sh), opt)
        step = build_train_step(lambda p, b: loss_fn(p, b, CFG), opt, mesh,
                                sh, bs, grad_accum=2)
        state, metrics = step(state, jax.device_put(_batch(16, 16), bs))
        assert np.isfinite(float(metrics["loss"]))

    def test_tp_must_divide_kv_heads(self):
        mesh = make_mesh({"tensor": 8})
        with pytest.raises(ValueError, match="n_kv_heads"):
            llama_param_shardings(LlamaConfig.tiny(n_kv_heads=2), mesh)


class TestMakeMeshErrors:
    """Mesh-shape mismatches must say what JAX actually discovered."""

    def test_mismatch_lists_devices_and_platform(self):
        with pytest.raises(ValueError) as e:
            make_mesh({"data": 3, "tensor": 5})   # 15 != 8
        msg = str(e.value)
        assert "needs 15 devices but 8 are available" in msg
        assert "discovered 8 device(s)" in msg
        assert "platform cpu" in msg
        assert "TFRT_CPU_0" in msg   # the actual device listing

    def test_indivisible_wildcard_names_the_axis(self):
        with pytest.raises(ValueError) as e:
            make_mesh({"data": -1, "tensor": 3})  # 8 % 3 != 0
        msg = str(e.value)
        assert "cannot infer axis 'data'" in msg
        assert "not divisible by the fixed-axis product 3" in msg
        assert "discovered 8 device(s)" in msg


class TestMultiHostDiscovery:
    """discover_devices joins jax.distributed exactly once, and only
    when coordinator env vars mark a multi-host launch (seen in the
    field: make_mesh saw 1 local device and rejected fsdp=4 because the
    global list is only visible after the join)."""

    def _reset(self, monkeypatch):
        from ray_tpu.parallel import mesh as mesh_mod
        for v in mesh_mod._COORDINATOR_VARS:
            monkeypatch.delenv(v, raising=False)
        monkeypatch.setattr(mesh_mod, "_distributed_join_attempted",
                            False)
        return mesh_mod

    def test_single_host_never_initializes(self, monkeypatch):
        mesh_mod = self._reset(monkeypatch)
        # A single-host TPU VM sets this too: it names no coordinator,
        # and an argument-less join there would go looking for one.
        monkeypatch.setenv("TPU_WORKER_HOSTNAMES", "localhost")
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda *a, **k: calls.append(1))
        assert len(mesh_mod.discover_devices()) == 8
        assert not calls                     # no coordinator: no join

    def test_multihost_env_joins_once(self, monkeypatch):
        mesh_mod = self._reset(monkeypatch)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda *a, **k: calls.append(1))
        mesh_mod.discover_devices()
        mesh_mod.discover_devices()          # once-guard
        assert len(calls) == 1

    def test_failed_join_raises(self, monkeypatch):
        """A join that was asked for and failed must not leave a mesh
        quietly built from this process's devices alone."""
        mesh_mod = self._reset(monkeypatch)
        monkeypatch.setenv("COORDINATOR_ADDRESS", "10.0.0.1:8476")

        def boom(*a, **k):
            raise RuntimeError("unreachable coordinator")

        monkeypatch.setattr(jax.distributed, "initialize", boom)
        with pytest.raises(RuntimeError, match="unreachable coordinator"):
            mesh_mod.discover_devices()

    def test_make_mesh_uses_global_discovery(self, monkeypatch):
        """The multi-axis request that failed in the field must work
        once discovery goes through the distributed join."""
        mesh_mod = self._reset(monkeypatch)
        monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:8476")
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda *a, **k: calls.append(1))
        mesh = make_mesh({"fsdp": 4, "tensor": 2})
        assert calls and mesh.shape["fsdp"] == 4

    def test_mesh_errors_report_process_topology(self):
        with pytest.raises(ValueError) as e:
            make_mesh({"data": 3, "tensor": 5})
        assert "process 0 of 1" in str(e.value)
