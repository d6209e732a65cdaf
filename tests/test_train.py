"""Ray-Train-equivalent e2e: JaxTrainer data-parallel training on a fake
2-host x 4-device CPU mesh — THE e2e milestone from SURVEY §7 M5."""

import json
import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu import train
from ray_tpu.train import (
    Checkpoint, CheckpointConfig, FailureConfig, JaxConfig, JaxTrainer,
    RunConfig, ScalingConfig, TrainingFailedError,
)


def _jax_cpu_multiprocess_supported() -> bool:
    """jax < 0.5 raises INVALID_ARGUMENT on any cross-process CPU
    computation (no gloo transport); the jax_num_cpu_devices config option
    landed in the same release line and is a cheap capability probe."""
    import jax

    return hasattr(jax.config, "jax_num_cpu_devices")


_needs_cpu_multiprocess = pytest.mark.skipif(
    not _jax_cpu_multiprocess_supported(),
    reason="installed jax lacks multiprocess CPU collectives (gloo)")


def mlp_train_loop(config):
    """Data-parallel MLP regression with a pjit'd step over the global mesh.
    Runs inside each train worker (2 processes x 4 virtual CPU devices)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu import train

    ctx = train.get_context()
    world = ctx.get_world_size()
    rank = ctx.get_world_rank()

    # Global mesh over ALL devices of the gang (both processes).
    mesh = jax.make_mesh((jax.device_count(),), ("data",))
    repl = NamedSharding(mesh, P())
    data_sharded = NamedSharding(mesh, P("data"))

    rng = np.random.RandomState(0)
    w_true = rng.randn(8, 1).astype(np.float32)

    def init_params(key):
        k1, k2 = jax.random.split(key)
        return {
            "w1": jax.random.normal(k1, (8, 32)) * 0.1,
            "b1": jnp.zeros(32),
            "w2": jax.random.normal(k2, (32, 1)) * 0.1,
            "b2": jnp.zeros(1),
        }

    start_epoch = 0
    ckpt = ctx.get_checkpoint()
    if ckpt is not None:
        state = ckpt.to_pytree()
        params = jax.device_put(state["params"], repl)
        start_epoch = int(state["epoch"]) + 1
    else:
        params = jax.device_put(init_params(jax.random.key(0)), repl)

    def loss_fn(p, x, y):
        h = jnp.tanh(x @ p["w1"] + p["b1"])
        pred = h @ p["w2"] + p["b2"]
        return jnp.mean((pred - y) ** 2)

    @jax.jit
    def step(p, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        new_p = jax.tree.map(lambda a, g: a - 0.1 * g, p, grads)
        return new_p, loss

    batch_global = 64
    epochs = config.get("epochs", 4)
    for epoch in range(start_epoch, epochs):
        # Each process contributes its local shard of the global batch.
        x_local = rng.randn(batch_global // world, 8).astype(np.float32)
        y_local = x_local @ w_true
        from jax.experimental import multihost_utils

        x = multihost_utils.host_local_array_to_global_array(
            x_local, mesh, P("data"))
        y = multihost_utils.host_local_array_to_global_array(
            y_local, mesh, P("data"))
        params, loss = step(params, x, y)
        loss_val = float(loss)

        checkpoint = None
        if rank == 0:
            checkpoint = Checkpoint.from_pytree(
                {"params": jax.device_get(params), "epoch": epoch})
        train.report({"loss": loss_val, "epoch": epoch},
                     checkpoint=checkpoint)


@pytest.fixture(scope="module")
def train_cluster():
    import ray_tpu

    info = ray_tpu.init(num_cpus=8, num_tpus=0,
                        object_store_memory=256 * 1024 * 1024,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


class TestJaxTrainer:
    @_needs_cpu_multiprocess
    def test_dp_training_2workers(self, train_cluster, tmp_path):
        trainer = JaxTrainer(
            mlp_train_loop,
            train_loop_config={"epochs": 4},
            scaling_config=ScalingConfig(num_workers=2),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=4),
            run_config=RunConfig(name="mlp-dp", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.metrics["epoch"] == 3
        assert len(result.metrics_dataframe) == 4
        losses = [m["loss"] for m in result.metrics_dataframe]
        assert losses[-1] < losses[0]  # actually learning
        assert result.checkpoint is not None
        state = result.checkpoint.to_pytree()
        assert state["epoch"] == 3

    @_needs_cpu_multiprocess
    def test_resume_from_checkpoint(self, train_cluster, tmp_path):
        trainer = JaxTrainer(
            mlp_train_loop,
            train_loop_config={"epochs": 2},
            scaling_config=ScalingConfig(num_workers=2),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=4),
            run_config=RunConfig(name="mlp-r1", storage_path=str(tmp_path)),
        )
        r1 = trainer.fit()
        assert r1.metrics["epoch"] == 1

        trainer2 = JaxTrainer(
            mlp_train_loop,
            train_loop_config={"epochs": 4},
            scaling_config=ScalingConfig(num_workers=2),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=4),
            run_config=RunConfig(name="mlp-r2", storage_path=str(tmp_path)),
            resume_from_checkpoint=r1.checkpoint,
        )
        r2 = trainer2.fit()
        # Resumed at epoch 2, so only epochs 2..3 ran.
        assert r2.metrics["epoch"] == 3
        assert len(r2.metrics_dataframe) == 2

    def test_single_worker(self, train_cluster, tmp_path):
        trainer = JaxTrainer(
            mlp_train_loop,
            train_loop_config={"epochs": 2},
            scaling_config=ScalingConfig(num_workers=1),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=4),
            run_config=RunConfig(name="mlp-1w", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        assert result.metrics["epoch"] == 1

    def test_failure_restart(self, train_cluster, tmp_path):
        """Worker crash mid-training: gang restarts from latest checkpoint
        (FailureConfig.max_failures, reference backend_executor._restart)."""

        def crashing_loop(config):
            import os

            from ray_tpu import train
            from ray_tpu.train import Checkpoint

            ctx = train.get_context()
            start = 0
            ckpt = ctx.get_checkpoint()
            if ckpt is not None:
                start = ckpt.to_dict()["epoch"] + 1
            marker = config["marker"]
            for epoch in range(start, 4):
                if epoch == 2 and ctx.get_world_rank() == 0 \
                        and not os.path.exists(marker):
                    open(marker, "w").close()
                    os._exit(1)  # hard crash, like a dead TPU host
                checkpoint = None
                if ctx.get_world_rank() == 0:
                    checkpoint = Checkpoint.from_dict({"epoch": epoch})
                train.report({"epoch": epoch}, checkpoint=checkpoint)

        marker = str(tmp_path / "crashed.marker")
        trainer = JaxTrainer(
            crashing_loop,
            train_loop_config={"marker": marker},
            scaling_config=ScalingConfig(num_workers=2),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=2),
            run_config=RunConfig(
                name="mlp-ft", storage_path=str(tmp_path),
                failure_config=FailureConfig(max_failures=1)),
        )
        result = trainer.fit()
        assert os.path.exists(marker)  # the crash really happened
        assert result.metrics["epoch"] == 3

    def test_failure_budget_exhausted(self, train_cluster, tmp_path):
        def always_fail(config):
            raise RuntimeError("deliberate")

        trainer = JaxTrainer(
            always_fail,
            scaling_config=ScalingConfig(num_workers=1),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=1),
            run_config=RunConfig(name="mlp-fail", storage_path=str(tmp_path)),
        )
        with pytest.raises(TrainingFailedError):
            trainer.fit()


def ingestion_train_loop(config):
    """Consumes a streaming_split Data shard (Train<->Data ingestion,
    reference `train/_internal/data_config.py`).  Every worker lays
    aside what it was fed: the rows' indices an epoch and the loss of
    each batch, in the order it took them."""
    import json
    import os

    import numpy as np

    from ray_tpu import train

    it = train.get_dataset_shard("train")
    assert it is not None, "dataset shard missing"
    rank = train.get_context().get_world_rank()
    w = np.zeros(4, np.float32)
    fed = {"rows": [], "batch_losses": []}
    for epoch in range(config.get("epochs", 2)):
        n_rows = 0
        loss_sum = 0.0
        fed["rows"].append([])
        for batch in it.iter_batches(batch_size=16):
            x = np.stack(batch["x"]).astype(np.float32)
            y = np.asarray(batch["y"], np.float32)
            pred = x @ w
            err = pred - y
            loss_sum += float((err ** 2).sum())
            n_rows += len(y)
            fed["rows"][-1] += [int(i) for i in batch["i"]]
            fed["batch_losses"].append(float((err ** 2).mean()))
            w -= 0.05 * (x.T @ err) / max(len(y), 1)  # SGD on the shard
        train.report({"loss": loss_sum / max(n_rows, 1), "rows": n_rows,
                      "epoch": epoch})
    with open(os.path.join(config["fed_dir"], f"rank{rank}.json"), "w") as f:
        json.dump(fed, f)


class TestTrainDataIngestion:
    def test_streaming_split_feeds_two_workers(self, train_cluster, tmp_path):
        from ray_tpu import data as rdata

        rng = np.random.RandomState(7)
        xs = rng.randn(256, 4).astype(np.float32)
        w_true = np.array([1.0, -2.0, 0.5, 3.0], np.float32)
        ys = xs @ w_true
        ds = rdata.from_items(
            [{"i": i, "x": xs[i], "y": float(ys[i])} for i in range(256)],
            override_num_blocks=8,
        ).map_batches(lambda b: b)  # exercise a fused transform stage

        trainer = JaxTrainer(
            ingestion_train_loop,
            train_loop_config={"epochs": 2, "fed_dir": str(tmp_path)},
            datasets={"train": ds},
            scaling_config=ScalingConfig(num_workers=2),
            jax_config=JaxConfig(platform="cpu", num_cpu_devices=1),
            run_config=RunConfig(name="ingest", storage_path=str(tmp_path)),
        )
        result = trainer.fit()
        # Both epochs ran and the split streamed every row exactly once
        # per epoch across the two workers.  Who takes how many is first
        # come, first served: under six loaded workers one rank can take a
        # whole epoch before the other asks, so the rows are counted over
        # both ranks (until PR 46: rank 0's `rows > 0` every epoch and its
        # loss lower in the second, which fails then), and the loss must
        # fall for whoever was fed enough to learn.
        assert result.metrics["epoch"] == 1
        assert len(result.metrics_dataframe) == 2
        fed = []
        for rank in range(2):
            with open(tmp_path / f"rank{rank}.json") as f:
                fed.append(json.load(f))
        for epoch in range(2):
            rows = fed[0]["rows"][epoch] + fed[1]["rows"][epoch]
            assert sorted(rows) == list(range(256)), epoch
        learned = 0
        for losses in (f["batch_losses"] for f in fed):
            if len(losses) >= 8:         # of 32 batches: one rank at least
                q = len(losses) // 4
                assert np.mean(losses[-q:]) < 0.5 * np.mean(losses[:q])
                learned += 1
        assert learned >= 1


# ------------------------------------------------------------ torch tier

def torch_ddp_loop(config):
    """DDP linear regression: gradients allreduce over gloo."""
    import numpy as np
    import torch
    import torch.distributed as dist

    from ray_tpu import train

    ctx = train.get_context()
    assert dist.is_initialized() and dist.get_world_size() == 2
    assert dist.get_rank() == ctx.get_world_rank()

    torch.manual_seed(0)
    model = torch.nn.Linear(4, 1)
    ddp = torch.nn.parallel.DistributedDataParallel(model)
    opt = torch.optim.SGD(ddp.parameters(), lr=0.1)
    rng = np.random.RandomState(ctx.get_world_rank())
    w_true = np.arange(1.0, 5.0, dtype=np.float32)
    for i in range(30):
        x = torch.from_numpy(rng.randn(16, 4).astype(np.float32))
        y = (x @ torch.from_numpy(w_true))[:, None]
        loss = torch.nn.functional.mse_loss(ddp(x), y)
        opt.zero_grad(); loss.backward(); opt.step()
        train.report({"loss": float(loss)})
    # DDP sync proof, asserted ACROSS ranks: allreduce would be a no-op
    # on identical replicas, so gather both ranks' weights and compare.
    w = model.weight.detach().clone()
    gathered = [torch.zeros_like(w) for _ in range(2)]
    dist.all_gather(gathered, w)
    np.testing.assert_allclose(gathered[0].numpy(), gathered[1].numpy(),
                               rtol=0, atol=1e-6)
    train.report({"loss": float(loss), "synced": True})


def test_torch_trainer_ddp_gloo(ray_start_regular):
    """TorchTrainer forms a gloo process group over the same worker-group
    machinery as JaxTrainer (reference: train/torch/config.py:146)."""
    from ray_tpu.train import ScalingConfig, TorchTrainer

    trainer = TorchTrainer(
        torch_ddp_loop,
        scaling_config=ScalingConfig(num_workers=2))
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["loss"] < 0.2, result.metrics


def test_torch_config_rejects_nccl():
    from ray_tpu.train.torch_backend import TorchBackend, TorchConfig

    with pytest.raises(ValueError, match="gloo"):
        TorchBackend().on_start(
            type("G", (), {"num_workers": 2, "metadata": lambda s: [],
                           "execute_single": lambda s, *a: 0,
                           "workers": []})(),
            TorchConfig(backend="nccl"))


def test_torch_trainer_single_worker_group_forms(ray_start_regular):
    """world_size=1 still forms the gloo group: the docstring's DDP
    pattern must work at any scale."""
    from ray_tpu.train import ScalingConfig, TorchTrainer

    def loop(config):
        import torch
        import torch.distributed as dist

        from ray_tpu import train

        assert dist.is_initialized() and dist.get_world_size() == 1
        model = torch.nn.parallel.DistributedDataParallel(
            torch.nn.Linear(2, 1))
        out = model(torch.zeros(3, 2))
        train.report({"ok": float(out.shape[0])})

    result = TorchTrainer(
        loop, scaling_config=ScalingConfig(num_workers=1)).fit()
    assert result.error is None, result.error
    assert result.metrics["ok"] == 3.0
