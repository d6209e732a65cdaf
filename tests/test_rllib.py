"""RLlib-equivalent: RLModule/Learner/LearnerGroup units + PPO CartPole e2e
(reference: `rllib/core/learner/learner_group.py`, `algorithms/ppo/ppo.py`).
PPO must reach the published CartPole-v1 target (475) on the CPU tier."""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import PPO, PPOConfig
from ray_tpu.rllib.core.rl_module import MLPModule, RLModuleSpec
from ray_tpu.rllib.env import CartPoleEnv
from ray_tpu.rllib.env.cartpole import make_env, register_env


def test_cartpole_env_physics():
    env = CartPoleEnv(seed=0)
    obs, _ = env.reset()
    assert obs.shape == (4,)
    assert np.all(np.abs(obs) <= 0.05)
    total = 0.0
    for _ in range(600):
        obs, r, term, trunc, _ = env.step(1)  # constant push tips the pole
        total += r
        if term or trunc:
            break
    assert term  # constant force terminates well before the 500 cap
    assert total < 100


def test_cartpole_truncates_at_500():
    env = CartPoleEnv(seed=1)
    env.reset()
    # Alternate pushes roughly balance; force truncation by patching limits.
    env.THETA_LIMIT = 100.0
    env.X_LIMIT = 1e9
    steps = 0
    while True:
        _, _, term, trunc, _ = env.step(steps % 2)
        steps += 1
        if term or trunc:
            break
    assert trunc and steps == 500


def test_rl_module_forward_shapes():
    env = CartPoleEnv()
    spec = RLModuleSpec(env.observation_space, env.action_space,
                        hidden=(16,))
    module = spec.build()
    import jax

    params = module.init(jax.random.key(0))
    obs = np.zeros((5, 4), np.float32)
    out = module.forward_train(params, obs)
    assert out["action_logits"].shape == (5, 2)
    assert out["vf"].shape == (5,)
    expl = module.forward_exploration(params, obs, jax.random.key(1))
    assert expl["actions"].shape == (5,)
    assert np.all(np.asarray(expl["logp"]) <= 0)


@pytest.mark.parametrize("rows,n", [(7, 2), (10, 3), (5, 5), (9, 4)])
def test_split_batch_conserves_remainder_rows(rows, n):
    """Uneven splits distribute the remainder instead of dropping it —
    every row lands in exactly one shard, larger shards first."""
    from ray_tpu.rllib.core.learner_group import _split_batch

    batch = {"obs": np.arange(rows * 2, dtype=np.float32).reshape(rows, 2),
             "actions": np.arange(rows, dtype=np.int32)}
    shards = _split_batch(batch, n)
    assert len(shards) == n
    sizes = [len(s["actions"]) for s in shards]
    assert sum(sizes) == rows
    assert max(sizes) - min(sizes) <= 1
    assert sizes == sorted(sizes, reverse=True)
    merged = np.concatenate([s["actions"] for s in shards])
    np.testing.assert_array_equal(merged, batch["actions"])
    merged_obs = np.concatenate([s["obs"] for s in shards])
    np.testing.assert_array_equal(merged_obs, batch["obs"])


@pytest.mark.parametrize("num_learners", [1, 2])
def test_learner_group_update_improves_loss(ray_start_regular, num_learners):
    if num_learners > 1:
        import jax

        if not hasattr(jax.config, "jax_num_cpu_devices"):
            pytest.skip("installed jax lacks multiprocess CPU collectives "
                        "(gloo); the 2-learner group needs cross-process "
                        "allreduce")
    from ray_tpu.rllib.algorithms.ppo import PPOLearner
    from ray_tpu.rllib.core.learner_group import LearnerGroup
    from ray_tpu.train.config import ScalingConfig
    from ray_tpu.train.jax_backend import JaxConfig

    env = CartPoleEnv()
    spec = RLModuleSpec(env.observation_space, env.action_space,
                        hidden=(16,))
    group = LearnerGroup(
        PPOLearner, spec, learner_config={"lr": 1e-2},
        scaling_config=ScalingConfig(num_workers=num_learners),
        jax_config=JaxConfig(platform="cpu", num_cpu_devices=2))
    try:
        rng = np.random.RandomState(0)
        batch = {
            "obs": rng.randn(64, 4).astype(np.float32),
            "actions": rng.randint(0, 2, 64).astype(np.int32),
            "logp_old": np.full(64, -0.693, np.float32),
            "advantages": rng.randn(64).astype(np.float32),
            "value_targets": rng.randn(64).astype(np.float32),
        }
        first = group.update(batch)
        for _ in range(10):
            last = group.update(batch)
        assert last["vf_loss"] < first["vf_loss"]
        w = group.get_weights()
        group.set_weights(w)  # roundtrip
    finally:
        group.shutdown()


def test_ppo_cartpole_reaches_target(ray_start_regular):
    """PPO solves CartPole-v1: mean episode return >= 475."""
    config = (
        PPOConfig()
        .environment("CartPole-v1")
        .training(lr=1e-3, train_batch_size=2048, num_epochs=10,
                  minibatch_size=256, gamma=0.99, gae_lambda=0.95,
                  entropy_coeff=0.01)
        .env_runners(num_env_runners=2, num_envs_per_runner=8)
        .learners(num_learners=1, jax_platform="cpu")
    )
    algo = config.build()
    try:
        best = 0.0
        for i in range(45):
            result = algo.train()
            ret = result.get("episode_return_mean", 0.0)
            best = max(best, ret)
            if ret >= 475:
                break
        assert best >= 475, f"PPO best return {best} < 475"
    finally:
        algo.stop()


def test_custom_env_registration(ray_start_regular):
    class TinyEnv(CartPoleEnv):
        MAX_STEPS = 10

    register_env("Tiny-v0", TinyEnv)
    env = make_env("Tiny-v0")
    assert isinstance(env, TinyEnv)
