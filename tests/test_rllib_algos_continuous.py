"""SAC, TD3 and DDPG on Pendulum (reference: `rllib/algorithms/{sac,td3,
ddpg}`): the continuous-control half of `test_rllib_algos.py`, a file of
its own so that `--dist loadfile` can give the two halves to two workers
(together they held one for 348 s of an 837 s run)."""

import numpy as np
import pytest


@pytest.fixture(scope="module")
def rl_cluster():
    import ray_tpu

    info = ray_tpu.init(num_cpus=8, num_tpus=0,
                        object_store_memory=256 * 1024 * 1024,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


# --------------------------------------------------------------------- SAC

def test_pendulum_env_units():
    from ray_tpu.rllib.env.pendulum import PendulumEnv

    env = PendulumEnv(seed=0)
    obs, _ = env.reset(seed=1)
    assert obs.shape == (3,)
    assert env.action_space.shape == (1,)
    total = 0.0
    for t in range(200):
        obs, r, term, trunc, _ = env.step(np.array([0.5]))
        assert -1.001 <= obs[0] <= 1.001 and abs(obs[2]) <= 8.0
        assert r <= 0.0          # cost-shaped reward
        total += r
        assert not term
    assert trunc                 # 200-step horizon
    assert total < 0.0


def test_sac_module_and_learner_units():
    import jax
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.sac import SACLearner, SACModule
    from ray_tpu.rllib.core.rl_module import RLModuleSpec
    from ray_tpu.rllib.env.spaces import Box

    obs_space = Box(low=-np.ones(3), high=np.ones(3))
    act_space = Box(low=np.array([-2.0]), high=np.array([2.0]))
    mod = SACModule(obs_space, act_space, (16,))
    params = mod.init(jax.random.key(0))
    obs = jnp.zeros((32, 3), jnp.float32)
    act, logp = mod.sample_action(params["actor"], obs,
                                  jax.random.key(1))
    assert act.shape == (32, 1) and logp.shape == (32,)
    assert np.all(np.abs(np.asarray(act)) <= 2.0)  # squashed + scaled

    learner = SACLearner(
        RLModuleSpec(observation_space=obs_space, action_space=act_space,
                     hidden=(16,), module_class=SACModule),
        config={"lr": 3e-4, "seed": 0, "target_entropy": -1.0,
                "tau": 0.5})
    learner.build()
    batch = {
        "obs": np.random.RandomState(0).randn(32, 3).astype(np.float32),
        "next_obs": np.random.RandomState(1).randn(32, 3).astype(
            np.float32),
        "actions": np.random.RandomState(2).uniform(
            -2, 2, (32, 1)).astype(np.float32),
        "rewards": np.zeros(32, np.float32),
        "dones": np.zeros(32, np.float32),
    }
    before_target = learner._state["target"]["q1"]
    before_leaf = np.asarray(
        __import__("jax").tree.leaves(before_target)[0]).copy()
    metrics = learner.update(batch)
    for key in ("critic_loss", "actor_loss", "alpha", "entropy"):
        assert key in metrics
    # Polyak ran inside the jitted update (tau=0.5 moves targets visibly).
    after_leaf = np.asarray(
        __import__("jax").tree.leaves(learner._state["target"]["q1"])[0])
    assert not np.allclose(before_leaf, after_leaf)


def test_sac_pendulum_improves(rl_cluster):
    """SAC swing-up: returns improve well above the random-policy floor
    (~-1200 avg) within a few iterations."""
    from ray_tpu.rllib import SACConfig

    config = (SACConfig()
              .environment("Pendulum-v1")
              .training(lr=1e-3, train_batch_size=256)
              .env_runners(num_env_runners=1, num_envs_per_runner=4)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(64, 64)))
    config.learning_starts = 500
    config.rollout_fragment_length = 50      # 200 env steps / iteration
    config.num_updates_per_iteration = 100
    config.tau = 0.02                        # fast target tracking
    config.metrics_episode_window = 20
    algo = config.build()
    try:
        best = -np.inf
        for i in range(60):
            m = algo.train()
            r = m.get("episode_return_mean")
            if r is not None:
                best = max(best, r)
            if best >= -500:
                break
        assert best >= -500, best
    finally:
        algo.stop()


# --------------------------------------------------------------- TD3 / DDPG

def test_td3_module_and_learner_units():
    import jax
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.td3 import TD3Learner, TD3Module
    from ray_tpu.rllib.core.rl_module import RLModuleSpec
    from ray_tpu.rllib.env.spaces import Box

    obs_space = Box(low=-np.ones(3), high=np.ones(3))
    act_space = Box(low=np.array([-2.0]), high=np.array([2.0]))

    # DDPG flavor: no twin critic in the param tree.
    single = TD3Module(obs_space, act_space, (16,), twin_q=False)
    p = single.init(jax.random.key(0))
    assert "q2" not in p
    q1, q2 = single.q_values(p, jnp.zeros((4, 3)), jnp.zeros((4, 1)))
    assert np.allclose(np.asarray(q1), np.asarray(q2))  # aliased

    mod = TD3Module(obs_space, act_space, (16,), twin_q=True,
                    exploration_sigma=0.3)
    params = mod.init(jax.random.key(0))
    obs = jnp.zeros((32, 3), jnp.float32)
    det = mod.forward_inference(params, obs)["actions"]
    noisy = mod.forward_exploration(params, obs, jax.random.key(1))
    assert noisy["actions"].shape == (32, 1)
    assert np.all(np.abs(np.asarray(noisy["actions"])) <= 2.0)
    assert not np.allclose(np.asarray(det), np.asarray(noisy["actions"]))

    learner = TD3Learner(
        RLModuleSpec(observation_space=obs_space, action_space=act_space,
                     hidden=(16,), module_class=TD3Module,
                     module_kwargs={"twin_q": True}),
        config={"lr": 1e-3, "seed": 0, "tau": 0.5, "policy_delay": 2,
                "target_noise": 0.2})
    learner.build()
    batch = {
        "obs": np.random.RandomState(0).randn(32, 3).astype(np.float32),
        "next_obs": np.random.RandomState(1).randn(32, 3).astype(
            np.float32),
        "actions": np.random.RandomState(2).uniform(
            -2, 2, (32, 1)).astype(np.float32),
        "rewards": np.ones(32, np.float32),
        "dones": np.zeros(32, np.float32),
    }
    leaf = lambda s: np.asarray(  # noqa: E731
        jax.tree.leaves(s["target"]["actor"])[0]).copy()
    actor_leaf = lambda s: np.asarray(  # noqa: E731
        jax.tree.leaves(s["params"]["actor"])[0]).copy()
    t0, a0 = leaf(learner._state), actor_leaf(learner._state)
    metrics = learner.update(batch)
    for key in ("critic_loss", "actor_loss", "q1_mean", "target_q_mean"):
        assert key in metrics
    t1, a1 = leaf(learner._state), actor_leaf(learner._state)
    assert not np.allclose(t0, t1)     # step 0: mask=1 -> polyak ran
    assert not np.allclose(a0, a1)     # step 0: actor stepped
    metrics = learner.update(batch)
    t2, a2 = leaf(learner._state), actor_leaf(learner._state)
    assert np.allclose(t1, t2)         # step 1: mask=0 -> targets frozen
    # Step 1: actor params EXACTLY frozen — the interval optimizer must
    # not leak Adam momentum into skipped steps (a zeroed loss alone
    # would still move the actor).
    assert np.array_equal(a1, a2)
    learner.update(batch)
    assert not np.allclose(t2, leaf(learner._state))  # step 2: mask=1 again
    assert not np.allclose(a2, actor_leaf(learner._state))


def test_td3_action_space_affine_map_and_validation():
    """Asymmetric Box bounds map through center + tanh * scale;
    unbounded or degenerate boxes fail at module construction."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.td3 import TD3Module
    from ray_tpu.rllib.env.spaces import Box

    obs_space = Box(low=-np.ones(3), high=np.ones(3))
    act_space = Box(low=np.array([0.0, -1.0]), high=np.array([4.0, 3.0]))
    mod = TD3Module(obs_space, act_space, (8,), twin_q=False,
                    exploration_sigma=0.5)
    params = mod.init(jax.random.key(0))
    obs = jax.random.normal(jax.random.key(1), (64, 3))
    det = np.asarray(mod.forward_inference(params, obs)["actions"])
    lo, hi = np.array([0.0, -1.0]), np.array([4.0, 3.0])
    assert det.shape == (64, 2)
    assert (det >= lo - 1e-6).all() and (det <= hi + 1e-6).all()
    noisy = np.asarray(
        mod.forward_exploration(params, obs, jax.random.key(2))["actions"])
    assert (noisy >= lo - 1e-6).all() and (noisy <= hi + 1e-6).all()
    # Zero-mean mu hits the center of the box, not zero.
    zero_mu = np.asarray(mod._act_center + jnp.tanh(0.0) * mod._act_scale)
    assert np.allclose(zero_mu, (lo + hi) / 2)

    with pytest.raises(ValueError):
        TD3Module(obs_space, Box(low=np.array([-np.inf]),
                                 high=np.array([np.inf])))
    with pytest.raises(ValueError):
        TD3Module(obs_space, Box(low=np.array([1.0]),
                                 high=np.array([1.0])))


def test_td3_pendulum_improves(rl_cluster):
    """TD3 swing-up clears the same bar as SAC (random floor ~-1200)."""
    from ray_tpu.rllib import TD3Config

    config = (TD3Config()
              .environment("Pendulum-v1")
              .training(lr=1e-3, train_batch_size=256)
              .env_runners(num_env_runners=1, num_envs_per_runner=4)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(64, 64)))
    config.learning_starts = 500
    config.rollout_fragment_length = 50      # 200 env steps / iteration
    config.num_updates_per_iteration = 100
    config.tau = 0.02
    config.exploration_sigma = 0.15
    config.metrics_episode_window = 20
    algo = config.build()
    try:
        best = -np.inf
        for i in range(60):
            m = algo.train()
            r = m.get("episode_return_mean")
            if r is not None:
                best = max(best, r)
            if best >= -500:
                break
        assert best >= -500, best
    finally:
        algo.stop()


def test_ddpg_smoke(rl_cluster):
    """DDPG builds (single critic, no delay/smoothing) and trains without
    NaNs; learning quality is TD3's job."""
    from ray_tpu.rllib import DDPGConfig

    config = (DDPGConfig()
              .environment("Pendulum-v1")
              .training(lr=1e-3, train_batch_size=128)
              .env_runners(num_env_runners=1, num_envs_per_runner=2)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(32,)))
    config.learning_starts = 200
    config.rollout_fragment_length = 50
    config.num_updates_per_iteration = 10
    algo = config.build()
    try:
        for _ in range(3):
            m = algo.train()
        assert m["num_gradient_updates"] > 0
        assert np.isfinite(m["critic_loss"])
    finally:
        algo.stop()
