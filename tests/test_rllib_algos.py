"""DQN, IMPALA, BC, APPO, ES and ARS (reference: `rllib/algorithms/`);
SAC, TD3 and DDPG are in `test_rllib_algos_continuous.py`."""

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


def test_vtrace_reduces_to_returns_on_policy():
    """With identical policies (rho=c=1) and no discount truncation,
    V-trace vs equals the n-step bootstrapped return."""
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.impala import vtrace

    T, B = 5, 3
    rng = np.random.RandomState(0)
    logp = jnp.asarray(rng.randn(T, B).astype(np.float32))
    rewards = jnp.asarray(rng.randn(T, B).astype(np.float32))
    dones = jnp.zeros((T, B), jnp.float32)
    values = jnp.asarray(rng.randn(T, B).astype(np.float32))
    bootstrap = jnp.asarray(rng.randn(B).astype(np.float32))
    gamma = 0.9

    vs, pg_adv = vtrace(logp, logp, rewards, dones, values, bootstrap,
                        gamma)
    # On-policy (rho=c=1): vs_t = sum_{k>=t} gamma^{k-t} r_k + gamma^{T-t} V_T
    expect = np.zeros((T, B), np.float32)
    acc = np.asarray(bootstrap)
    for t in range(T - 1, -1, -1):
        acc = np.asarray(rewards[t]) + gamma * acc
        expect[t] = acc
    np.testing.assert_allclose(np.asarray(vs), expect, rtol=1e-4, atol=1e-4)

    # A done cuts the recursion.
    dones2 = dones.at[2].set(1.0)
    vs2, _ = vtrace(logp, logp, rewards, dones2, values, bootstrap, gamma)
    np.testing.assert_allclose(np.asarray(vs2[2]), np.asarray(rewards[2]),
                               rtol=1e-4, atol=1e-4)


def test_qmodule_epsilon_greedy():
    import jax
    import jax.numpy as jnp

    from ray_tpu.rllib.algorithms.dqn import QModule
    from ray_tpu.rllib.env.spaces import Box, Discrete

    mod = QModule(Box(low=-np.ones(4), high=np.ones(4)), Discrete(2), (16,))
    params = mod.init(jax.random.key(0))
    obs = jnp.zeros((8, 4), jnp.float32)

    # epsilon=0 -> deterministic greedy
    params["epsilon"] = jnp.asarray(0.0, jnp.float32)
    a1 = mod.forward_exploration(params, obs, jax.random.key(1))["actions"]
    a2 = mod.forward_exploration(params, obs, jax.random.key(2))["actions"]
    np.testing.assert_array_equal(np.asarray(a1), np.asarray(a2))

    # epsilon=1 -> uniform random (both actions appear across keys)
    params["epsilon"] = jnp.asarray(1.0, jnp.float32)
    seen = set()
    for i in range(6):
        a = mod.forward_exploration(params, obs,
                                    jax.random.key(i))["actions"]
        seen.update(np.asarray(a).tolist())
    assert seen == {0, 1}


def test_dqn_learner_units():
    """TD loss decreases on a fixed synthetic batch; target sync works."""
    import jax

    from ray_tpu.rllib.algorithms.dqn import DQNLearner, QModule
    from ray_tpu.rllib.core.rl_module import RLModuleSpec
    from ray_tpu.rllib.env.spaces import Box, Discrete

    spec = RLModuleSpec(Box(low=-np.ones(4), high=np.ones(4)), Discrete(2),
                        hidden=(32,), module_class=QModule)
    learner = DQNLearner(spec, {"lr": 1e-2, "gamma": 0.9})
    learner.build()
    rng = np.random.RandomState(0)
    batch = {
        "obs": rng.randn(64, 4).astype(np.float32),
        "next_obs": rng.randn(64, 4).astype(np.float32),
        "actions": rng.randint(0, 2, 64).astype(np.int32),
        "rewards": rng.randn(64).astype(np.float32),
        "dones": (rng.rand(64) < 0.1).astype(np.float32),
    }
    losses = [learner.update(batch, rng_seed=i)["td_loss"]
              for i in range(30)]
    assert losses[-1] < losses[0]
    learner.sync_target()
    t = learner._state["target"]
    p = learner._state["params"]
    assert jax.tree.all(jax.tree.map(
        lambda a, b: bool((np.asarray(a) == np.asarray(b)).all()), t, p))


def test_dqn_cartpole_improves(rl_cluster):
    from ray_tpu.rllib import DQNConfig

    config = (DQNConfig()
              .environment("CartPole-v1")
              .training(lr=1e-3, train_batch_size=64)
              .env_runners(num_env_runners=1, num_envs_per_runner=4)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(64, 64)))
    config.learning_starts = 300
    config.rollout_fragment_length = 32      # 128 env steps / iteration
    config.epsilon_decay_steps = 4000
    config.num_updates_per_iteration = 48
    config.target_update_freq = 100
    algo = config.build()
    try:
        first = None
        best = -np.inf
        for i in range(60):
            m = algo.train()
            r = m.get("episode_return_mean")
            if r is not None:
                if first is None:
                    first = r
                best = max(best, r)
            if best >= 60:
                break
        assert first is not None
        assert best >= 60, (first, best)
    finally:
        algo.stop()


def test_impala_cartpole_improves(rl_cluster):
    from ray_tpu.rllib import IMPALAConfig

    config = (IMPALAConfig()
              .environment("CartPole-v1")
              .training(lr=5e-4)
              .env_runners(num_env_runners=2, num_envs_per_runner=4)
              .learners(num_learners=1, jax_platform="cpu"))
    config.rollout_fragment_length = 32
    config.num_rollouts_per_iteration = 8
    algo = config.build()
    try:
        best = -np.inf
        for i in range(60):
            m = algo.train()
            r = m.get("episode_return_mean")
            if r is not None:
                best = max(best, r)
            if best >= 100:
                break
        assert best >= 100, best
    finally:
        algo.stop()


# ---------------------------------------------------------------------- BC

def test_bc_clones_expert(rl_cluster):
    """BC on a scripted CartPole expert: the cloned policy far outlasts
    random play (reference: `rllib/algorithms/bc`)."""
    from ray_tpu.rllib import BCConfig
    from ray_tpu.rllib.env.cartpole import CartPoleEnv

    # Scripted expert: push the cart toward the pole's lean.
    env = CartPoleEnv(seed=0)
    rows = []
    for ep in range(40):
        obs, _ = env.reset(seed=ep)
        done = False
        while not done:
            a = int(obs[2] + 0.3 * obs[3] > 0)
            rows.append({"obs": obs.astype(np.float32), "actions": a})
            obs, _, term, trunc, _ = env.step(a)
            done = term or trunc

    config = (BCConfig()
              .environment("CartPole-v1")
              .training(lr=3e-3, train_batch_size=256)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(32, 32))
              .offline_data(rows))
    config.num_batches_per_iteration = 40
    algo = config.build()
    try:
        for _ in range(15):
            m = algo.train()
            if m["bc_accuracy"] > 0.92:
                break
        assert m["bc_accuracy"] > 0.9, m
        ev = algo.evaluate(num_episodes=5)
        assert ev["episode_return_mean"] >= 100, ev
    finally:
        algo.stop()


def test_bc_over_data_dataset(rl_cluster):
    """BC ingests a ray_tpu.data Dataset (offline-RL over the Data
    library, reference: `rllib/offline/`)."""
    from ray_tpu import data as rdata
    from ray_tpu.rllib import BCConfig

    rng = np.random.RandomState(0)
    obs = rng.randn(512, 4).astype(np.float32)
    actions = (obs[:, 2] > 0).astype(np.int64)   # linearly separable
    ds = rdata.from_items([{"obs": o, "actions": int(a)}
                           for o, a in zip(obs, actions)])

    config = (BCConfig()
              .environment("CartPole-v1")
              .training(lr=3e-3, train_batch_size=128)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(32,))
              .offline_data(ds))
    config.num_batches_per_iteration = 30
    algo = config.build()
    try:
        for _ in range(4):
            m = algo.train()
        assert m["bc_accuracy"] > 0.9, m
    finally:
        algo.stop()


# -------------------------------------------------------------------- APPO

def test_appo_cartpole_improves(rl_cluster):
    """APPO = IMPALA architecture + PPO clip on V-trace advantages
    (reference: rllib/algorithms/appo)."""
    from ray_tpu.rllib import APPOConfig

    config = (APPOConfig()
              .environment("CartPole-v1")
              .training(lr=5e-4)
              .env_runners(num_env_runners=2, num_envs_per_runner=4)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(64, 64)))
    config.rollout_fragment_length = 32
    config.num_rollouts_per_iteration = 8
    config.num_rollouts_per_update = 2
    config.metrics_episode_window = 30
    algo = config.build()
    try:
        best = -np.inf
        for i in range(40):
            m = algo.train()
            r = m.get("episode_return_mean")
            if r is not None:
                best = max(best, r)
            if best >= 100:
                break
        assert best >= 100, best
        # The surrogate's clip metrics flow through (engagement depends
        # on how off-policy the sampled rollouts happened to be).
        assert "clip_frac" in m and "mean_ratio" in m
    finally:
        algo.stop()


# ----------------------------------------------------------------- ES / ARS

def test_centered_ranks_units():
    from ray_tpu.rllib.algorithms.es import _centered_ranks

    r = _centered_ranks(np.array([10.0, -5.0, 3.0, 100.0]))
    assert np.isclose(r.max(), 0.5) and np.isclose(r.min(), -0.5)
    assert r[3] == 0.5 and r[1] == -0.5      # rank order, not magnitude
    assert np.isclose(r.sum(), 0.0, atol=1e-6)
    # Shape-preserving for the (P, 2) antithetic layout.
    m = _centered_ranks(np.arange(6, dtype=np.float32).reshape(3, 2))
    assert m.shape == (3, 2)


def test_es_cartpole_improves(rl_cluster):
    """Gradient-free ES clears the CartPole bar using only episode
    returns (no backprop anywhere in the update path)."""
    from ray_tpu.rllib import ESConfig

    config = (ESConfig()
              .environment("CartPole-v1")
              .training(lr=0.05)
              .env_runners(num_env_runners=2, num_envs_per_runner=1)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(32,)))
    config.noise_stdev = 0.1
    config.num_perturbations = 24
    config.metrics_episode_window = 48
    algo = config.build()
    try:
        best = -np.inf
        for i in range(30):
            m = algo.train()
            best = max(best, m["perturbed_return_max"])
            if m.get("episode_return_mean", 0) >= 100:
                break
        assert best >= 150, best
    finally:
        algo.stop()


def test_ars_smoke(rl_cluster):
    """ARS variant: top-k direction selection + std shaping run end to
    end and report selection metrics."""
    from ray_tpu.rllib import ARSConfig

    config = (ARSConfig()
              .environment("CartPole-v1")
              .training(lr=0.05)
              .env_runners(num_env_runners=2, num_envs_per_runner=1)
              .learners(num_learners=1, jax_platform="cpu")
              .rl_module(hidden=(16,)))
    config.num_perturbations = 8
    algo = config.build()
    try:
        m = algo.train()
        assert m["directions_kept"] == 4        # top_fraction 0.5
        assert np.isfinite(m["perturbed_return_mean"])
        assert np.isfinite(m["update_norm"])
    finally:
        algo.stop()
