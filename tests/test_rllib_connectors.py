"""Connector pipelines (reference: `rllib/connectors/connector_v2.py` —
env→module and module→learner transformation stages). Unit tests per
stage + PPO CartPole learning through a 3-stage pipeline without the
runner hard-coding any preprocessing."""

import numpy as np
import pytest

from ray_tpu.rllib.connectors import (
    ClipObs, ConnectorPipeline, FrameStack, ObsNormalizer, RecurrentState,
    build_pipeline,
)


@pytest.fixture(scope="module")
def conn_cluster():
    import ray_tpu

    info = ray_tpu.init(num_cpus=8, num_tpus=0,
                        object_store_memory=256 * 1024 * 1024,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


# ------------------------------------------------------------------- units
def test_obs_normalizer_stats_and_clip():
    norm = ObsNormalizer(clip=2.0)
    rng = np.random.RandomState(0)
    data = rng.normal(5.0, 3.0, (200, 4)).astype(np.float32)
    for i in range(0, 200, 8):
        out = norm.env_to_module(data[i:i + 8])
    assert out.shape == (8, 4)
    # After 200 samples the normalized stream is ~zero-mean unit-var.
    normed = norm.peek(data)
    assert abs(float(normed.mean())) < 0.2
    assert abs(float(normed.std()) - 1.0) < 0.3
    assert float(np.max(np.abs(normed))) <= 2.0  # clip applied
    # peek must not advance the stats.
    before = norm.get_state()["count"]
    norm.peek(data)
    assert norm.get_state()["count"] == before
    # module_to_learner normalizes next_obs with the same stats.
    b = norm.module_to_learner({"next_obs": data[:10]})
    np.testing.assert_allclose(b["next_obs"], normed[:10], atol=1e-5)


def test_frame_stack_lanes_and_resets():
    fs = FrameStack(k=3)
    fs.reset(2)
    o1 = np.array([[1.0, 1.0], [10.0, 10.0]], np.float32)
    o2 = o1 * 2
    o3 = o1 * 3
    s1 = fs.env_to_module(o1, np.zeros(2, bool))
    np.testing.assert_allclose(s1[0], [0, 0, 0, 0, 1, 1])  # zero-padded
    s2 = fs.env_to_module(o2, np.zeros(2, bool))
    s3 = fs.env_to_module(o3, np.zeros(2, bool))
    np.testing.assert_allclose(s3[0], [1, 1, 2, 2, 3, 3])
    # Lane 1 resets: its stack clears, lane 0's survives.
    s4 = fs.env_to_module(o1 * 4, np.array([False, True]))
    np.testing.assert_allclose(s4[0], [2, 2, 3, 3, 4, 4])
    np.testing.assert_allclose(s4[1], [0, 0, 0, 0, 40, 40])
    # peek simulates the next stack without committing it.
    peeked = fs.peek(o1 * 5)
    np.testing.assert_allclose(peeked[0], [3, 3, 4, 4, 5, 5])
    np.testing.assert_allclose(fs._buf[0, -1], [4, 4])  # unchanged

    # module_to_learner: next stack = drop oldest + append successor.
    batch = {"obs": np.stack([s2, s3])[:, :1],          # [T=2, N=1, 6]
             "next_obs": np.stack([o3, o1 * 4])[:, :1]}  # [T=2, N=1, 2]
    out = fs.module_to_learner(batch)
    np.testing.assert_allclose(out["next_obs"][0, 0], [1, 1, 2, 2, 3, 3])
    np.testing.assert_allclose(out["next_obs"][1, 0], [2, 2, 3, 3, 4, 4])


def test_frame_stack_widens_observation_space():
    from ray_tpu.rllib.env.spaces import Box

    space = Box(low=np.full(4, -1.0, np.float32),
                high=np.full(4, 1.0, np.float32))
    wide = FrameStack(k=2).transform_observation_space(space)
    assert int(np.prod(wide.shape)) == 8


def test_frame_stack_bounds_match_data_layout():
    """Stacked obs are frame-major; bounds must tile whole frames so
    bound[i] brackets element [i] of the actual stacked vector."""
    from ray_tpu.rllib.env.spaces import Box

    space = Box(low=np.array([0.0, -5.0], np.float32),
                high=np.array([1.0, 5.0], np.float32))
    fs = FrameStack(k=2)
    wide = fs.transform_observation_space(space)
    np.testing.assert_array_equal(wide.low, [0.0, -5.0, 0.0, -5.0])
    np.testing.assert_array_equal(wide.high, [1.0, 5.0, 1.0, 5.0])
    fs.reset(1)
    stacked = fs.env_to_module(np.array([[0.5, -4.0]], np.float32))
    stacked = fs.env_to_module(np.array([[1.0, 4.0]], np.float32))
    assert np.all(stacked[0] >= wide.low - 1e-6)
    assert np.all(stacked[0] <= wide.high + 1e-6)


def test_recurrent_state_resets_and_trace():
    rs = RecurrentState(state_size=3)
    rs.reset(2)
    s0 = rs.state_for_step(2, None)
    assert (s0 == 0).all()
    rs.observe_state_out(np.ones((2, 3), np.float32))
    s1 = rs.state_for_step(2, np.array([False, True]))
    np.testing.assert_allclose(s1[0], [1, 1, 1])
    np.testing.assert_allclose(s1[1], [0, 0, 0])  # lane reset
    batch = rs.module_to_learner({"obs": np.zeros((2, 2, 1))})
    assert batch["state_in"].shape == (2, 2, 3)
    np.testing.assert_allclose(batch["state_in"][0], 0.0)


def test_pipeline_composition_and_state_roundtrip():
    pipe = build_pipeline([lambda: ObsNormalizer(clip=5.0),
                           lambda: FrameStack(2), ClipObs(-4, 4)])
    assert isinstance(pipe, ConnectorPipeline)
    pipe.reset(2)
    obs = np.array([[1.0, -1.0], [2.0, -2.0]], np.float32)
    out = pipe.env_to_module(obs, np.zeros(2, bool))
    assert out.shape == (2, 4)            # stacked by the middle stage
    state = pipe.get_state()
    pipe2 = build_pipeline([lambda: ObsNormalizer(clip=5.0),
                            lambda: FrameStack(2), ClipObs(-4, 4)])
    pipe2.set_state(state)
    np.testing.assert_allclose(pipe2.peek(obs), pipe.peek(obs))


# --------------------------------------------------------------------- e2e
def test_ppo_learns_through_three_stage_pipeline(conn_cluster):
    """PPO CartPole through ObsNormalizer -> FrameStack(2) -> ClipObs:
    the module's input is the WIDENED, normalized view, preprocessing is
    pipeline config (no runner edits), and learning still works."""
    from ray_tpu.rllib.algorithms.ppo import PPOConfig

    config = (
        PPOConfig()
        .environment("CartPole-v1")
        .training(lr=1e-3, train_batch_size=2048, num_epochs=10,
                  minibatch_size=256, gamma=0.99, gae_lambda=0.95,
                  entropy_coeff=0.01)
        .env_runners(num_env_runners=2, num_envs_per_runner=8,
                     connectors=[lambda: ObsNormalizer(clip=10.0),
                                 lambda: FrameStack(2),
                                 lambda: ClipObs(-10, 10)])
        .learners(num_learners=1, jax_platform="cpu")
    )
    algo = config.build()
    try:
        # The module was built over the stacked (2x4=8-dim) space.
        assert int(np.prod(
            algo.module_spec.observation_space.shape)) == 8
        best = 0.0
        for _ in range(30):
            result = algo.train()
            best = max(best, result.get("episode_return_mean", 0.0))
            if best >= 300:
                break
        assert best >= 300, f"pipeline PPO best return {best} < 300"
        # Runner-side pipeline state is observable (normalizer saw data).
        import ray_tpu

        st = ray_tpu.get(
            algo.env_runners[0].get_connector_state.remote(), timeout=60)
        assert st[0]["count"] > 1000
    finally:
        algo.stop()
