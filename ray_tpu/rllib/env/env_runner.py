"""EnvRunner — rollout-collection actors.

Reference: `rllib/env/single_agent_env_runner.py` (vectorized gymnasium
envs + RLModule.forward_exploration) + `rllib/connectors/connector_v2.py`
(the env→module / module→learner pipelines the runner routes through).
Here the runner steps N env copies in lockstep with a batched CPU forward
(jax pinned to the host CPU device so a TPU-holding driver never contends
for the chip); preprocessing lives in the configured connector pipeline,
never hard-coded in the loop.

Two weight paths exist beyond the plain `set_weights` push:

- **Thin-client mode** (Sebulba, `execution="decoupled"`): constructed
  with an `inference_server`, the runner holds no current policy at
  all — `_forward` ships observations to the server's batched jitted
  forward and receives actions plus the weight version that produced
  them, which the runner stamps onto every rollout for downstream
  staleness accounting.
- **Versioned perturbations** (ES/ARS): constructed with a
  `weight_store`, `set_perturbed_weights` pulls the canonical theta
  for a published version from the channel (cached per version, so P
  perturbations cost one fetch) and regenerates its noise row locally
  from the integer seed.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional

import numpy as np

import ray_tpu
from ray_tpu.rllib.connectors import build_pipeline
from ray_tpu.rllib.core.rl_module import RLModuleSpec
from ray_tpu.rllib.env.cartpole import make_env


@ray_tpu.remote(num_cpus=1)
class EnvRunner:
    def __init__(self, env_spec, module_spec: RLModuleSpec,
                 num_envs: int = 1, seed: int = 0, connectors=None,
                 inference_server=None, weight_store=None):
        import jax

        # An env runner holds no TPU lease, so the raylet spawned this
        # worker with JAX_PLATFORMS=cpu: the query below can only
        # initialise the CPU backend, never open a chip.
        self._cpu = jax.devices("cpu")[0]
        self._server = inference_server
        self._weight_store = weight_store
        self._weight_version = 0
        self._theta_cache = None
        self._theta_version = -1
        self._envs = [make_env(env_spec, seed=seed * 10007 + i)
                      for i in range(num_envs)]
        from ray_tpu.observability.jit import tracked_jit

        with jax.default_device(self._cpu):
            self._module = module_spec.build()
            self._params = self._module.init(jax.random.key(seed))
            self._fwd = tracked_jit(self._module.forward_exploration,
                                    name="env_runner_fwd")
        self._rng = jax.random.key(seed + 1)
        self._obs = np.stack([e.reset(seed=seed * 31 + i)[0]
                              for i, e in enumerate(self._envs)])
        self._episode_returns = np.zeros(num_envs)
        self._completed: List[float] = []
        # env→module / module→learner pipeline (identity when None).
        self._pipeline = build_pipeline(connectors)
        if self._pipeline is not None:
            self._pipeline.reset(num_envs)
        self._recurrent = (self._pipeline.recurrent_stage
                           if self._pipeline is not None else None)
        if self._server is not None and self._recurrent is not None:
            raise ValueError(
                "thin-client mode cannot carry recurrent state through "
                "a shared inference server; use colocated execution "
                "for recurrent modules")
        # Lanes reset after the PREVIOUS step (carried across fragments
        # so stage state resets line up with episode boundaries).
        self._resets = np.zeros(num_envs, bool)
        self._infer = None          # lazily-jitted greedy inference
        self._seed = seed

    def set_weights(self, weights) -> bool:
        import jax

        with jax.default_device(self._cpu):
            self._params = jax.device_put(weights, self._cpu)
        return True

    def set_perturbed_weights(self, version: int, seed: int, sigma: float,
                              sign: float) -> bool:
        """ES/ARS fast path: install theta(version) + sign*sigma*eps(seed).

        The driver publishes the canonical theta ONCE per iteration
        into the versioned WeightStore channel; each runner fetches it
        once per VERSION (cached across the iteration's perturbations)
        and regenerates its noise row locally from the integer seed —
        so per perturbation only four scalars travel, instead of a
        full perturbed pytree 2*P times."""
        import jax
        from jax.flatten_util import ravel_pytree

        if self._weight_store is None:
            raise ValueError(
                "set_perturbed_weights needs the versioned weight "
                "channel; construct the runner with weight_store=...")
        if int(version) != self._theta_version:
            got, theta = self._weight_store.fetch(int(version))
            if theta is None:
                raise RuntimeError(
                    f"weight version {version} expired from the "
                    f"channel (latest {got})")
            self._theta_cache, self._theta_version = theta, int(version)
        with jax.default_device(self._cpu):
            flat, unravel = ravel_pytree(self._theta_cache)
            flat = np.asarray(flat, np.float32)
            eps = np.random.RandomState(seed).randn(
                flat.size).astype(np.float32)
            self._params = jax.device_put(
                unravel(flat + np.float32(sign * sigma) * eps), self._cpu)
        return True

    def get_connector_state(self) -> Optional[Dict[str, Any]]:
        """Pipeline state (normalizer stats, stack buffers) — for
        evaluation-side parity and checkpoint/restore."""
        return (None if self._pipeline is None
                else self._pipeline.get_state())

    def set_connector_state(self, state: Optional[Dict[str, Any]]) -> bool:
        """Adopt a training runner's pipeline state so evaluation sees the
        same normalization statistics (reference: eval workers share the
        training connectors' state)."""
        if self._pipeline is not None and state is not None:
            self._pipeline.set_state(state)
        return True

    def sample_episodes(self, num_episodes: int, explore: bool = False,
                        max_env_steps: int = 20_000) -> Dict[str, Any]:
        """Run complete fresh episodes and return their returns/lengths —
        the evaluation path (reference: `rllib/evaluation/worker_set.py`
        eval workers sample whole episodes, by default greedily).

        Greedy mode uses `forward_inference`; recurrent modules fall back
        to the exploration forward (their inference needs carried state,
        which the pipeline's recurrent stage manages on the sample path).
        """
        import jax

        n_envs = len(self._envs)
        recurrent = self._recurrent is not None and getattr(
            self._module, "is_recurrent", False)
        returns, lengths = [], []
        with jax.default_device(self._cpu):
            if self._infer is None and not recurrent:
                from ray_tpu.observability.jit import tracked_jit

                self._infer = tracked_jit(
                    self._module.forward_inference,
                    name="env_runner_infer")
            obs = np.stack([
                e.reset(seed=self._seed * 7919 + 1000 + i)[0]
                for i, e in enumerate(self._envs)])
            ep_ret = np.zeros(n_envs)
            ep_len = np.zeros(n_envs, np.int64)
            # Fresh-episode lanes: flush stack/recurrent state everywhere.
            resets = np.ones(n_envs, bool)
            steps = 0
            while len(returns) < num_episodes and steps < max_env_steps:
                if self._pipeline is None:
                    proc = obs.astype(np.float32)
                else:
                    proc = self._pipeline.env_to_module(
                        obs.astype(np.float32), resets)
                if explore or recurrent:
                    self._rng, key = jax.random.split(self._rng)
                    prev_resets, self._resets = self._resets, resets
                    out = self._forward(proc, key)
                    self._resets = prev_resets
                else:
                    out = self._infer(self._params, proc)
                actions = np.asarray(out["actions"])
                discrete = np.issubdtype(actions.dtype, np.integer)
                resets = np.zeros(n_envs, bool)
                for i, env in enumerate(self._envs):
                    act = int(actions[i]) if discrete else actions[i]
                    o, r, term, trunc, _ = env.step(act)
                    ep_ret[i] += r
                    ep_len[i] += 1
                    if term or trunc:
                        returns.append(float(ep_ret[i]))
                        lengths.append(int(ep_len[i]))
                        ep_ret[i] = 0.0
                        ep_len[i] = 0
                        o, _ = env.reset()
                        resets[i] = True
                    obs[i] = o
                steps += n_envs
            # Restore training lanes: next sample() starts from a reset.
            # pipeline.reset drains the recurrent stage's eval-time state
            # trace (else it would grow unboundedly across evaluations and
            # pollute the next training batch's state_in) and flushes
            # stack buffers; stateless stages (normalizer stats) keep
            # their statistics.
            if self._pipeline is not None:
                self._pipeline.reset(n_envs)
            # UNSEEDED resets: reseeding with the construction seeds would
            # restart training from the same few initial states after
            # every evaluation, biasing replay toward them.
            self._obs = np.stack([e.reset()[0] for e in self._envs])
            self._episode_returns[:] = 0.0
            self._resets = np.ones(n_envs, bool)
        return {"episode_returns": returns[:num_episodes],
                "episode_lengths": lengths[:num_episodes]}

    def _module_view(self, raw_obs: np.ndarray) -> np.ndarray:
        if self._pipeline is None:
            return raw_obs.astype(np.float32)
        return self._pipeline.env_to_module(
            raw_obs.astype(np.float32), self._resets)

    def _remote_forward(self, proc_obs: np.ndarray) -> Dict[str, Any]:
        """Thin-client step: one blocking round trip to the inference
        server, which coalesces concurrent runners into one batched
        jitted forward. The reply's weight_version is remembered and
        stamped onto the rollout."""
        server = self._server  # peer actor, not this runner (no self-wait)
        out = ray_tpu.get(server.infer.remote(proc_obs), timeout=300)
        self._weight_version = int(out.get("weight_version", 0))
        return out

    def _forward(self, proc_obs: np.ndarray, key):
        if self._server is not None:
            return self._remote_forward(proc_obs)
        if self._recurrent is not None and getattr(
                self._module, "is_recurrent", False):
            state_in = self._recurrent.state_for_step(
                proc_obs.shape[0], self._resets)
            out = self._fwd(self._params, proc_obs, key,
                            state_in=state_in)
            self._recurrent.observe_state_out(
                np.asarray(out["state_out"]))
            return out
        return self._fwd(self._params, proc_obs, key)

    def sample(self, num_steps: int) -> Dict[str, Any]:
        """Collect `num_steps * num_envs` transitions (fragments allowed:
        episodes are cut at the horizon and bootstrapped by the algorithm
        via the value head)."""
        import jax

        n_envs = len(self._envs)
        obs_buf, act_buf, logp_buf, rew_buf = [], [], [], []
        done_buf, term_buf, next_obs_buf, vf_buf = [], [], [], []

        with jax.default_device(self._cpu):
            for _ in range(num_steps):
                self._rng, key = jax.random.split(self._rng)
                proc_obs = self._module_view(self._obs)
                out = self._forward(proc_obs, key)
                actions = np.asarray(out["actions"])
                # Buffer the module's VIEW: the learner must train on
                # exactly what the policy saw at action time.
                obs_buf.append(proc_obs)
                act_buf.append(actions)
                logp_buf.append(np.asarray(out["logp"]))
                vf_buf.append(np.asarray(out["vf"]))

                rewards = np.zeros(n_envs, np.float32)
                dones = np.zeros(n_envs, bool)
                terms = np.zeros(n_envs, bool)
                next_obs = np.empty_like(self._obs)
                discrete = np.issubdtype(actions.dtype, np.integer)
                for i, env in enumerate(self._envs):
                    act = int(actions[i]) if discrete else actions[i]
                    obs, r, term, trunc, _ = env.step(act)
                    rewards[i] = r
                    self._episode_returns[i] += r
                    # The TRUE successor state, before any auto-reset —
                    # TD targets must bootstrap from this, never from the
                    # next episode's reset obs.
                    next_obs[i] = obs
                    terms[i] = term
                    if term or trunc:
                        dones[i] = True
                        self._completed.append(self._episode_returns[i])
                        self._episode_returns[i] = 0.0
                        obs, _ = env.reset()
                    self._obs[i] = obs
                self._resets = dones.copy()
                rew_buf.append(rewards)
                done_buf.append(dones)
                term_buf.append(terms)
                next_obs_buf.append(next_obs.copy())

            # Bootstrap value for the final observation of each env lane
            # — a PEEK through the pipeline (no stat/stack mutation).
            self._rng, key = jax.random.split(self._rng)
            last_proc = (self._obs.astype(np.float32)
                         if self._pipeline is None
                         else self._pipeline.peek(
                             self._obs.astype(np.float32)))
            if self._server is not None:
                last_out = self._remote_forward(last_proc)
            elif self._recurrent is not None and getattr(
                    self._module, "is_recurrent", False):
                # Current state, WITHOUT advancing the recorded trace.
                last_out = self._fwd(self._params, last_proc, key,
                                     state_in=self._recurrent._state)
            else:
                last_out = self._fwd(self._params, last_proc, key)
            last_vf = np.asarray(last_out["vf"])

        completed, self._completed = self._completed, []
        batch = {
            # [T, N, ...] time-major rollout fragments
            "obs": np.stack(obs_buf),
            "actions": np.stack(act_buf),
            "logp": np.stack(logp_buf),
            "rewards": np.stack(rew_buf),
            # dones = terminated | truncated (episode accounting / GAE
            # cuts); terminateds = env-true termination only (TD targets
            # bootstrap through time-limit truncations).
            "dones": np.stack(done_buf),
            "terminateds": np.stack(term_buf),
            "next_obs": np.stack(next_obs_buf),
            "vf": np.stack(vf_buf),
            "last_vf": last_vf,
            # Final observation per env lane (module view): lets value-
            # based algorithms (DQN) form next_obs for the last
            # transition of the fragment.
            "last_obs": np.asarray(last_proc),
            "episode_returns": completed,
        }
        if self._pipeline is not None:
            batch = self._pipeline.module_to_learner(batch)
        if self._server is not None:
            from ray_tpu.observability.rl import rl_metrics

            # Behavior version for downstream staleness accounting.
            batch["weight_version"] = int(self._weight_version)
            rl_metrics().env_steps.inc(num_steps * n_envs)
        return batch
