"""MultiAgentEnvRunner — rollout collection over agent-keyed envs.

Reference: `rllib/env/multi_agent_env_runner.py` (episodes as per-agent
streams routed through policy_mapping_fn).  TPU-first shape: instead of
ragged per-episode lists, every (env, agent) pair is a fixed LANE and the
fragment is a rectangular time-major [T, L, ...] block per module with an
explicit `mask` row — inactive lanes still flow through the batched
forward (zero obs) so shapes are static and each module's exploration
pass compiles exactly once.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, List, Optional

import numpy as np

import ray_tpu
from ray_tpu.rllib.core.multi_rl_module import (MultiRLModuleSpec,
                                                default_policy_mapping_fn)
from ray_tpu.rllib.env.cartpole import make_env


@ray_tpu.remote(num_cpus=1)
class MultiAgentEnvRunner:
    def __init__(self, env_spec, multi_module_spec: MultiRLModuleSpec,
                 policy_mapping_fn: Optional[Callable[[str], str]] = None,
                 num_envs: int = 1, seed: int = 0):
        import jax

        # An env runner holds no TPU lease, so the raylet spawned this
        # worker with JAX_PLATFORMS=cpu: the query below can only
        # initialise the CPU backend, never open a chip.
        self._cpu = jax.devices("cpu")[0]
        self._mapping = policy_mapping_fn or default_policy_mapping_fn
        self._envs = [make_env(env_spec, seed=seed * 10007 + i)
                      for i in range(num_envs)]
        agents = self._envs[0].possible_agents
        self._agents = list(agents)

        # Lane layout: per module, the ordered list of (env_idx, agent_id).
        self._lanes: Dict[str, List] = {}
        for ei in range(num_envs):
            for a in agents:
                self._lanes.setdefault(self._mapping(a), []).append((ei, a))
        self._module_ids = sorted(self._lanes)
        # env index -> [(module_id, lane_idx, agent_id)] so per-env work
        # touches only that env's lanes, not every lane of every module.
        self._env_lanes: List[List] = [[] for _ in range(num_envs)]
        for mid, lanes in self._lanes.items():
            for li, (ei, a) in enumerate(lanes):
                self._env_lanes[ei].append((mid, li, a))

        from ray_tpu.observability.jit import tracked_jit

        with jax.default_device(self._cpu):
            self._module = multi_module_spec.build()
            self._params = self._module.init(jax.random.key(seed))
            self._fwd = {mid: tracked_jit(
                self._module[mid].forward_exploration,
                name=f"ma_env_runner_fwd_{mid}")
                for mid in self._module_ids}
        self._rng = jax.random.key(seed + 1)

        # Current per-lane obs (zeros while inactive) and active flags.
        self._obs: Dict[str, np.ndarray] = {}
        self._active: Dict[str, np.ndarray] = {}
        for mid, lanes in self._lanes.items():
            dim = int(np.prod(
                self._envs[0].get_observation_space(lanes[0][1]).shape))
            self._obs[mid] = np.zeros((len(lanes), dim), np.float32)
            self._active[mid] = np.zeros(len(lanes), bool)

        self._env_return = np.zeros(num_envs, np.float32)
        self._agent_return = [dict.fromkeys(agents, 0.0)
                              for _ in range(num_envs)]
        # Agents whose episode already ended (distinct from "not acting
        # this turn" — both look inactive to the lane mask).
        self._finalized = [set() for _ in range(num_envs)]
        self._completed: List[float] = []
        self._agent_completed: Dict[str, List[float]] = {a: [] for a in agents}
        for ei, env in enumerate(self._envs):
            obs, _ = env.reset(seed=seed * 31 + ei)
            self._ingest_obs(ei, obs)

    # ------------------------------------------------------------ lane utils
    def _ingest_obs(self, env_idx: int, obs: Dict[str, np.ndarray]) -> None:
        for mid, li, a in self._env_lanes[env_idx]:
            if a in obs:
                self._obs[mid][li] = np.asarray(obs[a], np.float32).ravel()
                self._active[mid][li] = True
            else:
                self._active[mid][li] = False

    def set_weights(self, weights) -> bool:
        import jax

        with jax.default_device(self._cpu):
            self._params = jax.device_put(weights, self._cpu)
        return True

    # ---------------------------------------------------------------- sample
    def sample(self, num_steps: int) -> Dict[str, Any]:
        import jax

        bufs = {mid: {k: [] for k in ("obs", "actions", "logp", "vf",
                                      "rewards", "dones", "terminateds",
                                      "mask")}
                for mid in self._module_ids}
        # Step index of each lane's most recent recorded (mask=1) row in
        # THIS fragment — turn-based envs may deliver a reward or a
        # termination to an agent on a step it didn't act; both are
        # retro-credited to that row (cross-fragment arrivals only reach
        # the episode-return metrics, not training).
        last_rec = {mid: np.full(len(self._lanes[mid]), -1, np.int64)
                    for mid in self._module_ids}

        with jax.default_device(self._cpu):
            for step_t in range(num_steps):
                # One fixed-shape batched forward per module.
                step_out = {}
                for mid in self._module_ids:
                    self._rng, key = jax.random.split(self._rng)
                    out = self._fwd[mid](self._params[mid],
                                         self._obs[mid], key)
                    step_out[mid] = {k: np.asarray(v)
                                     for k, v in out.items()}

                # Assemble per-env action dicts from active lanes.
                act_dicts = [dict() for _ in self._envs]
                for mid, lanes in self._lanes.items():
                    acts = step_out[mid]["actions"]
                    discrete = np.issubdtype(acts.dtype, np.integer)
                    for li, (ei, a) in enumerate(lanes):
                        if self._active[mid][li]:
                            act_dicts[ei][a] = (int(acts[li]) if discrete
                                                else acts[li])

                # Record pre-step state.
                pre_active = {mid: self._active[mid].copy()
                              for mid in self._module_ids}
                for mid in self._module_ids:
                    b = bufs[mid]
                    b["obs"].append(self._obs[mid].copy())
                    b["actions"].append(step_out[mid]["actions"])
                    b["logp"].append(step_out[mid]["logp"])
                    b["vf"].append(step_out[mid]["vf"])
                    b["mask"].append(pre_active[mid].astype(np.float32))

                # Step the envs.
                rew = {mid: np.zeros(len(self._lanes[mid]), np.float32)
                       for mid in self._module_ids}
                done = {mid: np.zeros(len(self._lanes[mid]), bool)
                        for mid in self._module_ids}
                term = {mid: np.zeros(len(self._lanes[mid]), bool)
                        for mid in self._module_ids}
                for ei, env in enumerate(self._envs):
                    if not act_dicts[ei]:
                        continue
                    obs, rews, terms, truncs, _ = env.step(act_dicts[ei])
                    env_done = terms.get("__all__", False) or \
                        truncs.get("__all__", False)
                    # Fallback: an env that marks every agent done per-key
                    # without "__all__" must still end the episode, or all
                    # lanes go inactive and the env never resets.
                    if not env_done:
                        env_done = all(
                            a in self._finalized[ei]
                            or terms.get(a, False) or truncs.get(a, False)
                            for _m, _l, a in self._env_lanes[ei])
                    for mid, li, a in self._env_lanes[ei]:
                        if a in self._finalized[ei]:
                            continue
                        r = float(rews.get(a, 0.0))
                        a_done = (terms.get(a, False)
                                  or truncs.get(a, False) or env_done)
                        if pre_active[mid][li]:
                            rew[mid][li] = r
                            done[mid][li] = a_done
                            term[mid][li] = terms.get(a, False)
                            last_rec[mid][li] = step_t
                        elif a in rews or a_done:
                            # Turn-based arrival on a non-acting step:
                            # retro-credit the lane's last acted row.
                            lr = last_rec[mid][li]
                            if lr >= 0:
                                b = bufs[mid]
                                b["rewards"][lr][li] += r
                                if a_done:
                                    b["dones"][lr][li] = True
                                    b["terminateds"][lr][li] |= \
                                        terms.get(a, False)
                        else:
                            continue
                        self._env_return[ei] += r
                        self._agent_return[ei][a] += r
                        if a_done:
                            self._finalized[ei].add(a)
                            self._agent_completed[a].append(
                                self._agent_return[ei][a])
                            self._agent_return[ei][a] = 0.0
                    self._ingest_obs(ei, obs)
                    if env_done:
                        self._completed.append(float(self._env_return[ei]))
                        self._env_return[ei] = 0.0
                        self._finalized[ei].clear()
                        # Retro-credit must never cross an episode
                        # boundary: next episode's arrivals can't land on
                        # this episode's rows.
                        for mid, li, _a in self._env_lanes[ei]:
                            last_rec[mid][li] = -1
                        obs, _ = env.reset()
                        self._ingest_obs(ei, obs)

                for mid in self._module_ids:
                    b = bufs[mid]
                    b["rewards"].append(rew[mid])
                    b["dones"].append(done[mid])
                    b["terminateds"].append(term[mid])

            # Bootstrap value of each lane's current obs.
            last_vf = {}
            for mid in self._module_ids:
                self._rng, key = jax.random.split(self._rng)
                out = self._fwd[mid](self._params[mid], self._obs[mid], key)
                last_vf[mid] = np.asarray(out["vf"])

        completed, self._completed = self._completed, []
        agent_completed = {a: v for a, v in self._agent_completed.items()}
        self._agent_completed = {a: [] for a in self._agents}
        return {
            "modules": {
                mid: {**{k: np.stack(v) for k, v in bufs[mid].items()},
                      "last_vf": last_vf[mid]}
                for mid in self._module_ids
            },
            "episode_returns": completed,
            "agent_episode_returns": agent_completed,
        }
