"""CQL — Conservative Q-Learning, offline RL for continuous control.

Reference: `rllib/algorithms/cql/cql.py:1` + `cql/cql_learner.py` (SAC
trained purely from a fixed dataset, with the CQL(H) conservative
regularizer pushing Q down on out-of-distribution actions and up on
dataset actions, so the squashed-Gaussian actor cannot exploit Q-value
extrapolation error). TPU-first shape reuses SAC's single-pytree state:
the whole update — twin-critic TD loss + CQL penalty over N sampled
actions + actor + alpha losses + polyak targets — is one jitted,
donated XLA call; the N-action Q evaluations batch as one big matmul
(B*3N rows through the critic) instead of a Python loop.

Offline ingestion streams from `ray_tpu.data` (parquet shards via
`offline.DatasetReader`) or an in-memory row list — closing the
JSONL-only gap.
"""

from __future__ import annotations

from typing import Any, Dict, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.rllib.algorithms.algorithm import AlgorithmConfig
from ray_tpu.rllib.algorithms.sac import SACLearner, SACModule
from ray_tpu.rllib.core.learner import Learner
from ray_tpu.rllib.core.learner_group import LearnerGroup
from ray_tpu.rllib.core.rl_module import RLModuleSpec
from ray_tpu.rllib.env.cartpole import make_env
from ray_tpu.train.config import ScalingConfig
from ray_tpu.train.jax_backend import JaxConfig


class CQLLearner(SACLearner):
    """SAC losses + the CQL(H) penalty on both critics."""

    def compute_loss_from_state(self, state, batch, rng):
        cfg = self.config
        n = cfg.get("cql_n_actions", 10)
        cql_alpha = cfg.get("cql_alpha", 5.0)
        m: SACModule = self.module
        params = state["params"]

        k_sac, k_rand, k_pi, k_pi_next = jax.random.split(rng, 4)
        sac_loss, metrics = super().compute_loss_from_state(
            state, batch, k_sac)

        obs, acts = batch["obs"], batch["actions"]
        B = obs.shape[0]
        act_dim = acts.shape[-1]
        scale = jnp.asarray(m._act_scale)

        def q_of(a_flat, obs_rep):
            q1, q2 = m.q_values(params, obs_rep, a_flat)
            return q1.reshape(B, n), q2.reshape(B, n)

        obs_rep = jnp.repeat(obs, n, axis=0)
        next_rep = jnp.repeat(batch["next_obs"], n, axis=0)

        # (a) uniform random actions; density 1/(2*scale)^d.
        a_rand = jax.random.uniform(
            k_rand, (B * n, act_dim), minval=-1.0, maxval=1.0) * scale
        logp_rand = -act_dim * jnp.log(2.0) - jnp.log(scale).sum()
        # (b) current-policy actions at s and s' with their log-probs
        # (importance-corrected logsumexp, the CQL(H) estimator).
        # sample_action's logp is the density BEFORE the `* act_scale`
        # stretch; subtract the Jacobian so all three families measure
        # the SCALED action (same measure as logp_rand).
        log_scale_jac = jnp.log(scale).sum()
        actor_sg = jax.lax.stop_gradient(params["actor"])
        a_pi, logp_pi = m.sample_action(actor_sg, obs_rep, k_pi)
        a_pin, logp_pin = m.sample_action(actor_sg, next_rep, k_pi_next)
        logp_pi = logp_pi - log_scale_jac
        logp_pin = logp_pin - log_scale_jac

        cat_q1, cat_q2 = [], []
        for a_flat, logp in ((a_rand, logp_rand), (a_pi, logp_pi),
                             (a_pin, logp_pin)):
            q1, q2 = q_of(a_flat, obs_rep)
            lp = (jnp.broadcast_to(logp, (B * n,)).reshape(B, n)
                  if jnp.ndim(logp) else jnp.full((B, n), logp))
            cat_q1.append(q1 - lp)
            cat_q2.append(q2 - lp)
        cat_q1 = jnp.concatenate(cat_q1, axis=1)
        cat_q2 = jnp.concatenate(cat_q2, axis=1)

        q1_data, q2_data = m.q_values(params, obs, acts)
        gap1 = jax.nn.logsumexp(cat_q1, axis=1) - q1_data
        gap2 = jax.nn.logsumexp(cat_q2, axis=1) - q2_data
        cql_loss = cql_alpha * (gap1.mean() + gap2.mean())

        metrics = dict(metrics)
        metrics["cql_loss"] = cql_loss
        metrics["cql_gap"] = (gap1.mean() + gap2.mean()) / 2.0
        return sac_loss + cql_loss, metrics


class ContinuousBCLearner(Learner):
    """MSE behavior cloning over the SAC actor — the offline baseline
    CQL is measured against (discrete BC lives in `bc.py`)."""

    def compute_loss(self, params, batch, rng):
        m: SACModule = self.module
        mean, _ = m._actor.apply(params["actor"], batch["obs"])
        pred = jnp.tanh(mean) * jnp.asarray(m._act_scale)
        loss = ((pred - batch["actions"]) ** 2).mean()
        return loss, {"bc_mse": loss}


class CQLConfig(AlgorithmConfig):
    def __init__(self):
        super().__init__()
        self.env = "Pendulum-v1"
        self.lr = 3e-4
        self.grad_clip = 10.0
        self.tau = 0.005
        self.train_batch_size = 256
        self.num_batches_per_iteration = 64
        self.cql_alpha = 5.0
        self.cql_n_actions = 10
        self.target_entropy = None
        self.dataset = None   # ray_tpu.data.Dataset | path | list of rows

    def offline_data(self, dataset) -> "CQLConfig":
        self.dataset = dataset
        return self

    algo_class = property(lambda self: CQL)


class CQL:
    """Offline algorithm: no env runners; `train()` consumes the
    configured dataset (parquet path, Data pipeline, or rows)."""

    learner_class = CQLLearner

    def __init__(self, config: CQLConfig):
        if config.dataset is None:
            raise ValueError("CQLConfig.offline_data(dataset) is required")
        if isinstance(config.dataset, str):
            from ray_tpu.rllib.offline.io import DatasetReader

            config.dataset = DatasetReader(config.dataset).dataset
        probe_env = make_env(config.env)
        self.config = config
        self.module_spec = RLModuleSpec(
            observation_space=probe_env.observation_space,
            action_space=probe_env.action_space,
            hidden=config.module_hidden,
            module_class=SACModule)
        self.learner_group = LearnerGroup(
            self.learner_class, self.module_spec,
            learner_config=self._learner_config(),
            scaling_config=ScalingConfig(num_workers=config.num_learners),
            jax_config=JaxConfig(platform=config.jax_platform))
        self._iteration = 0
        self._batch_iter: Optional[Iterator] = None

    def _learner_config(self) -> Dict[str, Any]:
        cfg = self.config
        act_dim = int(np.prod(self.module_spec.action_space.shape))
        return {"lr": cfg.lr, "grad_clip": cfg.grad_clip,
                "seed": cfg.seed, "gamma": cfg.gamma, "tau": cfg.tau,
                "cql_alpha": cfg.cql_alpha,
                "cql_n_actions": cfg.cql_n_actions,
                "target_entropy": (cfg.target_entropy
                                   if cfg.target_entropy is not None
                                   else -float(act_dim))}

    # ------------------------------------------------------------ ingestion
    _batch_columns = (("obs", np.float32), ("actions", np.float32),
                      ("rewards", np.float32), ("next_obs", np.float32),
                      ("terminateds", np.float32))

    def _batches(self) -> Iterator[Dict[str, np.ndarray]]:
        ds = self.config.dataset
        bs = self.config.train_batch_size
        cols = self._batch_columns

        def clean(batch):
            out = {}
            for k, dt in cols:
                if k not in batch:
                    raise ValueError(f"CQL needs a '{k}' column "
                                     f"(got {sorted(batch)})")
                v = batch[k]
                if getattr(v, "dtype", None) == object:
                    v = np.stack([np.asarray(x, dt) for x in v])
                out[k] = np.asarray(v, dt)
            # SAC's TD target keys.
            out["dones"] = out.pop("terminateds")
            if out["actions"].ndim == 1:
                out["actions"] = out["actions"][:, None]
            return out

        if hasattr(ds, "iter_batches"):       # ray_tpu.data.Dataset
            epoch = 0
            while True:
                # Local shuffle: without it, parquet-backed training
                # would see temporally-correlated consecutive
                # transitions each epoch while the rows path samples
                # i.i.d. — results must not differ by ingestion format.
                for batch in ds.iter_batches(
                        batch_size=bs, batch_format="numpy",
                        drop_last=True,
                        local_shuffle_buffer_size=max(4 * bs, 1024),
                        local_shuffle_seed=self.config.seed + epoch):
                    yield clean(batch)
                epoch += 1
        else:
            rows = list(ds)
            arrays = {k: [r[k] for r in rows] for k, _ in cols}
            rng = np.random.RandomState(self.config.seed)
            while True:
                idx = rng.randint(0, len(rows), bs)
                yield clean({k: np.asarray(v, object)[idx]
                             if isinstance(v[0], (list, np.ndarray))
                             else np.asarray(v)[idx]
                             for k, v in arrays.items()})

    # ------------------------------------------------------------ training
    def train(self) -> Dict[str, Any]:
        self._iteration += 1
        if self._batch_iter is None:
            self._batch_iter = self._batches()
        metrics: Dict[str, Any] = {}
        for _ in range(self.config.num_batches_per_iteration):
            metrics.update(self.learner_group.update(
                next(self._batch_iter)))
        metrics["training_iteration"] = self._iteration
        return metrics

    def get_policy_params(self):
        return self.learner_group.get_weights()

    def evaluate(self, num_episodes: int = 10) -> Dict[str, float]:
        """Deterministic (tanh-mean) rollouts in the probe env."""
        module = self.module_spec.build()
        params = self.get_policy_params()
        from ray_tpu.observability.jit import tracked_jit

        fwd = tracked_jit(module.forward_train, name="cql_eval_fwd")
        returns = []
        env = make_env(self.config.env, seed=self.config.seed + 999)
        for ep in range(num_episodes):
            obs, _ = env.reset(seed=self.config.seed + ep)
            total, done = 0.0, False
            while not done:
                out = fwd(params, obs[None].astype(np.float32))
                act = np.asarray(out["actions"])[0]
                obs, r, term, trunc, _ = env.step(act)
                total += r
                done = term or trunc
            returns.append(total)
        return {"episode_return_mean": float(np.mean(returns)),
                "num_episodes": num_episodes}


class ContinuousBC(CQL):
    """beta-0 baseline: pure MSE cloning on the same offline pipeline
    (reference: BC over `MARWILConfig(beta=0)`)."""

    learner_class = ContinuousBCLearner

    def _learner_config(self) -> Dict[str, Any]:
        return {"lr": self.config.lr, "grad_clip": self.config.grad_clip,
                "seed": self.config.seed}
