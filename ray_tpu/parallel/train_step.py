"""Sharded train-step builder: one pjit'd SPMD step function.

The whole distributed-training engine is here: loss+grad under jit with
param/batch shardings; GSPMD inserts the data-parallel psum, FSDP
all-gather/reduce-scatter, and TP allreduces over ICI. Buffer donation keeps
params/opt-state in place in HBM (no copy per step).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P


class TrainState(NamedTuple):
    params: Any
    opt_state: Any
    step: jax.Array


def create_train_state(params, optimizer) -> TrainState:
    return TrainState(params=params, opt_state=optimizer.init(params),
                      step=jnp.zeros((), jnp.int32))


def state_shardings(param_shardings, optimizer, params_shape, mesh,
                    weight_update: str = "replicated") -> TrainState:
    """Shardings for the full TrainState: opt-state mirrors params (moments
    inherit each param's sharding — automatic ZeRO partitioning of optimizer
    state when fsdp is on).

    ``weight_update="sharded"`` additionally folds the ``data`` axis into
    each moment's dim 0 where divisible (`parallel.zero`), so placement
    matches the sharded-update constraint inside the step and the donated
    buffers never reshard.

    The mapping is STRUCTURAL: any subtree of the optimizer state whose
    pytree structure (and leaf shapes) mirrors the param tree — e.g. Adam's
    mu/nu — takes the param shardings subtree wholesale; everything else
    (step counters, empty states) is replicated.  Keying by leaf shape would
    silently mis-shard two same-shaped params with different PartitionSpecs.
    """
    repl = NamedSharding(mesh, P())
    opt_shape = jax.eval_shape(lambda p: optimizer.init(p), params_shape)
    params_td = jax.tree.structure(params_shape)
    param_leaf_shapes = [leaf.shape for leaf in jax.tree.leaves(params_shape)]

    def mirrors_params(node) -> bool:
        try:
            if jax.tree.structure(node) != params_td:
                return False
            leaves = jax.tree.leaves(node)
        except Exception:
            return False
        return [getattr(l, "shape", None) for l in leaves] == param_leaf_shapes

    opt_sh = jax.tree.map(
        lambda node: param_shardings if mirrors_params(node) else repl,
        opt_shape,
        is_leaf=lambda n: mirrors_params(n) or jax.tree.structure(
            n).num_leaves <= 1)
    if weight_update == "sharded":
        from ray_tpu.parallel.zero import zero_moment_shardings

        param_specs = jax.tree.map(lambda s: s.spec, param_shardings)
        zsh = zero_moment_shardings(param_specs, optimizer, params_shape,
                                    mesh)
        opt_sh = jax.tree.map(
            lambda default, z: z if isinstance(z, NamedSharding)
            else default,
            opt_sh, zsh)
    return TrainState(params=param_shardings, opt_state=opt_sh, step=repl)


def build_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh,
    param_shardings,
    batch_shardings,
    grad_accum: int = 1,
    weight_update: str = "replicated",
    params_shape=None,
) -> Callable[[TrainState, Dict[str, jax.Array]], Tuple[TrainState, Dict]]:
    """Returns jitted (state, batch) -> (state, metrics).

    ``weight_update="sharded"`` turns on the ZeRO-style partitioned
    optimizer update (`parallel.zero` GSPMD route): sharding constraints
    over the ``data`` axis on the optimizer moments make XLA rewrite
    allreduce(grads)+full-update into reduce-scatter + 1/n-update +
    allgather.  Needs ``params_shape`` (a `jax.eval_shape` of the param
    tree) to size the moment shardings.

    Whether XLA *overlaps* that rewrite's collectives with the update
    math is up to its scheduler; for explicit chunked split-phase
    overlap (and int8/error-feedback gradient exchange) use
    `parallel.zero.build_zero_train_step(..., overlap=True)` on a pure
    data mesh instead."""
    if weight_update not in ("replicated", "sharded"):
        raise ValueError(
            f"weight_update must be 'replicated'|'sharded', got "
            f"{weight_update!r}")
    moment_sh = None
    if weight_update == "sharded":
        if params_shape is None:
            raise ValueError(
                "weight_update='sharded' needs params_shape "
                "(jax.eval_shape of the param tree)")
        from ray_tpu.parallel.zero import zero_moment_shardings

        param_specs = jax.tree.map(lambda s: s.spec, param_shardings)
        moment_sh = zero_moment_shardings(param_specs, optimizer,
                                          params_shape, mesh)

    def _loss_and_grads(params, batch):
        if grad_accum <= 1:
            return jax.value_and_grad(loss_fn)(params, batch)

        def micro(carry, mb):
            loss_acc, grad_acc = carry
            loss, grads = jax.value_and_grad(loss_fn)(params, mb)
            return (loss_acc + loss,
                    jax.tree.map(jnp.add, grad_acc, grads)), None

        micro_batches = jax.tree.map(
            lambda x: x.reshape(grad_accum, x.shape[0] // grad_accum,
                                *x.shape[1:]), batch)
        zeros = jax.tree.map(jnp.zeros_like, params)
        (loss_sum, grad_sum), _ = jax.lax.scan(
            micro, (jnp.zeros(()), zeros), micro_batches)
        inv = 1.0 / grad_accum
        return loss_sum * inv, jax.tree.map(lambda g: g * inv, grad_sum)

    def step_fn(state: TrainState, batch) -> Tuple[TrainState, Dict]:
        loss, grads = _loss_and_grads(state.params, batch)
        grad_norm = optax.global_norm(grads)
        with jax.named_scope("optimizer"):
            updates, new_opt = optimizer.update(grads, state.opt_state,
                                                state.params)
            if moment_sh is not None:
                from ray_tpu.parallel.zero import constrain_opt_state

                new_opt = constrain_opt_state(new_opt, moment_sh)
            new_params = optax.apply_updates(state.params, updates)
        new_state = TrainState(new_params, new_opt, state.step + 1)
        return new_state, {"loss": loss, "grad_norm": grad_norm,
                           "step": new_state.step}

    from ray_tpu.observability.jit import tracked_jit

    return tracked_jit(
        step_fn, name="train_step",
        in_shardings=(None, batch_shardings),
        donate_argnums=(0,),
    )


def build_eval_step(loss_fn, mesh, batch_shardings):
    def eval_fn(params, batch):
        return loss_fn(params, batch)

    from ray_tpu.observability.jit import tracked_jit

    return tracked_jit(eval_fn, name="eval_step",
                       in_shardings=(None, batch_shardings))
