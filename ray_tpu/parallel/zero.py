"""ZeRO-style cross-replica sharding of the weight update.

The source-paper lever ("Automatic Cross-Replica Sharding of Weight Update
in Data-Parallel Training"): in plain data parallelism every replica
allreduces full gradients and then runs an *identical* optimizer update on
an *identical* full copy of the optimizer state — O(model) redundant work
and memory per replica.  Sharding the update converts

    allreduce(grads) ; full Adam          (per replica)
  → reduce-scatter(grads) ; Adam on 1/n   (per replica)
  → allgather(params)

with the same wire bytes as the allreduce (ring RS + ring AG = ring AR)
but 1/n the optimizer FLOPs and 1/n the moment memory per replica.

Two composable routes live here:

1. :func:`build_zero_train_step` — the explicit route.  A `shard_map` step
   over the ``data`` axis where the reduce-scatter / allgather are *our*
   Pallas ring kernels (`ray_tpu.util.collective.pallas`), with the lax
   fallback off-TPU and an optional EQuARX int8 path for the gradient
   exchange.  On a 2-way ring every element is produced by one float add
   in commuted-operand order, so this path is *bitwise* comparable to a
   replicated optax update (tests do exactly that).

2. :func:`zero_state_shardings` + :func:`constrain_opt_state` — the GSPMD
   route, matching the paper's XLA pass.  Composes with the existing pjit
   `build_train_step`: moments get a sharding constraint over the data
   axis, and XLA itself rewrites allreduce+update into
   reduce-scatter + sharded-update + allgather.  Enabled via
   ``build_train_step(..., weight_update="sharded")``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax import shard_map
from jax.flatten_util import ravel_pytree
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.parallel.mesh import mesh_axis_size
from ray_tpu.util.collective.pallas import (
    local_quantization_residual, quantized_ring_allreduce, ring_allgather,
    ring_reduce_scatter, start_quantized_ring_reduce_scatter,
    start_ring_allgather, start_ring_reduce_scatter,
    wait_quantized_ring_reduce_scatter, wait_ring_allgather,
    wait_ring_reduce_scatter,
)
from ray_tpu.util.collective.pallas.ring import LANES


class ZeroTrainState(NamedTuple):
    """Replicated params + *sharded* flat optimizer state.

    ``opt_state`` is the optax state over this replica's 1/n shard of the
    flattened parameter vector (moments are (shard_len,) per device).
    ``ef`` is the optional error-feedback accumulator for compressed
    gradient exchange: per-device f32 residual of the last quantization,
    global shape ``(n, padded)`` sharded over the data axis (row i is
    device i's buffer), or None when compression runs without feedback.
    """
    params: Any
    opt_state: Any
    step: jax.Array
    ef: Any = None


def _padded_len(size: int, n: int) -> int:
    group = n * LANES
    return ((size + group - 1) // group) * group


def _flat_shard_len(params, n: int) -> int:
    size = sum(int(jnp.size(l)) for l in jax.tree.leaves(params))
    return _padded_len(size, n) // n


def _pad_flat(flat, n: int):
    padded = _padded_len(flat.size, n)
    if padded != flat.size:
        flat = jnp.pad(flat, (0, padded - flat.size))
    return flat


def _my_shard(flat_padded, n: int, axis_name: str):
    shard = flat_padded.size // n
    my = lax.axis_index(axis_name)
    return lax.dynamic_slice(flat_padded, (my * shard,), (shard,))


def create_zero_state(params, optimizer, mesh, axis_name: str = "data",
                      error_feedback: bool = False) -> ZeroTrainState:
    """Initialize a ZeRO state: params replicated, moments sharded.

    Runs a tiny shard_map so each device initializes the optax state for
    *its* shard only (1/n moment memory from step zero, the whole point).
    With ``error_feedback`` the state also carries a zeroed per-device f32
    residual buffer for compressed-gradient error feedback (always float:
    an int EF buffer would re-quantize the correction itself).
    """
    n = mesh_axis_size(mesh, axis_name)
    shard = _flat_shard_len(params, n)

    def init_shard(flat_padded):
        return optimizer.init(_my_shard(flat_padded, n, axis_name))

    flat, _ = ravel_pytree(params)
    flat = _pad_flat(flat, n)
    opt_shape = jax.eval_shape(lambda f: optimizer.init(f),
                               jax.ShapeDtypeStruct((shard,), flat.dtype))
    out_specs = jax.tree.map(
        lambda l: P(axis_name) if getattr(l, "shape", ()) == (shard,)
        else P(),
        opt_shape)
    from ray_tpu.observability.jit import tracked_jit

    opt_state = tracked_jit(shard_map(
        init_shard, mesh=mesh, in_specs=P(),
        out_specs=out_specs, check_vma=False),
        name="zero_init_shard")(flat)
    ef = None
    if error_feedback:
        ef = jax.device_put(
            jnp.zeros((n, shard * n), jnp.float32),
            NamedSharding(mesh, P(axis_name, None)))
    return ZeroTrainState(params=params, opt_state=opt_state,
                          step=jnp.zeros((), jnp.int32), ef=ef)


def build_zero_train_step(
    loss_fn: Callable[[Any, Dict[str, jax.Array]], jax.Array],
    optimizer: optax.GradientTransformation,
    mesh,
    axis_name: str = "data",
    batch_spec: Optional[P] = None,
    collective: str = "auto",
    quantized_grads: bool = False,
    overlap: bool = False,
    n_chunks: int = 4,
    error_feedback: bool = False,
) -> Callable[[ZeroTrainState, Any], Tuple[ZeroTrainState, Dict]]:
    """Jitted DP step with a partitioned weight update over `axis_name`.

    Per device: local grads → ring reduce-scatter (sum) → optax update on
    this replica's flat shard → ring allgather of updated params.  With
    ``quantized_grads`` the gradient exchange rides the int8 EQuARX ring;
    the weight allgather stays exact.

    ``overlap=True`` replaces the monolithic exchange with a chunked
    split-phase schedule: the flat vector is cut into ``n_chunks`` chunks
    (boundaries on n*LANES multiples) and pipelined so chunk i+1's
    reduce-scatter hops and chunk i-1's param allgather hops run while
    chunk i's optimizer math executes — communication hides under compute
    instead of serializing with it.  Numerics match the monolithic step to
    float tolerance (per-chunk ring order differs, so not bitwise), and
    the optimizer-state vector uses a chunk-major element order: do not
    toggle ``overlap`` mid-run on the same state.  Requires an elementwise
    optimizer (adam/sgd/etc) since moment vectors are updated per chunk.

    ``error_feedback=True`` (requires ``quantized_grads`` and a state from
    ``create_zero_state(..., error_feedback=True)``) accumulates the local
    quantization residual and re-injects it into the next step's gradient,
    so compressed exchange stops biasing long runs.
    """
    n = mesh_axis_size(mesh, axis_name)
    if batch_spec is None:
        batch_spec = P(axis_name)
    if error_feedback and not quantized_grads:
        raise ValueError(
            "error_feedback corrects compression error and needs "
            "quantized_grads=True (the exact exchange has no residual)")
    if n_chunks < 1:
        raise ValueError(f"n_chunks must be >= 1, got {n_chunks}")

    def _start_rs(vec):
        c2d = vec.reshape(-1, LANES)
        if quantized_grads:
            return start_quantized_ring_reduce_scatter(
                c2d, axis_name, n=n, impl=collective)
        return start_ring_reduce_scatter(
            c2d, axis_name, n=n, op="sum", impl=collective)

    def _wait_rs(handle):
        if quantized_grads:
            return wait_quantized_ring_reduce_scatter(handle).reshape(-1)
        return wait_ring_reduce_scatter(handle).reshape(-1)

    def _overlap_update(carry, pflat_p, opt_state):
        """The pipelined schedule.  Chunk boundaries sit on n*LANES
        multiples so every chunk reduce-scatters to equal per-device
        slices and the concatenated shards exactly tile the padded
        vector."""
        my = lax.axis_index(axis_name)
        groups = pflat_p.size // (n * LANES)
        n_c = max(1, min(n_chunks, groups))
        base, rem = divmod(groups, n_c)
        sizes = [(base + (1 if i < rem else 0)) * n * LANES
                 for i in range(n_c)]
        offs = [sum(sizes[:i]) for i in range(n_c)]

        leaves, treedef = jax.tree.flatten(opt_state)
        is_vec = [getattr(l, "ndim", 0) == 1 for l in leaves]

        handles = [None] * n_c
        handles[0] = _start_rs(carry[offs[0]:offs[0] + sizes[0]])
        ag_handles = []
        new_chunk_leaves = []
        ef_chunks = []
        opt_off = 0
        for c in range(n_c):
            cs = sizes[c] // n
            if c + 1 < n_c:
                # Issue the next chunk's reduce-scatter before consuming
                # this one: its hops hide under this chunk's update math.
                handles[c + 1] = _start_rs(
                    carry[offs[c + 1]:offs[c + 1] + sizes[c + 1]])
            gshard_c = _wait_rs(handles[c])
            pshard_c = lax.dynamic_slice(
                pflat_p, (offs[c] + my * cs,), (cs,))
            opt_c = jax.tree.unflatten(treedef, [
                l[opt_off:opt_off + cs] if isv else l
                for l, isv in zip(leaves, is_vec)])
            updates_c, new_opt_c = optimizer.update(
                gshard_c, opt_c, pshard_c)
            new_pshard_c = optax.apply_updates(pshard_c, updates_c)
            new_chunk_leaves.append(jax.tree.leaves(new_opt_c))
            # The updated shard leaves immediately: its allgather hops
            # hide under the next chunk's wait + optimizer math.
            ag_handles.append(start_ring_allgather(
                new_pshard_c, axis_name, n=n, impl=collective))
            if error_feedback:
                ef_chunks.append(local_quantization_residual(
                    carry[offs[c]:offs[c] + sizes[c]].reshape(-1, LANES),
                    n).reshape(-1))
            opt_off += cs
        # Scalar leaves (e.g. adam's count) increment identically in every
        # chunk update; keep chunk 0's copy.  Vector leaves concatenate in
        # chunk-major order — the overlap state layout.
        merged = [
            jnp.concatenate([new_chunk_leaves[c][i] for c in range(n_c)])
            if is_vec[i] else new_chunk_leaves[0][i]
            for i in range(len(leaves))]
        new_opt = jax.tree.unflatten(treedef, merged)
        gathered = [wait_ring_allgather(h).reshape(-1)
                    for h in ag_handles]
        new_flat_p = jnp.concatenate(gathered)
        new_ef = (jnp.concatenate(ef_chunks)[None, :]
                  if error_feedback else None)
        return new_flat_p, new_opt, new_ef

    def step_fn(state: ZeroTrainState, batch):
        params, opt_state, step, ef = state
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        gflat, _ = ravel_pytree(grads)
        pflat, unravel = ravel_pytree(params)
        gflat = _pad_flat(gflat, n)
        pflat_p = _pad_flat(pflat, n)
        # Error feedback: re-inject the residual the wire dropped last
        # step, then remember what this step's compression will drop.
        carry = gflat + ef[0] if error_feedback else gflat

        if overlap:
            new_flat_p, new_opt, new_ef = _overlap_update(
                carry, pflat_p, opt_state)
        else:
            c2d = carry.reshape(-1, LANES)
            if quantized_grads:
                gfull = quantized_ring_allreduce(
                    c2d, axis_name, n=n, impl=collective).reshape(-1)
                gshard = _my_shard(gfull, n, axis_name)
            else:
                gshard = ring_reduce_scatter(
                    c2d, axis_name, n=n, op="sum",
                    impl=collective).reshape(-1)
            pshard = _my_shard(pflat_p, n, axis_name)
            updates, new_opt = optimizer.update(gshard, opt_state, pshard)
            new_pshard = optax.apply_updates(pshard, updates)
            gathered = ring_allgather(
                new_pshard.reshape(-1, LANES), axis_name, n=n,
                impl=collective)
            new_flat_p = gathered.reshape(-1)
            new_ef = (local_quantization_residual(c2d, n)
                      .reshape(-1)[None, :] if error_feedback else None)

        if not error_feedback:
            new_ef = ef  # pass any existing buffer through untouched
        new_params = unravel(new_flat_p[:pflat.size])
        grad_norm = jnp.sqrt(lax.psum(jnp.sum(gflat * gflat), axis_name))
        metrics = {"loss": lax.pmean(loss, axis_name),
                   "grad_norm": grad_norm, "step": step + 1}
        return ZeroTrainState(new_params, new_opt, step + 1,
                              new_ef), metrics

    jitted_cache: Dict[Any, Callable] = {}

    def wrapped(state: ZeroTrainState, batch):
        if error_feedback and state.ef is None:
            raise ValueError(
                "error_feedback=True needs a state carrying an ef buffer;"
                " build it with create_zero_state(..., "
                "error_feedback=True)")
        cache_key = (jax.tree.structure(state), jax.tree.structure(batch))
        fn = jitted_cache.get(cache_key)
        if fn is None:
            opt_specs = jax.tree.map(
                lambda l: P(axis_name) if getattr(l, "ndim", 0) == 1
                else P(),
                state.opt_state)
            state_specs = ZeroTrainState(
                params=jax.tree.map(lambda _: P(), state.params),
                opt_state=opt_specs,
                step=P(),
                ef=None if state.ef is None else P(axis_name, None))
            metric_specs = {"loss": P(), "grad_norm": P(), "step": P()}
            batch_specs = jax.tree.map(lambda _: batch_spec, batch)
            from ray_tpu.observability.jit import tracked_jit

            fn = tracked_jit(shard_map(
                step_fn, mesh=mesh,
                in_specs=(state_specs, batch_specs),
                out_specs=(state_specs, metric_specs),
                check_vma=False), name="zero_train_step",
                donate_argnums=(0,))
            jitted_cache[cache_key] = fn
        return fn(state, batch)

    return wrapped


# ---------------------------------------------------------------------------
# GSPMD route: sharding constraints that make XLA perform the same
# rewrite inside the existing pjit train step (the paper's compiler pass,
# expressed as annotations).
# ---------------------------------------------------------------------------

def _shard_leading(spec: P, axis: str, dim0: int, axis_size: int
                   ) -> Optional[P]:
    """Prepend `axis` onto dim 0 of `spec` when legal (dim divisible,
    dim 0 not already sharded)."""
    entries = tuple(spec) if len(tuple(spec)) else (None,)
    if dim0 % axis_size or entries[0] is not None:
        return None
    return P(axis, *entries[1:])


def zero_moment_shardings(param_specs, optimizer, params_shape, mesh,
                          axis_name: str = "data"):
    """Shardings for optimizer moments with the data axis folded in:
    each moment leaf whose param spec leaves dim 0 unsharded (and whose
    dim 0 divides the data-axis size) is additionally sharded over
    `axis_name` — the ZeRO partitioning of optimizer state.

    Returns the opt-state-shaped tree of `NamedSharding | "keep"` ("keep"
    = leave as the mirror-of-params default; a string sentinel because
    None is an empty subtree to pytrees and would break alignment)."""
    axis_size = mesh_axis_size(mesh, axis_name)
    opt_shape = jax.eval_shape(lambda p: optimizer.init(p), params_shape)
    params_td = jax.tree.structure(params_shape)
    param_leaf_shapes = [l.shape for l in jax.tree.leaves(params_shape)]
    spec_leaves = jax.tree.leaves(param_specs,
                                  is_leaf=lambda x: isinstance(x, P))

    def mirrors_params(node) -> bool:
        try:
            if jax.tree.structure(node) != params_td:
                return False
            leaves = jax.tree.leaves(node)
        except Exception:
            return False
        return [getattr(l, "shape", None) for l in leaves] \
            == param_leaf_shapes

    def shard_mirror(node):
        leaves, td = jax.tree.flatten(node)
        out = []
        for leaf, spec in zip(leaves, spec_leaves):
            zspec = _shard_leading(spec, axis_name, leaf.shape[0],
                                   axis_size) if leaf.ndim else None
            out.append(NamedSharding(mesh, zspec) if zspec else "keep")
        return jax.tree.unflatten(td, out)

    return jax.tree.map(
        lambda node: shard_mirror(node) if mirrors_params(node)
        else jax.tree.map(lambda _: "keep", node),
        opt_shape,
        is_leaf=lambda n: mirrors_params(n) or jax.tree.structure(
            n).num_leaves <= 1)


def constrain_opt_state(opt_state, moment_shardings):
    """Apply `lax.with_sharding_constraint` wherever `zero_moment_shardings`
    produced a sharding ("keep" leaves pass through untouched)."""
    return jax.tree.map(
        lambda x, s: lax.with_sharding_constraint(x, s)
        if isinstance(s, NamedSharding) else x,
        opt_state, moment_shardings)
