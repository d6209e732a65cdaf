"""Pipeline parallelism over a mesh axis (SURVEY §2.7 — absent in the
reference in-repo; net-new, TPU-native).

GPipe-style schedule expressed as pure SPMD: every device along the
"pipe" mesh axis holds ONE stage's parameters (stacked pytree sharded on
the leading axis), activations circulate stage-to-stage with
`jax.lax.ppermute` over ICI, and the M-microbatch loop is a `lax.scan`
of M + P - 1 fixed-shape ticks. No host scheduling, no per-stage
processes — the whole pipeline is one jitted program, differentiable
end-to-end (ppermute has a transpose rule, so `jax.grad` through
`pipeline_apply` yields the reverse-schedule backward pass).

Bubble fraction is the usual (P-1)/(M+P-1): pick M >= 4*P for <20%.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, mesh: Mesh,
                   axis: str = "pipe") -> jax.Array:
    """Run `stage_fn` P times (one stage per device along `axis`).

    stage_params: pytree with leaves stacked [P, ...] (stage-major),
        sharded over `axis`.
    x: microbatched input [M, mb, ...], replicated along `axis`.
    Returns [M, mb, ...] outputs (replicated along `axis`).
    """
    n_stages = mesh.shape[axis]

    def spmd(params, xs):
        # Inside shard_map: params = THIS stage's slice [1, ...] and xs
        # the full microbatch stack (replicated).
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        n_ticks = m + n_stages - 1

        def varying(v):
            # shard_map tracks "varying manual axes": the scan carry
            # becomes pipe-varying inside the loop, so the initial value
            # must be marked varying too (no-op data-wise).
            return jax.lax.pcast(v, (axis,), to="varying")

        zero = varying(jnp.zeros_like(xs[0]))
        ys = varying(jnp.zeros_like(xs))

        def tick(carry, t):
            recv, ys = carry
            # Stage 0 ingests microbatch t (while t < M); others take the
            # activation handed over by the previous stage.
            mb_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(stage == 0, xs[mb_idx], recv)
            out = stage_fn(params, inp)
            # Last stage completed microbatch t-(P-1) at tick t.
            done_idx = t - (n_stages - 1)
            is_done = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                ys, out, jnp.maximum(done_idx, 0), 0)
            ys = jnp.where(is_done, updated, ys)
            # Hand the activation to the next stage (ring; last->first
            # carries garbage that stage 0 ignores).
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            recv = jax.lax.ppermute(out, axis, perm)
            return (recv, ys), None

        (recv, ys), _ = jax.lax.scan(tick, (zero, ys),
                                     jnp.arange(n_ticks))
        # Only the last stage holds real outputs; replicate along the
        # pipe axis so the caller sees them everywhere.
        ys = jnp.where(stage == n_stages - 1, ys, jnp.zeros_like(ys))
        return jax.lax.psum(ys, axis)

    specs = jax.tree.map(
        lambda _: P(axis), stage_params)
    return shard_map(
        spmd, mesh=mesh,
        in_specs=(specs, P()), out_specs=P())(stage_params, x)


def microbatch(x: jax.Array, n_microbatches: int) -> jax.Array:
    """[B, ...] -> [M, B/M, ...] (B must divide evenly)."""
    B = x.shape[0]
    if B % n_microbatches:
        raise ValueError(f"batch {B} not divisible by M={n_microbatches}")
    return x.reshape(n_microbatches, B // n_microbatches, *x.shape[1:])


def unmicrobatch(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[0] * x.shape[1], *x.shape[2:])
