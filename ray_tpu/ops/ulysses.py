"""Ulysses-style sequence parallelism — all-to-all head/sequence reshard.

The config alternative to ring attention for long-context training
(SURVEY §5; the pattern of DeepSpeed-Ulysses, re-expressed as XLA
collectives over ICI). Where ring attention keeps queries home and
rotates KV shards around the ring, Ulysses re-shards: each device starts
with the full head set for a sequence shard [B, S/n, H, D], all-to-alls
into the full sequence for a head subset [B, S, H/n, D], runs ordinary
(flash) attention locally — exact, no online-softmax ring recursion —
and all-to-alls back.

Trade-off vs ring: two all-to-alls of the whole activation instead of
n-1 KV ppermute hops; exactness and a simpler kernel, but parallelism is
capped by the head count (n must divide both H and H_kv for GQA).

Usage mirrors `ops/ring_attention.py`::

    out = shard_map(
        lambda q, k, v: ulysses_attention(q, k, v, causal=True,
                                          axis_name="sp"),
        mesh=mesh, in_specs=P(None, "sp", None, None),
        out_specs=P(None, "sp", None, None),
    )(q, k, v)

or `ulysses_attention_global(q, k, v, mesh)` which applies the shard_map,
or `parallel.context_parallel_attention(mesh, impl="ulysses")` to plug
into the model layer. Called without the axis bound it degrades to exact
single-device attention.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.ring_attention import _axis_size


def ulysses_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True,
                      axis_name: str = "sp") -> jax.Array:
    """Per-shard Ulysses attention. q: [B, S_local, H, D]; k/v may carry
    fewer (grouped-query) heads. Requires the head counts to be divisible
    by the sequence-axis size."""
    n = _axis_size(axis_name)
    H, Hkv = q.shape[2], k.shape[2]
    if n is None or n == 1:  # axis unbound: plain exact attention
        if Hkv != H:
            k = jnp.repeat(k, H // Hkv, axis=2)
            v = jnp.repeat(v, H // Hkv, axis=2)
        return flash_attention(q, k, v, causal)
    if H % n or Hkv % n:
        raise ValueError(
            f"ulysses: sequence-axis size {n} must divide n_heads={H} "
            f"and n_kv_heads={Hkv} (use ring attention otherwise)")
    # [B, S/n, H, D] -> [B, S, H/n, D]: trade the sequence shard for a
    # head shard (one fused all-to-all per tensor over ICI).
    reshard = lambda x: lax.all_to_all(          # noqa: E731
        x, axis_name, split_axis=2, concat_axis=1, tiled=True)
    qg, kg, vg = reshard(q), reshard(k), reshard(v)
    if Hkv != H:
        # Grouped-query: expand the local KV head shard to the query
        # head count AFTER the reshard (ships Hkv/n heads over ICI,
        # repeats locally — cheaper than repeating before).
        kg = jnp.repeat(kg, H // Hkv, axis=2)
        vg = jnp.repeat(vg, H // Hkv, axis=2)
    out = flash_attention(qg, kg, vg, causal)
    # [B, S, H/n, D] -> [B, S/n, H, D]
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def ulysses_attention_global(q, k, v, mesh, causal: bool = True,
                             seq_axis: str = "sp"):
    """Apply the shard_map over `mesh[seq_axis]` for global [B, S, H, D]
    inputs sharded on the sequence dimension."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, seq_axis, None, None)
    return jax.shard_map(
        lambda a, b, c: ulysses_attention(a, b, c, causal=causal,
                                          axis_name=seq_axis),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False,
    )(q, k, v)
