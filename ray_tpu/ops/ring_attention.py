"""Ring attention — context parallelism over a mesh axis.

Long-context attention where the sequence is sharded across devices
(SURVEY §5 long-context; net-new vs the reference, which has no in-repo
kernels).  Each device holds a local query/key/value shard [B, S/n, H, D];
key/value shards rotate around the ring via ``lax.ppermute`` while every
device accumulates its queries' attention over the full sequence with an
online (streaming) softmax — the global [S, S] score matrix never exists,
and peak activation memory is O(S/n · S/n) per device per step.

Usage — under ``shard_map`` with the sequence axis bound::

    out = shard_map(
        lambda q, k, v: ring_attention(q, k, v, causal=True, axis_name="sp"),
        mesh=mesh,
        in_specs=P(None, "sp", None, None),
        out_specs=P(None, "sp", None, None),
    )(q, k, v)

or via :func:`ring_attention_global`, which applies the shard_map for you.
Called WITHOUT the axis bound (single-host tests, attn_impl="ring" on an
unsharded model) it degrades to exact single-device attention.

The communication pattern (kv rotation on a ring, one ``ppermute`` hop per
step, compute overlapping the next hop's transfer) is the TPU-idiomatic
equivalent of the reference's NCCL send/recv context parallelism: the hops
ride neighbouring ICI links, so bandwidth scales with the ring size.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.util.collective.pallas import (
    select_impl, start_ring_permute, wait_ring_permute,
)

_NEG = -1e30


def _axis_size(axis_name: str) -> Optional[int]:
    """Static size of a bound mesh axis, or None when unbound."""
    try:
        return lax.axis_size(axis_name)
    except NameError:
        return None


def ring_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                   causal: bool = True, axis_name: str = "sp",
                   impl: str = "lax") -> jax.Array:
    """Per-shard ring attention. q, k, v: [B, S_local, H, D].

    Inside ``shard_map`` (axis bound): the full-sequence result for the
    local query shard. Outside: falls back to exact local attention.

    ``impl`` selects the KV-exchange backend.  ``"lax"`` (default) is the
    ``ppermute`` rotation — differentiable, so it is what training uses.
    ``"pallas"``/``"pallas_interpret"``/``"auto"`` route the rotation
    through the split-phase Pallas ring (`start_ring_permute` before the
    block compute, `wait_ring_permute` after), putting the hop's DMA
    explicitly under the attention matmuls — the overlap the serving path
    wants for long-context KV exchange.  `pallas_call` has no autodiff
    rule, so the Pallas path is forward-only (inference/serving).
    """
    n = _axis_size(axis_name)
    if n is None or n == 1:
        from ray_tpu.models.llama import xla_attention

        return xla_attention(q, k, v, causal=causal)

    resolved = select_impl(impl)
    use_split = resolved in ("pallas", "pallas_interpret")

    B, Sl, H, D = q.shape
    scale = 1.0 / math.sqrt(D)
    my = lax.axis_index(axis_name)
    perm = [(i, (i + 1) % n) for i in range(n)]

    rows = jnp.arange(Sl)

    @jax.checkpoint
    def _block(q, k_cur, v_cur, src, m, l, acc):
        """One ring step: attend local q against the kv shard currently
        held (originating from shard ``src``), online-softmax style."""
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k_cur,
                       preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = (my * Sl + rows)[:, None]
            k_pos = (src * Sl + rows)[None, :]
            mask = q_pos >= k_pos                        # [Sl, Sl]
            s = jnp.where(mask[None, None], s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))      # [B,H,Sq]
        p = jnp.exp(s - m_new[..., None])
        if causal:
            p = jnp.where(mask[None, None], p, 0.0)
        alpha = jnp.exp(m - m_new)
        l_new = alpha * l + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bhqk,bkhd->bqhd", p.astype(v_cur.dtype), v_cur,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha.transpose(0, 2, 1)[..., None] + pv
        return m_new, l_new, acc_new

    def body(carry, step):
        m, l, acc, k_cur, v_cur = carry
        src = (my - step) % n
        if use_split:
            # Split-phase: the next shard's hop is in flight while this
            # shard's attention block computes — explicit overlap rather
            # than hoping the scheduler finds it.
            kh = start_ring_permute(k_cur, axis_name, n=n, impl=resolved)
            vh = start_ring_permute(v_cur, axis_name, n=n, impl=resolved)
            m, l, acc = _block(q, k_cur, v_cur, src, m, l, acc)
            k_nxt = wait_ring_permute(kh)
            v_nxt = wait_ring_permute(vh)
        else:
            m, l, acc = _block(q, k_cur, v_cur, src, m, l, acc)
            # Rotate kv one hop; XLA overlaps the transfer with the next
            # iteration's compute where dependencies allow.
            k_nxt = lax.ppermute(k_cur, axis_name, perm)
            v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return (m, l, acc, k_nxt, v_nxt), None

    def _vary(x):
        # shard_map vma typing: carries computed from axis_index become
        # "varying" over the axis; the zero-init carries must be cast to
        # match or lax.scan rejects the body signature.
        return lax.pcast(x, (axis_name,), to="varying")

    m0 = _vary(jnp.full((B, H, Sl), _NEG, jnp.float32))
    l0 = _vary(jnp.zeros((B, H, Sl), jnp.float32))
    acc0 = _vary(jnp.zeros((B, Sl, H, D), jnp.float32))
    (m, l, acc, _, _), _ = lax.scan(
        body, (m0, l0, acc0, k, v), jnp.arange(n))

    l_safe = jnp.where(l == 0.0, 1.0, l)                 # fully-masked rows
    out = acc / l_safe.transpose(0, 2, 1)[..., None]
    return out.astype(q.dtype)


def ring_attention_global(q: jax.Array, k: jax.Array, v: jax.Array,
                          mesh, causal: bool = True,
                          seq_axis: str = "sp",
                          impl: str = "lax") -> jax.Array:
    """Global-view convenience wrapper: q, k, v are full [B, S, H, D]
    arrays; the sequence dim is sharded over ``mesh[seq_axis]`` and the
    ring runs under ``shard_map``."""
    from jax.sharding import PartitionSpec as P

    spec = P(None, seq_axis, None, None)
    # check_vma off: Pallas kernels are opaque to the varying-axes checker.
    fn = jax.shard_map(
        partial(ring_attention, causal=causal, axis_name=seq_axis,
                impl=impl),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)
