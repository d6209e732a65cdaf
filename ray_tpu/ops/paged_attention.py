"""Decode attention over the paged KV pool, read through the block table.

The dense decoder's decode tick and speculative verify hold their keys
and values in ONE stacked pool a kind, `[L, NB, bs, kvH, D]`, and a block
table a sequence.  `paged_attention` is a Pallas TPU kernel that reads
the live blocks of every live sequence straight out of that pool, where
it lies in HBM, and nothing else: no dense `[B, S_pad, kvH, D]` view is
built, a dead slot costs nothing, and a table entry past a sequence's
length is never dereferenced (stale and out-of-range ids live there).

How it walks.  `plan` (plain XLA, once a program, outside the layer
scan) cuts every live sequence into chunks of `chunk` blocks and lays
the chunks of all sequences end to end as one work list.  The kernel is
one grid step: a loop over that list whose trip count is read from
scalar memory.  Each trip starts the copies of the NEXT chunk's blocks
(`pool.at[layer, table[b, j]]`, one contiguous block each) into the
other half of a double-buffered VMEM scratch, waits for its own, and
folds its chunk into a float32 online softmax (running max, sum and
accumulator in the loop's carry); a sequence's last chunk writes its
output row.  Laid end to end, the first chunk of the next sequence is
in flight while the last of this one is reduced.

How it multiplies.  A block is `[bs, kvH, D]`: in the pool's tiling one
(8, 128) tile a token when kvH is 8, so a block is a `[bs * kvH, D]`
matrix as it lies (the flat view of the pool is a bitcast) and a chunk
is `[chunk * bs * kvH, D]`, row = token * kvH + group.  All H query heads are multiplied against all of it and a
score whose row is not of the query head's group is masked like a key
past the query's position.  That is kvH times the multiplications the
mathematics needs and costs nothing: a key tile is loaded into the
matrix unit once either way, and the copies, not the products, are the
kernel's time (on a v5e, 32 sequences of 1000 and of 2048 rows: 88% and
91% of the HBM bound for the bytes it reads; PERF.md section 6, PR 31).

Heads narrower than a lane row.  A pool `[L, NB, bs, kvH, 64]` would be
stored with half of every 128-lane row as padding.  A model with heads
of 64 keeps ONE pool instead, a row a token a KV head holding that
head's K ‖ V (`[L, NB, bs, kvH, 2 D]`, no padded lanes), and calls the
kernel with `v_pool=None`: one copy a block brings both, the query is
laid into the K lanes of a zero row (so `q . row` is `q . k`), the
value product runs over the whole row, and the V lanes of the result
are the output.  The same loop, the same masks, one buffer instead of
two.

Precision: bf16 operands, float32 scores, softmax and accumulation (the
gather path it replaces rounds the scores to bf16 first).

The gather + `models.llama._decode_attention` stays as the reference and
as the path wherever the kernel does not engage (`engages`): off TPU,
and at shapes that do not tile.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as _attention

_MASK = -1e30
# Blocks a chunk (PERF.md section 6, PR 31: swept on the chip).
CHUNK_BLOCKS = 32


def engages(pool: jax.Array) -> bool:
    """Whether `_Paged.attend` runs the kernel over `pool` [L, NB, bs,
    kvH, D]: `ops.attention`'s rule for the backend (a TPU always, off
    TPU only when a test forces the interpreter), and shapes that tile:
    a bf16 pool whose rows are whole lanes (D % 128) and whose blocks
    are whole packed tiles (bs * kvH % 16), so that a block is a
    `[bs * kvH, D]` matrix as it lies in HBM and the flat view of the
    pool is no copy (PERF.md section 6, PR 31: compiled and run at 1,
    4, 8 and 32 KV heads)."""
    _, _, bs, kvh, d = pool.shape
    tiles = (pool.dtype == jnp.bfloat16 and d % 128 == 0
             and (bs * kvh) % 16 == 0)
    return tiles and (_attention._on_tpu()
                      or _attention.FORCE_PALLAS_INTERPRET)


def plan(tables: jax.Array, qpos: jax.Array, active, block_size: int,
         chunk: int = CHUNK_BLOCKS):
    """The kernel's scalars, computed once a program: tables [B, nb];
    qpos [B] or [B, Q], the queries' absolute positions (the last is
    the largest); active [B] bool or None (all live).  A sequence's
    length is its last query's position + 1, or 0 when it is dead; its
    chunks are `chunk` blocks each; the work list is the chunks of all
    sequences end to end, (sequence, chunk index) an item, padded to
    its static bound."""
    B, nb = tables.shape
    chunk = min(chunk, nb)
    qpos = qpos.reshape(B, -1).astype(jnp.int32)
    lengths = qpos[:, -1] + 1
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    n_chunks = -(-lengths // (chunk * block_size))
    ends = jnp.cumsum(n_chunks)
    item = jnp.arange(B * (-(-nb // chunk)), dtype=jnp.int32)
    seq = jnp.minimum((item[:, None] >= ends[None, :]).sum(-1), B - 1)
    first = (ends - n_chunks)[seq]
    return (ends[-1:].astype(jnp.int32), seq.astype(jnp.int32),
            (item - first).astype(jnp.int32), lengths.astype(jnp.int32),
            qpos.reshape(-1), tables.reshape(-1).astype(jnp.int32))


def _block_copy(pool, layer, phys, buf, slot, t, rows, sem):
    """One block of the flat pool, (layer, phys), to rows [t * rows,
    (t + 1) * rows) of half `slot` of a chunk buffer."""
    return pltpu.make_async_copy(
        pool.at[layer, phys], buf.at[slot, pl.ds(t * rows, rows)], sem)


def _kernel(layer_ref, n_ref, seq_ref, chunk_ref, len_ref, qpos_ref,
            tab_ref, q_ref, *refs, nb, bs, kvh, n_heads, n_q, chunk,
            scale):
    # refs: the pools in HBM, the output, a chunk buffer a pool, the
    # semaphores.  Two pools (K, V) or one whose rows are K ‖ V.
    n_pools = (len(refs) - 2) // 2
    o_ref, sems = refs[n_pools], refs[-1]
    pools = tuple(zip(refs[:n_pools], refs[n_pools + 1:-1]))
    kbuf, vbuf = pools[0][1], pools[-1][1]
    layer, n_items = layer_ref[0], n_ref[0]
    rows = bs * kvh                         # of a block in the flat view
    qh, d = q_ref.shape[1:]
    width = chunk * rows

    # A dead slot's row, and a chunk's rows past its last live block:
    # what the buffer holds there is multiplied by a probability of 0.
    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)

    def item_of(i):
        b, j = seq_ref[i], chunk_ref[i]
        live = (len_ref[b] + bs - 1) // bs - j * chunk
        return b, j, jnp.minimum(live, chunk)

    def copies(i, slot, start):
        b, j, live = item_of(i)

        @pl.loop(0, live)
        def _(t):
            # only `start` reads the table: entries past `live` are
            # never looked at
            phys = tab_ref[b * nb + j * chunk + t] if start else 0
            for which, (pool, buf) in enumerate(pools):
                copy = _block_copy(pool, layer, phys, buf, slot, t, rows,
                                   sems.at[slot, which])
                copy.start() if start else copy.wait()

    @pl.when(n_items > 0)
    def _():
        copies(0, 0, True)

    # row = query index * H + head; column = token in chunk * kvH + group
    row = lax.broadcasted_iota(jnp.int32, (qh, width), 0)
    col = lax.broadcasted_iota(jnp.int32, (qh, width), 1)
    own_group = (row % n_heads) // (n_heads // kvh) == col % kvh
    token = col // kvh
    row1 = lax.broadcasted_iota(jnp.int32, (qh, 1), 0)

    def step(i, carry):
        m, l, acc = carry
        slot = i % 2

        @pl.when(i + 1 < n_items)
        def _():
            copies(i + 1, 1 - slot, True)

        copies(i, slot, False)
        b, j, _ = item_of(i)
        fresh = j == 0
        m = jnp.where(fresh, _MASK, m)
        l = jnp.where(fresh, 0.0, l)
        acc = jnp.where(fresh, 0.0, acc)

        qpos = jnp.full((qh, 1), qpos_ref[b * n_q], jnp.int32)
        for t in range(1, n_q):
            qpos = jnp.where(row1 >= t * n_heads, qpos_ref[b * n_q + t],
                             qpos)
        seen = own_group & (token <= qpos - j * (chunk * bs))

        s = lax.dot_general(
            q_ref[b], kbuf[slot], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        s = jnp.where(seen, s, _MASK)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + p.sum(axis=-1, keepdims=True)
        acc = alpha * acc + jnp.dot(
            p.astype(vbuf.dtype), vbuf[slot],
            preferred_element_type=jnp.float32)

        @pl.when((j + 1) * (chunk * bs) >= len_ref[b])
        def _():
            o_ref[b] = (acc / l).astype(o_ref.dtype)

        return m_new, l, acc

    lax.fori_loop(0, n_items, step, (
        jnp.full((qh, 1), _MASK, jnp.float32),
        jnp.zeros((qh, 1), jnp.float32), jnp.zeros((qh, d), jnp.float32)))


def paged_attention(q: jax.Array, k_pool: jax.Array,
                    v_pool: Optional[jax.Array], layer: jax.Array,
                    scalars, *, chunk: int = CHUNK_BLOCKS) -> jax.Array:
    """q [B, Q, H, D] (rotated) against layer `layer` of the stacked
    pools [L, NB, bs, kvH, D], through `scalars` = `plan(tables, qpos,
    active, bs, chunk)`: [B, Q, H, D], zeros for a dead sequence.  Query
    j of sequence b sees keys at positions <= qpos[b, j].  The pools
    stay whole in HBM; the layer index is a scalar the kernel adds to
    its block addresses, never a slice of the pool.

    `v_pool=None`: `k_pool` [L, NB, bs, kvH, 2 D] holds K ‖ V a row (the
    module's docstring); q and the result are still D wide."""
    B, Q, H, D = q.shape
    L, NB, bs, kvh, W = k_pool.shape
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    if v_pool is None:
        q = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    nb = scalars[-1].shape[0] // B
    chunk = min(chunk, nb)
    if scalars[1].shape[0] != B * -(-nb // chunk):
        raise ValueError(
            f"the scalars were planned for another chunk than {chunk}")
    rows = bs * kvh
    interpret = not _attention._on_tpu()
    buf = pltpu.VMEM((2, chunk * rows, W), k_pool.dtype)
    kernel = functools.partial(
        _kernel, nb=nb, bs=bs, kvh=kvh, n_heads=H, n_q=Q, chunk=chunk,
        scale=1.0 / math.sqrt(D))
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(scalars),
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[buf] * len(pools)
            + [pltpu.SemaphoreType.DMA((2, len(pools)))]),
        out_shape=jax.ShapeDtypeStruct((B, Q * H, W), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *scalars,
      q.reshape(B, Q * H, W),
      *(pool.reshape(L, NB, rows, W) for pool in pools))
    return out.reshape(B, Q, H, W)[..., W - D:]
