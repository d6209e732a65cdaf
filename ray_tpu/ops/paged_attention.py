"""Decode attention over the paged KV pool, read through the block table.

The decode tick (and the dense decoder's speculative verify) holds its
keys and values in ONE stacked pool a kind, `[L, NB, bs, kvH, D]`, and a
block table a sequence.  `paged_attention` is a Pallas TPU kernel that
reads the live blocks of every live sequence straight out of that pool,
where it lies in HBM, and nothing else: no dense `[B, S_pad, kvH, D]`
view is built, a dead slot costs nothing, and a table entry past a
sequence's length is never dereferenced (stale and out-of-range ids
live there).  Three forms of one walk: two pools (K, V), one pool of
K ‖ V rows, and the latent pool's one row a token
(`paged_latent_attention`); `plan`, the work list, the copy loop and the
online softmax are the same code, and what differs is static: how many
buffers, how the query is laid into a row, the scale, whether heads are
grouped, and which lanes of the value product are kept.

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

The latent pool.  A latent-attention model (`models/latent_moe.py`,
and `models/kimi_linear.py`'s MLA layers) keeps ONE row a token a
layer, latent ‖ shared key ‖ zeros up to whole lanes (`[L, NB, bs, W]`,
W = 640 for a latent of 512 and a key of 64), which every query head
reads: one KV head, so a block is `[bs, W]` as it lies and no score is
masked for its group.  The caller absorbs `wkv_b`'s key part into the
query and lays it as a row is laid; `paged_latent_attention` scores it
against whole rows (x the caller's scale, 1/sqrt(qk_head_dim): the
row's width says nothing about it), and multiplies the probabilities
with the rows' latent lanes alone where those are whole lane tiles (4
of 5), which is the output: `attend_absorbed`'s arithmetic
(`models/latent_moe.py`) without its padded `[B, S_pad, W]` view.  With
32 query rows against a chunk of 512 x 640 the products, not the
copies, are about half of its time (PERF.md section 6, PR 36).

Few KV heads.  A pool `[L, NB, bs, 4, 128]` is tiled (4, 128) by the
TPU compiler, and an insert's scatter of whole blocks into it is then
compiled as a re-tiling copy of the WHOLE pool in and another out
(deviceless v5e compile, PERF.md section 6, PR 38).  A model with fewer
than 8 KV heads keeps a token's KV heads SIDE BY SIDE in one row instead
(`[L, NB, bs, kvH * D]`, 512 lanes at 4 heads of 128), a block is a
`[bs, kvH * D]` matrix as it lies, and `paged_attention` reads such a
pool in one of two forms, chosen by shapes alone (`walks_groups`: the
query rows a KV group, `Q * H / kvH`):

- Few rows a group (under 32: one query a sequence at 16, 8 or 4 heads
  a KV head): as the latent pool is read.  One "KV head" whose row
  every query head scores whole, a query laid into the lanes of its own
  group of a zero row (so `q_row . row` is `q . k` of that group), the
  value product over the whole row, and each head's output taken from
  its group's lanes.  As many multiplications as the group mask costs
  the `[bs, kvH, D]` form, no mask.
- 32 rows a group or more (a block of 4 queries a sequence at 8 heads a
  KV head): the call WALKS ITS GROUPS.  The query goes in D lanes wide,
  a group's rows together (`[B, kvH, Q * H / kvH, D]`, one relayout in
  XLA on the way in and one on the way out), and a trip multiplies each
  group's rows against lanes `[g D, (g + 1) D)` of the same chunk
  buffers, whole lane tiles that cost nothing to slice: the scores that
  a zero row's other lanes added nothing to, a running max, sum and
  accumulator a group, the position mask computed once a trip for all
  of them.  The copies, the work list, `plan` and the double buffer are
  the other form's.  What it saves is around the trips, not in them:
  laid 512 lanes wide, 4 queries x 32 heads were 128 KiB a sequence of
  query and as much of output, three quarters of it zeros written and
  read back by XLA and carried through vector memory, and 256
  sequences took four calls (`slot_parts`); 128 lanes wide they take
  one.  A trip itself is as long in both forms (a key or value tile
  passes through the matrix unit once either way, and a trip's chain of
  products and reductions is latency), so this form takes its keys 64
  blocks a trip (`chunk_blocks`; PERF.md section 6, PR 58).

Many KV heads that are no whole tile.  A pool `[L, NB, bs, 30, 128]`
(as many K/V heads as query heads, 30 of them) is laid by the TPU
compiler with a block's heads OUTSIDE its tokens, `[30, bs, 128]` in
HBM: 30 rows would pad to 32 under the (16, 128) bf16 tile, 16 do not.
The flat `[bs * kvH, D]` view of such a pool is a copy of all of it
(deviceless v5e compile: two 3 GB copies a call), so the kernel takes
the view that IS the bitcast, `swapaxes(pool, 2, 3)`: a block is
`[kvH * bs, D]` with row = group * bs + token, and the column masks are
computed for that order (`heads_major`, static).  `write_rows` puts a
tick's new rows into such a pool through the same view.  The same walk,
the same copies; what the group mask costs does grow with the heads:
at 30 groups of one query head the kernel forms 30 times the scores it
keeps, on a matrix unit fed 30 rows (PERF.md section 6, PR 40 has the
reading).

The window form.  A sliding-window layer's query at position p sees the
keys p - W + 1 .. p alone, and its pool holds no more: the table a
sequence is `ring` blocks wide and position t lives in
`table[(t // bs) % ring]` (`serve/llm/engine.py` keeps it so).  `plan`
and the kernel take `window=W`: a sequence's first chunk is the one that
holds p - W + 1 (chunks stay aligned to absolute positions, so at most
`(W - 2) // (chunk * bs) + 2` of them whatever the length), its copies
start at the block that holds that key, the table is indexed modulo its
width, and a key at or before p - W is masked beside one past p.  The
same kernel, the same loop: what differs is in the scalars and in three
static branches.  One query a sequence (no verify step over a window).

Precision: bf16 operands, float32 scores, softmax and accumulation (the
gather paths it replaces round the scores to bf16 first).

The gather + `models.llama._decode_attention` (or + `attend_absorbed`)
stays as the reference and as the path wherever the kernel does not
engage (`engages`): off TPU, and at shapes that do not tile.
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
    """Whether a decode tick runs the kernel over `pool` [L, NB, bs,
    kvH, D] (or [L, NB, bs, D]: the latent pool, one KV head):
    `ops.attention`'s rule for the backend (a TPU always, off TPU only
    when a test forces the interpreter), and shapes that tile: a bf16
    pool whose rows are whole lanes (D % 128) and whose blocks are
    whole packed tiles (bs * kvH % 16), so that a block is a
    `[bs * kvH, D]` matrix as it lies in HBM and the flat view of the
    pool is no copy (PERF.md section 6, PR 31: compiled and run at 1,
    4, 8 and 32 KV heads)."""
    bs, d = pool.shape[2], pool.shape[-1]
    kvh = pool.shape[3] if pool.ndim == 5 else 1
    tiles = (pool.dtype == jnp.bfloat16 and d % 128 == 0
             and (bs * kvh) % 16 == 0)
    return tiles and (_attention._on_tpu()
                      or _attention.FORCE_PALLAS_INTERPRET)


def _heads_major(kvh: int) -> bool:
    """Whether the TPU compiler lays a block `[bs, kvH, D]` head by head
    (`[kvH, bs, D]` in HBM, no padded rows): more than 4 KV heads that
    are not whole sublane tiles (deviceless v5e compiles: 6, 12, 20 and
    30 heads so; 2 and 4 in tiles of their own, 8, 24 and 32 token by
    token)."""
    return kvh > 4 and kvh % 8 != 0


def write_rows(pool: jax.Array, layer, phys: jax.Array, off: jax.Array,
               rows: jax.Array) -> jax.Array:
    """New rows into `pool` [L, NB, bs, kvH, D] at (layer, phys, off):
    phys and off [...] (a sequence, or a sequence and a query), rows
    [..., kvH, D]; a block id out of bounds is dropped.  Where a block
    lies head by head (`_heads_major`) the write goes through that view,
    a `[D]` row at (layer, block, head, offset) each: as
    `.at[layer, phys, off].set` the compiler re-tiles the WHOLE pool
    token by token for the scatter and back (deviceless v5e compile at
    30 heads: four copies of a 3 GB pool a layer)."""
    rows = rows.astype(pool.dtype)
    kvh = pool.shape[3]
    if not _heads_major(kvh):
        return pool.at[layer, phys, off].set(rows)
    view = jnp.swapaxes(pool, 2, 3).at[
        layer, phys[..., None], jnp.arange(kvh), off[..., None]].set(rows)
    return jnp.swapaxes(view, 2, 3)


def _window_chunks(window: int, chunk: int, block_size: int) -> int:
    """The most chunks of `chunk` blocks, aligned to absolute positions,
    that `window` consecutive keys straddle."""
    return (window - 2) // (chunk * block_size) + 2


def plan(tables: jax.Array, qpos: jax.Array, active, block_size: int,
         chunk: int = CHUNK_BLOCKS, window: Optional[int] = None):
    """The kernel's scalars, computed once a program: tables [B, nb];
    qpos [B] or [B, Q], the queries' absolute positions (the last is
    the largest); active [B] bool or None (all live).  A sequence's
    length is its last query's position + 1, or 0 when it is dead; its
    chunks are `chunk` blocks each; the work list is the chunks of all
    sequences end to end, (sequence, chunk index) an item, padded to
    its static bound.

    `window`: the module docstring's window form.  tables [B, ring] is
    a ring; a sequence's chunks run from the one that holds its first
    visible key, length - window, under their ABSOLUTE indices."""
    B, nb = tables.shape
    chunk = min(chunk, nb)
    span = chunk * block_size
    qpos = qpos.reshape(B, -1).astype(jnp.int32)
    lengths = qpos[:, -1] + 1
    if active is not None:
        lengths = jnp.where(active, lengths, 0)
    n_chunks = -(-lengths // span)
    if window is None:
        skipped, bound = 0, -(-nb // chunk)
    else:
        if qpos.shape[1] != 1:
            raise ValueError("the window form takes one query a sequence")
        skipped = jnp.maximum(lengths - window, 0) // span
        n_chunks, bound = n_chunks - skipped, _window_chunks(
            window, chunk, block_size)
    ends = jnp.cumsum(n_chunks)
    item = jnp.arange(B * bound, dtype=jnp.int32)
    seq = jnp.minimum((item[:, None] >= ends[None, :]).sum(-1), B - 1)
    first = (ends - n_chunks)[seq]
    if window is not None:
        first = first - skipped[seq]
    return (ends[-1:].astype(jnp.int32), seq.astype(jnp.int32),
            (item - first).astype(jnp.int32), lengths.astype(jnp.int32),
            qpos.reshape(-1), tables.reshape(-1).astype(jnp.int32))


# Scalar memory a call's prefetched operands may take together: the
# chip has 1 MiB, the compiler keeps some of it.
_SMEM_BUDGET = 768 * 2 ** 10


# Vector memory a call's queries and outputs, which lie there whole, may
# take together beside the chunk buffers (of the call's 64 MiB).
_VMEM_QUERY_BUDGET = 24 * 2 ** 20


def slot_parts(slots: int, table_width: int,
               chunk: int = CHUNK_BLOCKS, query_bytes: int = 0) -> int:
    """Into how many equal parts a caller cuts its `slots` sequences so
    that ONE call's scalars (`plan`: a sequence's table row, its chunks'
    two lists, its length and position) fit scalar memory: 1 at every
    geometry but a very wide one (384 slots x 768 blocks of table are
    1.18 MB, which the v5e compiler refuses; two calls of 192 fit).  A
    part is planned and attended on its own; the pool is one.

    `query_bytes`: what ONE sequence's queries take as the kernel is
    handed them (`query_bytes`); a call holds them and as many bytes of
    output whole in vector memory, so many query rows a sequence, each
    as wide as a side-by-side pool's row, are cut too (4 queries x 32
    heads x 512 lanes would be 128 KiB a sequence and 256 slots 4
    parts; walking the KV groups they are 32 KiB and the slots one)."""
    chunks = -(-table_width // min(chunk, table_width))
    a_slot = 4 * (table_width + 2 * chunks + 2)
    return next(p for p in range(1, slots + 1)
                if slots % p == 0 and slots // p * a_slot <= _SMEM_BUDGET
                and 2 * (slots // p) * query_bytes <= _VMEM_QUERY_BUDGET)


# Query rows a KV group (Q * H / kvH) from which a call over a
# side-by-side pool walks its groups (PERF.md section 6, PR 58: read on
# the chip at 32 and at 16 rows a group).
_GROUP_ROWS = 32


def walks_groups(n_q: int, n_heads: int, kv_heads: int) -> bool:
    """Which of its two forms `paged_attention` takes over a pool that
    holds `kv_heads` KV heads side by side in a row, for `n_q` queries a
    sequence of `n_heads` heads: True, a KV group's `n_q * n_heads /
    kv_heads` query rows against that group's own lanes; False, every
    row laid as wide as the pool's row against whole rows (the module
    docstring, "Few KV heads").  Shapes alone: the rows of a group are
    whole packed bf16 tiles (16) and enough of them that the narrower
    products pay for a loop over the groups."""
    rows = n_q * n_heads // kv_heads
    return kv_heads > 1 and rows % 16 == 0 and rows >= _GROUP_ROWS


# Blocks a chunk where a call walks its KV groups: a trip's chain of
# two products and two reductions a group is latency, 0.8 us of a trip
# whatever its width, so twice the keys a trip are fewer trips for the
# same copies (PERF.md section 6, PR 58: read on the chip at 16, 32, 64
# and 128 blocks).
_GROUPS_CHUNK_BLOCKS = 64


def chunk_blocks(n_q: int, n_heads: int, kv_heads: int) -> int:
    """The blocks a chunk that a caller over a side-by-side pool plans
    with and calls with (`plan`, `slot_parts`, `paged_attention`)."""
    return _GROUPS_CHUNK_BLOCKS if walks_groups(n_q, n_heads, kv_heads) \
        else CHUNK_BLOCKS


def query_bytes(n_q: int, n_heads: int, kv_heads: int, head_dim: int,
                itemsize: int = 2) -> int:
    """What ONE sequence's queries take as the kernel over a
    side-by-side pool is handed them (`slot_parts`' `query_bytes`):
    `n_q * n_heads` rows, `head_dim` lanes wide where the call walks
    its groups, as wide as the pool's row where it does not."""
    lanes = head_dim if walks_groups(n_q, n_heads, kv_heads) \
        else kv_heads * head_dim
    return n_q * n_heads * lanes * itemsize


def _block_copy(pool, layer, phys, buf, slot, t, rows, sem):
    """One block of the flat pool, (layer, phys), to rows [t * rows,
    (t + 1) * rows) of half `slot` of a chunk buffer."""
    return pltpu.make_async_copy(
        pool.at[layer, phys], buf.at[slot, pl.ds(t * rows, rows)], sem)


def _kernel(layer_ref, n_ref, seq_ref, chunk_ref, len_ref, qpos_ref,
            tab_ref, q_ref, *refs, nb, bs, kvh, n_heads, n_q, chunk,
            scale, window, heads_major, groups=1):
    # refs: the pools in HBM, the output, a chunk buffer a pool, the
    # semaphores.  Two pools (K, V) or one whose rows hold both (K ‖ V,
    # or the latent row).  The output keeps the value product's first
    # `d` lanes: all of a row, or the latent's whole lane tiles.
    # `groups` > 1: a row of the pools holds that many KV heads of `d`
    # lanes side by side, q_ref and o_ref are [B, groups, rows, d], and
    # a trip multiplies each group's rows against its own lanes of the
    # chunk buffers (`n_heads` is then the heads of ONE group).
    n_pools = (len(refs) - 2) // 2
    o_ref, sems = refs[n_pools], refs[-1]
    pools = tuple(zip(refs[:n_pools], refs[n_pools + 1:-1]))
    kbuf, vbuf = pools[0][1], pools[-1][1]
    layer, n_items = layer_ref[0], n_ref[0]
    rows = bs * kvh                         # of a block in the flat view
    qh, d = o_ref.shape[-2:]
    width = chunk * rows

    # A dead slot's row, and a chunk's rows past its last live block:
    # what the buffer holds there is multiplied by a probability of 0.
    o_ref[...] = jnp.zeros_like(o_ref)
    vbuf[...] = jnp.zeros_like(vbuf)

    def item_of(i):
        b, j = seq_ref[i], chunk_ref[i]
        live = (len_ref[b] + bs - 1) // bs - j * chunk
        return b, j, jnp.minimum(live, chunk)

    def first_key(b):               # of a window: the first one seen
        return jnp.maximum(len_ref[b] - window, 0)

    def copies(i, slot, start):
        b, j, live = item_of(i)
        # a window's first chunk begins at the block of its first key
        lo = 0 if window is None else jnp.maximum(
            first_key(b) // bs - j * chunk, 0)

        @pl.loop(lo, live)
        def _(t):
            # only `start` reads the table: entries past `live` are
            # never looked at (a window's table is a ring)
            at = j * chunk + t
            if window is not None:
                at = at % nb
            phys = tab_ref[b * nb + at] if start else 0
            for which, (pool, buf) in enumerate(pools):
                copy = _block_copy(pool, layer, phys, buf, slot, t, rows,
                                   sems.at[slot, which])
                copy.start() if start else copy.wait()

    @pl.when(n_items > 0)
    def _():
        copies(0, 0, True)

    # row = query index * H + head (H: the heads of ONE group where the
    # call walks its groups); column = token in chunk * kvH + group, or,
    # where a block lies head by head, block * rows + group * bs + token
    # in block
    row = lax.broadcasted_iota(jnp.int32, (qh, width), 0)
    col = lax.broadcasted_iota(jnp.int32, (qh, width), 1)
    if heads_major:
        group = col % rows // bs
        token = col // rows * bs + col % bs
    else:
        group, token = col % kvh, col // kvh
    # one KV head (the latent pool): every query head reads every row
    own_group = None if kvh == 1 else \
        (row % n_heads) // (n_heads // kvh) == group
    row1 = lax.broadcasted_iota(jnp.int32, (qh, 1), 0)

    def of_group(b, g):             # where q_ref and o_ref hold group g
        return (b, g) if groups > 1 else b

    def lanes_of(buf, slot, g):     # the group's lanes of a chunk buffer
        return buf[slot, :, pl.ds(g * d, d)]

    def step(i, carry):
        slot = i % 2

        @pl.when(i + 1 < n_items)
        def _():
            copies(i + 1, 1 - slot, True)

        copies(i, slot, False)
        b, j, _ = item_of(i)
        fresh = j == (0 if window is None
                      else first_key(b) // (chunk * bs))
        carry = [(jnp.where(fresh, _MASK, m), jnp.where(fresh, 0.0, l),
                  jnp.where(fresh, 0.0, acc)) for m, l, acc in carry]

        qpos = jnp.full((qh, 1), qpos_ref[b * n_q], jnp.int32)
        for t in range(1, n_q):
            qpos = jnp.where(row1 >= t * n_heads, qpos_ref[b * n_q + t],
                             qpos)
        seen = token <= qpos - j * (chunk * bs)
        if window is not None:
            seen = seen & (token > qpos - window - j * (chunk * bs))
        if own_group is not None:
            seen = own_group & seen

        def fold(g, m, l, acc):     # the chunk into group g's softmax
            rows_q = q_ref[of_group(b, g)]
            keys = lanes_of(kbuf, slot, g) if groups > 1 else kbuf[slot]
            s = lax.dot_general(
                rows_q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            s = jnp.where(seen, s, _MASK)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + p.sum(axis=-1, keepdims=True)
            if groups > 1:
                values = lanes_of(vbuf, slot, g)
            else:
                values = vbuf[slot] if d == vbuf.shape[-1] \
                    else vbuf[slot, :, :d]
            acc = alpha * acc + jnp.dot(
                p.astype(vbuf.dtype), values,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        carry = [fold(g, *state) for g, state in enumerate(carry)]

        @pl.when((j + 1) * (chunk * bs) >= len_ref[b])
        def _():
            for g, (_, l, acc) in enumerate(carry):
                o_ref[of_group(b, g)] = (acc / l).astype(o_ref.dtype)

        return tuple(carry)

    # the online softmax's running max, sum and accumulator, a KV group
    lax.fori_loop(0, n_items, step, ((
        jnp.full((qh, 1), _MASK, jnp.float32),
        jnp.zeros((qh, 1), jnp.float32),
        jnp.zeros((qh, d), jnp.float32)),) * groups)


def _call(q, pools, layer, scalars, *, kvh, n_heads, n_q, scale,
          out_width, chunk, window=None, heads_major=False, groups=1):
    """The kernel over `pools` (flat: [L, NB, bs * kvH, W] each, a
    block's rows token by token or, `heads_major`, head by head) for q
    [B, Q * H, W], laid as the first pool's rows are: [B, Q * H,
    out_width], the first lanes of the value product over the last
    pool's rows.

    `groups` > 1 (then `kvh` is 1 and `n_heads` the heads of one
    group): a pool row holds `groups` KV heads of `out_width` lanes
    side by side and q is [B, groups, Q * n_heads, out_width], a
    group's rows together, query by query: [B, groups, Q * n_heads,
    out_width], each group's rows against its own lanes."""
    B, W = q.shape[0], pools[0].shape[-1]
    rows = pools[0].shape[2]
    nb = scalars[-1].shape[0] // B
    chunk = min(chunk, nb)
    bound = -(-nb // chunk) if window is None else _window_chunks(
        window, chunk, rows // kvh)
    if scalars[1].shape[0] != B * bound:
        raise ValueError(
            f"the scalars were planned for another chunk than {chunk}")
    interpret = not _attention._on_tpu()
    buf = pltpu.VMEM((2, chunk * rows, W), pools[0].dtype)
    kernel = functools.partial(
        _kernel, nb=nb, bs=rows // kvh, kvh=kvh, n_heads=n_heads, n_q=n_q,
        chunk=chunk, scale=scale, window=window, heads_major=heads_major,
        groups=groups)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1 + len(scalars),
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.VMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=pl.BlockSpec(memory_space=pltpu.VMEM),
            scratch_shapes=[buf] * len(pools)
            + [pltpu.SemaphoreType.DMA((2, len(pools)))]),
        out_shape=jax.ShapeDtypeStruct(q.shape[:-1] + (out_width,), q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        name="paged_attention",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *scalars, q, *pools)


def paged_attention(q: jax.Array, k_pool: jax.Array,
                    v_pool: Optional[jax.Array], layer: jax.Array,
                    scalars, *, chunk: int = CHUNK_BLOCKS,
                    window: Optional[int] = None,
                    scale: Optional[float] = None) -> jax.Array:
    """q [B, Q, H, D] (rotated) against layer `layer` of the stacked
    pools [L, NB, bs, kvH, D], through `scalars` = `plan(tables, qpos,
    active, bs, chunk)`: [B, Q, H, D], zeros for a dead sequence.  Query
    j of sequence b sees keys at positions <= qpos[b, j].  The pools
    stay whole in HBM; the layer index is a scalar the kernel adds to
    its block addresses, never a slice of the pool.

    `v_pool=None`: `k_pool` [L, NB, bs, kvH, 2 D] holds K ‖ V a row (the
    module's docstring); q and the result are still D wide.

    Pools of FOUR axes, [L, NB, bs, kvH * D], hold a token's KV heads
    side by side in one row (the module's docstring, "Few KV heads").

    `window`: query b sees keys in (qpos[b] - window, qpos[b]] alone and
    the table is a ring (`plan(..., window=window)` made the scalars).

    `scale`: the scores' factor where it is not `D ** -0.5`
    (`models/sambay.py`: a pair of heads of 64 laid as one head of 128,
    each query in the lanes of its own key of a zero row)."""
    B, Q, H, D = q.shape
    scale = scale or 1.0 / math.sqrt(D)
    if k_pool.ndim == 4:
        W = k_pool.shape[-1]
        kvh = W // D
        if walks_groups(Q, H, kvh):
            return _over_groups(q, k_pool, v_pool, layer, scalars,
                                chunk=chunk, window=window, scale=scale)
        # head h of group g into lanes [g D, (g + 1) D) of a zero row
        place = jnp.eye(kvh, dtype=q.dtype)
        q_row = jnp.einsum("bqgrd,gk->bqgrkd",
                           q.reshape(B, Q, kvh, H // kvh, D), place)
        out = _call(
            q_row.reshape(B, Q * H, W), [k_pool, v_pool], layer, scalars,
            kvh=1, n_heads=H, n_q=Q, scale=scale, out_width=W,
            chunk=chunk, window=window)
        return jnp.einsum(
            "bqgrkd,gk->bqgrd",
            out.reshape(B, Q, kvh, H // kvh, kvh, D), place).reshape(
                B, Q, H, D)
    L, NB, bs, kvh, W = k_pool.shape
    pools = (k_pool,) if v_pool is None else (k_pool, v_pool)
    if v_pool is None:
        q = jnp.concatenate([q, jnp.zeros_like(q)], axis=-1)
    # a block that lies head by head: the kernel takes that view, which
    # is then the bitcast
    heads_major = _heads_major(kvh)
    if heads_major:
        pools = [jnp.swapaxes(pool, 2, 3) for pool in pools]
    out = _call(
        q.reshape(B, Q * H, W),
        [pool.reshape(L, NB, bs * kvh, W) for pool in pools], layer,
        scalars, kvh=kvh, n_heads=H, n_q=Q, scale=scale,
        out_width=W, chunk=chunk, window=window, heads_major=heads_major)
    return out.reshape(B, Q, H, W)[..., W - D:]


# Jitted as `paged_latent_attention` below is, and for the same reason:
# the layers of an unrolled stack lower the kernel once.
@functools.partial(jax.jit, static_argnames=("chunk", "window", "scale"))
def _over_groups(q, k_pool, v_pool, layer, scalars, *, chunk, window,
                 scale):
    """`paged_attention` over side-by-side pools [L, NB, bs, kvH * D]
    where `walks_groups`: a group's rows together, query by query, D
    lanes wide ([B, kvH, Q * H / kvH, D]: one relayout of the queries
    and one of the result, in XLA, and no lane of either is a zero)."""
    B, Q, H, D = q.shape
    kvh = k_pool.shape[-1] // D

    def regrouped(x, outer, inner):
        return jnp.swapaxes(x.reshape(B, outer, inner, H // kvh, D), 1, 2)

    out = _call(
        regrouped(q, Q, kvh).reshape(B, kvh, Q * H // kvh, D),
        [k_pool, v_pool], layer, scalars, kvh=1, n_heads=H // kvh, n_q=Q,
        scale=scale, out_width=D, chunk=chunk, window=window, groups=kvh)
    return regrouped(out, kvh, Q).reshape(B, Q, H, D)


# Jitted so that the call sites of an unrolled layer loop (one a latent
# layer, the layer index an argument) trace and lower the kernel ONCE:
# eight of them cost every process start half a second of lowering; the
# compiled tick is the same, instruction names apart.
@functools.partial(jax.jit, static_argnames=("scale", "rank", "chunk"))
def paged_latent_attention(q_row: jax.Array, pool: jax.Array,
                           layer: jax.Array, scalars, *, scale: float,
                           rank: int,
                           chunk: int = CHUNK_BLOCKS) -> jax.Array:
    """Absorbed latent attention: q_row [B, H, W], each head's absorbed
    query laid as a cache row is (latent ‖ shared key ‖ zeros), against
    layer `layer` of the latent pool [L, NB, bs, W], one row a token
    that every head reads: [B, H, rank], the probability-weighted sum
    of the rows' first `rank` lanes, zeros for a dead sequence.  Scores
    are q_row . row x `scale`; sequence b sees rows at positions <=
    qpos[b].  The value product runs over the latent's lanes alone
    where they are whole lane tiles (512 of 640), else over the row."""
    return _call(q_row, [pool], layer, scalars, kvh=1,
                 n_heads=q_row.shape[1], n_q=1, scale=scale,
                 out_width=rank if rank % 128 == 0 else pool.shape[-1],
                 chunk=chunk)[..., :rank]
