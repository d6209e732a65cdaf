"""Gated delta rules as a recurrence over a fixed-size state: Kimi Delta
Attention (one decay a key CHANNEL, keys and values of one size) and the
gated delta rule of `models/gdn_hybrid.py` (one decay a HEAD, keys of
another size than values, write strengths up to 2) are one function.

For each head, with a state `S` [dk, dv] (float32), a token's key and
query `k`, `q` [dk] (L2-normalised by the caller, `q` scaled), value
`v` [dv], log-decay `g` (<= 0, `alpha = exp(g)`: [dk], one a channel, or
[1], one a head, which broadcasts through every form below) and write
strength `beta` (a scalar: in (0, 1), or in (0, 2) where the model
doubles it):

    S' = Diag(alpha) S
    S  = S' + beta k (v - S'^T k)^T
       # = (I - beta k k^T) Diag(alpha) S + beta k v^T
    o  = S^T q

Three forms of that one function:

- `kda_step` (plain `jax.numpy`): one token a sequence (decode).  Both
  reductions over the state (`S'^T k` and `S'^T q`) are taken in one
  pass and the output is put together from them (`o = S'^T q + beta
  (k . q) u`), so the state is read for the reductions, read and
  written for the update, and never for the output.  It is the
  reference of the next form and the path wherever that does not
  engage (`engages`: off TPU, a state kept in bf16, rows of the stack
  that are no whole tiles).
- `kda_step_live` (a Pallas TPU kernel): the same step on one layer of
  the decode tick's WHOLE stacked state, left where it lies in HBM and
  aliased input to output, for the LIVE slots alone.  `live_plan`
  (plain XLA, once a tick, shared by its layers) lists the live slots
  first; the kernel is one grid step whose loop runs that many trips,
  as `ops/paged_attention.py`'s walk does: a trip starts the copy of
  the NEXT live slot's rows (2 MB at 32 heads of 128 x 128, 2.2 MB at
  30 of 96 x 192) into the other half of a double-buffered VMEM
  scratch, waits for its own, takes both reductions, `u`, `o` and the
  update row by row on the vector unit in float32, in the operations
  `kda_step` writes (on the chip the two agree to the bit), and starts
  ONE copy of the new rows back to where the old ones lay.  So a live
  slot's state is read once and written once a layer, and a dead
  slot's is never copied, computed or written: no `where(live, new,
  old)`, no `.at[layer].set`, no layer cut out of the stack.  A head's
  vectors over dk (`alpha k`, `alpha q`, `alpha`, `k`) multiply the
  state's ROWS, so XLA lays them as columns (`[B, dk, 4 H]`, dk down
  the sublanes) and the kernel broadcasts each along the lanes; `beta`
  and `k . q` are scalars in scalar memory.
- `kda_chunked`: a whole (padded) sequence from an initial state
  (prefill), `chunk` tokens at a time.  Inside a chunk the products of
  the `(I - beta k k^T) Diag(alpha)` factors are written in the WY / UT
  form: with `G_r` the cumulative log-decay up to row r of the chunk,
  `A[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])` (i < r) and
  `u = (I + A Diag(beta))^-1 (v - (k exp(G)) S_0)` are the
  pseudo-values every row writes, found for all chunks at once by
  forward substitution in blocks of `_SOLVE_BLOCK` rows whose inverses
  are taken together, `v` and `k exp(G)` two right-hand sides through
  them (`_unit_lower_solve`); a scan over chunks then carries `S`.
  With one decay a head the exponential comes out of the sum over
  channels, and `A` and its twin for the queries are two `[C, dk] x
  [dk, C]` matrix products under the `[C, C]` decays, the differences
  masked to `i <= r` BEFORE the exponential, where they are <= 0.  With
  one a channel it cannot come out, and the terms are taken by
  sub-blocks of the same 16 rows (`_decayed_products`).  For a
  sub-block of keys whose last row is R and a row r below it, `exp(G_r
  - G_i) = exp(G_r - G_R) exp(G_R - G_i)`: the reference row lies
  BETWEEN the two sides (`i <= R < r`) and G does not increase down the
  rows, so both exponents are <= 0, nothing overflows, and a factor
  underflows only where the whole term does.  The rows below, decayed
  back to R, times the sub-block's keys, decayed forward to R, are then
  ONE `[rows, dk] x [dk, 16]` matrix product, three a chunk of 64 (for
  the k rows and the q rows together).  Only the diagonal sub-blocks
  keep the masked difference itself, `[16, 16, dk]` for the k rows and
  again for the q rows, each exponential inside the sum it feeds: no
  tensor has both chunk axes and the channel axis (`[C, C, dk]` is 2.1
  GB a layer at a 2048-token insert of 32 heads of 128).  This is not
  the factoring from the chunk's START: `exp(-G_i)` alone overflows
  float32 after a few strongly decayed tokens.  Tokens at and past
  `n_real` (padding) get `g = 0`, `beta = 0`: they leave the state as
  it is, so what is handed back is the state after the last REAL token.

The stack's layout.  The kernel wants a slot's state of a layer in whole
(8, 128) float32 tiles, or HBM stores and every copy moves padding: a
head's `[96, 192]` would be tiled as `[96, 256]`, a third more bytes.
`pack` therefore lays `heads_a_row(H, dv)` consecutive heads SIDE BY
SIDE in the lanes, `[.., H / p, dk, p dv]`: one head a row at values of
128 (Kimi: `[32, 128, 128]`, the layout it always had), two at 192
(`[15, 96, 384]`: three whole lane rows, 2,211,840 B a slot a layer,
exactly heads x dk x dv x 4).  In a packed row the kernel picks a head's
columns and scalars by lane (`lane < dv`: one select a column), which
the one-head rows do not pay.  The models keep `S` packed wherever it
lives (`init_slot_state`); `kda_step` and `kda_chunked` take and give
`[.., H, dk, dv]`, and `unpack` / `pack` stand between (the identity at
one head a row).  On a v5e, 128 slots x 6 layers: at 128 x 128 x 32
heads 0.31 ms + 37 us a live slot a tick, 83% of the HBM bound for the
bytes it moves, against 12.0 ms whatever is live for the plain form's
passes over all 128 (PERF.md section 6, PR 39); at 96 x 192 x 30 heads
with about 70 live 74% of the bound (PR 40).

All state arithmetic is float32 with float32 matrix products
(`Precision.HIGHEST`): the products are small beside the model's and the
state is the one thing here whose error compounds over a sequence.

The causal depthwise convolution in front of q, k and v is
`ops/short_conv.py`'s.
"""

from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as _attention

_HI = lax.Precision.HIGHEST
CHUNK = 64      # tokens a chunk of the prefill form
_LANE = 128
_SUBLANE = 8


def kda_step(S: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """S [B, H, dk, dv] float32; q, k [B, H, dk]; g [B, H, dk] or
    [B, H, 1] (one decay a head); v [B, H, dv]; beta [B, H].  Returns
    (o [B, H, dv] float32, the new S)."""
    f = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = f(q), f(k), f(v), f(g), f(beta)
    a = jnp.exp(g)
    # S'^T k = S^T (alpha k) and S'^T q = S^T (alpha q) in ONE pass over
    # the state as it lies (the decayed state is never written out):
    # sums on the vector unit in float32, no matrix unit and its
    # one-pass bf16 default
    kq = jnp.stack([k, q], axis=-1) * a[..., None]           # [B,H,dk,2]
    r = jnp.sum(S[..., None] * kq[..., None, :], axis=-3)    # [B,H,dv,2]
    u = (v - r[..., 0]) * beta[..., None]
    o = r[..., 1] + jnp.sum(k * q, -1, keepdims=True) * u
    return o, S * a[..., None] + k[..., None] * u[..., None, :]


def heads_a_row(heads: int, dv: int) -> int:
    """How many heads' values lie side by side in one row of the stack
    (`pack`): the fewest that fill whole lane rows (1 at dv 128, 2 at
    192: 384 lanes), or 1 where the heads do not divide by that."""
    p = _LANE // math.gcd(dv, _LANE)
    return p if heads % p == 0 else 1


def pack(S: jax.Array, p: int) -> jax.Array:
    """[..., H, dk, dv] -> [..., H / p, dk, p dv]: the states of `p`
    consecutive heads side by side in the lanes (the layout the stack is
    kept in; the identity at p = 1)."""
    if p == 1:
        return S
    *lead, H, dk, dv = S.shape
    S = jnp.moveaxis(S.reshape(*lead, H // p, p, dk, dv), -3, -2)
    return S.reshape(*lead, H // p, dk, p * dv)


def unpack(S: jax.Array, p: int) -> jax.Array:
    """`pack`'s inverse: [..., H / p, dk, p dv] -> [..., H, dk, dv]."""
    if p == 1:
        return S
    *lead, G, dk, W = S.shape
    S = jnp.moveaxis(S.reshape(*lead, G, dk, p, W // p), -2, -3)
    return S.reshape(*lead, G * p, dk, W // p)


def engages(dk: int, dv: int, dtype) -> bool:
    """Whether a decode tick steps its states through `kda_step_live`:
    `ops.attention`'s rule for the backend (a TPU always, off TPU only
    when a test forces the interpreter), a float32 state, and rows of
    the stack AS IT LIES (`[.., dk, dv]` its last two axes, `dv` the
    packed width where heads share a row) that are whole tiles: `dk` in
    whole sublanes (% 8) and `dv` in whole lane rows (% 128), so that
    nothing of a slot's `[H / p, dk, p dv]` is padding in HBM."""
    tiles = (dtype == jnp.float32 and dk % _SUBLANE == 0
             and dv % _LANE == 0)
    return tiles and (_attention._on_tpu()
                      or _attention.FORCE_PALLAS_INTERPRET)


def live_plan(active: Optional[jax.Array], n: int):
    """The kernel's scalars, once a tick for all its layers: (the slots
    [n] int32 as a permutation, live first, each part in slot order; the
    live count [1]).  `active` [n] bool, or None: every slot live.  Sums
    over comparisons, no sort and no host."""
    slot = jnp.arange(n, dtype=jnp.int32)
    if active is None:
        return slot, jnp.full((1,), n, jnp.int32)
    live = active.astype(jnp.int32)
    before = jnp.where(slot[:, None] > slot[None, :], live[None, :],
                       0).sum(-1)                   # live slots before b
    count = live.sum()
    place = jnp.where(active, before, count + slot - before)
    order = jnp.where(place[None, :] == slot[:, None], slot[None, :],
                      0).sum(-1)
    return order.astype(jnp.int32), count.reshape(1).astype(jnp.int32)


def _live_kernel(layer_ref, slots_ref, n_ref, scal_ref, cols_ref, v_ref,
                 s_in, o_ref, s_out, cbuf, vbuf, sbuf, nbuf, sems, *, heads):
    # `heads` H; a row of the stack holds p = H / (its groups) of them
    # side by side, each over its own dv lanes.
    # s_in and s_out are ONE stack in HBM (aliased); a live slot's rows
    # of layer `layer` are read once from the one and written once
    # through the other, a dead slot's by neither.
    layer, n = layer_ref[0], n_ref[0]
    nslots, _, width = o_ref.shape      # rows padded to whole sublanes
    groups = sbuf.shape[1]
    p = heads // groups
    lane = lax.broadcasted_iota(jnp.int32, (1, width), 1) if p > 1 else None

    def wide(parts):
        # a head's column [dk, 1] or scalar over that head's lanes
        out = parts[-1]
        for j in range(p - 2, -1, -1):
            out = jnp.where(lane < (j + 1) * (width // p), parts[j], out)
        return out

    def loads(i, half):
        b = slots_ref[i]
        return (pltpu.make_async_copy(s_in.at[layer, b], sbuf.at[half],
                                      sems.at[0, half]),
                pltpu.make_async_copy(cols_ref.at[b], cbuf.at[half],
                                      sems.at[1, half]),
                pltpu.make_async_copy(v_ref.at[b], vbuf.at[half],
                                      sems.at[2, half]))

    def store(i, half):
        return pltpu.make_async_copy(
            nbuf.at[half], s_out.at[layer, slots_ref[i]], sems.at[3, half])

    o_ref[...] = jnp.zeros_like(o_ref)      # a dead slot's row

    @pl.when(n > 0)
    def _():
        for copy in loads(0, 0):
            copy.start()

    @pl.loop(0, n)
    def _(i):
        half = i % 2

        @pl.when(i + 1 < n)
        def _():
            for copy in loads(i + 1, 1 - half):
                copy.start()

        for copy in loads(i, half):
            copy.wait()

        @pl.when(i >= 2)                    # this half's last write-back
        def _():
            store(i - 2, half).wait()

        b = slots_ref[i]
        cols = cbuf[half]                   # [dk, 4 H]
        for j in range(groups):
            S = sbuf[half, j]               # [dk, p dv]
            hs = range(j * p, (j + 1) * p)
            ak, aq, a, k = (wide([cols[:, c * heads + h:c * heads + h + 1]
                                  for h in hs]) for c in range(4))
            r_k = jnp.sum(S * ak, axis=0, keepdims=True)     # [1, p dv]
            r_q = jnp.sum(S * aq, axis=0, keepdims=True)
            beta = wide([scal_ref[b * heads + h] for h in hs])
            kq = wide([scal_ref[(nslots + b) * heads + h] for h in hs])
            u = (vbuf[half, pl.ds(j, 1), :] - r_k) * beta
            o_ref[b, pl.ds(j, 1), :] = r_q + kq * u
            nbuf[half, j] = S * a + k * u
        store(i, half).start()

    for last in (n - 2, n - 1):             # the write-backs in flight
        @pl.when(last >= 0)
        def _():
            store(last, last % 2).wait()


# Jitted so that a tick's call sites (one a KDA layer, the layer index
# an argument) trace and lower the kernel once.
@jax.jit
def _step_live(S, layer, cols, v, scal, slots, count):
    Lk, B, G, dk, W = S.shape           # G rows of p heads, W = p dv
    H = scal.shape[0] // (2 * B)
    interpret = not _attention._on_tpu()
    block = pltpu.VMEM((2, G, dk, W), jnp.float32)
    return pl.pallas_call(
        functools.partial(_live_kernel, heads=H),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((2,) + cols.shape[1:], jnp.float32),
                            pltpu.VMEM((2,) + v.shape[1:], jnp.float32),
                            block, block, pltpu.SemaphoreType.DMA((4, 2))]),
        out_shape=(jax.ShapeDtypeStruct(v.shape, jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)),
        input_output_aliases={6: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        name="kda_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, count, scal, cols,
      v, S)


def kda_step_live(S: jax.Array, layer, q: jax.Array, k: jax.Array,
                  v: jax.Array, g: jax.Array, beta: jax.Array, plan
                  ) -> Tuple[jax.Array, jax.Array]:
    """`kda_step` on layer `layer` of the WHOLE stack S [Lk, B, H / p,
    dk, p dv] float32 (`pack`: p = `heads_a_row(H, dv)` heads a row)
    for the live slots of `plan` = `live_plan(active, B)`, in place:
    (o [B, H, dv] float32, zeros for a dead slot; the stack, the same
    buffer where the caller donates it).  q, k [B, H, dk]; g [B, H, dk]
    or [B, H, 1] (one decay a head); v [B, H, dv]; beta [B, H].  The
    stack stays in HBM; the layer index is a scalar the kernel adds to
    its addresses, never a slice."""
    f = lambda x: x.astype(jnp.float32)
    q, k, v, g, beta = f(q), f(k), f(v), f(g), f(beta)
    B, H, dv = v.shape
    a = jnp.broadcast_to(jnp.exp(g), k.shape)
    # a head's four vectors over dk as COLUMNS (dk down the sublanes,
    # as the state's rows lie): alpha k ‖ alpha q ‖ alpha ‖ k, heads
    # side by side in the lanes
    cols = jnp.swapaxes(jnp.concatenate([k * a, q * a, a, k], axis=1), 1, 2)
    if cols.shape[-1] % _LANE:              # whole lane rows for the copy
        cols = jnp.pad(cols, [(0, 0), (0, 0), (0, -cols.shape[-1] % _LANE)])
    scal = jnp.stack([beta, jnp.sum(k * q, -1)]).reshape(-1)
    G = S.shape[2]
    v = v.reshape(B, G, -1)
    if G % _SUBLANE:                        # whole sublane tiles likewise
        v = jnp.pad(v, [(0, 0), (0, -G % _SUBLANE), (0, 0)])
    o, S = _step_live(S, layer, cols, v, scal, *plan)
    return o[:, :G].reshape(B, H, dv), S


_SOLVE_BLOCK = 16


def _unit_lower_solve(N: jax.Array, *rhs: jax.Array) -> list:
    """X with (I + N) X = rhs, for each rhs [..., C, W], for strictly
    lower-triangular N [..., C, C], by forward substitution in float32,
    in blocks of `_SOLVE_BLOCK` rows.  The diagonal blocks' inverses
    `T_m = (I + N_mm)^-1` depend on no block before them, so all of
    them, of every chunk and head, are taken in one pass on the vector
    unit, the blocks side by side in the lanes (`[row, column, n]`: a
    block of 16 x 16 fills an eighth of its tiles, n blocks a row of
    them all): row i is `e_i - N_mm[i, :i] T[:i]`, its coefficients one
    slice, the sum over the rows before it a sum over the major axis,
    the 15 steps written out over the list of rows so far (no row is
    written into a block every step rewrites, none still zero is
    read).  The rows of a right-hand side then go block by block as
    they did, each block relieved of the blocks before it (one product)
    and multiplied by its inverse (another, `[block, block] x [block,
    W]`); several right-hand sides go through the same inverses apart,
    never joined and split again.  (The chip's own triangular solve
    multiplies in one bf16 pass; a Neumann product of powers of N
    cancels badly when keys repeat.)"""
    C, b = N.shape[-2], _SOLVE_BLOCK
    assert C % b == 0, (C, b)
    D = jnp.stack([N[..., s:s + b, s:s + b] for s in range(0, C, b)], -3)
    D = jnp.moveaxis(D.reshape((-1, b, b)), 0, -1)          # [i, m, n]
    eye = jnp.eye(b, dtype=N.dtype)[:, :, None]
    rows = [jnp.broadcast_to(eye[0], D.shape[1:])]
    for i in range(1, b):
        rows.append(eye[i] - jnp.sum(D[i, :i, None] * jnp.stack(rows), 0))
    T = jnp.moveaxis(jnp.stack(rows), -1, 0).reshape(
        N.shape[:-2] + (C // b, b, b))

    def blocks(x):
        out = []
        for m, s in enumerate(range(0, C, b)):
            r = x[..., s:s + b, :]
            if out:
                r = r - jnp.einsum("...ri,...iw->...rw", N[..., s:s + b, :s],
                                   jnp.concatenate(out, -2), precision=_HI)
            out.append(jnp.einsum("...ri,...iw->...rw", T[..., m, :, :], r,
                                  precision=_HI))
        return jnp.concatenate(out, -2)

    return [blocks(x) for x in rhs]


def _decayed_products(x: jax.Array, k: jax.Array, G: jax.Array
                      ) -> jax.Array:
    """`out[.., r, i] = sum_c x[.., r, c] k[.., i, c] exp(G[.., r, c] -
    G[.., i, c])` for i <= r and 0 above the diagonal, with one decay a
    channel: x [X, ..., C, dk] (X stacked sets of rows against the same
    keys), k, G [..., C, dk], G the cumulative log-decay inside the
    chunk (non-increasing down the rows).  By sub-blocks of
    `_SOLVE_BLOCK` rows, so that nothing `[C, C, dk]` is built: a
    sub-block of keys, decayed FORWARD to its own last row R, against
    all the rows below it, decayed BACK to R, is one `[rows, dk] x [dk,
    block]` matrix product (both exponents <= 0: i <= R < r); the
    diagonal sub-blocks take the difference itself, masked to i <= r
    before the exponential, at `[block, block, dk]`."""
    X, (C, dk), b = x.shape[0], k.shape[-2:], _SOLVE_BLOCK
    assert C % b == 0, (C, b)
    nb = C // b
    blocks = lambda a: a.reshape(a.shape[:-2] + (nb, b, dk))
    xb, kb, Gb = blocks(x), blocks(k), blocks(G)
    # the diagonal sub-blocks, the X sets' rows one under the other: each
    # exponential then feeds ONE product and is taken inside the
    # reduction (shared between the sets it is written out and read
    # back, 512 MB a layer at a 2048-token insert)
    diff = jnp.concatenate([Gb] * X, -2)[..., :, None, :] \
        - Gb[..., None, :, :]                            # [..,nb,X b,b,dk]
    lower = jnp.tile(jnp.tril(jnp.ones((b, b), bool)), (X, 1))
    diag = jnp.sum(jnp.concatenate(list(xb), -2)[..., :, None, :]
                   * kb[..., None, :, :]
                   * jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf)),
                   -1)
    diag = jnp.stack(jnp.split(diag, X, -2))             # [X,..,nb,b,b]
    G_ref = Gb[..., -1:, :]             # a sub-block's last row R
    k_fwd = kb * jnp.exp(G_ref - Gb)
    strips = []
    for m in range(nb):
        parts = [jnp.zeros(diag.shape[:-3] + (m * b, b), diag.dtype)] \
            if m else []                                 # above the diagonal
        parts.append(diag[..., m, :, :])
        if m + 1 < nb:
            below = slice((m + 1) * b, C)
            back = x[..., below, :] * jnp.exp(G[..., below, :]
                                              - G_ref[..., m, :, :])
            parts.append(jnp.einsum("x...rc,...ic->x...ri", back,
                                    k_fwd[..., m, :, :], precision=_HI))
        strips.append(jnp.concatenate(parts, -2))        # [X,..,C,b]
    return jnp.concatenate(strips, -1)


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, S0: jax.Array,
                n_real: Optional[jax.Array] = None, chunk: int = CHUNK
                ) -> Tuple[jax.Array, jax.Array]:
    """q, k [B, T, H, dk]; g [B, T, H, dk] or [B, T, H, 1] (one decay
    a head); v [B, T, H, dv]; beta [B, T, H]; S0 [B, H, dk, dv]
    float32; n_real [B] or a scalar (None: all T).
    Returns (o [B, T, H, dv] float32, S after token n_real - 1)."""
    f = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta, S0 = f(q), f(k), f(v), f(g), f(beta), f(S0)
    B, T, H, dk = k.shape
    dv = v.shape[-1]
    C = min(chunk, -(-T // _SOLVE_BLOCK) * _SOLVE_BLOCK)     # whole blocks
    Tp = -(-T // C) * C
    real = jnp.arange(Tp)[None, :] < (
        T if n_real is None else jnp.broadcast_to(n_real, (B,))[:, None])
    pad = lambda a: jnp.pad(a, [(0, 0), (0, Tp - T)] + [(0, 0)] * (a.ndim - 2))
    q, k, v, g, beta = pad(q), pad(k), pad(v), pad(g), pad(beta)
    g = jnp.where(real[..., None, None], g, 0.0)
    beta = jnp.where(real[..., None], beta, 0.0)
    N = Tp // C

    def chunks(a):                  # [B, Tp, H, ...] -> [B, N, H, C, ...]
        a = a.reshape((B, N, C) + a.shape[2:])
        return jnp.moveaxis(a, 2, 3)

    q, k, v, g, beta = chunks(q), chunks(k), chunks(v), chunks(g), \
        chunks(beta)
    G = jnp.cumsum(g, axis=-2)                  # [B,N,H,C,dk] (or 1)
    lower = jnp.tril(jnp.ones((C, C), bool))
    if g.shape[-1] == 1:
        # one decay a head comes out of the sum over channels: two
        # [C, dk] x [dk, C] products under the [C, C] decays from row i
        # to row r, masked before the exp
        diff = G[..., :, None, :] - G[..., None, :, :]       # [..,r,i,1]
        decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
        AA = jnp.einsum("x...rc,...ic->x...ri", jnp.stack([k, q]), k,
                        precision=_HI) * decay[..., 0]
    else:
        # rows k (for A) and q (for the outputs) against the decayed keys
        AA = _decayed_products(jnp.stack([k, q]), k, G)
    A, Aq = AA[0], AA[1]                        # [B,N,H,C,C]; Aq: i <= r
    A = jnp.where(jnp.tril(lower, -1), A, 0.0)               # i <  r
    # (I + A Diag(beta)) u = v - (k exp(G)) S_0: solve for both terms
    k_in = k * jnp.exp(G)
    Wv, Wk = _unit_lower_solve(A * beta[..., None, :], v, k_in)
    Aq = Aq * beta[..., None, :]
    q_in = q * jnp.exp(G)
    G_end = G[..., -1:, :]
    k_out = k * jnp.exp(G_end - G) * beta[..., None]         # to chunk's end
    a_end = jnp.exp(G_end[..., 0, :])                        # [B,N,H,dk]

    def one(S, xs):
        Wv, Wk, Aq, q_in, k_out, a_end = xs
        u = Wv - jnp.einsum("bhck,bhkv->bhcv", Wk, S, precision=_HI)
        o = jnp.einsum("bhck,bhkv->bhcv", q_in, S, precision=_HI) \
            + jnp.einsum("bhci,bhiv->bhcv", Aq, u, precision=_HI)
        S = S * a_end[..., None] \
            + jnp.einsum("bhck,bhcv->bhkv", k_out, u, precision=_HI)
        return S, o

    nfirst = lambda a: jnp.moveaxis(a, 1, 0)
    S, o = lax.scan(one, S0, tuple(map(
        nfirst, (Wv, Wk, Aq, q_in, k_out, a_end))))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 2, 3)            # [B,N,C,H,dv]
    return o.reshape(B, Tp, H, dv)[:, :T], S
