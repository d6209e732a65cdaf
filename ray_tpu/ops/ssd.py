"""The state-space duality layer of a Mamba-2 block: a recurrence over a
state of ONE decay a head, `B` and `C` shared by a group of heads.

For each head h of H, with a state `S_h` [N, P] (float32; N the state
size, P the head's channels), a token's step `dt_h` (> 0, after
softplus), input `x_h` [P], and the token's `B_g`, `C_g` [N] of the
head's GROUP g = h // (H / G), under the head's `A_h` (< 0):

    S_h = exp(dt_h A_h) S_h + B_g (dt_h x_h)^T
    y_h = S_h^T C_g

(`+ D_h x_h`, the gate and the group norm are the caller's.)  The state
is written `[N, P]`, the transpose of the published `[P, N]`: a head of
64 channels is half a lane row, so two heads lie SIDE BY SIDE in the
lanes (`ops.kda.pack`: `[H / 2, N, 2 P]`, 128 x 128 float32 a pair with
no padded lane, 2,097,152 B a slot a layer at 64 heads), `B` and `C`
multiply the state's ROWS (columns `[N, 1]`, broadcast along the lanes
once a group of four pairs), `dt x` and the decay are `[1, 2 P]` rows
over the lanes, and `y` is a sum down the sublanes: adds of whole
registers, no reduction across lanes.

Two situations:

- one token a slot (the decode tick).  `ssd_step` (plain `jax.numpy`) on
  one layer's rows of all slots, and, where `engages` says so (backend,
  dtype and shape alone), `ssd_step_live`, a Pallas TPU kernel over the
  tick's WHOLE stacked state `[layers, slots, H / 2, N, 2 P]`, left
  where it lies in HBM and aliased input to output, for the live slots
  of `ops.kda.live_plan` alone, as `ops.kda.kda_step_live` walks them:
  a trip starts the copy of the next live slot's rows into the other
  half of a double-buffered VMEM scratch, waits for its own, updates
  pair by pair on the vector unit and starts one copy back.  A live
  slot's state is read once and written once a layer (4 MiB of traffic
  against 2 M multiply-adds: the bandwidth's roofline); a dead slot's
  is never copied, computed or written.
- a (padded) sequence from a state handed in (an insert).  `ssd_chunked`,
  the matrix form over chunks of `CHUNK` rows, plain XLA products on
  every backend: with `l_t = cumsum(dt_t A)` inside a chunk,
  `Y_intra = ((C B^T) * exp(l_i - l_j) [i >= j]) (dt X)` where `C B^T`
  is ONE `[Q, Q]` product a group that its heads share, the chunk's own
  state `sum_j exp(l_Q - l_j) B_j (dt_j X_j)^T`, states handed from
  chunk to chunk under the decay `exp(l_Q)` (a `lax.scan` over the
  chunks, which also adds `Y_inter = exp(l_i) S_in^T C_i`).  The decay
  differences are masked to `i >= j` BEFORE the exponential, where they
  are <= 0.  Rows at and past `n_real` (padding) get `dt = 0`: decay 1,
  nothing written, so the state handed back is the one after the last
  REAL row.  This is the arithmetic of the one-decay-a-head arm of
  `ops.kda.kda_chunked` (a `[C, C]` product under `[C, C]` decays), not
  of `ops.kda._decayed_products` (that is for a decay a CHANNEL, which
  cannot come out of the sum); the arm is three lines inline there and
  its products are a head's, not a group's, so they are written here.

All of it float32 with float32 matrix products (`Precision.HIGHEST`):
about 3.4 MFLOP a token a layer, 4% of the layer's projections, and the
state is the one thing here whose error compounds over a sequence.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as _attention
from ray_tpu.ops import kda

_HI = lax.Precision.HIGHEST
CHUNK = 128     # rows a chunk of the insert's form (the published chunk_size)
_LANE = 128
_SUBLANE = 8


heads_a_row = kda.heads_a_row       # 2 at heads of 64


def engages(state: jax.Array) -> bool:
    """Whether a decode tick steps a state stack `[.., H / p, N, p P]`
    through `ssd_step_live`: `ops.kda.engages`' rule (a TPU always, off
    TPU only when a test forces the interpreter; float32; rows as they
    lie in whole (8, 128) tiles)."""
    return kda.engages(state.shape[-2], state.shape[-1], state.dtype)


def _per_head(bc: jax.Array, heads: int) -> jax.Array:
    """[..., G, N] -> [..., H, N]: head h reads group h // (H / G)."""
    return jnp.repeat(bc, heads // bc.shape[-2], axis=-2)


# ---------------------------------------------------------------------------
# One token a slot
# ---------------------------------------------------------------------------

def ssd_step(S: jax.Array, x: jax.Array, dt: jax.Array, A: jax.Array,
             Bm: jax.Array, Cm: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """S [B, H, N, P] float32; x [B, H, P]; dt [B, H]; A [H]; Bm, Cm
    [B, G, N].  Returns (y [B, H, P] float32, the new S)."""
    f = lambda t: t.astype(jnp.float32)
    S, x, dt, A, Bm, Cm = f(S), f(x), f(dt), f(A), f(Bm), f(Cm)
    H = x.shape[1]
    a = jnp.exp(dt * A)
    S = a[..., None, None] * S \
        + _per_head(Bm, H)[..., None] * (dt[..., None] * x)[..., None, :]
    return jnp.sum(S * _per_head(Cm, H)[..., None], axis=-2), S


def _live_kernel(layer_ref, slots_ref, n_ref, rows_ref, cols_ref, s_in,
                 y_ref, s_out, rbuf, cbuf, sbuf, nbuf, sems, *, groups):
    # s_in and s_out are ONE stack in HBM (aliased): a live slot's rows
    # of layer `layer` are read once from the one and written once
    # through the other, a dead slot's by neither.
    layer, n = layer_ref[0], n_ref[0]
    pairs = sbuf.shape[1]               # rows of the stack a slot: H / p
    per = pairs // groups               # of them a group of B and C

    def loads(i, half):
        b = slots_ref[i]
        return (pltpu.make_async_copy(s_in.at[layer, b], sbuf.at[half],
                                      sems.at[0, half]),
                pltpu.make_async_copy(rows_ref.at[b], rbuf.at[half],
                                      sems.at[1, half]),
                pltpu.make_async_copy(cols_ref.at[b], cbuf.at[half],
                                      sems.at[2, half]))

    def store(i, half):
        return pltpu.make_async_copy(
            nbuf.at[half], s_out.at[layer, slots_ref[i]], sems.at[3, half])

    y_ref[...] = jnp.zeros_like(y_ref)      # a dead slot's row

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
        cols = cbuf[half]                   # [N, lanes]: B's G columns, C's
        for g in range(groups):
            shape = sbuf.shape[2:]
            bcol = jnp.broadcast_to(cols[:, g:g + 1], shape)
            ccol = jnp.broadcast_to(cols[:, groups + g:groups + g + 1], shape)
            for j in range(g * per, (g + 1) * per):
                a = rbuf[half, 0, pl.ds(j, 1), :]           # [1, p P]
                dx = rbuf[half, 1, pl.ds(j, 1), :]
                new = sbuf[half, j] * a + bcol * dx
                nbuf[half, j] = new
                y_ref[b, pl.ds(j, 1), :] = jnp.sum(new * ccol, axis=0,
                                                   keepdims=True)
        store(i, half).start()

    for last in (n - 2, n - 1):             # the write-backs in flight
        @pl.when(last >= 0)
        def _():
            store(last, last % 2).wait()


# Jitted so that a tick's call sites trace and lower the kernel once.
@functools.partial(jax.jit, static_argnames=("groups",))
def _step_live(S, layer, rows, cols, slots, count, groups):
    L, B, R, N, W = S.shape             # R rows of p heads, W = p P
    interpret = not _attention._on_tpu()
    block = pltpu.VMEM((2, R, N, W), jnp.float32)
    return pl.pallas_call(
        functools.partial(_live_kernel, groups=groups),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY)] * 3,
            out_specs=(pl.BlockSpec(memory_space=pltpu.VMEM),
                       pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[pltpu.VMEM((2,) + rows.shape[1:], jnp.float32),
                            pltpu.VMEM((2,) + cols.shape[1:], jnp.float32),
                            block, block, pltpu.SemaphoreType.DMA((4, 2))]),
        out_shape=(jax.ShapeDtypeStruct((B, R, W), jnp.float32),
                   jax.ShapeDtypeStruct(S.shape, S.dtype)),
        input_output_aliases={5: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        name="ssd_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, count, rows, cols,
      S)


def ssd_step_live(S: jax.Array, layer, x: jax.Array, dt: jax.Array,
                  A: jax.Array, Bm: jax.Array, Cm: jax.Array, plan
                  ) -> Tuple[jax.Array, jax.Array]:
    """`ssd_step` on layer `layer` of the WHOLE stack S [L, B, H / p, N,
    p P] float32 (`ops.kda.pack`: p = `heads_a_row(H, P)` heads a row)
    for the live slots of `plan` = `ops.kda.live_plan(active, B)`, in
    place: (y [B, H, P] float32, zeros for a dead slot; the stack, the
    same buffer where the caller donates it).  x [B, H, P]; dt [B, H];
    A [H]; Bm, Cm [B, G, N].  The stack stays in HBM; the layer index
    is a scalar the kernel adds to its addresses, never a slice."""
    f = lambda t: t.astype(jnp.float32)
    x, dt, A, Bm, Cm = f(x), f(dt), f(A), f(Bm), f(Cm)
    B, H, P = x.shape
    R, G = S.shape[2], Bm.shape[1]
    if R % G:
        raise ValueError(f"{R} rows of the stack do not divide over {G} "
                         "groups of B and C")
    a = jnp.exp(dt * A)
    # a row of the stack's lanes: its p heads' decays, and their dt x
    rows = jnp.stack([jnp.repeat(a, P, axis=-1).reshape(B, R, -1),
                      (dt[..., None] * x).reshape(B, R, -1)], axis=1)
    if R % _SUBLANE:                        # whole sublane tiles for the copy
        rows = jnp.pad(rows, [(0, 0), (0, 0), (0, -R % _SUBLANE), (0, 0)])
    # B's and C's vectors over N as COLUMNS (N down the sublanes, as the
    # state's rows lie), the groups side by side in the lanes
    cols = jnp.swapaxes(jnp.concatenate([Bm, Cm], axis=1), 1, 2)
    if cols.shape[-1] % _LANE:              # whole lane rows likewise
        cols = jnp.pad(cols, [(0, 0), (0, 0), (0, -cols.shape[-1] % _LANE)])
    y, S = _step_live(S, layer, rows, cols, *plan, groups=G)
    return y.reshape(B, H, P), S


# ---------------------------------------------------------------------------
# A sequence from a state
# ---------------------------------------------------------------------------

def ssd_chunked(x: jax.Array, dt: jax.Array, A: jax.Array, Bm: jax.Array,
                Cm: jax.Array, S0: jax.Array,
                n_real: Optional[jax.Array] = None, chunk: int = CHUNK
                ) -> Tuple[jax.Array, jax.Array]:
    """x [B, T, H, P]; dt [B, T, H]; A [H]; Bm, Cm [B, T, G, N]; S0
    [B, H, N, P] float32; n_real [B] or a scalar (None: all T).
    Returns (y [B, T, H, P] float32, S after row n_real - 1)."""
    f = lambda t: t.astype(jnp.float32)
    x, dt, A, Bm, Cm, S0 = f(x), f(dt), f(A), f(Bm), f(Cm), f(S0)
    B, T, H, P = x.shape
    G, N = Bm.shape[-2:]
    r = H // G
    Q = min(chunk, -(-T // _SUBLANE) * _SUBLANE)
    Tp = -(-T // Q) * Q
    real = jnp.arange(Tp)[None, :] < (
        T if n_real is None else jnp.broadcast_to(n_real, (B,))[:, None])
    pad = lambda t: jnp.pad(t, [(0, 0), (0, Tp - T)] + [(0, 0)] * (t.ndim - 2))
    x, dt, Bm, Cm = pad(x), pad(dt), pad(Bm), pad(Cm)
    dt = jnp.where(real[..., None], dt, 0.0)
    Nc = Tp // Q

    def chunks(t):          # [B, Tp, heads, ...] -> [B, Nc, heads, Q, ...]
        return jnp.moveaxis(t.reshape((B, Nc, Q) + t.shape[2:]), 2, 3)

    x, dt, Bm, Cm = chunks(x), chunks(dt), chunks(Bm), chunks(Cm)
    l = jnp.cumsum(dt * A[:, None], axis=-1)                # [B,Nc,H,Q]
    grouped = lambda t: t.reshape((B, Nc, G, r) + t.shape[3:])
    dx = grouped(dt[..., None] * x)                         # [B,Nc,G,r,Q,P]
    lower = jnp.tril(jnp.ones((Q, Q), bool))
    # the decay from row j to row i of a chunk, masked before the exp
    diff = l[..., :, None] - l[..., None, :]                # [B,Nc,H,i,j]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    cb = jnp.einsum("bcgin,bcgjn->bcgij", Cm, Bm, precision=_HI)
    y = jnp.einsum("bcgrij,bcgrjp->bcgrip",
                   cb[:, :, :, None] * grouped(decay), dx, precision=_HI)
    l_end = l[..., -1:]                                     # [B,Nc,H,1]
    own = jnp.einsum("bcgjn,bcgrjp->bcgrnp", Bm,
                     grouped(jnp.exp(l_end - l))[..., None] * dx,
                     precision=_HI)
    a_end = grouped(jnp.exp(l_end[..., 0]))                 # [B,Nc,G,r]
    into = grouped(jnp.exp(l))                              # [B,Nc,G,r,Q]

    def one(S, xs):
        own, a_end, into, Cm = xs
        yi = jnp.einsum("bgin,bgrnp->bgrip", Cm, S, precision=_HI) \
            * into[..., None]
        return S * a_end[..., None, None] + own, yi

    nfirst = lambda t: jnp.moveaxis(t, 1, 0)
    S, yi = lax.scan(one, S0.reshape(B, G, r, N, P),
                     tuple(map(nfirst, (own, a_end, into, Cm))))
    y = y + jnp.moveaxis(yi, 0, 1)                          # [B,Nc,G,r,Q,P]
    y = jnp.moveaxis(y.reshape(B, Nc, H, Q, P), 2, 3)
    return y.reshape(B, Tp, H, P)[:, :T], S.reshape(B, H, N, P)
