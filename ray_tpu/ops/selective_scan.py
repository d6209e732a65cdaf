"""The selective scan of a Mamba layer: a recurrence over a DIAGONAL
state, one `[d_state]` vector a channel.

For each channel c of the layer's `d_inner`, with a state `h[c]`
[d_state] (float32), a token's step `Delta[c]` (> 0, after softplus),
input `x[c]`, and the token's `B`, `C` [d_state] that every channel
shares, under the layer's `A[c]` [d_state] (< 0):

    h[c] = exp(Delta[c] A[c]) * h[c] + (Delta[c] x[c]) B
    y[c] = h[c] . C

(`+ D[c] x[c]` and the `silu(z)` gate are the caller's.)  Nothing mixes
channels, so the state lies with the CHANNELS ON THE LANES: `[d_state,
d_inner / 128, 128]` a slot a layer (`fold`: the channels in whole lane
rows, `d_state` outermost), 327,680 B at 16 x 5120 in float32 with no
padded lane.  With `d_state` minor every `[16]` row would pad to 128
lanes, eight times the bytes.  In that layout a token's `B[n]` and
`C[n]` are SCALARS to the `[d_inner / 128, 128]` slab of state n: they
ride in scalar memory and broadcast for nothing, and `y` is a sum of
slabs, no reduction across lanes or sublanes.

Two situations, each in two forms chosen by backend and shape alone
(`engages`, as `ops/kda.py` chooses):

- one token a slot (the decode tick).  `ssm_step` (plain `jax.numpy`) on
  one layer's rows of all slots, and `ssm_step_live` (a Pallas TPU
  kernel) on the tick's WHOLE stacked state `[layers, slots, d_state,
  R, 128]`, left where it lies in HBM and aliased input to output, for
  the live slots of `ops.kda.live_plan` alone: a trip of its loop starts
  the copy of the next live slot's rows into the other half of a
  double-buffered VMEM scratch, waits for its own, updates the
  `d_state` slabs on the vector unit and starts one copy back.  A live
  slot's state is read once and written once a layer; a dead slot's is
  never copied, computed or written.
- a (padded) sequence from a state handed in (an insert).  `ssm_scan`
  token by token under `lax.scan` (the plain form), or the Pallas kernel
  `_scan_kernel`: a grid over blocks of 1024 channels (8 sublanes x 128
  lanes: each of the `d_state` slabs of a block is ONE vector register)
  and, inside, over tiles of `SCAN_ROWS` rows; a block's state stays in
  registers while the rows of a tile are walked, and in VMEM between
  tiles.  Every row costs `d_state` exponentials and about five
  multiply-adds a register: the vector unit's work, none of the matrix
  unit's (an associative scan would materialise `[rows, d_state,
  d_inner]` float32, 335 MB at 1024 rows, several times over; a
  token-by-token `lax.scan` is 1024 dependent trips of small fusions).
  Rows at and past `n_real` (padding) get `Delta = 0`: decay 1, nothing
  written, so the state handed back is the one after the last REAL row.

All of it float32: the state is the one thing here whose error
compounds over a sequence.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as _attention

LANES = 128
_SUBLANES = 8
SCAN_ROWS = 256     # rows a tile of the insert's kernel


def fold(x: jax.Array) -> jax.Array:
    """[..., C] -> [..., C / 128, 128]: a channel vector in whole lane
    rows, as the state's channels lie."""
    return x.reshape(x.shape[:-1] + (x.shape[-1] // LANES, LANES))


def unfold(x: jax.Array) -> jax.Array:
    return x.reshape(x.shape[:-2] + (x.shape[-2] * LANES,))


def engages(state: jax.Array) -> bool:
    """Whether a state `[.., d_state, R, 128]` goes through the Pallas
    forms: `ops.attention`'s rule for the backend (a TPU always, off TPU
    only when a test forces the interpreter), float32, and channels in
    whole (8, 128) tiles (R % 8: nothing of a slab is padding)."""
    tiles = (state.dtype == jnp.float32 and state.shape[-1] == LANES
             and state.shape[-2] % _SUBLANES == 0)
    return tiles and (_attention._on_tpu()
                      or _attention.FORCE_PALLAS_INTERPRET)


# ---------------------------------------------------------------------------
# One token a slot
# ---------------------------------------------------------------------------

def ssm_step(h: jax.Array, delta: jax.Array, x: jax.Array, b: jax.Array,
             c: jax.Array, a: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """h [B, N, R, 128] float32; delta, x [B, R, 128]; b, c [B, N];
    a [N, R, 128].  Returns (y [B, R, 128] float32, the new h)."""
    f = lambda t: t.astype(jnp.float32)
    h, delta, x, b, c, a = f(h), f(delta), f(x), f(b), f(c), f(a)
    h = jnp.exp(delta[:, None] * a) * h \
        + (delta * x)[:, None] * b[:, :, None, None]
    return jnp.sum(h * c[:, :, None, None], axis=1), h


def _live_kernel(layer_ref, slots_ref, n_ref, bc_ref, d_ref, dx_ref, a_ref,
                 h_in, y_ref, h_out, sbuf, nbuf, sems):
    # h_in and h_out are ONE stack in HBM (aliased): a live slot's rows
    # of layer `layer` are read once from the one and written once
    # through the other, a dead slot's by neither.
    layer, n = layer_ref[0], n_ref[0]
    N = sbuf.shape[1]

    def load(i, half):
        return pltpu.make_async_copy(
            h_in.at[layer, slots_ref[i]], sbuf.at[half], sems.at[0, half])

    def store(i, half):
        return pltpu.make_async_copy(
            nbuf.at[half], h_out.at[layer, slots_ref[i]], sems.at[1, half])

    y_ref[...] = jnp.zeros_like(y_ref)      # a dead slot's row

    @pl.when(n > 0)
    def _():
        load(0, 0).start()

    @pl.loop(0, n)
    def _(i):
        half = i % 2

        @pl.when(i + 1 < n)
        def _():
            load(i + 1, 1 - half).start()

        load(i, half).wait()

        @pl.when(i >= 2)                    # this half's last write-back
        def _():
            store(i - 2, half).wait()

        slot = slots_ref[i]
        d, dx = d_ref[slot], dx_ref[slot]   # [R, 128]
        y = jnp.zeros_like(d)
        for s in range(N):
            h = jnp.exp(d * a_ref[s]) * sbuf[half, s] \
                + dx * bc_ref[slot * 2 * N + s]
            y = y + h * bc_ref[slot * 2 * N + N + s]
            nbuf[half, s] = h
        y_ref[slot] = y
        store(i, half).start()

    for last in (n - 2, n - 1):             # the write-backs in flight
        @pl.when(last >= 0)
        def _():
            store(last, last % 2).wait()


# Jitted so that a tick's call sites trace and lower the kernel once.
@jax.jit
def _step_live(H, layer, d, dx, a, bc, slots, count):
    L, B, N, R, _ = H.shape
    interpret = not _attention._on_tpu()
    block = pltpu.VMEM((2, N, R, LANES), jnp.float32)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    return pl.pallas_call(
        _live_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM), vmem, vmem,
                      vmem, pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(vmem, pl.BlockSpec(memory_space=pl.ANY)),
            scratch_shapes=[block, block, pltpu.SemaphoreType.DMA((2, 2))]),
        out_shape=(jax.ShapeDtypeStruct(d.shape, jnp.float32),
                   jax.ShapeDtypeStruct(H.shape, H.dtype)),
        input_output_aliases={7: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        name="ssm_step",
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), slots, count, bc, d, dx,
      a, H)


def ssm_step_live(H: jax.Array, layer, delta: jax.Array, x: jax.Array,
                  b: jax.Array, c: jax.Array, a: jax.Array, plan
                  ) -> Tuple[jax.Array, jax.Array]:
    """`ssm_step` on layer `layer` of the WHOLE stack H [L, B, N, R,
    128] float32 for the live slots of `plan` = `ops.kda.live_plan(
    active, B)`, in place: (y [B, R, 128] float32, zeros for a dead
    slot; the stack, the same buffer where the caller donates it).  The
    stack stays in HBM; the layer index is a scalar the kernel adds to
    its addresses, never a slice."""
    f = lambda t: t.astype(jnp.float32)
    delta, x = f(delta), f(x)
    bc = jnp.concatenate([f(b), f(c)], axis=1).reshape(-1)
    return _step_live(H, layer, delta, delta * x, f(a), bc, *plan)


# ---------------------------------------------------------------------------
# A sequence from a state
# ---------------------------------------------------------------------------

def _scan_kernel(bc_ref, d_ref, x_ref, a_ref, h0_ref, y_ref, h_ref, *,
                 rows, n_state):
    # grid (channel blocks, row tiles); the block's state lives in the
    # output block h_ref, which every row tile of the block revisits
    tile = pl.program_id(1)

    @pl.when(tile == 0)
    def _():
        h_ref[...] = h0_ref[...]

    a = [a_ref[s] for s in range(n_state)]

    def row(t, h):
        d, x = d_ref[t], x_ref[t]           # [8, 128] each
        dx = d * x
        at = (tile * rows + t) * 2 * n_state
        y = jnp.zeros_like(d)
        new = []
        for s in range(n_state):
            hs = jnp.exp(d * a[s]) * h[s] + dx * bc_ref[at + s]
            y = y + hs * bc_ref[at + n_state + s]
            new.append(hs)
        y_ref[t] = y
        return tuple(new)

    # two rows a trip: the second row's loads and exponentials overlap
    # the first's dependent multiply-adds
    h = lax.fori_loop(0, rows // 2,
                      lambda i, h: row(2 * i + 1, row(2 * i, h)),
                      tuple(h_ref[s] for s in range(n_state)))
    for s in range(n_state):
        h_ref[s] = h[s]


@functools.partial(jax.jit, static_argnames=("rows",))
def _scan_pallas(h0, d, x, bc, a, rows):
    T, R, _ = d.shape
    N = h0.shape[0]
    rows = min(rows, T)
    interpret = not _attention._on_tpu()
    seq = pl.BlockSpec((rows, _SUBLANES, LANES), lambda r, t, *_: (t, r, 0))
    par = pl.BlockSpec((N, _SUBLANES, LANES), lambda r, t, *_: (0, r, 0))
    return pl.pallas_call(
        functools.partial(_scan_kernel, rows=rows, n_state=N),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(R // _SUBLANES, T // rows),
            in_specs=[seq, seq, par, par],
            out_specs=(seq, par)),
        out_shape=(jax.ShapeDtypeStruct(d.shape, jnp.float32),
                   jax.ShapeDtypeStruct(h0.shape, jnp.float32)),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        name="ssm_scan",
    )(bc, d, x, a, h0)


def ssm_scan(h0: jax.Array, delta: jax.Array, x: jax.Array, b: jax.Array,
             c: jax.Array, a: jax.Array, n_real
             ) -> Tuple[jax.Array, jax.Array]:
    """ONE sequence: h0 [N, R, 128] the state before its first row;
    delta, x [T, R, 128]; b, c [T, N]; a [N, R, 128]; the first `n_real`
    rows real.  Returns (y [T, R, 128] float32, the state after row
    `n_real - 1` in h0's dtype)."""
    f = lambda t: t.astype(jnp.float32)
    T = delta.shape[0]
    delta = jnp.where((jnp.arange(T) < n_real)[:, None, None], f(delta), 0.0)
    x, b, c, a = f(x), f(b), f(c), f(a)
    if engages(h0) and T % min(SCAN_ROWS, T) == 0 and T % 2 == 0:
        bc = jnp.concatenate([b, c], axis=1).reshape(-1)
        return _scan_pallas(h0, delta, x, bc, a, SCAN_ROWS)

    def row(h, t):
        d, xt, bt, ct = t
        y, h = ssm_step(h[None], d[None], xt[None], bt[None], ct[None], a)
        return h[0].astype(h0.dtype), y[0]

    h, y = lax.scan(row, h0, (delta, x, b, c))
    return y, h
