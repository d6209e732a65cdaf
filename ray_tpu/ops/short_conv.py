"""The short causal depthwise convolution over time that recurrent and
gated-convolution layers put in front of (or in place of) their mixer:
`y_t = sum_k w[k] * x_{t - (K-1) + k}`, one tap a channel, `w[K-1]` on
the current row, zeros before the sequence.  The width `K` is the
weights' first axis (4 in `models/kimi_linear.py`, 3 in
`models/conv_moe.py`).

What a sequence carries between calls is its last `K - 1` input rows
(`tail`).  The engine keeps them a row a slot a layer, `[L', B,
(K-1) C]`: tap `k` of a slot in lanes `k C .. (k+1) C` of that slot's
ONE row (`flat` / `rows` re-lay a `[..., K-1, C]` tail to that and
back).  Laid `[L', B, K-1, C]` the 3 rows of a slot were the
second-minor axis of a tile that holds 8 (bf16: 16), the compiler
wanted them elsewhere inside a layer loop and re-laid the whole stack
at both ends of every tick (PERF.md section 6, PR 60).

`short_conv` (a whole sequence, inserts) takes the rows before its
first token as `[B, K-1, C]` and hands back those before token `n_real`
(so a padded bucket's tail is taken after the last REAL token);
`step_in_place` (one token a slot, the tick) shifts a layer's rows of
the stack where they lie.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as _attention

_LANES = 128
_SUBLANES = 8
_BLOCK_BYTES = 2 ** 19


def short_conv(x: jax.Array, w: jax.Array, tail: jax.Array,
               n_real: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """x [B, T, C] rows at t = 0.., w [K, C] (w[K-1] multiplies the
    current row), tail [B, K-1, C] the rows before t = 0.  Returns
    (y [B, T, C], the K-1 rows before t = n_real [B])."""
    K = w.shape[0]
    T = x.shape[1]
    xx = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    y = sum(xx[:, j:j + T] * w[j].astype(x.dtype) for j in range(K))
    new_tail = jax.vmap(lambda rows, n: lax.dynamic_slice_in_dim(
        rows, n, K - 1, axis=0))(xx, jnp.broadcast_to(n_real, x.shape[:1]))
    return y, new_tail


def flat(tail: jax.Array) -> jax.Array:
    """[..., K-1, C] -> [..., (K-1) C]: a tail as the engine keeps it."""
    return tail.reshape(tail.shape[:-2] + (-1,))


def rows(tail: jax.Array, w: jax.Array) -> jax.Array:
    """[..., (K-1) C] -> [..., K-1, C] for weights w [K, C]."""
    return tail.reshape(tail.shape[:-1] + (w.shape[0] - 1, w.shape[1]))


def _shifted(row, x, w, live):
    """One step on rows `[b, (K-1) C]` and x `[b, C]`, w `[K, C]`, live
    `[b, 1]` bool or None (all): (y [b, C] in x's dtype, the rows with
    x behind them and their oldest tap gone where live).  K
    multiply-adds of lane slices, float32, rounded once; the kernel's
    body and the plain form both."""
    K, C = w.shape
    f32 = jnp.float32
    taps = w.astype(x.dtype).astype(f32)
    y = 0.0
    for k in range(K - 1):
        y += row[:, k * C:(k + 1) * C].astype(x.dtype).astype(f32) * taps[k]
    y += x.astype(f32) * taps[K - 1]
    new = jnp.concatenate([row[:, C:], x.astype(row.dtype)], axis=1)
    if live is not None:
        new = jnp.where(live, new, row)
    return y.astype(x.dtype), new


def _tile_rows(itemsize: int) -> int:
    """Rows of a sublane tile: 8 of 32 bits, 16 of bf16."""
    return _SUBLANES * 4 // itemsize


def engages(tails: jax.Array, w: jax.Array) -> bool:
    """Whether the step over `tails` goes through the Pallas kernel:
    `ops.attention`'s rule for the backend (a TPU always, off TPU only
    when a test forces the interpreter), taps that start on a lane tile
    (C % 128) and slots in whole sublane tiles of the tail's dtype.
    Everywhere else the same arithmetic in plain `jax.numpy`: on the
    chip that compiles to two reads of a layer's rows and a staging
    copy, not one read and one write (PERF.md section 6, PR 60)."""
    return (w.shape[1] % _LANES == 0
            and tails.shape[1] % _tile_rows(tails.dtype.itemsize) == 0
            and (_attention._on_tpu() or _attention.FORCE_PALLAS_INTERPRET))


def _block_rows(B: int, W: int, itemsize: int) -> int:
    """Slots a kernel block: whole sublane tiles that divide B, of
    `_BLOCK_BYTES` at most and an eighth of the slots at most (the
    blocks of a layer are pipelined: a block in and one out in flight
    while one is shifted; on the chip 0.25-2 MB blocks read alike and
    2-4 blocks a layer 10% slower, PR 60)."""
    tile = _tile_rows(itemsize)
    most = min(_BLOCK_BYTES // (W * itemsize), B // 8)
    return max(b for b in range(tile, max(tile, most) + 1, tile)
               if B % b == 0)


def _step_kernel(j_ref, tails_ref, x_ref, w_ref, live_ref, y_ref, out_ref):
    del j_ref                           # the index maps read it
    y_ref[...], out_ref[0] = _shifted(
        tails_ref[0], x_ref[...], w_ref[...], live_ref[...] != 0)


# Jitted so that a tick's call sites trace and lower the kernel once.
# The stack is held to HBM (`pltpu.HBM`, which the aliased input takes
# too): left free, the compiler staged a stack that fits fast memory
# there WHOLE around every call (`think`'s 26 MB, in and out a layer).
@jax.jit
def _step_pallas(tails, j, x, w, live):
    _, B, W = tails.shape
    K, C = w.shape
    b = _block_rows(B, W, tails.dtype.itemsize)
    interpret = not _attention._on_tpu()
    layer_rows = pl.BlockSpec((1, b, W), lambda i, j: (j[0], i, 0))
    slot_rows = lambda n: pl.BlockSpec((b, n), lambda i, j: (i, 0))
    return pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B // b,),
            in_specs=[layer_rows, slot_rows(C),
                      pl.BlockSpec((K, C), lambda i, j: (0, 0)),
                      slot_rows(1)],
            out_specs=(slot_rows(C), layer_rows)),
        out_shape=(jax.ShapeDtypeStruct(x.shape, x.dtype),
                   pltpu.HBM(tails.shape, tails.dtype)),
        input_output_aliases={1: 1},
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        name="short_conv_step",
    )(jnp.reshape(j, (1,)).astype(jnp.int32), tails, x, w, live)


def step_in_place(tails: jax.Array, j, x: jax.Array, w: jax.Array,
                  active: Optional[jax.Array] = None
                  ) -> Tuple[jax.Array, jax.Array]:
    """One row a slot through layer `j` of the WHOLE stack: tails
    [L', B, (K-1) C], j a static or traced index, x [B, C], w [K, C],
    active [B] bool or None (all) -> (y [B, C], the stack, the same
    buffer where the caller donates or carries it).  A live slot's row
    becomes `[row[C:] | x]`, a dead slot keeps its; `y` is computed for
    both.  By backend and shape alone (`engages`) a layer's rows are
    read once and written once by the Pallas kernel, blocks of slots at
    a time, the layer index a scalar in its index maps; or they go back
    by one `dynamic_update_slice`."""
    if engages(tails, w):
        live = jnp.ones(x.shape[:1], jnp.int32) if active is None \
            else active.astype(jnp.int32)
        return _step_pallas(tails, j, x, w, live[:, None])
    row = lax.dynamic_index_in_dim(tails, j, 0, keepdims=False)
    y, new = _shifted(row, x, w, None if active is None else active[:, None])
    return y, lax.dynamic_update_index_in_dim(tails, new, j, 0)
