"""Grouped matrix product for the experts: `lax.ragged_dot`'s contract,
walked so that a touched expert's matrix is read once and an untouched
one never.

`grouped_matmul(xs [M, K], w [G, K, N], sizes [G]) -> [M, N]`: rows of
`xs` are sorted by group, group g owns `sizes[g]` consecutive rows and
is multiplied by `w[g]`.  Rows past `sizes.sum()` belong to no group and
come back holding anything (`models/moe.py::dropless_moe` masks them).

How it walks.  `plan` (plain XLA, once a `dropless_moe` call, shared by
its three products) cuts the rows into tiles of `tm` and lists the
VISITS: (row tile, group) for every tile a non-empty group has rows in,
in row order.  A group whose rows straddle a tile boundary is visited
once a tile; a tile that several groups share is visited once a group.
The kernel's grid is (column tiles of N) x (visits), the visit count
read from scalar memory, and a step multiplies one row tile `[tm, K]`
by `w[group, :, column tile]` with K WHOLE: no loop over K, no
accumulator scratch, and successive visits of one group name the same
weight block, which the pipeline then does not fetch again.  A visit
stores only the rows of its own group (the first visit of a tile zeroes
the others, a later one keeps what earlier visits stored).

What it costs.  With tens of rows a group or fewer the step is bound by
the copy of the weight block, so the product runs at the bandwidth of
the bytes of the TOUCHED experts; `lax.ragged_dot` on the chip pads
every group to a 512-row tile and is bound by the matrix unit instead
(PERF.md section 6, PR 33).

Precision: operands as they come (bf16), float32 accumulation on the
matrix unit, one rounding to the operands' dtype — `lax.ragged_dot`'s.

`lax.ragged_dot` stays as the reference and as the path wherever the
kernel does not engage (`engages`): off TPU, float32, shapes that do
not tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import attention as _attention

_LANE = 128
_SUBLANE = 16           # rows of a packed bf16 tile


def row_tile(m: int) -> int:
    """Rows a visit: 128, or all of a smaller `m` in whole packed tiles.
    Swept on the chip at the three expert cells' ticks and largest
    inserts, 2 to 256 rows a group (PERF.md section 6, PR 33): a visit
    of up to 128 rows costs the matrix unit one pass over the weight
    block whatever its rows, so the fewest visits win: 16 to 128 tie
    within 1% at the latent cells' ticks (the copy of the block bounds
    them), 128 beats 16 by 11% at 20 rows a group and by 40% at the
    inserts; 256 ties at the inserts and loses 1-6% at the ticks; 512,
    `lax.ragged_dot`'s own, takes 1.4-2 times as long."""
    return min(128, -(-m // _SUBLANE) * _SUBLANE)


def col_tile(k: int, n: int) -> int:
    """Columns a step: the widest divisor of `n` in whole lane rows
    whose weight block `[k, tn]` of bf16 stays under 8 MiB a buffer,
    which is all of `n` at every expert the cells hold (3.1 to 7.3 MB:
    x is then read once and a group's matrix in one contiguous copy;
    blocks of 256 columns lost 7-26%, PERF.md section 6, PR 33).  An
    `n` that is no whole lane rows (1856 = 14.5 of them) has ONE legal
    block, all of it: a block as wide as the array needs no lane
    multiple, any narrower one does."""
    if n % _LANE:
        return n
    best = _LANE
    for tn in range(_LANE, n + 1, _LANE):
        if n % tn == 0 and k * tn * 2 <= 8 * 2 ** 20:
            best = tn
    return best


def _tiles(width: int) -> bool:
    """Whole lane rows; or, wider than one, whole half rows (the tiny
    models' widths of 64 stay with `lax.ragged_dot`)."""
    return width % _LANE == 0 or (width > _LANE and width % (_LANE // 2) == 0)


def engages(m: int, g: int, k: int, n: int, dtype) -> bool:
    """Whether `dropless_moe` runs the kernel for `[m, k] x [g, k, n]`:
    `ops.attention`'s rule for the backend (a TPU always, off TPU only
    when a test forces the interpreter), bf16 operands, and `k` and `n`
    in whole lane rows, or past one lane row in whole HALF rows (`_tiles`:
    an expert width of 1856 = 29 x 64 is walked as one whole-width
    block, the weights as they are stored).  No bound on `m` or `m / g`:
    on a v5e the kernel
    took 0.41 to 0.65 of `lax.ragged_dot`'s time at every shape the
    cells compile, 384 x 128 experts to 16384 x 64 (PERF.md section 6,
    PR 33)."""
    del m, g
    tiles = dtype == jnp.bfloat16 and _tiles(k) and _tiles(n)
    return tiles and (_attention._on_tpu()
                      or _attention.FORCE_PALLAS_INTERPRET)


@functools.partial(jax.jit, static_argnums=(1, 2))
def plan(sizes: jax.Array, m: int, tm: Optional[int] = None):
    """The kernel's scalars for `sizes` [G] over `m` rows in tiles of
    `tm` (`row_tile(m)`, which is what `grouped_matmul` walks them at):
    (visit count [1], then a visit's group, its row tile, and the first
    row and the row past the last of its group; [tiles + G - 1], the
    static bound, zeros past the count).  Sums over comparisons and no
    scan, gather or concatenation: two small fusions a call, 2 us a
    layer in the traced ticks (PERF.md section 6, PR 33)."""
    tm = tm or row_tile(m)
    g = sizes.shape[0]
    sizes = sizes.astype(jnp.int32)
    upto_incl = jnp.arange(g)[:, None] >= jnp.arange(g)[None, :]
    ends = jnp.where(upto_incl, sizes[None, :], 0).sum(-1)
    starts = ends - sizes
    first = starts // tm
    visits = jnp.where(sizes > 0, -(-ends // tm) - first, 0)
    upto = jnp.where(upto_incl, visits[None, :], 0).sum(-1)
    before = upto - visits
    v = jnp.arange(-(-m // tm) + g - 1, dtype=jnp.int32)[:, None]
    mine = (v >= before[None, :]) & (v < upto[None, :])     # [V, G]

    def pick(of_group):
        return jnp.where(mine, of_group, 0).sum(-1).astype(jnp.int32)

    return (upto[-1:].astype(jnp.int32),
            pick(jnp.arange(g, dtype=jnp.int32)[None, :]),
            pick(first[None, :] + v - before[None, :]),
            pick(starts[None, :]), pick(ends[None, :]))


def _kernel(n_ref, group_ref, tile_ref, lo_ref, hi_ref, *refs, tm,
            by_rows=False):
    # refs: x, w, o; in front of them the layer's index where w is a
    # stack of banks (only the index maps read it)
    del n_ref, group_ref
    x_ref, w_ref, o_ref = refs[-3:]
    v = pl.program_id(1)
    t = tile_ref[v]
    row = t * tm + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
    own = (row >= lo_ref[v]) & (row < hi_ref[v])
    if by_rows:     # the block is [tn, k]: both operands' lanes contract
        y = lax.dot_general(x_ref[...], w_ref[...], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    else:
        y = jnp.dot(x_ref[...], w_ref[...],
                    preferred_element_type=jnp.float32)
    y = y.astype(o_ref.dtype)
    fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)

    @pl.when(fresh)
    def _():
        o_ref[...] = jnp.where(own, y, jnp.zeros_like(y))

    @pl.when(jnp.logical_not(fresh))
    def _():
        o_ref[...] = jnp.where(own, y, o_ref[...])


def _call(xs, w, scalars, tm, tn, by_rows=False):
    m, k = xs.shape
    n = w.shape[-2] if by_rows else w.shape[-1]
    interpret = not _attention._on_tpu()
    # a group's block of w: [tn, k] of its rows, or [k, tn] of its columns
    block = (None, tn, k) if by_rows else (None, k, tn)
    at = (lambda g, j: (g, j, 0)) if by_rows else (lambda g, j: (g, 0, j))
    if w.ndim == 4:     # a stack of banks: the sixth scalar is the layer
        block = (None,) + block
        w_map = lambda j, v, n_, grp, til, lo, hi, lay: \
            (lay[0],) + at(grp[v], j)
    else:
        w_map = lambda j, v, n_, grp, til, lo, hi: at(grp[v], j)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, by_rows=by_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            grid=(n // tn, scalars[0][0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, n_, grp, til, *_:
                             (til[v], 0)),
                pl.BlockSpec(block, w_map),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda j, v, n_, grp, til, *_: (til[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        name="grouped_matmul",
    )(*scalars, xs, w)


def ragged(xs, w, sizes, by_rows=False):
    """`lax.ragged_dot`, the reference and the path wherever the kernel
    does not engage; `by_rows` as `grouped_matmul` takes it."""
    return lax.ragged_dot(xs, jnp.swapaxes(w, 1, 2) if by_rows else w, sizes)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _product(xs, w, sizes, scalars, tm, tn, by_rows=False):
    return _call(xs, w, scalars, tm, tn, by_rows)


def _product_fwd(xs, w, sizes, scalars, tm, tn, by_rows=False):
    return _call(xs, w, scalars, tm, tn, by_rows), (xs, w, sizes)


def _product_bwd(tm, tn, by_rows, res, ct):
    # nobody differentiates the serving programs; a derivative that is
    # asked for is `lax.ragged_dot`'s, not silently something else
    xs, w, sizes = res
    if w.ndim == 4:
        raise NotImplementedError("no derivative through a stack of banks")
    _, vjp = jax.vjp(lambda a, b: ragged(a, b, sizes, by_rows), xs, w)
    return (*vjp(ct), None, None)


_product.defvjp(_product_fwd, _product_bwd)
# Under `jit`, so that a program of 36 products traces and lowers the
# kernel once a shape, not once a call site: the call sites of one
# shape share one function of the lowered module (8 s of a warm
# set-up otherwise, PERF.md section 6, PR 33).  XLA inlines it.
_jit_product = jax.jit(_product, static_argnums=(4, 5, 6))


def grouped_matmul(xs: jax.Array, w: jax.Array, sizes: jax.Array,
                   scalars=None, by_rows: bool = False,
                   layer: Optional[jax.Array] = None) -> jax.Array:
    """`lax.ragged_dot(xs, w, sizes)` through the kernel.  `scalars` =
    `plan(sizes, M)`, computed here when the caller has none to share.

    `by_rows`: w is [G, N, K], a group's matrix with its OUTPUT
    channels down the rows (as a checkpoint stores a projection), and
    the product is `xs w[g]^T`: both operands contract over their lanes,
    which the matrix unit takes as it takes the other form.  For an N
    that is no whole lane rows this is the form that stores no padding:
    `[G, K, 1856]` lies in HBM with every row padded to 1920 lanes, and
    the deviceless v5e compile of the kernel over it copies the whole
    bank into that layout first (330 MB a product at 32 x 2688 x 1856);
    `[G, 1856, K]` lies as published.

    `layer`: w is a STACK of banks [L, G, ..] (the layers of a
    `lax.scan`) and the product is bank `layer`'s (traced).  The stack
    stays whole in HBM and the index is one more scalar the kernel adds
    to its block addresses, never a slice: cut out by the scan, a bank
    of 320 MB was COPIED for each product of each layer of each tick
    (1.9 ms a copy, twelve a tick: my chip runs, PR 52)."""
    m, k = xs.shape
    if scalars is None:
        scalars = plan(sizes, m)
    if layer is not None:
        scalars = (*scalars, jnp.reshape(layer, (1,)).astype(jnp.int32))
    n = w.shape[-2] if by_rows else w.shape[-1]
    return _jit_product(xs, w, sizes, scalars, row_tile(m), col_tile(k, n),
                        by_rows)
