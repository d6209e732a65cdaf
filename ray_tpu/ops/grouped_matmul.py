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
    blocks of 256 columns lost 7-26%, PERF.md section 6, PR 33)."""
    best = _LANE
    for tn in range(_LANE, n + 1, _LANE):
        if n % tn == 0 and k * tn * 2 <= 8 * 2 ** 20:
            best = tn
    return best


def engages(m: int, g: int, k: int, n: int, dtype) -> bool:
    """Whether `dropless_moe` runs the kernel for `[m, k] x [g, k, n]`:
    `ops.attention`'s rule for the backend (a TPU always, off TPU only
    when a test forces the interpreter), bf16 operands, and `k` and `n`
    in whole lane rows.  No bound on `m` or `m / g`: on a v5e the kernel
    took 0.41 to 0.65 of `lax.ragged_dot`'s time at every shape the
    cells compile, 384 x 128 experts to 16384 x 64 (PERF.md section 6,
    PR 33)."""
    del m, g
    tiles = (dtype == jnp.bfloat16 and k % _LANE == 0 and n % _LANE == 0)
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


def _kernel(n_ref, group_ref, tile_ref, lo_ref, hi_ref, x_ref, w_ref,
            o_ref, *, tm):
    del n_ref, group_ref
    v = pl.program_id(1)
    t = tile_ref[v]
    row = t * tm + lax.broadcasted_iota(jnp.int32, o_ref.shape, 0)
    own = (row >= lo_ref[v]) & (row < hi_ref[v])
    y = jnp.dot(x_ref[...], w_ref[...],
                preferred_element_type=jnp.float32).astype(o_ref.dtype)
    fresh = (v == 0) | (tile_ref[jnp.maximum(v - 1, 0)] != t)

    @pl.when(fresh)
    def _():
        o_ref[...] = jnp.where(own, y, jnp.zeros_like(y))

    @pl.when(jnp.logical_not(fresh))
    def _():
        o_ref[...] = jnp.where(own, y, o_ref[...])


def _call(xs, w, scalars, tm, tn):
    m, k = xs.shape
    n = w.shape[2]
    interpret = not _attention._on_tpu()
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tn, scalars[0][0]),
            in_specs=[
                pl.BlockSpec((tm, k),
                             lambda j, v, n_, grp, til, lo, hi: (til[v], 0)),
                pl.BlockSpec((None, k, tn),
                             lambda j, v, n_, grp, til, lo, hi:
                             (grp[v], 0, j)),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn),
                lambda j, v, n_, grp, til, lo, hi: (til[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), xs.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        name="grouped_matmul",
    )(*scalars, xs, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _product(xs, w, sizes, scalars, tm, tn):
    return _call(xs, w, scalars, tm, tn)


def _product_fwd(xs, w, sizes, scalars, tm, tn):
    return _call(xs, w, scalars, tm, tn), (xs, w, sizes)


def _product_bwd(tm, tn, res, ct):
    # nobody differentiates the serving programs; a derivative that is
    # asked for is `lax.ragged_dot`'s, not silently something else
    xs, w, sizes = res
    _, vjp = jax.vjp(lambda a, b: lax.ragged_dot(a, b, sizes), xs, w)
    return (*vjp(ct), None, None)


_product.defvjp(_product_fwd, _product_bwd)
# Under `jit`, so that a program of 36 products traces and lowers the
# kernel once a shape, not once a call site: the call sites of one
# shape share one function of the lowered module (8 s of a warm
# set-up otherwise, PERF.md section 6, PR 33).  XLA inlines it.
_jit_product = jax.jit(_product, static_argnums=(4, 5))


def grouped_matmul(xs: jax.Array, w: jax.Array, sizes: jax.Array,
                   scalars=None) -> jax.Array:
    """`lax.ragged_dot(xs, w, sizes)` through the kernel.  `scalars` =
    `plan(sizes, M)`, computed here when the caller has none to share."""
    m, k = xs.shape
    if scalars is None:
        scalars = plan(sizes, m)
    return _jit_product(xs, w, sizes, scalars, row_tile(m),
                        col_tile(k, w.shape[2]))
