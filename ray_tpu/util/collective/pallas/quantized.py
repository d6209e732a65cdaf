"""EQuARX-style quantized ring allreduce.

PAPERS.md ("EQuARX: Efficient Quantized AllReduce in XLA") shows that on
slow links an int8 allreduce with per-block scales buys ~2x wire time for a
small accuracy cost.  Here every ring hop re-quantizes the outgoing chunk
(a running f32 partial sum) to int8 with one f32 scale, the wire carries
`chunk/4` the bytes, and the receiver dequantizes into its f32 accumulator.
Error therefore grows with hop count, not ring size squared — each hop
contributes at most ``max|chunk| / 254`` per element (symmetric
round-to-nearest, 8 bits).

The wire leg is the same remote-copy kernel as the exact ring
(`ring._permute_block`, HBM to HBM): quantize and dequantize are XLA
fusions on either side of it, and the scale rides in a trailing int8 tile
of the payload so a hop stays one DMA.  The requantization of running
partial sums therefore still happens per hop and the wire only ever
carries int8.

Fallback ladder (mirrors `ring.select_impl`):

- non-float input → `TypeError` (quantizing integer grads is a bug; the
  graftlint `collective-consistency` pass flags call sites that try);
- f64 input, tiny tensors, or ``precision="bf16"`` → bf16-compressed
  allreduce (cast → ring/lax allreduce → cast back);
- off-TPU with interpret disabled → bf16 cast around `lax.psum`.
"""

from __future__ import annotations

import os

import jax.numpy as jnp
from jax import lax

from ray_tpu.util.collective.pallas import ring
from ray_tpu.util.collective.pallas.ring import (
    LANES, SplitPhaseHandle, _COMBINE, _ag_hop, _check_divisible, _chunk,
    _from_block, _rs_hop, _sender, _slabs_to_block, _to_block, select_impl,
)

# Below this many elements the scale traffic dominates any wire savings.
_MIN_QUANT_ELEMS = int(os.environ.get("RAY_TPU_QAR_MIN_ELEMS", "1024"))
_QMAX = 127.0
_add = _COMBINE["sum"]
# One int8 tile (32 sublanes) appended to the payload carries the scale.
_SCALE_ROWS = 32


def _quantize(chunk):
    scale = jnp.maximum(jnp.max(jnp.abs(chunk)) / _QMAX, 1e-30)
    q = jnp.clip(jnp.round(chunk / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def _quantized_sender(axis_name, n, impl):
    """`send(chunk)` for the ring hop schedules: quantize the outgoing f32
    chunk, move int8 payload + scale to the right neighbour in one hop,
    dequantize what arrived from the left."""
    send = _sender(axis_name, n, impl)

    def qsend(chunk):
        q, scale = _quantize(chunk)
        scale_bytes = lax.bitcast_convert_type(scale, jnp.int8)  # (4,)
        tile = jnp.zeros((_SCALE_ROWS * LANES,), jnp.int8)
        tile = tile.at[:4].set(scale_bytes).reshape(_SCALE_ROWS, LANES)
        got = send(jnp.concatenate([q, tile], axis=0))
        rows = chunk.shape[0]
        scale_in = lax.bitcast_convert_type(got[rows, :4], jnp.float32)
        return got[:rows].astype(chunk.dtype) * scale_in

    return qsend


def start_quantized_ring_reduce_scatter(x, axis_name: str, *, n: int,
                                        op: str = "sum",
                                        impl: str = "auto"
                                        ) -> SplitPhaseHandle:
    """Split-phase int8 reduce-scatter (sum/avg): hop 0's fused
    quantize→DMA→dequantize is issued now, the rest at the wait.  Same
    slab contract as `ring.ring_reduce_scatter`; same fallback ladder as
    `quantized_ring_allreduce` (bf16 compression when int8 cannot pay)."""
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        raise TypeError(
            "quantized reduce-scatter requires floating-point input, got "
            f"{jnp.asarray(x).dtype} — quantizing integer gradients "
            "silently corrupts them (use ring_reduce_scatter instead)")
    if op.lower() not in ("sum", "avg", "mean"):
        raise ValueError(
            f"quantized reduce-scatter supports sum/avg, got {op!r}")
    _check_divisible(x, n)
    impl = select_impl(impl)
    op = "avg" if op.lower() in ("avg", "mean") else "sum"
    wants_bf16 = (
        jnp.asarray(x).dtype == jnp.float64
        or x.size < _MIN_QUANT_ELEMS
    )
    h = SplitPhaseHandle("quantized_reduce_scatter", axis_name, n, op, impl)
    if impl == "lax" or n == 1 or wants_bf16:
        # bf16-compressed fallback: the cast is the (lossy) compression;
        # the wait performs the actual collective.
        h.impl = "lax" if impl == "lax" or n == 1 else impl
        h.meta = ("bf16", x.dtype)
        h.buf = x.astype(jnp.bfloat16)
        return h
    block, shard_shape, per_shard = _slabs_to_block(
        x.astype(jnp.float32), n)
    h.meta = ("int8", x.dtype, shard_shape, per_shard)
    h.buf = _rs_hop(block, 0, n, axis_name, _add,
                    _quantized_sender(axis_name, n, impl))
    h.hops_done = 1
    return h


def wait_quantized_ring_reduce_scatter(h: SplitPhaseHandle):
    """Await a `start_quantized_ring_reduce_scatter`."""
    n, op, axis_name = h.n, h.op, h.axis_name
    if h.meta and h.meta[0] == "bf16":
        _, orig_dtype = h.meta
        out = ring.ring_reduce_scatter(h.buf, axis_name, n=n, op=op,
                                       impl=h.impl)
        return out.astype(orig_dtype)
    qsend = _quantized_sender(axis_name, n, h.impl)
    block = h.buf
    for t in range(h.hops_done, n - 1):
        block = _rs_hop(block, t, n, axis_name, _add, qsend)
    mine = _chunk(block, lax.axis_index(axis_name), n)
    _, orig_dtype, shard_shape, per_shard = h.meta
    result = mine.reshape(-1)[:per_shard].reshape(shard_shape)
    if op == "avg":
        result = result / n
    return result.astype(orig_dtype)


def local_quantization_residual(block, n: int):
    """What this rank's data loses to the FIRST int8 compression on the
    wire: ``block - dequant(quant(block))`` with one f32 scale per ring
    chunk (the kernel's scale rule).  This is the increment an
    error-feedback accumulator keeps so systematic round-off is re-sent
    on the next step instead of silently dropped.

    `block` must be 2-D ``(rows, LANES)`` with ``rows % n == 0`` — the
    packed layout both the monolithic and split-phase quantized paths use.
    Always f32 (graftlint's ef-dtype rule: never keep EF state in int).
    """
    if block.ndim != 2 or block.shape[0] % n:
        raise ValueError(
            f"expected (rows, LANES) block with rows divisible by {n}, "
            f"got shape {block.shape}")
    if block.size < _MIN_QUANT_ELEMS:
        # Below the quantization threshold the wire carries bf16, whose
        # round-off is what EF should track there.
        b16 = block.astype(jnp.bfloat16).astype(jnp.float32)
        return block.astype(jnp.float32) - b16
    chunks = block.astype(jnp.float32).reshape(n, block.shape[0] // n,
                                               block.shape[1])
    scales = jnp.maximum(
        jnp.max(jnp.abs(chunks), axis=(1, 2), keepdims=True) / _QMAX,
        1e-30)
    q = jnp.clip(jnp.round(chunks / scales), -_QMAX, _QMAX)
    deq = (q * scales).reshape(block.shape)
    return block.astype(jnp.float32) - deq


def _bf16_fallback(x, axis_name, n, op, impl):
    out = ring.ring_allreduce(x.astype(jnp.bfloat16), axis_name, n=n,
                              op=op, impl=impl)
    return out.astype(x.dtype)


def quantized_ring_allreduce(x, axis_name: str, *, n: int, op: str = "sum",
                             precision: str = "int8", impl: str = "auto"):
    """int8 quantize→ring-allreduce→dequantize over mesh axis `axis_name`.

    Sum/avg only (quantized max/min/prod have no sane error story).  Raises
    `TypeError` on non-float input; falls back to a bf16-compressed
    allreduce for f64, tiny tensors, ``precision="bf16"``, or when the
    resolved impl is the off-TPU `lax` path.
    """
    if not jnp.issubdtype(jnp.asarray(x).dtype, jnp.floating):
        raise TypeError(
            "quantized allreduce requires floating-point input, got "
            f"{jnp.asarray(x).dtype} — quantizing integer gradients "
            "silently corrupts them (use ring_allreduce instead)")
    if op.lower() not in ("sum", "avg", "mean"):
        raise ValueError(f"quantized allreduce supports sum/avg, got {op!r}")
    if precision not in ("int8", "bf16"):
        raise ValueError(f"precision must be int8|bf16, got {precision!r}")
    impl = select_impl(impl)
    wants_bf16 = (
        precision == "bf16"
        or jnp.asarray(x).dtype == jnp.float64
        or x.size < _MIN_QUANT_ELEMS
    )
    if impl == "lax" or n == 1 or wants_bf16:
        return _bf16_fallback(x, axis_name, n, op, impl)
    qsend = _quantized_sender(axis_name, n, impl)
    block, shape, size = _to_block(x.astype(jnp.float32), n)
    for t in range(n - 1):  # reduce-scatter sweep over quantized partials
        block = _rs_hop(block, t, n, axis_name, _add, qsend)
    for t in range(n - 1):  # allgather sweep of the reduced chunks
        block = _ag_hop(block, t, n, axis_name, qsend)
    result = _from_block(block, shape, size).astype(x.dtype)
    if op.lower() in ("avg", "mean"):
        result = result / n
    return result
