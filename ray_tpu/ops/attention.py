"""Flash attention — pallas TPU kernels: the training pair (forward +
backward) and the serving insert's forward over a gathered history.

The hot op of the flagship model (net-new vs the reference, which has no
in-repo kernels — SURVEY §5 long-context). FlashAttention-2 style:

- forward: blockwise streaming attention with online softmax; per-row
  logsumexp (LSE) is written out for the backward pass. The [S, S] score
  matrix never exists in HBM.
- backward: two pallas passes plus a cheap elementwise delta precompute:
  (1) dk/dv: for each key block, stream query blocks, recomputing P from
      Q,K and the saved LSE; (2) dq: for each query block, stream key
      blocks. Peak memory stays O(S * D) — this is what lets batch and
      sequence scale on a 16G v5e chip (the XLA fallback's O(S^2) f32
      probabilities OOM first).
- `flash_prefill`: the forward alone for ONE sequence whose queries sit
  at an offset into a longer key array (a piece of a prompt against the
  slot's gathered history), under a causal mask and, for a
  sliding-window layer, a window.  Grouped-query heads share a key tile
  as rows of one query tile, the bounds are traced scalars (a bucket is
  one program whatever the piece's start), and a (query, key) tile pair
  that no query of the block can see is neither copied nor multiplied.
  `models/window_moe.py::blockwise_attention` is its XLA form, its
  reference, and the path wherever `prefill_engages` says no.

Layout: [B, S, H, D] public API (matches models/llama.py); kernels run in
[B, H, S, D]. Non-TPU platforms fall back to the XLA path end to end.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

NEG_INF = -1e30
_LANE = 128

# Test hook: when True, the pallas kernels run (in interpret mode off-TPU)
# instead of falling back to XLA — lets CPU tests exercise the real kernel
# bodies (values AND grads) against the reference attention.
FORCE_PALLAS_INTERPRET = False


def _cdiv(a: int, b: int) -> int:
    return (a + b - 1) // b


# ---------------------------------------------------------------------------
# Forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scratch, l_scratch,
                acc_scratch, *, scale: float, causal: bool,
                block_q: int, block_k: int):
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scratch[:] = jnp.full_like(m_scratch, NEG_INF)
        l_scratch[:] = jnp.zeros_like(l_scratch)
        acc_scratch[:] = jnp.zeros_like(acc_scratch)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True if not causal else (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)

        m_prev = m_scratch[:, 0][:, None]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_new = alpha * l_scratch[:, 0][:, None] + jnp.sum(
            p, axis=1, keepdims=True)
        m_scratch[:] = jnp.broadcast_to(m_new, m_scratch.shape)
        l_scratch[:] = jnp.broadcast_to(l_new, l_scratch.shape)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_scratch[:] = acc_scratch[:] * alpha + pv

    @pl.when(ki == nk - 1)
    def _finalize():
        m = m_scratch[:, 0][:, None]
        l = l_scratch[:, 0][:, None]
        l_safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_scratch[:] / l_safe).astype(o_ref.dtype)
        lse = jnp.where(l == 0.0, NEG_INF, m + jnp.log(l_safe))
        lse_ref[0, 0] = jnp.broadcast_to(lse, lse_ref.shape[2:])


def _flash_fwd_bhsd(q, k, v, causal, block_q, block_k, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = not _on_tpu()
    B, H, S, D = q.shape
    Sk = k.shape[2]
    grid = (B, H, _cdiv(S, block_q), _cdiv(Sk, block_k))
    kernel = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                               block_q=block_q, block_k=block_k)
    out, lse = pl.pallas_call(
        kernel,
        out_shape=(
            jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, S, _LANE), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _LANE),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, _LANE), jnp.float32),
            pltpu.VMEM((block_q, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return out, lse[:, :, :, 0]


# ---------------------------------------------------------------------------
# Backward kernels
# ---------------------------------------------------------------------------

def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_scratch, dv_scratch, *,
                    scale: float, causal: bool, block_q: int, block_k: int):
    """grid (B, H, nk, nq): one key block accumulates over query blocks."""
    from jax.experimental import pallas as pl

    ki = pl.program_id(2)
    qi = pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(qi == 0)
    def _init():
        dk_scratch[:] = jnp.zeros_like(dk_scratch)
        dv_scratch[:] = jnp.zeros_like(dv_scratch)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True if not causal else (q_start + block_q - 1 >= k_start)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]                                  # [bq, D]
        k = k_ref[0, 0]                                  # [bk, D]
        v = v_ref[0, 0]
        do = do_ref[0, 0]                                # [bq, D] bf16
        lse = lse_ref[0, 0][:, 0][:, None]               # [bq, 1]
        delta = delta_ref[0, 0][:, 0][:, None]           # [bq, 1]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bk]
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)                             # [bq, bk] f32
        # All matmul INPUTS stay bf16 (f32 operands run the MXU at a
        # fraction of peak on TPU); accumulation is f32 via
        # preferred_element_type.
        p_lo = p.astype(q.dtype)
        # dv += P^T dO
        dv_scratch[:] += jax.lax.dot_general(
            p_lo, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # dP = dO V^T ; dS = P * (dP - delta) * scale
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)          # [bq, bk]
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        # dk += dS^T q
        dk_scratch[:] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(qi == nq - 1)
    def _finalize():
        dk_ref[0, 0] = dk_scratch[:].astype(dk_ref.dtype)
        dv_ref[0, 0] = dv_scratch[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                   dq_ref, dq_scratch, *, scale: float, causal: bool,
                   block_q: int, block_k: int):
    """grid (B, H, nq, nk): one query block accumulates over key blocks."""
    from jax.experimental import pallas as pl

    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        dq_scratch[:] = jnp.zeros_like(dq_scratch)

    q_start = qi * block_q
    k_start = ki * block_k
    run = True if not causal else (k_start <= q_start + block_q - 1)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0]
        k = k_ref[0, 0]
        v = v_ref[0, 0]
        do = do_ref[0, 0]
        lse = lse_ref[0, 0][:, 0][:, None]
        delta = delta_ref[0, 0][:, 0][:, None]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if causal:
            q_pos = q_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = k_start + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        ds = (p * (dp - delta) * scale).astype(q.dtype)
        dq_scratch[:] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0, 0] = dq_scratch[:].astype(dq_ref.dtype)


def _bhsd_bwd(q, k, v, do, o, lse, causal, block_q, block_k, scale):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    interpret = not _on_tpu()
    B, H, S, D = q.shape
    Sk = k.shape[2]
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                    axis=-1)                              # [B,H,S]
    lse_l = jnp.broadcast_to(lse[..., None], (B, H, S, _LANE))
    delta_l = jnp.broadcast_to(delta[..., None], (B, H, S, _LANE))

    row_specs = [
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_k, D), lambda b, h, ki, qi: (b, h, ki, 0)),
        pl.BlockSpec((1, 1, block_q, D), lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, _LANE),
                     lambda b, h, ki, qi: (b, h, qi, 0)),
        pl.BlockSpec((1, 1, block_q, _LANE),
                     lambda b, h, ki, qi: (b, h, qi, 0)),
    ]
    dk, dv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=(
            jax.ShapeDtypeStruct((B, H, Sk, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, Sk, D), q.dtype),
        ),
        grid=(B, H, _cdiv(Sk, block_k), _cdiv(S, block_q)),
        in_specs=row_specs,
        out_specs=(
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, ki, qi: (b, h, ki, 0)),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, D), jnp.float32),
            pltpu.VMEM((block_k, D), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse_l, delta_l)

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k),
        out_shape=jax.ShapeDtypeStruct((B, H, S, D), q.dtype),
        grid=(B, H, _cdiv(S, block_q), _cdiv(Sk, block_k)),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_k, D),
                         lambda b, h, qi, ki: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_q, D),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _LANE),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_q, _LANE),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse_l, delta_l)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public API with XLA fallback + custom VJP
# ---------------------------------------------------------------------------

def _on_tpu() -> bool:
    # No exception handling here on purpose: a backend that fails to
    # initialise must fail the caller, not quietly select XLA attention.
    return jax.default_backend() == "tpu"


def _pad_to(x: jax.Array, axis: int, multiple: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _xla_attention(q, k, v, causal):
    from ray_tpu.models.llama import xla_attention

    return xla_attention(q, k, v, causal=causal)


def _blocks(S: int, Sk: int, causal: bool = True) -> Tuple[int, int]:
    """Tile sizes for the pallas grid (RAY_TPU_FLASH_BLOCK_Q/K override
    for tuning sweeps). In causal mode divisibility is NOT required:
    `_prep` pads the sequence up to the tile multiple, padded keys are
    excluded by the kernel's absolute-index masks, and padded query rows
    are sliced off the output. Non-causal has no mask to hide padded
    keys behind, so its key tile must divide Sk exactly."""
    def _env(name: str) -> int:
        raw = os.environ.get(name, "").strip()
        return int(raw) if raw.isdigit() else 0

    pad_s = -(-S // _LANE) * _LANE
    pad_sk = -(-Sk // _LANE) * _LANE
    # v5e sweep at seq 1024 / head dim 128 (PERF.md): bigger tiles win
    # monotonically up to 1024 (68.9% MFU vs 53.5% at 128-tiles); 1024
    # caps VMEM use for long sequences.
    bq = min(_env("RAY_TPU_FLASH_BLOCK_Q") or 1024, pad_s)
    bk = min(_env("RAY_TPU_FLASH_BLOCK_K") or 1024, pad_sk)
    if not causal and Sk % bk:
        bk = _LANE  # caller enforces Sk % 128 == 0 for non-causal
    return bq, bk


def _use_kernel(q, k) -> bool:
    """The one documented size rule, the same on every backend: under 128
    positions a tile would be mostly padding, and `flash_attention` IS
    `xla_attention`. From 128 on, a TPU always gets the compiled kernel
    (never interpret mode, never XLA attention); off-TPU the kernel runs
    only when a test forces the interpreter."""
    if q.shape[1] < 128 or k.shape[1] < 128:
        return False
    return _on_tpu() or FORCE_PALLAS_INTERPRET


def _prep(x, block, lane=_LANE):
    """[B,S,H,D] -> padded [B,H,S,D]."""
    return _pad_to(_pad_to(x.transpose(0, 2, 1, 3), 2, block), 3, lane)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True) -> jax.Array:
    """q,k,v: [B, S, H, D] -> [B, S, H, D]."""
    out, _ = _flash_fwd(q, k, v, causal)
    return out


def _flash_fwd(q, k, v, causal):
    B, S, H, D = q.shape
    Sk = k.shape[1]
    if not _use_kernel(q, k):
        return _xla_attention(q, k, v, causal), (q, k, v, None, None)
    if not causal and (S % 128 or Sk % 128):
        raise NotImplementedError(
            "non-causal flash requires seq_len % 128 == 0")
    block_q, block_k = _blocks(S, Sk, causal)
    qt, kt, vt = _prep(q, block_q), _prep(k, block_k), _prep(v, block_k)
    out, lse = _flash_fwd_bhsd(qt, kt, vt, causal, block_q, block_k,
                               scale=1.0 / math.sqrt(D))
    public = out[:, :, :S, :D].transpose(0, 2, 1, 3)
    return public, (q, k, v, out, lse)


def _flash_bwd(causal, residuals, g):
    q, k, v, o_pad, lse = residuals
    B, S, H, D = q.shape
    if o_pad is None:  # XLA fallback path
        _, vjp = jax.vjp(
            lambda q, k, v: _xla_attention(q, k, v, causal), q, k, v)
        return vjp(g)
    Sk = k.shape[1]
    block_q, block_k = _blocks(S, Sk, causal)
    qt, kt, vt = _prep(q, block_q), _prep(k, block_k), _prep(v, block_k)
    do = _prep(g.astype(q.dtype), block_q)
    dq, dk, dv = _bhsd_bwd(qt, kt, vt, do, o_pad, lse, causal,
                           block_q, block_k, scale=1.0 / math.sqrt(D))
    dq = dq[:, :, :S, :D].transpose(0, 2, 1, 3)
    dk = dk[:, :, :Sk, :D].transpose(0, 2, 1, 3)
    dv = dv[:, :, :Sk, :D].transpose(0, 2, 1, 3)
    return dq, dk, dv


flash_attention.defvjp(
    lambda q, k, v, causal: _flash_fwd(q, k, v, causal),
    _flash_bwd,
)


# ---------------------------------------------------------------------------
# Prefill over a gathered history: one sequence, a query offset, a window
# ---------------------------------------------------------------------------

# Queries and keys a tile, the most (PERF.md section 6, PR 48: swept on
# the chip at Trinity-Mini's widths, 8 heads a group over 2048 queries).
# The kernel multiplies a head's [queries, hd] against the key tile at a
# time, so the queries are the matrix unit's rows a key tile load: 512
# took 0.82-0.86 of 256's time and 128 1.19-1.26 of it.  Keys: 1024 took
# 0.59-0.76 of 512's time (a step pays for the rescaling of the
# accumulators whatever its keys); 2048 is 9% faster over 16 k keys and
# 15-20% slower under a window of 2048, whose edge it skips more coarsely.
PREFILL_BLOCK_Q = 512
PREFILL_BLOCK_K = 1024


def _prefill_blocks(Q: int, S: int) -> Tuple[int, int]:
    """(queries, keys) a tile for Q queries over S key rows: a power of
    two of queries that divides Q, the largest whole number of lane rows
    of keys that divides S (shapes that do not engage are still counted
    by tiles: of what divides them)."""
    bk = max((b for b in range(_LANE, PREFILL_BLOCK_K + 1, _LANE)
              if S % b == 0), default=math.gcd(S, PREFILL_BLOCK_K))
    return math.gcd(Q, PREFILL_BLOCK_Q), bk


# The least queries and key rows a call has to have.  Under them the XLA
# loop's score blocks are small enough that it runs as fast (on the chip,
# a layer alone: Trinity-Mini's 512 bucket 0.27-0.46 ms as a loop and
# 0.35-0.49 as the kernel, its 256 bucket 0.21 both ways;
# Phi-4-mini-flash's 1024 queries over 1536 rows 0.31-0.41 against 0.50),
# and every (bucket, kind) the kernel engages for costs each process
# half a second of tracing and lowering at its start, compile cache
# warm or not (PERF.md section 6, PR 48).
PREFILL_MIN_Q = 1024
PREFILL_MIN_K = 2048


def prefill_engages(Q: int, hd: int, S: int) -> bool:
    """Whether Q queries of heads `hd` wide over S key rows go through
    `flash_prefill`: `_use_kernel`'s rule for the backend (a TPU always,
    off TPU only when a test forces the interpreter) and shapes that
    tile and pay, heads of whole lane rows and at least `PREFILL_MIN_Q`
    queries and `PREFILL_MIN_K` key rows in whole tiles of 128.  One
    query (a cross layer's last row), the small buckets and the tiny
    models' heads of 16 keep the loop."""
    tiles = hd % _LANE == 0 and Q % _LANE == 0 and S % _LANE == 0
    pays = Q >= PREFILL_MIN_Q and S >= PREFILL_MIN_K
    return tiles and pays and (_on_tpu() or FORCE_PALLAS_INTERPRET)


def _prefill_span(qi, off, lo, hi, *, bq, bk, window, least=min, most=max):
    """(first, last) key tile that some query of block `qi` sees, none
    where last < first.  Query row q sits at key row q + off and sees
    key row i when lo <= i < hi, i <= q + off and, under a window,
    i > q + off - window.  Python integers, or traced ones with
    `jnp.minimum` / `jnp.maximum` (the kernel, its index maps)."""
    top = least(qi * bq + bq - 1 + off, hi - 1)
    low = lo if window is None else most(lo, qi * bq + off - window + 1)
    return low // bk, top // bk


def prefill_tiles(Q: int, S: int, off: int, lo: int, hi: int,
                  window: Optional[int]) -> int:
    """The (query block, key tile) pairs `flash_prefill`'s bounds let
    through at these sizes: host arithmetic over the kernel's own
    `_prefill_span`."""
    bq, bk = _prefill_blocks(Q, S)
    spans = (_prefill_span(qi, off, max(lo, 0), min(hi, S), bq=bq, bk=bk,
                           window=window) for qi in range(Q // bq))
    return sum(max(last - first + 1, 0) for first, last in spans)


def _prefill_kernel(s_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref,
                    acc_ref, *, scale, window, bq, bk, causal_block=1):
    """grid (KV head, query block, key step): the query tile holds the
    group's heads, [r, bq, hd]; step ki takes key tile first + ki of the
    block's span and folds it into each head's running maximum, sum and
    accumulator, a head a loop step (a head's `[bq, bk]` scores at a
    time; the r heads share the tile's mask).  All r heads as rows of
    ONE product read the same at start 0 and up to 14% faster over 16 k
    keys on the chip, for eight times the code: 3-5 s of compile a
    (bucket, kind) against under 1 (PERF.md section 6, PR 48)."""
    from jax.experimental import pallas as pl

    qi, ki = pl.program_id(1), pl.program_id(2)
    off, lo, hi = s_ref[0], s_ref[1], s_ref[2]
    first, last = _prefill_span(qi, off, lo, hi, bq=bq, bk=bk, window=window,
                                least=jnp.minimum, most=jnp.maximum)
    tile = first + ki
    r, _, hd = acc_ref.shape

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # the key rows the block's first query and the tile's first key sit at
    q0, k0 = qi * bq + off, tile * bk
    # every query of the block sees every key of the tile: no mask
    whole = (k0 + bk - 1 <= q0) & (k0 >= lo) & (k0 + bk <= hi)
    if window is not None:
        whole = whole & (k0 > q0 + bq - 1 - window)

    def fold(masked):
        k, v = k_ref[...], v_ref[...]
        seen = None
        if masked:
            qrow = q0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            krow = k0 + lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            if causal_block > 1:    # a query sees its whole block
                qrow = qrow | (causal_block - 1)
            seen = (krow <= qrow) & (krow >= lo) & (krow < hi)
            if window is not None:
                seen = seen & (krow > qrow - window)

        def head(j, _):
            s = lax.dot_general(
                q_ref[j], k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if masked:
                s = jnp.where(seen, s, NEG_INF)
            m_prev = m_ref[j][:, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            if masked:
                # a row that has seen no key yet: s - m_new is 0 there
                p = jnp.where(seen, p, 0.0)
            l_new = alpha * l_ref[j][:, :1] + jnp.sum(
                p, axis=1, keepdims=True)
            m_ref[j] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[j] = jnp.broadcast_to(l_new, l_ref.shape[1:])
            acc_ref[j] = acc_ref[j] * alpha + lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

        lax.fori_loop(0, r, head, None)

    run = tile <= last
    pl.when(run & whole)(lambda: fold(False))
    pl.when(run & jnp.logical_not(whole))(lambda: fold(True))

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...][:, :, :1]
        o_ref[...] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype)


# Jitted so that the call sites of an unrolled layer loop lower the kernel
# once a kind (`ops/paged_attention.py::paged_latent_attention`).
@functools.partial(jax.jit, static_argnames=(
    "window", "scale", "interpret", "causal_block"))
def _flash_prefill(q, k, v, off, lo, hi, *, window, scale, interpret,
                   causal_block=1):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    Q, H, hd = q.shape
    S, kvh = k.shape[:2]
    r = H // kvh
    bq, bk = _prefill_blocks(Q, S)
    tiles = S // bk
    # key tiles a query block may take, the grid's static bound: all of
    # them, or the most that window + bq - 1 consecutive rows straddle
    steps = tiles if window is None else min(
        tiles, (window + bq - 2) // bk + 2)
    span = functools.partial(_prefill_span, bq=bq, bk=bk, window=window,
                             least=jnp.minimum, most=jnp.maximum)

    def key_tile(g, qi, ki, s):
        # past the block's last tile the index stands still: no copy
        first, last = span(qi, s[0], s[1], s[2])
        return jnp.clip(jnp.minimum(first + ki, last), 0, tiles - 1), g

    def query_tile(g, qi, ki, s):
        return g, 0, qi, 0

    bounds = jnp.stack([off, jnp.maximum(lo, 0), jnp.minimum(hi, S)]
                       ).astype(jnp.int32)
    # [Q, kvH r, hd] -> [kvH, r, Q, hd]; K and V rows as they lie, a KV
    # head a block of `hd` lanes
    heads = jnp.transpose(q.reshape(Q, kvh, r, hd), (1, 2, 0, 3))
    out = pl.pallas_call(
        functools.partial(_prefill_kernel, scale=scale, window=window,
                          bq=bq, bk=bk, causal_block=causal_block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(kvh, Q // bq, steps),
            in_specs=[pl.BlockSpec((None, r, bq, hd), query_tile),
                      pl.BlockSpec((bk, hd), key_tile),
                      pl.BlockSpec((bk, hd), key_tile)],
            out_specs=pl.BlockSpec((None, r, bq, hd), query_tile),
            scratch_shapes=[pltpu.VMEM((r, bq, _LANE), jnp.float32),
                            pltpu.VMEM((r, bq, _LANE), jnp.float32),
                            pltpu.VMEM((r, bq, hd), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct(heads.shape, q.dtype),
        interpret=interpret,
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        name="flash_prefill",
    )(bounds, heads, k.reshape(S, kvh * hd), v.reshape(S, kvh * hd))
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(Q, H, hd)


def flash_prefill(q: jax.Array, k: jax.Array, v: jax.Array, off, lo, hi, *,
                  window: Optional[int] = None,
                  scale: Optional[float] = None,
                  causal_block: int = 1) -> jax.Array:
    """ONE sequence: q [Q, H, hd], query j at key row j + `off`, against
    k, v [S, kvH, hd]; head h reads KV head h // (H / kvH).  Query j sees
    key row i when lo <= i < hi, i <= j + off and, with `window`,
    i > j + off - window; `off`, `lo`, `hi` are traced scalars.  bf16 (or
    whatever the operands are) products, float32 scores, softmax and
    accumulation; [Q, H, hd], zeros for a query that sees no key.
    Shapes as `prefill_engages` asks.

    `causal_block` L > 1 (a power of two that divides `off` and a tile
    of queries; no window): the mask is BLOCK-causal, query j sees key
    row i <= (j + off) | (L - 1), the last row of its own block of L.
    The tile bounds stand: a block of queries ends where its tile
    does."""
    if causal_block > 1 and (window is not None
                             or causal_block & (causal_block - 1)):
        raise ValueError("a block-causal mask takes a power of two and "
                         "no window")
    return _flash_prefill(
        q, k, v, off, lo, hi, window=window,
        scale=scale or 1.0 / math.sqrt(q.shape[-1]),
        interpret=not _on_tpu(), causal_block=causal_block)
