"""Kimi Delta Attention (KDA): a gated delta rule with one decay a
key CHANNEL, as a recurrence over a fixed-size state.

For each head, with a state `S` [dk, dv] (float32), a token's key and
query `k`, `q` [dk] (L2-normalised by the caller, `q` scaled), value
`v` [dv], log-decay `g` [dk] (<= 0, `alpha = exp(g)`) and write strength
`beta` (a scalar in (0, 1)):

    S' = Diag(alpha) S
    S  = S' + beta k (v - S'^T k)^T
       # = (I - beta k k^T) Diag(alpha) S + beta k v^T
    o  = S^T q

Two forms of that one function, plain `jax.numpy`:

- `kda_step`: one token a sequence (decode).  Both reductions over the
  state (`S'^T k` and `S'^T q`) are taken in one pass and the output is
  put together from them (`o = S'^T q + beta (k . q) u`), so the state is
  read for the reductions, read and written for the update, and never
  for the output.
- `kda_chunked`: a whole (padded) sequence from an initial state
  (prefill), `chunk` tokens at a time.  Inside a chunk the products of
  the `(I - beta k k^T) Diag(alpha)` factors are written in the WY / UT
  form: with `G_r` the cumulative log-decay up to row r of the chunk,
  `A[r, i] = sum_c k_r[c] k_i[c] exp(G_r[c] - G_i[c])` (i < r) and
  `u = (I + A Diag(beta))^-1 (v - (k exp(G)) S_0)` are the
  pseudo-values every row writes, found for all chunks at once by
  forward substitution (`_unit_lower_solve`); a scan over chunks then
  carries `S`.
  The decay differences are masked to `i <= r` BEFORE the exponential,
  where they are <= 0: `exp(-G_i)` alone overflows float32 after a few
  strongly decayed tokens.  Tokens at and past `n_real` (padding) get
  `g = 0`, `beta = 0`: they leave the state as it is, so the state
  handed back is the one after the last REAL token.

All state arithmetic is float32 with float32 matrix products
(`Precision.HIGHEST`): the products are small beside the model's and the
state is the one thing here whose error compounds over a sequence.

The causal depthwise convolution in front of q, k and v is
`ops/short_conv.py`'s.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

_HI = lax.Precision.HIGHEST
CHUNK = 64      # tokens a chunk of the prefill form


def kda_step(S: jax.Array, q: jax.Array, k: jax.Array, v: jax.Array,
             g: jax.Array, beta: jax.Array
             ) -> Tuple[jax.Array, jax.Array]:
    """S [B, H, dk, dv] float32; q, k, g [B, H, dk]; v [B, H, dv];
    beta [B, H].  Returns (o [B, H, dv] float32, the new S)."""
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


_SOLVE_BLOCK = 16


def _unit_lower_solve(N: jax.Array, rhs: jax.Array) -> jax.Array:
    """X with (I + N) X = rhs for strictly lower-triangular N [..., C, C]
    and rhs [..., C, W], by forward substitution in float32 on the
    vector unit: blocks of `_SOLVE_BLOCK` rows, each first relieved of the
    blocks before it (one product), then solved row by row.  (The
    chip's own triangular solve multiplies in one bf16 pass; a Neumann
    product of powers of N cancels badly when keys repeat.)"""
    C, b = N.shape[-2], _SOLVE_BLOCK
    assert C % b == 0, (C, b)
    out = []
    for s in range(0, C, b):
        r = rhs[..., s:s + b, :]
        if out:
            r = r - jnp.einsum("...ri,...iw->...rw", N[..., s:s + b, :s],
                               jnp.concatenate(out, -2), precision=_HI)
        n = N[..., s:s + b, s:s + b]

        def row(i, x, n=n):
            ni = lax.dynamic_index_in_dim(n, i, -2, keepdims=False)
            xi = lax.dynamic_index_in_dim(x, i, -2, keepdims=False) \
                - jnp.sum(ni[..., None] * x, axis=-2)        # rows >= i: n 0
            return lax.dynamic_update_index_in_dim(x, xi, i, -2)

        out.append(lax.fori_loop(1, b, row, r))
    return jnp.concatenate(out, -2)


def kda_chunked(q: jax.Array, k: jax.Array, v: jax.Array, g: jax.Array,
                beta: jax.Array, S0: jax.Array,
                n_real: Optional[jax.Array] = None, chunk: int = CHUNK
                ) -> Tuple[jax.Array, jax.Array]:
    """q, k, g [B, T, H, dk]; v [B, T, H, dv]; beta [B, T, H]; S0
    [B, H, dk, dv] float32; n_real [B] or a scalar (None: all T).
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
    G = jnp.cumsum(g, axis=-2)                               # [B,N,H,C,dk]
    # decay from row i to row r, per channel; masked before the exp
    diff = G[..., :, None, :] - G[..., None, :, :]           # [..,r,i,dk]
    lower = jnp.tril(jnp.ones((C, C), bool))
    decay = jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    # rows k (for A) and q (for the outputs) against the decayed keys,
    # in one reduction: the [C, C, dk] products are never kept
    AA = jnp.sum(jnp.stack([k, q])[..., :, None, :]
                 * (k[..., None, :, :] * decay)[None], -1)   # [2,B,N,H,C,C]
    A, Aq = AA[0], AA[1]                                     # Aq: i <= r
    A = jnp.where(jnp.tril(lower, -1), A, 0.0)               # i <  r
    # (I + A Diag(beta)) u = v - (k exp(G)) S_0: solve for both terms
    k_in = k * jnp.exp(G)
    W = _unit_lower_solve(A * beta[..., None, :],
                          jnp.concatenate([v, k_in], -1))
    Wv, Wk = W[..., :dv], W[..., dv:]
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
