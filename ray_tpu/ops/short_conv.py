"""The short causal depthwise convolution over time that recurrent and
gated-convolution layers put in front of (or in place of) their mixer:
`y_t = sum_j w[j] * x_{t - (K-1) + j}`, one tap a channel, `w[K-1]` on
the current row, zeros before the sequence.  The width `K` is the
weights' first axis (4 in `models/kimi_linear.py`, 3 in
`models/conv_moe.py`).

What a sequence carries between calls is its last `K - 1` input rows
(`tail`): `short_conv` takes the rows before its first token and hands
back those before token `n_real` (so a padded bucket's tail is taken
after the last REAL token), `short_conv_step` shifts one row in.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax


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


def short_conv_step(x: jax.Array, w: jax.Array, tail: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    """One row a sequence: x [B, C], tail [B, K-1, C] -> (y [B, C],
    the tail with x behind it and its oldest row gone)."""
    xx = jnp.concatenate([tail.astype(x.dtype), x[:, None]], axis=1)
    y = jnp.einsum("bkc,kc->bc", xx, w.astype(x.dtype))
    return y, xx[:, 1:]
