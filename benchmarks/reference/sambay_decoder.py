"""Plain reference for the decoder-hybrid-decoder of Mamba layers,
differential attention in a window, one full-attention layer whose K and
V the later layers read, and gated memory units (`model_type`
`phi4flash`: Phi-4-mini-flash-reasoning's block; arXiv:2507.06607).

On one sequence in float32 under
`jax.default_matmul_precision("highest")`, with `D` = hidden_size, `LN`
a LayerNorm with weight AND bias, `L` = num_hidden_layers, `l` = 0..L-1,
NO positions anywhere:

  x = E[token]                                  tied with the head
  u = LN_in,l(x);  x = x + Mixer_l(u)
  [g | h] = LN_post,l(x) W1_l;  x = x + (silu(g) * h) W2_l
  logits = LN_f(x) E^T
  Mixer_l:  l even, l <= L/2  : Mamba_l(u)   (l = L/2 also hands on m)
            l odd,  l <  L/2  : DiffAttn_l(u), the query at p sees keys
                                p - sliding_window + 1 .. p
            l = L/2 + 1       : DiffAttn_l(u), every key up to p; its K
                                and V are what the later layers read
            l even, l > L/2+1 : GMU_l(u, m) = (m * silu(u W_g)) W_o,
                                m of the SAME token
            l odd,  l > L/2+1 : DiffAttn_l with q from u alone, K and V
                                those of layer L/2 + 1
  Mamba(u):  [xs | z] = u W_in;  xc = silu(conv(xs) + b_conv), a causal
      depthwise convolution of mamba_d_conv taps (zeros before the
      first token; written as a sum over shifted rows);
      [dt | B | C] = xc W_x;  Delta = softplus(dt W_dt + b_dt);
      TOKEN BY TOKEN, h [d_state, d_inner] = 0 before the first token,
        h = exp(Delta_t A) * h + (Delta_t xc_t) B_t,  A = -exp(A_log);
        y_t = sum_n h[n] C_t[n] + Dskip * xc_t;
      m = y (BEFORE the gate);  out = (y * silu(z)) W_out.
  DiffAttn(u):  [q | k | v] = u W_qkv + b_qkv in heads of hd = D / H;
      consecutive heads pair: q1_j = q[2j], q2_j = q[2j+1]; k1_i =
      k[2i], k2_i = k[2i+1], V_i = v[2i] ‖ v[2i+1]; query pair j reads
      K/V pair i = j // (H / kvH);
      a1 = softmax(q1 k1^T hd^-1/2 + mask) V,  a2 = softmax(q2 k2^T
      hd^-1/2 + mask) V;  lam0 = 0.8 - 0.6 exp(-0.3 l);
      lam = exp(lq1 . lk1) - exp(lq2 . lk2) + lam0;
      o_j = (1 - lam0) RMSNorm_2hd(a1 - lam a2; sub_w);
      out = concat_j(o_j) W_o + b_o.

The weights are the PROGRAM's own tree, as its `init_params` lays it
(`self`: the first L/4 (Mamba, window) pairs stacked; `mid`: layers L/2
and L/2 + 1; `cross`: the (GMU, cross) pairs stacked), in the program's
bf16: `init_weights` draws it once and the family hands the same buffers
to the engine, because a second copy of 7.7 GB does not fit the chip.
This file takes a pair at a time up to float32 inside a jitted call.

No kernels, no cache, no chunked scan, no batching of requests, every
layer for every row, no code of the program under test.  Departures from
the published description, each for memory and none for arithmetic:
attention runs in blocks of `Q_BLOCK` queries (`lax.map`), the
vocabulary in `V_BLOCKS` slices, and the sequence is padded on the right
to one of a few lengths (`_padded`; nothing here looks ahead).  What the
published config does not say and this file had to choose is the
configuration file's `assumed`.

`init_as_trainer` / `adamw_trajectory` raise: there is no train cell.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = "highest"
Q_BLOCK = 256
PAD_TO = 1024
V_BLOCKS = 8


def _sizes(c: Mapping) -> Dict[str, int]:
    D, H = c["hidden_size"], c["num_attention_heads"]
    L = c["num_hidden_layers"]
    assert c.get("mb_per_layer", 2) == 2 and L % 4 == 0 and L >= 8, L
    return dict(
        D=D, H=H, kvH=c["num_key_value_heads"], hd=D // H,
        F=c["intermediate_size"], V=c["vocab_size"], L=L,
        W=c["sliding_window"], N=c.get("mamba_d_state", 16),
        K=c.get("mamba_d_conv", 4), C=c.get("mamba_expand", 2) * D,
        R=c.get("mamba_dt_rank") or -(-D // 16), ns=L // 4, nc=L // 4 - 1)


def shapes(c: Mapping) -> Dict[str, Any]:
    """The full model's shapes, a kind's layers stacked."""
    z = _sizes(c)
    D, C, N, F, hd = z["D"], z["C"], z["N"], z["F"], z["hd"]
    Wkv = z["kvH"] * hd
    block = {"ln_in_w": (D,), "ln_in_b": (D,), "ln_post_w": (D,),
             "ln_post_b": (D,), "w1": (D, 2 * F), "w2": (F, D)}
    mamba = dict(block, w_in=(D, 2 * C), conv_w=(z["K"], C), conv_b=(C,),
                 w_x=(C, z["R"] + 2 * N), w_dt=(z["R"], C), b_dt=(C,),
                 A_log=(N, C), Dskip=(C,), w_out=(C, D))

    def attn(qkv):
        return dict(block, w_qkv=(D, qkv), b_qkv=(qkv,), lq1=(hd,),
                    lk1=(hd,), lq2=(hd,), lk2=(hd,), sub_w=(2 * hd,),
                    w_o=(D, D), b_o=(D,))

    gmu = dict(block, w_g=(D, C), w_o=(C, D))
    stack = lambda n, t: {k: (n,) + s for k, s in t.items()}
    return {"embed": (z["V"], D),
            "self": {"a": stack(z["ns"], mamba),
                     "b": stack(z["ns"], attn(D + 2 * Wkv))},
            "mid": {"a": mamba, "b": attn(D + 2 * Wkv)},
            "cross": {"a": stack(z["nc"], gmu), "b": stack(z["nc"], attn(D))},
            "norm_f_w": (D,), "norm_f_b": (D,)}


def param_counts(c: Mapping) -> Dict[str, int]:
    """Parameters by kind of layer and in all, from `shapes`."""
    sh = shapes(c)
    n = lambda t: sum(int(np.prod(s)) for s in t.values())
    z = _sizes(c)
    out = {"mamba_layer": n(sh["mid"]["a"]), "attn_layer": n(sh["mid"]["b"]),
           "gmu_layer": n(sh["cross"]["a"]) // z["nc"],
           "cross_layer": n(sh["cross"]["b"]) // z["nc"],
           "embed": int(np.prod(sh["embed"])), "norm_f": 2 * z["D"]}
    out["total"] = (
        (z["ns"] + 1) * out["mamba_layer"] + (z["ns"] + 1) * out["attn_layer"]
        + z["nc"] * (out["gmu_layer"] + out["cross_layer"]) + out["embed"]
        + out["norm_f"])
    return out


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights from `--seed`, drawn on the device in one
    jitted call, by the family's own draws so that decays are a trained
    model's and not 0 or 1: normal(0, initializer_range) matrices and
    taps, `A_log` = log(1 .. d_state) a channel, `b_dt` =
    softplus^-1(dt) with dt log-uniform in [1e-3, 1e-1], `Dskip` ones,
    the four lambda vectors N(0, 0.1), norm weights ones, every bias
    zeros (the decays, `Dskip` and the lambdas float32)."""
    z, std = _sizes(c), _std(c)
    sh = shapes(c)

    def leaf(key, name, shape):
        if name in ("ln_in_w", "ln_post_w", "sub_w", "norm_f_w"):
            return jnp.ones(shape, dtype)
        if name == "Dskip":
            return jnp.ones(shape, jnp.float32)
        if name == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, z["N"] + 1, dtype=jnp.float32))[:, None], shape)
        if name == "b_dt":
            dt = jnp.exp(jax.random.uniform(
                key, shape, jnp.float32, np.log(1e-3), np.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name in ("lq1", "lk1", "lq2", "lk2"):
            return 0.1 * jax.random.normal(key, shape, jnp.float32)
        if name.startswith(("b_", "ln_")) or name in ("conv_b", "norm_f_b"):
            return jnp.zeros(shape, dtype)
        return jax.random.normal(key, shape, dtype) * jnp.asarray(std, dtype)

    def make(key):
        flat, tree = jax.tree_util.tree_flatten_with_path(
            sh, is_leaf=lambda s: isinstance(s, tuple))
        keys = jax.random.split(key, len(flat))
        return jax.tree_util.tree_unflatten(tree, [
            leaf(k, path[-1].key, shape)
            for k, (path, shape) in zip(keys, flat)])

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("sambay_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("sambay_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _f(a):
    return a.astype(jnp.float32)


def _ln(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * _f(w) + _f(b)


def _ffn(c, x, w):
    g, h = jnp.split(_ln(x, w["ln_post_w"], w["ln_post_b"],
                         float(c["layer_norm_eps"])) @ _f(w["w1"]), 2, -1)
    return x + (jax.nn.silu(g) * h) @ _f(w["w2"])


def selective_scan(delta, xc, Bt, Ct, A, state_dtype=jnp.float32):
    """The recurrence token by token.  delta, xc [T, C]; Bt, Ct [T, N];
    A [N, C] -> (y [T, C], the state after the last row [N, C]).  The
    products with the state are multiply-and-sum (float32 as it stands,
    on any backend).  `state_dtype`: what the state is rounded to
    between tokens (the tests' bf16 mutilation)."""

    def step(h, t):
        d, x, b, cc = t
        h = jnp.exp(d[None, :] * A) * _f(h) + (d * x)[None, :] * b[:, None]
        return h.astype(state_dtype), jnp.sum(h * cc[:, None], axis=0)

    h0 = jnp.zeros(A.shape, state_dtype)
    h, y = lax.scan(step, h0, (delta, xc, Bt, Ct), unroll=8)
    return y, h


def mamba(c: Mapping, u, w, without=(), state_dtype=jnp.float32):
    """u [T, D], normed -> (the mixer's output [T, D], the memory m
    [T, C], the state after the last row [N, C])."""
    z = _sizes(c)
    T, C, N, R, K = u.shape[0], z["C"], z["N"], z["R"], z["K"]
    xs, gate = jnp.split(u @ _f(w["w_in"]), 2, -1)
    xp = jnp.concatenate([jnp.zeros((K - 1, C), xs.dtype), xs], 0)
    xc = jax.nn.silu(sum(xp[j:j + T] * _f(w["conv_w"][j]) for j in range(K))
                     + _f(w["conv_b"]))
    dbc = xc @ _f(w["w_x"])
    dt = dbc[:, :R] @ _f(w["w_dt"])
    if "dt_bias" not in without:
        dt = dt + _f(w["b_dt"])
    y, h = selective_scan(jax.nn.softplus(dt), xc, dbc[:, R:R + N],
                          dbc[:, R + N:], -jnp.exp(_f(w["A_log"])),
                          state_dtype)
    if "dskip" not in without:
        y = y + _f(w["Dskip"]) * xc
    gated = y * jax.nn.silu(gate)
    m = gated if "memory_before_gate" in without else y
    return gated @ _f(w["w_out"]), m, h


def _rope(x, theta=1e4):
    """The tests' mutilation: x [T, heads, hd] rotate-half by position."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * inv
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * jnp.cos(f) - b * jnp.sin(f),
                            b * jnp.cos(f) + a * jnp.sin(f)], -1)


def keys_values(c: Mapping, u, w_kv, b_kv, without=()):
    """u [T, D] -> (k [T, kvH, hd], v [T, kvH, hd])."""
    z = _sizes(c)
    k, v = jnp.split(u @ _f(w_kv) + _f(b_kv), 2, -1)
    k = k.reshape(-1, z["kvH"], z["hd"])
    if "no_rotary" in without:
        k = _rope(k)
    return k, v.reshape(-1, z["kvH"], z["hd"])


def diff_attention(c: Mapping, q, k, v, w, depth, window, q_block,
                   without=()):
    """q [T, H, hd] against k, v [T, kvH, hd] of the same positions ->
    [T, D].  `window`: keys a query sees, or None for every key up to
    its own."""
    z = _sizes(c)
    T, H, kvH, hd = q.shape[0], z["H"], z["kvH"], z["hd"]
    if "no_rotary" in without:
        q = _rope(q)
    rep = H // kvH
    q1, q2 = q[:, 0::2], q[:, 1::2]                         # [T, H/2, hd]
    k1 = jnp.repeat(k[:, 0::2], rep, axis=1)                # [T, H/2, hd]
    k2 = jnp.repeat(k[:, 1::2], rep, axis=1)
    halves = (v[:, 1::2], v[:, 0::2]) if "v_order" in without \
        else (v[:, 0::2], v[:, 1::2])
    V = jnp.repeat(jnp.concatenate(halves, -1), rep, axis=1)  # [T, H/2, 2hd]
    if "depth" in without:
        depth = depth + 1
    lam0 = 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, jnp.float32))
    lam = jnp.exp(jnp.sum(_f(w["lq1"]) * _f(w["lk1"]))) \
        - jnp.exp(jnp.sum(_f(w["lq2"]) * _f(w["lk2"]))) + lam0
    qb = T if T <= q_block else math.gcd(T, q_block)
    kpos = jnp.arange(T)

    def one(args):
        qa, qc, s0 = args
        qpos = (s0 + jnp.arange(qb))[:, None]
        mask = kpos[None, :] <= qpos
        if window is not None:
            mask = mask & (kpos[None, :] > qpos - window)

        def half(qi, ki):
            s = jnp.einsum("qhd,khd->hqk", qi, ki) * hd ** -0.5
            p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
            return jnp.einsum("hqk,khd->qhd", p, V)

        a1 = half(qa, k1)
        if "second_softmax" in without:
            return a1
        return a1 - lam * half(qc, k2)

    d = lax.map(one, (q1.reshape(T // qb, qb, H // 2, hd),
                      q2.reshape(T // qb, qb, H // 2, hd),
                      jnp.arange(T // qb) * qb)).reshape(T, H // 2, 2 * hd)
    if "sub_norm" not in without:
        d = d / jnp.sqrt(jnp.mean(d * d, -1, keepdims=True)
                         + float(c["layer_norm_eps"])) * _f(w["sub_w"])
    if "one_minus_lam0" not in without:
        d = d * (1.0 - lam0)
    return d.reshape(T, H * hd) @ _f(w["w_o"]) + _f(w["b_o"])


def _ln_in(c, x, w):
    return _ln(x, w["ln_in_w"], w["ln_in_b"], float(c["layer_norm_eps"]))


def mamba_layer(c, x, w, without=(), state_dtype=jnp.float32):
    out, m, h = mamba(c, _ln_in(c, x, w), w, without, state_dtype)
    return _ffn(c, x + out, w), m, h


def attn_layer(c, x, w, depth, window, q_block, without=()):
    """A layer with K and V of its own: (x, k, v)."""
    z = _sizes(c)
    D = z["D"]
    u = _ln_in(c, x, w)
    q = (u @ _f(w["w_qkv"][:, :D]) + _f(w["b_qkv"][:D])).reshape(
        -1, z["H"], z["hd"])
    k, v = keys_values(c, u, w["w_qkv"][:, D:], w["b_qkv"][D:], without)
    x = x + diff_attention(c, q, k, v, w, depth, window, q_block, without)
    return _ffn(c, x, w), k, v


def cross_pair(c, x, w, m, k, v, depth, mid_b, q_block, without=()):
    """A (GMU, cross attention) pair at layers depth, depth + 1."""
    z = _sizes(c)
    wa, wb = w["a"], w["b"]
    u = _ln_in(c, x, wa)
    x = _ffn(c, x + (m * jax.nn.silu(u @ _f(wa["w_g"]))) @ _f(wa["w_o"]), wa)
    u = _ln_in(c, x, wb)
    q = (u @ _f(wb["w_qkv"]) + _f(wb["b_qkv"])).reshape(-1, z["H"], z["hd"])
    if "shared_kv" in without:      # K and V of this layer's own input
        D = z["D"]
        k, v = keys_values(c, u, mid_b["w_qkv"][:, D:], mid_b["b_qkv"][D:],
                           without)
    x = x + diff_attention(c, q, k, v, wb, depth + 1, None, q_block, without)
    return _ffn(c, x, wb)


def _at(stacked, i):
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, i, keepdims=False), stacked)


_KEEP = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "intermediate_size", "vocab_size", "layer_norm_eps",
         "num_hidden_layers", "sliding_window")
_ASSUMED = ("mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")


def _cfg_key(c: Mapping) -> tuple:
    return tuple((k, c[k]) for k in _KEEP) + tuple(
        (k, c[k]) for k in _ASSUMED if k in c)


@partial(jax.jit, static_argnames=("cfg_key", "without", "state_dtype"))
def _self_pair_jit(x, stacked, i, cfg_key, without=(),
                      state_dtype=jnp.float32):
    c = dict(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        w = _at(stacked, i)
        x, _, h = mamba_layer(c, x, w["a"], without, state_dtype)
        W = c["sliding_window"] + (1 if "window" in without else 0)
        x, _, _ = attn_layer(c, x, w["b"], 2 * i + 1, W, Q_BLOCK, without)
        return x, h


@partial(jax.jit, static_argnames=("cfg_key", "without", "state_dtype"))
def _mid_jit(x, w, cfg_key, without=(), state_dtype=jnp.float32):
    c = dict(cfg_key)
    L = c["num_hidden_layers"]
    with jax.default_matmul_precision(HIGHEST):
        x, m, h = mamba_layer(c, x, w["a"], without, state_dtype)
        x, k, v = attn_layer(c, x, w["b"], L // 2 + 1, None, Q_BLOCK,
                             without)
        return x, m, k, v, h


@partial(jax.jit, static_argnames=("cfg_key", "without"))
def _cross_pair_jit(x, stacked, mid_b, m, k, v, cfg_key, i, without=()):
    c = dict(cfg_key)
    L = c["num_hidden_layers"]
    with jax.default_matmul_precision(HIGHEST):
        return cross_pair(c, x, _at(stacked, i), m, k, v, L // 2 + 2 + 2 * i,
                          mid_b, Q_BLOCK, without)


def _hidden(weights, c: Mapping, ids, without=(), state_dtype=jnp.float32):
    """ids [Tp] -> (the stream after the last layer [Tp, D], every Mamba
    layer's state after the LAST row)."""
    z, key = _sizes(c), _cfg_key(c)
    without = tuple(without)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    hs = []
    for i in range(z["ns"]):
        x, h = _self_pair_jit(x, weights["self"], jnp.int32(i), key, without,
                              state_dtype)
        hs.append(h)
    x, m, k, v, h = _mid_jit(x, weights["mid"], key, without, state_dtype)
    hs.append(h)
    for i in range(z["nc"]):
        x = _cross_pair_jit(x, weights["cross"], weights["mid"]["b"], m, k,
                            v, key, jnp.int32(i), without)
    return x, hs


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, nw, nb, embed, start, cfg_key, n_last):
    c = dict(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _ln(rows, nw, nb, float(c["layer_norm_eps"])) @ _f(embed).T


def _padded(tokens, pad_to):
    """`tokens` padded on the right with zeros to a length of few
    classes (a class is a compile of every pair): the next power of two
    times `pad_to`, multiples of `4 pad_to` past `8 pad_to`."""
    T = len(tokens)
    Tp, step = pad_to, 4 * pad_to
    while Tp < T:
        Tp *= 2
    if Tp > 2 * step:
        Tp = -(-T // step) * step
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    return ids


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO,
                         without=(), state_dtype=jnp.float32):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache, every state from zero).
    The sequence is padded on the right (`_padded`): nothing here looks
    ahead.  `without`: pieces left out or changed
    (the tests show that each is in the program)."""
    x, _ = _hidden(weights, c, _padded(tokens, pad_to), without, state_dtype)
    return _tail_jit(x, weights["norm_f_w"], weights["norm_f_b"],
                     weights["embed"], jnp.int32(start), _cfg_key(c), n)


def states(weights, c: Mapping, tokens: Sequence[int],
           state_dtype=jnp.float32) -> np.ndarray:
    """The state of every Mamba layer after the last of `tokens`, ONE
    sequence from zero states with no padding: [Mamba layers, d_state,
    d_inner] float32."""
    _, hs = _hidden(weights, c, np.asarray(tokens, np.int32),
                    state_dtype=state_dtype)
    return np.stack([np.asarray(h, np.float32) for h in hs])


@partial(jax.jit, static_argnames=("cfg_key", "n"))
def _deficits_jit(x, nw, nb, embed, start, served, cfg_key, n):
    """max - chosen of the logits of rows start .. start + n - 1, the
    vocabulary taken in `V_BLOCKS` slices of the tied table so that
    neither a float32 copy of it nor [n, V] logits is ever whole."""
    c = dict(cfg_key)
    V = embed.shape[0]
    vb = V // next(b for b in range(V_BLOCKS, 0, -1) if V % b == 0)
    with jax.default_matmul_precision(HIGHEST):
        h = _ln(lax.dynamic_slice_in_dim(x, start, n, 0), nw, nb,
                float(c["layer_norm_eps"]))

        def one(carry, j):
            top, chosen = carry
            lg = h @ _f(lax.dynamic_slice_in_dim(embed, j * vb, vb, 0)).T
            at = served - j * vb
            here = jnp.take_along_axis(
                lg, jnp.clip(at, 0, vb - 1)[:, None], -1)[:, 0]
            return (jnp.maximum(top, lg.max(-1)),
                    jnp.where((at >= 0) & (at < vb), here, chosen)), None

        (top, chosen), _ = lax.scan(
            one, (jnp.full((n,), -jnp.inf), jnp.zeros((n,))),
            jnp.arange(V // vb))
    return top - chosen


def served_token_deficits(weights, c: Mapping, prompt: Sequence[int],
                          served: Sequence[int]) -> np.ndarray:
    """For each served token, how far its reference logit lies under the
    reference maximum, given the served prefix (0 where the reference
    would have chosen the same token).  `logits_for_positions`' pass
    with the head taken in slices of the vocabulary."""
    seq = list(prompt) + list(served[:-1])
    x, _ = _hidden(weights, c, _padded(seq, PAD_TO))
    return np.asarray(_deficits_jit(
        x, weights["norm_f_w"], weights["norm_f_b"], weights["embed"],
        jnp.int32(len(prompt) - 1), jnp.asarray(served, jnp.int32),
        _cfg_key(c), len(served)), np.float64)
