"""Plain reference for the decoder of Mamba-1 layers with normed Delta, B
and C beside a few full-attention layers of ONE K/V head, a SwiGLU after
every mixer and a tied head (`model_type` `jamba` with `num_experts` 1:
AI21-Jamba2-3B's block).

On one sequence in float32 under
`jax.default_matmul_precision("highest")`, with `D` = hidden_size, `RMS`
an RMSNorm with a learned weight (eps rms_norm_eps), `L` =
num_hidden_layers, `l` = 0..L-1, NO positions anywhere:

  x = E[token]                                  tied with the head
  u = RMS_in,l(x);  x = x + Mixer_l(u)
  u = RMS_ff,l(x);  x = x + (silu(u W_gate) * (u W_up)) W_down
  logits = RMS_f(x) E^T
  Mixer_l:  l % attn_layer_period == attn_layer_offset : Attention_l(u)
            otherwise                                   : Mamba_l(u)
  Mamba(u):  [xs | z] = u W_in;  xc = silu(conv(xs) + b_conv), a causal
      depthwise convolution of mamba_d_conv taps (zeros before the
      first token; written as a sum over shifted rows);
      [dt | B | C] = xc W_x (mamba_dt_rank | d_state | d_state);
      dt = RMS_dt(dt), B = RMS_b(B), C = RMS_c(C);
      Delta = softplus(dt W_dt + b_dt);
      TOKEN BY TOKEN, h [d_state, d_inner] = 0 before the first token,
        h = exp(Delta_t A) * h + (Delta_t xc_t) B_t,  A = -exp(A_log);
        y_t = sum_n h[n] C_t[n] + Dskip * xc_t;
      out = (y * silu(z)) W_out.
  Attention(u):  q = u W_q in num_attention_heads heads of hd, k = u W_k
      and v = u W_v in num_key_value_heads heads (ONE at the published
      sizes) that the query heads share; causal softmax(q k^T hd^-1/2)
      v; W_o.  No rotary, no learned positions, no window.

The weights are the PROGRAM's own tree, as its `init_params` lays it
(`mamba`: the Mamba layers stacked in layer order; `attn`: the attention
layers stacked), in the program's bf16: `init_weights` draws it once and
the family hands the same buffers to the engine, because a second copy
of 6 GB does not fit the chip beside 4.8 GB of state and the pools.
This file takes a layer at a time up to float32 inside a jitted call.

No kernels, no cache, no chunked scan, no batching of requests, no code
of the program under test; `selective_scan`, `_rope`, `_padded` and
`_at` are the sibling reference's of `sambay_decoder.py` (the same
recurrence at the same widths), `_attention` `latent_moe_decoder.py`'s.  Departures from the published modelling
code, each for memory and none for arithmetic: attention runs in blocks
of `Q_BLOCK` queries (`lax.map`), the vocabulary in `V_BLOCKS` slices
(`served_token_deficits` never holds [n, V] logits), the sequence is
padded on the right to one of a few lengths (`_padded`; nothing here
looks ahead), the published `use_mamba_kernels` fused path is the
recurrence written out, and `num_experts` 1 is a dense feed-forward
with no router.  What the published config does not say and this file
had to choose is the configuration file's `assumed`.

`init_as_trainer` / `adamw_trajectory` raise: there is no train cell.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference.latent_moe_decoder import _attention  # the siblings'
from reference.sambay_decoder import (               # plain parts
    _at, _f, _padded, _rope, selective_scan)

HIGHEST = "highest"
Q_BLOCK = 256
PAD_TO = 1024
V_BLOCKS = 8


def _sizes(c: Mapping) -> Dict[str, int]:
    D, H = c["hidden_size"], c["num_attention_heads"]
    if c.get("num_experts", 1) != 1 or c.get("sliding_window") is not None:
        raise ValueError("mamba_mqa_decoder: dense feed-forwards and full "
                         "attention alone (num_experts 1, no sliding_window)")
    return dict(
        D=D, H=H, kvH=c["num_key_value_heads"],
        hd=c.get("head_dim") or D // H, F=c["intermediate_size"],
        V=c["vocab_size"], L=c["num_hidden_layers"], N=c["mamba_d_state"],
        K=c["mamba_d_conv"], C=c["mamba_expand"] * D, R=c["mamba_dt_rank"])


def layer_kinds(c: Mapping) -> List[str]:
    """`attention` or `mamba`, a layer."""
    period, offset = c["attn_layer_period"], c["attn_layer_offset"]
    return ["attention" if l % period == offset else "mamba"
            for l in range(c["num_hidden_layers"])]


def shapes(c: Mapping) -> Dict[str, Any]:
    """The full model's shapes, a kind's layers stacked."""
    z = _sizes(c)
    D, C, N, F, R = z["D"], z["C"], z["N"], z["F"], z["R"]
    A, Akv = z["H"] * z["hd"], z["kvH"] * z["hd"]
    block = {"norm_in": (D,), "norm_ff": (D,), "w_gate": (D, F),
             "w_up": (D, F), "w_down": (F, D)}
    mamba = dict(block, w_in=(D, 2 * C), conv_w=(z["K"], C), conv_b=(C,),
                 w_x=(C, R + 2 * N), dt_norm=(R,), b_norm=(N,), c_norm=(N,),
                 w_dt=(R, C), b_dt=(C,), A_log=(N, C), Dskip=(C,),
                 w_out=(C, D))
    attn = dict(block, wq=(D, A), wk=(D, Akv), wv=(D, Akv), wo=(A, D))
    kinds = layer_kinds(c)
    stack = lambda n, t: {k: (n,) + s for k, s in t.items()}
    return {"embed": (z["V"], D),
            "mamba": stack(kinds.count("mamba"), mamba),
            "attn": stack(kinds.count("attention"), attn),
            "norm_f": (D,)}


def param_counts(c: Mapping) -> Dict[str, int]:
    """Parameters by kind of layer and in all, from `shapes`."""
    sh = shapes(c)
    kinds = layer_kinds(c)
    n = lambda t: sum(int(np.prod(s)) for s in t.values())
    out = {"mamba_layer": n(sh["mamba"]) // kinds.count("mamba"),
           "attn_layer": n(sh["attn"]) // kinds.count("attention"),
           "embed": int(np.prod(sh["embed"])), "norm_f": sh["norm_f"][0]}
    out["total"] = (n(sh["mamba"]) + n(sh["attn"]) + out["embed"]
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
    norm weights ones, the convolution's bias zeros (the decays and
    `Dskip` float32)."""
    z, std = _sizes(c), _std(c)
    sh = shapes(c)

    def leaf(key, name, shape):
        if "norm" in name:
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
        if name == "conv_b":
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
    raise NotImplementedError("mamba_mqa_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("mamba_mqa_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f(w)


def _eps(c: Mapping) -> float:
    return float(c["rms_norm_eps"])


def _ffn(c, x, w):
    u = _rms(x, w["norm_ff"], _eps(c))
    return x + (jax.nn.silu(u @ _f(w["w_gate"])) * (u @ _f(w["w_up"]))) \
        @ _f(w["w_down"])


def mamba(c: Mapping, u, w, without=(), state_dtype=jnp.float32):
    """u [T, D], normed -> (the mixer's output [T, D], the state after
    the last row [N, C])."""
    z = _sizes(c)
    T, C, N, R, K = u.shape[0], z["C"], z["N"], z["R"], z["K"]
    xs, gate = jnp.split(u @ _f(w["w_in"]), 2, -1)
    xp = jnp.concatenate([jnp.zeros((K - 1, C), xs.dtype), xs], 0)
    xc = jax.nn.silu(sum(xp[j:j + T] * _f(w["conv_w"][j]) for j in range(K))
                     + _f(w["conv_b"]))
    dbc = xc @ _f(w["w_x"])
    dt, Bt, Ct = dbc[:, :R], dbc[:, R:R + N], dbc[:, R + N:]
    if "dt_norm" not in without:
        dt = _rms(dt, w["dt_norm"], _eps(c))
    if "bc_norms" not in without:
        Bt = _rms(Bt, w["b_norm"], _eps(c))
        Ct = _rms(Ct, w["c_norm"], _eps(c))
    dt = dt @ _f(w["w_dt"])
    if "dt_bias" not in without:
        dt = dt + _f(w["b_dt"])
    y, h = selective_scan(jax.nn.softplus(dt), xc, Bt, Ct,
                          -jnp.exp(_f(w["A_log"])), state_dtype)
    if "dskip" not in without:
        y = y + _f(w["Dskip"]) * xc
    return (y * jax.nn.silu(gate)) @ _f(w["w_out"]), h


def attention(c: Mapping, u, w, q_block, without=()):
    """u [T, D], normed -> [T, D]: every query head over the K/V head of
    its group, every key up to its own position, at scale hd^-1/2."""
    z = _sizes(c)
    T, H, kvH, hd = u.shape[0], z["H"], z["kvH"], z["hd"]
    q = (u @ _f(w["wq"])).reshape(T, H, hd)
    k = (u @ _f(w["wk"])).reshape(T, kvH, hd)
    v = (u @ _f(w["wv"])).reshape(T, kvH, hd)
    if "no_rotary" in without:
        q, k = _rope(q), _rope(k)
    k, v = (jnp.repeat(a, H // kvH, axis=1) for a in (k, v))
    qb = T if T <= q_block else math.gcd(T, q_block)
    return _attention(q, k, v, qb) @ _f(w["wo"])


def mamba_layer(c, x, w, without=(), state_dtype=jnp.float32):
    out, h = mamba(c, _rms(x, w["norm_in"], _eps(c)), w, without,
                   state_dtype)
    return _ffn(c, x + out, w), h


def attn_layer(c, x, w, q_block, without=()):
    out = attention(c, _rms(x, w["norm_in"], _eps(c)), w, q_block, without)
    return _ffn(c, x + out, w)


_KEEP = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "intermediate_size", "vocab_size", "rms_norm_eps",
         "num_hidden_layers", "attn_layer_period", "attn_layer_offset",
         "mamba_d_state", "mamba_d_conv", "mamba_expand", "mamba_dt_rank")


def _cfg_key(c: Mapping) -> tuple:
    return tuple((k, c[k]) for k in _KEEP) + (
        ("head_dim", c.get("head_dim")),)


@partial(jax.jit, static_argnames=("cfg_key", "without", "state_dtype"))
def _mamba_jit(x, stacked, i, cfg_key, without=(), state_dtype=jnp.float32):
    with jax.default_matmul_precision(HIGHEST):
        return mamba_layer(dict(cfg_key), x, _at(stacked, i), without,
                           state_dtype)


@partial(jax.jit, static_argnames=("cfg_key", "without"))
def _attn_jit(x, stacked, i, cfg_key, without=()):
    with jax.default_matmul_precision(HIGHEST):
        return attn_layer(dict(cfg_key), x, _at(stacked, i), Q_BLOCK,
                          without)


def _hidden(weights, c: Mapping, ids, without=(), state_dtype=jnp.float32):
    """ids [Tp] -> (the stream after the last layer [Tp, D], every Mamba
    layer's state after the LAST row)."""
    key, without = _cfg_key(c), tuple(without)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    hs, n = [], {"mamba": 0, "attention": 0}
    for kind in layer_kinds(c):
        i = jnp.int32(n[kind])
        n[kind] += 1
        if kind == "mamba":
            x, h = _mamba_jit(x, weights["mamba"], i, key, without,
                              state_dtype)
            hs.append(h)
        else:
            x = _attn_jit(x, weights["attn"], i, key, without)
    return x, hs


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, embed, start, cfg_key, n_last):
    c = dict(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, _eps(c)) @ _f(embed).T


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO,
                         without=(), state_dtype=jnp.float32):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache, every state from zero).
    The sequence is padded on the right (`_padded`): nothing here looks
    ahead.  `without`: pieces left out or changed (the tests show that
    each is in the program)."""
    x, _ = _hidden(weights, c, _padded(tokens, pad_to), without, state_dtype)
    return _tail_jit(x, weights["norm_f"], weights["embed"],
                     jnp.int32(start), _cfg_key(c), n)


def states(weights, c: Mapping, tokens: Sequence[int],
           state_dtype=jnp.float32) -> np.ndarray:
    """The state of every Mamba layer after the last of `tokens`, ONE
    sequence from zero states with no padding: [Mamba layers, d_state,
    d_inner] float32."""
    _, hs = _hidden(weights, c, np.asarray(tokens, np.int32),
                    state_dtype=state_dtype)
    return np.stack([np.asarray(h, np.float32) for h in hs])


@partial(jax.jit, static_argnames=("cfg_key", "n"))
def _deficits_jit(x, norm_f, embed, start, served, cfg_key, n):
    """max - chosen of the logits of rows start .. start + n - 1, the
    vocabulary taken in `V_BLOCKS` slices of the tied table so that
    neither a float32 copy of it nor [n, V] logits is ever whole."""
    c = dict(cfg_key)
    V = embed.shape[0]
    vb = V // next(b for b in range(V_BLOCKS, 0, -1) if V % b == 0)
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(lax.dynamic_slice_in_dim(x, start, n, 0), norm_f, _eps(c))

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
        x, weights["norm_f"], weights["embed"],
        jnp.int32(len(prompt) - 1), jnp.asarray(served, jnp.int32),
        _cfg_key(c), len(served)), np.float64)
