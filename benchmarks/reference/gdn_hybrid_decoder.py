"""Plain reference for the hybrid decoder of gated delta-rule layers and
full multi-head attention layers in post-norm blocks (`model_type`
`olmo_hybrid`: Olmo-Hybrid-7B's block).

On one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`, layer l of
`layer_types[:num_hidden_layers]`:

  a = x + RMSNorm(Mixer(x); attn_norm)       the norm is on the mixer's
  y = a + RMSNorm(FF(a); ffn_norm)           OUTPUT; Mixer and FF read
  FF(h) = (silu(h W1) * h W3) W2             the raw stream
  linear_attention (a gated delta rule), H heads, keys of dk, values of
    dv, per head:
      q~ = x Wq, k~ = x Wk (H dk wide), v~ = x Wv (H dv wide); on each a
      causal depthwise convolution of `linear_conv_kernel_dim` taps over
      time (zeros before the first token; written as a sum over shifted
      rows), no bias, then SiLU; q, k L2-normalised a head
      (x / sqrt(sum x^2 + 1e-6)), q times dk^-1/2;
      beta = 2 sigmoid(x Wb) (`linear_allow_neg_eigval`; sigmoid alone
      where it is false); g = -exp(A_log) softplus(x Wa + dt_bias),
      alpha = exp(g), ONE number a head a token; then TOKEN BY TOKEN
        S' = alpha_t S;  u = beta_t (v_t - S'^T k_t);  S = S' + k_t u^T;
        o_t = S^T q_t,   S [dk, dv] = 0 before the first token;
      y = (RMSNorm_dv(o; o_norm) * silu(x Wg)) Wo.
  full_attention, H heads and kvH K/V heads of hd:
      q = RMSNorm(x Wq; q_norm), k = RMSNorm(x Wk; k_norm), each over its
      WHOLE width (all heads together), then split into heads; v = x Wv;
      `rope_parameters.rope_theta` null: NO rotation (a number: the
      rotate-half form over all hd channels at theta^(-2i/hd)); K/V
      heads repeated H / kvH times; causal softmax(q k^T / sqrt(hd)) v
      under an explicit mask; Wo.
  after the last layer: RMSNorm (norm_f), logits = x W_head (untied).

No kernels, no cache, no chunked recurrence, no batching of requests,
no code of the program under test; `_rms`, `_attention` and `_swiglu`
are the sibling reference's.  Departures from the published
description, each for memory and none for arithmetic: attention runs in
blocks of `Q_BLOCK` queries (`lax.map`), and the sequence is padded on
the right to a multiple of `PAD_TO` (nothing here looks ahead).  What
the published config does not say and this file had to choose is the
configuration file's `assumed`.

`init_as_trainer` / `adamw_trajectory` raise: there is no train cell.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference.latent_moe_decoder import (  # the sibling's plain parts
    _attention, _rms, _swiglu)

HIGHEST = "highest"
Q_BLOCK = 512
PAD_TO = 512
V_BLOCKS = 8
L2_EPS = 1e-6

FF_KEYS = ("attn_norm", "ffn_norm", "w_gate", "w_up", "w_down")
GDN_KEYS = ("wq", "wk", "wv", "conv_q", "conv_k", "conv_v", "A_log",
            "dt_bias", "wa", "wb", "wg", "o_norm", "wo")
ATTN_KEYS = ("wq", "wk", "wv", "q_norm", "k_norm", "wo")
KINDS = ("linear_attention", "full_attention")


def _sizes(c: Mapping) -> Dict[str, int]:
    H = c["num_attention_heads"]
    return dict(
        D=c["hidden_size"], H=H, kvH=c["num_key_value_heads"],
        hd=c.get("head_dim") or c["hidden_size"] // H,
        Hg=c["linear_num_value_heads"], dk=c["linear_key_head_dim"],
        dv=c["linear_value_head_dim"], K=c["linear_conv_kernel_dim"],
        F=c["intermediate_size"], V=c["vocab_size"],
        L=c["num_hidden_layers"])


def is_attention(c: Mapping, i: int) -> bool:
    return c["layer_types"][i] == "full_attention"


def shapes(c: Mapping) -> Dict[str, Any]:
    """The full model's shapes."""
    z = _sizes(c)
    D, Hg = z["D"], z["Hg"]
    Wk, Wv, A, Akv = Hg * z["dk"], Hg * z["dv"], z["H"] * z["hd"], \
        z["kvH"] * z["hd"]
    ff = {"attn_norm": (D,), "ffn_norm": (D,), "w_gate": (D, z["F"]),
          "w_up": (D, z["F"]), "w_down": (z["F"], D)}
    gdn = {"wq": (D, Wk), "wk": (D, Wk), "wv": (D, Wv),
           "conv_q": (z["K"], Wk), "conv_k": (z["K"], Wk),
           "conv_v": (z["K"], Wv), "A_log": (Hg,), "dt_bias": (Hg,),
           "wa": (D, Hg), "wb": (D, Hg), "wg": (D, Wv),
           "o_norm": (z["dv"],), "wo": (Wv, D)}
    attn = {"wq": (D, A), "wk": (D, Akv), "wv": (D, Akv), "q_norm": (A,),
            "k_norm": (Akv,), "wo": (A, D)}
    return {"embed": (z["V"], D),
            "layers": [dict(ff, **(attn if is_attention(c, i) else gdn))
                       for i in range(z["L"])],
            "norm_f": (D,), "lm_head": (D, z["V"])}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights from `--seed`, drawn on the device in one
    jitted call: normal(0, initializer_range) matrices (the convolution
    taps too), unit norm vectors, `A_log = log U(1, 16)` and
    `dt_bias = softplus^-1(dt)`, `dt` log-uniform in [1e-3, 1e-1], a
    head (float32; the file's `assumed` says why)."""
    z, std = _sizes(c), _std(c)
    sh = shapes(c)

    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def draw(key, shape):
            return jax.random.normal(key, shape, dtype) \
                * jnp.asarray(std, dtype)

        layers = []
        for i, lk in enumerate(jax.random.split(k_layers, z["L"])):
            names = FF_KEYS + (ATTN_KEYS if is_attention(c, i) else GDN_KEYS)
            ks = dict(zip(names, jax.random.split(lk, len(names))))
            w = {}
            for name in names:
                shape = sh["layers"][i][name]
                if name.endswith("norm"):
                    w[name] = jnp.ones(shape, dtype)
                elif name == "A_log":
                    w[name] = jnp.log(jax.random.uniform(
                        ks[name], shape, jnp.float32, 1.0, 16.0))
                elif name == "dt_bias":
                    dt = jnp.exp(jax.random.uniform(
                        ks[name], shape, jnp.float32, np.log(1e-3),
                        np.log(1e-1)))
                    w[name] = dt + jnp.log(-jnp.expm1(-dt))
                else:
                    w[name] = draw(ks[name], shape)
            layers.append(w)
        return {"embed": draw(k_embed, sh["embed"]), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": draw(k_head, sh["lm_head"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("gdn_hybrid_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("gdn_hybrid_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _f(a):
    return a.astype(jnp.float32)


def _conv_silu(x, w):
    """x [T, C], taps w [K, C] (w[K-1] on the current row): causal
    depthwise convolution over time, zeros before the first row, SiLU."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return jax.nn.silu(sum(xp[j:j + T] * _f(w[j]) for j in range(K)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def delta_rule(q, k, v, alpha, beta, state_dtype=jnp.float32):
    """The gated delta rule token by token.  q, k [T, H, dk]; v
    [T, H, dv]; alpha, beta [T, H] -> (o [T, H, dv], the state after the
    last token [H, dk, dv]).  The two products with the state are
    written as multiply-and-sum (float32 as it stands, on any backend).
    `state_dtype`: what the state is rounded to between tokens (the
    tests' bf16 mutilation; float32 changes nothing)."""
    H, dk = q.shape[1:]

    def step(S, t):
        qt, kt, vt, at, bt = t
        Sd = at[:, None, None] * _f(S)                       # alpha S
        u = bt[:, None] * (vt - jnp.sum(Sd * kt[..., None], axis=1))
        S = Sd + kt[..., None] * u[:, None, :]               # + k u^T
        return S.astype(state_dtype), jnp.sum(S * qt[..., None], axis=1)

    S0 = jnp.zeros((H, dk, v.shape[-1]), state_dtype)
    S, o = lax.scan(step, S0, (q, k, v, alpha, beta), unroll=8)
    return o, S


def gdn(c: Mapping, x, w, without=(), state_dtype=jnp.float32):
    """The gated delta-rule mixer on the raw stream x [T, D] -> (its
    output [T, D], the state after the last row [H, dk, dv])."""
    z = _sizes(c)
    T, H, dk, dv = x.shape[0], z["Hg"], z["dk"], z["dv"]
    q = _l2(_conv_silu(x @ _f(w["wq"]), w["conv_q"]).reshape(T, H, dk)) \
        * dk ** -0.5
    k = _l2(_conv_silu(x @ _f(w["wk"]), w["conv_k"]).reshape(T, H, dk))
    v = _conv_silu(x @ _f(w["wv"]), w["conv_v"]).reshape(T, H, dv)
    beta = jax.nn.sigmoid(x @ _f(w["wb"]))
    if c.get("linear_allow_neg_eigval", False) and "beta_x2" not in without:
        beta = 2.0 * beta
    g = -jnp.exp(_f(w["A_log"])) * jax.nn.softplus(
        x @ _f(w["wa"]) + _f(w["dt_bias"]))                  # [T, H]
    o, S = delta_rule(q, k, v, jnp.exp(g), beta, state_dtype)
    o = _rms(o, w["o_norm"], float(c["rms_norm_eps"]))
    if "gate" not in without:
        o = o * jax.nn.silu(x @ _f(w["wg"])).reshape(T, H, dv)
    return o.reshape(T, H * dv) @ _f(w["wo"]), S


def _rope(x, pos, theta):
    """x [T, heads, hd], rotate-half: the pair (x[i], x[i + hd/2]) turned
    by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = _f(pos)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(f), jnp.sin(f)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def rope_theta(c: Mapping):
    return (c.get("rope_parameters") or {}).get("rope_theta")


def attention(c: Mapping, x, w, q_block, without=()):
    """Full attention on the raw stream x [T, D]."""
    z = _sizes(c)
    T, H, kvH, hd = x.shape[0], z["H"], z["kvH"], z["hd"]
    eps = float(c["rms_norm_eps"])
    q, k = x @ _f(w["wq"]), x @ _f(w["wk"])
    if "qk_norm_over_the_width" in without:      # a head at a time
        q = (_rms(q.reshape(T, H, hd), jnp.ones((hd,)), eps)
             * _f(w["q_norm"]).reshape(H, hd))
        k = (_rms(k.reshape(T, kvH, hd), jnp.ones((hd,)), eps)
             * _f(w["k_norm"]).reshape(kvH, hd))
    else:
        q = _rms(q, w["q_norm"], eps).reshape(T, H, hd)
        k = _rms(k, w["k_norm"], eps).reshape(T, kvH, hd)
    v = (x @ _f(w["wv"])).reshape(T, kvH, hd)
    theta = rope_theta(c)
    if theta is not None:
        pos = jnp.arange(T)
        q, k = _rope(q, pos, float(theta)), _rope(k, pos, float(theta))
    k = jnp.repeat(k, H // kvH, axis=1)
    v = jnp.repeat(v, H // kvH, axis=1)
    return _attention(q, k, v, q_block) @ _f(w["wo"])


def block(c: Mapping, x, w, q_block=Q_BLOCK, without=(),
          state_dtype=jnp.float32):
    """One decoder block on one sequence x [T, D] (float32); which mixer
    it has is read off its weights.  Returns (x, the delta-rule state
    after the last row or None).  `without`: pieces left out or changed
    (the tests show that each is in the program)."""
    eps = float(c["rms_norm_eps"])
    S = None

    def mixer(h):
        nonlocal S
        if "A_log" not in w:
            return attention(c, h, w, q_block, without)
        out, S = gdn(c, h, w, without, state_dtype)
        return out

    ff = lambda h: _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    if "post_norm" in without:                   # a pre-norm block
        x = x + mixer(_rms(x, w["attn_norm"], eps))
        return x + ff(_rms(x, w["ffn_norm"], eps)), S
    x = x + _rms(mixer(x), w["attn_norm"], eps)
    return x + _rms(ff(x), w["ffn_norm"], eps), S


@partial(jax.jit, static_argnames=("cfg_key", "without", "state_dtype"))
def _block_jit(x, w, cfg_key, without=(), state_dtype=jnp.float32):
    with jax.default_matmul_precision(HIGHEST):
        return block(_cfg(cfg_key), x, w, without=without,
                     state_dtype=state_dtype)


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, lm_head, start, cfg_key, n_last):
    c = _cfg(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, float(c["rms_norm_eps"])) @ _f(lm_head)


_KEEP = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "linear_num_key_heads", "linear_num_value_heads",
         "linear_key_head_dim", "linear_value_head_dim",
         "linear_conv_kernel_dim", "linear_allow_neg_eigval",
         "intermediate_size", "vocab_size", "rms_norm_eps",
         "num_hidden_layers")


def _cfg_key(c: Mapping) -> tuple:
    return tuple((k, c[k]) for k in _KEEP) + (
        ("head_dim", c.get("head_dim")), ("rope_theta", rope_theta(c)))


def _cfg(key: tuple) -> dict:
    c = dict(key)
    c["rope_parameters"] = {"rope_theta": c.pop("rope_theta")}
    return c


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO,
                         without=(), state_dtype=jnp.float32):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache, every state from zero).
    The sequence is padded on the right to a multiple of `pad_to`:
    nothing here looks ahead."""
    T = len(tokens)
    Tp = -(-T // pad_to) * pad_to
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for w in weights["layers"]:
        x, _ = _block_jit(x, w, key, tuple(without), state_dtype)
    return _tail_jit(x, weights["norm_f"], weights["lm_head"],
                     jnp.int32(start), key, n)


def states(weights, c: Mapping, tokens: Sequence[int]) -> np.ndarray:
    """The state of every delta-rule layer after the last of `tokens`,
    ONE sequence from zero states with no padding (at most `Q_BLOCK`
    tokens): [delta-rule layers, H, dk, dv] float32."""
    assert len(tokens) <= Q_BLOCK, len(tokens)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    out = []
    for w in weights["layers"]:
        x, S = _block_jit(x, w, key)
        if S is not None:
            out.append(np.asarray(S))
    return np.stack(out)


@partial(jax.jit, static_argnames=("cfg_key", "n"))
def _deficits_jit(x, norm_f, lm_head, start, served, cfg_key, n):
    """max - chosen of the logits of rows start .. start + n - 1, the
    vocabulary taken in `V_BLOCKS` slices so that neither a float32 copy
    of the head nor [n, V] logits is ever whole."""
    c = _cfg(cfg_key)
    V = lm_head.shape[1]
    vb = V // next(b for b in range(V_BLOCKS, 0, -1) if V % b == 0)
    with jax.default_matmul_precision(HIGHEST):
        h = _rms(lax.dynamic_slice_in_dim(x, start, n, 0), norm_f,
                 float(c["rms_norm_eps"]))

        def one(carry, j):
            top, chosen = carry
            lg = h @ _f(lax.dynamic_slice_in_dim(lm_head, j * vb, vb, 1))
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
    Tp = -(-len(seq) // PAD_TO) * PAD_TO
    ids = np.zeros((Tp,), np.int32)
    ids[:len(seq)] = np.asarray(seq, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for w in weights["layers"]:
        x, _ = _block_jit(x, w, key)
    return np.asarray(_deficits_jit(
        x, weights["norm_f"], weights["lm_head"],
        jnp.int32(len(prompt) - 1), jnp.asarray(served, jnp.int32), key,
        len(served)), np.float64)
