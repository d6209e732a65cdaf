"""Plain reference for the gated short-convolution / grouped-query
attention decoder with routed experts (`model_type` `lfm2_moe`:
LFM2-8B-A1B's block).

On one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`, layer l of
`layer_types[:num_hidden_layers]`:

  u = RMSNorm(x; operator_norm)
  conv:       [B, C, X] = split3(u W_in);  z = B * X;
              c_t = w[0] z_{t-2} + w[1] z_{t-1} + w[2] z_t   (zeros before
              the sequence; `conv_L_cache` taps, written as that sum of
              shifted rows); o = (C * c) W_out
  attention:  q = u W_q (H heads of hd), k = u W_k, v = u W_v (kvH heads);
              q and k RMS-normalised a head over hd (q_layernorm,
              k_layernorm) BEFORE rotary; rotary in the rotate-half form
              (x1, x2 the two halves of a head) over all hd channels at
              theta^(-2i/hd); KV heads repeated H / kvH times; causal
              softmax(q kT / sqrt(hd)) v; W_o
  h = x + o;  f = RMSNorm(h; ffn_norm)
  l < num_dense_layers: y = h + SwiGLU(f) at `intermediate_size`
  later layers: s = sigmoid(f W_r); the `num_experts_per_tok` largest of
              s + expert_bias chosen (repeated argmax: no sort); weights
              s of the chosen / (their sum + 1e-6) x routed_scaling_factor;
              y = h + sum of the chosen experts' SwiGLU.  DROPLESS: the
              sum runs over ALL experts with a weight of zero where an
              expert was not chosen.
  after the last layer: RMSNorm (embedding_norm), logits = x E^T with E
  the embedding table (tied head).

No kernels, no cache, no sorting, no batching of requests, no code of
the program under test.  Departures from the published description:

- `intermediate_size` is used as given (7168) for the two dense layers;
  the published block can round it through `block_multiple_of` /
  `block_ffn_dim_multiplier`, which the catalog's row does not carry.
- None in the arithmetic of routing: the `+ 1e-6` is kept (the program
  is handed the same constant; `models/moe.py::sigmoid_bias_top_k`).
- For memory and not for arithmetic: attention runs in blocks of
  queries and the experts in blocks of `E_BLOCK` (`lax.map`), and the
  routed experts' weights are not KEPT by this reference: `init_weights`
  keeps one key a block of experts, and `expert_block(experts, j)`
  (the sibling `reference/latent_moe_decoder.py`'s) draws them whenever
  they are needed; the same function gives the program its copy.

`init_as_trainer` / `adamw_trajectory` raise: there is no train cell.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the sibling's plain parts; the family reaches EXPERT_KEYS, expert_bank
# and map_expert_blocks through this module
from reference.latent_moe_decoder import (  # noqa: F401
    E_BLOCK, EXPERT_KEYS, HIGHEST, PAD_TO, Q_BLOCK, _attention, _experts,
    _rms, _swiglu, expert_bank, expert_block, map_expert_blocks)

CONV_KEYS = ("op_norm", "w_in", "conv", "w_out", "ffn_norm")
ATTN_KEYS = ("op_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
             "ffn_norm")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
MOE_KEYS = ("router", "router_bias")
ROUTE_EPS = 1e-6


def _sizes(c: Mapping) -> Dict[str, int]:
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        kvH=c["num_key_value_heads"], hd=c["head_dim"],
        K=c["conv_L_cache"], F=c["intermediate_size"],
        Fe=c["moe_intermediate_size"], E=c["num_experts"],
        k=c["num_experts_per_tok"], V=c["vocab_size"],
        L=c["num_hidden_layers"], Ld=c["num_dense_layers"])


def is_attention(c: Mapping, i: int) -> bool:
    return c["layer_types"][i] == "full_attention"


def shapes(c: Mapping) -> Dict[str, Any]:
    """The full model's shapes (the routed experts as `expert_bank`
    makes them); no head: it is the embedding table."""
    z = _sizes(c)
    D, hd = z["D"], z["hd"]
    conv = {"op_norm": (D,), "w_in": (D, 3 * D), "conv": (z["K"], D),
            "w_out": (D, D), "ffn_norm": (D,)}
    attn = {"op_norm": (D,), "wq": (D, z["H"] * hd),
            "wk": (D, z["kvH"] * hd), "wv": (D, z["kvH"] * hd),
            "q_norm": (hd,), "k_norm": (hd,), "wo": (z["H"] * hd, D),
            "ffn_norm": (D,)}
    dense = {"w_gate": (D, z["F"]), "w_up": (D, z["F"]),
             "w_down": (z["F"], D)}
    moe = {"router": (D, z["E"]), "router_bias": (z["E"],),
           "w_gate": (z["E"], D, z["Fe"]), "w_up": (z["E"], D, z["Fe"]),
           "w_down": (z["E"], z["Fe"], D)}
    return {"embed": (z["V"], D),
            "layers": [dict(attn if is_attention(c, i) else conv,
                            **(dense if i < z["Ld"] else moe))
                       for i in range(z["L"])],
            "norm_f": (D,)}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights: normal(0, initializer_range) matrices
    and convolution taps, unit norm vectors, a selection bias of
    normal(0, router_bias_scale) (float32, a buffer), drawn on the
    device in one jitted call; for each expert layer what `expert_block`
    draws its routed experts from, under `experts`."""
    z, std = _sizes(c), _std(c)
    bias_scale = float(c["router_bias_scale"])
    sh = shapes(c)

    def make(key):
        k_embed, k_layers = jax.random.split(key)

        def draw(key, shape):
            return jax.random.normal(key, shape, dtype) \
                * jnp.asarray(std, dtype)

        layers = []
        for i, lk in enumerate(jax.random.split(k_layers, z["L"])):
            names = (ATTN_KEYS if is_attention(c, i) else CONV_KEYS) \
                + (DENSE_KEYS if i < z["Ld"] else MOE_KEYS)
            ks = dict(zip(names, jax.random.split(lk, len(names))))
            w = {}
            for name in names:
                shape = sh["layers"][i][name]
                if name.endswith("norm"):
                    w[name] = jnp.ones(shape, dtype)
                elif name == "router_bias":
                    w[name] = jax.random.normal(
                        ks[name], shape, jnp.float32) * bias_scale
                else:
                    w[name] = draw(ks[name], shape)
            if i >= z["Ld"]:
                eb = min(E_BLOCK, z["E"])
                assert z["E"] % eb == 0, z["E"]
                w["experts"] = {
                    "keys": jax.random.split(
                        jax.random.fold_in(lk, 1 << 20), z["E"] // eb),
                    "like": jnp.zeros((0, eb, z["D"], z["Fe"]), dtype),
                    "std": jnp.float32(std)}
            layers.append(w)
        return {"embed": draw(k_embed, sh["embed"]), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype)}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("conv_moe_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("conv_moe_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _f(a):
    return a.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, heads, hd], rotate-half: the pair (x[i], x[i + hd/2]) turned
    by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = _f(pos)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(f), jnp.sin(f)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def gated_conv(u, w):
    """The convolution operator on u [T, D]."""
    T = u.shape[0]
    b, gate, xin = jnp.split(u @ _f(w["w_in"]), 3, axis=-1)
    z = b * xin
    taps = _f(w["conv"])
    K = taps.shape[0]
    zz = jnp.concatenate([jnp.zeros((K - 1, z.shape[1]), z.dtype), z], 0)
    conv = sum(taps[j] * zz[j:j + T] for j in range(K))
    return (gate * conv) @ _f(w["w_out"])


def attention(c: Mapping, u, w, q_block):
    """The attention operator on u [T, D]."""
    z = _sizes(c)
    T = u.shape[0]
    H, kvH, hd = z["H"], z["kvH"], z["hd"]
    eps, theta = float(c["norm_eps"]), float(c["rope_theta"])
    pos = jnp.arange(T)
    q = _rms((u @ _f(w["wq"])).reshape(T, H, hd), w["q_norm"], eps)
    k = _rms((u @ _f(w["wk"])).reshape(T, kvH, hd), w["k_norm"], eps)
    v = (u @ _f(w["wv"])).reshape(T, kvH, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // kvH, axis=1)
    v = jnp.repeat(v, H // kvH, axis=1)
    return _attention(q, k, v, q_block) @ _f(w["wo"])


def route(c: Mapping, h, router, bias):
    """h [T, D] -> weights [T, E] float32: zero where an expert was not
    chosen, else its renormalised, scaled sigmoid score."""
    E, k = c["num_experts"], c["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ _f(router))
    left = s + _f(bias)
    chosen = jnp.zeros_like(s)
    for _ in range(k):                       # the k largest, one at a time
        pick = jax.nn.one_hot(jnp.argmax(left, -1), E, dtype=s.dtype)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = s * chosen
    w = w / (w.sum(-1, keepdims=True) + ROUTE_EPS)
    return w * float(c["routed_scaling_factor"])


def block(c: Mapping, x, w, q_block=Q_BLOCK):
    """One decoder block on one sequence x [T, D] (float32); which
    operator and which feed-forward it has is read off its weights."""
    eps = float(c["norm_eps"])
    u = _rms(x, w["op_norm"], eps)
    x = x + (attention(c, u, w, q_block) if "wq" in w else gated_conv(u, w))
    h = _rms(x, w["ffn_norm"], eps)
    if "router" not in w:
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    return x + _experts(h, route(c, h, w["router"], w["router_bias"]),
                        w["experts"])


@partial(jax.jit, static_argnames=("cfg_key",))
def _block_jit(x, w, cfg_key):
    with jax.default_matmul_precision(HIGHEST):
        return block(dict(cfg_key), x, w)


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, embed, start, cfg_key, n_last):
    c = dict(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, float(c["norm_eps"])) @ _f(embed).T


_KEEP = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "conv_L_cache", "intermediate_size",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok",
         "routed_scaling_factor", "vocab_size", "norm_eps", "rope_theta",
         "num_hidden_layers", "num_dense_layers")


def _cfg_key(c: Mapping) -> tuple:
    return tuple((k, c[k]) for k in _KEEP) + (
        ("initializer_range", _std(c)),)


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache).  The sequence is padded on
    the right to a multiple of `pad_to`; under a causal mask and a
    causal convolution padding cannot reach an earlier position, and a
    token's experts do not depend on its neighbours."""
    T = len(tokens)
    Tp = -(-T // pad_to) * pad_to
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for w in weights["layers"]:
        x = _block_jit(x, w, key)
    return _tail_jit(x, weights["norm_f"], weights["embed"],
                     jnp.int32(start), key, n)


def served_token_deficits(weights, c: Mapping, prompt: Sequence[int],
                          served: Sequence[int]) -> np.ndarray:
    """For each served token, how far its reference logit lies under the
    reference maximum, given the served prefix (0 where the reference
    would have chosen the same token)."""
    n = len(served)
    seq = list(prompt) + list(served[:-1])
    lg = logits_for_positions(weights, c, seq, len(prompt) - 1, n)
    chosen = jnp.take_along_axis(lg, jnp.asarray(served, jnp.int32)[:, None],
                                 -1)[:, 0]
    return np.asarray(jnp.max(lg, -1) - chosen, np.float64)
