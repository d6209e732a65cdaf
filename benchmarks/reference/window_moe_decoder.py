"""Plain reference for the sliding-window / full attention decoder with
a gated attention output and routed experts beside a shared one
(`model_type` `afmoe`: Trinity-Mini's block).

On one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`.  The embedding is scaled:
x = E[tok] * sqrt(hidden_size) (`mup_enabled`).  Held layer i is the
published layer `first_layer + i`; its kind is `layer_types` of that
index:

  u = RMSNorm(x; attn_norm)
  q = u W_q (H heads of hd), k = u W_k, v = u W_v (kvH heads),
  g = u W_g (H * hd wide: the output gate);
  q and k RMS-normalised a head over hd (q_norm, k_norm);
  sliding_attention: rotary in the rotate-half form (x1, x2 the two
      halves of a head) over all hd channels at theta^(-2i/hd), and a
      query at position i sees the keys j with i - W < j <= i
      (`sliding_window` W keys, the query's own among them);
  full_attention: NO rotary, no positions at all; keys j <= i;
  KV heads repeated H / kvH times; softmax(q kT / sqrt(hd)) v under
  the kind's mask, written out as an explicit [q, T] mask;
  o = (attention * sigmoid(g)) W_o
  a = x + RMSNorm(o; post_attn_norm);  f = RMSNorm(a; ffn_norm)
  i < num_dense_layers: m = SwiGLU(f) at `intermediate_size`
  later layers: s = sigmoid(f W_r) (float32); the
      `num_experts_per_tok` largest of s + expert_bias chosen (repeated
      argmax: no sort; one group, so no group limit); weights s of the
      chosen / (their sum + 1e-20) (`route_norm`) x `route_scale`;
      m = shared SwiGLU(f) + sum of the chosen experts' SwiGLU.
      DROPLESS: the sum runs over ALL experts with a weight of zero
      where an expert was not chosen.
  y = a + RMSNorm(m; post_ffn_norm)
  after the last layer: RMSNorm (norm_f), logits = x W_head, unscaled.

No kernels, no cache, no sorting, no batching of requests, no code of
the program under test.  Departures from the published description
(each also under `assumed` in the configuration's file):

- the window's width is read as INCLUSIVE of the query (W keys in all);
- rotate-half over all 128 channels (the row carries no partial-rotary
  key), `1e-20` in the renormalisation, no scaling of the logits under
  muP, `expert_bias` drawn normal(0, router_bias_scale): a trained
  checkpoint's is not zero;
- for memory and not for arithmetic: attention runs in blocks of
  queries, the experts in blocks of `E_BLOCK` experts and `T_BLOCK`
  tokens, and the routed experts' weights are not KEPT by this
  reference: `init_weights` keeps one key a block of experts, and
  `expert_block(experts, j)` (the sibling
  `reference/latent_moe_decoder.py`'s) draws them whenever they are
  needed; the same function gives the program its copy.

`without`, an argument of `block` and `logits_for_positions`, leaves
one named piece OUT ("gate", "post_attn_norm", "post_ffn_norm",
"qk_norm", "embed_scale", "shared", "window", "rope"): it exists for
the tier-1 tests, which show that the program, equal to the whole
reference, is far from each mutilated one.  Nothing else passes it.

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

# the sibling's plain parts; the family reaches EXPERT_KEYS, expert_bank
# and map_expert_blocks through this module
from reference.latent_moe_decoder import (  # noqa: F401
    E_BLOCK, EXPERT_KEYS, HIGHEST, PAD_TO, _experts, _rms, _swiglu,
    expert_bank, expert_block, map_expert_blocks)

Q_BLOCK = 256       # queries a block of attention: [H, 256, T] scores
T_BLOCK = 4096      # tokens a block of the routed experts
# Lengths a sequence is padded to: few, so that a sample of 40 requests
# of 128 to 17,408 tokens compiles five sets of blocks and not sixteen
# (on the chip the compiles were most of the judge's 483 s, PR 38).
PAD_LENGTHS = (1024, 2048, 4096, 8192, 18432)
ATTN_KEYS = ("attn_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo",
             "post_attn_norm", "ffn_norm", "post_ffn_norm")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
MOE_KEYS = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")
ROUTE_EPS = 1e-20
KINDS = ("sliding_attention", "full_attention")


def _sizes(c: Mapping) -> Dict[str, int]:
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        kvH=c["num_key_value_heads"], hd=c["head_dim"],
        F=c["intermediate_size"], Fe=c["moe_intermediate_size"],
        Fs=c["moe_intermediate_size"] * c["num_shared_experts"],
        E=c["num_experts"], k=c["num_experts_per_tok"], V=c["vocab_size"],
        L=c["num_hidden_layers"], Ld=c["num_dense_layers"])


def layer_kinds(c: Mapping):
    """`layer_types` of the layers that are held: `num_hidden_layers` of
    them from the published layer `first_layer` on."""
    first = int(c.get("first_layer", 0))
    return list(c["layer_types"][first:first + c["num_hidden_layers"]])


def shapes(c: Mapping) -> Dict[str, Any]:
    """The held model's shapes (the routed experts as `expert_bank`
    makes them)."""
    z = _sizes(c)
    D, hd = z["D"], z["hd"]
    attn = {"attn_norm": (D,), "wq": (D, z["H"] * hd),
            "wk": (D, z["kvH"] * hd), "wv": (D, z["kvH"] * hd),
            "wg": (D, z["H"] * hd), "q_norm": (hd,), "k_norm": (hd,),
            "wo": (z["H"] * hd, D), "post_attn_norm": (D,),
            "ffn_norm": (D,), "post_ffn_norm": (D,)}
    dense = {"w_gate": (D, z["F"]), "w_up": (D, z["F"]),
             "w_down": (z["F"], D)}
    moe = {"router": (D, z["E"]), "router_bias": (z["E"],),
           "ws_gate": (D, z["Fs"]), "ws_up": (D, z["Fs"]),
           "ws_down": (z["Fs"], D),
           "w_gate": (z["E"], D, z["Fe"]), "w_up": (z["E"], D, z["Fe"]),
           "w_down": (z["E"], z["Fe"], D)}
    return {"embed": (z["V"], D),
            "layers": [dict(attn, **(dense if i < z["Ld"] else moe))
                       for i in range(z["L"])],
            "norm_f": (D,), "lm_head": (D, z["V"])}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights: normal(0, initializer_range) matrices,
    unit norm vectors, a selection bias of normal(0, router_bias_scale)
    (float32, a buffer), drawn on the device in one jitted call; for
    each expert layer what `expert_block` draws its routed experts from,
    under `experts`."""
    z, std = _sizes(c), _std(c)
    bias_scale = float(c["router_bias_scale"])
    sh = shapes(c)

    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def draw(key, shape):
            return jax.random.normal(key, shape, dtype) \
                * jnp.asarray(std, dtype)

        layers = []
        for i, lk in enumerate(jax.random.split(k_layers, z["L"])):
            names = ATTN_KEYS + (DENSE_KEYS if i < z["Ld"] else MOE_KEYS)
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
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": draw(k_head, sh["lm_head"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("window_moe_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("window_moe_decoder has no train cell")


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


def _attention(q, k, v, window, q_block):
    """One sequence under an explicit mask: q, k, v [T, H, hd]; query i
    sees key j when j <= i and, with a window, i - window < j."""
    T, H, hd = q.shape
    qb = min(q_block, T)
    assert T % qb == 0, (T, qb)
    starts = jnp.arange(T // qb) * qb
    kpos = jnp.arange(T)

    def one(args):
        qi, s0 = args
        qpos = (s0 + jnp.arange(qb))[:, None]
        mask = kpos[None, :] <= qpos
        if window is not None:
            mask = mask & (kpos[None, :] > qpos - window)
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = lax.map(one, (q.reshape(T // qb, qb, H, hd), starts))
    return out.reshape(T, -1)


def attention(c: Mapping, kind: str, u, w, q_block, without=()):
    """The gated attention operator of a layer of `kind` on u [T, D]."""
    z = _sizes(c)
    T = u.shape[0]
    H, kvH, hd = z["H"], z["kvH"], z["hd"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    q = (u @ _f(w["wq"])).reshape(T, H, hd)
    k = (u @ _f(w["wk"])).reshape(T, kvH, hd)
    v = (u @ _f(w["wv"])).reshape(T, kvH, hd)
    if "qk_norm" not in without:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    sliding = kind == "sliding_attention"
    if sliding and "rope" not in without:
        pos = jnp.arange(T)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // kvH, axis=1)
    v = jnp.repeat(v, H // kvH, axis=1)
    window = int(c["sliding_window"]) if sliding and \
        "window" not in without else None
    o = _attention(q, k, v, window, q_block)
    if "gate" not in without:
        o = o * jax.nn.sigmoid(u @ _f(w["wg"]))
    return o @ _f(w["wo"])


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
    return w * float(c["route_scale"])


def routed(c: Mapping, h, w):
    """The routed experts' sum for h [T, D], `T_BLOCK` tokens at a
    time."""
    weights = route(c, h, w["router"], w["router_bias"])
    T = h.shape[0]
    return jnp.concatenate([
        _experts(h[t:t + T_BLOCK], weights[t:t + T_BLOCK], w["experts"])
        for t in range(0, T, T_BLOCK)])


def block(c: Mapping, kind: str, x, w, q_block=Q_BLOCK, without=()):
    """One decoder block of `kind` on one sequence x [T, D] (float32);
    which feed-forward it has is read off its weights."""
    eps = float(c["rms_norm_eps"])

    def post(name, y):
        return y if name in without else _rms(y, w[name], eps)

    u = _rms(x, w["attn_norm"], eps)
    x = x + post("post_attn_norm",
                 attention(c, kind, u, w, q_block, without))
    h = _rms(x, w["ffn_norm"], eps)
    if "router" not in w:
        m = _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    else:
        m = routed(c, h, w)
        if "shared" not in without:
            m = m + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])
    return x + post("post_ffn_norm", m)


@partial(jax.jit, static_argnames=("cfg_key", "kind", "without"))
def _block_jit(x, w, cfg_key, kind, without):
    with jax.default_matmul_precision(HIGHEST):
        return block(dict(cfg_key), kind, x, w, without=without)


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, head, start, cfg_key, n_last):
    c = dict(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, float(c["rms_norm_eps"])) @ _f(head)


_KEEP = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "intermediate_size", "moe_intermediate_size",
         "num_shared_experts", "num_experts", "num_experts_per_tok",
         "route_scale", "vocab_size", "rms_norm_eps", "rope_theta",
         "sliding_window", "num_hidden_layers", "num_dense_layers")


def _cfg_key(c: Mapping) -> tuple:
    return tuple((k, c[k]) for k in _KEEP) + (
        ("initializer_range", _std(c)),)


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = 0,
                         without=()):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache).  The sequence is padded on
    the right to the smallest of `PAD_LENGTHS` that holds it (to a
    multiple of `pad_to` where that is given, or no such length holds
    it); under a causal mask padding cannot reach an earlier position,
    and a token's experts do not depend on its neighbours."""
    T = len(tokens)
    if pad_to:
        Tp = -(-T // pad_to) * pad_to
    else:
        Tp = next((p for p in PAD_LENGTHS if p >= T),
                  -(-T // PAD_TO) * PAD_TO)
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key, without = _cfg_key(c), tuple(sorted(without))
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    if "embed_scale" not in without:
        x = x * math.sqrt(c["hidden_size"])
    for kind, w in zip(layer_kinds(c), weights["layers"]):
        x = _block_jit(x, w, key, kind, without)
    return _tail_jit(x, weights["norm_f"], weights["lm_head"],
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
