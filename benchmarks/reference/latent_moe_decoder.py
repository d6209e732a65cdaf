"""Plain reference for the latent-attention, routed-expert decoder
(`model_type` `deepseek_v3` without query compression:
kanana-2-30b-a3b-instruct-2601's block).

Per layer, on one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`:

  h = RMSNorm(x); q = h Wq -> heads of q_nope ‖ q_rope;
  h Wkva -> c ‖ k_rope; c = RMSNorm(c); rotary on q_rope and k_rope in
  the published interleaved pairs (x[2i], x[2i+1]) at frequency i, kept
  where they lie; c Wkvb -> per head k_nope ‖ v; k = k_nope ‖ k_rope;
  causal softmax(q kT / sqrt(nope + rope)) v; heads concatenated, Wo.
  Layer < first_k_dense_replace: SwiGLU.  Later layers: s = sigmoid(h Wg),
  the top k of s + bias chosen (repeated argmax: no sort), weights s of
  the chosen, renormalised (+1e-20) and scaled; y = sum of the chosen
  experts' SwiGLU + the shared experts' SwiGLU.  DROPLESS: every token
  gets every expert it chose; the sum runs over ALL experts with a
  weight that is zero where an expert was not chosen.
  Final RMSNorm, untied head.

No kernels, no cache, no absorbed weights, no sorting, no batching of
requests, no code of the program under test.  Departures from a
textbook forward pass, all for memory and none for arithmetic:
attention runs in blocks of queries and the experts in blocks of
`E_BLOCK` (`lax.map`); and the routed experts' weights are not KEPT by
this reference: `init_weights` keeps one key a layer, and
`expert_block(experts, j)` draws experts j*E_BLOCK.. from it whenever a
block is needed (the same function gives the program its copy,
`expert_bank`).  So the reference's own tree is a fraction of the
model, the program's parameters are the only full copy on the chip, and
a control's lower-precision copy fits beside what made it.

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
Q_BLOCK = 512
PAD_TO = 1024
E_BLOCK = 8

ATTN_KEYS = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
             "ffn_norm")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
MOE_KEYS = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")
EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _sizes(c: Mapping) -> Dict[str, int]:
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        rank=c["kv_lora_rank"], n=c["qk_nope_head_dim"],
        r=c["qk_rope_head_dim"], v=c["v_head_dim"],
        F=c["intermediate_size"], Fe=c["moe_intermediate_size"],
        E=c["n_routed_experts"], k=c["num_experts_per_tok"],
        Fs=c["n_shared_experts"] * c["moe_intermediate_size"],
        V=c["vocab_size"], L=c["num_hidden_layers"],
        Ld=c["first_k_dense_replace"])


def shapes(c: Mapping) -> Dict[str, Any]:
    """The full model's shapes (the routed experts as `expert_bank`
    makes them)."""
    z = _sizes(c)
    attn = {"attn_norm": (z["D"],),
            "wq": (z["D"], z["H"] * (z["n"] + z["r"])),
            "wkv_a": (z["D"], z["rank"] + z["r"]), "kv_norm": (z["rank"],),
            "wkv_b": (z["rank"], z["H"] * (z["n"] + z["v"])),
            "wo": (z["H"] * z["v"], z["D"]), "ffn_norm": (z["D"],)}
    dense = dict(attn, w_gate=(z["D"], z["F"]), w_up=(z["D"], z["F"]),
                 w_down=(z["F"], z["D"]))
    moe = dict(attn, router=(z["D"], z["E"]), router_bias=(z["E"],),
               w_gate=(z["E"], z["D"], z["Fe"]),
               w_up=(z["E"], z["D"], z["Fe"]),
               w_down=(z["E"], z["Fe"], z["D"]),
               ws_gate=(z["D"], z["Fs"]), ws_up=(z["D"], z["Fs"]),
               ws_down=(z["Fs"], z["D"]))
    return {"embed": (z["V"], z["D"]),
            "layers": [dense if i < z["Ld"] else moe
                       for i in range(z["L"])],
            "norm_f": (z["D"],), "lm_head": (z["D"], z["V"])}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def expert_block(experts, j):
    """Experts j * E_BLOCK .. of one layer: (w_gate, w_up [Eb, D, Fe],
    w_down [Eb, Fe, D]), normal(0, std).  `experts` is what
    `init_weights` keeps of a layer's routed experts and says all there
    is to say: `keys` [n_blocks], one a block; `like`, an EMPTY array
    [0, Eb, D, Fe] whose shape and dtype are a block's `w_gate`; `std`.
    A function of `experts` and j alone, so whoever calls it gets the
    same experts."""
    _, eb, d, fe = experts["like"].shape
    kg, ku, kd = jax.random.split(experts["keys"][j], 3)

    # drawn in float32 and rounded once: a draw in bf16 may round
    # differently from one compiled program to the next
    def draw(k, *shape):
        return (jax.random.normal(k, shape, jnp.float32)
                * experts["std"]).astype(experts["like"].dtype)

    return draw(kg, eb, d, fe), draw(ku, eb, d, fe), draw(kd, eb, fe, d)


def map_expert_blocks(fn, experts):
    """fn(expert_block(experts, j)) for every j, stacked: leaves
    [n_blocks, ...]."""
    return lax.map(lambda j: fn(expert_block(experts, j)),
                   jnp.arange(experts["keys"].shape[0]))


@jax.jit
def expert_bank(experts) -> Dict[str, Any]:
    """All of one layer's routed experts [E, ...], for the program."""
    blocks = map_expert_blocks(lambda b: b, experts)
    return {k: b.reshape((-1,) + b.shape[2:])
            for k, b in zip(EXPERT_KEYS, blocks)}


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights: normal(0, initializer_range) matrices,
    unit norm vectors, a selection bias of normal(0, router_bias_scale)
    (float32, a buffer), drawn on the device in one jitted call; for
    each expert layer what `expert_block` draws its routed experts
    from, under `experts`."""
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
    raise NotImplementedError("latent_moe_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("latent_moe_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, ..., r], interleaved pairs (x[2i], x[2i+1]) rotated by
    pos * theta^(-2i/r), left where they lie."""
    r = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    f = pos.astype(jnp.float32)[:, None] * inv[None, :]
    f = f.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (r // 2,))
    cos, sin = jnp.cos(f), jnp.sin(f)
    pairs = x.reshape(x.shape[:-1] + (r // 2, 2))
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     -1).reshape(x.shape)


def _attention(q, k, v, q_block):
    """Causal attention for one sequence. q, k [T, H, dk], v [T, H, dv]."""
    T, H, dk = q.shape
    qb = min(q_block, T)
    assert T % qb == 0, (T, qb)
    starts = jnp.arange(T // qb) * qb
    kpos = jnp.arange(T)

    def one(args):
        qi, s0 = args
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(dk)
        mask = (s0 + jnp.arange(qb))[:, None] >= kpos[None, :]
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = lax.map(one, (q.reshape(T // qb, qb, H, dk), starts))
    return out.reshape(T, -1)


def _swiglu(h, w_gate, w_up, w_down):
    f = lambda a: a.astype(jnp.float32)
    return (jax.nn.silu(h @ f(w_gate)) * (h @ f(w_up))) @ f(w_down)


def route(c: Mapping, h, router, bias):
    """h [T, D] -> weights [T, E] float32: zero where an expert was not
    chosen, else its renormalised, scaled sigmoid score."""
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    s = jax.nn.sigmoid(h @ router.astype(jnp.float32))
    left = s + bias.astype(jnp.float32)
    chosen = jnp.zeros_like(s)
    for _ in range(k):                       # the k largest, one at a time
        pick = jax.nn.one_hot(jnp.argmax(left, -1), E, dtype=s.dtype)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = s * chosen
    if c.get("norm_topk_prob", True):
        w = w / (w.sum(-1, keepdims=True) + 1e-20)
    return w * float(c["routed_scaling_factor"])


def _experts(h, weights, experts):
    """Sum over ALL routed experts, a block at a time, of weight x
    SwiGLU_e(h); a block's weights are drawn as it is needed."""
    eb = experts["like"].shape[1]

    def one(j):
        wg, wu, wd = (a.astype(jnp.float32)
                      for a in expert_block(experts, j))
        a = jax.nn.silu(jnp.einsum("td,edf->etf", h, wg)) \
            * jnp.einsum("td,edf->etf", h, wu)
        y = jnp.einsum("etf,efd->etd", a, wd)
        wj = lax.dynamic_slice_in_dim(weights, j * eb, eb, 1)   # [T, eb]
        return jnp.einsum("etd,te->td", y, wj)

    return lax.map(one, jnp.arange(experts["keys"].shape[0])).sum(0)


def block(c: Mapping, x, w, q_block=Q_BLOCK):
    """One decoder block on one sequence x [T, D] (float32)."""
    z = _sizes(c)
    T = x.shape[0]
    H, n, r, v, rank = z["H"], z["n"], z["r"], z["v"], z["rank"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(T)
    h = _rms(x, w["attn_norm"], eps)
    q = (h @ f(w["wq"])).reshape(T, H, n + r)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], pos, theta)], -1)
    ckr = h @ f(w["wkv_a"])
    lat = _rms(ckr[:, :rank], w["kv_norm"], eps)
    k_rope = _rope(ckr[:, rank:], pos, theta)
    kv = (lat @ f(w["wkv_b"])).reshape(T, H, n + v)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope[:, None], (T, H, r))], -1)
    x = x + _attention(q, k, kv[..., n:], q_block) @ f(w["wo"])
    h = _rms(x, w["ffn_norm"], eps)
    if "router" not in w:
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"])
    weights = route(c, h, w["router"], w["router_bias"])
    y = _experts(h, weights, w["experts"])
    return x + y + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"])


@partial(jax.jit, static_argnames=("cfg_key",))
def _block_jit(x, w, cfg_key):
    with jax.default_matmul_precision(HIGHEST):
        return block(dict(cfg_key), x, w)


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, lm_head, start, cfg_key, n_last):
    c = dict(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, float(c["rms_norm_eps"])) \
            @ lm_head.astype(jnp.float32)


def _cfg_key(c: Mapping) -> tuple:
    keep = ("hidden_size", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "n_routed_experts", "num_experts_per_tok", "n_shared_experts",
            "routed_scaling_factor", "vocab_size", "rms_norm_eps",
            "rope_theta", "num_hidden_layers", "first_k_dense_replace")
    return tuple((k, c[k]) for k in keep) + (
        ("norm_topk_prob", bool(c.get("norm_topk_prob", True))),
        ("initializer_range", _std(c)))


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache).  The sequence is padded on
    the right to a multiple of `pad_to`; under a causal mask padding
    cannot reach an earlier position, and a token's experts do not
    depend on its neighbours."""
    T = len(tokens)
    Tp = -(-T // pad_to) * pad_to
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for w in weights["layers"]:
        x = _block_jit(x, w, key)
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
