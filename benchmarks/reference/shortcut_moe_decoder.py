"""Plain reference for the shortcut-connected expert decoder
(`model_type` `longcat_flash`: LongCat-Flash-Chat's block): two
latent-attention sublayers and two dense feed-forwards a layer, one
expert layer on a shortcut across them, zero-compute experts in the
router.

Per layer, on one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`, N an RMS norm with weight:

    x = x + MLA_0(N(x));  h = N(x);  s = MoE(h);  x = x + FFN_0(h)
    x = x + MLA_1(N(x));  x = x + FFN_1(N(x)) + s

  MLA_i(u): cq = N_q(u Wqa) * sqrt(D / q_lora_rank)   (mla_scale_q_lora)
    q = cq Wqb -> heads of q_nope ‖ q_rope;  u Wkva -> c ‖ k_rope;
    c = N_kv(c) * sqrt(D / kv_lora_rank)               (mla_scale_kv_lora)
    rotary on q_rope and k_rope (the shared key is NOT scaled) in the
    published interleaved pairs; c Wkvb -> per head k_nope ‖ v;
    causal softmax(q kT / sqrt(nope + rope)) v; heads concatenated, Wo.
  FFN_i: SwiGLU, no bias.
  MoE(h): p = softmax(h Wr) over ALL n_routed_experts + zero_expert_num
    columns, float32; the top k of p + bias chosen (repeated argmax: no
    sort); weights routed_scaling_factor x p of the chosen, NOT
    renormalised; s = sum over the chosen of weight x (SwiGLU_e(h) for a
    routed expert, h itself for a zero-compute one).
  Final RMSNorm, untied head.

THE SHARE (guide `model-configs` section 4): the file's
`n_routed_experts` is how many routed experts this chip holds,
`deployment` says of how many (`n_routed_experts`) and which (`rank`:
experts [rank Eh, (rank+1) Eh)); what the absent experts would have
added is left out here as in the program, the zero-compute picks are
computed whole (they are where the token is, and no share's), and the
partial result goes on to the next layer.  The vocabulary is the file's
`vocab_size` (a slice of the published one).

Departures from the published description, all for memory and none for
arithmetic: attention in blocks of queries, the held experts in blocks
of `E_BLOCK` (`lax.map`), their weights drawn when a block is needed
and not kept (one key an expert; `expert_bank` gives the program its
copy), the sequence padded on the right (nothing here looks ahead).  No
kernels, no cache, no absorbed weights, no folded constants, no sorting
by expert, no code of the program under test; `_rms`, `_rope`,
`_attention` and `_swiglu` are the sibling reference's, the held
experts' draws (`expert_block`, `expert_bank`) the other sibling's.

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

from reference.kda_hybrid_decoder import (  # one key an expert, a share
    EXPERT_KEYS, expert_bank, expert_block, map_expert_blocks)
from reference.latent_moe_decoder import (  # the sibling's plain parts
    _attention, _rms, _rope, _swiglu)

HIGHEST = "highest"
Q_BLOCK = 256
PAD_TO = 1024
E_BLOCK = 4

SUB_KEYS = ("attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm",
            "wkv_b", "wo", "ffn_norm", "w_gate", "w_up", "w_down")
MOE_KEYS = ("router", "router_bias")


def _sizes(c: Mapping) -> Dict[str, int]:
    dep = c.get("deployment", {})
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        qrank=c["q_lora_rank"], rank=c["kv_lora_rank"],
        n=c["qk_nope_head_dim"], r=c["qk_rope_head_dim"],
        v=c["v_head_dim"], F=c["ffn_hidden_size"],
        Fe=c["expert_ffn_hidden_size"], Eh=c["n_routed_experts"],
        E=dep.get("n_routed_experts", c["n_routed_experts"]),
        shard=dep.get("rank", 0), Z=c["zero_expert_num"], k=c["moe_topk"],
        V=c["vocab_size"], L=c["num_layers"])


def shapes(c: Mapping) -> Dict[str, Any]:
    """The held model's shapes (the routed experts as `expert_bank`
    makes them)."""
    z = _sizes(c)
    D, H = z["D"], z["H"]
    sub = {"attn_norm": (D,), "wq_a": (D, z["qrank"]),
           "q_norm": (z["qrank"],),
           "wq_b": (z["qrank"], H * (z["n"] + z["r"])),
           "wkv_a": (D, z["rank"] + z["r"]), "kv_norm": (z["rank"],),
           "wkv_b": (z["rank"], H * (z["n"] + z["v"])),
           "wo": (H * z["v"], D), "ffn_norm": (D,),
           "w_gate": (D, z["F"]), "w_up": (D, z["F"]),
           "w_down": (z["F"], D)}
    moe = {"router": (D, z["E"] + z["Z"]), "router_bias": (z["E"] + z["Z"],),
           "w_gate": (z["Eh"], D, z["Fe"]), "w_up": (z["Eh"], D, z["Fe"]),
           "w_down": (z["Eh"], z["Fe"], D)}
    return {"embed": (z["V"], D),
            "layers": [{"sub": [sub, sub], "moe": moe}
                       for _ in range(z["L"])],
            "norm_f": (D,), "lm_head": (D, z["V"])}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights from `--seed`, drawn on the device in one
    jitted call: normal(0, initializer_range) matrices, unit norm
    vectors, a selection bias of normal(0, router_bias_scale) over ALL
    the router's columns (float32, a buffer; the file's `assumed` says
    why that scale); for each layer, under `experts`, one key for each of
    the published routed experts' draws, of which the held range is
    kept."""
    z, std = _sizes(c), _std(c)
    bias_scale = float(c["router_bias_scale"])
    sh = shapes(c)

    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def draw(key, shape):
            return jax.random.normal(key, shape, dtype) \
                * jnp.asarray(std, dtype)

        def group(key, names, of):
            ks = dict(zip(names, jax.random.split(key, len(names))))
            w = {}
            for name in names:
                if name.endswith("norm"):
                    w[name] = jnp.ones(of[name], dtype)
                elif name == "router_bias":
                    w[name] = jax.random.normal(
                        ks[name], of[name], jnp.float32) * bias_scale
                else:
                    w[name] = draw(ks[name], of[name])
            return w

        eb = min(E_BLOCK, z["Eh"])
        assert z["Eh"] % eb == 0 and z["E"] % z["Eh"] == 0, z
        layers = []
        for i, lk in enumerate(jax.random.split(k_layers, z["L"])):
            k0, k1, km = jax.random.split(lk, 3)
            of = sh["layers"][i]
            keys = jax.random.split(jax.random.fold_in(lk, 1 << 20), z["E"])
            held = keys[z["shard"] * z["Eh"]:(z["shard"] + 1) * z["Eh"]]
            layers.append({
                "sub": [group(k0, SUB_KEYS, of["sub"][0]),
                        group(k1, SUB_KEYS, of["sub"][1])],
                "moe": group(km, MOE_KEYS, of["moe"]),
                "experts": {
                    "keys": held.reshape(z["Eh"] // eb, eb),
                    "like": jnp.zeros((0, z["D"], z["Fe"]), dtype),
                    "std": jnp.float32(std)}})
        return {"embed": draw(k_embed, sh["embed"]), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": draw(k_head, sh["lm_head"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("shortcut_moe_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("shortcut_moe_decoder has no train cell")


# ---------------------------------------------------------------- forward

def mla(c: Mapping, u, w, q_block=Q_BLOCK):
    """u [T, D], the normed input -> latent attention's output [T, D]."""
    z = _sizes(c)
    T = u.shape[0]
    H, n, r, v, rank = z["H"], z["n"], z["r"], z["v"], z["rank"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(T)
    cq = _rms(u @ f(w["wq_a"]), w["q_norm"], eps)
    if c.get("mla_scale_q_lora", False):
        cq = cq * math.sqrt(z["D"] / z["qrank"])
    q = (cq @ f(w["wq_b"])).reshape(T, H, n + r)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], pos, theta)], -1)
    ckr = u @ f(w["wkv_a"])
    lat = _rms(ckr[:, :rank], w["kv_norm"], eps)
    if c.get("mla_scale_kv_lora", False):
        lat = lat * math.sqrt(z["D"] / rank)
    k_rope = _rope(ckr[:, rank:], pos, theta)
    kv = (lat @ f(w["wkv_b"])).reshape(T, H, n + v)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(k_rope[:, None], (T, H, r))], -1)
    return _attention(q, k, kv[..., n:], q_block) @ f(w["wo"])


def route(c: Mapping, h, router, bias):
    """h [T, D] -> weights [T, E + Z] float32 over ALL the router's
    columns (the published routed experts, then the zero-compute ones):
    zero where a column was not chosen, else routed_scaling_factor x its
    softmax score, as it is."""
    z = _sizes(c)
    p = jax.nn.softmax(h @ router.astype(jnp.float32), axis=-1)
    left = p + bias.astype(jnp.float32)
    chosen = jnp.zeros_like(p)
    for _ in range(z["k"]):                  # the k largest, one at a time
        pick = jax.nn.one_hot(jnp.argmax(left, -1), p.shape[-1],
                              dtype=p.dtype)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    return p * chosen * float(c["routed_scaling_factor"])


def held_experts(c: Mapping, h, weights, experts):
    """Sum over the HELD routed experts, a block at a time, of weight x
    SwiGLU_e(h); `weights` [T, E + Z] over all the router's columns."""
    z = _sizes(c)
    eb = experts["keys"].shape[1]
    lo = z["shard"] * z["Eh"]

    def one(j):
        wg, wu, wd = (a.astype(jnp.float32)
                      for a in expert_block(experts, j))
        a = jax.nn.silu(jnp.einsum("td,edf->etf", h, wg)) \
            * jnp.einsum("td,edf->etf", h, wu)
        y = jnp.einsum("etf,efd->etd", a, wd)
        wj = lax.dynamic_slice_in_dim(weights, lo + j * eb, eb, 1)
        return jnp.einsum("etd,te->td", y, wj)

    return lax.map(one, jnp.arange(experts["keys"].shape[0])).sum(0)


def moe(c: Mapping, h, w, experts):
    """The expert layer's result for h [T, D]: the held experts' part
    plus the zero-compute picks' (`zero_expert_type` identity: the
    token itself x the pick's weight)."""
    z = _sizes(c)
    weights = route(c, h, w["router"], w["router_bias"])
    return held_experts(c, h, weights, experts) \
        + weights[:, z["E"]:].sum(-1, keepdims=True) * h


def block(c: Mapping, x, w, q_block=Q_BLOCK):
    """One (double) layer on one sequence x [T, D] (float32)."""
    eps = float(c["rms_norm_eps"])
    a, b = w["sub"]
    x = x + mla(c, _rms(x, a["attn_norm"], eps), a, q_block)
    h = _rms(x, a["ffn_norm"], eps)
    s = moe(c, h, w["moe"], w["experts"])
    x = x + _swiglu(h, a["w_gate"], a["w_up"], a["w_down"])
    x = x + mla(c, _rms(x, b["attn_norm"], eps), b, q_block)
    h = _rms(x, b["ffn_norm"], eps)
    return x + _swiglu(h, b["w_gate"], b["w_up"], b["w_down"]) + s


@partial(jax.jit, static_argnames=("cfg_key",))
def _block_jit(x, w, cfg_key):
    with jax.default_matmul_precision(HIGHEST):
        return block(_cfg(cfg_key), x, w)


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, lm_head, start, cfg_key, n_last):
    c = _cfg(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, float(c["rms_norm_eps"])) \
            @ lm_head.astype(jnp.float32)


_KEEP = ("hidden_size", "num_attention_heads", "q_lora_rank",
         "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
         "v_head_dim", "ffn_hidden_size", "expert_ffn_hidden_size",
         "n_routed_experts", "zero_expert_num", "moe_topk",
         "routed_scaling_factor", "vocab_size", "rms_norm_eps",
         "rope_theta", "num_layers")


def _cfg_key(c: Mapping) -> tuple:
    """What the forward pass reads of the file, hashable."""
    dep = c.get("deployment", {})
    return tuple((k, c[k]) for k in _KEEP) + (
        ("mla_scale_q_lora", bool(c.get("mla_scale_q_lora", False))),
        ("mla_scale_kv_lora", bool(c.get("mla_scale_kv_lora", False))),
        ("initializer_range", _std(c)),
        ("deployment", (dep.get("n_routed_experts", c["n_routed_experts"]),
                        dep.get("rank", 0))))


def _cfg(key: tuple) -> dict:
    c = dict(key)
    c["deployment"] = dict(zip(("n_routed_experts", "rank"),
                               c["deployment"]))
    return c


def hidden(weights, c: Mapping, ids):
    """The stream after the last layer for ids [T] (T a multiple of the
    query block or under it): [T, D] float32."""
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for w in weights["layers"]:
        x = _block_jit(x, w, key)
    return x


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
    x = hidden(weights, c, ids)
    return _tail_jit(x, weights["norm_f"], weights["lm_head"],
                     jnp.int32(start), _cfg_key(c), n)


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
