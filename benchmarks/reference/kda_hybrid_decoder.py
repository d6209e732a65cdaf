"""Plain reference for the hybrid decoder of recurrent delta-rule layers
(KDA) and latent-attention layers (MLA) with routed experts
(`model_type` `kimi_linear`: Kimi-Linear-48B-A3B-Instruct's block).

Per layer, on one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`, pre-norm residual blocks:

  KDA layer (layer number in `linear_attn_config.kda_layers`, from 1):
    h = RMSNorm(x); q~, k~, v~ = h Wq, h Wk, h Wv; on each a causal
    depthwise convolution of width K over time (zeros before the first
    token), then SiLU; q, k L2-normalised per head
    (x / sqrt(sum x^2 + 1e-6)), q times head_dim^-0.5;
    g = -exp(A_log[head]) softplus((h Wf_a) Wf_b + dt_bias), alpha = exp(g);
    beta = sigmoid(h Wbeta); then TOKEN BY TOKEN, a head at a time,
      S' = Diag(alpha_t) S;  S = S' + beta_t k_t (v_t - S'^T k_t)^T;
      o_t = S^T q_t,   S = 0 before the first token;
    y = (RMSNorm_head(o; o_norm) * sigmoid((h Wg_a) Wg_b)) Wo.
  MLA layer (the others): as `reference/latent_moe_decoder.py` has it
    (q = h Wq; h Wkva -> c ‖ k_r; c = RMSNorm(c); c Wkvb -> per head
    k_nope ‖ v; causal softmax(q k^T / sqrt(nope + rope)) v; Wo), with NO
    rotary on the `rope` channels (`mla_use_nope`).
  Feed-forward: SwiGLU in layers < first_k_dense_replace; later layers
    s = sigmoid(h Wr) over ALL published experts, the top k of s + bias
    chosen, weights s of the chosen renormalised and scaled; y = the sum
    over the experts HELD HERE of weight x SwiGLU_e(h), plus the shared
    expert's SwiGLU.  Final RMSNorm, untied head.

THE SHARE (guide `model-configs` section 4): the file's `num_experts` is
how many experts this chip holds, `deployment` says of how many
(`num_experts`) and which (`rank`: experts [rank E/n, (rank+1) E/n));
what the absent experts would have added is left out here as in the
program, and the partial result goes on to the next layer.  The
vocabulary is the file's `vocab_size` (a slice of the published one):
embedding, head and logits are over it.

Departures from the published description, all for memory and none for
arithmetic: attention in blocks of queries, the experts in blocks of
`E_BLOCK` (`lax.map`), the routed experts' weights drawn when a block
is needed and not kept (one key an expert; `expert_bank` gives the
program its copy), the sequence padded on the right to a multiple of
`PAD_TO` (nothing here looks ahead).  No kernels, no cache, no chunked
recurrence, no sorting, no batching, no code of the program under
test; `_rms`, `_attention`, `_swiglu` and `route` are the sibling
reference's.

The judge (`served_token_deficits`) looks at the program in ONE place:
`state_shortfall` holds the recurrent state that the program's engine
keeps against `kda_recurrence`'s, because served tokens cannot show a
state kept in lower precision than the configuration states (the
section at the end of this file says why, and what it reads).

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
    _attention, _rms, _swiglu, route as _route)

HIGHEST = "highest"
Q_BLOCK = 512
PAD_TO = 1024
E_BLOCK = 8
L2_EPS = 1e-6

KDA_KEYS = ("attn_norm", "wq", "wk", "wv", "conv_q", "conv_k", "conv_v",
            "A_log", "dt_bias", "wf_a", "wf_b", "w_beta", "wg_a", "wg_b",
            "o_norm", "wo", "ffn_norm")
MLA_KEYS = ("attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo",
            "ffn_norm")
DENSE_KEYS = ("w_gate", "w_up", "w_down")
MOE_KEYS = ("router", "router_bias", "ws_gate", "ws_up", "ws_down")
EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def _sizes(c: Mapping) -> Dict[str, int]:
    la, dep = c["linear_attn_config"], c.get("deployment", {})
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        rank=c["kv_lora_rank"], n=c["qk_nope_head_dim"],
        r=c["qk_rope_head_dim"], v=c["v_head_dim"],
        F=c["intermediate_size"], Fe=c["moe_intermediate_size"],
        Eh=c["num_experts"], E=dep.get("num_experts", c["num_experts"]),
        shard=dep.get("rank", 0), k=c["num_experts_per_token"],
        Fs=c["num_shared_experts"] * c["moe_intermediate_size"],
        V=c["vocab_size"], L=c["num_hidden_layers"],
        Ld=c["first_k_dense_replace"], Hk=la["num_heads"],
        dk=la["head_dim"], K=la["short_conv_kernel_size"])


def is_kda(c: Mapping, i: int) -> bool:
    """Layer i (from 0) is a KDA layer; the published lists count from 1."""
    return (i + 1) in c["linear_attn_config"]["kda_layers"]


def shapes(c: Mapping) -> Dict[str, Any]:
    """The held model's shapes (the routed experts as `expert_bank`
    makes them)."""
    z = _sizes(c)
    W = z["Hk"] * z["dk"]
    kda = {"attn_norm": (z["D"],), "wq": (z["D"], W), "wk": (z["D"], W),
           "wv": (z["D"], W), "conv_q": (z["K"], W), "conv_k": (z["K"], W),
           "conv_v": (z["K"], W), "A_log": (z["Hk"],), "dt_bias": (W,),
           "wf_a": (z["D"], z["dk"]), "wf_b": (z["dk"], W),
           "w_beta": (z["D"], z["Hk"]), "wg_a": (z["D"], z["dk"]),
           "wg_b": (z["dk"], W), "o_norm": (z["dk"],), "wo": (W, z["D"]),
           "ffn_norm": (z["D"],)}
    mla = {"attn_norm": (z["D"],),
           "wq": (z["D"], z["H"] * (z["n"] + z["r"])),
           "wkv_a": (z["D"], z["rank"] + z["r"]), "kv_norm": (z["rank"],),
           "wkv_b": (z["rank"], z["H"] * (z["n"] + z["v"])),
           "wo": (z["H"] * z["v"], z["D"]), "ffn_norm": (z["D"],)}
    dense = {"w_gate": (z["D"], z["F"]), "w_up": (z["D"], z["F"]),
             "w_down": (z["F"], z["D"])}
    moe = {"router": (z["D"], z["E"]), "router_bias": (z["E"],),
           "w_gate": (z["Eh"], z["D"], z["Fe"]),
           "w_up": (z["Eh"], z["D"], z["Fe"]),
           "w_down": (z["Eh"], z["Fe"], z["D"]),
           "ws_gate": (z["D"], z["Fs"]), "ws_up": (z["D"], z["Fs"]),
           "ws_down": (z["Fs"], z["D"])}
    return {"embed": (z["V"], z["D"]),
            "layers": [dict(kda if is_kda(c, i) else mla,
                            **(dense if i < z["Ld"] else moe))
                       for i in range(z["L"])],
            "norm_f": (z["D"],), "lm_head": (z["D"], z["V"])}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def expert_block(experts, j):
    """Held experts j * eb .. of one layer: (w_gate, w_up [eb, D, Fe],
    w_down [eb, Fe, D]), normal(0, std).  `experts` is what
    `init_weights` keeps of a layer's routed experts: `keys`
    [n_blocks, eb], ONE key an expert (so an expert's draw does not
    depend on how many its chip holds); `like`, an EMPTY array
    [0, D, Fe] with an expert's `w_gate` shape and dtype; `std`."""
    _, d, fe = experts["like"].shape

    def one(key):
        kg, ku, kd = jax.random.split(key, 3)

        # drawn in float32 and rounded once: a draw in bf16 may round
        # differently from one compiled program to the next
        def draw(k, *shape):
            return (jax.random.normal(k, shape, jnp.float32)
                    * experts["std"]).astype(experts["like"].dtype)

        return draw(kg, d, fe), draw(ku, d, fe), draw(kd, fe, d)

    return jax.vmap(one)(experts["keys"][j])


def map_expert_blocks(fn, experts):
    """fn(expert_block(experts, j)) for every j, stacked: leaves
    [n_blocks, ...]."""
    return lax.map(lambda j: fn(expert_block(experts, j)),
                   jnp.arange(experts["keys"].shape[0]))


@jax.jit
def expert_bank(experts) -> Dict[str, Any]:
    """All of one layer's HELD routed experts [Eh, ...], for the program."""
    blocks = map_expert_blocks(lambda b: b, experts)
    return {k: b.reshape((-1,) + b.shape[2:])
            for k, b in zip(EXPERT_KEYS, blocks)}


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights from `--seed`, drawn on the device in one
    jitted call: normal(0, initializer_range) matrices (the convolution
    taps too), unit norm vectors, a selection bias of
    normal(0, router_bias_scale) over ALL published experts (float32),
    `A_log = log U(1, 16)` a head and `dt_bias = softplus^-1(dt)`,
    `dt` log-uniform in [1e-3, 1e-1], a channel (float32; the file's
    `assumed` says why); for each expert layer, under `experts`, one
    key for each of the E published experts' draws, of which the held
    range is kept."""
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
            names = (KDA_KEYS if is_kda(c, i) else MLA_KEYS) \
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
            if i >= z["Ld"]:
                eb = min(E_BLOCK, z["Eh"])
                assert z["Eh"] % eb == 0 and z["E"] % z["Eh"] == 0, z
                keys = jax.random.split(
                    jax.random.fold_in(lk, 1 << 20), z["E"])
                held = keys[z["shard"] * z["Eh"]:(z["shard"] + 1) * z["Eh"]]
                w["experts"] = {
                    "keys": held.reshape(z["Eh"] // eb, eb),
                    "like": jnp.zeros((0, z["D"], z["Fe"]), dtype),
                    "std": jnp.float32(std)}
            layers.append(w)
        return {"embed": draw(k_embed, sh["embed"]), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": draw(k_head, sh["lm_head"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("kda_hybrid_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("kda_hybrid_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _conv_silu(x, w):
    """x [T, C], taps w [K, C] (w[K-1] on the current row): causal
    depthwise convolution over time, zeros before the first row, SiLU."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return jax.nn.silu(sum(xp[j:j + T] * w[j].astype(jnp.float32)
                           for j in range(K)))


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_recurrence(q, k, v, alpha, beta):
    """The delta rule token by token.  q, k, alpha [T, H, dk];
    v [T, H, dv]; beta [T, H] -> (o [T, H, dv], the state after the
    last token [H, dk, dv]).  The two products with the state are
    written as multiply-and-sum (float32 as it stands, on any
    backend)."""
    H, dk = q.shape[1:]

    def step(S, t):
        qt, kt, vt, at, bt = t
        Sd = at[..., None] * S                               # Diag(alpha) S
        u = vt - jnp.sum(Sd * kt[..., None], axis=1)         # v - S'^T k
        S = Sd + bt[:, None, None] * kt[..., None] * u[:, None, :]
        return S, jnp.sum(S * qt[..., None], axis=1)         # S^T q

    S0 = jnp.zeros((H, dk, v.shape[-1]), jnp.float32)
    S, o = lax.scan(step, S0, (q, k, v, alpha, beta), unroll=8)
    return o, S


def kda(c: Mapping, h, w):
    """h [T, D], the normed input -> (the mixer's output [T, D], the
    state after the last row [H, dk, dv])."""
    z = _sizes(c)
    T, Hk, dk = h.shape[0], z["Hk"], z["dk"]
    f = lambda a: a.astype(jnp.float32)
    heads = lambda a: a.reshape(T, Hk, dk)
    q = _l2(heads(_conv_silu(h @ f(w["wq"]), w["conv_q"]))) * dk ** -0.5
    k = _l2(heads(_conv_silu(h @ f(w["wk"]), w["conv_k"])))
    v = heads(_conv_silu(h @ f(w["wv"]), w["conv_v"]))
    g = -jnp.exp(f(w["A_log"]))[:, None] * heads(jax.nn.softplus(
        (h @ f(w["wf_a"])) @ f(w["wf_b"]) + f(w["dt_bias"])))
    beta = jax.nn.sigmoid(h @ f(w["w_beta"]))
    o, S = kda_recurrence(q, k, v, jnp.exp(g), beta)
    gate = jax.nn.sigmoid(heads((h @ f(w["wg_a"])) @ f(w["wg_b"])))
    o = _rms(o, w["o_norm"], float(c["rms_norm_eps"])) * gate
    return o.reshape(T, Hk * dk) @ f(w["wo"]), S


def mla(c: Mapping, h, w, q_block):
    """h [T, D] -> latent attention's output [T, D]; no rotary."""
    z = _sizes(c)
    T = h.shape[0]
    H, n, r, v, rank = z["H"], z["n"], z["r"], z["v"], z["rank"]
    f = lambda a: a.astype(jnp.float32)
    q = (h @ f(w["wq"])).reshape(T, H, n + r)
    ckr = h @ f(w["wkv_a"])
    lat = _rms(ckr[:, :rank], w["kv_norm"], float(c["rms_norm_eps"]))
    kv = (lat @ f(w["wkv_b"])).reshape(T, H, n + v)
    k = jnp.concatenate(
        [kv[..., :n], jnp.broadcast_to(ckr[:, None, rank:], (T, H, r))], -1)
    return _attention(q, k, kv[..., n:], q_block) @ f(w["wo"])


def route(c: Mapping, h, router, bias):
    """h [T, D] -> weights [T, E] over ALL published experts: zero where
    an expert was not chosen, else its renormalised, scaled score."""
    z = _sizes(c)
    return _route({"n_routed_experts": z["E"],
                   "num_experts_per_tok": z["k"],
                   "norm_topk_prob": bool(c.get("moe_renormalize", True)),
                   "routed_scaling_factor": c["routed_scaling_factor"]},
                  h, router, bias)


def held_experts(c: Mapping, h, weights, experts):
    """Sum over the HELD routed experts, a block at a time, of weight x
    SwiGLU_e(h); `weights` [T, E] over all published experts."""
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


def block(c: Mapping, x, w, q_block=Q_BLOCK):
    """One decoder block on one sequence x [T, D] (float32) -> (x, the
    KDA state after the last row, None for an MLA layer)."""
    eps = float(c["rms_norm_eps"])
    h = _rms(x, w["attn_norm"], eps)
    y, S = kda(c, h, w) if "A_log" in w else (mla(c, h, w, q_block), None)
    x = x + y
    h = _rms(x, w["ffn_norm"], eps)
    if "router" not in w:
        return x + _swiglu(h, w["w_gate"], w["w_up"], w["w_down"]), S
    weights = route(c, h, w["router"], w["router_bias"])
    return x + held_experts(c, h, weights, w["experts"]) \
        + _swiglu(h, w["ws_gate"], w["ws_up"], w["ws_down"]), S


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


_KEEP = ("hidden_size", "num_attention_heads", "kv_lora_rank",
         "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
         "intermediate_size", "moe_intermediate_size", "num_experts",
         "num_experts_per_token", "num_shared_experts",
         "routed_scaling_factor", "vocab_size", "rms_norm_eps",
         "num_hidden_layers", "first_k_dense_replace")
_KEEP_LA = ("num_heads", "head_dim", "short_conv_kernel_size")


def _cfg_key(c: Mapping) -> tuple:
    """What the forward pass reads of the file, hashable."""
    la, dep = c["linear_attn_config"], c.get("deployment", {})
    return tuple((k, c[k]) for k in _KEEP) + (
        ("moe_renormalize", bool(c.get("moe_renormalize", True))),
        ("initializer_range", _std(c)),
        ("deployment", (dep.get("num_experts", c["num_experts"]),
                        dep.get("rank", 0))),
        ("linear_attn_config", tuple((k, la[k]) for k in _KEEP_LA)))


def _cfg(key: tuple) -> dict:
    c = dict(key)
    c["linear_attn_config"] = dict(c["linear_attn_config"], kda_layers=())
    c["deployment"] = dict(zip(("num_experts", "rank"), c["deployment"]))
    return c


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache, every KDA state from zero).
    The sequence is padded on the right to a multiple of `pad_to`:
    nothing here looks ahead, and a token's experts do not depend on
    its neighbours."""
    T = len(tokens)
    Tp = -(-T // pad_to) * pad_to
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for w in weights["layers"]:
        x, _ = _block_jit(x, w, key)
    return _tail_jit(x, weights["norm_f"], weights["lm_head"],
                     jnp.int32(start), key, n)


def served_token_deficits(weights, c: Mapping, prompt: Sequence[int],
                          served: Sequence[int]) -> np.ndarray:
    """For each served token, how far its reference logit lies under the
    reference maximum, given the served prefix (0 where the reference
    would have chosen the same token).  Infinite for every token where
    the program keeps its recurrent state less exactly than this file's
    recurrence does (`state_shortfall`): the harness judges ONE number
    of the served tokens, and the tokens cannot show that."""
    n = len(served)
    seq = list(prompt) + list(served[:-1])
    lg = logits_for_positions(weights, c, seq, len(prompt) - 1, n)
    chosen = jnp.take_along_axis(lg, jnp.asarray(served, jnp.int32)[:, None],
                                 -1)[:, 0]
    deficits = np.asarray(jnp.max(lg, -1) - chosen, np.float64)
    if state_shortfall(c) > STATE_LIMIT:
        return np.full_like(deficits, np.inf)
    return deficits


# ------------------------------------------- the recurrent state's precision
#
# Why the judge looks at the program here, the one place this file does:
# the served tokens cannot tell a state kept in bf16 from one kept in
# float32.  At bf16 compute the keys, values and decays that go INTO the
# state are rounded to 2^-9 already and a state forgets in a dozen
# tokens, so rounding it once more a token moves `mean_deficit` by a
# tenth (PERF.md section 2: 0.0159 against 0.0106-0.0142 sound).  What
# does tell them apart is the state itself where nothing else is
# rounded: the program's engine in float32 against `kda_recurrence`.

STATE_LIMIT = 3e-4      # PERF.md section 2 has both readings
AUDIT_SEED = 30
AUDIT_PROMPT = 150      # three chunks of the audit engine: 64 + 64 + 22
AUDIT_TOKENS = 40       # then 39 ticks

_SHORTFALL: Dict[tuple, float] = {}


def kda_states(weights, c: Mapping, tokens: Sequence[int]) -> np.ndarray:
    """The state of every KDA layer after the last of `tokens`, ONE
    sequence from zero states with no padding (at most `Q_BLOCK`
    tokens): [KDA layers, H, dk, dv] float32."""
    assert len(tokens) <= Q_BLOCK, len(tokens)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    states = []
    for w in weights["layers"]:
        x, S = _block_jit(x, w, key)
        if S is not None:
            states.append(np.asarray(S))
    return np.stack(states)


def state_shortfall(c: Mapping) -> float:
    """How far the recurrent state that the program's ENGINE holds lies
    from `kda_recurrence`'s, float32 against float32: the largest, over
    the KDA layers, of |S_engine - S_reference| / |S_reference|
    (Frobenius), after a prompt of `AUDIT_PROMPT` tokens prefilled in
    chunks and `AUDIT_TOKENS` served (`families/kda_hybrid_decoder.py::
    served_state`: the configuration `c` cut to the family's
    `AUDIT_SIZES`, its `precision` kept, on weights and a prompt drawn
    from `AUDIT_SEED`).  Rounding to float32 reads 1e-6; a state kept
    in bf16 between tokens reads 2e-3, whatever else is exact.  Once a
    configuration a process; prints the reading."""
    from families import kda_hybrid_decoder as family

    tiny = family.audit_config(c)
    key = _cfg_key(tiny) + (str(c.get("precision", {}).get(
        "recurrent_state", "float32")),)
    if key not in _SHORTFALL:
        weights = init_weights(tiny, AUDIT_SEED, jnp.float32)
        prompt = [int(t) for t in np.random.RandomState(AUDIT_SEED).randint(
            0, tiny["vocab_size"], AUDIT_PROMPT)]
        served, got = family.served_state(tiny, weights, prompt,
                                          AUDIT_TOKENS)
        want = kda_states(weights, tiny, prompt + served[:-1])
        norm = lambda a: np.sqrt((a.astype(np.float64) ** 2).sum((1, 2, 3)))
        _SHORTFALL[key] = float(np.max(norm(got - want) / norm(want)))
        print(f"STATE AUDIT shortfall={_SHORTFALL[key]!r} "
              f"limit={STATE_LIMIT!r} (engine's recurrent state against "
              f"the reference's, float32, {len(want)} layers, "
              f"{AUDIT_PROMPT} + {AUDIT_TOKENS} tokens)", flush=True)
    return _SHORTFALL[key]
