"""Plain reference for the hybrid decoder whose every layer is ONE mixer:
Mamba-2 layers, non-gated relu² experts beside a shared one, and
grouped-query attention without positions (`model_type` `nemotron_h`:
NVIDIA-Nemotron-3-Nano-30B-A3B's block).

Per layer, on one sequence x [T, D] in float32 under
`jax.default_matmul_precision("highest")`: `x <- x + Mixer(RMSNorm(x))`,
the mixer by the layer's character of `hybrid_override_pattern`:

  M, Mamba-2: [z | xBC | dt] = h W_in (widths H P | H P + 2 G N | H);
    on xBC a causal depthwise convolution of width K over time (zeros
    before the first token) plus its bias, then SiLU; xBC split into
    X [H, P], B [G, N], C [G, N], head h reading group h // (H / G);
    Delta = softplus(dt + dt_bias) (no clamp), a = exp(Delta A),
    A = -exp(A_log); then TOKEN BY TOKEN, a head at a time, with the
    state S_h [P, N] as published,
      S_h = a_h S_h + (Delta_h X_h) B_g^T;  Y_h = S_h C_g + D_h X_h,
      S = 0 before the first token;
    y = RMSNorm_group(Y * silu(z)) * w, the mean square over each
    group's H P / G channels (the gate BEFORE the norm); y W_out.
  E, experts: s = sigmoid(h W_r) over ALL published experts, the top k
    of s + bias chosen, weights s of the chosen renormalised and scaled;
    y = the sum over the experts HELD HERE of weight x
    W_down_e relu(W_up_e h)^2 (no gate matrix), plus the shared
    expert's, the same form.
  *, attention: q, k, v = h Wq, h Wk, h Wv; H / kvH query heads read
    each K/V head; causal softmax(q k^T / sqrt(head_dim)) v; Wo.  NO
    rotary, no other positions.
Final RMSNorm, untied head.

THE SHARE (guide `model-configs` section 4): the file's
`n_routed_experts` is how many experts this chip holds, `deployment`
says of how many (`n_routed_experts`) and which (`rank`: experts
[rank E/n, (rank+1) E/n)); what the absent experts would have added is
left out here as in the program, and the partial result goes on to the
next layer.  The vocabulary is the file's `vocab_size` (a slice of the
published one): embedding, head and logits are over it.

Departures from the published description, all for memory and none for
arithmetic: attention in blocks of queries, the experts in blocks of
`E_BLOCK` (`lax.map`), the routed experts' weights drawn when a block
is needed and not kept (one key an expert; `expert_bank` gives the
program its copy), the sequence padded on the right to a multiple of
`PAD_TO` (nothing here looks ahead).  The recurrence is a `lax.scan`
over the tokens: NOT the chunked matrix form, which is the program's
algebra.  No kernels, no cache, no sorting, no batching, no code of the
program under test; `_rms`, `_attention` and `route` are the sibling
reference's.

The weights lie as the program's do, the SAME buffers under the same
names (`layout`: the repeated block's layers stacked over the repeats,
the rest a list; an expert's `w_up` [F, D] as a checkpoint stores it):
a second copy of them does not fit the chip beside the engine.

`init_as_trainer` / `adamw_trajectory` raise: there is no train cell.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from reference.latent_moe_decoder import (  # the sibling's plain parts
    _attention, _rms, route as _route)

HIGHEST = "highest"
Q_BLOCK = 512
PAD_TO = 1024
E_BLOCK = 4

KEYS = {
    "M": ("norm", "w_in", "conv_w", "conv_b", "A_log", "dt_bias", "D",
          "gate_norm", "w_out"),
    "*": ("norm", "wq", "wk", "wv", "wo"),
    "E": ("norm", "router", "router_bias", "ws_up", "ws_down"),
}
EXPERT_KEYS = ("w_up", "w_down")


def _sizes(c: Mapping) -> Dict[str, Any]:
    dep = c.get("deployment", {})
    return dict(
        D=c["hidden_size"], H=c["mamba_num_heads"], P=c["mamba_head_dim"],
        G=c["n_groups"], N=c["ssm_state_size"], K=c["conv_kernel"],
        Hq=c["num_attention_heads"], Hkv=c["num_key_value_heads"],
        hd=c["head_dim"], Fe=c["moe_intermediate_size"],
        Fs=c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"],
        Eh=c["n_routed_experts"],
        E=dep.get("n_routed_experts", c["n_routed_experts"]),
        shard=dep.get("rank", 0), k=c["num_experts_per_tok"],
        V=c["vocab_size"], pattern=c["hybrid_override_pattern"])


def layout(pattern: str) -> Tuple[str, int, str]:
    """(block, repeats, tail) with `pattern == block * repeats + tail`:
    the repeats (>= 2) of one block from layer 0 that cover the most
    layers, the shorter block on a tie; ("", 0, pattern) where nothing
    repeats.  How the weights are laid, nothing more."""
    best = ("", 0, pattern)
    for n in range(1, len(pattern) // 2 + 1):
        reps = 1
        while pattern.startswith(pattern[:n] * (reps + 1)):
            reps += 1
        if reps >= 2 and n * reps > len(best[0]) * best[1]:
            best = (pattern[:n], reps, pattern[n * reps:])
    return best


def layer_shapes(c: Mapping, kind: str) -> Dict[str, tuple]:
    """One layer of `kind` (the routed experts as `expert_bank` makes
    them)."""
    z = _sizes(c)
    D, C = z["D"], z["H"] * z["P"]
    W = C + 2 * z["G"] * z["N"]
    if kind == "M":
        return {"norm": (D,), "w_in": (D, C + W + z["H"]),
                "conv_w": (z["K"], W), "conv_b": (W,), "A_log": (z["H"],),
                "dt_bias": (z["H"],), "D": (z["H"],), "gate_norm": (C,),
                "w_out": (C, D)}
    if kind == "*":
        return {"norm": (D,), "wq": (D, z["Hq"] * z["hd"]),
                "wk": (D, z["Hkv"] * z["hd"]), "wv": (D, z["Hkv"] * z["hd"]),
                "wo": (z["Hq"] * z["hd"], D)}
    return {"norm": (D,), "router": (D, z["E"]), "router_bias": (z["E"],),
            "w_up": (z["Eh"], z["Fe"], D), "w_down": (z["Eh"], z["Fe"], D),
            "ws_up": (D, z["Fs"]), "ws_down": (z["Fs"], D)}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def expert_block(experts, j):
    """Held experts j * eb .. of one layer: (w_up, w_down), both
    [eb, Fe, D], normal(0, std).  `experts` is what `init_weights` keeps
    of a layer's routed experts: `keys` [n_blocks, eb], ONE key an
    expert (so an expert's draw does not depend on how many its chip
    holds); `like`, an EMPTY array [0, Fe, D] with a matrix's shape and
    dtype; `std`."""
    shape = experts["like"].shape[1:]

    def one(key):
        ku, kd = jax.random.split(key)

        # drawn in float32 and rounded once: a draw in bf16 may round
        # differently from one compiled program to the next
        def draw(k):
            return (jax.random.normal(k, shape, jnp.float32)
                    * experts["std"]).astype(experts["like"].dtype)

        return draw(ku), draw(kd)

    return jax.vmap(one)(experts["keys"][j])


def map_expert_blocks(fn, experts):
    """fn(expert_block(experts, j)) for every j, stacked: leaves
    [n_blocks, ...]."""
    return lax.map(lambda j: fn(expert_block(experts, j)),
                   jnp.arange(experts["keys"].shape[0]))


@jax.jit
def expert_bank(experts) -> Dict[str, Any]:
    """All of one layer's HELD routed experts [Eh, ...], for the
    program."""
    blocks = map_expert_blocks(lambda b: b, experts)
    return {k: b.reshape((-1,) + b.shape[2:])
            for k, b in zip(EXPERT_KEYS, blocks)}


def _init_layer(c: Mapping, kind: str, key, dtype):
    """The family's draws for one layer: normal(0, initializer_range)
    matrices and taps, unit norm vectors, a zero convolution bias, `D`
    ones, `A_log = log U(1, 16)` and `dt_bias = softplus^-1(dt)`, `dt`
    log-uniform in [time_step_min, time_step_max] floored at
    time_step_floor, a head (float32), a selection bias of
    normal(0, router_bias_scale) over ALL published experts (float32),
    and under `experts` one key for each of the E published experts'
    draws, of which the held range is kept."""
    z, std = _sizes(c), _std(c)
    sh = layer_shapes(c, kind)
    ks = dict(zip(KEYS[kind], jax.random.split(key, len(KEYS[kind]))))
    w = {}
    for name in KEYS[kind]:
        shape = sh[name]
        if name.endswith("norm"):
            w[name] = jnp.ones(shape, dtype)
        elif name == "conv_b":
            w[name] = jnp.zeros(shape, dtype)
        elif name == "D":
            w[name] = jnp.ones(shape, jnp.float32)
        elif name == "router_bias":
            w[name] = jax.random.normal(ks[name], shape, jnp.float32) \
                * float(c["router_bias_scale"])
        elif name == "A_log":
            w[name] = jnp.log(jax.random.uniform(
                ks[name], shape, jnp.float32, 1.0, 16.0))
        elif name == "dt_bias":
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                ks[name], shape, jnp.float32,
                np.log(float(c["time_step_min"])),
                np.log(float(c["time_step_max"])))),
                float(c["time_step_floor"]))
            w[name] = dt + jnp.log(-jnp.expm1(-dt))
        else:
            w[name] = (jax.random.normal(ks[name], shape, jnp.float32)
                       * std).astype(dtype)
    if kind == "E":
        eb = min(E_BLOCK, z["Eh"])
        assert z["Eh"] % eb == 0 and z["E"] % z["Eh"] == 0, z
        keys = jax.random.split(jax.random.fold_in(key, 1 << 20), z["E"])
        held = keys[z["shard"] * z["Eh"]:(z["shard"] + 1) * z["Eh"]]
        w["experts"] = {"keys": held.reshape(z["Eh"] // eb, eb),
                        "like": jnp.zeros((0,) + sh["w_up"][1:], dtype),
                        "std": jnp.float32(std)}
    return w


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights from `--seed`, drawn on the device in one
    jitted call (`_init_layer`), laid as `layout` says: `blocks` one
    entry a layer of the repeated block, every leaf with a leading
    repeat axis; `tail` a list of the layers that follow."""
    z, std = _sizes(c), _std(c)
    block, reps, tail = layout(z["pattern"])

    def make(key):
        k_embed, k_head, k_blocks, k_tail = jax.random.split(key, 4)
        draw = lambda k, *shape: (jax.random.normal(
            k, shape, jnp.float32) * std).astype(dtype)
        blocks = [jax.vmap(lambda k, kind=kind: _init_layer(
            c, kind, k, dtype))(jax.random.split(
                jax.random.fold_in(k_blocks, i), reps))
            for i, kind in enumerate(block)]
        return {"embed": draw(k_embed, z["V"], z["D"]), "blocks": blocks,
                "tail": [_init_layer(c, kind, k, dtype) for kind, k in zip(
                    tail, jax.random.split(k_tail, max(len(tail), 1)))],
                "norm_f": jnp.ones((z["D"],), dtype),
                "lm_head": draw(k_head, z["D"], z["V"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def layers_of(weights, c: Mapping):
    """(kind, the layer's weights) for every layer in order, a repeat's
    layers cut out of the stacked block."""
    block, reps, tail = layout(_sizes(c)["pattern"])
    for r in range(reps):
        for kind, w in zip(block, weights["blocks"]):
            yield kind, jax.tree.map(lambda a: a[r], w)
    yield from zip(tail, weights["tail"])


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("ssd_moe_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("ssd_moe_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _conv_silu(x, w, b):
    """x [T, C], taps w [K, C] (w[K-1] on the current row), bias b [C]:
    causal depthwise convolution over time, zeros before the first row,
    the bias, SiLU."""
    K, T = w.shape[0], x.shape[0]
    xp = jnp.concatenate([jnp.zeros((K - 1, x.shape[1]), x.dtype), x], 0)
    return jax.nn.silu(sum(xp[j:j + T] * w[j].astype(jnp.float32)
                           for j in range(K)) + b.astype(jnp.float32))


def ssd_recurrence(x, dt, A, Bm, Cm):
    """The state-space recurrence token by token.  x [T, H, P]; dt
    [T, H]; A [H]; Bm, Cm [T, G, N] -> (y [T, H, P], the state after
    the last token [H, P, N]).  Both products with the state are
    written as multiply-and-sum (float32 as it stands, on any
    backend)."""
    T, H, P = x.shape
    G, N = Bm.shape[1:]
    per_head = lambda a: jnp.repeat(a, H // G, axis=0)       # [G,N]->[H,N]

    def step(S, t):
        xt, dtt, bt, ct = t
        a = jnp.exp(dtt * A)
        S = a[:, None, None] * S \
            + (dtt[:, None] * xt)[:, :, None] * per_head(bt)[:, None, :]
        return S, jnp.sum(S * per_head(ct)[:, None, :], axis=-1)

    S, y = lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                    (x, dt, Bm, Cm), unroll=8)
    return y, S


def mamba2(c: Mapping, h, w):
    """h [T, D], the normed input -> (the mixer's output [T, D], the
    state after the last row [H, P, N])."""
    z = _sizes(c)
    T, H, P, G, N = h.shape[0], z["H"], z["P"], z["G"], z["N"]
    C = H * P
    f = lambda a: a.astype(jnp.float32)
    zxd = h @ f(w["w_in"])
    gate, dt = zxd[:, :C], zxd[:, 2 * C + 2 * G * N:]
    xbc = _conv_silu(zxd[:, C:2 * C + 2 * G * N], w["conv_w"], w["conv_b"])
    x = xbc[:, :C].reshape(T, H, P)
    Bm = xbc[:, C:C + G * N].reshape(T, G, N)
    Cm = xbc[:, C + G * N:].reshape(T, G, N)
    dt = jax.nn.softplus(dt + f(w["dt_bias"]))
    y, S = ssd_recurrence(x, dt, -jnp.exp(f(w["A_log"])), Bm, Cm)
    y = (y + f(w["D"])[:, None] * x).reshape(T, C) * jax.nn.silu(gate)
    g = y.reshape(T, G, C // G)
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True)
                      + float(c["layer_norm_epsilon"]))
    return (g.reshape(T, C) * f(w["gate_norm"])) @ f(w["w_out"]), S


def attention(c: Mapping, h, w, q_block):
    """h [T, D] -> grouped-query attention's output [T, D]; no
    positions."""
    z = _sizes(c)
    T, Hq, Hkv, hd = h.shape[0], z["Hq"], z["Hkv"], z["hd"]
    f = lambda a: a.astype(jnp.float32)
    q = (h @ f(w["wq"])).reshape(T, Hq, hd)
    k = jnp.repeat((h @ f(w["wk"])).reshape(T, Hkv, hd), Hq // Hkv, axis=1)
    v = jnp.repeat((h @ f(w["wv"])).reshape(T, Hkv, hd), Hq // Hkv, axis=1)
    return _attention(q, k, v, q_block) @ f(w["wo"])


def _relu2(h, w_up, w_down):
    f = lambda a: a.astype(jnp.float32)
    return jnp.square(jax.nn.relu(h @ f(w_up))) @ f(w_down)


def route(c: Mapping, h, router, bias):
    """h [T, D] -> weights [T, E] over ALL published experts: zero where
    an expert was not chosen, else its renormalised, scaled score."""
    z = _sizes(c)
    return _route({"n_routed_experts": z["E"],
                   "num_experts_per_tok": z["k"],
                   "norm_topk_prob": bool(c.get("norm_topk_prob", True)),
                   "routed_scaling_factor": c["routed_scaling_factor"]},
                  h, router, bias)


def held_experts(c: Mapping, h, weights, experts):
    """Sum over the HELD routed experts, a block at a time, of weight x
    W_down_e relu(W_up_e h)^2; `weights` [T, E] over all published
    experts."""
    z = _sizes(c)
    eb = experts["keys"].shape[1]
    lo = z["shard"] * z["Eh"]

    def one(j):
        wu, wd = (a.astype(jnp.float32) for a in expert_block(experts, j))
        a = jnp.square(jax.nn.relu(jnp.einsum("td,efd->etf", h, wu)))
        wj = lax.dynamic_slice_in_dim(weights, lo + j * eb, eb, 1)
        return jnp.einsum("etf,efd->td", a * wj.T[:, :, None], wd)

    return lax.map(one, jnp.arange(experts["keys"].shape[0])).sum(0)


def layer(c: Mapping, kind: str, x, w, q_block=Q_BLOCK):
    """One layer on one sequence x [T, D] (float32) -> (x, the Mamba-2
    state after the last row, None for another kind)."""
    h = _rms(x, w["norm"], float(c["layer_norm_epsilon"]))
    if kind == "M":
        y, S = mamba2(c, h, w)
        return x + y, S
    if kind == "*":
        return x + attention(c, h, w, q_block), None
    weights = route(c, h, w["router"], w["router_bias"])
    return x + held_experts(c, h, weights, w["experts"]) \
        + _relu2(h, w["ws_up"], w["ws_down"]), None


@partial(jax.jit, static_argnames=("kind", "cfg_key"))
def _layer_jit(x, w, kind, cfg_key):
    with jax.default_matmul_precision(HIGHEST):
        return layer(_cfg(cfg_key), kind, x, w)


@partial(jax.jit, static_argnames=("cfg_key", "n_last"))
def _tail_jit(x, norm_f, lm_head, start, cfg_key, n_last):
    c = _cfg(cfg_key)
    with jax.default_matmul_precision(HIGHEST):
        rows = lax.dynamic_slice_in_dim(x, start, n_last, 0)
        return _rms(rows, norm_f, float(c["layer_norm_epsilon"])) \
            @ lm_head.astype(jnp.float32)


_KEEP = ("hidden_size", "mamba_num_heads", "mamba_head_dim", "n_groups",
         "ssm_state_size", "conv_kernel", "num_attention_heads",
         "num_key_value_heads", "head_dim", "moe_intermediate_size",
         "moe_shared_expert_intermediate_size", "n_shared_experts",
         "n_routed_experts", "num_experts_per_tok", "routed_scaling_factor",
         "vocab_size", "layer_norm_epsilon", "hybrid_override_pattern")


def _cfg_key(c: Mapping) -> tuple:
    """What the forward pass reads of the file, hashable."""
    dep = c.get("deployment", {})
    return tuple((k, c[k]) for k in _KEEP) + (
        ("norm_topk_prob", bool(c.get("norm_topk_prob", True))),
        ("deployment", (dep.get("n_routed_experts", c["n_routed_experts"]),
                        dep.get("rank", 0))))


def _cfg(key: tuple) -> dict:
    c = dict(key)
    c["deployment"] = dict(zip(("n_routed_experts", "rank"),
                               c["deployment"]))
    return c


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = PAD_TO):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache, every state from zero).
    The sequence is padded on the right to a multiple of `pad_to`:
    nothing here looks ahead, and a token's experts do not depend on
    its neighbours."""
    T = len(tokens)
    Tp = -(-T // pad_to) * pad_to
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for kind, w in layers_of(weights, c):
        x, _ = _layer_jit(x, w, kind, key)
    return _tail_jit(x, weights["norm_f"], weights["lm_head"],
                     jnp.int32(start), key, n)


def states_after(weights, c: Mapping, tokens: Sequence[int]) -> np.ndarray:
    """The state of every Mamba-2 layer after the last of `tokens`, ONE
    sequence from zero states with no padding: [Mamba-2 layers, H, P,
    N] float32 (the tests hold the engine's slot against it)."""
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(tokens, jnp.int32)].astype(jnp.float32)
    states = []
    for kind, w in layers_of(weights, c):
        x, S = _layer_jit(x, w, kind, key)
        if S is not None:
            states.append(np.asarray(S))
    return np.stack(states)


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
