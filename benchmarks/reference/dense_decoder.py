"""Plain reference for the dense decoder family (Mistral-7B-v0.3 block).

RMSNorm -> grouped-query attention with rotary embeddings (rotate-half
convention, as in the published modelling code) -> residual -> RMSNorm ->
SwiGLU -> residual; a final RMSNorm and an untied output head.  Written in
straightforward `jax.numpy`, float32, under
`jax.default_matmul_precision("highest")`: no kernels, no cache, no
batching of requests, no code of the program under test.  Departures
from a textbook forward pass, all for memory and none for arithmetic:
attention and the feed-forward run in blocks of queries / tokens
(`lax.map`), the cross-entropy in blocks of tokens, and training
re-computes a block's activations in the backward pass
(`jax.checkpoint`).

Weights come from the seed (`init_weights`, the benchmark's own
generator, or `init_as_trainer`, which draws what the training entry
point draws from the same seed so that the two trajectories start at one
point).  Nothing the program has computed enters here except the TOKENS
it served, whose reference logits are what is judged.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Mapping, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = "highest"
Q_BLOCK = 512
T_BLOCK = 2048

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "ffn_norm",
              "w_gate", "w_up", "w_down")


def _hd(c: Mapping) -> int:
    return int(c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"])


def shapes(c: Mapping) -> Dict[str, Any]:
    d, L, hd = c["hidden_size"], c["num_hidden_layers"], _hd(c)
    H, KV, F, V = (c["num_attention_heads"], c["num_key_value_heads"],
                   c["intermediate_size"], c["vocab_size"])
    return {
        "embed": (V, d),
        "layers": {
            "attn_norm": (L, d), "wq": (L, d, H * hd), "wk": (L, d, KV * hd),
            "wv": (L, d, KV * hd), "wo": (L, H * hd, d), "ffn_norm": (L, d),
            "w_gate": (L, d, F), "w_up": (L, d, F), "w_down": (L, F, d)},
        "norm_f": (d,),
        "lm_head": (d, V),
    }


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights for serving: normal(0, initializer_range)
    matrices and unit norm vectors, drawn on the device in `dtype` in one
    jitted call.  Both the engine and this reference are handed them."""
    std = float(c.get("initializer_range", 0.02))
    sh = shapes(c)

    def make(key):
        names = ["embed", "lm_head"] + [k for k in LAYER_KEYS
                                        if not k.endswith("norm")]
        keys = dict(zip(names, jax.random.split(key, len(names))))

        def draw(name, shape):
            return (jax.random.normal(keys[name], shape, dtype)
                    * jnp.asarray(std, dtype))

        layers = {k: (jnp.ones(s, dtype) if k.endswith("norm")
                      else draw(k, s)) for k, s in sh["layers"].items()}
        return {"embed": draw("embed", sh["embed"]), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": draw("lm_head", sh["lm_head"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(c: Mapping, seed: int, dtype=jnp.float32,
                    out_shardings=None) -> Dict[str, Any]:
    """What `run_pod_training` starts from for this seed: the key split
    in three (embedding, blocks, head), the blocks' key in eight, each
    matrix `normal(0, 0.02)` in the parameter type.  A copy of the
    program's draw ORDER, made here so that the reference needs nothing
    the program has produced; if the program changes its initializer the
    trajectories part at step one and the check says so."""
    sh = shapes(c)

    def make(key):
        k_embed, k_layers, k_out = jax.random.split(key, 3)
        lk = jax.random.split(k_layers, 8)
        init = jax.nn.initializers.normal(0.02)
        order = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
        layers = {k: jnp.ones(s, dtype) for k, s in sh["layers"].items()
                  if k.endswith("norm")}
        for i, k in enumerate(order):
            layers[k] = init(lk[i], sh["layers"][k], dtype)
        return {"embed": init(k_embed, sh["embed"], dtype), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": init(k_out, sh["lm_head"], dtype)}

    return jax.jit(make, out_shardings=out_shardings)(jax.random.key(seed))


# ---------------------------------------------------------------- forward

def _rms(x, w, eps):
    x = x.astype(jnp.float32)
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * w.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, H, hd]; rotate-half convention."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = pos.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(f)[:, None, :], jnp.sin(f)[:, None, :]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, q_block):
    """Causal GQA for one sequence. q [T, H, hd], k/v [T, KV, hd]."""
    T, H, hd = q.shape
    KV = k.shape[1]
    rep = H // KV
    qb = min(q_block, T)
    assert T % qb == 0, (T, qb)
    qg = q.reshape(T // qb, qb, KV, rep, hd)
    starts = jnp.arange(T // qb) * qb
    kpos = jnp.arange(T)

    @jax.checkpoint       # keep no block of scores for the backward pass
    def one(args):
        qi, s0 = args
        s = jnp.einsum("qgrd,kgd->grqk", qi, k) / math.sqrt(hd)
        mask = (s0 + jnp.arange(qb))[:, None] >= kpos[None, :]
        s = jnp.where(mask[None, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v)

    out = lax.map(one, (qg, starts))
    return out.reshape(T, H * hd)


def _ffn(h, w_gate, w_up, w_down, t_block):
    T = h.shape[0]
    tb = min(t_block, T)
    if T % tb:
        tb = math.gcd(T, tb)

    @jax.checkpoint
    def one(hi):
        return (jax.nn.silu(hi @ w_gate) * (hi @ w_up)) @ w_down

    return lax.map(one, h.reshape(T // tb, tb, -1)).reshape(T, -1)


def block(c: Mapping, x, w, q_block=Q_BLOCK, t_block=T_BLOCK):
    """One decoder block on one sequence x [T, D] (float32)."""
    T = x.shape[0]
    hd, H, KV = _hd(c), c["num_attention_heads"], c["num_key_value_heads"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    f = lambda a: a.astype(jnp.float32)
    pos = jnp.arange(T)
    h = _rms(x, w["attn_norm"], eps)
    q = _rope((h @ f(w["wq"])).reshape(T, H, hd), pos, theta)
    k = _rope((h @ f(w["wk"])).reshape(T, KV, hd), pos, theta)
    v = (h @ f(w["wv"])).reshape(T, KV, hd)
    x = x + _attention(q, k, v, q_block) @ f(w["wo"])
    h = _rms(x, w["ffn_norm"], eps)
    return x + _ffn(h, f(w["w_gate"]), f(w["w_up"]), f(w["w_down"]), t_block)


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
    keep = ("hidden_size", "num_attention_heads", "num_key_value_heads",
            "intermediate_size", "vocab_size", "rms_norm_eps", "rope_theta",
            "num_hidden_layers")
    return tuple((k, c[k]) for k in keep) + (("head_dim", _hd(c)),)


def logits_for_positions(weights, c: Mapping, tokens: Sequence[int],
                         start: int, n: int, pad_to: int = Q_BLOCK):
    """Reference logits [n, V] at positions start .. start+n-1 of ONE
    sequence (a full forward pass: no cache).  The sequence is padded on
    the right to a multiple of `pad_to`; under a causal mask padding
    cannot reach an earlier position."""
    T = len(tokens)
    Tp = -(-T // pad_to) * pad_to
    ids = np.zeros((Tp,), np.int32)
    ids[:T] = np.asarray(tokens, np.int32)
    key = _cfg_key(c)
    x = weights["embed"][jnp.asarray(ids)].astype(jnp.float32)
    for i in range(c["num_hidden_layers"]):
        w = {k: weights["layers"][k][i] for k in LAYER_KEYS}
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


# ---------------------------------------------------------------- training

def loss(params, tokens, c: Mapping, q_block=Q_BLOCK, t_block=T_BLOCK):
    """Mean next-token cross-entropy over tokens [B, S+1] (S positions a
    sequence), float32 throughout.  The blocks see all B*S tokens at once
    for their matmuls and each sequence alone for attention."""
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    B, S = inputs.shape
    eps = float(c["rms_norm_eps"])
    hd, H, KV = _hd(c), c["num_attention_heads"], c["num_key_value_heads"]
    theta = float(c["rope_theta"])
    pos = jnp.arange(S)

    @jax.checkpoint
    def blk(x, w):                                  # x [B, S, D]
        h = _rms(x, w["attn_norm"], eps)
        q = jax.vmap(lambda a: _rope(a, pos, theta))(
            (h @ w["wq"]).reshape(B, S, H, hd))
        k = jax.vmap(lambda a: _rope(a, pos, theta))(
            (h @ w["wk"]).reshape(B, S, KV, hd))
        v = (h @ w["wv"]).reshape(B, S, KV, hd)
        att = lax.map(lambda a: _attention(*a, q_block), (q, k, v))
        x = x + att @ w["wo"]
        h = _rms(x, w["ffn_norm"], eps).reshape(B * S, -1)
        return x + _ffn(h, w["w_gate"], w["w_up"], w["w_down"],
                        t_block).reshape(B, S, -1)

    x = params["embed"][inputs].astype(jnp.float32)
    x, _ = lax.scan(lambda x, w: (blk(x, w), None), x, params["layers"])
    h = _rms(x, params["norm_f"], eps).reshape(B * S, -1)
    tb = min(t_block, B * S)

    @jax.checkpoint
    def nll(args):
        hi, ti = args
        lg = hi @ params["lm_head"]
        return (jax.nn.logsumexp(lg, -1)
                - jnp.take_along_axis(lg, ti[:, None], -1)[:, 0]).sum()

    total = lax.map(nll, (h.reshape(-1, tb, h.shape[-1]),
                          targets.reshape(-1, tb))).sum()
    return total / targets.size


def adamw_trajectory(c: Mapping, params, tokens, steps: int,
                     lr: float = 1e-3, b1: float = 0.9, b2: float = 0.999,
                     eps: float = 1e-8, weight_decay: float = 1e-4,
                     q_block=Q_BLOCK, t_block=T_BLOCK) -> Dict[str, List[float]]:
    """Losses L(p_0) .. L(p_steps) and the gradient norms before them, of
    plain AdamW (decoupled decay, bias correction: the defaults of the
    optimizer the training entry point builds) on ONE repeated batch, in
    float32.  `params` is consumed.  For the few steps compared, the two
    moments are re-derived from the kept gradients instead of stored, so
    that the reference holds one copy of the parameters per step kept
    and fits beside nothing else on the chip."""

    @jax.jit
    def value_and_grad(p, t):
        with jax.default_matmul_precision(HIGHEST):
            return jax.value_and_grad(loss)(p, t, c, q_block, t_block)

    @jax.jit
    def value(p, t):
        with jax.default_matmul_precision(HIGHEST):
            return loss(p, t, c, q_block, t_block)

    @partial(jax.jit, donate_argnums=(0,))
    def update(p, grads):
        t = len(grads)

        def leaf(p, *gs):
            m = sum((1 - b1) * b1 ** (t - 1 - i) * g for i, g in enumerate(gs))
            v = sum((1 - b2) * b2 ** (t - 1 - i) * g * g
                    for i, g in enumerate(gs))
            mh, vh = m / (1 - b1 ** t), v / (1 - b2 ** t)
            return p - lr * (mh / (jnp.sqrt(vh) + eps) + weight_decay * p)

        return jax.tree.map(leaf, p, *grads)

    losses, norms, grads = [], [], []
    for t in range(steps):
        l, g = value_and_grad(params, tokens)
        losses.append(float(l))
        norms.append(float(jnp.sqrt(sum(jnp.vdot(x, x)
                                        for x in jax.tree.leaves(g)))))
        grads.append(g)
        params = update(params, tuple(grads))
    losses.append(float(value(params, tokens)))
    return {"losses": losses, "grad_norms": norms}
