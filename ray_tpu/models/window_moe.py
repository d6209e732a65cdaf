"""Sliding-window / full attention decoder with a gated attention output
and routed experts beside a shared one (`model_type` `afmoe`).

A block has FOUR RMS norms (sandwich): `a = x + N2(Attn(N1(x)))`,
`y = a + N4(FF(N3(a)))`; the embedding is scaled by `sqrt(dim)` (muP),
the head is its own matrix and the logits are not scaled.

- **Which kind a layer is** comes from the config (`full_layers`,
  indices from 0; every other layer is a WINDOW layer), as do all sizes.
  Both kinds: GQA with heads of `head_dim`, q and k RMS-normalised a
  head, an output gate `sigmoid(h Wg)` on the attention result before
  `Wo`.  A window layer rotates q and k (rotate-half,
  `models/llama.py::apply_rope`) and its query at position p sees the
  keys p - window + 1 .. p; a full layer has NO positions at all and
  sees every key up to its own.
- **Two kinds of pool** (models/serving.py), a row a token holding its
  KV heads side by side (`kvH * hd` = 512 lanes: with 4 KV heads a
  `[bs, kvH, hd]` block is tiled (4, 128) and every insert then copies
  the whole pool to re-tile it; `ops/paged_attention.py`, "Few KV
  heads"): the full layers' K and V (`k`, `v`: `[n_full, NB, bs, kvH
  hd]`) hold a sequence's every row; the window layers' (`k_w`, `v_w`:
  `[n_window, NBw, bs, kvH hd]`) are walked through a RING of a table,
  `ring` blocks wide, in which position t lives at
  `table[(t // bs) % ring]`: a sequence holds at most `ring` blocks of
  that kind whatever its length.  The decode tick plans
  `ops/paged_attention.py` twice, once a kind (the window form for the
  ring), outside the layer loop.
- **Prefill attends blockwise over the keys** under an online softmax:
  float32 scores `[H, Pb, S_pad]` over a long history would not fit
  beside the weights.  A full layer walks its gathered history up to the
  chunk's end; a window layer walks the `window` rows before the chunk,
  taken out of its ring, and the chunk's own (`piece_walk`).  By one of
  two paths, chosen by backend and shape alone
  (`ops.attention.prefill_engages`, inside `blockwise_attention`): the
  Pallas forward `ops.attention.flash_prefill`, whose scores stay on
  the chip and which skips every (query, key) tile no query sees, or
  the XLA loop over key blocks, which is also the kernel's reference.
- **Feed-forward**: `n_dense_layers` leading SwiGLU layers, then ONE
  shared SwiGLU every token passes through plus
  `models/moe.py::dropless_moe` over all `n_experts` under the
  sigmoid-with-bias routing rule (renormalised, x
  `routed_scaling_factor`).
- One definition of a layer over three situations: no cache (`forward`),
  one sequence's call of a bucketed / chunked prefill (`prefill_paged`),
  one token a slot (`decode_step_paged`).  The layer loop is unrolled
  over a LIST of per-layer dicts, as in `models/latent_moe.py`.

Every size comes from `WindowMoEConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.latent_moe import _swiglu
from ray_tpu.models.llama import apply_rope, embed_lookup, rms_norm
from ray_tpu.models.moe import (
    dropless_moe, serving_grouped_path, sigmoid_bias_top_k,
)
from ray_tpu.models.serving import ServingFns
from ray_tpu.ops import attention as flash
from ray_tpu.ops import paged_attention as paged

_MASK = -1e30
WINDOW_LEAVES = ("k_w", "v_w")      # the pool leaves of the window kind
# Which kinds of layer rotate q and k (the tests' mutilated program adds
# "full").
ROTARY_KINDS = ("window",)


@dataclasses.dataclass(frozen=True)
class WindowMoEConfig:
    vocab_size: int = 200192
    dim: int = 2048
    n_layers: int = 32
    # layers (from 0) that attend to every key; the others have a window
    full_layers: Tuple[int, ...] = (3, 7, 11, 15, 19, 23, 27, 31)
    window: int = 2048              # keys a window layer's query sees
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    dense_hidden_dim: int = 6144
    expert_hidden_dim: int = 1024
    shared_hidden_dim: int = 1024   # n_shared_experts x their width
    n_experts: int = 128
    top_k: int = 8
    routed_scaling_factor: float = 2.826
    max_seq_len: int = 131072
    rope_theta: float = 1e4
    norm_eps: float = 1e-5
    # keys a step of the prefill's blockwise attention takes
    prefill_key_block: int = 1024
    dtype: Any = jnp.bfloat16   # activation/matmul dtype
    param_dtype: Any = jnp.bfloat16

    def kind(self, i: int) -> str:
        return "full" if i in self.full_layers else "window"

    @property
    def n_full_layers(self) -> int:
        return sum(l < self.n_layers for l in self.full_layers)

    @property
    def n_window_layers(self) -> int:
        return self.n_layers - self.n_full_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @staticmethod
    def tiny(**overrides) -> "WindowMoEConfig":
        """Test-size config: a dense window layer, then a period
        (window, FULL, window, window)."""
        return WindowMoEConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=5, full_layers=(2,), window=8,
            n_dense_layers=1, n_heads=4, n_kv_heads=2, head_dim=16,
            dense_hidden_dim=128, expert_hidden_dim=32, shared_hidden_dim=32,
            n_experts=8, top_k=2, max_seq_len=128, prefill_key_block=8),
            **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: WindowMoEConfig, key: jax.Array,
                bias_scale: float = 0.02) -> Dict[str, Any]:
    """normal(0, 0.02) matrices, unit norms, a selection bias drawn at
    `bias_scale`."""
    c = config
    dt = c.param_dtype
    D, hd = c.dim, c.head_dim

    def draw(key, *shape):
        return jax.nn.initializers.normal(0.02)(key, shape, dt)

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers: List[Dict[str, jax.Array]] = []
    for i, lk in enumerate(jax.random.split(k_layers, c.n_layers)):
        ks = jax.random.split(lk, 14)
        p = {name: jnp.ones((D,), dt) for name in
             ("attn_norm", "post_attn_norm", "ffn_norm", "post_ffn_norm")}
        p.update(wq=draw(ks[0], D, c.n_heads * hd),
                 wk=draw(ks[1], D, c.n_kv_heads * hd),
                 wv=draw(ks[2], D, c.n_kv_heads * hd),
                 wg=draw(ks[3], D, c.n_heads * hd),
                 q_norm=jnp.ones((hd,), dt), k_norm=jnp.ones((hd,), dt),
                 wo=draw(ks[4], c.n_heads * hd, D))
        if i < c.n_dense_layers:
            F = c.dense_hidden_dim
            p.update(w_gate=draw(ks[5], D, F), w_up=draw(ks[6], D, F),
                     w_down=draw(ks[7], F, D))
        else:
            E, F, Fs = c.n_experts, c.expert_hidden_dim, c.shared_hidden_dim
            p.update(
                router=draw(ks[5], D, E),
                router_bias=jax.random.normal(ks[6], (E,), jnp.float32)
                * bias_scale,
                w_gate=draw(ks[7], E, D, F), w_up=draw(ks[8], E, D, F),
                w_down=draw(ks[9], E, F, D),
                ws_gate=draw(ks[10], D, Fs), ws_up=draw(ks[11], D, Fs),
                ws_down=draw(ks[12], Fs, D))
        layers.append(p)
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt),
            "lm_head": draw(k_head, D, c.vocab_size)}


def lm_head_weight(params: Dict[str, Any], config: WindowMoEConfig):
    return params["lm_head"].astype(config.dtype)


# ---------------------------------------------------------------------------
# Attention under a mask, two ways: all keys at once (short sequences,
# one decode row), and a block of keys at a time (prefill over a long
# history).  q [B, Q, H, hd], k and v [B, S, kvH, hd]; head h reads KV
# group h // (H / kvH), each row once.
# ---------------------------------------------------------------------------

def _seen(qpos, kpos, window):
    """[.., Q, S] bool: key position in (qpos - window, qpos], and a
    real one (>= 0).  `window` None: every key up to the query's."""
    q, k = qpos[..., :, None], kpos[..., None, :]
    ok = (k <= q) & (k >= 0)
    return ok if window is None else ok & (k > q - window)


def _masked_attention(q, k, v, mask, scale=None):
    B, Q, H, hd = q.shape
    kvh = k.shape[2]
    qg = q.reshape(B, Q, kvh, H // kvh, hd)
    s = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k).astype(jnp.float32) \
        * (scale or 1.0 / math.sqrt(hd))
    s = jnp.where(mask[:, None, None], s, _MASK)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bgrqk,bkgd->bqgrd", p, v).reshape(B, Q, H, hd)


def blockwise_attention(q, k, v, qpos, kpos0, lo, hi, window, block,
                        scale=None, causal_block=1):
    """ONE sequence: q [Q, H, hd] at positions qpos [Q] against k, v
    [S, kvH, hd] whose row i is the key of position kpos0 + i, taken
    `block` rows a step over steps lo .. hi - 1 (traced: rows outside
    them are never read) under `_seen`'s mask, in an online softmax
    (float32 running max, sum and accumulator).  [Q, H, hd].  `scale`:
    the scores' factor where it is not `hd ** -0.5`.  `causal_block` L
    (a power of two; `models/blockdiff_moe.py`): a query sees the keys
    up to the last position of its own block of L, `qpos | (L - 1)`.

    Several queries are consecutive positions from qpos[0] (every
    caller's are), and where `ops.attention.prefill_engages` says so
    they go through the kernel, the same steps' rows as its bounds:
    this is the one place that chooses, for every model that walks a
    history so.  The loop below is the path everywhere else (off TPU,
    the tiny models' heads, one query) and what the kernel is tested
    against."""
    Q, H, hd = q.shape
    S, kvh = k.shape[:2]
    kb = math.gcd(S, block)
    scale = scale or 1.0 / math.sqrt(hd)
    if flash.prefill_engages(Q, hd, S):
        # a position before the sequence's first is no key
        return flash.flash_prefill(
            q, k, v, qpos[0] - kpos0, jnp.maximum(lo * kb, -kpos0), hi * kb,
            window=window, scale=scale, causal_block=causal_block)
    qg = q.reshape(Q, kvh, H // kvh, hd)
    if causal_block > 1:
        qpos = qpos | (causal_block - 1)

    def step(i, carry):
        m, l, acc = carry
        ki = lax.dynamic_slice_in_dim(k, i * kb, kb, 0)
        vi = lax.dynamic_slice_in_dim(v, i * kb, kb, 0)
        s = jnp.einsum("qgrd,kgd->grqk", qg, ki,
                       preferred_element_type=jnp.float32) * scale
        mask = _seen(qpos, kpos0 + i * kb + jnp.arange(kb), window)
        s = jnp.where(mask, s, _MASK)
        m_new = jnp.maximum(m, s.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        l = alpha * l + p.sum(-1, keepdims=True)
        acc = alpha * acc + jnp.einsum(
            "grqk,kgd->grqd", p.astype(v.dtype), vi,
            preferred_element_type=jnp.float32)
        return m_new, l, acc

    shape = (kvh, H // kvh, Q)
    _, l, acc = lax.fori_loop(lo, hi, step, (
        jnp.full(shape + (1,), _MASK, jnp.float32),
        jnp.zeros(shape + (1,), jnp.float32),
        jnp.zeros(shape + (hd,), jnp.float32)))
    return jnp.moveaxis(acc / l, 2, 0).reshape(Q, H, hd).astype(q.dtype)


def piece_walk(kind, start, Pb, S, window, block, most=max):
    """What `blockwise_attention` is told of a piece of Pb rows at
    `start` over a `kind` layer's S key rows (the full kind's gathered
    history by position; the window kind's `window` rows before the
    piece, then the piece's own): (the position of key row 0, the first
    step, one past the last), steps of gcd(S, block) rows.  `start` a
    Python integer, or traced with `most=jnp.maximum`."""
    step = math.gcd(S, block)
    if kind == "full":
        return 0, 0, -(-(start + Pb) // step)
    return start - window, most(window - start, 0) // step, S // step


def walk_tiles(layers, start, Pb, S_pad, window, block, hd):
    """Host arithmetic for `engine.stats()`: a piece of Pb rows at
    `start` through `layers` ({kind: how many}; S_pad rows of gathered
    history a full layer; heads `hd` wide) -> (which form its attention
    compiled to, "kernel" | "loop"; the (query, key) tiles
    `flash_prefill`'s bounds let through; the tiles of the rectangles
    the loop multiplies, every query against every row of its steps),
    summed over the layers."""
    run = dense = 0
    kernel = True
    for kind, n in layers.items():
        S = S_pad if kind == "full" else window + Pb
        kpos0, lo, hi = piece_walk(kind, start, Pb, S, window, block)
        step = math.gcd(S, block)
        bq, bk = flash._prefill_blocks(Pb, S)
        run += n * flash.prefill_tiles(
            Pb, S, start - kpos0, max(lo * step, -kpos0), hi * step,
            window if kind == "window" else None)
        dense += n * (Pb // bq) * -(-(hi - lo) * step // bk)
        kernel = kernel and flash.prefill_engages(Pb, hd, S)
    return "kernel" if kernel else "loop", run, dense


# ---------------------------------------------------------------------------
# The layers' caches: where a layer's new K and V rows go and which
# rows its queries see.  `attend(c, kind, l, q, k, v)` for layer l OF
# ITS KIND: q [B, S, H, hd], k and v [B, S, kvH, hd] -> [B, S, H, hd].
# ---------------------------------------------------------------------------

class _NoCache:
    """The sequence's own rows are its keys (scoring, tests)."""

    def __init__(self, qpos):
        self.qpos = qpos

    def attend(self, c, kind, l, q, k, v):
        mask = _seen(self.qpos, self.qpos,
                     c.window if kind == "window" else None)
        return _masked_attention(q, k, v, mask)


class _History:
    """ONE sequence with its gathered history (models/serving.py): the
    full kind's [Lf, S_pad, kvH hd] by position, the window kind's
    [Lw, R, kvH hd] a RING in which position t lies at row t % R.  The
    chunk sits at `start`..; its rows are kept for the engine to
    scatter.  Both kinds attend through `blockwise_attention`: the
    kernel's tiles where it engages, a block of keys at a time
    elsewhere."""

    def __init__(self, hist, start, qpos):
        self.hist, self.start, self.qpos = hist, start, qpos
        self.rows = {name: [] for name in hist}

    def attend(self, c, kind, l, q, k, v):
        names = WINDOW_LEAVES if kind == "window" else ("k", "v")
        dt = self.hist[names[0]].dtype
        Pb, start = k.shape[1], self.start
        new = [x[0].reshape(Pb, -1).astype(dt) for x in (k, v)]
        for name, x in zip(names, new):
            self.rows[name].append(x)

        def heads(x):
            return x.reshape(x.shape[0], c.n_kv_heads, c.head_dim).astype(
                c.dtype)

        if kind == "full":
            keys = [lax.dynamic_update_slice(
                self.hist[name][l], x, (start, 0))
                for name, x in zip(names, new)]
            window = None
        else:
            # the `window` rows before the chunk, out of the ring (a
            # position before the sequence's first is masked), then the
            # chunk's own
            window = c.window
            R = self.hist[names[0]].shape[1]
            before = (start - window + jnp.arange(window)) % R
            keys = [jnp.concatenate([self.hist[name][l][before], x])
                    for name, x in zip(names, new)]
        kpos0, lo, hi = piece_walk(kind, start, Pb, keys[0].shape[0],
                                   c.window, c.prefill_key_block,
                                   most=jnp.maximum)
        out = blockwise_attention(
            q[0], *map(heads, keys), self.qpos, kpos0, lo, hi, window,
            c.prefill_key_block)
        return out[None]

    def stacked(self):
        return {name: jnp.stack(x) for name, x in self.rows.items()}


class _Paged:
    """One new row a sequence at positions `qpos` [B], written into its
    kind's pool at its table's position (a physical block out of bounds,
    so dropped, for a dead slot): the full kind's table by `pos // bs`,
    the window kind's ring by `(pos // bs) % ring`.  Then attended by
    one of two paths, chosen by backend and shape alone
    (`ops.paged_attention.engages`): the kernel reads the live blocks
    through the table where they lie (its window form for the ring), the
    gather builds every slot's padded view and masks it.  The kernel's
    scalars are planned here, once a kind a program.  Keeps the updated
    pools."""

    def __init__(self, c, pools, tables, qpos, active):
        self.pools = dict(pools)
        self.qpos = qpos
        bs = pools["k"].shape[2]
        seq = jnp.arange(qpos.shape[0])
        self.off = qpos % bs
        self.tables = tables
        ring = tables["window"].shape[1]
        at = {"full": qpos // bs, "window": (qpos // bs) % ring}
        self.phys = {}
        for kind, leaf in (("full", "k"), ("window", "k_w")):
            phys = self.tables[kind][seq, at[kind]]
            if active is not None:
                phys = jnp.where(active, phys, pools[leaf].shape[1])
            self.phys[kind] = phys
        self.plans = {"full": None, "window": None}
        if paged.engages(pools["k"]) and paged.engages(pools["k_w"]):
            with jax.named_scope("attn"):
                with jax.named_scope("paged"):
                    self.plans["full"] = paged.plan(
                        tables["full"], qpos, active, bs)
                with jax.named_scope("paged_window"):
                    self.plans["window"] = paged.plan(
                        tables["window"], qpos, active, bs, window=c.window)

    def attend(self, c, kind, l, q, k, v):
        names = WINDOW_LEAVES if kind == "window" else ("k", "v")
        window = c.window if kind == "window" else None
        with jax.named_scope("kv_write"):
            for name, x in zip(names, (k, v)):
                pool = self.pools[name]
                self.pools[name] = pool.at[
                    l, self.phys[kind], self.off].set(
                        x[:, 0].reshape(x.shape[0], -1).astype(pool.dtype))
        k_pool, v_pool = (self.pools[name] for name in names)
        if self.plans[kind] is not None:
            with jax.named_scope("paged_window" if window else "paged"):
                return paged.paged_attention(
                    q, k_pool, v_pool, l, self.plans[kind], window=window)
        table = self.tables[kind]
        B, nb = table.shape
        rows = nb * k_pool.shape[2]
        with jax.named_scope("kv_gather"):
            dense = [pool[l, table].reshape(
                B, rows, c.n_kv_heads, c.head_dim).astype(c.dtype)
                for pool in (k_pool, v_pool)]
        pos = self.qpos[:, None]
        kpos = jnp.arange(rows)[None]
        if window:
            # row r of the ring holds the last position <= pos that is
            # r modulo the ring's rows
            kpos = pos - (pos - kpos) % rows
        return _masked_attention(q, *dense, _seen(pos, kpos, window))


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def attention_operator(c: WindowMoEConfig, kind: str, l: int, p, x, cos,
                       sin, cache):
    """x [B, S, D] -> x + the layer's gated attention, its rows going
    through `cache` at index l of its kind; cos/sin [B, S, hd/2] are
    used by the kinds that rotate (`ROTARY_KINDS`)."""
    B, S, _ = x.shape
    dt, hd = c.dtype, c.head_dim
    with jax.named_scope("attn"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        q = (h @ p["wq"].astype(dt)).reshape(B, S, c.n_heads, hd)
        k = (h @ p["wk"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        v = (h @ p["wv"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        with jax.named_scope("gate"):
            gate = jax.nn.sigmoid(h @ p["wg"].astype(dt))
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, p["q_norm"], c.norm_eps)
            k = rms_norm(k, p["k_norm"], c.norm_eps)
        if kind in ROTARY_KINDS:
            q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        o = cache.attend(c, kind, l, q, k, v)
        with jax.named_scope("gate"):
            o = o.reshape(B, S, c.n_heads * hd) * gate
        return x + rms_norm(o @ p["wo"].astype(dt), p["post_attn_norm"],
                            c.norm_eps)


def routed_experts(c: WindowMoEConfig, p, h, live=None, share=None):
    """h [T, D] -> (the routed experts' sum [T, D], tokens routed to
    each held expert); `share` as in `models/moe.py::dropless_moe`."""
    return dropless_moe(
        h, p, sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor),
        live=live, share=share)


def shared_expert(c: WindowMoEConfig, p, h):
    with jax.named_scope("shared"):
        return _swiglu(h, p["ws_gate"], p["ws_up"], p["ws_down"], c.dtype)


def feed_forward(c: WindowMoEConfig, p, x, live=None, share=None):
    """The feed-forward half: a dense SwiGLU, or the shared expert plus
    the routed ones.  Returns (x, tokens routed to each expert or
    None)."""
    B, S, D = x.shape
    if "router" not in p:
        with jax.named_scope("mlp"):
            h = rms_norm(x, p["ffn_norm"], c.norm_eps)
            y = _swiglu(h, p["w_gate"], p["w_up"], p["w_down"], c.dtype)
            return x + rms_norm(y, p["post_ffn_norm"], c.norm_eps), None
    with jax.named_scope("moe"):
        h = rms_norm(x, p["ffn_norm"], c.norm_eps)
        y, sizes = routed_experts(
            c, p, h.reshape(B * S, D),
            None if live is None else live.reshape(B * S), share)
        y = y.reshape(B, S, D) + shared_expert(c, p, h)
        return x + rms_norm(y, p["post_ffn_norm"], c.norm_eps), sizes


def _stack(c: WindowMoEConfig, params, tokens, qpos, cache, live=None):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> (normed hidden [B, S, D], tokens routed to each expert
    [n_moe_layers, E])."""
    hd = c.head_dim
    inv = 1.0 / (c.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                  / hd))
    freqs = qpos.astype(jnp.float32)[..., None] * inv       # [B, S, hd/2]
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    x = embed_lookup(params["embed"].astype(c.dtype), tokens) \
        * jnp.asarray(math.sqrt(c.dim), c.dtype)
    routed = []
    at = {"full": 0, "window": 0}
    for i, p in enumerate(params["layers"]):
        kind = c.kind(i)
        x = attention_operator(c, kind, at[kind], p, x, cos, sin, cache)
        at[kind] += 1
        x, sizes = feed_forward(c, p, x, live)
        if sizes is not None:
            routed.append(sizes)
    return rms_norm(x, params["norm_f"], c.norm_eps), jnp.stack(routed)


def _head(c: WindowMoEConfig, params, x):
    with jax.named_scope("lm_head"):
        return jnp.dot(x, params["lm_head"].astype(c.dtype),
                       preferred_element_type=jnp.float32)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: WindowMoEConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _ = _stack(config, params, tokens, qpos, _NoCache(qpos))
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def window_kind(config: WindowMoEConfig) -> Tuple[int, Tuple[str, ...]]:
    """(keys a window layer's query sees, the pool leaves of that kind)."""
    return config.window, WINDOW_LEAVES


def init_paged_pool(config: WindowMoEConfig, num_blocks: int,
                    block_size: int, window_blocks: Optional[int] = None
                    ) -> Dict[str, jax.Array]:
    """K and V a token, its KV heads side by side: `num_blocks` blocks
    for the full layers, `window_blocks` for the window layers."""
    c = config
    row = (block_size, c.n_kv_heads * c.head_dim)
    full = (c.n_full_layers, num_blocks) + row
    ring = (c.n_window_layers, window_blocks or num_blocks) + row
    return {"k": jnp.zeros(full, c.dtype), "v": jnp.zeros(full, c.dtype),
            "k_w": jnp.zeros(ring, c.dtype), "v_w": jnp.zeros(ring, c.dtype)}


def prefill_paged(params, tokens, start, hist, config: WindowMoEConfig,
                  n_real):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `hist` the gathered history, the window kind's
    as its ring.  Padding goes through no expert."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = _History(hist, start, qpos[0])
    x, _ = _stack(config, params, tokens, qpos, cache,
                  live=(jnp.arange(Pb) < n_real)[None])
    return x, cache.stacked()


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: WindowMoEConfig,
                      active: Optional[jax.Array] = None):
    """One token a slot against both kinds of pool: tokens [B] at
    positions [B], `tables` {"full": [B, nb], "window": [B, ring]}.  A
    dead slot writes no row and goes through no expert.  Returns (logits
    [B, V], pools, counts): tokens routed to each expert of each expert
    layer, the distinct experts touched summed over those layers, and 1
    for the tick."""
    cache = _Paged(config, pools, tables, positions, active)
    x, routed = _stack(config, params, tokens[:, None], positions[:, None],
                       cache, live=None if active is None
                       else active[:, None])
    counts = {"expert_tokens": routed,
              "experts_touched": jnp.sum(routed > 0, dtype=jnp.int32),
              "ticks": jnp.ones((), jnp.int32)}
    return _head(config, params, x[:, 0]), cache.pools, counts


def init_counts(config: WindowMoEConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts."""
    return {"expert_tokens": jnp.zeros(
                (config.n_moe_layers, config.n_experts), jnp.int32),
            "experts_touched": jnp.zeros((), jnp.int32),
            "ticks": jnp.zeros((), jnp.int32)}


def insert_attention(config: WindowMoEConfig, start: int, bucket: int,
                     max_seq_len: int):
    """`walk_tiles` of one piece through every layer."""
    c = config
    return walk_tiles({"full": c.n_full_layers, "window": c.n_window_layers},
                      start, bucket, max_seq_len, c.window,
                      c.prefill_key_block, c.head_dim)


def _paged_attention(pools) -> str:
    both = paged.engages(pools["k"]) and paged.engages(pools["k_w"])
    return "kernel" if both else "gather"


_SERVING = ServingFns(
    name="window + full GQA (gated, QK-norm), experts beside a shared one "
         "(models/window_moe.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=lm_head_weight, init_counts=init_counts,
    window_kind=window_kind, paged_attention=_paged_attention,
    grouped_matmul=serving_grouped_path, insert_attention=insert_attention)
