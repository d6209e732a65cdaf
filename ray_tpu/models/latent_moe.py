"""Latent-attention decoder with dropless sigmoid-routed experts.

The block of the DeepSeek-V3 family as published (`model_type`
`deepseek_v3`), written once over a cache interface; the query is
projected whole, or through a low rank under a norm of its own
(`q_lora_rank`, with both low-rank paths scaled where `scale_lora` says
so: `models/shortcut_moe.py` runs this attention twice a layer):

- **Latent attention (MLA).** A token's key/value state is ONE row per
  layer, `c ‖ k_rope`: the normed latent (`kv_lora_rank` wide) and one
  rotary key shared by all heads, after rotary.  Two forms of the same
  function read it:
  the *expanded* form rebuilds per-head keys and values from the latent
  (`c @ wkv_b`: prefill, many queries a key), the *absorbed* form folds
  `wkv_b` into the query and the output instead (decode: one query, the
  rows are read as they lie; multi-query attention over one wide head).
  Rotary pairs are the published interleaved ones, (x[2i], x[2i+1]) at
  frequency i; the program stores q_rope and k_rope de-interleaved
  (evens, then odds), which leaves every score as it was because both
  sides are laid out alike.
- **Layers.** `n_dense_layers` leading SwiGLU layers, then expert
  layers: `models/moe.py::dropless_moe` under the sigmoid-with-bias
  routing rule, plus shared experts every token passes through.
  Parameters are a LIST of per-layer dicts and the layer loop is
  unrolled: a layer's expert weights are then buffers of their own that
  the grouped products read in place (a `lax.scan` over stacked weights
  copies each layer's slice out first), and the paged pool is written
  and read (by `ops/paged_attention.py`'s kernel through the block
  table, or gathered) at a static layer index, in place.
- **One definition of a layer** (`_layer`) over three caches: none
  (`forward`: scoring and tests), history + write-back (`prefill_paged`:
  bucketed and chunked prefill with a prefix history, the expanded
  form tile by tile over the keys the request has and not the slot's
  padded rows: `_History.attend`), paged decode (`decode_step_paged`).

Every size comes from `LatentMoEConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import embed_lookup, rms_norm
from ray_tpu.models.moe import (
    dropless_moe, serving_grouped_path, sigmoid_bias_top_k,
)
from ray_tpu.models.serving import HISTORY_TILE, ServingFns
from ray_tpu.ops import paged_attention as paged


@dataclasses.dataclass(frozen=True)
class LatentMoEConfig:
    vocab_size: int = 128256
    dim: int = 2048
    n_layers: int = 48
    n_dense_layers: int = 1
    n_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    dense_hidden_dim: int = 6144
    expert_hidden_dim: int = 768
    n_experts: int = 128
    top_k: int = 6
    n_shared_experts: int = 2
    routed_scaling_factor: float = 2.448
    # the query through a low rank with a norm of its own (None: `wq`
    # whole), and the two low-rank paths x sqrt(dim / rank) after
    # their norms (`mla_scale_q_lora` / `mla_scale_kv_lora`)
    q_lora_rank: Optional[int] = None
    scale_lora: bool = False
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16   # activation/matmul dtype
    param_dtype: Any = jnp.bfloat16

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def cache_row(self) -> int:
        """Width of a cache row: latent ‖ rotary key, then zeros up to a
        whole number of the chip's 128-lane tiles.  The tiled layout pads
        the minor dimension to that anyway; a pool declared 576 wide made
        the v5e compiler turn it round (blocks minor-most) and copy it
        whole on the way in and out of every tick."""
        return -(-(self.kv_lora_rank + self.qk_rope_head_dim) // 128) * 128

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @staticmethod
    def tiny(**overrides) -> "LatentMoEConfig":
        """Test-size config: runs on the CPU in milliseconds."""
        return LatentMoEConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=3, n_dense_layers=1, n_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, dense_hidden_dim=128, expert_hidden_dim=32,
            n_experts=8, top_k=2, n_shared_experts=2, max_seq_len=128),
            **overrides})

    def serving(self):
        """This model's functions for `serve/llm/engine.py`
        (models/serving.py)."""
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: LatentMoEConfig, key: jax.Array,
                bias_scale: float = 0.01) -> Dict[str, Any]:
    """normal(0, 0.02) matrices, unit norms, and a selection bias drawn
    at `bias_scale` (a trained checkpoint's is not zero)."""
    c = config
    dt = c.param_dtype
    H, D = c.n_heads, c.dim
    k_embed, k_out, k_layers = jax.random.split(key, 3)

    def draw(key, *shape):
        return jax.nn.initializers.normal(0.02)(key, shape, dt)

    layers: List[Dict[str, jax.Array]] = []
    for i, lk in enumerate(jax.random.split(k_layers, c.n_layers)):
        ks = jax.random.split(lk, 12)
        p = {
            "attn_norm": jnp.ones((D,), dt),
            "wq": draw(ks[0], D, H * c.qk_head_dim),
            "wkv_a": draw(ks[1], D, c.kv_lora_rank + c.qk_rope_head_dim),
            "kv_norm": jnp.ones((c.kv_lora_rank,), dt),
            "wkv_b": draw(ks[2], c.kv_lora_rank,
                          H * (c.qk_nope_head_dim + c.v_head_dim)),
            "wo": draw(ks[3], H * c.v_head_dim, D),
            "ffn_norm": jnp.ones((D,), dt),
        }
        if i < c.n_dense_layers:
            F = c.dense_hidden_dim
            p.update(w_gate=draw(ks[4], D, F), w_up=draw(ks[5], D, F),
                     w_down=draw(ks[6], F, D))
        else:
            E, F = c.n_experts, c.expert_hidden_dim
            Fs = c.n_shared_experts * F
            p.update(
                router=draw(ks[4], D, E),
                router_bias=jax.random.normal(ks[5], (E,), jnp.float32)
                * bias_scale,
                w_gate=draw(ks[6], E, D, F), w_up=draw(ks[7], E, D, F),
                w_down=draw(ks[8], E, F, D),
                ws_gate=draw(ks[9], D, Fs), ws_up=draw(ks[10], D, Fs),
                ws_down=draw(ks[11], Fs, D))
        layers.append(p)
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt),
            "lm_head": draw(k_out, D, c.vocab_size)}


def lm_head_weight(params: Dict[str, Any], config: LatentMoEConfig):
    return params["lm_head"].astype(config.dtype)


# ---------------------------------------------------------------------------
# Latent attention: one row a token, two forms of one function
# ---------------------------------------------------------------------------

def _rope_interleaved(x: jax.Array, cos: jax.Array, sin: jax.Array):
    """x [..., r] with pairs (x[2i], x[2i+1]); cos/sin broadcast against
    [..., r/2].  Returns the rotated pairs de-interleaved: all first
    elements, then all second ones."""
    x = x.astype(jnp.float32)
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _masked_softmax(scores, qpos, n_keys, dtype):
    """scores [B, H, Q, K] float32; query j of row b sees keys at
    positions <= qpos[b, j]."""
    mask = jnp.arange(n_keys)[None, None, None, :] \
        <= qpos[:, None, :, None]
    return jax.nn.softmax(jnp.where(mask, scores, -1e30),
                          axis=-1).astype(dtype)


def attend_expanded(c: LatentMoEConfig, wkv_b, q_nope, q_rope, rows, qpos):
    """Prefill form.  q_nope [B, Q, H, n], q_rope [B, Q, H, r]; cache
    rows [B, K, cache_row].  Per-head keys and values are rebuilt from
    the latent.  Returns [B, Q, H * v]."""
    B, K, _ = rows.shape
    n, v, rank = c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank
    r = c.qk_rope_head_dim
    kv = (rows[..., :rank] @ wkv_b).reshape(B, K, c.n_heads, n + v)
    scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, kv[..., :n])
              + jnp.einsum("bqhr,bkr->bhqk", q_rope,
                           rows[..., rank:rank + r])
              ).astype(jnp.float32) * (1.0 / math.sqrt(c.qk_head_dim))
    probs = _masked_softmax(scores, qpos, K, q_nope.dtype)
    out = jnp.einsum("bhqk,bkhv->bqhv", probs, kv[..., n:])
    return out.reshape(B, -1, c.n_heads * v)


def _absorbed_query(c: LatentMoEConfig, w, q_nope, q_rope, row: int):
    """Each head's query as a cache row is laid: `wkv_b`'s key part
    folded into q_nope (w [rank, H, n + v]), then the shared-key
    channels, then zeros up to `row`.  [B, Q, H, row]."""
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope,
                       w[..., :c.qk_nope_head_dim])
    return jnp.concatenate(
        [q_lat, q_rope, jnp.zeros(q_lat.shape[:-1] + (
            row - c.kv_lora_rank - c.qk_rope_head_dim,), q_lat.dtype)],
        axis=-1)


def _absorbed_output(c: LatentMoEConfig, w, o_lat):
    """o_lat [B, Q, H, rank], the weighted sum of the latents, through
    `wkv_b`'s value part: [B, Q, H * v]."""
    out = jnp.einsum("bqhr,rhv->bqhv", o_lat, w[..., c.qk_nope_head_dim:])
    return out.reshape(out.shape[0], -1, c.n_heads * c.v_head_dim)


def attend_absorbed(c: LatentMoEConfig, wkv_b, q_nope, q_rope, rows, qpos):
    """Decode form: `wkv_b`'s key part goes into the query and its value
    part onto the output, so the rows are read as they lie: multi-query
    attention with ONE key/value head a row wide and all H query heads
    on it.  The weighted sum runs over whole rows and the columns past
    the latent are dropped from its result: a quarter more
    multiply-adds, and no copy of the rows without them."""
    _, K, row = rows.shape
    rank = c.kv_lora_rank
    w = wkv_b.reshape(rank, c.n_heads, -1)
    q_row = _absorbed_query(c, w, q_nope, q_rope, row)
    scores = jnp.einsum("bqhr,bkr->bhqk", q_row, rows).astype(
        jnp.float32) * (1.0 / math.sqrt(c.qk_head_dim))
    probs = _masked_softmax(scores, qpos, K, q_nope.dtype)
    o_lat = jnp.einsum("bhqk,bkr->bqhr", probs, rows)[..., :rank]
    return _absorbed_output(c, w, o_lat)


# ---------------------------------------------------------------------------
# The cache interface: where a layer's new rows go and which rows its
# queries see.  `update(l, new [B, S, cache_row])` -> rows [B, K, cache_row]
# (what the cache's own `attend` reads).
# ---------------------------------------------------------------------------

class _NoCache:
    """The sequence's own rows are its keys (scoring, tests)."""
    attend = staticmethod(attend_expanded)

    def update(self, l, new):
        return new


class _History:
    """One sequence with a gathered history `hist` [L, S_pad, cache_row]:
    the new rows land at `start` of the layer's history, and are kept
    for the engine to scatter into the pool."""

    def __init__(self, hist, start):
        self.hist, self.start = hist, start
        self.rows: List[jax.Array] = []

    def update(self, l, new):
        self.rows.append(new[0].astype(self.hist.dtype))
        return lax.dynamic_update_slice(
            self.hist[l], self.rows[-1], (self.start, 0))[None].astype(
                new.dtype)

    def attend(self, c, wkv_b, q_nope, q_rope, rows, qpos):
        """`attend_expanded` over the keys this call can see and no
        more: the updated history `rows` [B, S_pad, cache_row] is walked
        in tiles of `HISTORY_TILE` rows up to `start + Q`, one past the
        last query's own key, and everything behind it (stale rows of
        the slot's padded table, which the position mask gives weight
        zero) is never read.  The trip count is data, so a bucket is
        still one program.

        A tile is the expanded form on its rows (keys and values
        rebuilt from the latent, both score products in the compute
        dtype, float32 scores) under an online softmax: the running
        maximum and sum [B, H, Q] and the values' accumulator
        [B, Q, H, v] are float32, and a tile's probabilities go into the
        value product in the compute dtype as the plain form's do.
        Tile 0 holds key 0, which every query sees, so from the first
        tile on the running maximum is a real score: a later tile that
        a query sees nothing of adds exp(-1e30 - m) = 0 to it, and no
        query divides by zero.

        The last tile of a history that is no multiple of the tile (or
        shorter than one) is slid back to end at S_pad, as
        `dynamic_slice` would clamp it: its keys' positions are those
        of the rows actually read, and the rows the tile before it
        scored already are masked."""
        B, S_pad, _ = rows.shape
        Q, H, dt = q_nope.shape[1], c.n_heads, q_nope.dtype
        n, v, rank = c.qk_nope_head_dim, c.v_head_dim, c.kv_lora_rank
        r = c.qk_rope_head_dim
        T = min(HISTORY_TILE, S_pad)
        scale = 1.0 / math.sqrt(c.qk_head_dim)

        def tile(i, carry):
            m, s, acc = carry
            first = jnp.minimum(i * T, S_pad - T)
            t = lax.dynamic_slice_in_dim(rows, first, T, axis=1)
            kv = (t[..., :rank] @ wkv_b).reshape(B, T, H, n + v)
            scores = (jnp.einsum("bqhn,bkhn->bhqk", q_nope, kv[..., :n])
                      + jnp.einsum("bqhr,bkr->bhqk", q_rope,
                                   t[..., rank:rank + r])
                      ).astype(jnp.float32) * scale
            kpos = first + jnp.arange(T)
            seen = (kpos[None, None, None, :] <= qpos[:, None, :, None]) \
                & (kpos >= i * T)
            scores = jnp.where(seen, scores, -1e30)
            m_new = jnp.maximum(m, scores.max(-1))
            p = jnp.exp(scores - m_new[..., None])
            keep = jnp.exp(m - m_new)
            out = jnp.einsum("bhqk,bkhv->bqhv", p.astype(dt), kv[..., n:],
                             preferred_element_type=jnp.float32)
            return (m_new, s * keep + p.sum(-1),
                    acc * keep.swapaxes(1, 2)[..., None] + out)

        m, s, acc = lax.fori_loop(
            0, (self.start + Q + T - 1) // T, tile,
            (jnp.full((B, H, Q), -1e30, jnp.float32),
             jnp.zeros((B, H, Q), jnp.float32),
             jnp.zeros((B, Q, H, v), jnp.float32)))
        out = acc / s.swapaxes(1, 2)[..., None]
        return out.astype(dt).reshape(B, Q, H * v)


class _PagedDecode:
    """One new row a sequence at `positions` [B]: written into the pool
    [L, NB, bs, cache_row] at its block-table position (a physical
    block out of bounds, so dropped, for a dead slot), then attended in
    the absorbed form by one of two paths, chosen by backend and shape
    alone (`ops.paged_attention.engages`, as `models/llama.py::_Paged`):

    - the kernel: `paged_latent_attention` reads the live blocks of the
      live sequences out of the whole pool through the block table, at
      (l, table[b, j]); nothing is gathered.  Its scalars (`plan`) are
      made here, once a program;
    - the gather (everywhere else, and the reference the kernel is
      tested against): every sequence's padded view `pool[l, tables]`,
      read by `attend_absorbed` under the position mask.

    Keeps the updated pool."""

    def __init__(self, pool, tables, positions, active):
        NB, bs = pool.shape[1:3]
        phys = tables[jnp.arange(positions.shape[0]), positions // bs]
        if active is not None:
            phys = jnp.where(active, phys, NB)
        self.pool, self.tables = pool, tables
        self.phys, self.off = phys, positions % bs
        self.plan = None
        if paged.engages(pool):
            with jax.named_scope("attn"), jax.named_scope("paged"):
                self.plan = paged.plan(tables, positions, active, bs)

    def update(self, l, new):
        """Writes the rows; returns what `attend` reads: the gathered
        rows, or the layer whose blocks the kernel walks."""
        B, nb = self.tables.shape
        pool = self.pool
        with jax.named_scope("kv_write"):
            pool = pool.at[l, self.phys, self.off].set(
                new[:, 0].astype(pool.dtype))
        self.pool = pool
        if self.plan is not None:
            return l
        with jax.named_scope("kv_gather"):
            return pool[l, self.tables].reshape(
                B, nb * pool.shape[2], pool.shape[3]).astype(new.dtype)

    def attend(self, c, wkv_b, q_nope, q_rope, rows, qpos):
        if self.plan is None:
            return attend_absorbed(c, wkv_b, q_nope, q_rope, rows, qpos)
        w = wkv_b.reshape(c.kv_lora_rank, c.n_heads, -1)
        q_row = _absorbed_query(c, w, q_nope, q_rope, self.pool.shape[-1])
        with jax.named_scope("paged"):
            o_lat = paged.paged_latent_attention(
                q_row[:, 0], self.pool, rows, self.plan,
                scale=1.0 / math.sqrt(c.qk_head_dim), rank=c.kv_lora_rank)
        return _absorbed_output(c, w, o_lat[:, None])


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def _swiglu(h, w_gate, w_up, w_down, dt):
    return (jax.nn.silu(h @ w_gate.astype(dt)) * (h @ w_up.astype(dt))) \
        @ w_down.astype(dt)


def _low_rank(c: LatentMoEConfig, z, norm):
    """A low-rank path after its norm: z [..., rank], x sqrt(dim / rank)
    where the config scales them.  The constant is applied where the
    published block applies it and folded into nothing: the cached
    latent is the scaled one (the shared rotary key beside it is not
    scaled), so `wkv_b` and the absorbed forms read it as it lies."""
    z = rms_norm(z, norm, c.norm_eps)
    return z * math.sqrt(c.dim / z.shape[-1]) if c.scale_lora else z


def latent_attention(c: LatentMoEConfig, l: int, p, x, qpos, cos, sin,
                     cache):
    """The attention half of a layer: x [B, S, D] at absolute positions
    qpos [B, S] -> x + attention, the layer's rows going through `cache`
    at index `l`.  cos/sin [B, S, r/2], or None for a model that applies
    no rotary (the r "rope" channels of query and key are then plain
    channels, as they lie)."""
    B, S, D = x.shape
    dt, H = c.dtype, c.n_heads
    n, r, rank = c.qk_nope_head_dim, c.qk_rope_head_dim, c.kv_lora_rank
    with jax.named_scope("attn"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        if c.q_lora_rank is None:
            q = h @ p["wq"].astype(dt)
        else:
            q = _low_rank(c, h @ p["wq_a"].astype(dt), p["q_norm"]) \
                @ p["wq_b"].astype(dt)
        q = q.reshape(B, S, H, n + r)
        ckr = h @ p["wkv_a"].astype(dt)
        if cos is None:
            q_rope, k_rope = q[..., n:], ckr[..., rank:]
        else:
            q_rope = _rope_interleaved(q[..., n:], cos[:, :, None],
                                       sin[:, :, None]).astype(dt)
            k_rope = _rope_interleaved(ckr[..., rank:], cos, sin).astype(dt)
        new = jnp.concatenate(
            [_low_rank(c, ckr[..., :rank], p["kv_norm"]), k_rope,
             jnp.zeros((B, S, c.cache_row - rank - r), dt)], -1)
    rows = cache.update(l, new)
    with jax.named_scope("attn"):
        attn = cache.attend(c, p["wkv_b"].astype(dt), q[..., :n], q_rope,
                            rows, qpos)
        return x + attn @ p["wo"].astype(dt)


def feed_forward(c: LatentMoEConfig, p, x, live=None, share=None):
    """The feed-forward half of a layer: a dense SwiGLU, or the routed
    experts (of which this chip holds `share`, models/moe.py) plus the
    shared ones.  Returns (x, tokens routed to each held expert or
    None)."""
    B, S, D = x.shape
    dt = c.dtype
    if "router" not in p:
        with jax.named_scope("mlp"):
            h = rms_norm(x, p["ffn_norm"], c.norm_eps)
            return x + _swiglu(h, p["w_gate"], p["w_up"], p["w_down"],
                               dt), None
    with jax.named_scope("moe"):
        h = rms_norm(x, p["ffn_norm"], c.norm_eps)
        y, sizes = dropless_moe(
            h.reshape(B * S, D), p,
            sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor),
            live=None if live is None else live.reshape(B * S),
            share=share)
        with jax.named_scope("shared"):
            y = y.reshape(B, S, D) + _swiglu(
                h, p["ws_gate"], p["ws_up"], p["ws_down"], dt)
        return x + y, sizes


def _layer(c: LatentMoEConfig, l: int, p, x, qpos, cos, sin, cache,
           live=None):
    """x [B, S, D] at absolute positions qpos [B, S]; cos/sin
    [B, S, r/2].  Returns (x, tokens routed to each expert or None)."""
    x = latent_attention(c, l, p, x, qpos, cos, sin, cache)
    return feed_forward(c, p, x, live)


def rotary(c: LatentMoEConfig, qpos):
    """cos, sin [B, S, r/2] of the rope channels at positions qpos."""
    r = c.qk_rope_head_dim
    inv = 1.0 / (c.rope_theta ** (jnp.arange(0, r, 2, dtype=jnp.float32) / r))
    freqs = qpos.astype(jnp.float32)[..., None] * inv
    return jnp.cos(freqs), jnp.sin(freqs)


def _stack(c: LatentMoEConfig, params, tokens, qpos, cache, live=None):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> (normed hidden [B, S, D], routed tokens [n_moe_layers, E])."""
    cos, sin = rotary(c, qpos)
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    routed = []
    for l, p in enumerate(params["layers"]):
        x, sizes = _layer(c, l, p, x, qpos, cos, sin, cache, live)
        if sizes is not None:
            routed.append(sizes)
    return rms_norm(x, params["norm_f"], c.norm_eps), jnp.stack(routed)


def _head(c, params, x):
    with jax.named_scope("lm_head"):
        return lax.dot_general(
            x, lm_head_weight(params, c),
            (((x.ndim - 1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: LatentMoEConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _ = _stack(config, params, tokens, qpos, _NoCache())
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The paged cache: one row of latent ‖ rotary key a token a layer
# ---------------------------------------------------------------------------

def init_paged_pool(config: LatentMoEConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    c = config
    return {"latent": jnp.zeros(
        (c.n_layers, num_blocks, block_size, c.cache_row), c.dtype)}


def prefill_paged(params, tokens, start, hist, config: LatentMoEConfig,
                  n_real):
    """Suffix prefill of ONE sequence with history (models/serving.py):
    tokens [1, Pb] at start..start+Pb-1, of which the first `n_real`
    are real (padding goes through no expert)."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = _History(hist["latent"], start)
    x, _ = _stack(config, params, tokens, qpos, cache,
                  live=(jnp.arange(Pb) < n_real)[None])
    return x, {"latent": jnp.stack(cache.rows)}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: LatentMoEConfig,
                      active: Optional[jax.Array] = None):
    """One token a sequence against the paged pool (models/serving.py):
    tokens [B] at positions [B], tables [B, max_blocks].  A dead slot
    writes out of bounds (dropped) and goes through no expert.  Returns
    (logits [B, V], pools, counts): tokens routed to each expert of each
    expert layer, the distinct experts touched summed over those layers,
    and 1 for the tick."""
    cache = _PagedDecode(pools["latent"], tables, positions, active)
    x, routed = _stack(config, params, tokens[:, None], positions[:, None],
                       cache, live=None if active is None
                       else active[:, None])
    counts = {"expert_tokens": routed,
              "experts_touched": jnp.sum(routed > 0, dtype=jnp.int32),
              "ticks": jnp.ones((), jnp.int32)}
    return _head(config, params, x[:, 0]), {"latent": cache.pool}, counts


def init_counts(config: LatentMoEConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts."""
    return {"expert_tokens": jnp.zeros(
                (config.n_moe_layers, config.n_experts), jnp.int32),
            "experts_touched": jnp.zeros((), jnp.int32),
            "ticks": jnp.zeros((), jnp.int32)}


def serving_paged_attention(pools) -> str:
    return "kernel" if paged.engages(pools["latent"]) else "gather"


_SERVING = ServingFns(
    name="latent attention + dropless experts (models/latent_moe.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=lm_head_weight, init_counts=init_counts,
    paged_attention=serving_paged_attention,
    grouped_matmul=serving_grouped_path)
