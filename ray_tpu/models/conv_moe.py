"""Gated short-convolution / grouped-query-attention decoder with a
whole bank of routed experts (`model_type` `lfm2_moe`).

A block is `h = x + Op(RMSNorm(x))`, `y = h + FF(RMSNorm(h))`; after the
last block one more RMSNorm, then a head TIED to the embedding table.

- **Which operator a layer has** comes from the config (`attn_layers`,
  indices from 0; every other layer is a convolution), as do all sizes.
  *Convolution*: `[B, C, X] = split3(u W_in)`, `z = B * X`, a causal
  depthwise convolution of `conv_size` taps over `z`
  (`ops/short_conv.py`, no activation), `o = (C * conv(z)) W_out`.
  *Attention*: GQA with heads of `head_dim`, q and k RMS-normalised a
  head BEFORE the rotate-half rotary (`models/llama.py::apply_rope`),
  causal softmax.
- **Two kinds of state.**  An attention layer keeps one row a token a
  KV head in the paged pool, that head's K ‖ V: heads of 64 make a
  128-lane row with no padding, and `ops/paged_attention.py` reads it
  with one copy a block (`v_pool=None`).  The pool's `L` counts the
  ATTENTION layers only.  A convolution layer keeps, for each sequence,
  its last `conv_size - 1` rows of `z` whatever the length: the engine
  holds that by SLOT (`init_slot_state`; models/serving.py), zeros at
  admission, advanced over the real tokens of a prefill call and handed
  to the next chunk of the same prompt, shifted in place in the tick.
- **Feed-forward**: `n_dense_layers` leading SwiGLU layers, then
  `models/moe.py::dropless_moe` over ALL `n_experts` (no share, no
  shared expert) under the sigmoid-with-bias routing rule, the chosen
  scores renormalised with the published `+ 1e-6`.
- One definition of a layer over three situations: no cache
  (`forward`), one sequence's call of a bucketed / chunked prefill
  (`prefill_paged`), one token a slot (`decode_step_paged`).  The layer
  loop is unrolled over a LIST of per-layer dicts, as in
  `models/latent_moe.py`: a layer's experts are buffers of their own.

Every size comes from `ConvMoEConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.latent_moe import _swiglu
from ray_tpu.models.llama import (_decode_attention, _repeat_kv, apply_rope,
                                  embed_lookup, rms_norm, xla_attention)
from ray_tpu.models.moe import (
    dropless_moe, serving_grouped_path, sigmoid_bias_top_k,
)
from ray_tpu.models.serving import ServingFns
from ray_tpu.ops import paged_attention as paged
from ray_tpu.ops import short_conv

ROUTE_EPS = 1e-6    # in the renormalisation of the chosen scores


@dataclasses.dataclass(frozen=True)
class ConvMoEConfig:
    vocab_size: int = 65536
    dim: int = 2048
    n_layers: int = 24
    # layers (from 0) whose operator is attention; the others convolve
    attn_layers: Tuple[int, ...] = (2, 6, 10, 14, 18, 21)
    n_dense_layers: int = 2
    n_heads: int = 32
    n_kv_heads: int = 8
    head_dim: int = 64
    conv_size: int = 3
    dense_hidden_dim: int = 7168
    expert_hidden_dim: int = 1792
    n_experts: int = 32
    top_k: int = 4
    routed_scaling_factor: float = 1.0
    max_seq_len: int = 128000
    rope_theta: float = 1e6
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16   # activation/matmul dtype, and the tail's
    param_dtype: Any = jnp.bfloat16

    @property
    def n_attn_layers(self) -> int:
        return sum(l < self.n_layers for l in self.attn_layers)

    @property
    def n_conv_layers(self) -> int:
        return self.n_layers - self.n_attn_layers

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers

    @staticmethod
    def tiny(**overrides) -> "ConvMoEConfig":
        """Test-size config: two dense conv layers, then a period and a
        half (attention conv conv conv attention conv)."""
        return ConvMoEConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=8, attn_layers=(2, 6),
            n_dense_layers=2, n_heads=4, n_kv_heads=2, head_dim=16,
            dense_hidden_dim=128, expert_hidden_dim=32, n_experts=8,
            top_k=2, max_seq_len=128), **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: ConvMoEConfig, key: jax.Array,
                bias_scale: float = 0.02) -> Dict[str, Any]:
    """normal(0, 0.02) matrices and taps, unit norms, a selection bias
    drawn at `bias_scale`; no head (tied)."""
    c = config
    dt = c.param_dtype
    D, hd = c.dim, c.head_dim

    def draw(key, *shape):
        return jax.nn.initializers.normal(0.02)(key, shape, dt)

    k_embed, k_layers = jax.random.split(key)
    layers: List[Dict[str, jax.Array]] = []
    for i, lk in enumerate(jax.random.split(k_layers, c.n_layers)):
        ks = jax.random.split(lk, 10)
        p = {"op_norm": jnp.ones((D,), dt), "ffn_norm": jnp.ones((D,), dt)}
        if i in c.attn_layers:
            p.update(wq=draw(ks[0], D, c.n_heads * hd),
                     wk=draw(ks[1], D, c.n_kv_heads * hd),
                     wv=draw(ks[2], D, c.n_kv_heads * hd),
                     q_norm=jnp.ones((hd,), dt), k_norm=jnp.ones((hd,), dt),
                     wo=draw(ks[3], c.n_heads * hd, D))
        else:
            p.update(w_in=draw(ks[0], D, 3 * D),
                     conv=draw(ks[1], c.conv_size, D),
                     w_out=draw(ks[2], D, D))
        if i < c.n_dense_layers:
            F = c.dense_hidden_dim
            p.update(w_gate=draw(ks[4], D, F), w_up=draw(ks[5], D, F),
                     w_down=draw(ks[6], F, D))
        else:
            E, F = c.n_experts, c.expert_hidden_dim
            p.update(
                router=draw(ks[4], D, E),
                router_bias=jax.random.normal(ks[5], (E,), jnp.float32)
                * bias_scale,
                w_gate=draw(ks[6], E, D, F), w_up=draw(ks[7], E, D, F),
                w_down=draw(ks[8], E, F, D))
        layers.append(p)
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt)}


def lm_head_weight(params: Dict[str, Any], config: ConvMoEConfig):
    """[D, V]: the embedding table turned round inside the program that
    multiplies by it (a dot over the table's minor axis; no transposed
    copy is held)."""
    return params["embed"].T.astype(config.dtype)


# ---------------------------------------------------------------------------
# The convolution layers' tails: where they come from and where they go.
# `conv(j, z, w)` for convolution layer j.
# ---------------------------------------------------------------------------

def init_slot_state(config: ConvMoEConfig, num_slots: int
                    ) -> Dict[str, jax.Array]:
    """A row a slot a convolution layer (models/serving.py): the last
    `conv_size - 1` rows of `z` side by side in the lanes of one
    (`ops/short_conv.py`), zeros."""
    c = config
    return {"tail": jnp.zeros((c.n_conv_layers, num_slots,
                               (c.conv_size - 1) * c.dim), c.dtype)}


class _Sequences:
    """Whole (padded) sequences, each from the tail handed in
    [Lc, B, (K-1) D]; the tails after the first `n_real` tokens are kept
    for the caller."""

    def __init__(self, state, n_real):
        self.inp, self.n_real = state["tail"], n_real
        self.tails: List[jax.Array] = []

    def conv(self, j, z, w):
        y, tail = short_conv.short_conv(
            z, w, short_conv.rows(self.inp[j], w), self.n_real)
        self.tails.append(short_conv.flat(tail).astype(self.inp.dtype))
        return y

    def state(self):
        return {"tail": jnp.stack(self.tails)}


class _Step:
    """One token a slot: each layer's rows of the whole tree shifted at
    a static layer index where they lie (`short_conv.step_in_place`);
    a dead slot keeps its."""

    def __init__(self, state, active):
        self.tails, self.active = state["tail"], active

    def conv(self, j, z, w):
        y, self.tails = short_conv.step_in_place(
            self.tails, j, z[:, 0], w, self.active)
        return y[:, None]

    def state(self):
        return {"tail": self.tails}


# ---------------------------------------------------------------------------
# The attention layers' cache: where a layer's new K ‖ V rows go and
# which rows its queries see.  `attend(c, l, q, k, v)` for attention
# layer l: q [B, S, H, hd], k and v [B, S, kvH, hd] -> [B, S, H, hd].
# ---------------------------------------------------------------------------

class _NoCache:
    """The sequence's own rows are its keys (scoring, tests)."""

    def attend(self, c, l, q, k, v):
        rep = c.n_heads // c.n_kv_heads
        return xla_attention(q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                             causal=True)


class _History:
    """ONE sequence with its gathered history [La, S_pad, kvH, 2 hd]:
    the new rows land at `start` of the layer's history, the queries at
    `qpos` see keys at positions <= their own, and the rows are kept
    for the engine to scatter into the pool."""

    def __init__(self, hist, start, qpos):
        self.hist, self.start, self.qpos = hist, start, qpos
        self.rows: List[jax.Array] = []

    def attend(self, c, l, q, k, v):
        self.rows.append(
            jnp.concatenate([k[0], v[0]], -1).astype(self.hist.dtype))
        kv = lax.dynamic_update_slice(
            self.hist[l], self.rows[-1], (self.start, 0, 0))[None].astype(
                c.dtype)
        rep = c.n_heads // c.n_kv_heads
        return xla_attention(
            q, _repeat_kv(kv[..., :c.head_dim], rep),
            _repeat_kv(kv[..., c.head_dim:], rep), causal=True,
            positions=self.qpos)


class _Paged:
    """One new row a sequence at positions `qpos` [B], written into the
    pool [La, NB, bs, kvH, 2 hd] at its block-table position (a physical
    block out of bounds, so dropped, for a dead slot); then attended by
    one of two paths, chosen by backend and shape alone
    (`ops.paged_attention.engages`) as in `models/llama.py::_Paged`: the
    kernel reads the live blocks through the table where they lie, the
    gather builds every slot's padded view.  The kernel's scalars are
    planned here, once a program.  Keeps the updated pool."""

    def __init__(self, pool, tables, qpos, active):
        NB, bs = pool.shape[1:3]
        phys = tables[jnp.arange(qpos.shape[0]), qpos // bs]
        if active is not None:
            phys = jnp.where(active, phys, NB)
        self.pool, self.tables, self.qpos = pool, tables, qpos
        self.phys, self.off = phys, qpos % bs
        self.plan = None
        if paged.engages(pool):
            with jax.named_scope("attn"), jax.named_scope("paged"):
                self.plan = paged.plan(tables, qpos, active, bs)

    def attend(self, c, l, q, k, v):
        pool = self.pool
        with jax.named_scope("kv_write"):
            pool = pool.at[l, self.phys, self.off].set(
                jnp.concatenate([k[:, 0], v[:, 0]], -1).astype(pool.dtype))
        self.pool = pool
        if self.plan is not None:
            with jax.named_scope("paged"):
                return paged.paged_attention(q, pool, None, l, self.plan)
        B, nb = self.tables.shape
        with jax.named_scope("kv_gather"):
            kv = pool[l, self.tables].reshape(
                (B, nb * pool.shape[2]) + pool.shape[3:]).astype(c.dtype)
        return _decode_attention(q, kv[..., :c.head_dim],
                                 kv[..., c.head_dim:], self.qpos[:, None])


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def conv_operator(c: ConvMoEConfig, j: int, p, x, rec):
    """x [B, S, D] -> x + the gated convolution, the layer's tail going
    through `rec` at convolution-layer index j."""
    dt = c.dtype
    with jax.named_scope("conv"):
        h = rms_norm(x, p["op_norm"], c.norm_eps)
        with jax.named_scope("in_proj"):
            b, gate, xin = jnp.split(h @ p["w_in"].astype(dt), 3, axis=-1)
        with jax.named_scope("mix"):
            y = gate * rec.conv(j, b * xin, p["conv"])
        with jax.named_scope("out_proj"):
            return x + y @ p["w_out"].astype(dt)


def attention_operator(c: ConvMoEConfig, l: int, p, x, cos, sin, cache):
    """x [B, S, D] -> x + attention, the layer's rows going through
    `cache` at attention-layer index l; cos/sin [B, S, hd/2]."""
    B, S, _ = x.shape
    dt, hd = c.dtype, c.head_dim
    with jax.named_scope("attn"):
        h = rms_norm(x, p["op_norm"], c.norm_eps)
        q = (h @ p["wq"].astype(dt)).reshape(B, S, c.n_heads, hd)
        k = (h @ p["wk"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        v = (h @ p["wv"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, p["q_norm"], c.norm_eps)
            k = rms_norm(k, p["k_norm"], c.norm_eps)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        o = cache.attend(c, l, q, k, v)
        return x + o.reshape(B, S, c.n_heads * hd) @ p["wo"].astype(dt)


def feed_forward(c: ConvMoEConfig, p, x, live=None):
    """The feed-forward half: a dense SwiGLU, or ALL the routed experts.
    Returns (x, tokens routed to each expert or None)."""
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
            sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor, ROUTE_EPS),
            live=None if live is None else live.reshape(B * S))
        return x + y.reshape(B, S, D), sizes


def _stack(c: ConvMoEConfig, params, tokens, qpos, cache, rec, live=None):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> (normed hidden [B, S, D], tokens routed to each expert
    [n_moe_layers, E])."""
    hd = c.head_dim
    inv = 1.0 / (c.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                  / hd))
    freqs = qpos.astype(jnp.float32)[..., None] * inv       # [B, S, hd/2]
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    routed = []
    jc = ja = 0
    for p in params["layers"]:
        if "wq" in p:
            x = attention_operator(c, ja, p, x, cos, sin, cache)
            ja += 1
        else:
            x = conv_operator(c, jc, p, x, rec)
            jc += 1
        x, sizes = feed_forward(c, p, x, live)
        if sizes is not None:
            routed.append(sizes)
    return rms_norm(x, params["norm_f"], c.norm_eps), jnp.stack(routed)


def _head(c: ConvMoEConfig, params, x):
    """Normed hidden [..., D] -> logits [..., V] float32, by the
    embedding table as it lies [V, D]."""
    with jax.named_scope("lm_head"):
        return lax.dot_general(
            x, params["embed"].astype(c.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: ConvMoEConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache, every
    sequence from a zero tail."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    rec = _Sequences(init_slot_state(config, B), S)
    x, _ = _stack(config, params, tokens, qpos, _NoCache(), rec)
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def init_paged_pool(config: ConvMoEConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    """One row of K ‖ V a token a KV head, for the attention layers."""
    c = config
    return {"kv": jnp.zeros((c.n_attn_layers, num_blocks, block_size,
                             c.n_kv_heads, 2 * c.head_dim), c.dtype)}


def prefill_paged(params, tokens, start, hist, config: ConvMoEConfig,
                  n_real, state):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `state` {tail: [Lc, (K-1) D]} the slot's tails
    after its first `start` tokens.  Padding goes through no expert and
    leaves the tails where the last real token put them."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = _History(hist["kv"], start, qpos[0])
    rec = _Sequences({k: v[:, None] for k, v in state.items()}, n_real)
    x, _ = _stack(config, params, tokens, qpos, cache, rec,
                  live=(jnp.arange(Pb) < n_real)[None])
    return x, {"kv": jnp.stack(cache.rows)}, \
        {k: v[:, 0] for k, v in rec.state().items()}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: ConvMoEConfig,
                      active: Optional[jax.Array] = None, state=None):
    """One token a slot against the paged pool and the slots' tails:
    tokens [B] at positions [B].  A dead slot writes no row, goes
    through no expert and keeps its tail.  Returns (logits [B, V],
    pools, counts, state): tokens routed to each expert of each expert
    layer, the distinct experts touched summed over those layers, and 1
    for the tick."""
    cache = _Paged(pools["kv"], tables, positions, active)
    rec = _Step(state, active)
    x, routed = _stack(config, params, tokens[:, None], positions[:, None],
                       cache, rec, live=None if active is None
                       else active[:, None])
    counts = {"expert_tokens": routed,
              "experts_touched": jnp.sum(routed > 0, dtype=jnp.int32),
              "ticks": jnp.ones((), jnp.int32)}
    return _head(config, params, x[:, 0]), {"kv": cache.pool}, counts, \
        rec.state()


def init_counts(config: ConvMoEConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts."""
    return {"expert_tokens": jnp.zeros(
                (config.n_moe_layers, config.n_experts), jnp.int32),
            "experts_touched": jnp.zeros((), jnp.int32),
            "ticks": jnp.zeros((), jnp.int32)}


def _paged_attention(pools) -> str:
    return "kernel" if paged.engages(pools["kv"]) else "gather"


_SERVING = ServingFns(
    name="gated short convolution + GQA, a whole bank of experts "
         "(models/conv_moe.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=lm_head_weight, init_counts=init_counts,
    init_slot_state=init_slot_state, paged_attention=_paged_attention,
    grouped_matmul=serving_grouped_path)
