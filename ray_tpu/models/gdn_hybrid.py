"""Hybrid decoder: gated delta-rule layers and full multi-head attention
layers in one stack, post-norm blocks, dense SwiGLU (`model_type`
`olmo_hybrid`).

- **The block, both kinds**, is the family's reordered norm
  (`_block_half`): `a = x + RMSNorm(Mixer(x))`, `y = a + RMSNorm(FF(a))`.
  The norm is on the sub-layer's OUTPUT; the mixer and the feed-forward
  read the raw stream.  After the last block one RMSNorm, then an untied
  head.
- **A gated delta-rule layer** (`gdn_mixer`): q, k (heads of
  `gdn_key_dim`) and v (heads of `gdn_value_dim`) each through a causal
  depthwise convolution (`ops/short_conv.py`) and SiLU; q and k
  L2-normalised a head, q scaled; ONE log-decay a head
  `g = -exp(A_log) softplus(x Wa + dt_bias)` and a write strength
  `beta = 2 sigmoid(x Wb)` in (0, 2); the recurrence is `ops/kda.py`'s
  with `g` of width 1; the output is RMS-normalised a head, gated by
  `silu(x Wg)` and projected.  What it keeps for a sequence is of a
  fixed size whatever the length, `S` [heads, dk, dv] in `state_dtype`
  and the last `conv_size - 1` pre-activation rows of q ‖ k ‖ v, held by
  the engine by SLOT (`init_slot_state`; models/serving.py) exactly as
  `models/kimi_linear.py`'s (its `_Sequences` and `_Step` carry both
  models' states).  `S` lies in the stack as `ops.kda.pack` lays it:
  `heads_a_row` heads side by side in the lanes, so that keys of 96
  against values of 192 store no padding (2 heads a row of 384 lanes).
- **A full-attention layer** (`attention`): as many K/V heads as the
  config says (as many as query heads in the published model), q and k
  RMS-normalised over their WHOLE width before the split into heads,
  rotate-half rotary only where `rope_theta` is a number (the published
  config has none: the recurrent layers carry order), causal softmax.
  Its rows go to two paged pools `k`, `v` `[attention layers, NB, bs,
  kvH, head_dim]` through `models/llama.py`'s caches (`_Paged`: the
  Pallas kernel of `ops/paged_attention.py` where it engages, the
  block-table gather elsewhere; the new rows written as the pool lies,
  `write_rows`).
- **Which layer is which** comes from the config (`attn_layers`, indices
  from 0; every other layer is a delta rule), as do all sizes.
- One definition of a layer over three situations: no cache (`forward`),
  one sequence's call of a bucketed / chunked prefill (`prefill_paged`),
  one token a slot (`decode_step_paged`).  The layer loop is unrolled
  over a LIST of per-layer dicts, as in `models/kimi_linear.py`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import kimi_linear as KL
from ray_tpu.models import latent_moe as LM
from ray_tpu.models import llama
from ray_tpu.models.llama import apply_rope, embed_lookup, rms_norm
from ray_tpu.models.serving import ServingFns
from ray_tpu.ops import kda


@dataclasses.dataclass(frozen=True)
class GdnHybridConfig:
    vocab_size: int = 100352
    dim: int = 3840
    n_layers: int = 32
    # layers (from 0) whose mixer is full attention; the others are
    # gated delta rules
    attn_layers: Tuple[int, ...] = (3, 7, 11, 15, 19, 23, 27, 31)
    n_heads: int = 30
    n_kv_heads: int = 30
    head_dim: int = 128
    gdn_heads: int = 30
    gdn_key_dim: int = 96
    gdn_value_dim: int = 192
    conv_size: int = 4
    hidden_dim: int = 11008
    max_seq_len: int = 65536
    rope_theta: Optional[float] = None      # None: no rotation
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16   # activation/matmul dtype, and the tail's
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the recurrent state between tokens

    @property
    def n_attn_layers(self) -> int:
        return sum(l < self.n_layers for l in self.attn_layers)

    @property
    def n_gdn_layers(self) -> int:
        return self.n_layers - self.n_attn_layers

    @property
    def key_width(self) -> int:
        return self.gdn_heads * self.gdn_key_dim

    @property
    def value_width(self) -> int:
        return self.gdn_heads * self.gdn_value_dim

    @property
    def heads_a_row(self) -> int:
        return kda.heads_a_row(self.gdn_heads, self.gdn_value_dim)

    @staticmethod
    def tiny(**overrides) -> "GdnHybridConfig":
        """Test-size config: a period and a half (delta delta delta
        attention delta delta), keys half as wide as values, two heads
        a row of the state's stack."""
        return GdnHybridConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=6, attn_layers=(3,),
            n_heads=4, n_kv_heads=4, head_dim=16, gdn_heads=2,
            gdn_key_dim=32, gdn_value_dim=64, hidden_dim=128,
            max_seq_len=128), **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: GdnHybridConfig, key: jax.Array) -> Dict[str, Any]:
    """normal(0, 0.02) matrices and taps, unit norms, decays by
    `kimi_linear.draw_decay` (one `dt_bias` a head)."""
    c = config
    dt = c.param_dtype
    D, H, Wk, Wv = c.dim, c.gdn_heads, c.key_width, c.value_width
    A = c.n_heads * c.head_dim
    Akv = c.n_kv_heads * c.head_dim
    k_embed, k_out, k_layers = jax.random.split(key, 3)

    def draw(key, *shape):
        return jax.nn.initializers.normal(0.02)(key, shape, dt)

    layers: List[Dict[str, jax.Array]] = []
    for i, lk in enumerate(jax.random.split(k_layers, c.n_layers)):
        ks = jax.random.split(lk, 16)
        p = {"attn_norm": jnp.ones((D,), dt), "ffn_norm": jnp.ones((D,), dt),
             "w_gate": draw(ks[0], D, c.hidden_dim),
             "w_up": draw(ks[1], D, c.hidden_dim),
             "w_down": draw(ks[2], c.hidden_dim, D)}
        if i in c.attn_layers:
            p.update(wq=draw(ks[3], D, A), wk=draw(ks[4], D, Akv),
                     wv=draw(ks[5], D, Akv),
                     q_norm=jnp.ones((A,), dt), k_norm=jnp.ones((Akv,), dt),
                     wo=draw(ks[6], A, D))
        else:
            a_log, dt_bias = KL.draw_decay(ks[3], H, H)
            p.update(
                wq=draw(ks[4], D, Wk), wk=draw(ks[5], D, Wk),
                wv=draw(ks[6], D, Wv),
                conv_q=draw(ks[7], c.conv_size, Wk),
                conv_k=draw(ks[8], c.conv_size, Wk),
                conv_v=draw(ks[9], c.conv_size, Wv),
                A_log=a_log, dt_bias=dt_bias,
                wa=draw(ks[10], D, H), wb=draw(ks[11], D, H),
                wg=draw(ks[12], D, Wv),
                o_norm=jnp.ones((c.gdn_value_dim,), dt),
                wo=draw(ks[13], Wv, D))
        layers.append(p)
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt),
            "lm_head": draw(k_out, D, c.vocab_size)}


def init_slot_state(config: GdnHybridConfig, num_slots: int
                    ) -> Dict[str, jax.Array]:
    """A row a slot a delta-rule layer (models/serving.py): the state,
    `ops.kda.pack`ed (`heads_a_row` heads side by side: at 30 heads of
    96 x 192, `[15, 96, 384]`, 2,211,840 B a slot a layer in float32
    with no padded lane), and the convolution's tail, its
    `conv_size - 1` rows of q ‖ k ‖ v side by side in the lanes of one
    (`ops/short_conv.py`), zeros."""
    c = config
    p = c.heads_a_row
    return {
        "S": jnp.zeros((c.n_gdn_layers, num_slots, c.gdn_heads // p,
                        c.gdn_key_dim, p * c.gdn_value_dim), c.state_dtype),
        "conv": jnp.zeros((c.n_gdn_layers, num_slots, (c.conv_size - 1)
                           * (2 * c.key_width + c.value_width)), c.dtype)}


class _Layers:
    """`models/llama.py`'s caches (`_NoCache`, `_History`, `_Paged`),
    made for a scan over stacked layers, driven by an unrolled loop: a
    layer gets its own slice of the cache's leaves, and this keeps what
    the cache carries (`stacks`) and what each attention layer emitted
    (`rows`)."""

    def __init__(self, cache):
        self.cache, self.stacks = cache, cache.stacks
        self.rows: List[Any] = []

    def attend(self, c, l, q, k, v):
        o, self.stacks, rows = self.cache.attend(
            c, q, k, v, self.stacks,
            jax.tree.map(lambda a: a[l], self.cache.leaves))
        self.rows.append(rows)
        return o


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def _block_half(x, sub_layer, w, eps):
    """The reordered norm: the sub-layer reads the raw stream and its
    OUTPUT is normalised before it is added."""
    return x + rms_norm(sub_layer(x), w, eps)


def _write_strength(x):
    """beta in (0, 2): 1 - beta in (-1, 1) (`linear_allow_neg_eigval`)."""
    return 2.0 * jax.nn.sigmoid(x)


def _gated_norm(o, w, gate, eps):
    """A head's output RMS-normalised, then gated: float32."""
    return rms_norm(o, w, eps).astype(jnp.float32) * gate


def _qk_norm(x, w, eps):
    """RMSNorm over the WHOLE width of q (or k), all heads together."""
    return rms_norm(x, w, eps)


def gdn_mixer(c: GdnHybridConfig, j: int, p, x, rec):
    """x [B, S, D], the raw stream -> the gated delta rule's output
    [B, S, D], the layer's state going through `rec` at delta-rule
    layer index j."""
    B, S, D = x.shape
    dt, H, dk, dv = c.dtype, c.gdn_heads, c.gdn_key_dim, c.gdn_value_dim
    Wk = c.key_width
    f32 = jnp.float32
    with jax.named_scope("proj"):
        qkv = jnp.concatenate([x @ p[n].astype(dt)
                               for n in ("wq", "wk", "wv")], -1)
    with jax.named_scope("conv"):
        w = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], -1)
        qkv = jax.nn.silu(rec.conv(j, qkv, w))
        q = KL._l2norm(qkv[..., :Wk].reshape(B, S, H, dk)) * dk ** -0.5
        k = KL._l2norm(qkv[..., Wk:2 * Wk].reshape(B, S, H, dk))
        v = qkv[..., 2 * Wk:].reshape(B, S, H, dv)
    with jax.named_scope("gate"):
        g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(
            (x @ p["wa"].astype(dt)).astype(f32) + p["dt_bias"].astype(f32))
        beta = _write_strength((x @ p["wb"].astype(dt)).astype(f32))
        gate = jax.nn.silu((x @ p["wg"].astype(dt)).astype(f32)
                           ).reshape(B, S, H, dv)
    with jax.named_scope("state"):
        o = rec.recur(j, q, k, v, g[..., None], beta)       # float32
    with jax.named_scope("out"):
        o = _gated_norm(o, p["o_norm"], gate, c.norm_eps).astype(dt)
        return o.reshape(B, S, H * dv) @ p["wo"].astype(dt)


def attention(c: GdnHybridConfig, l: int, p, x, rope, cache):
    """x [B, S, D], the raw stream -> attention's output [B, S, D], the
    layer's rows going through `cache` at attention-layer index l;
    `rope` (cos, sin) [B, S, hd/2] or None."""
    B, S, _ = x.shape
    dt, hd = c.dtype, c.head_dim
    with jax.named_scope("attn"):
        q, k = x @ p["wq"].astype(dt), x @ p["wk"].astype(dt)
        with jax.named_scope("qk_norm"):
            q = _qk_norm(q, p["q_norm"], c.norm_eps)
            k = _qk_norm(k, p["k_norm"], c.norm_eps)
        q = q.reshape(B, S, c.n_heads, hd)
        k = k.reshape(B, S, c.n_kv_heads, hd)
        v = (x @ p["wv"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        if rope is not None:
            q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    o = cache.attend(c, l, q, k, v)
    with jax.named_scope("attn"):
        return o.reshape(B, S, c.n_heads * hd) @ p["wo"].astype(dt)


def _stack(c: GdnHybridConfig, params, tokens, qpos, cache, rec):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> normed hidden [B, S, D]."""
    rope = None
    if c.rope_theta is not None:
        hd = c.head_dim
        inv = 1.0 / (c.rope_theta ** (
            jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
        freqs = qpos.astype(jnp.float32)[..., None] * inv   # [B, S, hd/2]
        rope = (jnp.cos(freqs), jnp.sin(freqs))
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    jg = ja = 0
    for p in params["layers"]:
        if "A_log" in p:
            with jax.named_scope("gdn"):
                x = _block_half(
                    x, lambda h, p=p, jg=jg: gdn_mixer(c, jg, p, h, rec),
                    p["attn_norm"], c.norm_eps)
            jg += 1
        else:
            x = _block_half(
                x, lambda h, p=p, ja=ja: attention(c, ja, p, h, rope, cache),
                p["attn_norm"], c.norm_eps)
            ja += 1
        with jax.named_scope("mlp"):
            x = _block_half(x, lambda h, p=p: LM._swiglu(
                h, p["w_gate"], p["w_up"], p["w_down"], c.dtype),
                p["ffn_norm"], c.norm_eps)
    return rms_norm(x, params["norm_f"], c.norm_eps)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: GdnHybridConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache, every
    sequence from a zero state."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    rec = KL._Sequences(init_slot_state(config, B), S)
    x = _stack(config, params, tokens, qpos,
               _Layers(llama._NoCache(llama.xla_attention)), rec)
    return LM._head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def init_paged_pool(config: GdnHybridConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    """A row of K and a row of V a token a KV head, for the attention
    layers."""
    c = config
    shape = (c.n_attn_layers, num_blocks, block_size, c.n_kv_heads,
             c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def prefill_paged(params, tokens, start, hist, config: GdnHybridConfig,
                  n_real, state):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `state` {leaf: [Lg, ...]} the slot's recurrent
    rows after its first `start` tokens.  Padding advances no state (its
    K/V rows are masked as keys, not skipped)."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = _Layers(llama._History(hist["k"], hist["v"], start, qpos[0]))
    rec = KL._Sequences({k: v[:, None] for k, v in state.items()}, n_real)
    x = _stack(config, params, tokens, qpos, cache, rec)
    rows = {name: jnp.stack([r[i][0] for r in cache.rows]).astype(
        config.dtype) for i, name in enumerate(("k", "v"))}
    return x, rows, {k: v[:, 0] for k, v in rec.state().items()}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: GdnHybridConfig,
                      active: Optional[jax.Array] = None, state=None):
    """One token a slot against the paged pools and the slots' recurrent
    states: tokens [B] at positions [B].  A dead slot writes no row and
    keeps its state.  Returns (logits [B, V], pools, counts, state)."""
    c = config
    B = tokens.shape[0]
    cache = _Layers(llama._Paged(pools, tables, positions, active))
    rec = KL._Step(state, active)
    x = _stack(c, params, tokens[:, None], positions[:, None], cache, rec)
    n_live = jnp.asarray(B, jnp.int32) if active is None \
        else jnp.sum(active, dtype=jnp.int32)
    counts = {"ticks": jnp.ones((), jnp.int32), "live_slots": n_live,
              # slot-layers the Pallas step advanced (0: `kda_step` ran)
              "gdn_rows_stepped": n_live * (
                  c.n_gdn_layers if rec.plan is not None else 0)}
    k_pool, v_pool = cache.stacks
    return LM._head(c, params, x[:, 0]), {"k": k_pool, "v": v_pool}, \
        counts, rec.state()


def init_counts(config: GdnHybridConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts: ticks, live slots
    summed over ticks, and the slot-layers whose state the Pallas step
    advanced (`live_slots` x delta-rule layers where it engages, 0
    where `kda_step` ran)."""
    z = jnp.zeros((), jnp.int32)
    return {"ticks": z, "live_slots": z, "gdn_rows_stepped": z}


_SERVING = ServingFns(
    name="gated delta rule + full multi-head attention, post-norm "
         "(models/gdn_hybrid.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=LM.lm_head_weight, init_counts=init_counts,
    init_slot_state=init_slot_state,
    paged_attention=llama._serve_paged_attention)
