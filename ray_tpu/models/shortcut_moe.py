"""Shortcut-connected expert decoder: two latent-attention sublayers and
two dense feed-forwards a layer, with ONE expert layer on a shortcut
across them (`model_type` `longcat_flash`).

    x = x + MLA_0(N(x));  h = N(x);  s = MoE(h)      # computed here ...
    x = x + FFN_0(h)
    x = x + MLA_1(N(x));  x = x + FFN_1(N(x)) + s    # ... added here

- **Attention** is `models/latent_moe.py`'s, used as it is (its cache
  classes, `attend_expanded` / `attend_absorbed`, the paged kernel's
  latent form), with the query compressed through a low rank under a
  norm of its own and both low-rank paths scaled
  (`LatentMoEConfig.q_lora_rank`, `.scale_lora`).  A layer keeps TWO
  rows a token, one a sublayer: the pool is `2 * n_layers` latent layers
  deep and sublayer i of layer l reads and writes index `2 l + i`.
- **The expert layer** is `models/moe.py::dropless_moe` under
  `softmax_bias_top_k`: one softmax over `n_experts + n_zero_experts`
  columns, top k of score + bias, weights the scores x the scaling
  factor, not renormalised.  A pick past the routed experts costs
  nothing and adds its weight times the token; of the routed experts
  this chip holds `expert_rank` of `expert_shards`.  Its input is the
  normed stream after the first attention, its output joins the stream
  after the second feed-forward: on one chip nothing runs beside
  anything, the topology is what the reference has to agree with.
- One definition of a layer over three caches, as in `latent_moe.py`.

Every size comes from `ShortcutMoEConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe as LM
from ray_tpu.models.llama import embed_lookup, rms_norm
from ray_tpu.models.moe import (
    dropless_moe, serving_grouped_path, softmax_bias_top_k, walk_counts,
)
from ray_tpu.models.serving import ServingFns


@dataclasses.dataclass(frozen=True)
class ShortcutMoEConfig(LM.LatentMoEConfig):
    vocab_size: int = 131072
    dim: int = 6144
    n_layers: int = 28              # double layers
    n_dense_layers: int = 0         # every layer has both kinds
    n_heads: int = 64
    q_lora_rank: Optional[int] = 1536
    scale_lora: bool = True
    dense_hidden_dim: int = 12288
    expert_hidden_dim: int = 2048
    n_experts: int = 512            # routed, as published
    n_zero_experts: int = 256       # identities: the router's last columns
    top_k: int = 12
    n_shared_experts: int = 0
    routed_scaling_factor: float = 6.0
    rope_theta: float = 1e7
    norm_eps: float = 1e-5
    # this chip holds routed experts [rank E/n, (rank+1) E/n) of a layer
    expert_rank: int = 0
    expert_shards: int = 1

    @property
    def n_held_experts(self) -> int:
        return self.n_experts // self.expert_shards

    @property
    def router_width(self) -> int:
        return self.n_experts + self.n_zero_experts

    @staticmethod
    def tiny(**overrides) -> "ShortcutMoEConfig":
        """Test-size config: runs on the CPU in milliseconds."""
        return ShortcutMoEConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, q_lora_rank=24,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, dense_hidden_dim=128, expert_hidden_dim=32,
            n_experts=8, n_zero_experts=4, top_k=3, max_seq_len=128),
            **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: ShortcutMoEConfig, key: jax.Array,
                bias_scale: float = 1e-3) -> Dict[str, Any]:
    """normal(0, 0.02) matrices, unit norms, a selection bias drawn at
    `bias_scale` over the router's whole width; the HELD experts only."""
    c = config
    dt = c.param_dtype
    H, D, F = c.n_heads, c.dim, c.dense_hidden_dim
    k_embed, k_out, k_layers = jax.random.split(key, 3)

    def draw(key, *shape):
        return jax.nn.initializers.normal(0.02)(key, shape, dt)

    def sublayer(key):
        ks = jax.random.split(key, 8)
        return {
            "attn_norm": jnp.ones((D,), dt),
            "wq_a": draw(ks[0], D, c.q_lora_rank),
            "q_norm": jnp.ones((c.q_lora_rank,), dt),
            "wq_b": draw(ks[1], c.q_lora_rank, H * c.qk_head_dim),
            "wkv_a": draw(ks[2], D, c.kv_lora_rank + c.qk_rope_head_dim),
            "kv_norm": jnp.ones((c.kv_lora_rank,), dt),
            "wkv_b": draw(ks[3], c.kv_lora_rank,
                          H * (c.qk_nope_head_dim + c.v_head_dim)),
            "wo": draw(ks[4], H * c.v_head_dim, D),
            "ffn_norm": jnp.ones((D,), dt),
            "w_gate": draw(ks[5], D, F), "w_up": draw(ks[6], D, F),
            "w_down": draw(ks[7], F, D)}

    layers: List[Dict[str, Any]] = []
    for lk in jax.random.split(k_layers, c.n_layers):
        k0, k1, *ks = jax.random.split(lk, 7)
        R, Eh, Fe = c.router_width, c.n_held_experts, c.expert_hidden_dim
        layers.append({
            "sub": [sublayer(k0), sublayer(k1)],
            "moe": {
                "router": draw(ks[0], D, R),
                "router_bias": jax.random.normal(ks[1], (R,), jnp.float32)
                * bias_scale,
                "w_gate": draw(ks[2], Eh, D, Fe),
                "w_up": draw(ks[3], Eh, D, Fe),
                "w_down": draw(ks[4], Eh, Fe, D)}})
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt),
            "lm_head": draw(k_out, D, c.vocab_size)}


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def shortcut_experts(c: ShortcutMoEConfig, p, h, live=None):
    """h [B, S, D], the normed stream -> (the expert layer's result
    [B, S, D], tokens routed to each held expert and, last, to the
    zero-compute ones [E held + 1])."""
    B, S, D = h.shape
    with jax.named_scope("moe"):
        y, sizes = dropless_moe(
            h.reshape(B * S, D), p,
            softmax_bias_top_k(c.top_k, c.routed_scaling_factor),
            live=None if live is None else live.reshape(B * S),
            share=(c.expert_rank, c.expert_shards),
            n_zero=c.n_zero_experts)
        return y.reshape(B, S, D), sizes


def _layer(c: ShortcutMoEConfig, l: int, p, x, qpos, cos, sin, cache,
           live=None):
    """x [B, S, D] at absolute positions qpos [B, S]; cos/sin
    [B, S, r/2].  Returns (x, the expert layer's counts)."""
    a, b = p["sub"]
    dt = c.dtype
    x = LM.latent_attention(c, 2 * l, a, x, qpos, cos, sin, cache)
    with jax.named_scope("mlp"):
        h = rms_norm(x, a["ffn_norm"], c.norm_eps)
    shortcut, sizes = shortcut_experts(c, p["moe"], h, live)
    with jax.named_scope("mlp"):
        x = x + LM._swiglu(h, a["w_gate"], a["w_up"], a["w_down"], dt)
    x = LM.latent_attention(c, 2 * l + 1, b, x, qpos, cos, sin, cache)
    with jax.named_scope("mlp"):
        h = rms_norm(x, b["ffn_norm"], c.norm_eps)
        return x + LM._swiglu(h, b["w_gate"], b["w_up"], b["w_down"],
                              dt) + shortcut, sizes


def _stack(c: ShortcutMoEConfig, params, tokens, qpos, cache, live=None):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> (normed hidden [B, S, D], counts [n_layers, E held + 1])."""
    cos, sin = LM.rotary(c, qpos)
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    routed = []
    for l, p in enumerate(params["layers"]):
        x, sizes = _layer(c, l, p, x, qpos, cos, sin, cache, live)
        routed.append(sizes)
    return rms_norm(x, params["norm_f"], c.norm_eps), jnp.stack(routed)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: ShortcutMoEConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _ = _stack(config, params, tokens, qpos, LM._NoCache())
    return LM._head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def init_paged_pool(config: ShortcutMoEConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    """One row of latent ‖ rotary key a token a SUBLAYER: sublayer i of
    layer l at index 2 l + i."""
    c = config
    return {"latent": jnp.zeros(
        (2 * c.n_layers, num_blocks, block_size, c.cache_row), c.dtype)}


def prefill_paged(params, tokens, start, hist, config: ShortcutMoEConfig,
                  n_real):
    """Suffix prefill of ONE sequence with history (models/serving.py):
    tokens [1, Pb] at start.., the first `n_real` real (padding goes
    through no expert and counts for no zero pick)."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = LM._History(hist["latent"], start)
    x, _ = _stack(config, params, tokens, qpos, cache,
                  live=(jnp.arange(Pb) < n_real)[None])
    return x, {"latent": jnp.stack(cache.rows)}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: ShortcutMoEConfig,
                      active: Optional[jax.Array] = None):
    """One token a sequence against the paged pool: tokens [B] at
    positions [B].  A dead slot writes no row and makes no pick.
    Returns (logits [B, V], pools, counts): see `init_counts`."""
    c = config
    cache = LM._PagedDecode(pools["latent"], tables, positions, active)
    x, routed = _stack(c, params, tokens[:, None], positions[:, None],
                       cache, live=None if active is None
                       else active[:, None])
    n_live = jnp.asarray(tokens.shape[0], jnp.int32) if active is None \
        else jnp.sum(active, dtype=jnp.int32)
    held, zero = routed[:, :-1], jnp.sum(routed[:, -1])
    counts = {"expert_tokens": held,
              "experts_touched": jnp.sum(held > 0, dtype=jnp.int32),
              "ticks": jnp.ones((), jnp.int32),
              "zero_picks": zero,
              "real_picks": n_live * (c.top_k * c.n_layers) - zero,
              "held_picks": jnp.sum(held),
              **walk_counts(held, tokens.shape[0] * c.top_k,
                            c.router_width)}
    return LM._head(c, params, x[:, 0]), {"latent": cache.pool}, counts


def init_counts(config: ShortcutMoEConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts: tokens routed to each
    HELD expert of each layer, the distinct ones touched, ticks, and the
    live tokens' assignments: to zero-compute experts, to routed experts
    (held anywhere: `zero_picks + real_picks` = live tokens x top_k x
    layers) and to the held ones; and what the expert layers' walk of
    the held picks cost (`models/moe.py::walk_counts`)."""
    z = jnp.zeros((), jnp.int32)
    return {"expert_tokens": jnp.zeros(
                (config.n_layers, config.n_held_experts), jnp.int32),
            "experts_touched": z, "ticks": z, "zero_picks": z,
            "real_picks": z, "held_picks": z, "moe_rows_walked": z,
            "moe_rows_dense": z, "moe_extra_passes": z}


_SERVING = ServingFns(
    name="two latent sublayers + a shortcut expert layer with "
         "zero-compute experts (models/shortcut_moe.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=LM.lm_head_weight, init_counts=init_counts,
    paged_attention=LM.serving_paged_attention,
    grouped_matmul=serving_grouped_path)
