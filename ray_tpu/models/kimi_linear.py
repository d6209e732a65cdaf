"""Hybrid decoder: recurrent delta-rule layers (KDA) and latent-attention
layers (MLA) in one stack, each followed by the feed-forward half of
`models/latent_moe.py` (`model_type` `kimi_linear`).

- **Two kinds of state.**  An MLA layer keeps one row a token in the
  paged latent pool, exactly as `models/latent_moe.py` does (its cache
  classes, `attend_expanded` / `attend_absorbed` and `latent_attention`
  are used as they are, with NO rotary: the 64 "rope" channels of query
  and key are plain channels).  A KDA layer (`ops/kda.py`) keeps, for
  each sequence, a state of a fixed size whatever the length: `S`
  [heads, dk, dv] in `state_dtype` and the last `conv_size - 1`
  pre-activation rows of q, k and v for the short convolution.  The
  engine holds that by SLOT (`init_slot_state`; models/serving.py):
  zeros at admission, advanced over the real tokens of a prefill call
  and handed to the next chunk of the same prompt, updated in place at
  a static layer index in the tick.
- **Which layer is which** comes from the config (`kda_layers`, indices
  from 0; every other layer is MLA), as do all sizes.  The pool has one
  leaf with a row a token for the MLA layers only, the slot state a row
  a slot for the KDA layers only.
- **The expert half** is `latent_moe.feed_forward`, told which experts
  this chip holds (`expert_rank` of `expert_shards`: the router is
  `n_experts` wide as published, the weights are the held
  `n_experts / expert_shards`; models/moe.py).  The vocabulary is what
  the config says: ids, logits and sampling are over it.
- One definition of a layer over three situations, as in
  `latent_moe.py`: no cache (`forward`), one sequence's call of a
  bucketed / chunked prefill (`prefill_paged`), one token a slot
  (`decode_step_paged`).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ray_tpu.models import latent_moe as LM
from ray_tpu.models.llama import embed_lookup, rms_norm
from ray_tpu.models.moe import serving_grouped_path
from ray_tpu.models.serving import ServingFns
from ray_tpu.ops import kda, short_conv

L2_EPS = 1e-6


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig(LM.LatentMoEConfig):
    vocab_size: int = 163840
    dim: int = 2304
    n_layers: int = 27
    dense_hidden_dim: int = 9216
    expert_hidden_dim: int = 1024
    n_experts: int = 256            # the router's width, as published
    top_k: int = 8
    n_shared_experts: int = 1
    routed_scaling_factor: float = 2.446
    norm_eps: float = 1e-5
    # layers (from 0) whose mixer is KDA; the others are MLA
    kda_layers: Tuple[int, ...] = (0, 1, 2, 4, 5, 6, 8, 9, 10, 12, 13, 14,
                                   16, 17, 18, 20, 21, 22, 24, 25)
    kda_heads: int = 32
    kda_head_dim: int = 128         # keys and values alike
    conv_size: int = 4
    state_dtype: Any = jnp.float32  # the recurrent state between tokens
    # this chip holds experts [rank E/n, (rank+1) E/n) of every layer
    expert_rank: int = 0
    expert_shards: int = 1

    @property
    def n_kda_layers(self) -> int:
        return sum(l < self.n_layers for l in self.kda_layers)

    @property
    def n_mla_layers(self) -> int:
        return self.n_layers - self.n_kda_layers

    @property
    def n_held_experts(self) -> int:
        return self.n_experts // self.expert_shards

    @property
    def kda_width(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @staticmethod
    def tiny(**overrides) -> "KimiLinearConfig":
        """Test-size config: one period and a half (KDA KDA KDA MLA KDA)."""
        return KimiLinearConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=5, n_dense_layers=1, n_heads=4,
            kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
            v_head_dim=16, dense_hidden_dim=128, expert_hidden_dim=32,
            n_experts=8, top_k=2, n_shared_experts=1, max_seq_len=128,
            kda_layers=(0, 1, 2, 4), kda_heads=4, kda_head_dim=16),
            **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def draw_decay(key: jax.Array, heads: int, width: int):
    """`A_log` [heads] and `dt_bias` [width] float32, drawn so that a
    step's decay `exp(-exp(A_log) softplus(dt_bias + ...))` lies where a
    trained model's do: A uniform in [1, 16], dt log-uniform in
    [1e-3, 1e-1] through the inverse softplus."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, (heads,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(kd, (width,), jnp.float32,
                                    jnp.log(1e-3), jnp.log(1e-1)))
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def init_params(config: KimiLinearConfig, key: jax.Array,
                bias_scale: float = 0.01) -> Dict[str, Any]:
    """normal(0, 0.02) matrices, unit norms, a selection bias drawn at
    `bias_scale`, decays by `draw_decay`; the HELD experts only."""
    c = config
    dt = c.param_dtype
    D, W, r = c.dim, c.kda_width, c.kda_head_dim
    k_embed, k_out, k_layers = jax.random.split(key, 3)

    def draw(key, *shape):
        return jax.nn.initializers.normal(0.02)(key, shape, dt)

    layers: List[Dict[str, jax.Array]] = []
    for i, lk in enumerate(jax.random.split(k_layers, c.n_layers)):
        ks = jax.random.split(lk, 24)
        p = {"attn_norm": jnp.ones((D,), dt), "ffn_norm": jnp.ones((D,), dt)}
        if i in c.kda_layers:
            a_log, dt_bias = draw_decay(ks[0], c.kda_heads, W)
            p.update(
                wq=draw(ks[1], D, W), wk=draw(ks[2], D, W),
                wv=draw(ks[3], D, W),
                conv_q=draw(ks[4], c.conv_size, W),
                conv_k=draw(ks[5], c.conv_size, W),
                conv_v=draw(ks[6], c.conv_size, W),
                A_log=a_log, dt_bias=dt_bias,
                wf_a=draw(ks[7], D, r), wf_b=draw(ks[8], r, W),
                w_beta=draw(ks[9], D, c.kda_heads),
                wg_a=draw(ks[10], D, r), wg_b=draw(ks[11], r, W),
                o_norm=jnp.ones((r,), dt), wo=draw(ks[12], W, D))
        else:
            H = c.n_heads
            p.update(
                wq=draw(ks[1], D, H * c.qk_head_dim),
                wkv_a=draw(ks[2], D, c.kv_lora_rank + c.qk_rope_head_dim),
                kv_norm=jnp.ones((c.kv_lora_rank,), dt),
                wkv_b=draw(ks[3], c.kv_lora_rank,
                           H * (c.qk_nope_head_dim + c.v_head_dim)),
                wo=draw(ks[4], H * c.v_head_dim, D))
        if i < c.n_dense_layers:
            F = c.dense_hidden_dim
            p.update(w_gate=draw(ks[13], D, F), w_up=draw(ks[14], D, F),
                     w_down=draw(ks[15], F, D))
        else:
            E, Eh, F = c.n_experts, c.n_held_experts, c.expert_hidden_dim
            Fs = c.n_shared_experts * F
            p.update(
                router=draw(ks[13], D, E),
                router_bias=jax.random.normal(ks[14], (E,), jnp.float32)
                * bias_scale,
                w_gate=draw(ks[15], Eh, D, F), w_up=draw(ks[16], Eh, D, F),
                w_down=draw(ks[17], Eh, F, D),
                ws_gate=draw(ks[18], D, Fs), ws_up=draw(ks[19], D, Fs),
                ws_down=draw(ks[20], Fs, D))
        layers.append(p)
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt),
            "lm_head": draw(k_out, D, c.vocab_size)}


# ---------------------------------------------------------------------------
# The recurrent layers' state: where it comes from and where it goes.
# `conv(j, x, w)` and `recur(j, q, k, v, g, beta)` for KDA layer j.
# ---------------------------------------------------------------------------

def init_slot_state(config: KimiLinearConfig, num_slots: int
                    ) -> Dict[str, jax.Array]:
    """A row a slot a KDA layer (models/serving.py): the delta-rule
    state and the convolution's tail, its `conv_size - 1` rows of
    q ‖ k ‖ v side by side in the lanes of one (`ops/short_conv.py`),
    zeros."""
    c = config
    return {
        "S": jnp.zeros((c.n_kda_layers, num_slots, c.kda_heads,
                        c.kda_head_dim, c.kda_head_dim), c.state_dtype),
        "conv": jnp.zeros((c.n_kda_layers, num_slots,
                           (c.conv_size - 1) * 3 * c.kda_width), c.dtype)}


class _Sequences:
    """Whole (padded) sequences, each from the state handed in
    {leaf: [Lk, B, ...]}: the chunkwise form over the first `n_real`
    tokens; the states after them are kept for the caller.  (This and
    `_Step` carry `models/gdn_hybrid.py`'s states too, whose `S` lies
    several heads a row, `ops.kda.pack`: one a row here.)"""

    def __init__(self, state, n_real):
        self.inp, self.n_real = state, n_real
        self.S: List[jax.Array] = []
        self.tails: List[jax.Array] = []

    def conv(self, j, x, w):
        y, tail = short_conv.short_conv(
            x, w, short_conv.rows(self.inp["conv"][j], w), self.n_real)
        self.tails.append(short_conv.flat(tail).astype(
            self.inp["conv"].dtype))
        return y

    def recur(self, j, q, k, v, g, beta):
        S0 = self.inp["S"][j]
        p = q.shape[-2] // S0.shape[-3]     # heads a row (`ops.kda.pack`)
        o, S = kda.kda_chunked(q, k, v, g, beta, kda.unpack(S0, p),
                               self.n_real)
        self.S.append(kda.pack(S, p).astype(S0.dtype))
        return o

    def state(self):
        return {"S": jnp.stack(self.S), "conv": jnp.stack(self.tails)}


class _Step:
    """One token a slot, the whole tree updated in place at a static
    layer index; a dead slot keeps its rows.  The delta-rule state goes
    by ONE of two paths, chosen by backend and shape alone
    (`ops.kda.engages`): the Pallas step over the whole stack, which
    reads and writes each LIVE slot's `[H, dk, dv]` rows once where
    they lie and a dead slot's never (`plan`: the live slots' indices,
    made here once a tick for all its layers), or `kda_step` on the
    layer's rows of all slots and `_keep`.  The convolution's tail
    (`[(conv_size - 1) 3 W]` a slot) is shifted where it lies by
    `short_conv.step_in_place`, which chooses its form the same way."""

    def __init__(self, state, active):
        self.tree, self.active = dict(state), active
        S = state["S"]
        self.plan = kda.live_plan(active, S.shape[1]) if kda.engages(
            S.shape[-2], S.shape[-1], S.dtype) else None

    def _keep(self, new, old):
        if self.active is None:
            return new.astype(old.dtype)
        live = self.active.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(live, new.astype(old.dtype), old)

    def conv(self, j, x, w):
        y, self.tree["conv"] = short_conv.step_in_place(
            self.tree["conv"], j, x[:, 0], w, self.active)
        return y[:, None]

    def recur(self, j, q, k, v, g, beta):
        now = (q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0])
        if self.plan is not None:
            o, self.tree["S"] = kda.kda_step_live(
                self.tree["S"], j, *now, self.plan)
            return o[:, None]
        old = self.tree["S"][j]
        p = q.shape[-2] // old.shape[-3]    # heads a row (`ops.kda.pack`)
        o, S = kda.kda_step(kda.unpack(old, p).astype(jnp.float32), *now)
        self.tree["S"] = self.tree["S"].at[j].set(
            self._keep(kda.pack(S, p), old))
        return o[:, None]

    def state(self):
        return self.tree


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def _l2norm(x):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + L2_EPS)


def kda_mixer(c: KimiLinearConfig, j: int, p, x, rec):
    """x [B, S, D] -> x + KDA(x), the layer's state going through `rec`
    at KDA-layer index j."""
    B, S, D = x.shape
    dt, H, dk, W = c.dtype, c.kda_heads, c.kda_head_dim, c.kda_width
    f32 = jnp.float32
    with jax.named_scope("kda"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        with jax.named_scope("proj"):
            qkv = jnp.concatenate([h @ p[n].astype(dt)
                                   for n in ("wq", "wk", "wv")], -1)
        with jax.named_scope("conv"):
            w = jnp.concatenate([p["conv_q"], p["conv_k"], p["conv_v"]], -1)
            qkv = jax.nn.silu(rec.conv(j, qkv, w)).reshape(B, S, 3, H, dk)
            q = _l2norm(qkv[:, :, 0]) * dk ** -0.5
            k = _l2norm(qkv[:, :, 1])
            v = qkv[:, :, 2]
        with jax.named_scope("gate"):
            g = -jnp.exp(p["A_log"].astype(f32))[:, None] * jax.nn.softplus(
                ((h @ p["wf_a"].astype(dt)) @ p["wf_b"].astype(dt)
                 ).astype(f32) + p["dt_bias"].astype(f32)
            ).reshape(B, S, H, dk)
            beta = jax.nn.sigmoid((h @ p["w_beta"].astype(dt)).astype(f32))
            gate = jax.nn.sigmoid(
                ((h @ p["wg_a"].astype(dt)) @ p["wg_b"].astype(dt)
                 ).astype(f32)).reshape(B, S, H, dk)
        with jax.named_scope("state"):
            o = rec.recur(j, q, k, v, g, beta)              # float32
        with jax.named_scope("out"):
            o = (rms_norm(o, p["o_norm"], c.norm_eps).astype(f32)
                 * gate).astype(dt)
            return x + o.reshape(B, S, W) @ p["wo"].astype(dt)


def _stack(c: KimiLinearConfig, params, tokens, qpos, cache, rec,
           live=None):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> (normed hidden [B, S, D], tokens routed to each held expert
    [n_moe_layers, E held])."""
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    share = (c.expert_rank, c.expert_shards)
    routed = []
    jk = jm = 0
    for p in params["layers"]:
        if "A_log" in p:
            x = kda_mixer(c, jk, p, x, rec)
            jk += 1
        else:
            x = LM.latent_attention(c, jm, p, x, qpos, None, None, cache)
            jm += 1
        x, sizes = LM.feed_forward(c, p, x, live, share)
        if sizes is not None:
            routed.append(sizes)
    return rms_norm(x, params["norm_f"], c.norm_eps), jnp.stack(routed)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: KimiLinearConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache, every
    sequence from a zero state."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    rec = _Sequences(init_slot_state(config, B), S)
    x, _ = _stack(config, params, tokens, qpos, LM._NoCache(), rec)
    return LM._head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def init_paged_pool(config: KimiLinearConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    """One row of latent ‖ shared key a token, for the MLA layers."""
    c = config
    return {"latent": jnp.zeros(
        (c.n_mla_layers, num_blocks, block_size, c.cache_row), c.dtype)}


def prefill_paged(params, tokens, start, hist, config: KimiLinearConfig,
                  n_real, state):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `state` {leaf: [Lk, ...]} the slot's recurrent
    rows after its first `start` tokens.  Padding goes through no
    expert and advances no state."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = LM._History(hist["latent"], start)
    rec = _Sequences({k: v[:, None] for k, v in state.items()}, n_real)
    x, _ = _stack(config, params, tokens, qpos, cache, rec,
                  live=(jnp.arange(Pb) < n_real)[None])
    return x, {"latent": jnp.stack(cache.rows)}, \
        {k: v[:, 0] for k, v in rec.state().items()}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: KimiLinearConfig,
                      active: Optional[jax.Array] = None, state=None):
    """One token a slot against the paged latent pool and the slots'
    recurrent states: tokens [B] at positions [B].  A dead slot writes
    no row, goes through no expert and keeps its state.  Returns
    (logits [B, V], pools, counts, state)."""
    c = config
    B = tokens.shape[0]
    cache = LM._PagedDecode(pools["latent"], tables, positions, active)
    rec = _Step(state, active)
    x, routed = _stack(c, params, tokens[:, None], positions[:, None],
                       cache, rec, live=None if active is None
                       else active[:, None])
    n_live = jnp.asarray(B, jnp.int32) if active is None \
        else jnp.sum(active, dtype=jnp.int32)
    counts = {"expert_tokens": routed,
              "experts_touched": jnp.sum(routed > 0, dtype=jnp.int32),
              "ticks": jnp.ones((), jnp.int32),
              "live_slots": n_live,
              "pairs_local": jnp.sum(routed, dtype=jnp.int32),
              "pairs_total": n_live * (c.top_k * c.n_moe_layers),
              # slot-layers the Pallas step advanced (0: `kda_step` ran)
              "kda_rows_stepped": n_live * (
                  c.n_kda_layers if rec.plan is not None else 0)}
    return LM._head(c, params, x[:, 0]), {"latent": cache.pool}, counts, \
        rec.state()


def init_counts(config: KimiLinearConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts: tokens routed to each
    HELD expert of each expert layer, the distinct ones touched, ticks,
    live slots summed over ticks, the token-expert assignments that
    fell on held experts beside all that were made, and the slot-layers
    whose state the Pallas step advanced (`live_slots` x KDA layers
    where it engages, 0 where `kda_step` ran)."""
    z = jnp.zeros((), jnp.int32)
    return {"expert_tokens": jnp.zeros(
                (config.n_moe_layers, config.n_held_experts), jnp.int32),
            "experts_touched": z, "ticks": z, "live_slots": z,
            "pairs_local": z, "pairs_total": z, "kda_rows_stepped": z}


_SERVING = ServingFns(
    name="KDA + latent attention, a share of the experts "
         "(models/kimi_linear.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=LM.lm_head_weight, init_counts=init_counts,
    init_slot_state=init_slot_state,
    paged_attention=LM.serving_paged_attention,
    grouped_matmul=serving_grouped_path)
