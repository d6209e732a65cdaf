"""Mamba-1 layers with normed Delta, B and C beside a few attention
layers of ONE K/V head, a SwiGLU after every mixer (`model_type`
`jamba`, dense: `num_experts` 1).

Every layer is `x = x + Mixer(RMSNorm_in(x))`, `x = x + W_down(silu(
W_gate u) * W_up u)` with `u = RMSNorm_ff(x)`; after the last one more
RMSNorm and a head TIED to the embedding table.  Layer i is attention
where `i % attn_period == attn_offset` (7 and 21 of 28) and Mamba
elsewhere.  No positions anywhere: the recurrence carries order.

- **Mamba** (`models/sambay.py::ssm_mixer`, the one Mamba-1 mixer body
  of the tree, with `_Step` / `_Sequences` carrying state and tail;
  the state's layout `init_slot_state`, the tied head `_head` and the
  control `quantize_int8` are that file's too, as they stand):
  `[xs | z] = u W_in`; a causal depthwise convolution of `d_conv` taps
  with bias, SiLU; `[dt | B | C] = xc W_x`; then what this family adds,
  **an RMSNorm with a learned weight on each of the three**
  (`_normed`: over 160, 16 and 16 values), `Delta = softplus(dt W_dt +
  b_dt)`, the selective scan of `ops/selective_scan.py` under `A =
  -exp(A_log)`, `+ D xc`, the `silu(z)` gate, `W_out`.  What it keeps
  for a sequence is of a fixed size whatever the length: `h` `[d_state,
  d_inner / 128, 128]` in `state_dtype` and the last `d_conv - 1` rows
  of `xs` (side by side in the lanes of one row: 30,720 B a slot a
  layer), held by the engine by SLOT (`init_slot_state`;
  models/serving.py): 26 layers x 327,680 B = 8.5 MB of float32 state a
  slot at the published sizes, against 1,024 B of K and V a token.
- **Attention** (`models/nemotron_h.py::attention_mixer` and its three
  caches): 20 query heads of 128 over ONE K/V head, causal softmax, no
  rotary.  Its rows go to two `full`-kind pools `k`, `v` `[attention
  layers, NB, bs, 128]` (`ops/paged_attention.py`, "Few KV heads": at
  one head the side-by-side row IS the head, and every query head
  scores whole rows with no mask and no zero lane).
- **The layers ride loops, a kind's weights stacked**: `params["mamba"]`
  and `params["attn"]` hold their layers on a leading axis; a run of
  consecutive Mamba layers is one `lax.fori_loop` that reads layer j of
  the stack at a traced index (no slice of the stack is ever cut out),
  the attention layers between the runs are unrolled: 28 layers trace
  as three Mamba bodies and two attention bodies.
- One definition of a layer over three situations: no cache
  (`forward`), one sequence's call of a bucketed / chunked prefill
  (`prefill_paged`), one token a slot (`decode_step_paged`).

Every size comes from `JambaConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import embed_lookup, rms_norm
from ray_tpu.models.nemotron_h import (
    _History, _NoCache, _Paged, _paged_attention, attention_mixer,
    init_paged_pool,
)
from ray_tpu.models.sambay import (
    _head, _Sequences, _Step, init_slot_state, lm_head_weight, quantize_int8,
    ssm_mixer,
)
from ray_tpu.models.serving import ServingFns

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class JambaConfig:
    vocab_size: int = 65536
    dim: int = 2560
    n_layers: int = 28
    attn_period: int = 14           # layer i attends where
    attn_offset: int = 7            # i % attn_period == attn_offset
    n_heads: int = 20
    n_kv_heads: int = 1
    head_dim: int = 128
    hidden_dim: int = 8192
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    dt_rank: int = 160
    max_seq_len: int = 262144
    norm_eps: float = 1e-6
    # keys a step of the prefill's blockwise attention takes
    prefill_key_block: int = 1024
    dtype: Any = jnp.bfloat16   # activation/matmul dtype, and the tail's
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the recurrent state between tokens

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def kinds(self) -> str:
        """A character a layer: `*` attention, `M` Mamba."""
        return "".join("*" if i % self.attn_period == self.attn_offset
                       else "M" for i in range(self.n_layers))

    @property
    def n_attn_layers(self) -> int:
        return self.kinds.count("*")

    @property
    def n_ssm_layers(self) -> int:
        return self.kinds.count("M")

    @property
    def mamba_runs(self) -> List[int]:
        """The Mamba layers between attention layers, as run lengths:
        one more entry than there are attention layers (7, 13, 6)."""
        return [len(run) for run in self.kinds.split("*")]

    @staticmethod
    def tiny(**overrides) -> "JambaConfig":
        """Test-size config: `M*MM*M`, two query heads over the one K/V
        head, `d_inner` one lane row."""
        return JambaConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=6, attn_period=3,
            attn_offset=1, n_heads=2, head_dim=16, hidden_dim=128,
            d_state=4, dt_rank=8, max_seq_len=128, prefill_key_block=8),
            **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: JambaConfig, key: jax.Array, std: float = 0.02
                ) -> Dict[str, Any]:
    """The family's draws: normal(0, std) matrices and taps, `A_log` =
    log(1 .. d_state) a channel, `b_dt` = softplus^-1(dt) with dt
    log-uniform in [1e-3, 1e-1], `Dskip` ones, norms ones, the
    convolution's bias zeros.  A kind's layers are stacked on a leading
    axis."""
    c = config
    dt = c.param_dtype
    D, C, N, F, R = c.dim, c.d_inner, c.d_state, c.hidden_dim, c.dt_rank
    A, Akv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim

    def draw(key, *shape):
        return jax.nn.initializers.normal(std)(key, shape, dt)

    def block(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return {"norm_in": jnp.ones((D,), dt), "norm_ff": jnp.ones((D,), dt),
                "w_gate": draw(k1, D, F), "w_up": draw(k2, D, F),
                "w_down": draw(k3, F, D)}

    def mamba(key):
        ks = jax.random.split(key, 7)
        step = jnp.exp(jax.random.uniform(
            ks[5], (C,), _F32, math.log(1e-3), math.log(1e-1)))
        return dict(
            block(ks[6]), w_in=draw(ks[0], D, 2 * C),
            conv_w=draw(ks[1], c.d_conv, C), conv_b=jnp.zeros((C,), dt),
            w_x=draw(ks[2], C, R + 2 * N), dt_norm=jnp.ones((R,), dt),
            b_norm=jnp.ones((N,), dt), c_norm=jnp.ones((N,), dt),
            w_dt=draw(ks[3], R, C), b_dt=step + jnp.log(-jnp.expm1(-step)),
            A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=_F32))[:, None], (N, C)),
            Dskip=jnp.ones((C,), _F32), w_out=draw(ks[4], C, D))

    def attention(key):
        ks = jax.random.split(key, 5)
        return dict(block(ks[4]), wq=draw(ks[0], D, A), wk=draw(ks[1], D, Akv),
                    wv=draw(ks[2], D, Akv), wo=draw(ks[3], A, D))

    k_embed, k_mamba, k_attn = jax.random.split(key, 3)
    return {"embed": draw(k_embed, c.vocab_size, D),
            "mamba": jax.vmap(mamba)(jax.random.split(
                k_mamba, c.n_ssm_layers)),
            "attn": jax.vmap(attention)(jax.random.split(
                k_attn, c.n_attn_layers)),
            "norm_f": jnp.ones((D,), dt)}


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def _normed(c: JambaConfig, p, dbc):
    """[dt | B | C] float32 -> the same with an RMSNorm over each of the
    three, under its own learned weight."""
    R, N = c.dt_rank, c.d_state
    return jnp.concatenate([
        rms_norm(dbc[..., lo:hi], p[name].astype(_F32), c.norm_eps)
        for name, lo, hi in (("dt_norm", 0, R), ("b_norm", R, R + N),
                             ("c_norm", R + N, R + 2 * N))], axis=-1)


def feed_forward(c: JambaConfig, p, x):
    """x -> x + W_down (silu(W_gate u) * W_up u), u = RMSNorm_ff(x)."""
    dt = c.dtype
    with jax.named_scope("ffn"):
        u = rms_norm(x, p["norm_ff"], c.norm_eps)
        return x + (jax.nn.silu(u @ p["w_gate"].astype(dt))
                    * (u @ p["w_up"].astype(dt))) @ p["w_down"].astype(dt)


def _stack(c: JambaConfig, params, tokens, cache, rec, st):
    """Embedding, every layer, final norm: tokens [B, S] -> (normed
    hidden [B, S, D], the states, the cache's `kv`)."""
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    normed = lambda p, dbc: _normed(c, p, dbc)

    def mamba_layer(j, carry):
        x, st = carry
        p = jax.tree.map(lambda w: lax.dynamic_index_in_dim(
            w, j, keepdims=False), params["mamba"])
        out, _, st = ssm_mixer(c, j, p, rms_norm(x, p["norm_in"], c.norm_eps),
                               rec, st, normed)
        return feed_forward(c, p, x + out), st

    kv, j = cache.kv, 0
    for l, run in enumerate(c.mamba_runs):
        if l:
            p = jax.tree.map(lambda w: w[l - 1], params["attn"])
            out, kv = attention_mixer(
                c, l - 1, p, rms_norm(x, p["norm_in"], c.norm_eps), cache, kv)
            x = feed_forward(c, p, x + out)
        x, st = lax.fori_loop(j, j + run, mamba_layer, (x, st))
        j += run
    return rms_norm(x, params["norm_f"], c.norm_eps), st, kv


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: JambaConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache, every
    sequence from a zero state."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _, _ = _stack(config, params, tokens, _NoCache(qpos), _Sequences(S),
                     init_slot_state(config, B))
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def prefill_paged(params, tokens, start, hist, config: JambaConfig,
                  n_real, state):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `hist` {k, v: [La, S_pad, 128]} the gathered
    history; `state` {leaf: [Lm, ...]} the slot's rows after its first
    `start` tokens.  Padding advances no state (its K/V rows are masked
    as keys, not skipped)."""
    cache = _History(config, hist, start, tokens.shape[1])
    x, st, kv = _stack(config, params, tokens, cache, _Sequences(n_real),
                       {k: v[:, None] for k, v in state.items()})
    return x, kv, {k: v[:, 0] for k, v in st.items()}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: JambaConfig,
                      active: Optional[jax.Array] = None, state=None):
    """One token a slot against the paged pools and the slots' states:
    tokens [B] at positions [B].  A dead slot writes no row and keeps
    its state.  Returns (logits [B, V], pools, counts, state)."""
    c = config
    cache = _Paged(c, pools, tables, positions, active)
    rec = _Step(state, active)
    x, st, kv = _stack(c, params, tokens[:, None], cache, rec, state)
    live = jnp.ones_like(positions, bool) if active is None else active
    n_live = jnp.sum(live, dtype=jnp.int32)
    counts = {
        "ticks": jnp.ones((), jnp.int32), "live_slots": n_live,
        # slot-layers the Pallas step advanced (0: `ssm_step` ran)
        "ssm_live_steps": n_live * (
            c.n_ssm_layers if rec.plan is not None else 0),
        # rows the attention layers read, every live slot's in each
        "mqa_rows_read": c.n_attn_layers * jnp.sum(
            jnp.where(live, positions + 1, 0).astype(_F32))}
    return _head(c, params, x[:, 0]), kv, counts, st


def init_counts(config: JambaConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts: ticks, live slots
    summed over ticks, the slot-layers the Pallas step advanced, and the
    rows the attention layers read (float32: a run reads more than 2^31
    of them)."""
    z = jnp.zeros((), jnp.int32)
    return {"ticks": z, "live_slots": z, "ssm_live_steps": z,
            "mqa_rows_read": jnp.zeros((), _F32)}


_SERVING = ServingFns(
    name="Mamba-1 with normed Delta, B, C + one-K/V-head attention "
         "without positions, a SwiGLU a layer (models/jamba.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=lm_head_weight, init_counts=init_counts,
    init_slot_state=init_slot_state, quantize_int8=quantize_int8,
    paged_attention=_paged_attention)
