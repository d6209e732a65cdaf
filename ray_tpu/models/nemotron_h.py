"""Hybrid decoder whose every layer is ONE mixer: Mamba-2 (state-space
duality) layers, non-gated relu² experts beside a shared one, and a few
grouped-query attention layers with no positions (`model_type`
`nemotron_h`).

A layer is `x + Mixer(RMSNorm(x))` and nothing else: no feed-forward
follows a Mamba or an attention layer.  Which mixer comes from the
config's `pattern`, a character a layer as the family publishes it: `M`
Mamba-2, `E` experts, `*` attention.  After the last layer one RMSNorm
and an untied head.

- **M, Mamba-2** (`ssd_mixer`): `[z | xBC | dt] = u W_in`; a causal
  depthwise convolution of `conv_size` taps with bias over `xBC`
  (`ops/short_conv.py`), SiLU; `xBC` split into `X` [H, P], `B` and `C`
  [G, N], head h reading group `h // (H / G)`; `dt = softplus(dt +
  dt_bias)`, not clamped; the recurrence of `ops/ssd.py` under `A =
  -exp(A_log)`, one decay a HEAD; `+ D_h X_h`; then the gate BEFORE the
  norm, `RMSNorm_group(y * silu(z)) * w` with the mean square over each
  group's `H P / G` channels; `W_out`.  What it keeps for a sequence is
  of a fixed size whatever the length: `S` `[H / 2, N, 2 P]` in
  `state_dtype` (two heads of 64 side by side in the lanes,
  `ops.kda.pack`) and the last `conv_size - 1` rows of `xBC`, held by
  the engine by SLOT (`init_slot_state`; models/serving.py).
- **E, experts** (`expert_mixer`): `models/moe.py::dropless_moe` under
  the sigmoid-with-bias rule (renormalised, x `routed_scaling_factor`)
  over the experts this chip holds (`expert_rank` of `expert_shards`:
  the router is `n_experts` wide as published), an expert being
  `W_down relu(W_up x)^2`, NO gate matrix, `w_up` and `w_down` both
  `[E, F, D]` (the expert width F = 1856 is no whole lane rows and is
  never the minor axis of a stored weight); plus the shared expert, the
  same form, for every token.
- **\\*, attention** (`attention_mixer`): grouped-query attention, 16
  query heads a K/V head at the published sizes, causal softmax, no
  rotary and no other positional encoding (the recurrent layers carry
  order).  Its rows go to two `full`-kind pools `k`, `v` `[attention
  layers, NB, bs, kvH hd]`, a token's K/V heads SIDE BY SIDE in one row
  (`ops/paged_attention.py`, "Few KV heads"); prefill walks the
  gathered history through `models/window_moe.py::blockwise_attention`.
- **The layers ride a scan where the pattern repeats** (`layout`): the
  longest run of whole repeats of one block from layer 0 is STACKED
  (`params["blocks"]`: one entry a layer of the block, each leaf with
  a leading repeat axis) and one `lax.scan` over the repeats, so
  `MEMEM*E` twice lowers as 7 layers; whatever follows
  (`params["tail"]`, the published pattern's last 17 layers) is
  unrolled.
- One definition of a layer over three situations: no cache
  (`forward`), one sequence's call of a bucketed / chunked prefill
  (`prefill_paged`), one token a slot (`decode_step_paged`).

Every size comes from `NemotronHConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import embed_lookup, rms_norm
from ray_tpu.models.moe import (
    dropless_moe, serving_grouped_path, sigmoid_bias_top_k,
)
from ray_tpu.models.serving import ServingFns
from ray_tpu.models.window_moe import (
    _masked_attention, _seen, blockwise_attention, piece_walk,
)
from ray_tpu.ops import kda
from ray_tpu.ops import paged_attention as paged
from ray_tpu.ops import short_conv, ssd

_F32 = jnp.float32
PUBLISHED_PATTERN = "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"


@dataclasses.dataclass(frozen=True)
class NemotronHConfig:
    vocab_size: int = 131072
    dim: int = 2688
    pattern: str = PUBLISHED_PATTERN    # M Mamba-2, E experts, * attention
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8                 # of heads that share B and C
    ssm_state: int = 128
    conv_size: int = 4
    n_heads: int = 32
    n_kv_heads: int = 2
    head_dim: int = 128
    expert_hidden_dim: int = 1856
    shared_hidden_dim: int = 3712       # n_shared_experts x their width
    n_experts: int = 128                # the router's width, as published
    top_k: int = 6
    routed_scaling_factor: float = 2.5
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    # keys a step of the prefill's blockwise attention takes
    prefill_key_block: int = 1024
    dtype: Any = jnp.bfloat16   # activation/matmul dtype, and the tail's
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the recurrent state between tokens
    # this chip holds experts [rank E/n, (rank+1) E/n) of every layer
    expert_rank: int = 0
    expert_shards: int = 1

    def __post_init__(self):
        if set(self.pattern) - set("ME*") or not self.pattern:
            raise ValueError(f"pattern {self.pattern!r}: M, E and * only")

    @property
    def n_layers(self) -> int:
        return len(self.pattern)

    @property
    def n_ssm_layers(self) -> int:
        return self.pattern.count("M")

    @property
    def n_attn_layers(self) -> int:
        return self.pattern.count("*")

    @property
    def n_moe_layers(self) -> int:
        return self.pattern.count("E")

    @property
    def n_held_experts(self) -> int:
        return self.n_experts // self.expert_shards

    @property
    def d_inner(self) -> int:
        return self.ssm_heads * self.ssm_head_dim

    @property
    def conv_width(self) -> int:        # X, then B and C of every group
        return self.d_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def heads_a_row(self) -> int:
        return ssd.heads_a_row(self.ssm_heads, self.ssm_head_dim)

    @property
    def layout(self) -> Tuple[str, int, str]:
        """(block, repeats, tail): `pattern == block * repeats + tail`
        with the repeats (>= 2) that cover the most layers from layer 0,
        the shorter block on a tie; ("", 0, pattern) where nothing
        repeats."""
        best = ("", 0, self.pattern)
        for n in range(1, len(self.pattern) // 2 + 1):
            block, reps = self.pattern[:n], 1
            while self.pattern.startswith(block * (reps + 1)):
                reps += 1
            if reps >= 2 and n * reps > len(best[0]) * best[1]:
                best = (block, reps, self.pattern[n * reps:])
        return best

    @staticmethod
    def tiny(**overrides) -> "NemotronHConfig":
        """Test-size config: `MEM*E` twice (a scan of two repeats), then
        `ME` unrolled; two groups of two Mamba heads, two query heads a
        K/V head, all 8 experts held."""
        return NemotronHConfig(**{**dict(
            vocab_size=512, dim=64, pattern="MEM*EMEM*EME", ssm_heads=4,
            ssm_head_dim=16, ssm_groups=2, ssm_state=8, n_heads=4,
            n_kv_heads=2, head_dim=16, expert_hidden_dim=32,
            shared_hidden_dim=64, n_experts=8, top_k=2, max_seq_len=128,
            prefill_key_block=8), **overrides})

    def serving(self):
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def draw_step(key: jax.Array, heads: int, lo=1e-3, hi=1e-1, floor=1e-4):
    """`A_log` = log U(1, 16) and `dt_bias` = softplus^-1(dt), dt
    log-uniform in [lo, hi] floored at `floor`, a head, float32: the
    family's initialisation."""
    ka, kd = jax.random.split(key)
    a = jax.random.uniform(ka, (heads,), _F32, 1.0, 16.0)
    dt = jnp.maximum(jnp.exp(jax.random.uniform(
        kd, (heads,), _F32, jnp.log(lo), jnp.log(hi))), floor)
    return jnp.log(a), dt + jnp.log(-jnp.expm1(-dt))


def init_layer(config: NemotronHConfig, kind: str, key: jax.Array,
               std: float = 0.02, bias_scale: float = 0.02
               ) -> Dict[str, jax.Array]:
    """One layer of `kind`: normal(0, std) matrices and taps, unit
    norms, a zero convolution bias, `D` ones, a selection bias drawn at
    `bias_scale`, the decays by `draw_step`; the HELD experts only."""
    c = config
    dt = c.param_dtype
    D = c.dim
    ks = jax.random.split(key, 8)

    def draw(key, *shape):
        return jax.nn.initializers.normal(std)(key, shape, dt)

    p = {"norm": jnp.ones((D,), dt)}
    if kind == "M":
        C, H = c.d_inner, c.ssm_heads
        a_log, dt_bias = draw_step(ks[0], H)
        p.update(w_in=draw(ks[1], D, C + c.conv_width + H),
                 conv_w=draw(ks[2], c.conv_size, c.conv_width),
                 conv_b=jnp.zeros((c.conv_width,), dt),
                 A_log=a_log, dt_bias=dt_bias, D=jnp.ones((H,), _F32),
                 gate_norm=jnp.ones((C,), dt), w_out=draw(ks[3], C, D))
    elif kind == "*":
        A, Akv = c.n_heads * c.head_dim, c.n_kv_heads * c.head_dim
        p.update(wq=draw(ks[0], D, A), wk=draw(ks[1], D, Akv),
                 wv=draw(ks[2], D, Akv), wo=draw(ks[3], A, D))
    else:
        E, Eh, F, Fs = (c.n_experts, c.n_held_experts, c.expert_hidden_dim,
                        c.shared_hidden_dim)
        p.update(router=draw(ks[0], D, E),
                 router_bias=jax.random.normal(ks[1], (E,), _F32)
                 * bias_scale,
                 w_up=draw(ks[2], Eh, F, D), w_down=draw(ks[3], Eh, F, D),
                 ws_up=draw(ks[4], D, Fs), ws_down=draw(ks[5], Fs, D))
    return p


def init_params(config: NemotronHConfig, key: jax.Array, std: float = 0.02
                ) -> Dict[str, Any]:
    """`init_layer` for every layer: the repeated block's layers stacked
    over the repeats (`layout`), the rest a list."""
    c = config
    block, reps, tail = c.layout
    k_embed, k_head, k_blocks, k_tail = jax.random.split(key, 4)
    draw = lambda key, *shape: jax.nn.initializers.normal(std)(
        key, shape, c.param_dtype)
    blocks = [jax.vmap(lambda k, kind=kind: init_layer(c, kind, k, std))(
        jax.random.split(jax.random.fold_in(k_blocks, i), reps))
        for i, kind in enumerate(block)]
    return {"embed": draw(k_embed, c.vocab_size, c.dim), "blocks": blocks,
            "tail": [init_layer(c, kind, k, std) for kind, k in zip(
                tail, jax.random.split(k_tail, max(len(tail), 1)))],
            "norm_f": jnp.ones((c.dim,), c.param_dtype),
            "lm_head": draw(k_head, c.dim, c.vocab_size)}


def lm_head_weight(params: Dict[str, Any], config: NemotronHConfig):
    return params["lm_head"].astype(config.dtype)


def init_slot_state(config: NemotronHConfig, num_slots: int
                    ) -> Dict[str, jax.Array]:
    """A row a slot a Mamba-2 layer (models/serving.py): the state,
    `ops.kda.pack`ed two heads a row (`[32, 128, 128]` float32 at 64
    heads of 64 x 128, 2,097,152 B a slot a layer with no padded lane),
    and the convolution's tail, its `conv_size - 1` rows side by side
    in the lanes of one (`ops/short_conv.py`), zeros."""
    c = config
    p = c.heads_a_row
    return {
        "S": jnp.zeros((c.n_ssm_layers, num_slots, c.ssm_heads // p,
                        c.ssm_state, p * c.ssm_head_dim), c.state_dtype),
        "tail": jnp.zeros((c.n_ssm_layers, num_slots,
                           (c.conv_size - 1) * c.conv_width), c.dtype)}


# ---------------------------------------------------------------------------
# The Mamba-2 layers' states: where they come from and where they go.
# The whole tree {S, tail} rides through the layer scan; layer j of it is
# read and written at a traced index.
# ---------------------------------------------------------------------------

class _Sequences:
    """Whole (padded) sequences, each from the state handed in {leaf:
    [Lm, B, ...]}, over their first `n_real` rows (the chunked matrix
    form); the tree then holds the states after them."""

    def __init__(self, c, n_real):
        self.p, self.n_real = c.heads_a_row, n_real

    def conv(self, st, j, x, w):
        y, tail = short_conv.short_conv(
            x, w, short_conv.rows(st["tail"][j], w), self.n_real)
        return y, dict(st, tail=st["tail"].at[j].set(
            short_conv.flat(tail).astype(st["tail"].dtype)))

    def recur(self, st, j, x, dt, A, Bm, Cm):
        S0 = st["S"][j]
        y, S = ssd.ssd_chunked(x, dt, A, Bm, Cm, kda.unpack(S0, self.p),
                               self.n_real)
        return y, dict(st, S=st["S"].at[j].set(
            kda.pack(S, self.p).astype(S0.dtype)))


class _Step:
    """One token a slot; a dead slot keeps its rows.  The state goes by
    ONE of two paths, chosen by backend and shape alone
    (`ops.ssd.engages`): the Pallas step over the whole stack, which
    reads and writes each LIVE slot's rows once where they lie (`plan`:
    the live slots, made here once a tick for all its layers), or
    `ssd_step` on the layer's rows of all slots and a `where`.  The
    tail's layer of the stack is shifted where it lies by
    `short_conv.step_in_place`, which chooses its form the same way."""

    def __init__(self, c, state, active):
        self.p, self.active = c.heads_a_row, active
        S = state["S"]
        self.plan = kda.live_plan(active, S.shape[1]) \
            if ssd.engages(S) else None

    def _keep(self, new, old):
        if self.active is None:
            return new.astype(old.dtype)
        live = self.active.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(live, new.astype(old.dtype), old)

    def conv(self, st, j, x, w):
        y, tails = short_conv.step_in_place(st["tail"], j, x[:, 0], w,
                                            self.active)
        return y[:, None], dict(st, tail=tails)

    def recur(self, st, j, x, dt, A, Bm, Cm):
        now = (x[:, 0], dt[:, 0], A, Bm[:, 0], Cm[:, 0])
        if self.plan is not None:
            y, S = ssd.ssd_step_live(st["S"], j, *now, self.plan)
            return y[:, None], dict(st, S=S)
        old = st["S"][j]
        y, S = ssd.ssd_step(kda.unpack(old, self.p), *now)
        return y[:, None], dict(st, S=st["S"].at[j].set(
            self._keep(kda.pack(S, self.p), old)))


# ---------------------------------------------------------------------------
# The attention layers' caches.  `attend(kv, l, q, k, v) -> (o, kv)` for
# attention layer l (traced): q [B, S, H, hd], k and v [B, S, kvH, hd];
# `kv` is what rides through the layer scan.
# ---------------------------------------------------------------------------

class _NoCache:
    """The sequences' own rows are their keys (scoring, tests)."""

    def __init__(self, qpos):
        self.qpos, self.kv = qpos, {}

    def attend(self, kv, l, q, k, v):
        return _masked_attention(q, k, v, _seen(self.qpos, self.qpos,
                                                None)), kv


class _History:
    """ONE sequence with its gathered history {k, v: [La, S_pad, kvH
    hd]} by position (models/serving.py).  The chunk sits at `start`..;
    `kv` holds its new rows for the engine to scatter.  Attends through
    `window_moe.blockwise_attention` over the history up to the chunk's
    end: the kernel's tiles where it engages, a block of keys at a time
    elsewhere."""

    def __init__(self, c, hist, start, Pb):
        self.c, self.hist, self.start = c, hist, start
        self.qpos = start + jnp.arange(Pb)
        self.kv = {name: jnp.zeros((h.shape[0], Pb, h.shape[-1]), h.dtype)
                   for name, h in hist.items()}

    def attend(self, kv, l, q, k, v):
        c, Pb = self.c, k.shape[1]
        kv, keys = dict(kv), []
        for name, x in zip(("k", "v"), (k, v)):
            hist = self.hist[name]
            x = x[0].reshape(Pb, -1).astype(hist.dtype)
            kv[name] = kv[name].at[l].set(x)
            rows = lax.dynamic_update_slice(hist[l], x, (self.start, 0))
            keys.append(rows.reshape(rows.shape[0], c.n_kv_heads,
                                     c.head_dim).astype(c.dtype))
        kpos0, lo, hi = piece_walk("full", self.start, Pb, keys[0].shape[0],
                                   None, c.prefill_key_block)
        out = blockwise_attention(q[0], *keys, self.qpos, kpos0, lo, hi,
                                  None, c.prefill_key_block)
        return out[None], kv


class _Paged:
    """One new row a sequence at positions `qpos` [B], written into the
    pools at its table's position (a physical block out of bounds, so
    dropped, for a dead slot), then attended by one of two paths, chosen
    by backend and shape alone (`ops.paged_attention.engages`): the
    kernel reads the live blocks through the table where they lie, the
    gather builds every slot's padded view and masks it.  The kernel's
    scalars are planned here, once a program (a part of the slots at a
    time where all of them do not fit scalar memory).  `kv` is the
    pools."""

    def __init__(self, c, pools, tables, qpos, active):
        self.c, self.kv, self.qpos, self.tables = c, dict(pools), qpos, tables
        bs = pools["k"].shape[2]
        self.off = qpos % bs
        phys = tables[jnp.arange(qpos.shape[0]), qpos // bs]
        self.phys = phys if active is None else jnp.where(
            active, phys, pools["k"].shape[1])
        self.plans = None
        if _paged_attention(pools) == "kernel":
            # the slots in as many parts as keep a call's scalars in
            # scalar memory (`slot_parts`: 2 at 384 slots x 768 blocks)
            B = qpos.shape[0]
            n = B // paged.slot_parts(*tables.shape)
            self.cuts = [slice(i, i + n) for i in range(0, B, n)]
            with jax.named_scope("attn"), jax.named_scope("paged"):
                self.plans = [paged.plan(
                    tables[s], qpos[s], None if active is None else active[s],
                    bs) for s in self.cuts]

    def attend(self, kv, l, q, k, v):
        c = self.c
        kv = dict(kv)
        with jax.named_scope("kv_write"):
            for name, x in zip(("k", "v"), (k, v)):
                kv[name] = kv[name].at[l, self.phys, self.off].set(
                    x[:, 0].reshape(x.shape[0], -1).astype(kv[name].dtype))
        with jax.named_scope("paged"):
            if self.plans is not None:
                return jnp.concatenate([paged.paged_attention(
                    q[s], kv["k"], kv["v"], l, plan)
                    for s, plan in zip(self.cuts, self.plans)]), kv
            B, nb = self.tables.shape
            rows = nb * kv["k"].shape[2]
            dense = [kv[name][l][self.tables].reshape(
                B, rows, c.n_kv_heads, c.head_dim).astype(c.dtype)
                for name in ("k", "v")]
            mask = _seen(self.qpos[:, None], jnp.arange(rows)[None], None)
            return _masked_attention(q, *dense, mask), kv


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def _gated_group_norm(c: NemotronHConfig, y, z, w):
    """`RMSNorm_group(y * silu(z)) * w`, float32: the gate BEFORE the
    norm, the mean square over each group's channels."""
    y = y * jax.nn.silu(z)
    g = y.reshape(y.shape[:-1] + (c.ssm_groups, -1))
    g = g * lax.rsqrt(jnp.mean(g * g, -1, keepdims=True) + c.norm_eps)
    return g.reshape(y.shape) * w.astype(_F32)


def ssd_mixer(c: NemotronHConfig, j, p, u, rec, st):
    """u [B, S, D], normed -> (the Mamba-2 mixer's output [B, S, D],
    the states), layer j of `st` going through `rec`."""
    B, S, _ = u.shape
    dt_, C, H = c.dtype, c.d_inner, c.ssm_heads
    G, N = c.ssm_groups, c.ssm_state
    with jax.named_scope("ssd"):
        with jax.named_scope("proj"):
            zxd = jnp.dot(u, p["w_in"].astype(dt_),
                          preferred_element_type=_F32)
            z, xbc = zxd[..., :C], zxd[..., C:C + c.conv_width].astype(dt_)
            dt = zxd[..., C + c.conv_width:]
        with jax.named_scope("conv"):
            y, st = rec.conv(st, j, xbc, p["conv_w"])
            xbc = jax.nn.silu(y + p["conv_b"].astype(dt_))
        with jax.named_scope("state"):
            x = xbc[..., :C].reshape(B, S, H, -1)
            Bm = xbc[..., C:C + G * N].reshape(B, S, G, N)
            Cm = xbc[..., C + G * N:].reshape(B, S, G, N)
            dt = jax.nn.softplus(dt + p["dt_bias"].astype(_F32))
            y, st = rec.recur(st, j, x, dt, -jnp.exp(p["A_log"].astype(_F32)),
                              Bm, Cm)
            y = y + p["D"].astype(_F32)[:, None] * x.astype(_F32)
        with jax.named_scope("norm"):
            y = _gated_group_norm(c, y.reshape(B, S, C), z, p["gate_norm"])
        with jax.named_scope("proj"):
            return y.astype(dt_) @ p["w_out"].astype(dt_), st


def attention_mixer(c: NemotronHConfig, l, p, u, cache, kv):
    """u [B, S, D], normed -> (attention's output [B, S, D], the
    cache's `kv`), the layer's rows going through `cache` at attention
    layer l.  No positions."""
    B, S, _ = u.shape
    dt_, hd = c.dtype, c.head_dim
    with jax.named_scope("attn"):
        q = (u @ p["wq"].astype(dt_)).reshape(B, S, c.n_heads, hd)
        k = (u @ p["wk"].astype(dt_)).reshape(B, S, c.n_kv_heads, hd)
        v = (u @ p["wv"].astype(dt_)).reshape(B, S, c.n_kv_heads, hd)
        o, kv = cache.attend(kv, l, q, k, v)
        return o.reshape(B, S, c.n_heads * hd) @ p["wo"].astype(dt_), kv


def _relu2(h, w_up, w_down, dt_):
    return jnp.square(jax.nn.relu(h @ w_up.astype(dt_))) @ w_down.astype(dt_)


def expert_mixer(c: NemotronHConfig, p, u, live=None, layer=None):
    """u [B, S, D], normed -> (the held routed experts' part plus the
    shared expert's [B, S, D], tokens routed to each held expert);
    `layer`: p's `w_up` and `w_down` are the scan's stacks and this is
    the repeat's index (`models/moe.py::dropless_moe`)."""
    B, S, D = u.shape
    with jax.named_scope("moe"):
        y, sizes = dropless_moe(
            u.reshape(B * S, D), p,
            sigmoid_bias_top_k(c.top_k, c.routed_scaling_factor),
            live=None if live is None else live.reshape(B * S),
            share=(c.expert_rank, c.expert_shards), layer=layer)
        with jax.named_scope("shared"):
            shared = _relu2(u, p["ws_up"], p["ws_down"], c.dtype)
        return y.reshape(B, S, D) + shared, sizes


def _stack(c: NemotronHConfig, params, tokens, cache, rec, st, live=None):
    """Embedding, every layer, final norm: tokens [B, S] -> (normed
    hidden [B, S, D], the states, the cache's `kv`, tokens routed to
    each held expert [n_moe_layers, E held])."""
    block, reps, tail = c.layout

    def layers(kinds, ps, x, st, kv, jm, la, r=None):
        routed = []
        for kind, p in zip(kinds, ps):
            u = rms_norm(x, p["norm"], c.norm_eps)
            if kind == "M":
                y, st = ssd_mixer(c, jm, p, u, rec, st)
                jm += 1
            elif kind == "*":
                y, kv = attention_mixer(c, la, p, u, cache, kv)
                la += 1
            else:
                y, sizes = expert_mixer(c, p, u, live, r)
                routed.append(sizes)
            x = x + y
        return x, st, kv, routed

    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    kv, routed = cache.kv, []
    n_m, n_a = block.count("M"), block.count("*")
    if reps:
        # the experts' banks stay out of what the scan cuts a repeat
        # from: each is read through its stack at the repeat's index
        banks = [{k: v for k, v in p.items() if k in ("w_up", "w_down")}
                 for p in params["blocks"]]
        rest = [{k: v for k, v in p.items() if k not in bank}
                for p, bank in zip(params["blocks"], banks)]

        def repeat(carry, xs):
            x, st, kv = carry
            ps, r = xs
            x, st, kv, sizes = layers(
                block, [dict(p, **bank) for p, bank in zip(ps, banks)],
                x, st, kv, r * n_m, r * n_a, r)
            return (x, st, kv), jnp.stack(sizes) if sizes else None

        (x, st, kv), sizes = lax.scan(
            repeat, (x, st, kv), (rest, jnp.arange(reps)))
        if sizes is not None:
            routed.extend(sizes.reshape((-1,) + sizes.shape[2:]))
    x, st, kv, sizes = layers(tail, params["tail"], x, st, kv, reps * n_m,
                              reps * n_a)
    routed.extend(sizes)
    held = jnp.stack(routed) if routed else jnp.zeros(
        (0, c.n_held_experts), jnp.int32)
    return rms_norm(x, params["norm_f"], c.norm_eps), st, kv, held


def _head(c: NemotronHConfig, params, x):
    with jax.named_scope("lm_head"):
        return jnp.dot(x, params["lm_head"].astype(c.dtype),
                       preferred_element_type=_F32)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: NemotronHConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache, every
    sequence from a zero state."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _, _, _ = _stack(config, params, tokens, _NoCache(qpos),
                        _Sequences(config, S), init_slot_state(config, B))
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def init_paged_pool(config: NemotronHConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    """K and V a token, its K/V heads side by side (256 lanes at 2 heads
    of 128), for the attention layers."""
    c = config
    shape = (c.n_attn_layers, num_blocks, block_size,
             c.n_kv_heads * c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def prefill_paged(params, tokens, start, hist, config: NemotronHConfig,
                  n_real, state):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `state` {leaf: [Lm, ...]} the slot's rows after
    its first `start` tokens.  Padding goes through no expert and
    advances no state (its K/V rows are masked as keys, not skipped)."""
    Pb = tokens.shape[1]
    cache = _History(config, hist, start, Pb)
    x, st, kv, _ = _stack(
        config, params, tokens, cache, _Sequences(config, n_real),
        {k: v[:, None] for k, v in state.items()},
        live=(jnp.arange(Pb) < n_real)[None])
    return x, kv, {k: v[:, 0] for k, v in st.items()}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: NemotronHConfig,
                      active: Optional[jax.Array] = None, state=None):
    """One token a slot against the paged pools and the slots' states:
    tokens [B] at positions [B].  A dead slot writes no row, goes
    through no expert and keeps its state.  Returns (logits [B, V],
    pools, counts, state)."""
    c = config
    B = tokens.shape[0]
    cache = _Paged(c, pools, tables, positions, active)
    rec = _Step(c, state, active)
    x, st, kv, routed = _stack(
        c, params, tokens[:, None], cache, rec, state,
        live=None if active is None else active[:, None])
    n_live = jnp.asarray(B, jnp.int32) if active is None \
        else jnp.sum(active, dtype=jnp.int32)
    counts = {"expert_tokens": routed,
              "experts_touched": jnp.sum(routed > 0, dtype=jnp.int32),
              "ticks": jnp.ones((), jnp.int32), "live_slots": n_live,
              # slot-layers the Pallas step advanced (0: `ssd_step` ran)
              "ssd_live_steps": n_live * (
                  c.n_ssm_layers if rec.plan is not None else 0)}
    return _head(c, params, x[:, 0]), kv, counts, st


def init_counts(config: NemotronHConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts: tokens routed to each
    HELD expert of each expert layer, the distinct ones touched, ticks,
    live slots summed over ticks, and the slot-layers the Pallas step
    advanced."""
    z = jnp.zeros((), jnp.int32)
    return {"expert_tokens": jnp.zeros(
                (config.n_moe_layers, config.n_held_experts), jnp.int32),
            "experts_touched": z, "ticks": z, "live_slots": z,
            "ssd_live_steps": z}


def quantize_int8(params: Dict[str, Any]) -> Dict[str, Any]:
    """Every matmul weight (not the embedding table, a gather, nor the
    taps, the norms, the biases, the decays) rounded per output channel
    to int8 and handed back in its own dtype: the benchmark's control.
    A leaf with leading axes (the repeats, the experts) is rounded a
    matrix at a time (`lax.map`), so that the temporaries are one
    matrix's; `w_up` of the experts lies [F, D], its output channels
    down the rows."""
    def rounded(w, axis):
        w32 = w.astype(_F32)
        s = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return (jnp.round(w32 / s) * s).astype(w.dtype)

    def leaf(path, w):
        name = path[-1].key
        if not (name.startswith("w") or name in ("router", "lm_head")):
            return w                # `conv_w` among them
        stacked = path[0].key == "blocks"
        by_rows = name == "w_up" and w.ndim - stacked == 3
        fn = lambda m: rounded(m, 1 if by_rows else 0)
        for _ in range(w.ndim - 2):
            fn = (lambda inner: lambda m: lax.map(inner, m))(fn)
        return fn(w)

    return jax.tree_util.tree_map_with_path(leaf, params)


def _paged_attention(pools) -> str:
    return "kernel" if paged.engages(pools["k"]) else "gather"


_SERVING = ServingFns(
    name="Mamba-2 + relu² experts beside a shared one + GQA without "
         "positions, one mixer a layer (models/nemotron_h.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=lm_head_weight, init_counts=init_counts,
    init_slot_state=init_slot_state, quantize_int8=quantize_int8,
    paged_attention=_paged_attention, grouped_matmul=serving_grouped_path)
