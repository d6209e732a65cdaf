"""Decoder-hybrid-decoder: Mamba layers beside DIFFERENTIAL attention in a
short window, then ONE full-attention layer whose K and V every later
attention layer reads, with gated memory units between them
(`model_type` `phi4flash`; arXiv:2507.06607).

A block is `x = x + Mixer(LN(x))`, `x = x + W2 (silu(g) * h)` with
`[g | h] = LN(x) W1`; `LN` is a LayerNorm with weight AND bias; after
the last block one more, then a head TIED to the embedding table.  No
positions anywhere: the recurrence carries order.  Layers go in PAIRS:

- the self-decoder, `n_layers / 4` pairs (Mamba, window attention):
  layers 0 .. n_layers / 2 - 1;
- the middle pair: the last Mamba (layer `n_layers / 2`), which also
  hands on its scan output `m`, BEFORE its `silu(z)` gate, as the
  memory; and the one FULL attention layer, whose K and V rows are the
  cache of everything after it;
- the cross-decoder's `n_layers / 4 - 1` pairs (gated memory unit,
  cross attention): a GMU is `(m * silu(u Wg)) Wo` with `m` of the SAME
  token; a cross layer projects a query alone and attends over the
  middle pair's K and V.

The pairs of a kind are alike, so their weights are STACKED and each
kind is one `lax.scan` (`lam0` of a layer's depth is data): 32 layers
compile as three pairs' worth.

- **Mamba** (`ssm_mixer`): `[xs | z] = u W_in`; a causal depthwise
  convolution of `d_conv` taps with bias (`ops/short_conv.py`), SiLU;
  `[dt | B | C] = xc W_x`; `Delta = softplus(dt W_dt + b_dt)`; the
  selective scan of `ops/selective_scan.py` under `A = -exp(A_log)`;
  `+ Dskip * xc`; gate; `W_out`.  What it keeps for a sequence is of a
  fixed size whatever the length: `h` `[d_state, d_inner / 128, 128]`
  in `state_dtype` (channels on the lanes) and the last `d_conv - 1`
  rows of `xs` side by side in the lanes of one, held by the engine by
  SLOT (`init_slot_state`; models/serving.py).
- **Differential attention** (`_lay_queries`, `_differ`): consecutive
  heads pair; query pair j reads K/V pair j // 2, whose row is `k1 ‖ k2`
  (128 lanes) and `v1 ‖ v2`; `a1 - lam a2` of the two softmaxes, a
  sub-norm over the 128 and `(1 - lam0)`.  Laid as grouped-query
  attention with heads of 128: `q1` in the lanes of `k1` of a zero row,
  `q2` in those of `k2`, so that `q_row . k_row` is `q1 . k1` or
  `q2 . k2`, scaled by `head_dim ** -0.5`; every caller of attention
  here (`models/window_moe.py`'s masked and blockwise forms,
  `ops/paged_attention.py`'s kernel in both its forms) then runs as it
  stands.
- **Two kinds of pool AND a state by slot** (models/serving.py).  A row
  a token holds its K (or V) pairs side by side, 1280 lanes.  The
  window layers' `k_w`, `v_w` `[n_layers / 4, NBw, bs, 1280]` are
  walked through a ring (`models/window_moe.py::WINDOW_LEAVES`); the
  full kind `k`, `v` has ONE layer, `[1, NB, bs, 1280]`, that the
  middle pair writes and `n_layers / 4` layers read.
- **An insert's second half runs for one row.**  The self-decoder, the
  middle Mamba and the full layer's K and V are computed for every row
  of the chunk; the full layer's attention and everything after it for
  the LAST REAL row alone (`_History.narrow`): nothing else of them
  feeds a served token, a cache or a state.  `prefill_paged` hands back
  `[1, 1, D]` (models/serving.py says what the engine makes of it).
- One definition of a layer over three situations: no cache
  (`forward`), one sequence's call of a bucketed / chunked prefill
  (`prefill_paged`), one token a slot (`decode_step_paged`).

Every size comes from `SambaYConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import embed_lookup, rms_norm
from ray_tpu.models.serving import ServingFns
from ray_tpu.models.window_moe import (
    WINDOW_LEAVES, _masked_attention, _seen, blockwise_attention,
    piece_walk, walk_tiles,
)
from ray_tpu.ops import kda
from ray_tpu.ops import paged_attention as paged
from ray_tpu.ops import selective_scan as ssm
from ray_tpu.ops import short_conv

_F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class SambaYConfig:
    vocab_size: int = 200064
    dim: int = 2560
    n_layers: int = 32              # a multiple of 4, at least 8
    n_heads: int = 40
    n_kv_heads: int = 20
    window: int = 512               # keys a window layer's query sees
    hidden_dim: int = 10240
    d_state: int = 16
    d_conv: int = 4
    expand: int = 2
    max_seq_len: int = 262144
    norm_eps: float = 1e-5
    # keys a step of the prefill's blockwise attention takes
    prefill_key_block: int = 1024
    dtype: Any = jnp.bfloat16   # activation/matmul dtype, and the tail's
    param_dtype: Any = jnp.bfloat16
    state_dtype: Any = jnp.float32  # the recurrent state between tokens

    def __post_init__(self):
        if self.n_layers % 4 or self.n_layers < 8:
            raise ValueError("n_layers: a multiple of 4, at least 8")

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @property
    def d_inner(self) -> int:
        return self.expand * self.dim

    @property
    def dt_rank(self) -> int:
        return -(-self.dim // 16)

    @property
    def n_self_pairs(self) -> int:
        return self.n_layers // 4

    @property
    def n_cross_pairs(self) -> int:
        return self.n_layers // 4 - 1

    @property
    def n_ssm_layers(self) -> int:
        return self.n_self_pairs + 1

    @property
    def n_kv_pairs(self) -> int:
        return self.n_kv_heads // 2

    @property
    def kv_width(self) -> int:      # lanes of a token's K (or V) row
        return self.n_kv_heads * self.head_dim

    @property
    def scale(self) -> float:
        return self.head_dim ** -0.5

    @staticmethod
    def tiny(**overrides) -> "SambaYConfig":
        """Test-size config: two self pairs, the middle pair, one cross
        pair; `d_inner` one lane row."""
        return SambaYConfig(**{**dict(
            vocab_size=512, dim=64, n_layers=8, n_heads=8, n_kv_heads=4,
            window=8, hidden_dim=128, d_state=4, max_seq_len=128,
            prefill_key_block=8), **overrides})

    def serving(self):
        return _SERVING


def lam0(depth):
    """The differential attention's `lambda_init` at a layer's index."""
    return 0.8 - 0.6 * jnp.exp(-0.3 * jnp.asarray(depth, _F32))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: SambaYConfig, key: jax.Array,
                std: float = 0.02) -> Dict[str, Any]:
    """The family's draws: normal(0, std) matrices and taps, `A_log` =
    log(1 .. d_state) a channel, `b_dt` = softplus^-1(dt) with dt
    log-uniform in [1e-3, 1e-1], `Dskip` ones, the four lambda vectors
    N(0, 0.1), norms ones and zeros, biases zeros.  A kind's layers are
    stacked on a leading axis."""
    c = config
    dt = c.param_dtype
    D, C, N, F, hd = c.dim, c.d_inner, c.d_state, c.hidden_dim, c.head_dim

    def draw(key, *shape):
        return jax.nn.initializers.normal(std)(key, shape, dt)

    def block(key):
        k1, k2 = jax.random.split(key)
        return {"ln_in_w": jnp.ones((D,), dt), "ln_in_b": jnp.zeros((D,), dt),
                "ln_post_w": jnp.ones((D,), dt),
                "ln_post_b": jnp.zeros((D,), dt),
                "w1": draw(k1, D, 2 * F), "w2": draw(k2, F, D)}

    def mamba(key):
        ks = jax.random.split(key, 7)
        step = jnp.exp(jax.random.uniform(
            ks[5], (C,), _F32, math.log(1e-3), math.log(1e-1)))
        return dict(
            block(ks[6]), w_in=draw(ks[0], D, 2 * C),
            conv_w=draw(ks[1], c.d_conv, C), conv_b=jnp.zeros((C,), dt),
            w_x=draw(ks[2], C, c.dt_rank + 2 * N),
            w_dt=draw(ks[3], c.dt_rank, C),
            b_dt=step + jnp.log(-jnp.expm1(-step)),
            A_log=jnp.broadcast_to(jnp.log(jnp.arange(
                1, N + 1, dtype=_F32))[:, None], (N, C)),
            Dskip=jnp.ones((C,), _F32), w_out=draw(ks[4], C, D))

    def attention(key, cross):
        ks = jax.random.split(key, 7)
        qkv = D if cross else D + 2 * c.kv_width
        return dict(
            block(ks[6]), w_qkv=draw(ks[0], D, qkv),
            b_qkv=jnp.zeros((qkv,), dt),
            **{n: 0.1 * jax.random.normal(k, (hd,), _F32)
               for n, k in zip(("lq1", "lk1", "lq2", "lk2"), ks[1:5])},
            sub_w=jnp.ones((2 * hd,), dt), w_o=draw(ks[5], D, D),
            b_o=jnp.zeros((D,), dt))

    def gmu(key):
        k1, k2, k3 = jax.random.split(key, 3)
        return dict(block(k3), w_g=draw(k1, D, C), w_o=draw(k2, C, D))

    def pair(key, first, second):
        ka, kb = jax.random.split(key)
        return {"a": first(ka), "b": second(kb)}

    k_embed, k_self, k_mid, k_cross = jax.random.split(key, 4)
    window = lambda k: attention(k, False)
    return {
        "embed": draw(k_embed, c.vocab_size, D),
        "self": jax.vmap(lambda k: pair(k, mamba, window))(
            jax.random.split(k_self, c.n_self_pairs)),
        "mid": pair(k_mid, mamba, window),
        "cross": jax.vmap(lambda k: pair(
            k, gmu, lambda k: attention(k, True)))(
                jax.random.split(k_cross, c.n_cross_pairs)),
        "norm_f_w": jnp.ones((D,), dt), "norm_f_b": jnp.zeros((D,), dt)}


def lm_head_weight(params: Dict[str, Any], config: SambaYConfig):
    """[D, V]: the embedding table turned round inside the program that
    multiplies by it (no transposed copy is held)."""
    return params["embed"].T.astype(config.dtype)


def init_slot_state(config: SambaYConfig, num_slots: int
                    ) -> Dict[str, jax.Array]:
    """A row a slot a Mamba layer (models/serving.py): the scan's state
    with its channels in whole lane rows (`ops.selective_scan.fold`:
    `[16, 40, 128]` float32 at 5120 channels, 327,680 B a slot a layer
    with no padded lane) and the convolution's tail, its `d_conv - 1`
    rows side by side in the lanes of one (`ops/short_conv.py`), zeros."""
    c = config
    return {
        "h": jnp.zeros((c.n_ssm_layers, num_slots, c.d_state,
                        c.d_inner // ssm.LANES, ssm.LANES), c.state_dtype),
        "tail": jnp.zeros((c.n_ssm_layers, num_slots,
                           (c.d_conv - 1) * c.d_inner), c.dtype)}


# ---------------------------------------------------------------------------
# The Mamba layers' states: where they come from and where they go.  The
# whole tree {h, tail} rides through the layer scans; layer j of it is
# read and written at a traced index.
# ---------------------------------------------------------------------------

class _Sequences:
    """Whole (padded) sequences, each from the state handed in {leaf:
    [Ls, B, ...]}, over their first `n_real` rows; the tree then holds
    the states after them."""

    def __init__(self, n_real):
        self.n_real = n_real

    def conv(self, st, j, xs, w):
        y, tail = short_conv.short_conv(
            xs, w, short_conv.rows(st["tail"][j], w), self.n_real)
        return y, dict(st, tail=st["tail"].at[j].set(
            short_conv.flat(tail).astype(st["tail"].dtype)))

    def recur(self, st, j, delta, x, b, c, a):
        h0 = st["h"][j]
        outs = [ssm.ssm_scan(h0[i], ssm.fold(delta[i]), ssm.fold(x[i]),
                             b[i], c[i], a, self.n_real)
                for i in range(h0.shape[0])]
        h = jnp.stack([o[1] for o in outs]).astype(h0.dtype)
        return ssm.unfold(jnp.stack([o[0] for o in outs])), \
            dict(st, h=st["h"].at[j].set(h))


class _Step:
    """One token a slot; a dead slot keeps its rows.  The scan's state
    goes by ONE of two paths, chosen by backend and shape alone
    (`ops.selective_scan.engages`): the Pallas step over the whole
    stack, which reads and writes each LIVE slot's rows once where they
    lie (`plan`: the live slots, made here once a tick for all its
    layers), or `ssm_step` on the layer's rows of all slots and a
    `where`.  The tail's layer of the stack is shifted where it lies
    by `short_conv.step_in_place`, which chooses its form the same way."""

    def __init__(self, state, active):
        self.active = active
        h = state["h"]
        self.plan = kda.live_plan(active, h.shape[1]) \
            if ssm.engages(h) else None

    def _keep(self, new, old):
        if self.active is None:
            return new.astype(old.dtype)
        live = self.active.reshape((-1,) + (1,) * (new.ndim - 1))
        return jnp.where(live, new.astype(old.dtype), old)

    def conv(self, st, j, xs, w):
        y, tails = short_conv.step_in_place(st["tail"], j, xs[:, 0], w,
                                            self.active)
        return y[:, None], dict(st, tail=tails)

    def recur(self, st, j, delta, x, b, c, a):
        now = (ssm.fold(delta[:, 0]), ssm.fold(x[:, 0]), b[:, 0], c[:, 0], a)
        if self.plan is not None:
            y, h = ssm.ssm_step_live(st["h"], j, *now, self.plan)
            return ssm.unfold(y)[:, None], dict(st, h=h)
        old = st["h"][j]
        y, h = ssm.ssm_step(old, *now)
        return ssm.unfold(y)[:, None], dict(
            st, h=st["h"].at[j].set(self._keep(h, old)))


# ---------------------------------------------------------------------------
# The attention layers' caches.  q [B, S, H, 2 hd] (`_lay_queries`), k
# and v [B, S, pairs, 2 hd]; `kv` is what rides through the layer scans.
#   window(kv, l, q, k, v) -> (a, kv)    window layer l
#   shared(kv, q, k, v)    -> (a, kv)    the one full layer: rows kept
#   cross(kv, q)           -> a          a cross layer over those rows
#   narrow(*xs)                          the rows the second half runs for
# ---------------------------------------------------------------------------

class _NoCache:
    """The sequences' own rows are their keys (scoring, tests)."""

    def __init__(self, c, qpos):
        self.c, self.qpos, self.kv = c, qpos, {}

    def narrow(self, *xs):
        return xs

    def window(self, kv, l, q, k, v):
        mask = _seen(self.qpos, self.qpos, self.c.window)
        return _masked_attention(q, k, v, mask, self.c.scale), kv

    def shared(self, kv, q, k, v):
        self.k, self.v = k, v
        return self.cross(kv, q), kv

    def cross(self, kv, q):
        mask = _seen(self.qpos, self.qpos, None)
        return _masked_attention(q, self.k, self.v, mask, self.c.scale)


class _History:
    """ONE sequence with its gathered history (models/serving.py): the
    full kind's [1, S_pad, W] by position, the window kind's [Lw, R, W]
    a RING in which position t lies at row t % R.  The chunk sits at
    `start`..; `kv` holds the window layers' new rows for the engine to
    scatter, and this keeps the full layer's.  Both kinds attend through
    `window_moe.blockwise_attention`: a window layer's whole piece by
    the kernel's tiles where it engages (a block of keys at a time
    elsewhere, the published window of 512 among it:
    `ops.attention.PREFILL_MIN_K`), the full layer and the cross layers
    for the last real row alone, which is the loop everywhere."""

    def __init__(self, c, hist, start, Pb, n_real):
        self.c, self.hist, self.start, self.n_real = c, hist, start, n_real
        self.qpos = start + jnp.arange(Pb)
        self.kv = {name: jnp.zeros(
            (hist[name].shape[0], Pb, c.kv_width), hist[name].dtype)
            for name in WINDOW_LEAVES}

    def _heads(self, x):
        return x.reshape(x.shape[0], self.c.n_kv_pairs, -1).astype(
            self.c.dtype)

    def narrow(self, *xs):
        return tuple(lax.dynamic_slice_in_dim(x, self.n_real - 1, 1, 1)
                     for x in xs)

    def window(self, kv, l, q, k, v):
        c, W, start = self.c, self.c.window, self.start
        Pb = k.shape[1]
        kv = dict(kv)
        keys = []
        for name, x in zip(WINDOW_LEAVES, (k, v)):
            ring = self.hist[name]
            x = x[0].reshape(Pb, -1).astype(ring.dtype)
            kv[name] = kv[name].at[l].set(x)
            # the `window` rows before the chunk, out of the ring (a
            # position before the sequence's first is masked), then the
            # chunk's own
            before = (start - W + jnp.arange(W)) % ring.shape[1]
            keys.append(self._heads(jnp.concatenate([ring[l][before], x])))
        kpos0, lo, hi = piece_walk("window", start, Pb, W + Pb, W,
                                   c.prefill_key_block, most=jnp.maximum)
        out = blockwise_attention(
            q[0], *keys, self.qpos, kpos0, lo, hi, W, c.prefill_key_block,
            c.scale)
        return out[None], kv

    def shared(self, kv, q, k, v):
        self.rows, self.keys = {}, []
        for name, x in zip(("k", "v"), (k, v)):
            hist = self.hist[name]
            x = x[0].reshape(x.shape[1], -1).astype(hist.dtype)
            self.rows[name] = x[None]
            self.keys.append(self._heads(lax.dynamic_update_slice(
                hist[0], x, (self.start, 0))))
        return self.cross(kv, q), kv

    def cross(self, kv, q):
        kb = self.c.prefill_key_block
        S = self.keys[0].shape[0]
        last = self.start + self.n_real - 1
        out = blockwise_attention(
            q[0], *self.keys, last[None], 0, 0,
            last // math.gcd(S, kb) + 1, None, kb, self.c.scale)
        return out[None]


class _Paged:
    """One new row a sequence at positions `qpos` [B], written into its
    kind's pool at its table's position (a physical block out of bounds,
    so dropped, for a dead slot): the full kind's table by `pos // bs`,
    the window kind's ring by `(pos // bs) % ring`.  Then attended by
    one of two paths, chosen by backend and shape alone
    (`ops.paged_attention.engages`): the kernel reads the live blocks
    through the table where they lie (its window form for the ring),
    the gather builds every slot's padded view and masks it.  The
    kernel's scalars are planned here, once a kind a program; layer 0
    of the full kind is read by the full layer and every cross layer
    under the one plan.  `kv` is the pools."""

    def __init__(self, c, pools, tables, qpos, active):
        self.c, self.kv, self.qpos, self.tables = c, dict(pools), qpos, tables
        bs = pools["k"].shape[2]
        seq = jnp.arange(qpos.shape[0])
        self.off = qpos % bs
        ring = tables["window"].shape[1]
        at = {"full": qpos // bs, "window": (qpos // bs) % ring}
        self.phys = {}
        for kind, leaf in (("full", "k"), ("window", "k_w")):
            phys = tables[kind][seq, at[kind]]
            if active is not None:
                phys = jnp.where(active, phys, pools[leaf].shape[1])
            self.phys[kind] = phys
        self.plans = None
        if _paged_attention(pools) == "kernel":
            with jax.named_scope("attn"):
                with jax.named_scope("paged_shared"):
                    full = paged.plan(tables["full"], qpos, active, bs)
                with jax.named_scope("paged_window"):
                    self.plans = {"full": full, "window": paged.plan(
                        tables["window"], qpos, active, bs,
                        window=c.window)}

    def narrow(self, *xs):
        return xs

    def _write(self, kv, kind, names, l, k, v):
        kv = dict(kv)
        with jax.named_scope("kv_write"):
            for name, x in zip(names, (k, v)):
                kv[name] = kv[name].at[l, self.phys[kind], self.off].set(
                    x[:, 0].reshape(x.shape[0], -1).astype(kv[name].dtype))
        return kv

    def _attend(self, kv, kind, names, l, q):
        c = self.c
        window = c.window if kind == "window" else None
        k_pool, v_pool = (kv[name] for name in names)
        with jax.named_scope("paged_window" if window else "paged_shared"):
            if self.plans is not None:
                return paged.paged_attention(
                    q, k_pool, v_pool, l, self.plans[kind], window=window,
                    scale=c.scale)
            table = self.tables[kind]
            B, nb = table.shape
            rows = nb * k_pool.shape[2]
            dense = [pool[l][table].reshape(B, rows, c.n_kv_pairs, -1)
                     .astype(c.dtype) for pool in (k_pool, v_pool)]
            pos = self.qpos[:, None]
            kpos = jnp.arange(rows)[None]
            if window:
                # row r of the ring holds the last position <= pos that
                # is r modulo the ring's rows
                kpos = pos - (pos - kpos) % rows
            return _masked_attention(q, *dense, _seen(pos, kpos, window),
                                     c.scale)

    def window(self, kv, l, q, k, v):
        kv = self._write(kv, "window", WINDOW_LEAVES, l, k, v)
        return self._attend(kv, "window", WINDOW_LEAVES, l, q), kv

    def shared(self, kv, q, k, v):
        kv = self._write(kv, "full", ("k", "v"), 0, k, v)
        return self.cross(kv, q), kv

    def cross(self, kv, q):
        return self._attend(kv, "full", ("k", "v"), 0, q)


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def layer_norm(x, w, b, eps):
    x32 = x.astype(_F32)
    mu = jnp.mean(x32, -1, keepdims=True)
    var = jnp.mean(jnp.square(x32 - mu), -1, keepdims=True)
    y = (x32 - mu) * lax.rsqrt(var + eps)
    return (y * w.astype(_F32) + b.astype(_F32)).astype(x.dtype)


def _ln_in(c, p, x):
    return layer_norm(x, p["ln_in_w"], p["ln_in_b"], c.norm_eps)


def feed_forward(c: SambaYConfig, p, x):
    """x -> x + W2 (silu(g) * h), [g | h] = LN_post(x) W1."""
    dt = c.dtype
    with jax.named_scope("mlp"):
        u = layer_norm(x, p["ln_post_w"], p["ln_post_b"], c.norm_eps)
        g, h = jnp.split(u @ p["w1"].astype(dt), 2, axis=-1)
        return x + (jax.nn.silu(g) * h) @ p["w2"].astype(dt)


def ssm_mixer(c, j, p, u, rec, st, normed=lambda p, dbc: dbc):
    """u [B, S, D], normed -> (the Mamba mixer's output [B, S, D], its
    scan output before the gate, the states), layer j of `st` through
    `rec`; `normed(p, [dt | B | C])`: models/jamba.py's three norms."""
    dt, C, N, R = c.dtype, c.d_inner, c.d_state, c.dt_rank
    with jax.named_scope("ssm"):
        with jax.named_scope("proj"):
            xs, z = jnp.split(u @ p["w_in"].astype(dt), 2, axis=-1)
        with jax.named_scope("conv"):
            y, st = rec.conv(st, j, xs, p["conv_w"])
            xc = jax.nn.silu(y + p["conv_b"].astype(dt))
        with jax.named_scope("proj"):
            dbc = normed(p, jnp.dot(xc, p["w_x"].astype(dt),
                                    preferred_element_type=_F32))
            delta = jax.nn.softplus(jnp.dot(
                dbc[..., :R].astype(dt), p["w_dt"].astype(dt),
                preferred_element_type=_F32) + p["b_dt"].astype(_F32))
        with jax.named_scope("state"):
            a = ssm.fold(-jnp.exp(p["A_log"].astype(_F32)))
            y, st = rec.recur(st, j, delta, xc, dbc[..., R:R + N],
                              dbc[..., R + N:], a)
            y = y + p["Dskip"].astype(_F32) * xc.astype(_F32)
        with jax.named_scope("out"):
            out = (y * jax.nn.silu(z.astype(_F32))).astype(dt) \
                @ p["w_out"].astype(dt)
        return out, y.astype(dt), st


def gmu(c: SambaYConfig, p, u, m):
    """The gated memory unit: the memory of the SAME token, gated by
    this layer's input."""
    dt = c.dtype
    with jax.named_scope("gmu"):
        return (m * jax.nn.silu(u @ p["w_g"].astype(dt))) \
            @ p["w_o"].astype(dt)


def _lay_queries(c: SambaYConfig, q):
    """[B, S, D] -> [B, S, H, 2 hd]: head 2j (`q1` of pair j) in the
    first hd lanes of a zero row, head 2j + 1 (`q2`) in the last."""
    B, S, _ = q.shape
    q = q.reshape(B, S, c.n_heads // 2, 2, c.head_dim)
    return jnp.einsum("bsjtd,tu->bsjtud", q, jnp.eye(2, dtype=q.dtype)
                      ).reshape(B, S, c.n_heads, 2 * c.head_dim)


def _differ(c: SambaYConfig, p, a, depth):
    """a [B, S, H, 2 hd], the two softmaxes' value products of every
    query pair -> `(1 - lam0) RMSNorm(a1 - lam a2)` [B, S, D]."""
    B, S = a.shape[:2]
    with jax.named_scope("diff"):
        f = lambda n: p[n].astype(_F32)
        base = lam0(depth)
        lam = jnp.exp(jnp.sum(f("lq1") * f("lk1"))) \
            - jnp.exp(jnp.sum(f("lq2") * f("lk2"))) + base
        a = a.astype(_F32).reshape(B, S, c.n_heads // 2, 2, -1)
        d = rms_norm(a[..., 0, :] - lam * a[..., 1, :],
                     p["sub_w"].astype(_F32), c.norm_eps) * (1.0 - base)
        return d.astype(c.dtype).reshape(B, S, c.dim)


def _kv_pairs(c: SambaYConfig, kv):
    B, S, _ = kv.shape
    k, v = jnp.split(kv, 2, axis=-1)
    shape = (B, S, c.n_kv_pairs, 2 * c.head_dim)
    return k.reshape(shape), v.reshape(shape)


def _attn_out(c: SambaYConfig, p, a, depth):
    dt = c.dtype
    return _differ(c, p, a, depth) @ p["w_o"].astype(dt) \
        + p["b_o"].astype(dt)


def _stack(c: SambaYConfig, params, tokens, cache, rec, st):
    """Embedding, every layer, final norm: tokens [B, S] -> (normed
    hidden [B, S', D], S' the rows `cache.narrow` keeps; the states;
    the cache's `kv`)."""
    dt, D = c.dtype, c.dim
    ns, nc = c.n_self_pairs, c.n_cross_pairs
    x = embed_lookup(params["embed"].astype(dt), tokens)

    def mamba_layer(p, j, x, st):
        out, m, st = ssm_mixer(c, j, p, _ln_in(c, p, x), rec, st)
        return feed_forward(c, p, x + out), m, st

    def self_pair(carry, xs):
        x, st, kv = carry
        p, i = xs
        x, _, st = mamba_layer(p["a"], i, x, st)
        p = p["b"]
        with jax.named_scope("attn"):
            qkv = _ln_in(c, p, x) @ p["w_qkv"].astype(dt) \
                + p["b_qkv"].astype(dt)
            a, kv = cache.window(kv, i, _lay_queries(c, qkv[..., :D]),
                                 *_kv_pairs(c, qkv[..., D:]))
            x = x + _attn_out(c, p, a, 2 * i + 1)
        return (feed_forward(c, p, x), st, kv), None

    with jax.named_scope("self_decoder"):
        (x, st, kv), _ = lax.scan(
            self_pair, (x, st, cache.kv), (params["self"], jnp.arange(ns)))

    with jax.named_scope("cross_decoder"):
        p = params["mid"]
        x, m, st = mamba_layer(p["a"], ns, x, st)
        p = p["b"]
        with jax.named_scope("attn"):
            u = _ln_in(c, p, x)
            w, b = p["w_qkv"].astype(dt), p["b_qkv"].astype(dt)
            k, v = _kv_pairs(c, u @ w[:, D:] + b[D:])
            # what the rest of the stack runs for: every row, or an
            # insert's last real one
            x, u, m = cache.narrow(x, u, m)
            a, kv = cache.shared(kv, _lay_queries(c, u @ w[:, :D] + b[:D]),
                                 k, v)
            x = x + _attn_out(c, p, a, 2 * ns + 1)
        x = feed_forward(c, p, x)

        def cross_pair(x, xs):
            p, i = xs
            pa, pb = p["a"], p["b"]
            x = feed_forward(c, pa, x + gmu(c, pa, _ln_in(c, pa, x), m))
            with jax.named_scope("attn"):
                q = _ln_in(c, pb, x) @ pb["w_qkv"].astype(dt) \
                    + pb["b_qkv"].astype(dt)
                a = cache.cross(kv, _lay_queries(c, q))
                x = x + _attn_out(c, pb, a, 2 * (ns + i) + 3)
            return feed_forward(c, pb, x), None

        x, _ = lax.scan(cross_pair, x, (params["cross"], jnp.arange(nc)))
    return layer_norm(x, params["norm_f_w"], params["norm_f_b"],
                      c.norm_eps), st, kv


def _head(c: SambaYConfig, params, x):
    """Normed hidden [..., D] -> logits [..., V] float32, by the
    embedding table as it lies [V, D]."""
    with jax.named_scope("lm_head"):
        return lax.dot_general(
            x, params["embed"].astype(c.dtype),
            (((x.ndim - 1,), (1,)), ((), ())),
            preferred_element_type=_F32)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: SambaYConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32; no cache, every
    sequence from a zero state."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _, _ = _stack(config, params, tokens, _NoCache(config, qpos),
                     _Sequences(S), init_slot_state(config, B))
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def window_kind(config: SambaYConfig) -> Tuple[int, Tuple[str, ...]]:
    """(keys a window layer's query sees, the pool leaves of that kind)."""
    return config.window, WINDOW_LEAVES


def init_paged_pool(config: SambaYConfig, num_blocks: int, block_size: int,
                    window_blocks: Optional[int] = None
                    ) -> Dict[str, jax.Array]:
    """K and V a token, its pairs side by side: `num_blocks` blocks of
    ONE layer for the full kind, `window_blocks` of every window layer's
    for the window kind."""
    c = config
    row = (block_size, c.kv_width)
    full = (1, num_blocks) + row
    ring = (c.n_self_pairs, window_blocks or num_blocks) + row
    return {"k": jnp.zeros(full, c.dtype), "v": jnp.zeros(full, c.dtype),
            "k_w": jnp.zeros(ring, c.dtype), "v_w": jnp.zeros(ring, c.dtype)}


def prefill_paged(params, tokens, start, hist, config: SambaYConfig,
                  n_real, state):
    """Suffix prefill of ONE sequence: tokens [1, Pb] at start.., the
    first `n_real` real; `hist` the gathered history, the window kind's
    as its ring; `state` {leaf: [Ls, ...]} the slot's rows after its
    first `start` tokens.  Returns the normed hidden of the LAST REAL
    row alone, [1, 1, D] (the module's docstring), every leaf's new
    rows, and the state after the last real row: padding advances no
    state (its K/V rows are masked as keys, not skipped)."""
    cache = _History(config, hist, start, tokens.shape[1], n_real)
    x, st, kv = _stack(config, params, tokens, cache, _Sequences(n_real),
                       {k: v[:, None] for k, v in state.items()})
    return x, dict(kv, **cache.rows), {k: v[:, 0] for k, v in st.items()}


def decode_step_paged(params, pools, tables, tokens, positions,
                      config: SambaYConfig,
                      active: Optional[jax.Array] = None, state=None):
    """One token a slot against both kinds of pool and the slots'
    states: tokens [B] at positions [B], `tables` {"full": [B, nb],
    "window": [B, ring]}.  A dead slot writes no row and keeps its
    state.  Returns (logits [B, V], pools, counts, state)."""
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
        # rows of the one full layer read, by it and by every cross layer
        "shared_kv_rows_read": (c.n_cross_pairs + 1) * jnp.sum(
            jnp.where(live, positions + 1, 0).astype(_F32))}
    return _head(c, params, x[:, 0]), kv, counts, st


def init_counts(config: SambaYConfig) -> Dict[str, jax.Array]:
    """Zeros of what `decode_step_paged` counts: ticks, live slots
    summed over ticks, the slot-layers the Pallas step advanced, and the
    rows of the shared layer read (float32: a run reads more than 2^31
    of them)."""
    z = jnp.zeros((), jnp.int32)
    return {"ticks": z, "live_slots": z, "ssm_live_steps": z,
            "shared_kv_rows_read": jnp.zeros((), _F32)}


def quantize_int8(params: Dict[str, Any]) -> Dict[str, Any]:
    """Every matmul weight (not the tied table, the taps, the norms, the
    biases, the decays, the lambdas) rounded per output channel to int8
    and handed back in its own dtype: the benchmark's control.  A
    stacked leaf is rounded a layer at a time (`lax.map`), so that the
    temporaries are one layer's."""
    def rounded(w):
        w32 = w.astype(_F32)
        s = jnp.max(jnp.abs(w32), axis=0, keepdims=True) / 127.0
        s = jnp.where(s == 0, 1.0, s)
        return (jnp.round(w32 / s) * s).astype(w.dtype)

    def leaf(path, w):
        name = path[-1].key
        if not name.startswith("w") or name == "conv_w":
            return w
        return rounded(w) if w.ndim == 2 else lax.map(rounded, w)

    return jax.tree_util.tree_map_with_path(leaf, params)


def _paged_attention(pools) -> str:
    both = paged.engages(pools["k"]) and paged.engages(pools["k_w"])
    return "kernel" if both else "gather"


def insert_attention(config: SambaYConfig, start: int, bucket: int,
                     max_seq_len: int):
    """`window_moe.walk_tiles` of one piece through the window layers
    (heads laid as pairs of 128 lanes); the full layer and the cross
    layers attend for one row, which is the loop's."""
    c = config
    return walk_tiles({"window": c.n_self_pairs}, start, bucket,
                      max_seq_len, c.window, c.prefill_key_block,
                      2 * c.head_dim)


_SERVING = ServingFns(
    name="Mamba + differential window attention, one shared full layer, "
         "gated memory units (models/sambay.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=decode_step_paged,
    head_weight=lm_head_weight, init_counts=init_counts,
    init_slot_state=init_slot_state, window_kind=window_kind,
    quantize_int8=quantize_int8, paged_attention=_paged_attention,
    insert_attention=insert_attention)
