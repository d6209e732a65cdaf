"""Block-diffusion decoder with softmax-routed experts (`model_type`
`sdar_moe`): the pre-norm GQA + QK-norm + routed SwiGLU block of the
Qwen3-MoE lineage, generating by DIFFUSION OVER BLOCKS.

    h = x + Wo Attn(q, k, v)      q = rope(rms(Wq n1(x)) a head), k alike
    y = h + sum_{e in top-k(p)} p_e / sum_{top-k} p . SwiGLU_e(n2(h)),
        p = softmax_f32(Wr n2(h))

- **The mask is block-causal**, in the prompt and in generation alike:
  with L = `block_length` the key at j is visible to the query at i iff
  `j // L <= i // L`, i.e. `j <= i | (L - 1)` (`_block_end`: L is a
  power of two).  Rotary positions are absolute.  The logits are NOT
  shifted: those at position i predict the token AT i.
- **Generation** (`serve/llm/engine.py` drives it; models/serving.py
  `BlockFns`): the sequence is cut into blocks of L at absolute
  positions.  A block starts as mask tokens (`mask_token_id`; the first
  one opens with the prompt's trailing `P mod L` tokens fixed).  ONE
  forward over the block's L rows (`denoise_paged`) writes their K/V
  rows into the pool at the block's positions and attends, every row
  seeing every key up to the block's last, and hands back the normed
  hidden rows; the engine multiplies those of them that still hold a
  masked position by the head, fixes positions by the rule or, where
  none is masked, takes the rows just written as final and moves on.
  A denoising step's rows are provisional and the next forward
  overwrites them; no query reads past its own block, so this equals
  the published loop's `store_kv=False`.
- **One kind of pool**, a row a token holding its 4 KV heads side by
  side (`[L, NB, bs, kvH hd]`, `ops/paged_attention.py` "Few KV heads"),
  read by the paged kernel at Q = L queries a sequence, all of them at
  the block's last position.  At 4 queries of 8 heads a KV head the
  call walks its KV groups (`paged.walks_groups`): a slot's queries are
  L x H rows of head_dim lanes (`paged.query_bytes`), all slots'
  queries and outputs fit one call's vector memory, and a layer is one
  call; `paged.slot_parts` still cuts the slots where they would not.
- **The experts this chip holds** are `expert_rank` of `expert_shards`
  of the router's `n_experts` columns (`models/moe.py::dropless_moe`'s
  `share`): routing, the k chosen and their renormalised weights are
  over all columns as published, and an assignment to an expert held
  elsewhere reads nothing here.
- **The prompt goes in** through `models/window_moe.py::
  blockwise_attention` with `causal_block=L`: the kernel's tiles where
  it engages, the loop elsewhere.

Every size comes from `BlockDiffMoEConfig`; there is no knob beside it.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.llama import apply_rope, embed_lookup, rms_norm
from ray_tpu.models.moe import (
    dropless_moe, serving_grouped_path, softmax_top_k, walk_counts,
)
from ray_tpu.models.serving import BlockFns, BlockSpec, ServingFns
from ray_tpu.models.window_moe import (
    _masked_attention, blockwise_attention, piece_walk,
)
from ray_tpu.ops import paged_attention as paged

REMASKING = ("low_confidence_dynamic", "low_confidence_static", "sequential")


@dataclasses.dataclass(frozen=True)
class BlockDiffMoEConfig:
    vocab_size: int = 151936
    dim: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    expert_hidden_dim: int = 768
    n_experts: int = 128            # the router's columns, as published
    top_k: int = 8
    # the experts this chip holds: [rank E, (rank + 1) E), E = n_experts
    # / expert_shards
    expert_rank: int = 0
    expert_shards: int = 1
    max_seq_len: int = 32768
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    # generation by diffusion over blocks (models/serving.py BlockSpec)
    block_length: int = 4
    denoising_steps: int = 4
    remasking: str = "low_confidence_dynamic"
    confidence_threshold: float = 0.9
    mask_token_id: int = 151669
    # keys a step of the prefill's blockwise attention takes
    prefill_key_block: int = 1024
    dtype: Any = jnp.bfloat16   # activation/matmul dtype
    param_dtype: Any = jnp.bfloat16

    def __post_init__(self):
        L = self.block_length
        if L < 1 or L & (L - 1):
            raise ValueError(f"block_length {L} is not a power of two")
        if not 1 <= self.denoising_steps <= L:
            raise ValueError(
                f"denoising_steps {self.denoising_steps} not in 1..{L}")
        if self.remasking not in REMASKING:
            raise ValueError(f"remasking {self.remasking!r} is none of "
                             f"{REMASKING}")
        if self.n_experts % self.expert_shards or not \
                0 <= self.expert_rank < self.expert_shards:
            raise ValueError(
                f"rank {self.expert_rank} of {self.expert_shards} shards "
                f"of {self.n_experts} experts")
        if not 0 <= self.mask_token_id < self.vocab_size:
            raise ValueError(f"mask_token_id {self.mask_token_id} is no "
                             f"row of a vocabulary of {self.vocab_size}")

    @property
    def n_held_experts(self) -> int:
        return self.n_experts // self.expert_shards

    def serving(self):
        return _SERVING


def init_params(config: BlockDiffMoEConfig, key: jax.Array,
                std: float = 0.02) -> Dict[str, Any]:
    """normal(0, std) matrices, unit norms; the held experts alone."""
    c = config
    dt = c.param_dtype
    D, hd, Eh, F = c.dim, c.head_dim, c.n_held_experts, c.expert_hidden_dim

    def draw(key, *shape):
        return jax.nn.initializers.normal(std)(key, shape, dt)

    k_embed, k_head, k_layers = jax.random.split(key, 3)
    layers: List[Dict[str, jax.Array]] = []
    for lk in jax.random.split(k_layers, c.n_layers):
        ks = jax.random.split(lk, 8)
        layers.append(dict(
            attn_norm=jnp.ones((D,), dt), ffn_norm=jnp.ones((D,), dt),
            q_norm=jnp.ones((hd,), dt), k_norm=jnp.ones((hd,), dt),
            wq=draw(ks[0], D, c.n_heads * hd),
            wk=draw(ks[1], D, c.n_kv_heads * hd),
            wv=draw(ks[2], D, c.n_kv_heads * hd),
            wo=draw(ks[3], c.n_heads * hd, D),
            router=draw(ks[4], D, c.n_experts),
            w_gate=draw(ks[5], Eh, D, F), w_up=draw(ks[6], Eh, D, F),
            w_down=draw(ks[7], Eh, F, D)))
    return {"embed": draw(k_embed, c.vocab_size, D), "layers": layers,
            "norm_f": jnp.ones((D,), dt),
            "lm_head": draw(k_head, D, c.vocab_size)}


def lm_head_weight(params: Dict[str, Any], config: BlockDiffMoEConfig):
    return params["lm_head"].astype(config.dtype)


def _block_end(pos, L: int):
    """The last position of the block of L that holds `pos`: the keys a
    query at `pos` sees are those up to it."""
    return pos | (L - 1)


def _rope_positions(qpos):
    """The positions q and k are rotated by: absolute."""
    return qpos


# ---------------------------------------------------------------------------
# The layers' caches: where a layer's new K and V rows go and which rows
# its queries see.  `attend(c, l, q, k, v)`: q [B, S, H, hd], k and v
# [B, S, kvH, hd] -> [B, S, H, hd].
# ---------------------------------------------------------------------------

class _NoCache:
    """The sequence's own rows are its keys (scoring, tests)."""

    def __init__(self, qpos):
        self.qpos = qpos

    def attend(self, c, l, q, k, v):
        seen = self.qpos[..., None, :] <= _block_end(
            self.qpos, c.block_length)[..., :, None]
        return _masked_attention(q, k, v, seen)


class _History:
    """ONE sequence with its gathered history `[L, S_pad, kvH hd]` by
    position (models/serving.py); the piece sits at `start`.. (a multiple
    of the block length) and its rows are kept for the engine to
    scatter."""

    def __init__(self, hist, start, qpos):
        self.hist, self.start, self.qpos = hist, start, qpos
        self.rows = {"k": [], "v": []}

    def attend(self, c, l, q, k, v):
        dt = self.hist["k"].dtype
        Pb = k.shape[1]
        keys = []
        for name, x in (("k", k), ("v", v)):
            x = x[0].reshape(Pb, -1).astype(dt)
            self.rows[name].append(x)
            keys.append(lax.dynamic_update_slice(
                self.hist[name][l], x, (self.start, 0)).reshape(
                    -1, c.n_kv_heads, c.head_dim).astype(c.dtype))
        kpos0, lo, hi = piece_walk("full", self.start, Pb,
                                   keys[0].shape[0], None,
                                   c.prefill_key_block)
        out = blockwise_attention(
            q[0], *keys, self.qpos, kpos0, lo, hi, None,
            c.prefill_key_block, causal_block=c.block_length)
        return out[None]

    def stacked(self):
        return {name: jnp.stack(x) for name, x in self.rows.items()}


class _Paged:
    """A block of L rows a sequence at positions `pos0 .. pos0 + L - 1`
    [B] (a multiple of L, and L divides the pool's block: the rows lie
    in ONE pool block), written into the pool through the table (a
    physical block out of bounds, so dropped, for a slot that does not
    write), then attended by one of two paths, chosen by backend and
    shape alone (`ops.paged_attention.engages`): the kernel reads the
    live blocks through the table where they lie, L queries a sequence;
    the gather builds every slot's padded view and masks it.  The
    kernel's scalars are planned here, once a program."""

    def __init__(self, c, pools, tables, qpos, active, write):
        self.pools, self.tables, self.qpos = dict(pools), tables, qpos
        B, L = qpos.shape
        bs = pools["k"].shape[2]
        phys = tables[jnp.arange(B), qpos[:, 0] // bs]
        if write is not None:
            phys = jnp.where(write, phys, pools["k"].shape[1])
        self.phys, self.off = phys[:, None], qpos % bs
        self.seen = _block_end(qpos, L)
        self.plans = None
        if _paged_attention(pools) == "kernel":
            shape = (L, c.n_heads, c.n_kv_heads)
            self.chunk = paged.chunk_blocks(*shape)
            n = B // paged.slot_parts(
                *tables.shape, self.chunk, query_bytes=paged.query_bytes(
                    *shape, c.head_dim, pools["k"].dtype.itemsize))
            self.cuts = [slice(i, i + n) for i in range(0, B, n)]
            with jax.named_scope("attn"), jax.named_scope("paged"):
                self.plans = [paged.plan(
                    tables[s], self.seen[s],
                    None if active is None else active[s], bs, self.chunk)
                    for s in self.cuts]

    def attend(self, c, l, q, k, v):
        B, L = self.qpos.shape
        with jax.named_scope("block_write"):
            for name, x in (("k", k), ("v", v)):
                pool = self.pools[name]
                self.pools[name] = pool.at[l, self.phys, self.off].set(
                    x.reshape(B, L, -1).astype(pool.dtype))
        k_pool, v_pool = self.pools["k"], self.pools["v"]
        with jax.named_scope("paged"):
            if self.plans is not None:
                return jnp.concatenate([paged.paged_attention(
                    q[s], k_pool, v_pool, l, plan, chunk=self.chunk)
                    for s, plan in zip(self.cuts, self.plans)])
            rows = self.tables.shape[1] * k_pool.shape[2]
            dense = [pool[l, self.tables].reshape(
                B, rows, c.n_kv_heads, c.head_dim).astype(c.dtype)
                for pool in (k_pool, v_pool)]
            seen = jnp.arange(rows)[None, None, :] <= self.seen[..., None]
            return _masked_attention(q, *dense, seen)


# ---------------------------------------------------------------------------
# One layer, one stack
# ---------------------------------------------------------------------------

def attention_operator(c: BlockDiffMoEConfig, l: int, p, x, cos, sin,
                       cache):
    """x [B, S, D] -> x + the layer's attention, its rows going through
    `cache` at layer l."""
    B, S, _ = x.shape
    dt, hd = c.dtype, c.head_dim
    with jax.named_scope("attn"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        q = (h @ p["wq"].astype(dt)).reshape(B, S, c.n_heads, hd)
        k = (h @ p["wk"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        v = (h @ p["wv"].astype(dt)).reshape(B, S, c.n_kv_heads, hd)
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, p["q_norm"], c.norm_eps)
            k = rms_norm(k, p["k_norm"], c.norm_eps)
        q, k = apply_rope(q, cos, sin), apply_rope(k, cos, sin)
        o = cache.attend(c, l, q, k, v)
        return x + o.reshape(B, S, c.n_heads * hd) @ p["wo"].astype(dt)


def routed_experts(c: BlockDiffMoEConfig, p, h, live=None):
    """h [T, D] -> (the held experts' part of the layer's sum [T, D],
    tokens routed to each held expert)."""
    return dropless_moe(
        h, p, softmax_top_k(c.top_k, norm=True), live=live,
        share=(c.expert_rank, c.expert_shards))


def _stack(c: BlockDiffMoEConfig, params, tokens, qpos, cache, live=None):
    """Embedding, every layer, final norm: tokens [B, S] at qpos [B, S]
    -> (normed hidden [B, S, D], tokens routed to each held expert
    [n_layers, Eh])."""
    B, S = tokens.shape
    hd = c.head_dim
    inv = 1.0 / (c.rope_theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32)
                                  / hd))
    freqs = _rope_positions(qpos).astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(freqs), jnp.sin(freqs)
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)
    routed = []
    for l, p in enumerate(params["layers"]):
        x = attention_operator(c, l, p, x, cos, sin, cache)
        with jax.named_scope("moe"):
            h = rms_norm(x, p["ffn_norm"], c.norm_eps)
            y, sizes = routed_experts(
                c, p, h.reshape(B * S, c.dim),
                None if live is None else live.reshape(B * S))
            x = x + y.reshape(B, S, c.dim)
        routed.append(sizes)
    return rms_norm(x, params["norm_f"], c.norm_eps), jnp.stack(routed)


def _head(c: BlockDiffMoEConfig, params, x):
    with jax.named_scope("head"):
        return jnp.dot(x, params["lm_head"].astype(c.dtype),
                       preferred_element_type=jnp.float32)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: BlockDiffMoEConfig) -> jax.Array:
    """tokens [B, S] -> logits [B, S, V] float32 under the block-causal
    mask; no cache.  The logits at i are of the token AT i."""
    B, S = tokens.shape
    qpos = jnp.broadcast_to(jnp.arange(S), (B, S))
    x, _ = _stack(config, params, tokens, qpos, _NoCache(qpos))
    return _head(config, params, x)


# ---------------------------------------------------------------------------
# The engine's functions (models/serving.py)
# ---------------------------------------------------------------------------

def init_paged_pool(config: BlockDiffMoEConfig, num_blocks: int,
                    block_size: int) -> Dict[str, jax.Array]:
    """K and V a token, its KV heads side by side."""
    c = config
    if block_size % c.block_length:
        raise ValueError(
            f"a pool block of {block_size} rows does not hold whole "
            f"generation blocks of {c.block_length}")
    shape = (c.n_layers, num_blocks, block_size, c.n_kv_heads * c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def prefill_paged(params, tokens, start, hist, config: BlockDiffMoEConfig,
                  n_real):
    """The prompt's whole blocks of ONE sequence: tokens [1, Pb] at
    start.. (a multiple of the block length), the first `n_real` real (a
    multiple of it too, so no real row sees a padding one).  Padding
    goes through no expert."""
    Pb = tokens.shape[1]
    qpos = (start + jnp.arange(Pb))[None]
    cache = _History(hist, start, qpos[0])
    x, _ = _stack(config, params, tokens, qpos, cache,
                  live=(jnp.arange(Pb) < n_real)[None])
    return x[:, :1], cache.stacked()        # no row of it is read


def denoise_paged(params, pools, tables, tokens, pos0,
                  config: BlockDiffMoEConfig,
                  active: Optional[jax.Array] = None,
                  write: Optional[jax.Array] = None):
    """One forward over a block a slot: tokens [B, L] at positions
    pos0 .. pos0 + L - 1 (pos0 [B], multiples of L).  The block's K/V
    rows are written at those positions (`write` [B] bool, `active`
    where None: a slot that does not write drops them) and every row
    attends to every key up to the block's last.  A dead slot goes
    through no expert.  Returns (normed hidden [B, L, D], pools,
    counts): the engine multiplies by the head the rows its rule reads
    (`serve/llm/programs.py::_block_tick_fn`); the counts are the tokens
    routed to each held expert of each layer, the distinct held experts
    touched summed over the layers, what the walks cost
    (`models/moe.py::walk_counts`), and 1 for the tick."""
    c = config
    B, L = tokens.shape
    qpos = pos0[:, None] + jnp.arange(L)
    cache = _Paged(c, pools, tables, qpos, active,
                   active if write is None else write)
    x, routed = _stack(
        c, params, tokens, qpos, cache,
        live=None if active is None else jnp.broadcast_to(
            active[:, None], (B, L)))
    counts = dict(
        walk_counts(routed, B * L * c.top_k, c.n_experts),
        expert_tokens=routed,
        experts_touched=jnp.sum(routed > 0, dtype=jnp.int32),
        ticks=jnp.ones((), jnp.int32))
    return x, cache.pools, counts


def init_counts(config: BlockDiffMoEConfig) -> Dict[str, jax.Array]:
    """Zeros of what `denoise_paged` counts."""
    zero = jnp.zeros((), jnp.int32)
    return {"expert_tokens": jnp.zeros(
                (config.n_layers, config.n_held_experts), jnp.int32),
            "experts_touched": zero, "ticks": zero,
            "moe_rows_walked": zero, "moe_rows_dense": zero,
            "moe_extra_passes": zero}


def block_spec(config: BlockDiffMoEConfig) -> BlockSpec:
    c = config
    return BlockSpec(c.block_length, c.denoising_steps, c.remasking,
                     c.confidence_threshold, c.mask_token_id)


def _refused(*_a, **_k):
    raise NotImplementedError(
        "a model that generates by blocks has no one-token decode step: "
        "the engine runs `ServingFns.block.denoise`")


def _paged_attention(pools) -> str:
    return "kernel" if paged.engages(pools["k"]) else "gather"


def _grouped_matmul(config: BlockDiffMoEConfig, slots: int) -> str:
    """The tick's grouped products run over slots x L x top_k picks."""
    return serving_grouped_path(config, slots * config.block_length)


_SERVING = ServingFns(
    name="block-diffusion GQA (QK-norm) with softmax-routed experts "
         "(models/blockdiff_moe.py)",
    init_params=init_params, init_pool=init_paged_pool,
    prefill=prefill_paged, decode=_refused, head_weight=lm_head_weight,
    init_counts=init_counts, paged_attention=_paged_attention,
    grouped_matmul=_grouped_matmul,
    block=BlockFns(spec=block_spec, denoise=denoise_paged))
