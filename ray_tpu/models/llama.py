"""Llama-2-family decoder — the flagship model, TPU-first.

Design (not a port — the reference has no in-repo model zoo; its Llama runs
arrive via HF/DeepSpeed through the generic worker group, e.g.
`train/examples/deepspeed/deepspeed_torch_trainer.py`):

- Pure-functional: params are a pytree of arrays; no module framework in the
  hot path, so pjit sharding rules are plain pytrees too (parallel/sharding.py).
- Layers are STACKED along a leading [n_layers, ...] axis and iterated with
  `lax.scan` — one compiled layer body instead of n_layers inlined copies:
  small XLA programs, fast compiles, and the idiomatic substrate for
  pipeline parallelism (a stage = a slice of the stacked tree).
- bfloat16 activations/matmuls (MXU-native), fp32 params + softmax/norm
  accumulators.
- GQA (n_kv_heads <= n_heads), RoPE, RMSNorm, SwiGLU — Llama-2/3 shapes.
- Attention is pluggable: "xla" einsum (fused by XLA), "flash"
  (ray_tpu.ops pallas kernel on TPU), or "ring" (context parallel over a
  mesh axis) — selected by config or overridden per call.
- ONE definition of a layer (`_layer`: norm, q/k/v projections, rotary,
  the cache's attention, `wo`, SwiGLU or experts) and one trunk
  (`_trunk`: embedding, the `layers` scan) under every entry point.
  What differs between training, prefill, decode and verify is where a
  layer's new K/V rows go and which rows its queries see, and that is a
  cache object: `_NoCache` (`forward_hidden`, `prefill_kv`), `_Stripe`
  (`decode_step`: `generate`, the speculative draft), `_History`
  (`prefill_kv_paged`: the engine's insert), `_Paged`
  (`decode_step_paged`, `verify_kv_paged`: the engine's tick and
  verify). `generate` / `prefill` / `decode_step` are the reference the
  engine's parity tests compare against: a path of their own over the
  shared layer.
- What a cache KEEPS is CARRIED through the layer scan; what it EMITS
  is a scan output.  `_Paged`'s stacked pools and `_Stripe`'s stacked
  stripes [L, ...] ride in the scan's carry beside the activations and
  each layer writes its rows in place at (its index, ...), so a donated
  pool is one buffer from the program's argument to its result.
  `_History` and `_NoCache` keep nothing: their new rows (and, when
  scoring, the layers' aux terms) are the scan's stacked outputs.
"""

from __future__ import annotations

import dataclasses
import math
from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ray_tpu.models.serving import DraftFns, ServingFns


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    dim: int = 4096
    n_layers: int = 32
    n_heads: int = 32
    n_kv_heads: int = 32
    hidden_dim: int = 11008
    max_seq_len: int = 4096
    rope_theta: float = 10000.0
    norm_eps: float = 1e-5
    dtype: Any = jnp.bfloat16   # activation/matmul dtype
    param_dtype: Any = jnp.float32
    attn_impl: str = "xla"      # "xla" | "flash" | "ring"
    # False | True (full per-layer jax.checkpoint) | "dots" (checkpoint
    # with dots-saveable policy: keep matmul outputs, recompute the rest)
    remat: Any = False
    tie_embeddings: bool = False
    # Mixture-of-Experts FFN (0 = dense). Experts shard over the mesh
    # "expert" axis (SURVEY §2.7 EP; see models/moe.py).
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25

    @property
    def head_dim(self) -> int:
        return self.dim // self.n_heads

    @staticmethod
    def llama2_7b(**overrides) -> "LlamaConfig":
        return LlamaConfig(**{**dict(
            vocab_size=32000, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=32, hidden_dim=11008, max_seq_len=4096), **overrides})

    @staticmethod
    def llama3_8b(**overrides) -> "LlamaConfig":
        return LlamaConfig(**{**dict(
            vocab_size=128256, dim=4096, n_layers=32, n_heads=32,
            n_kv_heads=8, hidden_dim=14336, max_seq_len=8192,
            rope_theta=500000.0), **overrides})

    @staticmethod
    def tiny(**overrides) -> "LlamaConfig":
        """Test-size config: runs on CPU in milliseconds."""
        return LlamaConfig(**{**dict(
            vocab_size=256, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            hidden_dim=128, max_seq_len=128), **overrides})

    def num_params(self) -> int:
        d, h, v = self.dim, self.hidden_dim, self.vocab_size
        ffn = (self.n_experts * 3 * d * h + d * self.n_experts
               if self.n_experts else 3 * d * h)
        per_layer = (self.dim * self.head_dim * self.n_heads      # wq
                     + 2 * self.dim * self.head_dim * self.n_kv_heads  # wk,wv
                     + self.dim * self.dim                         # wo
                     + ffn                                         # ffn/moe
                     + 2 * d)                                      # norms
        out_head = 0 if self.tie_embeddings else d * v
        return v * d + self.n_layers * per_layer + d + out_head

    def serving(self):
        """This model's functions for `serve/llm/engine.py`
        (models/serving.py)."""
        return _SERVING


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------

def init_params(config: LlamaConfig, key: jax.Array) -> Dict[str, Any]:
    """Stacked-layer parameter pytree."""
    c = config
    k_embed, k_layers, k_out = jax.random.split(key, 3)
    initializer = jax.nn.initializers.normal(0.02)

    def dense(key, shape):
        return initializer(key, shape, c.param_dtype)

    kd = c.head_dim
    lk = jax.random.split(k_layers, 8)

    def stacked(key, shape):
        return dense(key, (c.n_layers, *shape))

    params = {
        "embed": dense(k_embed, (c.vocab_size, c.dim)),
        "layers": {
            "attn_norm": jnp.ones((c.n_layers, c.dim), c.param_dtype),
            "wq": stacked(lk[0], (c.dim, c.n_heads * kd)),
            "wk": stacked(lk[1], (c.dim, c.n_kv_heads * kd)),
            "wv": stacked(lk[2], (c.dim, c.n_kv_heads * kd)),
            "wo": stacked(lk[3], (c.n_heads * kd, c.dim)),
            "ffn_norm": jnp.ones((c.n_layers, c.dim), c.param_dtype),
            **(
                {
                    "router": stacked(lk[7], (c.dim, c.n_experts)),
                    "w_gate": stacked(lk[4], (c.n_experts, c.dim,
                                              c.hidden_dim)),
                    "w_up": stacked(lk[5], (c.n_experts, c.dim,
                                            c.hidden_dim)),
                    "w_down": stacked(lk[6], (c.n_experts, c.hidden_dim,
                                              c.dim)),
                } if c.n_experts else {
                    "w_gate": stacked(lk[4], (c.dim, c.hidden_dim)),
                    "w_up": stacked(lk[5], (c.dim, c.hidden_dim)),
                    "w_down": stacked(lk[6], (c.hidden_dim, c.dim)),
                }
            ),
        },
        "norm_f": jnp.ones((c.dim,), c.param_dtype),
    }
    if not c.tie_embeddings:
        params["lm_head"] = dense(k_out, (c.dim, c.vocab_size))
    return params


# ---------------------------------------------------------------------------
# Building blocks
# ---------------------------------------------------------------------------

def quantize_weights_int8(params: Dict[str, Any]) -> Dict[str, Any]:
    """Weight-only int8 quantization for the decode path (serving):
    per-output-channel symmetric scales on every large matmul weight
    (attention/FFN projections + lm_head). Decode is HBM-bandwidth-bound
    — each generated token reads every weight once — so halving weight
    bytes converts ~directly into decode throughput; dequant happens
    per-layer inside the scan (int8 travels HBM→VMEM, bf16 never
    materializes). Norms and the embedding gather stay in bf16.

    Returns a params-shaped pytree where each quantized weight `w`
    becomes the pair `w_q` (int8) + `w_s` (f32 scales); every path
    reads its weights through `_weight`, which takes either.
    """
    def quant(w):
        w32 = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(w32), axis=-2, keepdims=True) / 127.0
        scale = jnp.maximum(scale, 1e-8)
        q = jnp.clip(jnp.round(w32 / scale), -127, 127).astype(jnp.int8)
        return q, scale

    out: Dict[str, Any] = {"embed": params["embed"],
                           "norm_f": params["norm_f"]}
    layers = dict(params["layers"])
    qlayers: Dict[str, Any] = {
        "attn_norm": layers["attn_norm"], "ffn_norm": layers["ffn_norm"]}
    for name in ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"):
        q, s = quant(layers[name])
        qlayers[name + "_q"] = q
        qlayers[name + "_s"] = s
    if "router" in layers:
        qlayers["router"] = layers["router"]
    out["layers"] = qlayers
    if "lm_head" in params:
        q, s = quant(params["lm_head"])
        out["lm_head_q"] = q
        out["lm_head_s"] = s
    return out


def _weight(p: Dict[str, Any], name: str, dtype) -> jax.Array:
    """Fetch a matmul weight in compute dtype, dequantizing int8+scale
    pairs in place (fused by XLA into the consuming dot's operand)."""
    q = p.get(name + "_q")
    if q is not None:
        return (q.astype(dtype) * p[name + "_s"].astype(dtype))
    return p[name].astype(dtype)


def rms_norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    orig_dtype = x.dtype
    x32 = x.astype(jnp.float32)
    rrms = lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return ((x32 * rrms).astype(orig_dtype)
            * weight.astype(orig_dtype))


def rope_freqs(head_dim: int, max_len: int, theta: float) -> Tuple[jax.Array, jax.Array]:
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    t = jnp.arange(max_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv)                       # [S, D/2]
    return jnp.cos(freqs), jnp.sin(freqs)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, Q, H, D] rotated by its positions' rows of the table:
    cos/sin [B, Q, D/2], or [Q, D/2] where every sequence sits at the
    same positions."""
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def xla_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                  causal: bool = True,
                  positions: Optional[jax.Array] = None) -> jax.Array:
    """Reference attention, [B, S, H, D] layout; fp32 softmax accumulator.
    XLA fuses this well on TPU for short/medium sequences; flash/ring
    kernels take over for long context."""
    scale = 1.0 / math.sqrt(q.shape[-1])
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if causal:
        s_q, s_k = q.shape[1], k.shape[1]
        if positions is None:
            q_pos = jnp.arange(s_q)[:, None]
        else:
            q_pos = positions[:, None]
        mask = q_pos >= jnp.arange(s_k)[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def _repeat_kv(x: jax.Array, n_rep: int) -> jax.Array:
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=2)


def _get_attention_fn(impl):
    if callable(impl):
        # e.g. parallel.context_parallel_attention(mesh): ring attention
        # with the mesh/axis already bound.
        return impl
    if impl == "flash":
        from ray_tpu.ops.attention import flash_attention

        return flash_attention
    if impl == "ring":
        from ray_tpu.ops.ring_attention import ring_attention

        return ring_attention
    return xla_attention


def _make_embed_lookup(vocab: int, dtype_name: str):
    """Embedding gather whose BACKWARD is a one-hot matmul, not a scatter.

    XLA lowers the gather's transpose to a serialized scatter-add on TPU —
    hundreds of ms at [V, D] scale; the MXU does the same reduction as a
    [V, B*S] x [B*S, D] matmul in milliseconds. Static (vocab, dtype) live
    in this closure: custom_vjp residuals must be JAX arrays only.
    """

    @jax.custom_vjp
    def lookup(embed, tokens):
        return embed[tokens]

    def fwd(embed, tokens):
        return embed[tokens], tokens

    def bwd(tokens, g):
        flat_tok = tokens.reshape(-1)
        flat_g = g.reshape(flat_tok.shape[0], -1)
        onehot = jax.nn.one_hot(flat_tok, vocab, dtype=flat_g.dtype, axis=0)
        d_embed = jax.lax.dot_general(
            onehot, flat_g, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return d_embed.astype(dtype_name), None

    lookup.defvjp(fwd, bwd)
    return lookup


_EMBED_LOOKUP_CACHE: Dict[Tuple[int, str], Any] = {}


def embed_lookup(embed: jax.Array, tokens: jax.Array) -> jax.Array:
    key = (embed.shape[0], jnp.dtype(embed.dtype).name)
    fn = _EMBED_LOOKUP_CACHE.get(key)
    if fn is None:
        fn = _EMBED_LOOKUP_CACHE[key] = _make_embed_lookup(*key)
    return fn(embed, tokens)


# ---------------------------------------------------------------------------
# One layer, one trunk, and the caches a layer attends through
# ---------------------------------------------------------------------------

def _ffn(c: LlamaConfig, x, p):
    """A layer's feed-forward half, SwiGLU or experts: (x + delta, aux)."""
    h = rms_norm(x, p["ffn_norm"], c.norm_eps)
    if c.n_experts:
        from ray_tpu.models.moe import MoEConfig, moe_layer

        mcfg = MoEConfig(
            dim=c.dim, hidden_dim=c.hidden_dim, n_experts=c.n_experts,
            top_k=c.moe_top_k, capacity_factor=c.moe_capacity_factor,
            dtype=c.dtype)
        delta, aux = moe_layer(h, {
            "router": p["router"], "w_gate": p["w_gate"],
            "w_up": p["w_up"], "w_down": p["w_down"]}, mcfg)
        return x + delta, aux
    gate = jax.nn.silu(h @ _weight(p, "w_gate", c.dtype))
    up = h @ _weight(p, "w_up", c.dtype)
    x = x + (gate * up) @ _weight(p, "w_down", c.dtype)
    return x, jnp.zeros((), jnp.float32)


def _layer(c: LlamaConfig, p, x, rope, cache, stacks, leaves):
    """The one definition of a layer: x [B, Q, D], rotated by `rope`
    (cos, sin as `apply_rope` takes them), attends through `cache`:
    `stacks` is what the cache carries through the layers (whole, every
    layer's), `leaves` its inputs for this layer alone.  Returns (x, the
    stacks as this layer leaves them, the rows it emits, aux).

    Scope names (`attn`, `mlp`, ...) reach each operation's `op_name`
    and so a device trace; they change nothing in the compiled program."""
    B, Q, _ = x.shape
    kd = c.head_dim
    with jax.named_scope("attn"):
        h = rms_norm(x, p["attn_norm"], c.norm_eps)
        q = (h @ _weight(p, "wq", c.dtype)).reshape(B, Q, c.n_heads, kd)
        k = (h @ _weight(p, "wk", c.dtype)).reshape(B, Q, c.n_kv_heads, kd)
        v = (h @ _weight(p, "wv", c.dtype)).reshape(B, Q, c.n_kv_heads, kd)
        q, k = apply_rope(q, *rope), apply_rope(k, *rope)
    attn, stacks, rows = cache.attend(c, q, k, v, stacks, leaves)
    with jax.named_scope("attn"):
        x = x + attn.reshape(B, Q, -1) @ _weight(p, "wo", c.dtype)
    with jax.named_scope("mlp"):
        x, aux = _ffn(c, x, p)
    return x, stacks, rows, aux


def _trunk(c: LlamaConfig, params, tokens, rope, cache, scoring=False):
    """Embedding and the scan over the stacked layers: tokens [B, Q] ->
    (hidden before the final norm, what the cache keeps).

    A cache is one of two kinds, by its class.  One that KEEPS an
    updated stack (`_Paged`'s two pools, `_Stripe`'s two stripes) has it
    CARRIED through the scan beside `x`, whole, and each layer writes its
    rows in place at its own index, which the scan hands the layer beside
    its weights (`cache.leaves` is `arange(L)`).  A scan's inputs and
    outputs are separate buffers and cannot alias, so a pool scanned in a
    layer at a time and stacked back out is two pools, with each layer's
    slice cut out of one and copied into the other (PERF.md F3); the
    carry is one buffer from the first layer to the last, so a donated
    pool stays the only pool in the program.  One that only EMITS
    (`_History`'s and `_NoCache`'s new k, v rows) carries nothing: its
    per-layer inputs are scanned in and its rows come out as the scan's
    stacked outputs [L, ...].  With `scoring` (no cache is made:
    training, `forward`) the outputs are the layers' aux terms [L]
    instead, the only case experts are implemented for."""
    if c.n_experts and not scoring:
        raise NotImplementedError(
            "KV-cache prefill, decode and verify are not implemented for "
            "MoE configs; use forward() for scoring")
    x = embed_lookup(params["embed"].astype(c.dtype), tokens)

    def layer_fn(carry, inputs):
        x, stacks = carry
        p, leaves = inputs
        x, stacks, rows, aux = _layer(c, p, x, rope, cache, stacks, leaves)
        return (x, stacks), (aux if scoring else rows)

    if isinstance(c.remat, str) and c.remat != "dots":
        raise ValueError(
            f"remat={c.remat!r}: expected False, True, or 'dots'")
    if c.remat == "dots":
        # Keep matmul outputs, recompute only cheap elementwise ops on
        # the backward — ~5x less recompute than full remat at a modest
        # HBM premium (policy: dots_with_no_batch_dims_saveable).
        layer_fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    elif c.remat:
        layer_fn = jax.checkpoint(layer_fn)

    # `layers` names what the scan itself does around the body: slicing
    # a layer's weights (and an emitting cache's leaves) out of the
    # stacks, stacking what the backward needs and what a cache emits.
    with jax.named_scope("layers"):
        (x, stacks), outs = lax.scan(
            layer_fn, (x, cache.stacks), (params["layers"], cache.leaves))
    return x, (stacks if cache.stacks else outs)


class _NoCache:
    """The sequence's own rows are its keys, through the pluggable
    attention; emits the new (pre-repeat) k, v rows."""
    stacks = leaves = ()

    def __init__(self, attn_fn):
        self.attn_fn = attn_fn

    def attend(self, c, q, k, v, stacks, leaves):
        rep = c.n_heads // c.n_kv_heads
        with jax.named_scope("attn"):
            return self.attn_fn(q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                                causal=True), stacks, (k, v)


class _Stripe:
    """One [S] stripe a sequence, [L, B, S, n_kv, head_dim], carried
    whole through the layers: layer `l` writes its new row at (l, b,
    the sequence's position) -- a position out of bounds, so dropped,
    for an inactive one -- and then attends its own [B, S] stripes under
    the position mask.  Keeps the updated stripes."""

    def __init__(self, cache, positions, active):
        self.stacks = (cache["k"], cache["v"])
        L, _, S = cache["k"].shape[:3]
        self.leaves = jnp.arange(L)
        self.positions = positions
        self.write_pos = (positions if active is None
                          else jnp.where(active, positions, S))

    def attend(self, c, q, k, v, stacks, l):
        k_cache, v_cache = stacks
        bidx = jnp.arange(q.shape[0])
        with jax.named_scope("kv_write"):
            k_cache = k_cache.at[l, bidx, self.write_pos].set(k[:, 0])
            v_cache = v_cache.at[l, bidx, self.write_pos].set(v[:, 0])
        attn = _decode_attention(q, k_cache[l], v_cache[l],
                                 self.positions[:, None])
        return attn, (k_cache, v_cache), ()


class _History:
    """ONE sequence with its gathered history [L, S_pad, n_kv, head_dim],
    scanned in a layer at a time: the new rows land at `start` of the
    layer's history and the queries at `qpos` see keys at positions <=
    their own.  Emits the new rows for the engine to scatter into the
    pool; carries nothing."""
    stacks = ()

    def __init__(self, hist_k, hist_v, start, qpos):
        self.leaves = (hist_k, hist_v)
        self.start, self.qpos = start, qpos

    def attend(self, c, q, k, v, stacks, leaves):
        hk, hv = leaves
        keys = lax.dynamic_update_slice(hk, k[0].astype(hk.dtype),
                                        (self.start, 0, 0))
        vals = lax.dynamic_update_slice(hv, v[0].astype(hv.dtype),
                                        (self.start, 0, 0))
        rep = c.n_heads // c.n_kv_heads
        with jax.named_scope("attn"):
            attn = xla_attention(
                q, _repeat_kv(keys[None].astype(c.dtype), rep),
                _repeat_kv(vals[None].astype(c.dtype), rep),
                causal=True, positions=self.qpos)
        return attn, stacks, (k, v)


class _Paged:
    """New rows against the paged pool [L, NB, bs, n_kv, head_dim], at
    absolute positions `qpos`: [B, Q] for Q queries a sequence, or [B]
    for one (the same thing at the index shapes the decode tick was
    compiled with).  The two stacked pools are carried whole through the
    layers, ONE buffer each from the tick's donated argument to its
    result: layer `l` writes each row in place at (l, table[pos // bs],
    pos % bs), as the pool lies (`ops.paged_attention.write_rows`) -- a
    physical block id out of bounds, so dropped, for an inactive
    sequence -- and then attends (AFTER the writes, so a query
    sees its own row and those before it) by one of two paths, chosen
    by backend and shape alone (`ops.paged_attention.engages`):

    - the kernel (a TPU, shapes that tile): `ops.paged_attention` reads
      the live blocks of the live sequences out of the whole pool
      through the block table, at (l, table[b, j]); nothing is
      gathered, a dead slot and a row past a sequence's length cost
      nothing.  Its scalars (`plan`) are made here, once a program.
    - the gather (everywhere else, and the reference the kernel is
      tested against): every sequence's dense [S_pad] view of its own
      layer, `pool[l, tables]`, read as it lies by `_decode_attention`
      under the position mask.

    Keeps the updated pools."""

    def __init__(self, pools, block_tables, qpos, active):
        from ray_tpu.ops import paged_attention

        self.stacks = (pools["k"], pools["v"])
        L, NB, bs = pools["k"].shape[:3]
        self.leaves = jnp.arange(L)
        seq = jnp.arange(qpos.shape[0]).reshape(
            (-1,) + (1,) * (qpos.ndim - 1))
        self.tables, self.qpos = block_tables, qpos
        phys = block_tables[seq, qpos // bs]
        if active is not None:
            phys = jnp.where(active.reshape(seq.shape), phys, NB)
        self.phys, self.off = phys, qpos % bs
        self.plan = None
        if paged_attention.engages(pools["k"]):
            with jax.named_scope("attn"), jax.named_scope("paged"):
                self.plan = paged_attention.plan(
                    block_tables, qpos, active, bs)

    def attend(self, c, q, k, v, stacks, l):
        k_pool, v_pool = stacks
        B, nb = self.tables.shape
        new = self.phys.shape + k.shape[2:]
        from ray_tpu.ops.paged_attention import paged_attention, write_rows

        with jax.named_scope("kv_write"):
            k_pool = write_rows(k_pool, l, self.phys, self.off,
                                k.reshape(new))
            v_pool = write_rows(v_pool, l, self.phys, self.off,
                                v.reshape(new))
        if self.plan is not None:
            with jax.named_scope("attn"), jax.named_scope("paged"):
                attn = paged_attention(q, k_pool, v_pool, l, self.plan)
            return attn, (k_pool, v_pool), ()
        dense = (B, nb * k_pool.shape[2], c.n_kv_heads, c.head_dim)
        with jax.named_scope("kv_gather"):
            k_dense = k_pool[l, self.tables].reshape(dense)
            v_dense = v_pool[l, self.tables].reshape(dense)
        attn = _decode_attention(q, k_dense, v_dense,
                                 self.qpos.reshape(B, -1))
        return attn, (k_pool, v_pool), ()


# ---------------------------------------------------------------------------
# Forward (training, scoring)
# ---------------------------------------------------------------------------

def forward_hidden(params: Dict[str, Any], tokens: jax.Array,
                   config: LlamaConfig,
                   attn_impl: Optional[str] = None):
    """Trunk only: tokens [B, S] -> (hidden [B, S, D], aux). The fused
    training loss consumes hidden states directly so the [B, S, V]
    logits tensor never materializes (ops/fused_loss.py); `forward`
    adds the lm_head matmul on top."""
    c = config
    S = tokens.shape[1]
    cos, sin = rope_freqs(c.head_dim, c.max_seq_len, c.rope_theta)
    cache = _NoCache(_get_attention_fn(attn_impl or c.attn_impl))
    x, aux = _trunk(c, params, tokens, (cos[:S], sin[:S]),
                    cache, scoring=True)
    x = rms_norm(x, params["norm_f"], c.norm_eps)
    return x, jnp.sum(aux)


def forward(params: Dict[str, Any], tokens: jax.Array,
            config: LlamaConfig,
            attn_impl: Optional[str] = None,
            return_aux: bool = False):
    """tokens [B, S] int32 -> logits [B, S, V] (or (logits, aux_loss)
    with return_aux — the MoE router load-balance term)."""
    x, aux = forward_hidden(params, tokens, config, attn_impl)
    with jax.named_scope("lm_head"):
        logits = _head(config, params, x)
    if return_aux:
        return logits, aux
    return logits


def loss_fn(params: Dict[str, Any], batch: Dict[str, jax.Array],
            config: LlamaConfig,
            attn_impl: Optional[str] = None,
            fused: Optional[bool] = None) -> jax.Array:
    """Next-token cross-entropy. batch: tokens [B, S] (+ optional mask).

    ``fused`` (default: env RAY_TPU_FUSED_LOSS, on unless =0) streams
    the lm_head matmul + logsumexp over vocab blocks so the [B, S, V]
    logits tensor never round-trips to HBM (ops/fused_loss.py) —
    identical numerics, fraction of the loss-stage memory traffic."""
    import os

    tokens = batch["tokens"]
    targets = tokens[:, 1:]
    if fused is None:
        fused = os.environ.get("RAY_TPU_FUSED_LOSS", "1") != "0"
    if fused:
        from ray_tpu.ops.fused_loss import blockwise_xent

        hidden, aux = forward_hidden(params, tokens[:, :-1], config,
                                     attn_impl)
        c = config
        with jax.named_scope("loss_head"):
            head = lm_head_weight(params, c)
            b, s, d = hidden.shape
            nll = blockwise_xent(hidden.reshape(b * s, d), head,
                                 targets.reshape(-1)).reshape(b, s)
    else:
        logits, aux = forward(params, tokens[:, :-1], config, attn_impl,
                              return_aux=True)
        # NLL via logsumexp - target_logit: one [B,S,V] reduction instead
        # of a materialized log_softmax plus gather.
        with jax.named_scope("loss_head"):
            lse = jax.scipy.special.logsumexp(logits, axis=-1)
            tgt = jnp.take_along_axis(logits, targets[..., None],
                                      axis=-1)[..., 0]
            nll = lse - tgt
    mask = batch.get("mask")
    if mask is not None:
        m = mask[:, 1:].astype(jnp.float32)
        return (nll * m).sum() / jnp.maximum(m.sum(), 1.0) + aux
    return nll.mean() + aux


# ---------------------------------------------------------------------------
# Inference: KV-cache decode + generation (the Serve-on-TPU path)
# ---------------------------------------------------------------------------

def lm_head_weight(params: Dict[str, Any], config: LlamaConfig) -> jax.Array:
    """Output-projection matrix [D, V] in compute dtype (tied or not)."""
    if config.tie_embeddings:
        return params["embed"].T.astype(config.dtype)
    return _weight(params, "lm_head", config.dtype)


def _head(c: LlamaConfig, params, x):
    """Normed hidden [..., D] -> logits [..., V]: bf16 matmul on the MXU
    (fp32 here costs ~4x), fp32 accumulation for the softmax/loss that
    follows."""
    return jax.lax.dot_general(
        x, lm_head_weight(params, c), (((x.ndim - 1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _logits(c: LlamaConfig, params, x, query=None):
    """Final norm and output head of a cached step: hidden [B, Q, D] ->
    logits [B, Q, V], or [B, V] of the one query `query`."""
    with jax.named_scope("lm_head"):
        x = rms_norm(x, params["norm_f"], c.norm_eps)
        return _head(c, params, x if query is None else x[:, query])


def init_kv_cache(config: LlamaConfig, batch_size: int,
                  max_len: Optional[int] = None) -> Dict[str, jax.Array]:
    """Stacked per-layer cache [L, B, S, n_kv, head_dim] (bf16)."""
    c = config
    S = max_len or c.max_seq_len
    shape = (c.n_layers, batch_size, S, c.n_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def _decode_attention(q, k_cache, v_cache, qpos):
    """q [B,Q,H,D] at absolute positions qpos [B,Q]; caches [B,S,kvH,D].
    Query j of row b attends to keys at positions <= qpos[b, j].

    Grouped-query form: head h belongs to KV group h // rep (the order
    `_repeat_kv` lays them), so the rep query heads of a group contract
    against that group's K and V rows as they lie in the cache and each
    row is read once -- no rep-fold copy of the cache is built. rep == 1
    (MHA) and kvH == 1 (MQA) are the same contraction at other shapes.
    """
    B, S, KVH, D = k_cache.shape
    Q, H = q.shape[1], q.shape[2]
    with jax.named_scope("attn"):
        qg = q.reshape(B, Q, KVH, H // KVH, D)
        scores = jnp.einsum("bqgrd,bkgd->bgrqk", qg, k_cache).astype(
            jnp.float32) * (1.0 / math.sqrt(D))
        mask = jnp.arange(S)[None, None, None, None, :] \
            <= qpos[:, None, None, :, None]
        scores = jnp.where(mask, scores, -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bgrqk,bkgd->bqgrd", probs, v_cache).reshape(
            B, Q, H, D)


def decode_step(params: Dict[str, Any], cache: Dict[str, jax.Array],
                tokens: jax.Array, positions: jax.Array,
                config: LlamaConfig,
                active: Optional[jax.Array] = None):
    """One incremental token: tokens [B] int32 at `positions` [B].
    Returns (logits [B, V], updated cache). Jittable; scan over layers.

    ``active`` [B] bool (optional) slot-masks the KV write: inactive
    rows keep their cache untouched (the write index is pushed out of
    bounds, where scatter drops it) so a fixed-shape program (the
    engine's speculative draft) can run dead slots through without
    corrupting rows a later prefill has already claimed. Logits for
    inactive rows are garbage by construction — callers ignore them.
    """
    c = config
    cos, sin = rope_freqs(c.head_dim, cache["k"].shape[2], c.rope_theta)
    x, (new_k, new_v) = _trunk(
        c, params, tokens[:, None],
        (cos[positions][:, None, :], sin[positions][:, None, :]),
        _Stripe(cache, positions, active))
    return _logits(c, params, x, query=0), {"k": new_k, "v": new_v}


def init_paged_kv_cache(config: LlamaConfig, num_blocks: int,
                        block_size: int) -> Dict[str, jax.Array]:
    """Paged cache: a fixed POOL of KV blocks shared by all sequences,
    [L, num_blocks, block_size, n_kv, head_dim] (bf16). A sequence owns
    a *block table* — the list of physical block ids covering its
    logical positions — instead of a dense [S] stripe, so short and long
    requests share HBM instead of each reserving max_seq rows
    (PagedAttention, arXiv:2309.06180)."""
    c = config
    shape = (c.n_layers, num_blocks, block_size, c.n_kv_heads, c.head_dim)
    return {"k": jnp.zeros(shape, c.dtype), "v": jnp.zeros(shape, c.dtype)}


def verify_kv_paged(params: Dict[str, Any], pools: Dict[str, jax.Array],
                    block_tables: jax.Array, tokens: jax.Array,
                    positions: jax.Array, config: LlamaConfig,
                    active: Optional[jax.Array] = None):
    """K tokens a sequence against the paged pool, consumed in parallel:
    tokens [B, K], token j of row b at absolute position
    ``positions[b] + j``; block_tables [B, max_blocks] int32 maps each
    sequence's logical block index -> physical pool block. Returns
    (logits [B, K, V], updated pools). The speculative verify step, and
    at K = 1 the decode step (`decode_step_paged`).

    Row j's logits are the target model's distribution for the token
    FOLLOWING input j — exactly what K single-token steps would produce
    after consuming inputs 0..j one at a time, because every op here is
    row-independent (per-position matmuls, and decode attention with K
    queries a row instead of one): running K queries through one
    program instead of K programs changes batching, not values. The
    engine exploits this for draft verification: accept the longest
    prefix where the target's argmax agrees with the draft, and greedy
    parity holds by construction.

    All K KV writes scatter before the layer attends (`_Paged`: the
    paged-attention kernel over the live blocks on a TPU, the dense
    gather + `_decode_attention` elsewhere; the same mask in both), so
    input j attends to inputs i < j (their positions pass the ``key_pos
    <= pos + j`` mask) and never to inputs i > j. Rejected inputs leave
    stale rows past the accepted position — the same stale-rows-
    overwritten-before-attended invariant every other path in this file
    relies on. ``active`` masks writes by pushing the physical block id
    out of bounds.
    """
    c = config
    K = tokens.shape[1]
    S_pad = block_tables.shape[1] * pools["k"].shape[2]
    cos, sin = rope_freqs(c.head_dim, S_pad, c.rope_theta)
    # Absolute position of every query; clamped so inactive rows with
    # garbage positions still index rope/scatter safely (their writes
    # are dropped and their logits ignored).
    qpos = jnp.minimum(positions[:, None] + jnp.arange(K)[None, :],
                       S_pad - 1)                            # [B, K]
    x, (new_k, new_v) = _trunk(
        c, params, tokens, (cos[qpos], sin[qpos]),
        _Paged(pools, block_tables, qpos, active))
    return _logits(c, params, x), {"k": new_k, "v": new_v}


def decode_step_paged(params: Dict[str, Any], pools: Dict[str, jax.Array],
                      block_tables: jax.Array, tokens: jax.Array,
                      positions: jax.Array, config: LlamaConfig,
                      active: Optional[jax.Array] = None):
    """One incremental token against the paged pool: tokens [B] at
    `positions` [B]. Returns (logits [B, V], updated pools).

    The write lands at (table[pos // bs], pos % bs); then `_Paged`
    attends by one of two paths.  On a TPU, at shapes that tile, the
    `ops.paged_attention` kernel reads each live sequence's live blocks
    out of the stacked pool through its table row (float32 scores and
    softmax; a dead slot and rows past a sequence's length are never
    read).  Everywhere else the gather assembles each sequence's dense
    [S_pad] view (S_pad = max_blocks * block_size) and the same
    `_decode_attention` as `decode_step`'s reads it as it lies ([B,
    S_pad, kvH, D], no GQA repeat), dropping padding/stale rows to
    exact zeros: token-exact with `decode_step` on a dense cache
    holding the same logical contents, and the reference the kernel is
    tested against.
    """
    c = config
    S_pad = block_tables.shape[1] * pools["k"].shape[2]
    cos, sin = rope_freqs(c.head_dim, S_pad, c.rope_theta)
    x, (new_k, new_v) = _trunk(
        c, params, tokens[:, None],
        (cos[positions][:, None, :], sin[positions][:, None, :]),
        _Paged(pools, block_tables, positions, active))
    return _logits(c, params, x, query=0), {"k": new_k, "v": new_v}


def prefill_kv_paged(params: Dict[str, Any], tokens: jax.Array,
                     start: jax.Array, hist_k: jax.Array,
                     hist_v: jax.Array, config: LlamaConfig):
    """Suffix prefill with history: the prefix-cache hit path. tokens
    [1, Pb] sit at absolute positions start..start+Pb-1; hist_k/hist_v
    [L, S_pad, n_kv, head_dim] hold the cached prefix KV (rows >= start
    are don't-care — masked, then overwritten by the suffix). Returns
    (normed hidden [1, Pb, D], suffix ks/vs [L, 1, Pb, n_kv, head_dim]).

    With start=0 and zero history this reduces exactly to `prefill_kv`
    over a padded bucket: real queries attend only real keys (mask
    key_pos <= start + i), so bit-identical KV and logits — the engine
    uses ONE program family for both fresh and prefix-hit admission.
    """
    c = config
    cos, sin = rope_freqs(c.head_dim, hist_k.shape[1], c.rope_theta)
    qpos = start + jnp.arange(tokens.shape[1])
    x, (ks, vs) = _trunk(c, params, tokens, (cos[qpos], sin[qpos]),
                         _History(hist_k, hist_v, start, qpos))
    return rms_norm(x, params["norm_f"], c.norm_eps), ks, vs


def prefill_kv(params: Dict[str, Any], tokens: jax.Array,
               config: LlamaConfig):
    """Prefill trunk: prompt [B, P] -> (normed hidden [B, P, D],
    per-layer pre-repeat ks/vs [L, B, P, n_kv, head_dim]).

    Shared by `prefill` (whole-cache fill) and the engine's seeding of
    the speculative draft's cache (serve/llm/engine.py) so both produce
    bit-identical KV for the same prompt."""
    c = config
    cos, sin = rope_freqs(c.head_dim, tokens.shape[1], c.rope_theta)
    x, (ks, vs) = _trunk(c, params, tokens, (cos, sin),
                         _NoCache(_get_attention_fn(c.attn_impl)))
    return rms_norm(x, params["norm_f"], c.norm_eps), ks, vs


def prefill(params: Dict[str, Any], tokens: jax.Array,
            config: LlamaConfig, max_len: Optional[int] = None):
    """Fill the cache from a prompt [B, P] in ONE batched forward pass
    (all prompt positions hit the MXU together; the per-layer pre-repeat
    k/v come out of the layer scan and land in the cache with a single
    dynamic_update_slice). Returns (last-token logits [B, V], cache)."""
    c = config
    B, P = tokens.shape
    S = max_len or c.max_seq_len

    x, ks, vs = prefill_kv(params, tokens, config)
    logits = _head(c, params, x[:, -1])

    cache = init_kv_cache(c, B, S)
    cache = {
        "k": lax.dynamic_update_slice(
            cache["k"], ks.astype(c.dtype), (0, 0, 0, 0, 0)),
        "v": lax.dynamic_update_slice(
            cache["v"], vs.astype(c.dtype), (0, 0, 0, 0, 0)),
    }
    return logits, cache


def generate(params: Dict[str, Any], prompt: jax.Array,
             config: LlamaConfig, max_new_tokens: int,
             temperature: float = 0.0,
             rng: Optional[jax.Array] = None) -> jax.Array:
    """Greedy (or temperature) generation, fully jit-compatible:
    prompt [B, P] -> [B, max_new_tokens]."""
    B, P = prompt.shape
    logits, cache = prefill(params, prompt, config,
                            max_len=P + max_new_tokens)
    rng = rng if rng is not None else jax.random.key(0)

    def sample(logits, key):
        if temperature == 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature).astype(jnp.int32)

    def body(carry, i):
        cache, logits, key = carry
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        pos = jnp.full((B,), P, jnp.int32) + i
        logits, cache = decode_step(params, cache, tok, pos, config)
        return (cache, logits, key), tok

    (_, _, _), toks = lax.scan(
        body, (cache, logits, rng), jnp.arange(max_new_tokens))
    return toks.T  # [B, max_new_tokens]


# ---------------------------------------------------------------------------
# The serving engine's view of this model (models/serving.py)
# ---------------------------------------------------------------------------

def _serve_prefill(params, tokens, start, hist, config, n_real):
    del n_real              # padding rows are masked as keys, not skipped
    x, ks, vs = prefill_kv_paged(params, tokens, start, hist["k"],
                                 hist["v"], config)
    return x, {"k": ks[:, 0].astype(config.dtype),
               "v": vs[:, 0].astype(config.dtype)}


def _serve_decode(params, pools, tables, tok, pos, config, active):
    logits, pools = decode_step_paged(params, pools, tables, tok, pos,
                                      config, active=active)
    return logits, pools, {}


def _serve_paged_attention(pools):
    from ray_tpu.ops import paged_attention

    return "kernel" if paged_attention.engages(pools["k"]) else "gather"


_SERVING = ServingFns(
    name="dense decoder (models/llama.py)",
    init_params=init_params, init_pool=init_paged_kv_cache,
    prefill=_serve_prefill, decode=_serve_decode,
    head_weight=lm_head_weight, quantize_int8=quantize_weights_int8,
    draft=DraftFns(init_kv_cache, prefill_kv, decode_step),
    verify=verify_kv_paged, paged_attention=_serve_paged_attention)
