"""What a model hands the serving engine: its functions over a paged
cache, reached through its config object (`config.serving()`), so that
`serve/llm/engine.py` names no model module.

The pool is a small tree (a flat dict) of leaves `[L, NB, bs, ...]` that
the model names: `k` and `v` rows per KV head for the dense decoder,
one `latent` row (latent ‖ rotary key) for latent attention.  The engine
and the cache manager move whole blocks of every leaf (insert scatter,
export, adopt, spill, promote) and never look inside a row.

A pool layer is not a model layer: `L` is what the model says it is.
`models/sambay.py` keeps ONE layer of the full kind that its one full
attention layer writes and eight of its layers read (the kernel's layer
index is 0 for all of them, and `plan` runs once a kind a tick).

Leaves are of one of two KINDS.  `full` (every leaf of every model but
two): a sequence holds a block for each `bs` of its rows, in a table as
wide as `max_seq_len`, block `t // bs` for position t.  `window`
(`window_kind(config) -> (W, leaf names)`, None for a model that has
none): leaves of layers whose query at p reads the keys p - W + 1 .. p
alone.  They have `[L', NBw, bs, ...]` blocks of their OWN, an allocator
and a table of their own, and that table is a RING, `ring = (W + largest
prefill bucket) / bs` blocks wide (never wider than `max_seq_len`):
position t lives in `table_w[(t // bs) % ring]`, and a sequence holds
`min(its blocks, ring)` of them whatever its length while its prompt
goes in, and once it decodes the `W / bs + 1` that the window before a
position straddles (`kv_cache.WindowRing.cover` gives the others back
and hands the block that fell out of the window to the position that
comes into it: the table stays `ring` wide).  A model with
window leaves gets both tables wherever it got one:
`init_pool(config, num_blocks, block_size, window_blocks=NBw)`,
`decode(..., tables={"full": [B, nb], "window": [B, ring]}, ...)`, and
in `prefill` the window leaves' history is the ring as it lies,
`{leaf: [L', ring * bs, ...]}`, row r holding the position that is r
modulo `ring * bs` (those of the last `ring * bs` positions before
`start` that were written; a chunk needs the W before it).  Nothing
that moves rows without knowing kinds (prefix reuse, spill, export,
adopt, preemption, speculation) is offered for such a model; a prompt
longer than a bucket goes into its slot chunk by chunk.

    init_pool(config, num_blocks, block_size) -> {leaf: [L, NB, bs, ...]}
    prefill(params, tokens [1, Pb], start, hist, config, n_real)
        -> (normed hidden [1, Pb, D], rows {leaf: [L, Pb, ...]})
        `hist` {leaf: [L, S_pad, ...]} is the slot's gathered history
        (rows >= start are stale); the Pb tokens sit at start..; only
        the first `n_real` of them are real.  The engine reads row
        `n_real - 1` of the hidden and nothing else of it, so a model
        may hand back that row ALONE, [1, 1, D] (the shape says which;
        `models/sambay.py` runs its second half for that one row).
    decode(params, pools, tables, tok [B], pos [B], config, active)
        -> (logits [B, V], pools, counts)
        `counts` is a dict of per-call integer counters that the engine
        sums on the device, from `init_counts(config)`'s zeros, and
        shows under `stats()["counters"]` (empty, and `init_counts`
        None, for a model that counts nothing).
    head_weight(params, config) -> [D, V]
    init_params(config, key), and what only some models have (None
    where a model has none, and the engine refuses by name):
    init_slot_state(config, num_slots) -> {leaf: [L', B, ...]}, a
    SECOND kind of state, of a fixed size a slot whatever the sequence's
    length: a row a slot a layer that keeps one (the delta-rule state
    and convolution tail of `models/kimi_linear.py` and
    `models/gdn_hybrid.py`, the convolution tail of
    `models/conv_moe.py`, the selective scan's state and tail of
    `models/sambay.py` and `models/jamba.py`, the Mamba-2 state and
    tail of `models/nemotron_h.py`: each but sambay's beside a `full`
    pool).  Every convolution tail lies `[L', B, (K-1) C]`, a slot's
    K-1 rows side by side in the lanes of its one row, and one step
    shifts a layer's rows where they lie (`ops/short_conv.py`).
    A model that has it takes and returns it beside the pool:
        prefill(..., n_real, state) -> (hidden, rows, state), `state`
        {leaf: [L', ...]} ONE slot's rows, as they stood after the
        sequence's first `start` tokens (zeros at start 0) in, after
        the last REAL token of this call out;
        decode(..., active, state) -> (logits, pools, counts, state),
        the whole tree, a dead slot's rows left as they were.
    The engine keeps such a model's state by slot and a sequence in the
    slot it was admitted to: nothing that moves rows without the state
    (prefix reuse, spill, export, adopt, preemption, speculation) is
    offered for it.  A model may have window leaves AND a state by slot
    (`models/sambay.py`): it gets both tables and the state wherever
    this says so, a chunk of its prompt hands ring, state and tails on
    in the slot, and a slot's release gives the ring back and leaves
    the state for the next admission to zero.
    quantize_int8(params); verify(params, pools, tables, tok [B, K],
    pos [B], config, active) -> (logits [B, K, V], pools), the
    speculative target's step; draft, what a model needs to BE a
    speculative draft (`DraftFns`); paged_attention(pools) -> "kernel"
    | "gather", which path `decode` compiles its attention to over
    these pools on this backend (`engine.stats()` shows it);
    grouped_matmul(config, num_slots) -> "kernel" | "xla", the same for
    the experts' grouped products at the tick's shape
    (`models/moe.py::serving_grouped_path`; what the products ARE is the
    parameters' to say: where an expert layer's tree has no `w_gate`,
    `dropless_moe` reads `w_up` [E, F, D] and `w_down` [E, F, D] and
    runs `w_down relu(w_up x)^2`, two products, `models/nemotron_h.py`);
    insert_attention(config, start, bucket, max_seq_len) -> (form,
    tiles run, tiles dense), host arithmetic for a model whose
    `prefill` walks its history through
    `models/window_moe.py::blockwise_attention`: "kernel" | "loop",
    which form a piece of `bucket` rows compiles its attention to on
    this backend, and of a piece at `start` the (query, key) tiles the
    kernel's bounds let through over those of the rectangles the loop
    multiplies (`window_moe.walk_tiles`; `engine.stats()` sums them).

A model that GENERATES BY BLOCKS (diffusion over blocks of L positions,
`models/blockdiff_moe.py`) hands the engine `block`, a `BlockFns`, and
the engine then runs no `decode`:
    block.spec(config) -> BlockSpec(length L, steps, remasking,
        confidence_threshold, mask_token_id): L a power of two that
        divides the pool's block; `steps` denoising steps fix L / steps
        positions each (the remainder to the first), by `remasking`:
        `low_confidence_dynamic` (every masked position whose confidence
        passes the threshold if those are at least the step's share,
        else the share of largest confidence), `low_confidence_static`
        (always the share of largest confidence), `sequential` (the
        leftmost).
    block.denoise(params, pools, tables, tok [B, L], pos0 [B], config,
        active, write) -> (normed hidden [B, L, D], pools, counts): ONE
        forward over the block at positions pos0 .. pos0 + L - 1, which
        writes the block's K/V rows there (`write` [B]: which slots do)
        and attends with every row seeing every key <= pos0 + L - 1.
        It applies NO head: the engine multiplies by `head_weight` the
        rows its rule can read, those still masked in a live slot whose
        block is open, R = three eighths of the B x L rows a pass and as
        many passes as they fill (`serve/llm/programs.py::
        _block_predict_rows`, `_block_pass_rows`).  The logits of a row
        are of the token AT its position (no shift).
    prefill(...) is handed the prompt's WHOLE blocks alone (`start` and
        `n_real` multiples of L) under the block-causal mask, key j
        visible to query i iff j // L <= i // L; its hidden is not read:
        an insert yields no token, and the prompt's trailing P mod L
        tokens open the slot's first block as fixed positions.
The engine's tick for such a model is one `denoise` over every live
slot whatever each slot's step, the head over the rows still masked,
then a slot EITHER fixes positions by the rule (its block had a masked
one) OR commits (it had none: the rows just written are final, pos0 +=
L, the block is masked anew); a block's tokens are emitted together
when the tick that fixed its last position lands.  A slot holds an OPEN
block between ticks (tokens, which are fixed, the step), on the device,
that nothing but the tick carries, so the engine refuses for such a
model, by name: `decode_block > 1`, a draft (speculation),
`prefill_only` and export, adopting a KVState, preemption, and prefix
reuse with the spill that rides it.
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Optional

# Rows of `hist` a prefill reads at a time where it walks its history
# (`models/latent_moe.py::_History.attend`): the insert's attention
# costs `start + Pb` rounded up to this, not S_pad.  A multiple of the
# chip's 128 lanes.  On the chip, one sublayer alone at LongCat's
# widths: 256 reads as 512 does at the 128-512 buckets and 0.71-0.76 of
# it at the 1024 and 2048 buckets (at 512 and 1024 a tile's float32
# scores no longer stay on the chip between the products); 128 reads
# as 256 and 1024 worse than 512 at every shape (PERF.md section 6,
# PR 45).  The
# engine counts what such a walk reads for every model
# (`stats()["insert_keys_walked"]`).
HISTORY_TILE = 256


class DraftFns(NamedTuple):
    """A speculative draft's own cache, one [S] stripe a slot and not
    paged (the draft is small; paging it would buy nothing):
    init_cache(config, B, S) -> {k, v: [L, B, S, ...]},
    prefill(params, tokens [1, Pb], config) -> (hidden, ks, vs), the
    rows of one slot's stripe, and decode(params, cache, tok [B],
    pos [B], config, active) -> (logits [B, V], cache)."""
    init_cache: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]


class BlockSpec(NamedTuple):
    """How a model generates by blocks (the module's docstring)."""
    length: int
    steps: int
    remasking: str
    confidence_threshold: float
    mask_token_id: int


class BlockFns(NamedTuple):
    """What a model that generates by blocks hands the engine beside
    `prefill` (the module's docstring)."""
    spec: Callable[..., BlockSpec]
    denoise: Callable[..., Any]


class ServingFns(NamedTuple):
    name: str
    init_params: Callable[..., Any]
    init_pool: Callable[..., Any]
    prefill: Callable[..., Any]
    decode: Callable[..., Any]
    head_weight: Callable[..., Any]
    init_counts: Optional[Callable[..., Any]] = None
    init_slot_state: Optional[Callable[..., Any]] = None
    window_kind: Optional[Callable[..., Any]] = None
    quantize_int8: Optional[Callable[..., Any]] = None
    draft: Optional[DraftFns] = None
    verify: Optional[Callable[..., Any]] = None
    paged_attention: Optional[Callable[..., str]] = None
    grouped_matmul: Optional[Callable[..., str]] = None
    insert_attention: Optional[Callable[..., Any]] = None
    block: Optional[BlockFns] = None
