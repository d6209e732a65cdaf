"""The engine's device half: the jitted programs and the arrays they
donate, in ONE object that the scheduler (`LLMEngine`, engine.py, whose
docstring says why the programs are few and of fixed shape) calls by
what it means. What a program takes, which arguments it donates and
which outputs replace them is known here and nowhere else: every
donating call is made by a method below, on the scheduler thread, and
hands back only what the host reads. The model's parameters are the
engine's, handed to each call and never kept.

Two forms, chosen once from what the model hands over (`programs_for`).
Either is built from `(model, model_config, engine_config)` alone and
places nothing before `allocate`, so the same object lowers its
programs on shapes (`lower`). A profiler trace and `jit_stats()` know
the programs by their families' names (`llm_engine_tick`, ...).
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from ray_tpu.observability import tracked_jit


def programs_for(model, model_config, config, draft_config=None):
    """The form of the programs that `model` (a `ServingFns`) runs."""
    form = BlockPrograms if model.block else Programs
    return form(model, model_config, config, draft_config)


class Programs:
    """The token form: a tick is `decode_block` decode steps of every
    live slot; an insert prefills a piece of a prompt and samples the
    token behind its last row."""

    def __init__(self, model, model_config, config, draft_config=None):
        self.model, self.model_config, self.config = (
            model, model_config, config)
        c = config
        # (W, leaf names) of a model with a window kind of pool leaves
        # (models/serving.py), else None
        window = (model.window_kind(model_config)
                  if model.window_kind else None)
        self.window = window[0] if window else None
        self.window_leaves = window[1] if window else ()
        # That kind's table a slot, in blocks (a ring holds the window
        # before a chunk and the chunk), and its pool's blocks.
        self.ring_blocks = self.window_blocks = None
        if window is not None:
            self.ring_blocks = min(
                -(-(self.window + c.prefill_buckets[-1]) // c.kv_block_size),
                c.max_blocks_per_slot)
            self.window_blocks = (c.num_window_blocks
                                  or c.num_slots * self.ring_blocks)
        # How a model that generates by blocks does (`BlockSpec`).
        self.block = model.block.spec(model_config) if model.block else None
        # The draft of speculative decoding keeps a cache of its own,
        # one [S] stripe a slot (models/serving.py `DraftFns`) — it is
        # tiny, so paging it would buy nothing.
        self.draft_config = draft_config
        self.draft_model = (draft_config.serving().draft
                            if draft_config is not None else None)

        # Compile tracking through the shared telemetry plane: the
        # TrackedJit probe runs ONLY when jax traces a new program, so
        # .traces counts compiled engine programs — the compile-guard
        # test asserts trace_count <= n_buckets + 1, and the recompile
        # detector warns if either program family exceeds its budget
        # (ONE tick, one insert per prefill bucket).
        self._wrap_tick_and_insert()
        # KV migration programs: block counts are data (padded
        # ids, out-of-bounds scatters dropped), so the adopt is ONE
        # trace and the export one per row length of `export_rows`.
        self._jit_export = tracked_jit(
            self._export_fn, name="llm_engine_export",
            trace_budget=len(c.export_rows))
        self._jit_adopt = tracked_jit(
            self._adopt_fn, name="llm_engine_adopt",
            trace_budget=1, donate_argnums=(0, 1, 2))
        if self.draft_model is not None:
            self._jit_spec = tracked_jit(
                self._spec_fn, name="llm_engine_spec",
                trace_budget=1, donate_argnums=(2, 3, 5, 6))
            self._jit_draft_insert = tracked_jit(
                self._draft_insert_fn,
                name="llm_engine_draft_insert",
                trace_budget=len(c.prefill_buckets),
                donate_argnums=(1,))

    def _wrap_tick_and_insert(self) -> None:
        # No fence inside a dispatch (it would drain the pipeline and
        # time two ticks as one): a sampled tick's wall is taken where
        # the scheduler waits for it anyway (`landed`).
        self._jit_tick = tracked_jit(
            self._tick_fn, name="llm_engine_tick", fence_samples=False,
            trace_budget=1, donate_argnums=(1, 3, 4, 9))
        self._jit_insert = tracked_jit(
            self._insert_fn, name="llm_engine_insert",
            trace_budget=len(self.config.prefill_buckets),
            donate_argnums=(1, 2, 3, 12))

    # ------------------------------------------------------- device state

    def _fresh(self, rng_seed: int = 0) -> Dict[str, Any]:
        """The device values an engine starts with, by the attribute
        each is kept under (fixed shapes for the engine's whole
        lifetime). Under `jax.eval_shape`: their shapes."""
        model, mc, c = self.model, self.model_config, self.config
        B = c.num_slots
        kinds = ({} if self.window is None
                 else {"window_blocks": self.window_blocks})
        fresh = {
            # the pool {leaf: [L, NB, bs, ...]}, and the second kind of
            # state {leaf: [L', B, ...]}: a row a slot a layer that
            # keeps one, None for a model whose whole state is rows in
            # the pool (models/serving.py)
            "_cache": model.init_pool(mc, c.pool_blocks, c.kv_block_size,
                                      **kinds),
            "_slot_state": (model.init_slot_state(mc, B)
                            if model.init_slot_state else None),
            "_tok": jnp.zeros((B,), jnp.int32),
            "_pos": jnp.zeros((B,), jnp.int32),
            "_key": jax.random.key(rng_seed),
            # What the model's decode step counts (models/serving.py),
            # summed on the device tick by tick. Not donated: `counters`
            # reads, maybe from another thread, those of the last tick
            # read back, while the next tick takes them on.
            "_counters": model.init_counts(mc) if model.init_counts else {},
        }
        if self.draft_model is not None:
            fresh["_draft_cache"] = self.draft_model.init_cache(
                self.draft_config, B, c.max_seq_len)
        return fresh

    def allocate(self, rng_seed: int = 0) -> None:
        vars(self).update(self._fresh(rng_seed))
        self._counters_read = self._counters

    def block_bytes(self, kind: str) -> int:
        """HBM bytes per block of `kind` ("full" or "window": every
        leaf's rows across all layers): the byte-accounting basis for
        allocator/prefix/tier stats."""
        total = sum(int(x.nbytes) for name, x in self._cache.items()
                    if (name in self.window_leaves) == (kind == "window"))
        return total // (self.window_blocks if kind == "window"
                         else self.config.pool_blocks)

    # ------------------------------------------- what the scheduler calls

    def insert(self, params, slot, row, hist_len, padded, suffix_len,
               scatter_ids, temperature, tail=()) -> None:
        """Dispatch the insert program of `padded`'s bucket: the piece
        into `slot`'s blocks `scatter_ids`, behind the `hist_len` rows
        that `row` names (`tail` is the block form's)."""
        (self._cache, self._tok, self._pos, self._key,
         *state) = self._jit_insert(
            params, self._cache, self._tok, self._pos, row,
            np.int32(hist_len), padded, np.int32(suffix_len),
            scatter_ids, np.int32(slot), np.float32(temperature),
            self._key, self._slot_state)
        if state:
            self._slot_state, = state

    def tick(self, params, tables, mask, temp):
        """Dispatch one tick over the slots of `mask`. Returns what the
        host reads back (here the tokens [K, B]), `TrackedJit`'s mark
        if this was a sampled call, and the model's counters as the
        tick leaves them."""
        (self._cache, self._tok, self._pos, self._key, out,
         self._counters, *state) = self._jit_tick(
            params, self._cache, tables, self._tok, self._pos, mask, temp,
            self._key, self._counters, self._slot_state)
        if state:
            self._slot_state, = state
        return (out,), self._jit_tick.take_sample(), self._counters

    def spec(self, params, draft_params, tables, mask):
        """Dispatch one speculative round; returns as `tick` does (the
        target's tokens [B, K] and the tokens emitted a slot [B])."""
        (self._cache, self._draft_cache, self._tok, self._pos,
         t, n_emit) = self._jit_spec(
            params, draft_params, self._cache, self._draft_cache,
            tables, self._tok, self._pos, mask)
        return (t, n_emit), None, self._counters

    def landed(self, counters, sample, wall_s: float) -> None:
        """A tick was read back: `counters` shows its own from now on,
        and a sampled tick's wall is the one the scheduler took."""
        if sample is not None:
            self._jit_tick.record_wall(sample, wall_s)
        self._counters_read = counters

    def counters(self) -> Dict[str, Any]:
        """The model's own counters on the host, summed on the device
        since start, as the last tick READ BACK left them: no caller (a
        metrics thread, a load generator) waits for the tick in
        flight."""
        return {name: np.asarray(x)
                for name, x in self._counters_read.items()}

    def draft_insert(self, draft_params, padded, slot) -> None:
        self._draft_cache = self._jit_draft_insert(
            draft_params, self._draft_cache, padded, np.int32(slot))

    def export(self, ids) -> Dict[str, Any]:
        """Dispatch the export gather over the blocks `ids` (at most
        `max_blocks_per_slot` of them), padded with block 0 to the
        smallest row of `export_rows` that holds them. Returns the
        device row {leaf: [L, row, bs, ...]}; its first len(ids)
        blocks are the ones asked for."""
        n = next(r for r in self.config.export_rows if r >= len(ids))
        row = np.zeros((n,), np.int32)
        row[:len(ids)] = ids
        return self._jit_export(self._cache, row)

    def adopt(self, blocks, ids, slot, tok, pos) -> None:
        """Scatter the host blocks {leaf: [L, n, bs, ...]} into the
        pool at `ids` [max_blocks_per_slot] (those past the n point one
        past the pool) and seed `slot`'s token and position."""
        nb = self.config.max_blocks_per_slot
        padded = {}     # the program's fixed shape: zeros after the n
        for name, x in blocks.items():
            padded[name] = np.zeros((x.shape[0], nb) + x.shape[2:], x.dtype)
            padded[name][:, :x.shape[1]] = x
        self._cache, self._tok, self._pos = self._jit_adopt(
            self._cache, self._tok, self._pos, padded, ids,
            np.int32(slot), np.int32(tok), np.int32(pos))

    def tokens(self):
        """Every slot's pending token [B], on the host."""
        return np.asarray(self._tok)

    def positions(self):
        """Every slot's position [B], on the host."""
        return np.asarray(self._pos)

    def slot_state(self, slot) -> Optional[Dict[str, Any]]:
        """`slot`'s rows of the model's per-slot state on the host
        (None for a model that keeps none)."""
        if self._slot_state is None:
            return None
        return {name: np.asarray(x[:, slot])
                for name, x in self._slot_state.items()}

    def traces(self) -> Dict[str, int]:
        """Traces by program family (the last two exist with a draft)."""
        return {name: getattr(self, f"_jit_{name}").traces
                for name in ("tick", "insert", "export", "adopt", "spec",
                             "draft_insert")
                if hasattr(self, f"_jit_{name}")}

    def stats(self) -> Dict[str, Any]:
        """What `LLMEngine.stats()` shows of the device half."""
        return {
            "traces": self.traces(),
            # which path the tick's attention compiled to: the model
            # says (by backend and shape alone); "gather" for a model
            # that has only that one
            "paged_attention": (self.model.paged_attention(self._cache)
                                if self.model.paged_attention else "gather"),
            "slot_state_bytes": sum(
                int(x.nbytes) for x in (self._slot_state or {}).values())}

    # ------------------------------------------------- lowering on shapes

    def shapes(self, sharding=None):
        """(the device values' shapes by attribute, `arg(dtype,
        *shape)`), every shape on `sharding` where one is given."""
        def arg(dtype, *shape):
            return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

        return jax.tree.map(lambda x: arg(x.dtype, *x.shape),
                            jax.eval_shape(self._fresh)), arg

    def lower(self, params, bucket: Optional[int] = None, sharding=None):
        """The tick or, given `bucket`, that bucket's insert, lowered on
        shapes alone (the device values' as `allocate` makes them, the
        host's arguments as the scheduler hands them, `params` as given;
        all on `sharding`, a described chip's say, where one is given):
        the program the engine runs, donation and all, under the body's
        own name and counted by nothing."""
        s, arg = self.shapes(sharding)
        c = self.config
        B, nb = c.num_slots, c.max_blocks_per_slot

        def by_kind(of):    # a table or ids: one, or one a kind
            if self.window is None:
                return of(nb)
            return {"full": of(nb), "window": of(self.ring_blocks)}

        if bucket is None:
            return self._jit_tick.unprobed().lower(
                params, s["_cache"], by_kind(lambda n: arg(jnp.int32, B, n)),
                s["_tok"], s["_pos"], arg(jnp.bool_, B), arg(jnp.float32, B),
                s["_key"], s["_counters"], s["_slot_state"])
        ids = arg(jnp.int32, bucket // c.kv_block_size)
        return self._jit_insert.unprobed().lower(
            params, s["_cache"], s["_tok"], s["_pos"],
            by_kind(lambda n: arg(jnp.int32, n)), arg(jnp.int32),
            arg(jnp.int32, bucket), arg(jnp.int32), by_kind(lambda n: ids),
            arg(jnp.int32), arg(jnp.float32), s["_key"], s["_slot_state"])

    # ------------------------------------------------------------ programs

    def _tick_fn(self, params, pools, tables, tok, pos, active, temp,
                 key, counters=None, state=None):
        """`decode_block` decode steps for all B slots in one dispatch
        (lax.scan — still ONE compiled program; the KV write/read goes
        through the block tables, which are data). Inactive slots are
        computed but masked: no KV write, token/pos parked. Positions
        clamp at S-1 so a slot finishing mid-block can speculate ahead
        without ever attending past rows it wrote itself; the host
        discards post-stop tokens. What the model's step counts is
        added to `counters` (an empty tree for a model that counts
        nothing: no operation, no argument). `state` is the per-slot
        state of a model that keeps one (None otherwise: no argument),
        advanced for the live slots."""
        decode = self.model.decode
        S = self.config.max_seq_len

        def body(carry, _):
            pools, tok, pos, key, counters, state = carry
            if state is None:
                logits, pools, counts = decode(
                    params, pools, tables, tok, pos, self.model_config,
                    active)
            else:
                logits, pools, counts, state = decode(
                    params, pools, tables, tok, pos, self.model_config,
                    active, state)
            counters = jax.tree.map(jnp.add, counters, counts)
            with jax.named_scope("sample"):
                key, sub = jax.random.split(key)
                nxt = _sample(logits, temp, sub)
                tok = jnp.where(active, nxt, tok)
                pos = jnp.where(active, jnp.minimum(pos + 1, S - 1), pos)
            return (pools, tok, pos, key, counters, state), tok

        (pools, tok, pos, key, counters, state), toks = jax.lax.scan(
            body, (pools, tok, pos, key, counters or {}, state), None,
            length=self.config.decode_block)
        out = (pools, tok, pos, key, toks, counters)         # toks [K, B]
        return out if state is None else out + (state,)

    def _of_kind(self, x, name):
        # a model with a window kind hands a table row and block ids a
        # kind (models/serving.py)
        if self.window is None:
            return x
        return x["window" if name in self.window_leaves else "full"]

    def _history(self, pools, table_row):
        """What an insert's prefill reads behind its piece: the slot's
        dense [S_pad] gather of every leaf (a window leaf's: its ring
        as it lies). Rows at and past the history's length are stale —
        masked inside the model's prefill."""
        return {name: pool[:, self._of_kind(table_row, name)].reshape(
            (pool.shape[0], -1) + pool.shape[3:])
            for name, pool in pools.items()}

    def _put_rows(self, pools, rows, new_block_ids):
        """rows: {leaf: [L, Pb, ...]} -> whole blocks into the pool at
        the slot's new physical ids (padding rows ride along; decode
        overwrites each before attending)."""
        bs = self.config.kv_block_size
        return {name: pool.at[:, self._of_kind(new_block_ids, name)].set(
            rows[name].astype(pool.dtype).reshape(
                (pool.shape[0], -1, bs) + pool.shape[3:]))
            for name, pool in pools.items()}

    def _insert_fn(self, params, pools, tok, pos, table_row, hist_len,
                   padded_suffix, suffix_len, new_block_ids, slot,
                   temperature, key, state=None):
        """Prefill the (possibly prefix-truncated) suffix of one prompt
        and scatter its KV into the slot's freshly-allocated blocks;
        sample the first generated token from the logits at the last
        REAL prompt position.

        The prefix-hit path IS the miss path: ``hist_len`` (dynamic
        data) tells the model's prefill where the suffix starts; a miss
        is just hist_len = 0 over an all-zero history. One trace per
        suffix bucket — the only static shapes are ``padded_suffix``
        [Pb] and ``new_block_ids`` [Pb / block_size], both functions of
        the bucket — so compile count stays <= len(prefill_buckets).

        A model with per-slot `state` gets this slot's rows as they
        stand after the `hist_len` tokens already inserted — zeros when
        there are none, which is how a slot is cleared at admission —
        and its rows after the last real token of this call are put
        back: the hand-off between the chunks of one prompt.
        """
        c = self.model_config
        hist = self._history(pools, table_row)
        if state is None:
            hidden, rows = self.model.prefill(
                params, padded_suffix[None], hist_len, hist, c, suffix_len)
        else:
            hidden, rows, mine = self.model.prefill(
                params, padded_suffix[None], hist_len, hist, c, suffix_len,
                {name: jnp.where(hist_len > 0, x[:, slot], 0)
                 for name, x in state.items()})
            state = {name: x.at[:, slot].set(mine[name].astype(x.dtype))
                     for name, x in state.items()}
        pools = self._put_rows(pools, rows, new_block_ids)
        # [1, Pb, D], or the last real row alone (models/serving.py)
        x_last = hidden[0, 0] if hidden.shape[1] == 1 else \
            jax.lax.dynamic_index_in_dim(
                hidden[0], suffix_len - 1, axis=0, keepdims=False)
        logits = jax.lax.dot_general(
            x_last[None], self.model.head_weight(params, c),
            (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)       # [1, V]
        key, sub = jax.random.split(key)
        first = _sample(logits, temperature[None], sub)[0]
        tok = tok.at[slot].set(first)
        pos = pos.at[slot].set(hist_len + suffix_len)
        out = (pools, tok, pos, key)
        return out if state is None else out + (state,)

    def _export_fn(self, pools, table_row):
        """Gather the blocks `table_row` names into dense {leaf: [L,
        len(table_row), bs, ...]} arrays (the host slices the valid
        prefix). Read-only on the pool; the ids are data and the row's
        length is a shape: one trace per length of
        `EngineConfig.export_rows` (see `_export_blocks`)."""
        return {name: pool[:, table_row] for name, pool in pools.items()}

    def _adopt_fn(self, pools, tok, pos, blocks, scatter_ids, slot,
                  new_tok, new_pos):
        """Scatter an imported KVState's blocks into the pool at this
        engine's freshly-allocated ids and seed the slot's token /
        position. ``scatter_ids`` is padded to max_blocks with the pool
        size (out-of-bounds scatters are dropped under jit), so ONE
        compiled program serves every valid-block count."""
        pools = {name: pool.at[:, scatter_ids].set(blocks[name])
                 for name, pool in pools.items()}
        tok = tok.at[slot].set(new_tok)
        pos = pos.at[slot].set(new_pos)
        return pools, tok, pos

    def _draft_insert_fn(self, draft_params, dcache, padded_prompt,
                         slot):
        """Prefill the draft model's cache stripe for one admitted slot
        (always the FULL padded prompt — the draft has no prefix cache;
        padding rows are stale but masked, and overwritten before they
        are attended). One trace per prompt bucket."""
        dc = self.draft_config
        _, ks, vs = self.draft_model.prefill(
            draft_params, padded_prompt[None], dc)
        return {
            "k": lax.dynamic_update_slice(
                dcache["k"], ks.astype(dc.dtype), (0, slot, 0, 0, 0)),
            "v": lax.dynamic_update_slice(
                dcache["v"], vs.astype(dc.dtype), (0, slot, 0, 0, 0)),
        }

    def _spec_fn(self, params, draft_params, pools, dcache, tables,
                 tok, pos, active):
        """One speculative round (greedy lanes only): the draft
        proposes spec_k - 1 tokens from its own cache, ONE paged
        verify step scores all spec_k inputs on the target, and the
        longest draft prefix agreeing with the target argmax is
        accepted. Every emitted token IS the target's argmax given
        correct inputs, so a round is token-identical to 1..spec_k
        plain ticks — a zero-accept round still emits the one token a
        plain tick would have. Rejected inputs leave stale rows past
        the new position in both caches; both are overwritten before
        ever being attended (the recycled-slot invariant)."""
        decode_step = self.draft_model.decode
        verify_kv_paged = self.model.verify
        c = self.config
        K = c.spec_k
        S = c.max_seq_len
        B = tok.shape[0]

        def draft_body(carry, _):
            dcache, dtok, dpos = carry
            dlogits, dcache = decode_step(
                draft_params, dcache, dtok, dpos, self.draft_config,
                active=active)
            nxt = jnp.argmax(dlogits, axis=-1).astype(jnp.int32)
            dtok = jnp.where(active, nxt, dtok)
            dpos = jnp.where(active, jnp.minimum(dpos + 1, S - 1), dpos)
            return (dcache, dtok, dpos), dtok

        (dcache, _, _), drafts = lax.scan(
            draft_body, (dcache, tok, pos), None, length=K - 1)
        # Verify inputs: the accepted stream so far ends at `tok`
        # (sampled, unconsumed); the draft continues it. [B, K]
        inputs = jnp.concatenate([tok[None], drafts], axis=0).T
        logits, pools = verify_kv_paged(
            params, pools, tables, inputs, pos, self.model_config,
            active=active)
        t = jnp.argmax(logits, axis=-1).astype(jnp.int32)    # [B, K]
        # Draft token j+1 survives iff the target's argmax after input
        # j equals it; acceptance is the leading run of agreements.
        agree = (t[:, :-1] == drafts.T).astype(jnp.int32)    # [B, K-1]
        acc = jnp.cumprod(agree, axis=1).sum(axis=1)         # 0..K-1
        n_emit = jnp.where(active, acc + 1, 0)
        new_tok = t[jnp.arange(B), jnp.maximum(n_emit, 1) - 1]
        tok = jnp.where(active, new_tok, tok)
        pos = jnp.where(active, jnp.minimum(pos + n_emit, S - 1), pos)
        return pools, dcache, tok, pos, t, n_emit


class BlockPrograms(Programs):
    """The block form, of a model that generates by blocks
    (`ServingFns.block`): the same two families of programs, where a
    tick is ONE forward over the L rows of every live slot's open block
    and an insert samples no token but opens the slot's first block."""

    def _wrap_tick_and_insert(self) -> None:
        self._jit_tick = tracked_jit(
            self._block_tick_fn, name="llm_engine_tick",
            fence_samples=False, trace_budget=1, donate_argnums=(1, 3))
        self._jit_insert = tracked_jit(
            self._block_insert_fn, name="llm_engine_insert",
            trace_budget=len(self.config.prefill_buckets),
            donate_argnums=(1, 2))

    def _fresh(self, rng_seed: int = 0) -> Dict[str, Any]:
        fresh = super()._fresh(rng_seed)
        del fresh["_tok"], fresh["_pos"]    # no token is pending: a block is
        B, L = self.config.num_slots, self.block.length
        # A slot's OPEN block: its tokens, which of them are fixed (a
        # flag, never `token == mask`: a prompt may hold the mask's id),
        # the denoising step, and the block's first position. Carried
        # from tick to tick on the device, by nothing but the tick.
        fresh["_blk"] = {
            "tok": jnp.full((B, L), self.block.mask_token_id, jnp.int32),
            "fixed": jnp.zeros((B, L), bool),
            "step": jnp.zeros((B,), jnp.int32),
            "pos0": jnp.zeros((B,), jnp.int32)}
        # what the block tick counts itself, beside the model's own
        fresh["_counters"] = dict(fresh["_counters"], **{
            name: jnp.zeros((), jnp.int32) for name in _BLOCK_COUNTERS})
        return fresh

    def insert(self, params, slot, row, hist_len, padded, suffix_len,
               scatter_ids, temperature, tail=()) -> None:
        """As `Programs.insert`. `tail`: the prompt's tokens behind the
        rows this piece completes (none before the last piece), which
        open the slot's first block; no token is sampled."""
        fixed = np.zeros((self.block.length,), np.int32)
        fixed[:len(tail)] = tail
        self._cache, self._blk = self._jit_insert(
            params, self._cache, self._blk, row, np.int32(hist_len),
            padded, np.int32(suffix_len), scatter_ids, np.int32(slot),
            fixed, np.int32(len(tail)))

    def tick(self, params, tables, mask, temp):
        """As `Programs.tick`; the host reads back the blocks' tokens
        [B, L] and which slots' blocks this tick completed [B]."""
        (self._cache, self._blk, self._key, out, done,
         self._counters) = self._jit_tick(
            params, self._cache, tables, self._blk, mask, temp, self._key,
            self._counters)
        return (out, done), self._jit_tick.take_sample(), self._counters

    def lower(self, params, bucket: Optional[int] = None, sharding=None):
        s, arg = self.shapes(sharding)
        c = self.config
        B, nb = c.num_slots, c.max_blocks_per_slot
        if bucket is None:
            return self._jit_tick.unprobed().lower(
                params, s["_cache"], arg(jnp.int32, B, nb), s["_blk"],
                arg(jnp.bool_, B), arg(jnp.float32, B), s["_key"],
                s["_counters"])
        return self._jit_insert.unprobed().lower(
            params, s["_cache"], s["_blk"], arg(jnp.int32, nb),
            arg(jnp.int32), arg(jnp.int32, bucket), arg(jnp.int32),
            arg(jnp.int32, bucket // c.kv_block_size), arg(jnp.int32),
            arg(jnp.int32, self.block.length), arg(jnp.int32))

    def _block_tick_fn(self, params, pools, tables, blk, active, temp, key,
                       counters):
        """The tick of a model that generates by blocks: ONE forward
        over the L rows of every live slot's open block
        (`ServingFns.block.denoise`), the same program whatever each
        slot's step. It writes the block's K/V rows into the pool at
        the block's positions and attends with every row seeing every
        key up to the block's last; then, a slot, EITHER fixes
        positions by the rule (the block had a masked position:
        `_block_predict_rows`, `_block_choose`; its rows were
        provisional and the next tick overwrites them) OR commits (it
        had none: the rows just written are final, the block moves on
        by L and is masked anew). The head's product and the softmax
        run over the rows the rule can read and over no other: those
        still masked in a live slot whose block is open, known from the
        tick's arguments before the forward, `_block_pass_rows` of them
        a pass and as many passes as they fill (none on a tick where
        every live slot commits). Returns the block's tokens as the
        tick leaves them [B, L] and which slots' blocks this tick
        COMPLETED (fixed their last masked position) [B]: those the
        host emits."""
        spec = self.block
        L, S = spec.length, self.config.max_seq_len
        tok, fixed, step, pos0 = (blk[k] for k in
                                  ("tok", "fixed", "step", "pos0"))
        masked = ~fixed
        is_open = masked.any(-1)                # else: nothing left to fix
        fixing, committing = active & is_open, active & ~is_open
        hidden, pools, counts = self.model.block.denoise(
            params, pools, tables, tok, pos0, self.model_config, active,
            _block_writes(active, is_open))
        key, sub = jax.random.split(key)
        x0, conf, passes = _block_predict_rows(
            hidden, self.model.head_weight(params, self.model_config),
            masked & fixing[:, None], temp, sub, spec.mask_token_id)
        with jax.named_scope("unmask"):
            share = _block_share(step, spec)
            pick = _block_choose(conf, masked, share, spec)
            pick = pick & fixing[:, None]
            tok = jnp.where(pick, x0, tok)
            fixed = fixed | pick
            done = fixing & fixed.all(-1)
            out, mine = tok, committing[:, None]
            blk = {"tok": jnp.where(mine, spec.mask_token_id, tok),
                   "fixed": jnp.where(mine, False, fixed),
                   "step": jnp.where(committing, 0, step + fixing),
                   "pos0": jnp.where(committing,
                                     jnp.minimum(pos0 + L, S - L), pos0)}
            n_pick = pick.sum(-1, dtype=jnp.int32)
            counts = dict(
                counts,
                block_forwards=active.sum(dtype=jnp.int32),
                block_commits=committing.sum(dtype=jnp.int32),
                block_tokens_fixed=n_pick.sum(),
                block_threshold_fixes=jnp.where(
                    fixing, n_pick - jnp.minimum(
                        share, masked.sum(-1, dtype=jnp.int32)), 0).sum(),
                head_passes=passes,
                head_rows_walked=passes * _block_pass_rows(*masked.shape),
                head_rows_dense=jnp.asarray(masked.size, jnp.int32))
        counters = jax.tree.map(jnp.add, counters, counts)
        return pools, blk, key, out, done, counters

    def _block_insert_fn(self, params, pools, blk, table_row, hist_len,
                         padded_suffix, suffix_len, new_block_ids, slot,
                         tail, tail_len):
        """The insert of a model that generates by blocks: the prompt's
        WHOLE blocks (a piece of them: `padded_suffix` [Pb], the first
        `suffix_len` real, at `hist_len`..; both multiples of the block
        length) go through the model's block-causal prefill and their
        rows into the slot's blocks, as `_insert_fn` puts them. It
        yields NO token: the slot's open block is set to the prompt's
        trailing tokens (`tail` [L], the first `tail_len` of them),
        fixed, and mask tokens behind them, at step 0 and at the
        position behind the rows now in."""
        hist = self._history(pools, table_row)
        _, rows = self.model.prefill(
            params, padded_suffix[None], hist_len, hist, self.model_config,
            suffix_len)
        pools = self._put_rows(pools, rows, new_block_ids)
        ours = jnp.arange(self.block.length) < tail_len
        blk = {"tok": blk["tok"].at[slot].set(
                   jnp.where(ours, tail, self.block.mask_token_id)),
               "fixed": blk["fixed"].at[slot].set(ours),
               "step": blk["step"].at[slot].set(0),
               "pos0": blk["pos0"].at[slot].set(hist_len + suffix_len)}
        return pools, blk


# What the block tick counts beside the model's own counters: live
# slot-forwards, those that committed a block, positions fixed by a
# denoising step, and those of them that the confidence threshold fixed
# beyond the step's share; the passes of the head over the rows still
# masked (`_block_predict_rows`), the rows they multiplied (passes x
# `_block_pass_rows`) and the rows a head over every slot's block would
# have (slots x L a tick).
_BLOCK_COUNTERS = ("block_forwards", "block_commits", "block_tokens_fixed",
                   "block_threshold_fixes", "head_passes",
                   "head_rows_walked", "head_rows_dense")


def _block_writes(active, is_open):
    """Which slots' forwards write their block's rows into the pool:
    every live one. A denoising step's rows are provisional (the next
    tick overwrites them, and no query reads past its own block); the
    commit's, computed from the block's final tokens, are the ones that
    stay."""
    del is_open
    return active


def _block_share(step, spec):
    """Positions a denoising step fixes at least [B]: L / steps spread
    evenly, the remainder to the first steps (the family's
    `get_num_transfer_tokens`)."""
    base, rem = divmod(spec.length, spec.steps)
    table = jnp.asarray([base + (i < rem) for i in range(spec.steps)],
                        jnp.int32)
    return table[jnp.minimum(step, spec.steps - 1)]


def _block_pass_rows(slots, length):
    """R, the rows one pass of the block tick's head multiplies of the
    `slots x length` a tick forwards: THREE EIGHTHS of them in whole
    tiles of 128 rows (all of them where they are fewer than a tile),
    from shapes alone.  A live slot holds a masked position in half its
    rows over a block's steps (L / 2), so an engine three quarters
    full or less needs one pass, a full one two, and every slot at step
    0 at once three, which cost less than a head over all rows.  A pass
    costs a fixed part (the head's weight is read once a pass) and a
    part by the row, so few large passes beat many small ones until a
    pass is mostly spare rows: on a v5e at 1,024 rows x 2,048 x 151,936,
    head + unmask a tick read 2.66, 2.71, 1.98, 2.50 ms at R = 128, 256,
    384, 512 with 134-146 of 256 slots live and 3.74, 3.21, 3.93, 2.78
    with 237 (6.47 and 6.40 over all rows: PERF.md section 6, PR 56)."""
    rows = slots * length
    return min(rows, -(-3 * rows // (8 * 128)) * 128)


def _block_predict_rows(hidden, head, need, temp, key, mask_id):
    """The head's product and `_block_predict` over the rows of hidden
    [B, L, D] that `need` [B, L] names, and over no other: their flat
    indices in row order, R = `_block_pass_rows(B, L)` of them a PASS
    and `ceil(needed / R)` passes (a loop whose trip count is data: none
    where nothing is needed), each gathering R rows, multiplying them
    by head [D, V] into float32 [R, V] and scattering what
    `_block_predict` makes of them, at the temperature temp [B] of each
    row's slot, back to [B, L].  A row of a matrix product does not
    depend on the rows beside it, so a needed row's x0 and confidence
    are those of a product over all B x L rows; a row not needed keeps
    x0 0 and confidence 0, which `_block_choose` never reads (`need`
    holds every masked position of every slot that fixes).  Returns
    (x0 [B, L] int32, confidence [B, L] float32, passes)."""
    B, L, D = hidden.shape
    N, R = B * L, _block_pass_rows(B, L)
    P = -(-N // R)                                          # passes at most
    hidden, need = hidden.reshape(N, D), need.reshape(N)
    rank = jnp.cumsum(need, dtype=jnp.int32)
    # a row not needed lands past the last pass; a pass's spare rows
    # name row N, which is no row
    rows = jnp.full((P * R,), N, jnp.int32).at[
        jnp.where(need, rank - 1, P * R)].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop",
        unique_indices=True).reshape(P, R)

    def one_pass(p, out):
        x0, conf = out
        at = rows[p]
        with jax.named_scope("head"):
            logits = jax.lax.dot_general(
                hidden[jnp.minimum(at, N - 1)], head,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)         # [R, V]
        with jax.named_scope("unmask"):
            # a spare row is greedy: it sends no pass into the sampler
            mine, sure = _block_predict(
                logits,
                jnp.where(at < N, temp[jnp.minimum(at // L, B - 1)], 0),
                jax.random.fold_in(key, p), mask_id)
            return (x0.at[at].set(mine, mode="drop"),
                    conf.at[at].set(sure, mode="drop"))

    passes = -(-rank[-1] // R)
    x0, conf = jax.lax.fori_loop(
        0, passes, one_pass,
        (jnp.zeros((N,), jnp.int32), jnp.zeros((N,), jnp.float32)))
    return x0.reshape(B, L), conf.reshape(B, L), passes


def _block_predict(logits, temp, key, mask_id):
    """logits [R, V] float32 -> (x0 [R] int32, its probability [R]
    float32 under the float32 softmax over the vocabulary): a row's
    argmax where its temp [R] is 0, else a sample at that temperature
    and its probability under the softmax at that temperature. The
    mask token's own column is never predicted (-inf). A row's draw
    comes from `key` over the [R, V] rows it is handed with (a pass of
    `_block_predict_rows`): the distribution of a draw over all slots'
    rows at once, not the same draw."""
    V = logits.shape[-1]
    logits = jnp.where(jnp.arange(V) == mask_id, -jnp.inf, logits)

    def greedy(logits):
        top = jnp.max(logits, axis=-1)
        x0 = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        z = jnp.sum(jnp.exp(logits - top[..., None]), axis=-1)
        return x0, 1.0 / z

    def sampled(logits):
        scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
        x = jax.random.categorical(key, scaled).astype(jnp.int32)
        lp = jnp.take_along_axis(jax.nn.log_softmax(scaled, axis=-1),
                                 x[..., None], axis=-1)[..., 0]
        g, c = greedy(logits)
        hot = temp > 0
        return jnp.where(hot, x, g), jnp.where(hot, jnp.exp(lp), c)

    return jax.lax.cond(jnp.any(temp > 0), sampled, greedy, logits)


def _block_choose(conf, masked, share, spec):
    """Which masked positions a denoising step fixes [B, L] bool, by
    `spec.remasking`: `sequential` the leftmost `share`;
    `low_confidence_static` the `share` of largest confidence;
    `low_confidence_dynamic` every masked position whose confidence
    passes the threshold if those are at least `share`, else as
    static. Ties go to the left."""
    L = conf.shape[-1]
    at = jnp.arange(L)
    score = (-at.astype(jnp.float32) * jnp.ones_like(conf)
             if spec.remasking == "sequential" else conf)
    score = jnp.where(masked, score, -jnp.inf)
    a, b = score[..., :, None], score[..., None, :]     # a: mine, b: other
    ahead = (b > a) | ((b == a) & (at[None, :] < at[:, None]))
    pick = masked & (ahead.sum(-1) < share[:, None])
    if spec.remasking == "low_confidence_dynamic":
        high = masked & (conf > spec.confidence_threshold)
        pick = jnp.where((high.sum(-1) >= share)[:, None], high, pick)
    return pick


def _sample(logits, temp, key):
    """Per-row sampling: greedy where temp == 0, else temperature
    categorical. Both branches are computed (fixed shape); `where`
    selects."""
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temp, 1e-6)[:, None]
    sampled = jax.random.categorical(key, scaled).astype(jnp.int32)
    return jnp.where(temp > 0, sampled, greedy)
