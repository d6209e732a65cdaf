"""Continuous-batching LLM inference engine — the Serve-on-TPU data plane.

The serving problem on TPU is a compile-boundary problem: XLA programs
are shape-specialized, so a naive server that launches one `generate`
per request (or per ad-hoc batch) either retraces constantly or decodes
in lockstep where every short request pays for the longest one
(models/llama.py::generate — the static path this engine replaces).
Podracer (arXiv:2104.06272) and RLAX (arXiv:2512.06392) both land on
the same answer: keep ONE fixed-shape compiled program fed continuously.

Design — a bounded set of compiled programs, everything else is data:

- A fixed pool of ``B = num_slots`` decode slots over ONE paged KV
  pool: ``pool_blocks`` blocks of ``kv_block_size`` rows, shared by all
  slots through per-slot block tables (serve/llm/kv_cache.py), so a
  short request does not reserve ``max_seq_len`` rows and a prompt
  prefix that is already in the pool is not prefilled again (the prefix
  cache). The pool's leaves are the model's (models/serving.py); the
  engine moves whole blocks and never looks inside a row. Tables,
  positions, last tokens and the active mask are arrays of fixed shape:
  data, so who owns which block retraces nothing.
- ONE jitted decode tick advances all live slots together (the model's
  paged decode step with the slot-active mask: dead slots ride through
  the program but their KV writes are dropped). The tick runs
  `decode_block` steps per dispatch through an internal lax.scan —
  still one compiled program — so the host's per-tick work (dispatch,
  token readback, slot bookkeeping) is paid once per block, not once
  per token.
- The plain tick is software-pipelined ONE deep: a step dispatches tick
  k while tick k-1 still runs, THEN waits for tick k-1, reads it back
  and emits its tokens, so the chip has its next tick queued while the
  host does its own work of a tick. Everything tick k takes from tick
  k-1 is a device array chained from output to input; what the host
  has to know one tick early it counts (`_rows`: the rows dispatched a
  slot, against `_row_limit`, say which slots tick k-1 finishes by
  `max_tokens` or the sequence limit; a slot that ends by `eos_id` or a
  stop token is found one tick late, computed once more, and its row
  of the tick in flight dropped: a row is emitted only to the handle
  that held the slot when the tick was dispatched). Whatever needs the
  emitted tokens and the device state to agree (a checkpoint, an
  adoption, a control call, a speculative round, an engine with no slot
  left to tick, `drain`, `run`'s exit) first reads the tick in flight
  back: `_settle`, the one such point.
- Jitted prefill at a small set of padded prompt-length buckets: the
  insert prefills the part of the prompt the prefix cache does not hold
  over the slot's gathered history and scatters its rows into the
  slot's new blocks. One compiled program per bucket, so a mixed
  workload traces ``len(prefill_buckets) + 1`` programs; migration,
  spill and promotion add the export gather (one per row length of
  `export_rows`) and ONE adopt scatter, speculation one round program
  and a draft insert per bucket. `trace_count` exposes the number for
  the compile-guard test.
- Slot eviction/recycling is host-side bookkeeping: EOS / stop-token /
  max_tokens free the slot and its blocks, the next queued request
  prefills into it; when the pool is exhausted a request waits at the
  head of its lane. Stale rows beyond a sequence's position are
  harmless — decode masks positions > pos and overwrites each position
  before ever attending to it.

Greedy decoding is token-identical to per-request
`models.llama.generate`: padding columns contribute exact zeros through
the masked softmax, so bucket-padded prefill and the block-table
decode reproduce the static path bit-for-bit (pinned by
tests/test_serve_llm.py::test_greedy_parity_*).
"""

from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from collections import deque
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from ray_tpu.observability.profiling import trace_live, trace_span


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Shapes of the engine's compiled programs (all static)."""

    num_slots: int = 8              # B: concurrent sequences in flight
    max_seq_len: int = 512          # S: longest sequence a slot can hold
    # Padded prompt lengths; a prompt compiles into the smallest bucket
    # that holds it. Keep this SHORT — each bucket is one XLA program.
    prefill_buckets: Tuple[int, ...] = (32, 64, 128)
    eos_id: Optional[int] = None    # config-level end-of-sequence token
    # Decode steps per tick dispatch (lax.scan inside the ONE tick
    # program). >1 pays the host's per-tick work (dispatch, readback,
    # slot bookkeeping) once per block instead of once per token, at the
    # cost of up to K-1 speculative tokens per finished slot (computed,
    # then discarded host-side; parity is unaffected because truncation
    # happens at the same stop condition single-stepping would hit) and
    # admission latency of one block. Since the tick is pipelined (PR
    # 41) the device no longer idles during that work, so a block only
    # pays where the host's work of a tick outlasts the tick; the
    # measured pair is in ROADMAP.md, Design D4.
    decode_block: int = 1
    # The one KV layout: a fixed pool of [kv_block_size]-row blocks
    # shared by all slots through per-slot block tables. The field has
    # one legal value and stays only because the benchmark's cell files
    # pass it (ROADMAP D14).
    kv_layout: str = "paged"
    kv_block_size: int = 16
    # Pool size; None -> num_slots * (max_seq_len / kv_block_size):
    # every slot can reach max_seq_len at once. Undersize it to
    # oversubscribe HBM: admission queues on exhaustion, never crashes.
    num_kv_blocks: Optional[int] = None
    # Blocks of a model's WINDOW kind of pool leaves (models/serving.py;
    # unused by a model that has none); None -> num_slots rings.
    num_window_blocks: Optional[int] = None
    prefix_cache: bool = True       # prompt-prefix reuse
    # Speculative decoding (armed by constructing the engine with
    # draft_params/draft_config): the draft proposes
    # spec_k - 1 tokens per round, one paged verify step accepts the
    # longest target-agreeing prefix — 1..spec_k tokens per round with
    # greedy parity by construction.
    spec_k: int = 4
    # Batch-lane preemption hysteresis: interactive pressure must hold
    # preempt_hold_s before a batch decode is checkpointed, and grants
    # are spaced by preempt_cooldown_s (observability/control.py gate).
    preempt_hold_s: float = 0.25
    preempt_cooldown_s: float = 1.0
    # Tiered KV spill (kv_cache.KVTierManager): prefix-cache evictions
    # gather their HBM rows into a host-RAM tier (object-store overflow
    # when a cluster is attached) and re-admissions promote them back
    # through the adopt scatter when the PromoteCostModel favors the
    # transfer over recompute. None -> on with `prefix_cache` (both
    # migration programs already exist; the spill runs the export
    # gather at the row lengths of `export_rows`, one trace each, all
    # compiled by `warmup()`).
    kv_spill: Optional[bool] = None
    kv_host_tier_bytes: int = 256 * 1024 * 1024     # the host tier's budget
    # PromoteCostModel, milliseconds: a promote's dispatch, a promoted
    # block's transfer, and a prompt token's recompute on the other side.
    kv_adopt_cost_fixed_ms: float = 2.0
    kv_adopt_cost_per_block_ms: float = 0.1
    kv_prefill_cost_per_token_ms: float = 0.05

    def __post_init__(self):
        if self.decode_block < 1:
            raise ValueError("decode_block must be >= 1")
        if not self.prefill_buckets:
            raise ValueError("need at least one prefill bucket")
        if self.spec_k < 2:
            raise ValueError("spec_k must be >= 2 (one draft proposal "
                             "plus the bonus target token)")
        b = tuple(sorted(set(int(x) for x in self.prefill_buckets)))
        object.__setattr__(self, "prefill_buckets", b)
        if b[-1] > self.max_seq_len:
            raise ValueError(
                f"largest prefill bucket {b[-1]} exceeds max_seq_len "
                f"{self.max_seq_len}")
        if self.kv_layout != "paged":
            raise ValueError(
                f"kv_layout={self.kv_layout!r}: the engine has one KV "
                f"layout, 'paged' (the dense per-slot stripe was removed "
                f"at PR 28)")
        if self.kv_spill is None:
            object.__setattr__(self, "kv_spill", self.prefix_cache)
        elif self.kv_spill and not self.prefix_cache:
            raise ValueError(
                "kv_spill requires prefix_cache=True (the spill hook "
                "rides prefix-cache eviction)")
        bs = self.kv_block_size
        if bs < 1:
            raise ValueError("kv_block_size must be >= 1")
        if self.max_seq_len % bs:
            raise ValueError(
                f"max_seq_len {self.max_seq_len} must be a multiple "
                f"of kv_block_size {bs} (block tables tile the "
                f"sequence exactly)")
        bad = [x for x in b if x % bs]
        if bad:
            raise ValueError(
                f"prefill buckets {bad} must be multiples of "
                f"kv_block_size {bs} (suffix KV scatters whole "
                f"blocks)")
        if self.num_kv_blocks is not None and self.num_kv_blocks < 1:
            raise ValueError("num_kv_blocks must be >= 1")
        if self.num_window_blocks is not None and self.num_window_blocks < 1:
            raise ValueError("num_window_blocks must be >= 1")

    @property
    def max_blocks_per_slot(self) -> int:
        return self.max_seq_len // self.kv_block_size

    @property
    def export_rows(self) -> Tuple[int, ...]:
        """Row lengths (in blocks) the export gather is traced at:
        powers of two from 16 below `max_blocks_per_slot`, then
        `max_blocks_per_slot` itself. An export pads its block ids to
        the smallest of these that holds them, so it moves at most
        twice the blocks it was asked for (and never under 16)."""
        nb, n, rows = self.max_blocks_per_slot, 16, []
        while n < nb:
            rows.append(n)
            n *= 2
        return tuple(rows) + (nb,)

    @property
    def pool_blocks(self) -> int:
        if self.num_kv_blocks is not None:
            return self.num_kv_blocks
        return self.num_slots * self.max_blocks_per_slot


@dataclasses.dataclass
class Request:
    """One generation request (token-id domain; tokenization is the
    caller's concern)."""

    prompt: Sequence[int]
    max_tokens: int = 64
    temperature: float = 0.0
    stop: Tuple[int, ...] = ()      # tokens that halt WITHOUT being emitted
    # Streaming hook: called as on_token(request_id, token_id) from the
    # engine loop as each token lands.
    on_token: Optional[Callable[[int, int], None]] = None
    # SLO lane: "interactive" requests are admitted first and, under
    # pressure, may preempt "batch" decodes (whose checkpoints resume
    # later — see LLMEngine.preempt).
    slo: str = "interactive"
    # Stop after prefill + the first sampled token and export the KV
    # state (handle.kv_state) instead of decoding — the disaggregated
    # prefill tier's mode (serve/llm/disagg).
    prefill_only: bool = False
    # Admit a prompt longer than the largest bucket piece by piece: the
    # request takes its slot and its blocks with the first piece and
    # one piece goes in a scheduler step, so other admissions
    # interleave instead of stalling behind one long prefill.
    chunked_prefill: bool = False
    # Cost-accounting identity: whose ledger row this request bills to
    # (observability/accounting.py). The schema is ready for the
    # multiplexing roadmap item; until then callers that don't care
    # all bill to "default".
    tenant: str = "default"


class RequestHandle:
    """Host-side view of a submitted request; completion is an Event."""

    def __init__(self, request_id: int, request: Request):
        self.request_id = request_id
        self.request = request
        self.tokens: List[int] = []
        self.submitted_at = time.monotonic()
        self.admitted_at: Optional[float] = None
        self.first_token_at: Optional[float] = None
        self.finished_at: Optional[float] = None
        # Wall-clock mirror of submitted_at: lifecycle spans need
        # epoch timestamps (timeline rows), latency math stays
        # monotonic.
        self.submitted_wall = time.time()
        # "eos" | "stop" | "length" | "prefill" | "cancelled"
        self.finish_reason: Optional[str] = None
        # Exported KV checkpoint (kv_cache.KVState): set by prefill_only
        # completion and by preemption; consumed by submit_adopted /
        # readmission.
        self.kv_state: Optional[Any] = None
        # Prompt positions THIS engine actually prefilled (suffix after
        # prefix-cache hits and tier promotes; summed across chunks).
        # len(prompt) - prefilled_tokens is the prefill work avoided —
        # the cost meter's `prefill_tokens_avoided`.
        self.prefilled_tokens = 0
        # Request-scoped tracing: the TraceContext active on the
        # submitting thread (the replica's llm.server_call span) plus a
        # pre-allocated span id for this request's llm.request span —
        # the scheduler thread records phases with no ambient context,
        # so kv.promote / kv.migrate / phase spans all parent under the
        # same explicit id.
        self.trace: Optional[Any] = None
        self.trace_span_id: Optional[str] = None
        # Cost accounting (observability/accounting.py): attached at
        # submit when the plane is enabled, integrated by the scheduler
        # thread, finalized + folded at finish. None when disabled.
        self.meter: Optional[Any] = None
        self._done = threading.Event()
        self._engine: Optional["LLMEngine"] = None
        # Where the prompt's pieces end (one piece for a prompt that
        # fits a bucket), and how many of its rows lie in its slot:
        # None until it has one (`LLMEngine._admit_piece`).
        self._piece_ends: List[int] = []
        self._prompt_rows: Optional[int] = None
        # the rows its inserts put in (`LLMEngine._rows_in`)
        self._rows_in = len(request.prompt)
        self._adopted_submit = False   # arrived via submit_adopted

    def done(self) -> bool:
        return self._done.is_set()

    @property
    def engine(self) -> Optional["LLMEngine"]:
        """The engine this request was submitted to (its `stats()`)."""
        return self._engine

    def cancel(self) -> bool:
        """Cancel the request: queued handles finish immediately with
        finish_reason "cancelled"; a handle live in a decode slot is
        torn down by the scheduler thread at its next step boundary,
        releasing the slot's paged blocks and prefix-cache refs (the
        reclaim path for client-abandoned requests). Returns False if
        the request already finished."""
        if self._done.is_set() or self._engine is None:
            return False
        return self._engine.cancel(self)

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished in {timeout}s")
        return self.tokens

    # Latency accounting (seconds).
    @property
    def ttft_s(self) -> Optional[float]:
        if self.first_token_at is None:
            return None
        return self.first_token_at - self.submitted_at

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean per-output-token latency after the first token."""
        if self.finished_at is None or self.first_token_at is None:
            return None
        n = len(self.tokens)
        if n <= 1:
            return 0.0
        return (self.finished_at - self.first_token_at) / (n - 1)


class _Slot:
    __slots__ = ("handle", "uses")

    def __init__(self):
        self.handle: Optional[RequestHandle] = None
        self.uses = 0


class _LoopClock:
    """Where the scheduler thread's time goes, always on: seconds on
    `time.monotonic()` and calls of each phase of `_step` and `run`,
    read through `stats()["loop"]`. `phase("llm_engine.<name>")` is the
    ONE way a phase is opened: it opens that span of a profiler trace
    and adds the elapsed time to `<name>`'s counter, so the two have
    the same boundaries. A phase opened inside another (a landing
    before a tier lookup inside `admit`, a `settle` inside `ctrl`, the
    `tick_ready`, `tick_readback` and `emit` of a `settle`) stops the
    outer one's clock until it closes: every instant is counted once,
    under the innermost phase open, and every phase counts its calls.
    `now` is the clock's last reading. Beside them `ticks` (dispatched),
    `overlapped` (those dispatched while another was in flight) and
    `settles` (times the tick in flight was read back out of turn, by
    cause). `warmup` starts it anew, so that it holds no compile.
    Scheduler thread only."""

    PHASES = ("ctrl", "admit", "first_token_wait", "settle",
              "tick_dispatch", "spill_land", "tick_ready", "tick_readback",
              "emit", "gauges", "idle")
    # who reads the tick in flight back out of turn (`LLMEngine._settle`)
    SETTLES = ("preempt", "ctrl", "adopt", "prefill_only", "spec", "empty",
               "drain", "stop")

    def __init__(self):
        self.steps = self.ticks = self.overlapped = 0
        self.settles = dict.fromkeys(self.SETTLES, 0)
        self.seconds = dict.fromkeys(self.PHASES, 0.0)
        self.calls = dict.fromkeys(self.PHASES, 0)
        self.now = time.monotonic()
        self._open: List[str] = []      # innermost last

    def phase(self, span: str, **args) -> "_Phase":
        return _Phase(self, span.rpartition(".")[2],
                      trace_span(span, **args))

    def _read(self) -> None:
        """Lay the time since the last reading to the innermost phase."""
        t = time.monotonic()
        if self._open:
            self.seconds[self._open[-1]] += t - self.now
        self.now = t

    def stats(self) -> Dict[str, Any]:
        return {"steps": self.steps, "ticks": self.ticks,
                "overlapped": self.overlapped,
                "settles": dict(self.settles),
                "seconds": dict(self.seconds), "calls": dict(self.calls)}


class _Phase:
    """One `with` of `_LoopClock.phase`; yields the span."""

    __slots__ = ("_clock", "_name", "_span")

    def __init__(self, clock, name, span):
        self._clock, self._name, self._span = clock, name, span

    def __enter__(self):
        span = self._span.__enter__()
        self._clock._read()
        self._clock._open.append(self._name)
        return span

    def __exit__(self, *exc):
        c = self._clock
        c._read()
        c._open.pop()
        c.calls[self._name] += 1
        return self._span.__exit__(*exc)


class _Tick:
    """A dispatched tick (or speculative round) that has not been read
    back: its output arrays, the slots that were live in it with the
    handle each held THEN (a slot released and filled again meanwhile
    must not get the old request's row), the clock's reading when its
    dispatch began, `TrackedJit`'s mark if it is a sampled call, and
    the model's counters as the tick leaves them."""

    __slots__ = ("outs", "live", "handles", "at", "spec", "sample",
                 "counters")

    def __init__(self, outs, live, handles, at, spec, sample, counters):
        self.outs, self.live, self.handles = outs, live, handles
        self.at, self.spec, self.sample = at, spec, sample
        self.counters = counters


class LLMEngine:
    """Slot-based continuous-batching engine over one model's
    parameters; the model's functions come from its config object
    (`model_config.serving()`, models/serving.py).

    The host-side scheduler. The jitted device programs (an insert per
    prefill bucket, ONE tick for the decode step, ...) and the arrays
    they donate are its `Programs` object's (serve/llm/programs.py),
    called by what each call means; `params` stay here and are handed
    to each call. Thread model: `submit()` is thread-safe;
    `step()`/`run()` must be driven by a single scheduler thread
    (serve/llm/deployment.py runs one per replica).
    """

    def __init__(self, params: Any, model_config: Any,
                 engine_config: Optional[EngineConfig] = None,
                 rng_seed: int = 0,
                 draft_params: Any = None,
                 draft_config: Any = None):
        import numpy as np

        from ray_tpu._private import compile_cache
        from ray_tpu.serve.llm.programs import programs_for

        compile_cache.configure()      # before this engine's first compile
        self.params = params
        self.model_config = model_config
        self.config = engine_config or EngineConfig()
        c = self.config
        B = c.num_slots
        # The model's own functions over its cache (models/serving.py):
        # the engine names no model module.
        self._model = model = model_config.serving()
        if draft_params is not None and model.verify is None:
            raise ValueError(
                f"{model.name} has no speculative verify step")
        if draft_params is not None and draft_config is None:
            raise ValueError("draft_params given without draft_config")
        self._stateful = model.init_slot_state is not None
        # The device half (serve/llm/programs.py): the jitted programs
        # and the arrays they donate, in the form the model's way of
        # generating asks for. It places nothing before `allocate`.
        self._programs = programs = programs_for(
            model, model_config, c,
            draft_config if draft_params is not None else None)
        # A sequence with window leaves (models/serving.py), like one
        # with a state by slot, stays in the slot it was admitted to
        # and is moved by nothing.
        self._pinned = self._stateful or programs.window is not None
        # How a model that generates by blocks does (models/serving.py
        # `BlockSpec`), else None. A slot of such a model holds an open
        # block between ticks that nothing but the tick carries: it is
        # pinned too, and what does not fit the form is refused here.
        self._block = programs.block
        if self._block is not None:
            self._pinned = True
            self._refuse_for_blocks(draft_params is not None)
        if c.prefix_cache:
            self._refuse_if_pinned(
                "prefix reuse (and the spill that rides it; set "
                "prefix_cache=False)", "a cached block")

        from ray_tpu.serve.llm.kv_cache import (
            BlockAllocator, KVTierManager, PrefixCache, PromoteCostModel,
            WindowRing)

        programs.allocate(rng_seed)
        bs = c.kv_block_size
        self._allocator = BlockAllocator(
            c.pool_blocks, bs, block_bytes=programs.block_bytes("full"))
        # The window kind's blocks and its ring of a table a slot; None
        # for a model all of whose leaves are of the full kind.
        self._ring = None
        if programs.window is not None:
            self._ring = WindowRing(
                programs.window, programs.ring_blocks, BlockAllocator(
                    programs.window_blocks, bs,
                    block_bytes=programs.block_bytes("window")), B,
                lookahead=c.decode_block)
        self._prefix = (PrefixCache(self._allocator)
                        if c.prefix_cache else None)
        # Per-slot block tables (host copy is the truth; the device
        # sees it as a plain [B, max_blocks] int32 argument — data,
        # not shape, so tables never retrace anything).
        self._tables = np.zeros((B, c.max_blocks_per_slot), np.int32)
        self._slot_blocks: List[List[int]] = [[] for _ in range(B)]
        self._prefix_seen = {"hits": 0, "misses": 0, "hit_tokens": 0,
                             "evictions": 0, "spilled": 0}
        self._cost_model = PromoteCostModel(
            adopt_fixed_s=c.kv_adopt_cost_fixed_ms * 1e-3,
            adopt_per_block_s=c.kv_adopt_cost_per_block_ms * 1e-3,
            prefill_per_token_s=c.kv_prefill_cost_per_token_ms * 1e-3)
        self._tiers = None
        if c.kv_spill:                  # implies the prefix cache
            self._tiers = KVTierManager(
                c.kv_host_tier_bytes, c.kv_block_size,
                put_fn=_tier_store_put, get_fn=_tier_store_get)
            self._prefix.spill_fn = self._spill_evicted
        # The slots whose prompts are under way, a piece a step,
        # inactive until their last piece (`_admit`).
        self._chunking: deque = deque()
        # Host-side mirrors fed into each program call (tiny transfers).
        # `_active`: the slot holds a decoding sequence (from its last
        # insert until it is released).
        self._active = np.zeros((B,), bool)
        self._temp = np.zeros((B,), np.float32)
        # Rows DISPATCHED a slot: prompt + generated tokens, those of a
        # tick in flight among them (the pending token's own row too),
        # and the count at which the sequence ends by `max_tokens` or
        # the sequence limit. The host knows both before the tick in
        # flight ends: the next tick's mask is `_active & (_rows <
        # _row_limit)`, and the rows it reads and the ring it needs are
        # counted from `_rows`.
        self._rows = np.zeros((B,), np.int64)
        self._row_limit = np.zeros((B,), np.int64)
        # A model that generates by blocks: a tick forwards L rows a
        # live slot and yields 0 or up to L tokens, so the two are
        # counted apart (`stats()["block"]`). Which blocks complete is
        # known when a tick lands, not before: `_rows` is then the rows
        # a slot holds, its open block among them, as of the last tick
        # read back, and a slot ends at a landing alone (`_row_limit`
        # never binds). `_blk_skip`: positions of a slot's FIRST block
        # that the prompt's tail fixed, which are not emitted.
        self._blk_skip = np.zeros((B,), np.int64)
        self._slot_forwards = 0
        self._blocks_emitted = 0
        self._tokens_emitted = 0
        # Dispatched, not read back: at most one between two steps
        # (oldest first). Scheduler thread only.
        self._flying: deque = deque()
        self._ready_at = 0.0            # the clock when the last tick was ready

        # Host-side scheduler state. One queue per SLO lane; admission
        # drains "interactive" before "batch" (all queue accesses under
        # _lock — submit/cancel are cross-thread).
        self._slots = [_Slot() for _ in range(B)]
        self._free: deque = deque(range(B))
        self._queues: Dict[str, deque] = {"interactive": deque(),
                                          "batch": deque()}
        self._lock = threading.Lock()
        self._work = threading.Event()
        self._ids = itertools.count()
        self._completed = 0
        self._live_rows_sum = 0         # over all ticks: `_live_rows`
        self._padded_rows_sum = 0
        self._live_kv_bytes_sum = 0     # a model with a window kind
        # over all inserts: the keys a walk of the history up to the
        # piece's last row reads (models/serving.py::HISTORY_TILE),
        # and the rows of the padded history the insert is handed
        self._insert_keys_walked = 0
        self._insert_keys_padded = 0
        # and, of a model whose inserts attend by tiles
        # (`ServingFns.insert_attention`), the (query, key) tiles its
        # kernel's bounds let through and those of the rectangles its
        # loop multiplies
        self._insert_attn_tiles_run = 0
        self._insert_attn_tiles_dense = 0
        self._slot_reuses = 0
        self._cancelled: set = set()    # request ids, guarded by _lock
        self._admit_blocked = False     # interactive admission starved
        self._preempted = 0
        self._migrated_blocks = 0       # KVStates adopted into this pool
        self._migrated_bytes = 0
        self._promoted_blocks = 0       # tier blocks re-adopted to HBM
        self._promote_skips = 0         # cost model chose recompute
        # Spills whose export is dispatched and whose copy to the host
        # is under way: (device row, the victims' tokens). `_step` lands
        # them behind the dispatched tick; none outlives the step that
        # made it. Scheduler thread only.
        self._pending_spills: List[Tuple[Dict[str, Any],
                                         List[Tuple[int, ...]]]] = []
        self._in_step = False
        self._loop = _LoopClock()
        self._warmup: Optional[Dict[str, Any]] = None
        self._spill_lands = 0           # landings, and those that had
        self._spill_lands_waited = 0    # to wait for the transfer
        self._tier_seen = {t: {"hits": 0, "misses": 0, "spills": 0,
                               "promotes": 0}
                           for t in ("host", "store")}
        # Cross-thread control calls executed by step() on the
        # scheduler thread (the only thread allowed to touch device
        # state alongside the donating programs) — export_prefix from
        # a replica's Serve thread goes through here.
        self._ctrl_q: deque = deque()

        from ray_tpu.observability.control import Hysteresis

        self._preempt_gate = Hysteresis(
            up_delay_s=c.preempt_hold_s, down_delay_s=0.0,
            cooldown_s=c.preempt_cooldown_s)

        # Speculative decoding: a small draft model proposing
        # spec_k - 1 greedy tokens per round, verified in one paged
        # K-token target step (the model's `verify`).
        self._draft = draft_params
        self.draft_config = draft_config
        self._spec_ok = np.zeros((B,), bool)
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0

        from ray_tpu.observability import serve_metrics
        from ray_tpu.observability.device import ensure_sampler_registered

        self._metrics = serve_metrics()
        ensure_sampler_registered()

        # Per-request cost accounting (observability/accounting.py).
        # The gate is latched once per engine: meters attach at submit,
        # so flipping the knob mid-flight would half-meter requests.
        from ray_tpu.observability.accounting import accounting_enabled

        self._acct = accounting_enabled()
        mc = model_config
        self._model_label = (
            f"llama_d{getattr(mc, 'dim', 0)}"
            f"_l{getattr(mc, 'n_layers', 0)}")

    # ----------------------------------------------------------- submission

    def submit(self, request: Request) -> RequestHandle:
        c = self.config
        P = len(request.prompt)
        top = c.prefill_buckets[-1]
        if P == 0:
            raise ValueError("empty prompt")
        if request.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if request.slo not in ("interactive", "batch"):
            raise ValueError(
                f"slo must be 'interactive' or 'batch', got "
                f"{request.slo!r}")
        if request.prefill_only:
            self._refuse_if_pinned("prefill_only", "an exported KVState")
        handle = RequestHandle(next(self._ids), request)
        # the rows an insert puts in: the prompt, or its whole blocks
        handle._rows_in = rows_in = self._rows_in(P)
        if P > top:
            if not request.chunked_prefill:
                raise ValueError(
                    f"prompt length {P} exceeds largest prefill bucket "
                    f"{top} (set chunked_prefill=True)")
            if P >= c.max_seq_len or -(-P // top) * top > c.max_seq_len:
                raise ValueError(
                    f"prompt length {P} cannot be chunk-prefilled: "
                    f"ceil({P}/{top}) bucket-sized chunks exceed "
                    f"max_seq_len {c.max_seq_len}")
        handle._piece_ends = list(range(top, rows_in, top)) + [rows_in]
        # A request the pool can never hold must fail loudly at
        # submit — queuing it would deadlock admission forever.
        worst = self._blocks_to_take(handle, 0)
        if worst > c.pool_blocks:
            raise ValueError(
                f"request needs up to {worst} KV blocks but the "
                f"pool only has {c.pool_blocks}; raise "
                f"num_kv_blocks or lower max_tokens")
        if self._ring is not None and self._ring.blocks_for(worst) \
                > self._ring.allocator.num_blocks:
            raise ValueError(
                f"request needs {self._ring.blocks_for(worst)} blocks of "
                f"the window kind but that pool only has "
                f"{self._ring.allocator.num_blocks}; raise "
                f"num_window_blocks")
        handle._engine = self
        self._capture_trace(handle)
        self._attach_meter(handle)
        with self._lock:
            self._queues[request.slo].append(handle)
        self._work.set()
        return handle

    def _rows_in(self, prompt_len: int) -> int:
        """Rows of a prompt that its inserts put into the pool: all of
        it, or for a model that generates by blocks its whole blocks
        (the trailing `P mod L` tokens open the slot's first block)."""
        if self._block is None:
            return prompt_len
        return prompt_len - prompt_len % self._block.length

    def _refuse_for_blocks(self, draft: bool) -> None:
        """What the block form of the tick does not offer, by the
        model's name (models/serving.py)."""
        c, L = self.config, self._block.length
        refused = {
            f"decode_block {c.decode_block} (a tick is ONE forward over "
            f"a block)": c.decode_block != 1,
            "a draft model (speculation)": draft,
            f"kv_block_size {c.kv_block_size}, which holds no whole "
            f"blocks of {L}": c.kv_block_size % L != 0,
        }
        if any(refused.values()):
            raise ValueError(
                f"{self._model.name} generates by blocks of {L}: "
                + ", ".join(k for k, v in refused.items() if v)
                + " is not offered")

    def _refuse_if_pinned(self, what: str, carrier: str) -> None:
        """Whatever moves a sequence's rows without its per-slot state,
        without its open block, or without telling a window kind's ring
        from a full kind's table, would lose rows: refused, by the
        model's name."""
        if self._block is not None:
            raise ValueError(
                f"{self._model.name} generates by blocks, and a slot "
                f"holds an open block that {carrier} does not carry: "
                f"{what} is not offered")
        if self._stateful:
            raise ValueError(
                f"{self._model.name} keeps a state by slot that {carrier} "
                f"does not carry: {what} is not offered")
        if self._programs.window is not None:
            raise ValueError(
                f"{self._model.name} keeps its window layers' rows in a "
                f"ring of blocks of their own kind that {carrier} does "
                f"not carry: {what} is not offered")

    def _attach_meter(self, handle: RequestHandle) -> None:
        """Attach a cost meter (after _capture_trace: the meter is
        stamped with the captured trace id)."""
        if not self._acct:
            return
        try:
            from ray_tpu.observability.accounting import RequestMeter

            req = handle.request
            handle.meter = RequestMeter(
                tenant=req.tenant, model=self._model_label,
                lane=req.slo,
                trace_id=(handle.trace.trace_id if handle.trace
                          else None),
                request_id=handle.request_id)
        except Exception:
            handle.meter = None   # accounting must never break submit

    def submit_adopted(self, request: Request, state: Any, *,
                       front: bool = False,
                       meter_snapshot: Optional[Dict[str, Any]] = None
                       ) -> RequestHandle:
        """Submit a request whose prefill already ran elsewhere: `state`
        is the kv_cache.KVState exported by the prefill tier (or by
        preemption). Admission imports the blocks into this engine's
        pool and decoding continues exactly where the checkpoint
        stopped — token-for-token what a monolithic engine would have
        produced. `front=True` queues at the lane head (resume
        semantics)."""
        from ray_tpu.serve.llm.kv_cache import KVState

        c = self.config
        self._refuse_if_pinned("adopting one", "a KVState")
        if not isinstance(state, KVState):
            raise TypeError(f"expected KVState, got {type(state)!r}")
        state.validate()
        if state.block_size != c.kv_block_size:
            raise ValueError(
                f"KVState block_size {state.block_size} != engine "
                f"kv_block_size {c.kv_block_size}")
        if list(request.prompt) != list(state.prompt):
            raise ValueError(
                "request.prompt does not match the exported KVState "
                "prompt (the checkpoint is prompt-specific)")
        if request.max_tokens <= len(state.tokens):
            raise ValueError(
                f"max_tokens {request.max_tokens} already reached by "
                f"the checkpoint ({len(state.tokens)} tokens)")
        if len(state.prompt) + len(state.tokens) >= c.max_seq_len:
            raise ValueError(
                f"the checkpoint already holds max_seq_len "
                f"{c.max_seq_len} rows: nothing is left to decode")
        if request.slo not in ("interactive", "batch"):
            raise ValueError(
                f"slo must be 'interactive' or 'batch', got "
                f"{request.slo!r}")
        need = max(self._blocks_needed(len(request.prompt),
                                       request.max_tokens),
                   state.n_blocks)
        if need > c.pool_blocks:
            raise ValueError(
                f"adopted request needs up to {need} KV blocks but the "
                f"pool only has {c.pool_blocks}")
        handle = RequestHandle(next(self._ids), request)
        handle._engine = self
        handle._adopted_submit = True
        self._capture_trace(handle)
        self._attach_meter(handle)
        if handle.meter is not None and meter_snapshot:
            # The prefill tier's meter rides next to the KVState so the
            # migrated request lands on ONE ledger row (prefill
            # chip-seconds and all).
            handle.meter.absorb(meter_snapshot)
        handle.tokens = list(state.tokens)
        handle.kv_state = state
        with self._lock:
            q = self._queues[request.slo]
            if front:
                q.appendleft(handle)
            else:
                q.append(handle)
        self._work.set()
        return handle

    def cancel(self, handle: RequestHandle) -> bool:
        """Cancel a submitted request. Queued handles finish here;
        live handles are marked and torn down by the scheduler thread
        at its next step boundary (slot + blocks + prefix refs all
        released there, on the only thread that owns device state)."""
        with self._lock:
            if handle._done.is_set():
                return False
            for q in self._queues.values():
                if handle in q:
                    q.remove(handle)
                    break
            else:
                self._cancelled.add(handle.request_id)
                self._work.set()
                return True
        self._finish_cancelled(handle)
        return True

    def _finish_cancelled(self, handle: RequestHandle) -> None:
        handle.finish_reason = "cancelled"
        handle.finished_at = time.monotonic()
        self._completed += 1
        self._record_finished(handle)
        handle._done.set()

    def has_work(self) -> bool:
        return (any(self._queues.values()) or bool(self._active.any())
                or bool(self._cancelled) or bool(self._ctrl_q)
                or bool(self._chunking) or bool(self._flying))

    # ------------------------------------------------------------ scheduling

    def _bucket_for(self, n: int) -> int:
        for b in self.config.prefill_buckets:
            if n <= b:
                return b
        raise ValueError(n)  # pre-checked in submit()

    def _blocks_needed(self, prompt_len: int, max_tokens: int) -> int:
        """Blocks covering every position this request can ever write:
        prompt + generated tokens + up to decode_block - 1 (or
        spec_k - 1 when a draft model is wired — a verify step writes
        spec_k rows) speculative writes after the stop condition,
        capped at the sequence limit (positions clamp at S - 1)."""
        c = self.config
        if self._block is not None:
            # whole blocks of L, and one more for the tick dispatched
            # behind the one that finished the request
            L = self._block.length
            top = min(-(-(prompt_len + max_tokens) // L) * L + L,
                      c.max_seq_len)
            return -(-top // c.kv_block_size)
        over = max(c.decode_block,
                   c.spec_k if self._draft is not None else 1)
        top = min(prompt_len + max_tokens + over - 1, c.max_seq_len)
        return -(-top // c.kv_block_size)

    def _pop_next(self) -> Optional[RequestHandle]:
        """Next admissible handle, interactive lane first (strict
        priority; batch only drains when interactive is empty)."""
        with self._lock:
            for lane in ("interactive", "batch"):
                if self._queues[lane]:
                    return self._queues[lane].popleft()
        return None

    def _requeue(self, handle: RequestHandle, *,
                 front: bool = True) -> None:
        with self._lock:
            q = self._queues[handle.request.slo]
            if front:
                q.appendleft(handle)
            else:
                q.append(handle)

    def _admit(self) -> List[Tuple[int, bool]]:
        """Move queued requests into free slots (one prefill each);
        returns (slot, fresh) pairs whose prompts are in this step —
        `fresh` is False for adopted checkpoints, whose last sampled
        token was already emitted by the exporting engine. Admission
        needs blocks as well as a slot — on pool exhaustion the
        request goes BACK to the lane head and admission stops
        (requests queue, never crash; blocks free as running sequences
        finish). A prompt of several pieces takes its slot and blocks
        with the first and keeps them: a later piece goes into that
        slot ahead of anything queued, and one such piece goes out a
        step, so other admissions interleave with a long prefill."""
        inserted: List[Tuple[int, bool]] = []
        piece_budget = 1
        if self._chunking:
            slot = self._chunking.popleft()
            self._admit_one(self._slots[slot].handle, slot, inserted)
            piece_budget = 0
        while self._free:
            handle = self._pop_next()
            if handle is None:
                break
            if handle._done.is_set():
                continue   # cancelled while queued by a racing cancel()
            if handle.kv_state is None and len(handle._piece_ends) > 1:
                if piece_budget == 0:
                    self._requeue(handle)
                    break
                piece_budget -= 1
            slot = self._free.popleft()
            if not self._admit_one(handle, slot, inserted):
                self._free.appendleft(slot)
                if handle.request.slo == "interactive":
                    self._admit_blocked = True
                self._requeue(handle)
                break
            self._occupy(handle, slot)
        return inserted

    def _admit_one(self, handle: RequestHandle, slot: int,
                   inserted: List[Tuple[int, bool]]) -> bool:
        """One admission dispatch for `handle` into `slot`: its
        checkpoint, or the next piece of its prompt. With the prompt in,
        the slot joins the tick (and `inserted`); with pieces left it
        waits in `_chunking`. False, and nothing changed, where the
        pool cannot cover the sequence."""
        req = handle.request
        fresh = handle.kv_state is None
        said = {"chunk": 1} if fresh and len(handle._piece_ends) > 1 else {}
        t_admit = time.monotonic()
        with trace_span("llm_engine.admit_one", **said) as sp:
            ok = (self._admit_piece(handle, slot, sp) if fresh
                  else self._admit_adopted(handle, slot))
        if not ok:
            return False
        prompt_in = not fresh or handle._prompt_rows == handle._rows_in
        if prompt_in and fresh and self._draft is not None:
            self._draft_admit(list(req.prompt), slot)
        if handle.meter is not None:
            # Admission dispatch (insert/adopt + draft seed) billed
            # as this request's prefill chip-time; fresh admissions
            # resume-from-preempt included — the adopt scatter is
            # real chip work this request caused.
            handle.meter.note_chip("prefill", time.monotonic() - t_admit)
        if prompt_in:
            self._activate(handle, slot, fresh)
            inserted.append((slot, fresh))
        else:
            self._chunking.appendleft(slot)
        return True

    def _activate(self, handle: RequestHandle, slot: int,
                  fresh: bool) -> None:
        """The sequence's prompt is in: the slot joins the next tick.
        Its rows so far are the prompt's and its tokens', the pending
        one's own among them (a fresh insert's first token is sampled
        and not yet on the handle)."""
        import numpy as np

        req = handle.request
        P = len(req.prompt)
        self._active[slot] = True
        self._temp[slot] = req.temperature
        if self._block is not None:
            L = self._block.length
            self._rows[slot] = handle._rows_in + L
            self._row_limit[slot] = np.iinfo(np.int64).max
            self._blk_skip[slot] = P % L
            return
        self._rows[slot] = P + len(handle.tokens) + int(fresh)
        self._row_limit[slot] = min(P + req.max_tokens,
                                    self.config.max_seq_len)

    def _occupy(self, handle: RequestHandle, slot: int) -> None:
        """The request has its slot: the queue wait ends here."""
        if handle.admitted_at is None:
            handle.admitted_at = time.monotonic()
            self._metrics.queue_wait.observe(
                handle.admitted_at - handle.submitted_at)
            if handle.meter is not None:
                handle.meter.note_queue_wait(
                    handle.admitted_at - handle.submitted_at)
        st = self._slots[slot]
        if st.uses:
            self._slot_reuses += 1
            self._metrics.slot_reuses.inc()
        st.uses += 1
        st.handle = handle

    def _blocks_to_take(self, handle: RequestHandle, start: int) -> int:
        """Blocks the sequence takes when its prompt goes in from row
        `start` on (the rows before it are a cached prefix's): every
        position it can write, and the whole bucket of every piece
        (the insert scatters whole blocks, and the slot must own each
        block written)."""
        req, bs = handle.request, self.config.kv_block_size
        need = self._blocks_needed(len(req.prompt), req.max_tokens)
        for end in handle._piece_ends:
            if end > start:
                need = max(need,
                           (start + self._bucket_for(end - start)) // bs)
                start = end
        return need

    def _take_blocks(self, slot: int, n: int, span) -> Optional[List[int]]:
        """`n` blocks of the full kind and, for a model with a window
        kind, the ring's share of them into `slot`'s ring: all or
        nothing (None). Says on `span` what a window model took."""
        blocks = self._allocator.alloc(n)
        if blocks is None or self._ring is None:
            return blocks
        if not self._ring.take(slot, n):
            self._allocator.free(blocks)
            return None
        span.set_metadata(blocks_full=n,
                          blocks_window=self._ring.blocks_for(n))
        return blocks

    def _insert(self, slot: int, row, hist_len: int, padded, suffix_len: int,
                scatter_ids, temperature: float, tail=()) -> None:
        """Dispatch the insert program of `padded`'s bucket. `tail`: for
        a model that generates by blocks, the prompt's tokens behind
        the rows this piece completes (none before the last piece)."""
        if self._ring is not None:      # a table row and ids a kind
            row = {"full": row, "window": self._ring.tables[slot].copy()}
            scatter_ids = {"full": scatter_ids,
                           "window": self._ring.block_ids(
                               slot, hist_len, len(padded))}
        said = {"bucket": len(padded)}
        # what the chunk is, for the trace's readers
        if self._stateful or self._block is not None:
            said["tokens"] = int(suffix_len)
        if self._stateful:
            said["state_in"] = int(hist_len > 0)
        with trace_span("llm_engine.insert_dispatch", **said):
            self._programs.insert(self.params, slot, row, hist_len, padded,
                                  suffix_len, scatter_ids, temperature, tail)

    def _admit_piece(self, handle: RequestHandle, slot: int, span) -> bool:
        """The next piece of this request's prompt into this slot: the
        ONE way a prompt goes in, whatever the model (a prompt that
        fits a bucket is the case of one piece). The first piece takes
        the slot's blocks (`_take_prompt_blocks`: False, nothing taken
        and nothing inserted, when the pool cannot cover them) and
        starts behind the cached prefix; a later one finds the rows,
        and the state by slot of a model that keeps one, where the
        pieces before it left them. The slot holds its own references
        from the first piece to its release, so nothing is handed over
        between two pieces and no eviction in between can take a row
        away. Pieces end at multiples of the largest bucket
        (`submit`). After EACH piece the prompt's full blocks so far
        are registered in the prefix cache (their rows are real once
        its insert is dispatched: programs run in order), so a request
        sharing the prefix hits them while the rest still goes in."""
        import numpy as np

        from ray_tpu.models.serving import HISTORY_TILE

        req = handle.request
        bs = self.config.kv_block_size
        if handle._prompt_rows is None and \
                not self._take_prompt_blocks(handle, slot, span):
            return False
        start = handle._prompt_rows
        # (a prompt shorter than a block has no row to put in: its
        # insert still runs, over padding alone, and opens the block)
        end = next((e for e in handle._piece_ends if e > start), start)
        n = end - start
        bucket = self._bucket_for(n)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = np.asarray(req.prompt[start:end], np.int32)
        row = self._tables[slot].copy()
        self._insert(slot, row, start, padded, n,
                     row[start // bs:(start + bucket) // bs],
                     req.temperature,
                     tail=req.prompt[end:] if end == handle._rows_in else ())
        S = self.config.max_seq_len
        self._insert_keys_walked += min(
            -(-(start + bucket) // HISTORY_TILE) * HISTORY_TILE, S)
        self._insert_keys_padded += S
        if self._model.insert_attention:
            _, run, dense = self._model.insert_attention(
                self.model_config, start, bucket, S)
            self._insert_attn_tiles_run += run
            self._insert_attn_tiles_dense += dense
        handle.prefilled_tokens += n
        handle._prompt_rows = end
        if self._prefix is not None and end >= bs:
            # the next request sharing this prefix skips its prefill
            self._prefix.insert(req.prompt[:end],
                                self._slot_blocks[slot][:end // bs])
        return True

    def _take_prompt_blocks(self, handle: RequestHandle, slot: int,
                            span) -> bool:
        """Before a prompt's first piece: every block the sequence can
        need (`_blocks_to_take`) into `slot`'s table, all or nothing,
        evicting cold prefix entries if that closes the gap. With a
        prefix cache the table starts with the longest cached prefix,
        extended by spilled chain links where the cost model says the
        transfer beats the recompute; `handle._prompt_rows` says how
        many of the prompt's rows the slot holds so."""
        req = handle.request
        c = self.config
        bs = c.kv_block_size
        prompt = req.prompt
        P = len(prompt)

        def fits(n_blocks: int) -> bool:
            # history + every padded piece within the slot's table (a
            # shallow hit on a near-max prompt can otherwise push a
            # bucket's whole-block scatter past S)
            return self._blocks_to_take(handle, n_blocks * bs) \
                <= c.max_blocks_per_slot

        # Longest cached prefix, capped so the LAST prompt token is
        # always prefilled (its logits seed the first sampled token).
        hit_blocks: List[int] = []
        if self._prefix is not None:
            hit_blocks = self._prefix.match(prompt,
                                            max_blocks=(P - 1) // bs)
        while hit_blocks and not fits(len(hit_blocks)):
            self._allocator.free([hit_blocks.pop()])
        n_hit = len(hit_blocks)
        # Tier continuation: extend the HBM hit with spilled chain
        # links, re-adopted through the adopt scatter — but only when
        # the cost model says the transfer beats recomputing those
        # positions (short suffixes recompute; the crossover is the
        # whole point of the hierarchy).
        promote: List[Any] = []
        if self._tiers is not None and self._prefix is not None:
            cap = (P - 1) // bs - n_hit
            if cap > 0:
                # what an earlier admission of this step evicted is
                # found here as it would be one step later
                self._land_spills()
                promote = self._tiers.lookup(prompt, bs,
                                             start_depth=n_hit,
                                             max_blocks=cap)
            while promote and not fits(n_hit + len(promote)):
                promote.pop()
            if promote and not self._cost_model.should_promote(
                    len(promote), bs):
                self._promote_skips += len(promote)
                promote = []
        while True:
            n_pro = len(promote)
            n_new = self._blocks_to_take(
                handle, (n_hit + n_pro) * bs) - n_hit
            new_blocks = self._take_blocks(slot, n_new, span)
            if new_blocks is None and self._prefix is not None:
                want = n_new - self._allocator.free_blocks
                with trace_span("llm_engine.evict", blocks=want):
                    self._prefix.evict(want)
                new_blocks = self._allocator.alloc(n_new)
            if new_blocks is not None or not promote:
                break
            # All-or-nothing promote: the pool cannot cover the full
            # run even after eviction — drop the promote entirely
            # (tier entries untouched) and retry as a plain recompute.
            promote = []
        if new_blocks is None:
            if hit_blocks:
                self._allocator.free(hit_blocks)
            return False

        blocks = hit_blocks + new_blocks
        self._tables[slot] = 0
        self._tables[slot, :len(blocks)] = blocks
        self._slot_blocks[slot] = blocks
        if handle.meter is not None:
            # Block-seconds meter opens here; _release_slot closes
            # it with the same count (all blocks alloc up front).
            handle.meter.blocks_acquired(len(blocks))
        if promote:
            # Land the tier links in new_blocks[:n_pro] BEFORE the
            # first piece's insert reads them as history.
            with trace_span("llm_engine.promote", blocks=n_pro):
                self._promote_tier_hits(promote, new_blocks[:n_pro],
                                        slot, handle=handle)
        handle._prompt_rows = (n_hit + n_pro) * bs
        return True

    def _admit_adopted(self, handle: RequestHandle, slot: int) -> bool:
        """Import a KVState checkpoint into this engine's pool and
        resume the sequence in `slot`. All-or-nothing: either every
        block the sequence can ever need is allocated (evicting cold
        prefix entries if that closes the gap) and the scatter runs, or
        nothing changes and the request stays queued. ONE adopt trace
        serves every valid-block count — the blocks are zero-padded to
        max_blocks_per_slot and the scatter ids of padding rows point
        one past the pool (out-of-bounds writes drop under jit)."""
        import numpy as np

        self._settle("adopt")
        t_mig = time.time()
        req = handle.request
        st = handle.kv_state
        c = self.config
        bs = c.kv_block_size
        n_valid = st.n_blocks
        need_total = max(
            self._blocks_needed(len(req.prompt), req.max_tokens),
            n_valid)
        blocks = self._allocator.adopt(need_total, self._prefix)
        if blocks is None:
            return False
        row = np.zeros((c.max_blocks_per_slot,), np.int32)
        row[:need_total] = blocks
        self._tables[slot] = row
        self._slot_blocks[slot] = blocks
        if handle.meter is not None:
            handle.meter.blocks_acquired(len(blocks))

        # Padding rows scatter to pool_blocks (out of bounds → dropped).
        ids = np.full((c.max_blocks_per_slot,), c.pool_blocks, np.int32)
        ids[:n_valid] = blocks[:n_valid]
        self._programs.adopt(st.blocks, ids, slot, st.next_tok, st.pos)
        if self._prefix is not None:
            # Shared prompts stay warm across the migration: register
            # the prompt's FULL blocks exactly like a fresh admission.
            full = len(req.prompt) // bs
            full = min(full, n_valid)
            if full:
                self._prefix.insert(req.prompt, blocks[:full])
        self._migrated_blocks += n_valid
        self._migrated_bytes += st.payload_bytes
        self._metrics.kv_migrated_blocks.inc(float(n_valid))
        self._metrics.kv_migrated_bytes.inc(float(st.payload_bytes))
        try:
            from ray_tpu.util.tracing import record_span

            record_span("kv.migrate", t_mig, time.time() - t_mig,
                        attrs={"blocks": int(n_valid),
                               "bytes": int(st.payload_bytes)},
                        trace=self._phase_trace(handle))
        except Exception:
            pass  # telemetry must never break admission
        handle.kv_state = None
        if self._draft is not None:
            # The draft cache never migrated: re-prefill it with
            # everything the sequence has consumed so far.
            self._draft_admit(
                list(req.prompt) + list(handle.tokens[:-1]), slot)
        return True

    def _draft_admit(self, consumed: List[int], slot: int) -> None:
        """Prefill the draft model's cache stripe with a slot's consumed
        tokens (prompt, plus prior output for adopted sequences). A
        sequence whose consumed length exceeds the largest bucket
        cannot seed the draft in one insert — it simply decodes without
        speculation (spec_ok stays False; the plain tick handles it)."""
        import numpy as np

        n = len(consumed)
        if n > self.config.prefill_buckets[-1]:
            self._spec_ok[slot] = False
            return
        bucket = self._bucket_for(n)
        padded = np.zeros((bucket,), np.int32)
        padded[:n] = np.asarray(consumed, np.int32)
        self._programs.draft_insert(self._draft, padded, slot)
        self._spec_ok[slot] = True

    def _release_slot(self, slot: int, donate: bool = False) -> None:
        """Clear a slot's scheduler state and reclaim its blocks.
        `donate=True` hands the blocks to a pending checkpoint (export
        already copied the data; `BlockAllocator.donate` asserts the
        refs are live) instead of plain freeing."""
        st = self._slots[slot]
        handle = st.handle
        st.handle = None
        self._active[slot] = False
        self._temp[slot] = 0.0
        self._spec_ok[slot] = False
        if slot in self._chunking:      # cancelled between two chunks
            self._chunking.remove(slot)
        if self._slot_blocks[slot]:
            # Drop this sequence's refs; blocks shared with the prefix
            # cache (or other sequences) stay resident.
            if handle is not None and handle.meter is not None:
                # Close the block-seconds interval symmetrically with
                # the acquisition count; preempt → resume reopens it
                # at re-admission, so occupancy stays monotone and
                # never double-counts.
                handle.meter.blocks_released(
                    len(self._slot_blocks[slot]))
            if donate:
                self._allocator.donate(self._slot_blocks[slot])
            else:
                self._allocator.free(self._slot_blocks[slot])
            self._slot_blocks[slot] = []
            if self._ring is not None:
                self._ring.release(slot)
        self._free.append(slot)

    def _emit(self, slot: int, token: int) -> None:
        """Record one generated token for `slot`; free the slot when the
        request is finished (eos/stop halt, max_tokens bounds)."""
        st = self._slots[slot]
        handle = st.handle
        req = handle.request
        now = time.monotonic()
        reason = None
        if token in req.stop:
            reason = "stop"                      # halt, token NOT emitted
        else:
            handle.tokens.append(token)
            if handle.first_token_at is None:
                handle.first_token_at = now
            if req.on_token is not None:
                try:
                    req.on_token(handle.request_id, token)
                except Exception:
                    pass                          # streaming is best-effort
            if (self.config.eos_id is not None
                    and token == self.config.eos_id):
                reason = "eos"                   # halt, eos IS emitted
            elif len(handle.tokens) >= req.max_tokens:
                reason = "length"
        # Hard cap: a slot may never write past its block table. The
        # NEXT token would land at pos = prompt + len(tokens); stop while
        # it still fits.
        if reason is None and (len(req.prompt) + len(handle.tokens)
                               >= self.config.max_seq_len):
            reason = "length"
        if reason is not None:
            handle.finish_reason = reason
            handle.finished_at = now
            self._release_slot(slot)
            self._completed += 1
            self._record_finished(handle)
            handle._done.set()

    def _finish_prefill(self, slot: int, token: int) -> None:
        """Prefill-only completion: record the first sampled token,
        export the slot's KV blocks as the handle's checkpoint, and
        free the slot. A request that already terminates at its first
        token (stop/eos/length) finishes with that reason instead —
        the decode tier has nothing left to do and the router skips
        the migration hop."""
        st = self._slots[slot]
        handle = st.handle
        req = handle.request
        now = time.monotonic()
        reason = None
        if token in req.stop:
            reason = "stop"
        else:
            handle.tokens.append(token)
            handle.first_token_at = now
            if (self.config.eos_id is not None
                    and token == self.config.eos_id):
                reason = "eos"
            elif req.max_tokens <= 1 or \
                    len(req.prompt) + 1 >= self.config.max_seq_len:
                reason = "length"
        donate = False
        if reason is None:
            self._settle("prefill_only")
            handle.kv_state = self._export_state(slot)
            reason = "prefill"
            donate = True
        handle.finish_reason = reason
        handle.finished_at = now
        self._release_slot(slot, donate=donate)
        self._completed += 1
        self._record_finished(handle)
        handle._done.set()

    def _export_state(self, slot: int) -> Any:
        """Snapshot a live slot's sequence as a host-side KVState:
        dense copies of its valid KV blocks + the resume bookkeeping
        (consumed position, pending sampled token). The gather runs
        at the smallest row of `export_rows` that holds the valid
        blocks; the host slices them off."""
        import numpy as np

        from ray_tpu.serve.llm.kv_cache import KVState

        handle = self._slots[slot].handle
        req = handle.request
        bs = self.config.kv_block_size
        pos = int(self._programs.positions()[slot])
        next_tok = int(self._programs.tokens()[slot])
        n_valid = -(-pos // bs)
        row = self._programs.export(self._tables[slot, :n_valid])
        state = KVState(
            prompt=list(req.prompt),
            tokens=list(handle.tokens),
            next_tok=next_tok,
            pos=pos,
            temperature=req.temperature,
            block_size=bs,
            blocks={name: np.asarray(x)[:, :n_valid].copy()
                    for name, x in row.items()},
        )
        state.validate()
        return state

    # ------------------------------------------------------- KV tiering

    def _spill_evicted(self, victims: List[Any]) -> int:
        """PrefixCache eviction hook: gather the victims' HBM rows
        (still cache-owned at this point — the free happens after we
        return) through the export program, start their copy to the
        host, and keep the device row with the victims' tokens as a
        pending spill. Returns the blocks so exported. The programs
        dispatched after this one (the insert that overwrites the
        freed blocks, the tick) run after it on the chip, so the row
        holds the blocks as they were; `_land_spills` parks them in
        the tier manager — behind the dispatched tick when the
        eviction came from inside a step, at once otherwise. Runs on
        the scheduler thread (eviction only happens there)."""
        if self._tiers is None:
            return 0
        ents = [e for e in victims if e.tokens]
        if not ents:
            return 0
        # one eviction's rows on the device at a time
        self._land_spills()
        nb = self.config.max_blocks_per_slot
        exported = 0
        with trace_span("llm_engine.spill", evicted_blocks=len(ents)) as sp:
            for i in range(0, len(ents), nb):
                chunk = ents[i:i + nb]
                row = self._programs.export([e.block for e in chunk])
                for x in row.values():
                    x.copy_to_host_async()
                    exported += x.nbytes
                self._pending_spills.append(
                    (row, [e.tokens for e in chunk]))
            sp.set_metadata(bytes=exported)
        if not self._in_step:
            self._land_spills()
        return len(ents)

    def _land_spills(self) -> None:
        """Park every pending spill in the tier manager: read the
        exported rows on the host (the transfer `_spill_evicted`
        started), one single-block KVPrefix per chain link. Every
        reader of the tier on the scheduler thread calls this first. A
        landing that fails is a spill that failed: counted, and the
        eviction it came from has long gone through."""
        import numpy as np

        if not self._pending_spills:
            return
        pending, self._pending_spills = self._pending_spills, []
        bs = self.config.kv_block_size
        n_blocks = sum(len(toks) for _, toks in pending)
        with self._loop.phase("llm_engine.spill_land",
                              blocks=n_blocks) as sp:
            t0 = time.monotonic()
            landed, waited = 0, True
            try:
                rows = [({name: np.asarray(x) for name, x in row.items()},
                         toks) for row, toks in pending]
                waited = time.monotonic() - t0 > _SPILL_READY_S
                landed = sum(x.nbytes for got, _ in rows
                             for x in got.values())
                self._tiers.spill([p for got, toks in rows
                                   for p in _block_prefixes(got, toks, bs)])
            except Exception:
                self._prefix.spill_failed(n_blocks)
            self._spill_lands += 1
            self._spill_lands_waited += waited
            sp.set_metadata(bytes=landed, ready=int(not waited))

    def _promote_tier_hits(self, hits: List[Any],
                           dst_blocks: List[int], slot: int,
                           handle: Optional[RequestHandle] = None
                           ) -> None:
        """Scatter tier-resident chain links into freshly-allocated
        pool blocks through the ONE adopt program (padding ids point
        one past the pool — dropped under jit). The tok/pos writes are
        placeholders: the insert that follows for the same slot owns
        them. Tier entries
        are popped only after the scatter dispatched — the
        all-or-nothing contract."""
        import numpy as np

        t_pro = time.time()
        c = self.config
        ids = np.full((c.max_blocks_per_slot,), c.pool_blocks, np.int32)
        ids[:len(dst_blocks)] = dst_blocks
        # each hit carries its chain link as its payload's LAST block
        last = {name: np.concatenate(
            [h.prefix.blocks[name][:, -1:] for h in hits], axis=1)
            for name in hits[0].prefix.blocks}
        self._programs.adopt(last, ids, slot, 0, 0)
        self._tiers.pop(hits)
        self._promoted_blocks += len(hits)
        if handle is not None:
            try:
                from ray_tpu.util.tracing import record_span

                record_span("kv.promote", t_pro, time.time() - t_pro,
                            attrs={"blocks": len(hits)},
                            trace=self._phase_trace(handle))
            except Exception:
                pass  # telemetry must never break admission

    def call_on_scheduler(self, fn: Callable[[], Any],
                          timeout_s: float = 60.0) -> Any:
        """Run ``fn()`` on the scheduler thread between steps and
        return its result. Device state may only be touched alongside
        the donating programs from that thread — a concurrent reader
        could gather a buffer the tick just donated. Deadlocks if
        called FROM the scheduler thread (call the target directly
        there)."""
        box: List[Any] = []
        ev = threading.Event()
        with self._lock:
            self._ctrl_q.append((fn, box, ev))
        self._work.set()
        if not ev.wait(timeout_s):
            raise TimeoutError("scheduler thread did not service the "
                               "control call (is run() driving it?)")
        if isinstance(box[0], BaseException):
            raise box[0]
        return box[0]

    def _process_ctrl(self) -> bool:
        with self._lock:
            batch = list(self._ctrl_q)
            self._ctrl_q.clear()
        if batch:
            self._settle("ctrl")
        for fn, box, ev in batch:
            try:
                box.append(fn())
            except BaseException as e:          # relayed to the caller
                box.append(e)
            ev.set()
        return bool(batch)

    def export_prefix(self, tokens: Sequence[int],
                      max_blocks: Optional[int] = None) -> List[Any]:
        """Donor side of a peer pull: the longest HBM + tier chain
        covering a prefix of ``tokens``, as one single-block KVPrefix
        per link (plain ndarrays — a task returning them rides the
        object store zero-copy). Non-destructive: the donor keeps its
        copies. Must run on the scheduler thread — wrap in
        :meth:`call_on_scheduler` from anywhere else."""
        import numpy as np

        self._refuse_if_pinned("export_prefix", "an exported block")
        if self._prefix is None:
            return []
        c = self.config
        bs = c.kv_block_size
        cap = len(tokens) // bs
        if max_blocks is not None:
            cap = min(cap, max_blocks)
        if cap <= 0:
            return []
        out: List[Any] = []
        self._land_spills()
        hit = self._prefix.match(tokens, max_blocks=cap)
        if hit:
            n = min(len(hit), c.max_blocks_per_slot)
            got = {name: np.asarray(x) for name, x in
                   self._programs.export(hit[:n]).items()}
            out = _block_prefixes(
                got, [tuple(tokens[: (j + 1) * bs]) for j in range(n)], bs)
            self._allocator.free(hit)       # match increfed for us
        if self._tiers is not None and len(out) < cap:
            for h in self._tiers.lookup(tokens, bs,
                                        start_depth=len(out),
                                        max_blocks=cap - len(out)):
                out.append(h.prefix)
        return out

    def import_prefix(self, prefixes: Sequence[Any]) -> int:
        """Receiver side of a peer pull: park pulled chain links in the
        host tier; the pulling request's admission then promotes them
        through the normal cost-model path. Thread-safe (tier manager
        locks) — no scheduler hop needed."""
        if self._tiers is None:
            return 0
        return self._tiers.spill(list(prefixes))

    def prefix_index_heads(self,
                           max_heads: Optional[int] = None
                           ) -> List[Tuple[int, int]]:
        """What this replica publishes to the cluster-wide prefix
        index: ``(stable_hash, depth)`` chain links it can serve
        without prefilling — HBM-resident first (hottest), then tier
        residents — deduped and capped at
        ``serve_prefix_index_max_heads``."""
        from ray_tpu._private.config import GlobalConfig

        if max_heads is None:
            max_heads = int(GlobalConfig.serve_prefix_index_max_heads)
        heads: List[Tuple[int, int]] = []
        seen: set = set()
        sources: List[List[Tuple[int, int]]] = []
        if self._prefix is not None:
            sources.append(self._prefix.snapshot_heads(max_heads))
        if self._tiers is not None:
            sources.append(self._tiers.stable_heads(max_heads))
        for src in sources:
            for h, d in src:
                if len(heads) >= max_heads:
                    return heads
                if h not in seen:
                    seen.add(h)
                    heads.append((h, d))
        return heads

    def preempt(self, slot: int) -> None:
        """Checkpoint a live slot and requeue it at its lane head: the
        sequence's KV blocks are exported onto the handle
        (handle.kv_state), the slot and blocks are released, and the
        next admission resumes decoding through the adopt path — the
        preempt → resume cycle is token-invisible to the client."""
        self._refuse_if_pinned("preemption", "a checkpoint")
        # the exported pending token must be one the client has
        self._settle("preempt")
        if not self._active[slot]:      # free, or its prompt still going in
            raise ValueError(f"slot {slot} is not live")
        handle = self._slots[slot].handle
        handle.kv_state = self._export_state(slot)
        self._release_slot(slot, donate=True)
        self._preempted += 1
        self._metrics.preemptions.inc(
            tags={"lane": handle.request.slo})
        self._requeue(handle, front=True)

    def _maybe_preempt(self) -> None:
        """Preemption policy, gated by the PR-7 Hysteresis controller:
        when interactive requests are waiting and admission is starved
        (no free slot, or the pool rejected an interactive admission
        last step), checkpoint the NEWEST-admitted batch decode — it
        has the least sunk prefill work per token emitted. The
        hold/cooldown gate means transient pressure (one tick of a
        full batch) never thrashes checkpoints."""
        with self._lock:
            waiting = len(self._queues["interactive"])
        if not waiting or self._pinned:
            self._preempt_gate.propose(0, 0)
            return
        batch_slots = [
            s for s in range(self.config.num_slots)
            if self._active[s]          # not a prompt still going in
            and self._slots[s].handle.request.slo == "batch"
            and not self._slots[s].handle.request.prefill_only
        ]
        pressure = bool(batch_slots) and (
            not self._free or self._admit_blocked)
        if self._preempt_gate.propose(0, 1 if pressure else 0) != 1:
            return
        if self._settle("preempt"):     # a candidate may just have ended
            batch_slots = [s for s in batch_slots
                           if self._slots[s].handle is not None]
            if not batch_slots:
                return
        victim = max(batch_slots,
                     key=lambda s: self._slots[s].handle.admitted_at)
        try:
            from ray_tpu.observability.control import record_decision

            record_decision(
                "llm_engine", "preempt",
                "interactive lane starved; checkpointing newest batch "
                "decode", float(waiting), slot=victim)
        except Exception:
            pass
        self.preempt(victim)

    def _process_cancels(self) -> None:
        """Tear down cancelled requests on the scheduler thread (the
        only thread allowed to touch slots/blocks): live slots are
        released, requeued checkpoints are dropped."""
        with self._lock:
            if not self._cancelled:
                return
            ids, self._cancelled = self._cancelled, set()
            requeued = []
            for q in self._queues.values():
                for h in list(q):
                    if h.request_id in ids:
                        q.remove(h)
                        requeued.append(h)
        for h in requeued:
            self._finish_cancelled(h)
        for slot in range(self.config.num_slots):
            h = self._slots[slot].handle
            if h is not None and h.request_id in ids:
                self._release_slot(slot)
                self._finish_cancelled(h)

    @staticmethod
    def _capture_trace(handle: RequestHandle) -> None:
        """Stamp the submitting thread's TraceContext onto the handle
        and pre-allocate the llm.request span id, so scheduler-thread
        phase reconstruction can parent spans correctly without any
        ambient context of its own."""
        try:
            from ray_tpu.util.tracing import current_trace, new_span_id

            tc = current_trace()
            if tc is not None:
                handle.trace = tc
                handle.trace_span_id = new_span_id()
        except Exception:
            pass  # telemetry must never break submit

    @staticmethod
    def _phase_trace(handle: RequestHandle) -> Optional[Dict[str, Any]]:
        """Explicit trace fields for a phase/KV span of this request:
        fresh span id parented under the handle's llm.request span."""
        if handle.trace is None:
            return None
        from ray_tpu.util.tracing import new_span_id

        return {"trace_id": handle.trace.trace_id,
                "span_id": new_span_id(),
                "parent_span_id": handle.trace_span_id}

    def _record_finished(self, handle: RequestHandle) -> None:
        """Latency histograms + per-request lifecycle spans
        (queued -> prefill -> decode) so `/metrics` and
        `ray_tpu.timeline()` both render a serve run end-to-end. Spans
        carry the request's captured trace identity — passed explicitly
        (not via ambient context: this runs on the scheduler thread),
        so the GCS assembles them under the request's causal tree. The
        TTFT observation links its trace_id as the histogram exemplar —
        the dashboard's jump from "p99 is bad" to the worst request's
        actual trace."""
        m = self._metrics
        e2e = handle.finished_at - handle.submitted_at
        trace_id = handle.trace.trace_id if handle.trace else None
        m.e2e.observe(e2e, trace_id=trace_id)
        if handle.ttft_s is not None:
            m.ttft.observe(handle.ttft_s, trace_id=trace_id)
        if handle.tpot_s is not None:
            m.tpot.observe(handle.tpot_s)
        m.tokens.inc(float(len(handle.tokens)))
        m.requests.inc(tags={"finish_reason": handle.finish_reason})
        try:
            from ray_tpu.util.tracing import record_span

            # Monotonic offsets re-anchored on the wall-clock submit
            # time so span rows line up with task events.
            wall0 = handle.submitted_wall
            rid = handle.request_id
            admit = handle.admitted_at or handle.finished_at
            record_span("llm.queued", wall0,
                        admit - handle.submitted_at, attrs={"rid": rid},
                        trace=self._phase_trace(handle))
            if handle.first_token_at is not None:
                record_span(
                    "llm.prefill",
                    wall0 + (admit - handle.submitted_at),
                    handle.first_token_at - admit, attrs={"rid": rid},
                    trace=self._phase_trace(handle))
                record_span(
                    "llm.decode",
                    wall0 + (handle.first_token_at - handle.submitted_at),
                    handle.finished_at - handle.first_token_at,
                    attrs={"rid": rid,
                           "tokens": len(handle.tokens)},
                    trace=self._phase_trace(handle))
            req_trace = None
            if handle.trace is not None:
                # The llm.request span itself parents under the span
                # active at submit (the replica's llm.server_call).
                req_trace = {"trace_id": handle.trace.trace_id,
                             "span_id": handle.trace_span_id,
                             "parent_span_id": handle.trace.span_id}
            record_span("llm.request", wall0, e2e, attrs={
                "rid": rid, "tokens": len(handle.tokens),
                "finish_reason": handle.finish_reason},
                trace=req_trace)
        except Exception:
            pass  # telemetry must never break the scheduler
        self._account_finished(handle, e2e)

    def _account_finished(self, handle: RequestHandle,
                          e2e: float) -> None:
        """Close the request's cost meter. A "prefill" finish does NOT
        fold — its snapshot rides the disagg hand-off next to the
        KVState and the decode tier's meter absorbs it, so the whole
        migrated request lands on one ledger row."""
        meter = handle.meter
        if meter is None:
            return
        try:
            computed = handle.prefilled_tokens
            avoided = 0
            if not handle._adopted_submit:
                # prefix/tier hits = prompt positions this engine never
                # prefilled. Adopted submissions skip the credit: their
                # prompt was prefilled (and already credited) by the
                # exporting engine.
                avoided = max(len(handle.request.prompt) - computed, 0)
            meter.note_prefill(computed, avoided)
            if handle.finish_reason == "prefill":
                if handle.ttft_s is not None:
                    meter.ttft_s = handle.ttft_s
                return
            from ray_tpu.observability.accounting import fold_finished

            row = meter.finalize(
                handle.finish_reason or "unknown",
                len(handle.tokens), ttft_s=handle.ttft_s,
                tpot_s=handle.tpot_s, e2e_s=e2e)
            fold_finished(row)
        except Exception:
            pass  # accounting must never break the scheduler

    def step(self) -> bool:
        """One scheduler iteration: process cancellations, apply the
        preemption policy, admit queued requests into free slots
        (prefill + first token each; prefill_only requests finish here
        with their checkpoint), then dispatch one decode tick for every
        slot that has a token left to decode and, behind it, read the
        tick of the step before back and emit its tokens (a
        speculative round, when every live slot qualifies, is read back
        in its own step; a step that finds no slot to tick reads the
        tick in flight back). Returns True if any work was done."""
        with trace_span("llm_engine.step"):
            self._in_step = True
            self._loop.steps += 1
            try:
                return self._step()
            finally:
                self._in_step = False
                self._land_spills()     # only a step that raised

    def _step(self) -> bool:
        """`step`'s body. Its phases stand in a profiler trace as
        `llm_engine.<phase>` spans that together cover the step:
        `ctrl`, `admit`, `first_token_wait` (an admitting step),
        `tick_dispatch` (tick k; `in_flight=1` while tick k-1 runs),
        `spill_land`, `tick_wait` (tick k-1: `tick_ready` +
        `tick_readback`), `emit` (tick k-1's tokens), `gauges`. The
        first tick after a settle has none to wait for, and the step
        that finds no slot to tick writes `settle` (the last tick's
        `tick_wait` and `emit` inside it) in `tick_dispatch`'s place.
        Who the tick advances is counted under `admit` (once more under
        `first_token_wait`, where a first token can end its sequence,
        and after a round's settle, which can end any), and a round's
        last condition is read under `tick_dispatch`: no wait of the
        host's lies between two phases."""
        phase = self._loop.phase
        with phase("llm_engine.ctrl"):
            did_cancel = bool(self._cancelled)
            did_ctrl = self._process_ctrl()
            self._process_cancels()
            self._maybe_preempt()
        self._admit_blocked = False
        with phase("llm_engine.admit") as sp:
            inserted = self._admit()
            sp.set_metadata(admitted=len(inserted))
            mask, live = self._tick_slots()
        did_ctrl = did_ctrl or bool(self._chunking)   # a piece went out
        if inserted and self._block is None:
            # First generated token per freshly-prefilled slot (before
            # the tick below overwrites it with the second). Adopted
            # slots skip this: their pending token was emitted by the
            # exporting engine already. The insert ran behind the tick
            # in flight, whose tokens go out after this step's dispatch.
            with phase("llm_engine.first_token_wait"):
                tok_host = self._programs.tokens()
                for slot, fresh in inserted:
                    if not fresh:
                        continue
                    if self._slots[slot].handle.request.prefill_only:
                        self._finish_prefill(slot, int(tok_host[slot]))
                    else:
                        self._emit(slot, int(tok_host[slot]))
                # a first token can end its sequence
                mask, live = self._tick_slots()
        want_spec = live.size > 0 and self._spec_wanted(live)
        settled = want_spec and self._settle("spec")
        if settled:
            # a round reads `_pos` on the host and its length is known
            # only when it is read back; the tick that landed may have
            # ended slots (eos, a stop token): who is left
            mask, live = self._tick_slots()
        if not live.size:
            settled = settled or self._settle("empty")
            self._land_spills()         # no tick to land behind
            with phase("llm_engine.gauges"):
                self._update_gauges()
            return bool(inserted) or did_cancel or did_ctrl or settled
        with phase("llm_engine.tick_dispatch") as sp:
            at = self._loop.now
            spec = want_spec and self._spec_fits(live)
            if self._ring is not None:
                self._cover_rings(live)
            sp.set_metadata(live=len(live), in_flight=len(self._flying),
                            **self._live_rows(live))
            self._loop.ticks += 1
            self._loop.overlapped += bool(self._flying)
            if spec:
                outs, sample, counters = self._programs.spec(
                    self.params, self._draft, self._tables.copy(), mask)
            else:
                # tokens [K, B]; or, of blocks, tokens [B, L], completed [B]
                outs, sample, counters = self._programs.tick(
                    self.params, self._tick_tables(), mask,
                    self._temp.copy())
                if self._block is not None:
                    self._slot_forwards += len(live)
                else:
                    self._rows[live] += self.config.decode_block
            self._flying.append(_Tick(
                outs, live, [self._slots[s].handle for s in live], at, spec,
                sample, counters))
        # What this step's admissions evicted lands while the chip
        # runs their inserts and the tick.
        self._land_spills()
        if spec or len(self._flying) > 1:
            self._land_tick()           # a round: its own; else tick k-1
        with phase("llm_engine.gauges"):
            self._update_gauges()
        return True

    def _tick_slots(self):
        """The slots the next tick advances, as its mask and as
        indices: those that hold a decoding sequence which the ticks
        dispatched so far do not finish by `max_tokens` or the sequence
        limit (a slot that ends by `eos_id` or a stop token is still
        here until that token is read)."""
        import numpy as np

        mask = self._active & (self._rows < self._row_limit)
        return mask, np.nonzero(mask)[0]

    def _settle(self, cause: str) -> bool:
        """Read the tick in flight back and emit its tokens, out of
        turn: after it the handles' tokens, the host's counts and the
        device's state agree. Everything that reads a slot's state, or
        hands it on, calls this first (`cause` says who; counted in
        `stats()["loop"]["settles"]`). False where nothing was in
        flight."""
        if not self._flying:
            return False
        with self._loop.phase("llm_engine.settle") as sp:
            sp.set_metadata(cause=cause)
            self._loop.settles[cause] += 1
            while self._flying:
                self._land_tick()
        return True

    def _land_tick(self) -> None:
        """Wait for the oldest dispatched tick (or round), read it back
        (`llm_engine.tick_wait`) and emit its tokens
        (`llm_engine.emit`), each row to the handle that held the slot
        when the tick was dispatched and to no other. The tick's wall
        (a sampled call's `jit.wall_sample`, and what the handles are
        billed) is known when the wait ends and handed on under
        `emit`."""
        tick = self._flying.popleft()
        ready_before = self._ready_at
        with trace_span("llm_engine.tick_wait"):
            if tick.spec:
                toks_host, n_emit = self._spec_wait(*tick.outs)
            elif self._block is not None:
                toks_host, done = self._read_back(*tick.outs)   # [B, L], [B]
            else:
                toks_host, = self._read_back(*tick.outs)    # [K, B]
        with self._loop.phase("llm_engine.emit") as sp:
            if tick.counters and trace_live():
                # Under a profiler session alone (a device read a tick):
                # the model's scalar counters as THIS tick left them,
                # summed since the engine started, so that a reader has
                # them at both ends of any interval of the trace and
                # lays counts and device time of the same ticks side by
                # side.
                import jax

                scalars = jax.device_get({k: v for k, v in
                                          tick.counters.items() if not v.ndim})
                sp.set_metadata(**{k: int(v) for k, v in scalars.items()})
            # The tick's wall on the one clock: it could not start
            # before its dispatch nor before the tick ahead of it was
            # done, and `tick_ready` ended when the host knew it done
            # (between two ticks of a full pipeline: the interval
            # between their `tick_ready` ends).
            wall = self._ready_at - max(tick.at, ready_before)
            self._programs.landed(tick.counters, tick.sample, wall)
            self._credit_decode(tick.handles, wall)
            if self._block is not None:
                self._emit_blocks(tick, toks_host, done, sp)
                del tick, toks_host
                return
            for slot, handle in zip(map(int, tick.live), tick.handles):
                n = int(n_emit[slot]) if tick.spec else toks_host.shape[0]
                if tick.spec:
                    self._rows[slot] += n
                    if n > 0 and handle.meter is not None:
                        # Per-slot speculative accounting: a live slot's
                        # round proposed spec_k - 1 drafts, accepted n - 1.
                        handle.meter.note_spec(self.config.spec_k - 1, n - 1)
                for k in range(n):
                    if self._slots[slot].handle is not handle:
                        break      # finished earlier in the block (the
                        #            rest was speculative), or released
                        #            while the tick was in flight
                    self._emit(slot, int(toks_host[k, slot]))
            # the tick's outputs and their host view are let go under
            # this phase, not between two
            del tick, toks_host

    def _emit_blocks(self, tick: "_Tick", toks_host, done, span) -> None:
        """A landed block tick's tokens: a slot whose block this tick
        COMPLETED hands its L tokens to `_emit` in position order (its
        first block less the positions the prompt's tail fixed); `eos`,
        a stop token or `max_tokens` inside a block drop the block's
        rest. Every other live slot yields nothing this tick."""
        L = self._block.length
        n_blocks = n_tokens = 0
        for slot, handle in zip(map(int, tick.live), tick.handles):
            if not done[slot] or self._slots[slot].handle is not handle:
                continue        # mid-block, or released while in flight
            first = int(self._blk_skip[slot])
            self._blk_skip[slot] = 0
            self._rows[slot] += L
            n_blocks += 1
            for k in range(first, L):
                if self._slots[slot].handle is not handle:
                    break       # ended inside the block
                self._emit(slot, int(toks_host[slot, k]))
                n_tokens += 1
        self._blocks_emitted += n_blocks
        self._tokens_emitted += n_tokens
        span.set_metadata(tokens=n_tokens, blocks=n_blocks)

    def _read_back(self, *outs):
        """`llm_engine.tick_wait`'s two halves: wait until the tick's
        outputs are defined (`tick_ready`: the device is done and the
        host has been told), then read them on the host
        (`tick_readback`). The tick waited for is the one dispatched a
        step ago: the next one is already queued behind it, so the
        device has work while the host reads and emits. The copies are
        asked for BEFORE the wait, so they queue behind the tick as
        `np.asarray` of a pending array queues its own: asked for after
        it, each costs one more wake-up of this thread (0.1 ms a tick
        on a v5e host, PERF.md PR 34), and `tick_readback` is what is
        left of them once the tick is known done."""
        import numpy as np

        with self._loop.phase("llm_engine.tick_ready"):
            for x in outs:
                x.copy_to_host_async()
            nbytes = sum(x.nbytes for x in outs)    # behind the wait
            for x in outs:
                x.block_until_ready()
        self._ready_at = self._loop.now
        with self._loop.phase("llm_engine.tick_readback", bytes=nbytes):
            return [np.asarray(x) for x in outs]

    def _cover_rings(self, live) -> None:
        """A model's window kind: each live slot's ring is brought to
        cover the positions the tick about to go out writes, and no
        more than the window before them (kv_cache.WindowRing.cover)."""
        c = self.config
        for slot in map(int, live):
            # its rows, the pending token's own among them, and those
            # the tick in flight writes
            n = min(int(self._rows[slot]), c.max_seq_len)
            self._ring.cover(slot, n - 1, min(
                n - 2 + c.decode_block, c.max_seq_len - 1))

    def _tick_tables(self):
        """The block tables as the tick takes them: one, or one a kind
        for a model with a window kind (models/serving.py)."""
        if self._ring is None:
            return self._tables.copy()
        return {"full": self._tables.copy(),
                "window": self._ring.tables.copy()}

    def _live_rows(self, live) -> Dict[str, int]:
        """`rows`: KV rows the tick about to go out has to read in a
        layer that reads them all: the live slots' prompt and generated
        tokens, summed (the pending token's own row among them, and
        what a tick in flight writes: `_rows`), and
        counted beside the rows of the padded [num_slots, max_seq_len]
        view over all ticks (`stats()`). For a model with a window kind
        also `window_rows`, what a window layer has to read (a slot's
        rows or the window, whichever is less); the bytes of both
        kinds' blocks that the live slots hold are summed over all
        ticks beside their rows (`stats()["kv"]["live_bytes"]`)."""
        import numpy as np

        S = self.config.max_seq_len
        W = self._ring.window if self._ring is not None else S
        n = np.minimum(self._rows[live], S)
        rows, window_rows = int(n.sum()), int(np.minimum(n, W).sum())
        self._live_rows_sum += rows
        self._padded_rows_sum += self.config.num_slots * S
        if self._ring is None:
            return {"rows": rows}
        self._live_kv_bytes_sum += sum(
            len(self._slot_blocks[s]) * self._allocator.block_bytes
            + len(self._ring.slot_blocks[s])
            * self._ring.allocator.block_bytes for s in map(int, live))
        return {"rows": rows, "window_rows": window_rows}

    def _credit_decode(self, handles, dt: float) -> None:
        """Split one decode/verify tick's wall time evenly across the
        requests that were live in it (an attribution, not a hardware
        counter — documented as approximate in accounting.py). Runs
        BEFORE the emit loop so a request finishing this tick still
        gets billed for it."""
        if not self._acct or dt <= 0 or not handles:
            return
        share = dt / len(handles)
        for h in handles:
            if h.meter is not None:
                h.meter.note_chip("decode", share)

    def _spec_wanted(self, live) -> bool:
        """A speculative round runs only when EVERY live slot
        qualifies: greedy sampling (acceptance compares argmaxes),
        draft cache seeded (spec_ok), and (`_spec_fits`) spec_k - 1
        positions of headroom before the sequence limit. Mixed batches
        fall back to the plain tick — correctness never depends on
        this gate, only decode speed. What the host knows without the
        device: a step that wants a round settles first."""
        return (self._draft is not None
                and bool(self._spec_ok[live].all())
                and not bool((self._temp[live] > 0).any()))

    def _spec_fits(self, live) -> bool:
        """`_spec_wanted`'s last condition, from `_pos` read on the
        host (settled: nothing is in flight that would move it)."""
        pos_host = self._programs.positions()
        return bool((pos_host[live] <= self.config.max_seq_len
                     - self.config.spec_k).all())

    def _spec_wait(self, t, n_emit):
        """Read a speculative round back: (tokens [K, B] host, n_emit
        [B] host); the caller emits tokens[0:n_emit[s], s] per slot."""
        t_host, n_host = self._read_back(t, n_emit)
        live = int((n_host > 0).sum())
        self._spec_rounds += 1
        self._spec_proposed += (self.config.spec_k - 1) * live
        self._spec_accepted += int(n_host.sum()) - live
        self._metrics.spec_proposed.inc(
            float((self.config.spec_k - 1) * live))
        self._metrics.spec_accepted.inc(float(int(n_host.sum()) - live))
        return t_host.T, n_host

    def _update_gauges(self) -> None:
        m = self._metrics
        active = int(self._active.sum())
        with self._lock:
            depths = {lane: len(q) for lane, q in self._queues.items()}
        m.queue_depth.set(float(sum(depths.values())))
        for lane, d in depths.items():
            m.lane_queue_depth.set(float(d), tags={"lane": lane})
        if self._spec_proposed:
            m.spec_accept_ratio.set(
                self._spec_accepted / self._spec_proposed)
        m.active_slots.set(float(active))
        m.batch_utilization.set(active / self.config.num_slots)
        m.kv_blocks_used.set(float(self._allocator.used_blocks))
        m.kv_blocks_free.set(float(self._allocator.free_blocks))
        if self._prefix is not None:
            cur = self._prefix.stats()
            seen = self._prefix_seen
            for field, ctr in (("hits", m.prefix_hits),
                               ("misses", m.prefix_misses),
                               ("hit_tokens", m.prefix_hit_tokens),
                               ("evictions", m.prefix_evictions)):
                d = cur[field] - seen[field]
                if d > 0:
                    ctr.inc(float(d))
                    seen[field] = cur[field]
        if self._tiers is not None:
            ts = self._tiers.stats()
            for tier in ("host", "store"):
                cur, seen = ts[tier], self._tier_seen[tier]
                for field, ctr in (
                        ("hits", m.prefix_tier_hits),
                        ("misses", m.prefix_tier_misses),
                        ("spills", m.prefix_tier_spills),
                        ("promotes", m.prefix_tier_promotes)):
                    d = cur[field] - seen[field]
                    if d > 0:
                        ctr.inc(float(d), tags={"tier": tier})
                        seen[field] = cur[field]
                m.kv_tier_bytes.set(float(cur["bytes"]),
                                    tags={"tier": tier})
            m.kv_tier_bytes.set(float(self._allocator.used_bytes),
                                tags={"tier": "hbm"})

    def run(self, stop_event: threading.Event,
            idle_wait_s: float = 0.02) -> None:
        """Scheduler loop for a background thread (one per engine)."""
        while not stop_event.is_set():
            if not self.step():
                self._work.clear()
                if not self.has_work():
                    # empty for want of traffic: both counts read 0
                    # unless a submit raced the test above
                    with self._lock:
                        queued = sum(map(len, self._queues.values()))
                    with self._loop.phase(
                            "llm_engine.idle", queued=queued,
                            live=int(self._active.sum())):
                        self._work.wait(idle_wait_s)
        self._settle("stop")

    def drain(self, timeout: float = 300.0) -> None:
        """Synchronously step until queue and slots are empty (tests and
        offline batch use; do not mix with a run() thread)."""
        deadline = time.monotonic() + timeout
        while self.has_work():
            if time.monotonic() > deadline:
                raise TimeoutError("engine did not drain")
            self.step()
        self._settle("drain")

    def warmup(self) -> None:
        """Compile every program the engine can run — the decode tick
        plus one insert per prefill bucket, and the export gather at
        every row length (`export_rows`; a spill, a checkpoint or a
        peer pull picks one by its block count) — before real traffic.
        The prefix cache is bypassed while warming: a warm hit shrinks
        the padded suffix to a
        SMALLER bucket, leaving the larger bucket's insert uncompiled
        until a cache-miss request pays the compile inside its own
        latency. Synchronous; call before starting a run() thread.
        What it cost stays in `stats()["warmup"]`: `seconds`, its wall,
        and `programs`, what it added to `jit_stats()`, by program
        (first-call walls by stage; what any engine of the process
        compiled before or compiles later under the same names is not
        in them)."""
        from ray_tpu.observability.jit import jit_stats, jit_stats_since

        t0, before = time.monotonic(), jit_stats()
        prefix, self._prefix = self._prefix, None
        draft, self._draft = self._draft, None
        try:
            # max_tokens=2: a 1-token request finishes AT insert and the
            # decode tick would never trace. Draft disabled: phase one
            # compiles the PLAIN tick (the spec gate would otherwise
            # route every greedy warmup batch through a round).
            handles = [self.submit(Request(prompt=[1] * b, max_tokens=2))
                       for b in self.config.prefill_buckets]
            while any(h.finished_at is None for h in handles):
                self.step()
            if draft is not None:
                # Phase two: draft inserts (one per bucket) + the
                # speculative round program.
                self._draft = draft
                handles = [self.submit(
                    Request(prompt=[1] * b, max_tokens=2))
                    for b in self.config.prefill_buckets]
                while any(h.finished_at is None for h in handles):
                    self.step()
        finally:
            self._prefix = prefix
            self._draft = draft
        # the phase clock counts a warm engine's time: the compiles
        # above stood inside `admit` and `tick_dispatch`
        self._loop = _LoopClock()
        import jax

        if not self._pinned:    # else exports nothing (models/serving.py)
            for n in self.config.export_rows:   # one row alive at a time
                jax.block_until_ready(self._programs.export([0] * n))
        self._warmup = {"seconds": time.monotonic() - t0,
                        "programs": jit_stats_since(before)}

    # ------------------------------------------------------------ inspection

    @property
    def trace_count(self) -> int:
        """Number of engine XLA programs traced so far (compile guard:
        bounded by the per-family trace budgets under any workload —
        len(buckets) inserts + 1 tick, plus at most len(export_rows)
        exports, 1 adopt, 1 spec round, and len(buckets) draft inserts
        when wired)."""
        return sum(self._programs.traces().values())

    def slot_state(self, slot: int) -> Optional[Dict[str, Any]]:
        """One slot's rows of the model's per-slot state, on the host
        (None for a model that keeps none): what the slot holds after
        its last insert or tick.  A finished request's rows stay as it
        left them until the next admission into the slot zeroes them.
        For tests and for the benchmark's audit of the state's
        precision; call it between steps."""
        return self._programs.slot_state(slot)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            queued_by_lane = {lane: len(q)
                              for lane, q in self._queues.items()}
        device = self._programs.stats()
        out = {
            "num_slots": self.config.num_slots,
            "active_slots": int(self._active.sum()),
            "queued": sum(queued_by_lane.values()),
            "queued_by_lane": queued_by_lane,
            "completed": self._completed,
            "slot_reuses": self._slot_reuses,
            "preempted": self._preempted,
            "kv_layout": self.config.kv_layout,
            # which path the tick's attention compiled to
            "paged_attention": device["paged_attention"],
            # and the experts' grouped products, at the tick's shape
            # ("xla" also for a model that has none)
            "grouped_matmul": (
                self._model.grouped_matmul(self.model_config,
                                           self.config.num_slots)
                if self._model.grouped_matmul else "xla"),
            # what the ticks had to read against what the padded
            # [num_slots, max_seq_len] view holds
            "live_rows": self._live_rows_sum,
            "padded_rows": self._padded_rows_sum,
            # the same of the inserts: history + bucket rounded up to
            # the walk's tile (what `latent_moe._History` reads; what a
            # walk WOULD read, for a family that scores the whole padded
            # history) against the padded history's rows
            "insert_keys_walked": self._insert_keys_walked,
            "insert_keys_padded": self._insert_keys_padded,
            # which form the largest bucket's attention compiled to, the
            # model says (by backend and shape alone; "plain" for one
            # that has one form), and what the kernel's bounds let
            # through of the (query, key) tiles the loop multiplies
            "insert_attention": (
                self._model.insert_attention(
                    self.model_config, 0, self.config.prefill_buckets[-1],
                    self.config.max_seq_len)[0]
                if self._model.insert_attention else "plain"),
            "insert_attn_tiles_run": self._insert_attn_tiles_run,
            "insert_attn_tiles_dense": self._insert_attn_tiles_dense,
            # the scheduler thread's seconds and calls by phase
            "loop": self._loop.stats(),
            # `warmup`'s wall and the programs it compiled, as copies
            # (None until it has run)
            "warmup": self._warmup and {
                "seconds": self._warmup["seconds"],
                "programs": {k: dict(v) for k, v in
                             self._warmup["programs"].items()}},
            "traces": device["traces"],
            "trace_count": sum(device["traces"].values()),
            # the full kind's blocks; a model's window kind under
            # "window" (models/serving.py), and the bytes of both kinds
            # the live slots held, summed over all ticks as `live_rows`
            "kv": dict(self._allocator.stats(),
                       block_size=self.config.kv_block_size,
                       **({} if self._ring is None
                          else {"window": self._ring.stats(),
                                "live_bytes": self._live_kv_bytes_sum})),
            "migration": {
                "blocks": self._migrated_blocks,
                "bytes": self._migrated_bytes,
            },
        }
        if self._prefix is not None:
            out["prefix_cache"] = self._prefix.stats()
        if self._tiers is not None:
            out["kv_tiers"] = dict(
                self._tiers.stats(),
                promoted_blocks=self._promoted_blocks,
                promote_skips=self._promote_skips,
                spill_lands=self._spill_lands,
                spill_lands_waited=self._spill_lands_waited)
        if self._block is not None:
            # rows forwarded and tokens emitted, apart: a tick forwards
            # L rows a live slot and emits a block when it completes
            out["block"] = {
                "length": self._block.length,
                "slot_forwards": self._slot_forwards,
                "rows_forwarded": self._slot_forwards * self._block.length,
                "blocks_emitted": self._blocks_emitted,
                "tokens_emitted": self._tokens_emitted}
        if self._stateful:
            out["slot_state"] = {
                "bytes": device["slot_state_bytes"],
                "prompts_under_way": len(self._chunking)}
        counters = self._programs.counters()
        if counters:
            # the model's own, as the last tick READ BACK left them:
            # they agree with the tokens emitted
            out["counters"] = counters
        if self._draft is not None or self._spec_rounds:
            denom = max(self._spec_proposed, 1)
            out["spec"] = {
                "rounds": self._spec_rounds,
                "proposed": self._spec_proposed,
                "accepted": self._spec_accepted,
                "accept_ratio": self._spec_accepted / denom,
            }
        return out


# A landing whose rows were all on the host reads them in microseconds;
# one that blocks longer than this waited for the transfer.
_SPILL_READY_S = 1e-3


def _tier_store_put(prefix):
    """Object-store leg of the KV hierarchy: demote a KVPrefix below
    host RAM. Raises when no cluster is attached — KVTierManager then
    counts the drop and moves on (a dropped block is a future
    recompute, never an error)."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker_or_none

    if global_worker_or_none() is None:
        raise RuntimeError(
            "no cluster attached: object-store KV tier unavailable")
    return ray_tpu.put(prefix)


def _tier_store_get(ref):
    import ray_tpu

    return ray_tpu.get(ref, timeout=30.0)


def _block_prefixes(got, keys, block_size):
    """One single-block KVPrefix per key: block j of the exported host
    row `got` ({leaf: [L, row, bs, ...]}) under the covered prefix
    `keys[j]`, each a copy of its own (a view would keep the row)."""
    from ray_tpu.serve.llm.kv_cache import KVPrefix

    return [KVPrefix(tokens=tokens, block_size=block_size,
                     blocks={name: x[:, j:j + 1].copy()
                             for name, x in got.items()})
            for j, tokens in enumerate(keys)]

