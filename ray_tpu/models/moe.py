"""Mixture-of-Experts layer — expert parallelism over a mesh axis.

SURVEY §2.7: the reference has NO in-repo expert parallelism (delegated
to user libraries); this is the net-new TPU-native implementation. The
design is the GShard/Switch dispatch pattern rather than a scatter loop:

  router logits -> top-k experts per token -> capacity-masked one-hot
  dispatch tensor -> three einsums (dispatch, expert FFN, combine).

Everything is dense, fixed-shape einsums, so XLA tiles them onto the MXU
and — when the expert dimension is sharded over a mesh "expert" axis
while tokens are data-sharded — inserts the all-to-alls over ICI
automatically. No hand-written collectives; the mesh does EP.

Sharding recipe (see `moe_param_specs`): experts [E, ...] sharded
P("expert", ...); token tensors data-sharded; jit with those out/in
shardings and GSPMD places dispatch/combine all-to-alls on the ICI ring.

Two expert layers live here.  `dropless_moe` is THE dropless layer, the
one every served model runs: the assignments are laid in expert order
and three grouped matrix products (`ops/grouped_matmul.py` on a TPU in
bf16, `jax.lax.ragged_dot` elsewhere) run over the experts this chip
holds (`share`), so every token gets all of its held experts whatever
the load and an expert nobody picked is not read; an assignment to a
ZERO-COMPUTE expert (`n_zero`: the router's last columns) is in no
group, reads no weight and adds its weight times the token itself.
Where the chip holds a small share of the router's columns, only the
picks that have a group here are gathered, multiplied and added back
(`_held_picks`: passes of `compact_rows` rows); where it holds a quarter
or all of them, all `tokens x top_k` are sorted and walked at once
(`_all_picks`).
Its routing rule is an argument (`softmax_top_k`, `sigmoid_bias_top_k`,
`softmax_bias_top_k`).
`moe_layer` below is the older capacity-dispatch layer that
`LlamaConfig.n_experts` trains with; it DROPS tokens past an expert's
capacity and is due for folding into the dropless one (ROADMAP Design).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from ray_tpu.ops import grouped_matmul


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    dim: int
    hidden_dim: int          # per-expert FFN width
    n_experts: int = 8
    top_k: int = 2
    capacity_factor: float = 1.25
    router_aux_weight: float = 1e-2
    dtype: Any = jnp.bfloat16

    def capacity(self, n_tokens: int) -> int:
        cap = int(self.capacity_factor * n_tokens * self.top_k
                  / self.n_experts)
        return max(cap, self.top_k)


def init_moe_params(cfg: MoEConfig, key: jax.Array,
                    param_dtype=jnp.float32) -> Dict[str, jax.Array]:
    kr, kg, ku, kd = jax.random.split(key, 4)
    d, f, e = cfg.dim, cfg.hidden_dim, cfg.n_experts
    scale = d ** -0.5
    return {
        "router": (jax.random.normal(kr, (d, e)) * scale).astype(param_dtype),
        "w_gate": (jax.random.normal(kg, (e, d, f)) * scale).astype(param_dtype),
        "w_up": (jax.random.normal(ku, (e, d, f)) * scale).astype(param_dtype),
        "w_down": (jax.random.normal(kd, (e, f, d)) * (f ** -0.5)).astype(param_dtype),
    }


def moe_param_specs() -> Dict[str, P]:
    """PartitionSpecs placing experts on the "expert" mesh axis (router
    stays replicated — it is tiny and every token needs it)."""
    return {
        "router": P(),
        "w_gate": P("expert", None, None),
        "w_up": P("expert", None, None),
        "w_down": P("expert", None, None),
    }


def _top_k_dispatch(probs: jax.Array, k: int, capacity: int,
                    out_dtype) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """probs [T, E] fp32 -> (dispatch [T, E, C], combine [T, E, C],
    raw_assign [k, T, E]).

    Capacity enforcement: tokens beyond an expert's C slots are dropped
    (their combine weight is 0 → they pass through the residual only),
    keeping every shape static for XLA. ALL position bookkeeping is
    int32 — counts beyond 256 would silently round in bf16 and collide
    capacity slots.
    """
    T, E = probs.shape
    topk_probs, topk_idx = jax.lax.top_k(probs, k)          # [T, k]
    # For each of the k choices: one-hot expert assignment [k, T, E].
    assign_raw = jax.nn.one_hot(topk_idx.T, E, dtype=jnp.int32)
    # Position of each token within its expert's queue, counted across
    # choice-major order so k=0 assignments fill first.
    flat = assign_raw.reshape(k * T, E)
    pos = (jnp.cumsum(flat, axis=0) - flat).reshape(k, T, E)
    assign = assign_raw * (pos < capacity)
    slot = jax.nn.one_hot(jnp.sum(pos * assign, axis=-1), capacity,
                          dtype=jnp.int32)                   # [k, T, C]
    # dispatch[t, e, c] = 1 iff token t occupies slot c of expert e.
    dispatch = jnp.einsum("kte,ktc->tec", assign, slot).astype(out_dtype)
    weight = jnp.sum(assign.astype(jnp.float32)
                     * topk_probs.T[..., None], axis=0)      # [T, E]
    combine = dispatch * weight[..., None].astype(out_dtype)
    return dispatch, combine, assign_raw


def moe_layer(x: jax.Array, params: Dict[str, jax.Array], cfg: MoEConfig
              ) -> Tuple[jax.Array, jax.Array]:
    """x [B, S, D] -> (out [B, S, D], aux_loss scalar).

    aux_loss is the standard load-balancing term (Switch eq. 4):
    E * sum_e f_e * p_e, minimized when routing is uniform.
    """
    B, S, D = x.shape
    T = B * S
    C = cfg.capacity(T)
    xt = x.reshape(T, D)

    logits = (xt @ params["router"].astype(cfg.dtype)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)                  # [T, E]
    dispatch, combine, assign_raw = _top_k_dispatch(
        probs, cfg.top_k, C, cfg.dtype)

    # Load-balance aux loss (Switch eq. 4) from the PRE-capacity
    # assignment: computed post-drop it would saturate at C/T exactly
    # when an expert overloads — the regime the loss exists to fix.
    frac_tokens = jnp.mean(assign_raw.astype(jnp.float32),
                           axis=(0, 1)) * cfg.top_k          # [E]
    frac_probs = jnp.mean(probs, axis=0)                     # [E]
    aux = cfg.n_experts * jnp.sum(frac_tokens * frac_probs) \
        * cfg.router_aux_weight

    # Dispatch -> per-expert FFN -> combine: three MXU einsums; with
    # experts sharded over the mesh "expert" axis these become the EP
    # all-to-alls.
    expert_in = jnp.einsum("tec,td->ecd", dispatch, xt)      # [E, C, D]
    gate = jax.nn.silu(jnp.einsum(
        "ecd,edf->ecf", expert_in, params["w_gate"].astype(cfg.dtype)))
    up = jnp.einsum("ecd,edf->ecf", expert_in,
                    params["w_up"].astype(cfg.dtype))
    h = gate * up
    expert_out = jnp.einsum("ecf,efd->ecd", h,
                            params["w_down"].astype(cfg.dtype))
    out = jnp.einsum("tec,ecd->td", combine, expert_out)
    return out.reshape(B, S, D), aux


# ---------------------------------------------------------------------------
# The dropless layer: rows by expert, grouped matrix products, back to tokens
# ---------------------------------------------------------------------------

Routing = Callable[[jax.Array, Dict[str, jax.Array]],
                   Tuple[jax.Array, jax.Array]]


def softmax_top_k(k: int, norm: bool = False) -> Routing:
    """The k largest softmax probabilities, as they are, or (`norm`)
    over their sum."""

    def route(logits, params):
        w, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)
        return idx, w / w.sum(-1, keepdims=True) if norm else w

    return route


def sigmoid_bias_top_k(k: int, scale: float = 1.0,
                       eps: float = 1e-20) -> Routing:
    """Sigmoid scores; the k experts with the largest score PLUS the
    selection bias (`router_bias`, a buffer, not a weight) are chosen,
    and weighted by their scores WITHOUT it, renormalised to sum to
    `scale` (the `noaux_tc` rule with one group).  `eps` is what the
    published rule adds to the sum it divides by."""

    def route(logits, params):
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(
            s + params["router_bias"].astype(jnp.float32), k)
        w = jnp.take_along_axis(s, idx, axis=-1)
        return idx, w / (w.sum(-1, keepdims=True) + eps) * scale

    return route


def softmax_bias_top_k(k: int, scale: float = 1.0) -> Routing:
    """Softmax scores over the router's WHOLE width (zero-compute
    columns among them); the k with the largest score PLUS the selection
    bias (`router_bias`, a buffer, not a weight) are chosen, and
    weighted by their scores WITHOUT it x `scale`, not renormalised: a
    token's weights sum to what its chosen k hold of the softmax."""

    def route(logits, params):
        s = jax.nn.softmax(logits, axis=-1)
        _, idx = jax.lax.top_k(
            s + params["router_bias"].astype(jnp.float32), k)
        return idx, jnp.take_along_axis(s, idx, axis=-1) * scale

    return route


def grouped_path(rows: int, w_shape: Tuple[int, int, int], dtype) -> str:
    """"kernel" | "xla": which grouped product `dropless_moe` compiles
    to for `rows` assignments over experts `w_gate` [E, D, F] on this
    backend (`engine.stats()` shows it): `ops.grouped_matmul` where it
    engages for BOTH shapes, D -> F and F -> D, else `lax.ragged_dot`."""
    e, d, f = w_shape
    both = (grouped_matmul.engages(rows, e, d, f, dtype)
            and grouped_matmul.engages(rows, e, f, d, dtype))
    return "kernel" if both else "xla"


def serving_grouped_path(config, slots: int) -> str:
    """`ServingFns.grouped_matmul` of a model whose expert layers are
    `dropless_moe`: `grouped_path` at the decode tick's shape, `slots`
    tokens of `top_k` assignments over the experts this chip holds.
    The path hangs on dtype and widths alone; the rows the products are
    COMPILED for are `compact_rows`': where a small share of the router
    is held, M, one pass over the picks that have a group here
    (assignments to experts held elsewhere and zero-compute picks are
    never rows); else `slots * top_k`, an upper bound on the rows a tick
    fills (dead slots, and picks of no group, sort last and are in
    none)."""
    held = getattr(config, "n_held_experts", config.n_experts)
    return grouped_path(
        slots * config.top_k,
        (held, config.dim, config.expert_hidden_dim), config.dtype)


def _grouped_product(sizes, rows, w_shape, dtype, layer=None):
    """The ONE call site of a grouped product: (xs [rows, K], w [E, K,
    N]) -> [rows, N] over `sizes`, by the Pallas kernel (its walk
    planned once here, shared by the layer's products) or by
    `lax.ragged_dot`; `by_rows=True` for a w [E, N, K], and with `layer`
    w is a stack of banks of which that one is multiplied
    (`ops.grouped_matmul.grouped_matmul`)."""
    if grouped_path(rows, w_shape, dtype) == "kernel":
        scalars = grouped_matmul.plan(sizes, rows)
        return lambda xs, w, by_rows=False: grouped_matmul.grouped_matmul(
            xs, w, sizes, scalars, by_rows, layer)
    return lambda xs, w, by_rows=False: grouped_matmul.ragged(
        xs, w if layer is None else w[layer], sizes, by_rows)


_SKEW = 2      # `compact_rows`: rows a pass over a uniform router's


def compact_rows(rows: int, held: int, routed: int) -> int:
    """M, the rows one pass of `dropless_moe` walks of `rows`
    assignments where the layer holds `held` of the router's `routed`
    columns; `rows` itself where all of them are walked at once.

    M is what a uniform router would send here times `_SKEW`, in whole
    row tiles of the grouped product: from shapes alone, so one M a
    bucket, shared by a layer's products and by all layers.  The walk of
    the held picks has a fixed cost the walk of all picks has not (a
    cumulative sum, a scatter of `rows` scalars, a loop, a scatter-add
    that XLA sorts for) and its added row costs about three gathered
    ones, so it engages where M is a QUARTER of the rows or less, a
    share of an eighth: on a v5e at a share of 1/48, 1/32, 1/16, 1/8 a
    1024- to 2048-row call took 0.46, 0.46, 0.49-0.91, 0.64-0.99 of the
    all-picks walk's time and a tick's 0.89-0.98; at a quarter (M half
    the rows) 0.84 to 1.19, and every tick 1.00-1.03 (PERF.md section
    6, PR 53)."""
    m = min(rows, _SKEW * -(-rows * held // routed))
    tile = grouped_matmul.row_tile(m)
    m = -(-m // tile) * tile
    return m if 4 * m <= rows else rows


def walk_counts(sizes: jax.Array, rows: int, routed: int
                ) -> Dict[str, jax.Array]:
    """What `dropless_moe`'s walk cost, from what it returns: `sizes`
    [L, E] the held experts' counts of L calls of `rows` assignments
    each under a router `routed` wide -> `moe_rows_walked` (passes x M:
    the rows gathered, multiplied and added back; `rows` a call where
    all picks are walked at once), `moe_rows_dense` (`rows` a call) and
    `moe_extra_passes` (passes beyond a call's first: a router that
    sends more than M rows here)."""
    m = compact_rows(rows, sizes.shape[-1], routed)
    passes = -(-jnp.sum(sizes, axis=-1, dtype=jnp.int32) // m)
    if m == rows:                   # one walk of all rows, whatever is held
        passes = jnp.ones_like(passes)
    return {"moe_rows_walked": jnp.sum(passes) * m,
            "moe_rows_dense": jnp.asarray(sizes.shape[0] * rows, jnp.int32),
            "moe_extra_passes": jnp.sum(jnp.maximum(passes - 1, 0))}


def _all_picks(x, idx, w, E, experts):
    """Every pick has a group (but a dead token's, `idx` == E): the
    T * k assignments sorted by expert, one walk of all of them, back by
    the inverse permutation."""
    T, k = idx.shape
    flat = idx.reshape(T * k)
    order = jnp.argsort(flat)                               # stable
    sizes = jnp.zeros((E,), jnp.int32).at[flat].add(1, mode="drop")
    xs = x[order // k]                                      # [T * k, D]
    ys = experts(xs, sizes)
    # rows past the last group belong to no expert: whatever the
    # grouped product left there must not reach a token
    ys = jnp.where((jnp.arange(T * k) < sizes.sum())[:, None], ys, 0)
    back = jnp.zeros((T * k,), jnp.int32).at[order].set(
        jnp.arange(T * k, dtype=jnp.int32))
    y = jnp.einsum("tkd,tk->td",
                   ys[back].reshape(T, k, x.shape[-1]).astype(jnp.float32),
                   w)
    return y, sizes


def _held_picks(x, idx, w, E, M, experts):
    """A small share of the router's columns is held: only the picks
    with a group here (`idx` < E) are gathered, multiplied and added
    back, M of them a PASS and as many passes as they fill, so
    nothing is dropped whatever the router does and a call costs what
    its held picks cost.  No sort: a pick's row is its expert's first
    row plus its rank among that expert's picks, both from one
    cumulative sum over the [T * k, E] comparison."""
    T, k = idx.shape
    N = T * k
    P = -(-N // M)                                          # passes at most
    flat = idx.reshape(N)
    mine = flat[:, None] == jnp.arange(E, dtype=flat.dtype)[None, :]
    upto = jnp.cumsum(mine, axis=0, dtype=jnp.int32)        # [N, E]
    sizes = upto[-1]
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    row = jnp.where(mine, starts[None, :] + upto - 1, 0).sum(-1)
    # a pick in no group lands past the last row, each on a row of its own
    row = jnp.where(flat < E, row, P * M + jnp.arange(N))
    picks = jnp.zeros((P * M,), jnp.int32).at[row].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop",
        unique_indices=True).reshape(P, M)
    w = w.reshape(N)

    def one_pass(p, y):
        at = picks[p]                                       # [M], by expert
        real = p * M + jnp.arange(M) < ends[-1]
        token = at // k
        ys = experts(x[token], jnp.clip(ends - p * M, 0, M)
                     - jnp.clip(starts - p * M, 0, M))
        # rows past the last group belong to no expert: whatever the
        # grouped product left there must not reach a token
        ys = jnp.where(real[:, None], ys.astype(jnp.float32), 0)
        return y.at[token].add(ys * w[at][:, None])

    y = jax.lax.fori_loop(0, -(-ends[-1] // M), one_pass,
                          jnp.zeros((T, x.shape[-1]), jnp.float32))
    return y, sizes


def dropless_moe(x: jax.Array, params: Dict[str, jax.Array],
                 routing: Routing, live: Optional[jax.Array] = None,
                 share: Optional[Tuple[int, int]] = None, n_zero: int = 0,
                 layer: Optional[jax.Array] = None
                 ) -> Tuple[jax.Array, jax.Array]:
    """x [T, D] -> (y [T, D], tokens routed to each HELD expert [E]
    int32; with `n_zero`, one more entry: the zero picks).

    params: `router` [D, R], what `routing` reads beside it, and the
    held experts' SwiGLU weights `w_gate` / `w_up` [E, D, F], `w_down`
    [E, F, D].  The expert's FORM is data: where `params` has no
    `w_gate` an expert is `w_down relu(w_up x)^2`, two grouped products
    and no gate, and `w_up` lies [E, F, D], its output channels down the
    rows as `w_down`'s input channels do (a width F of half lane rows,
    1856, then stores no padded lane: `ops.grouped_matmul`'s `by_rows`).
    With `layer` (a traced index: the repeat of a `lax.scan` over
    stacked layers) the expert weights are STACKS [L, E, ..] handed in
    whole and this layer's bank is read where it lies (a bank cut out
    of the stack by the scan is copied for every product).
    Router logits, scores and selection are float32 (a
    float32 matrix product, not the chip's one-pass default); the expert
    products run in x's dtype.  No capacity: the assignments are laid in
    expert order, each expert's rows are one group of the grouped
    products, and every row's result is added to its token, weighted, in
    float32.  `live` [T] bool takes rows out altogether (padding, a
    dead decode slot): they are in no group, add nothing to the counts
    and get y = 0, so an expert only they picked is not read.

    Which assignments become ROWS is decided by how much of the router
    the layer holds, a static branch on shapes (`compact_rows`: E
    against the router's R).  Holding a small share (an eighth of the
    columns or less), only the picks that have a group here are rows
    (`_held_picks`): M = `compact_rows(T * k, E, R)` of them a pass,
    twice what a uniform router would send here, in as many passes as
    the held picks fill (`ceil(held / M)`, a loop whose trip count is
    data: one where the router is anywhere near uniform, `T * k / M`
    where it sends everything here), so the layer stays dropless and
    exact for every routing and costs what its held picks cost.
    Holding more (a quarter; every column, where `share` is None or of
    one shard and `n_zero` 0), all T * k are sorted, gathered and walked
    at once (`_all_picks`), the picks of no group sorting last.  The
    arithmetic is the same either way (a token's at most k float32 terms
    may be added in another order); `walk_counts` says from the counts
    what a call walked.

    The router's R columns are the routed experts, then `n_zero`
    ZERO-COMPUTE experts (identities): an assignment to one of those is
    in no group and reads no weight; it adds its weight times the token
    itself, `sum_k w_k x` over a token's zero picks, computed here for
    every live token whatever `share` says (a token's zero picks are
    where the token is, and no share's).  So the rows the products fill
    vary from token to token: k less its zero picks.  The live tokens'
    zero picks are counted in the counts' last entry, [E + 1].

    `share` (rank r, of n) says WHICH routed experts this layer holds:
    the contiguous range [r E, (r+1) E) of the R - n_zero routed
    columns, `w_gate` / `w_up` / `w_down` being those E = (R - n_zero)
    / n.  Routing, the k chosen and their weights are over all R columns
    as published; an assignment to an expert held elsewhere is in no
    group here and reads nothing, so y is the part of the layer's result
    that the held experts give plus the zero picks' (the shares' parts,
    with the zero picks' counted once, add up to the whole layer's) and
    the counts are the held experts' own.  A router that is not
    `n * E + n_zero` wide is refused.

    The three grouped products run by ONE of two paths, chosen by
    backend, dtype and shape alone (`grouped_path`): on a TPU, in bf16,
    with D and F in whole lane rows, the Pallas kernel of
    `ops/grouped_matmul.py`, which reads each TOUCHED expert's matrix
    once at a row tile that fits the group; everywhere else (every CPU
    run unless a test forces the interpreter, float32 as the model
    tests run it) `lax.ragged_dot`, the reference.  The same rows and
    the same arithmetic either way: bf16 operands, float32
    accumulation, one rounding."""
    T, D = x.shape
    R = params["router"].shape[-1]
    gated = "w_gate" in params
    E = params["w_down"].shape[-3]
    shards = 1 if share is None else share[1]
    if R != shards * E + n_zero:
        raise ValueError(
            f"dropless_moe: a router {R} wide does not route over "
            f"{shards} x {E} held experts + {n_zero} zero-compute ones "
            f"(= {shards * E + n_zero})")
    with jax.named_scope("router"):
        logits = jnp.dot(x.astype(jnp.float32),
                         params["router"].astype(jnp.float32),
                         precision=jax.lax.Precision.HIGHEST)
        idx, w = routing(logits, params)                    # [T, k]
        if n_zero:
            zero = idx >= R - n_zero
            if live is not None:
                zero = zero & live[:, None]
            w_zero = jnp.where(zero, w, 0.0).sum(-1)        # [T]
        if share is not None or n_zero:
            idx = idx - (0 if share is None else share[0] * E)
            held = (idx >= 0) & (idx < E)
            idx = jnp.where(held, idx, E)                   # sorts last
            w = jnp.where(held, w, 0.0)
        if live is not None:
            idx = jnp.where(live[:, None], idx, E)          # sorts last
            w = jnp.where(live[:, None], w, 0.0)
    dt = x.dtype
    F = params["w_down"].shape[-2]

    def experts(xs, sizes):
        """xs [rows, D] sorted by expert, `sizes` [E] rows each -> what
        the experts make of them [rows, D] (rows past the last group
        hold anything)."""
        product = _grouped_product(sizes, xs.shape[0], (E, D, F), dt, layer)
        if gated:
            gate = product(xs, params["w_gate"].astype(dt))
            up = product(xs, params["w_up"].astype(dt))
            return product(jax.nn.silu(gate) * up,
                           params["w_down"].astype(dt))
        up = product(xs, params["w_up"].astype(dt), by_rows=True)
        return product(jnp.square(jax.nn.relu(up)),
                       params["w_down"].astype(dt))

    with jax.named_scope("experts"):
        M = compact_rows(idx.size, E, R)
        if M == idx.size:
            y, sizes = _all_picks(x, idx, w, E, experts)
        else:
            y, sizes = _held_picks(x, idx, w, E, M, experts)
    if n_zero:
        with jax.named_scope("zero"):
            y = y + w_zero[:, None] * x.astype(jnp.float32)
            sizes = jnp.append(sizes, jnp.sum(zero, dtype=jnp.int32))
    return y.astype(dt), sizes
