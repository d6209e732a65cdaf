"""Plain reference for the block-diffusion decoder with softmax-routed
experts (`model_type` `sdar_moe`: SDAR-30B-A3B-Chat's block and its
family's `block_diffusion_generate`).

On rows x [T, D] in float32 under
`jax.default_matmul_precision("highest")`, a layer is

  u = RMSNorm(x; attn_norm)
  q = u W_q (H heads of hd), k = u W_k, v = u W_v (kvH heads);
  q and k RMS-normalised a head over hd (q_norm, k_norm), then rotary in
  the rotate-half form over all hd channels at theta^(-2i/hd), at the
  row's ABSOLUTE position; KV heads repeated H / kvH times;
  softmax(q kT / sqrt(hd)) v under an explicit [q, T] mask; W_o;
  h = x + that;  f = RMSNorm(h; ffn_norm)
  p = softmax(f W_r) over the router's R columns (float32); the
  `num_experts_per_tok` largest chosen (repeated argmax: no sort);
  weights p of the chosen / their sum (`norm_topk_prob`);
  y = h + sum of the chosen experts' SwiGLU.  DROPLESS: the sum runs
  over ALL held experts with a weight of zero where one was not chosen.
  Of the R routed experts this reference holds `num_experts` of them,
  those of `deployment.rank` (columns [rank E, (rank + 1) E)): an
  assignment to an expert held elsewhere adds nothing here.
  after the last layer: RMSNorm (norm_f), logits = x W_head, NOT
  shifted: the logits at position i are of the token AT i.

**The mask is block-causal**: with L = `block_length`, the key at j is
visible to the query at i iff j // L <= i // L.  Rows are of two sorts
(`_seen`): FINAL rows, the tokens of blocks that are done, under that
mask; and STATE rows, a block as it stands part way through its
denoising, which see the final rows of the blocks before theirs and the
state rows of their own block.

**Generation** (`generate`): the sequence is cut into blocks of L at
absolute positions.  The prompt's whole blocks are final.  Then block by
block: the block starts as the prompt's trailing P mod L tokens (first
block only), fixed, and the mask token M elsewhere; while an M is left:
forward the block, take at every M position x0 = argmax (M's own column
at -inf: a departure, the published loop never leaves a block whose
prediction is M) and its probability c under the softmax; fix positions
by `remasking_strategy`: `low_confidence_dynamic` every M position with
c > `confidence_threshold` if those are at least n_s, else the n_s of
largest c; `low_confidence_static` always the n_s of largest c;
`sequential` the leftmost n_s; n_s = L / `denoising_steps` spread
evenly, the remainder to the first steps.  Whether a position is fixed
is a FLAG beside the block, never `token == M`: a prompt may hold M's
id.  With no M left the block is final.

`served_token_deficits` judges a served generation from its tokens
alone.  The ORDER in which a block's positions were fixed is not given,
and with random weights confidences are near ties, so the reference
does not replay its own order: it infers the served one.  At each step
of a block, given the block's state, it computes its logits at every M
position and the deficit d_i = max_v logit_i[v] - logit_i[served_i] of
each and, under a low-confidence rule, the POSITION's deficit, max over
M positions of log c less log c of this one; it fixes the n_s positions
whose two deficits together are smallest (the leftmost n_s under
`sequential`; every position over the threshold where the dynamic rule
finds n_s of them) to their served tokens and charges each both.  A sound engine's true
order costs rounding at every step; a wrong guess here can only raise
later deficits, never hide one.  Step s of ALL whole blocks of a
request is one forward over [final rows ‖ every block's state rows]: as
many forwards a request as a block has steps.  A trailing block of
which only a part was served (`max_tokens`, an end token or a stop
inside it) is walked alone, in the rule's OWN order, its unserved
positions taking the reference's prediction.

No kernels, no cache, no sorting, no batching of requests, no code of
the program under test; the routed experts are drawn a block of
`E_BLOCK` at a time by the sibling `reference/latent_moe_decoder.py`'s
`expert_block`, which also gives the program its copy.

`init_as_trainer` / `adamw_trajectory` raise: there is no train cell.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Any, Dict, List, Mapping, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

# the sibling's plain parts; the family reaches EXPERT_KEYS, expert_bank
# and map_expert_blocks through this module
from reference.latent_moe_decoder import (  # noqa: F401
    E_BLOCK, EXPERT_KEYS, HIGHEST, _experts, _rms, expert_bank,
    expert_block, map_expert_blocks)

Q_BLOCK = 256       # queries a block of attention: [H, 256, T] scores
T_BLOCK = 2048      # tokens a block of the routed experts
ROWS = 256          # rows of logits [ROWS, V] at a time
COLUMNS = 16384     # columns of the head in float32 at a time
# Lengths the rows of a forward are padded to: few, so that few sets of
# programs compile.
PAD_LENGTHS = (64, 128, 256, 512, 1024, 2048, 3072, 4096, 5120)
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "q_norm", "k_norm", "wo",
              "ffn_norm", "router")
REMASKING = ("low_confidence_dynamic", "low_confidence_static", "sequential")
_PAD_BLOCK = 1 << 30    # the block of a padding row: no real row sees it


def _sizes(c: Mapping) -> Dict[str, int]:
    dep = c.get("deployment", {})
    E = c["num_experts"]
    return dict(
        D=c["hidden_size"], H=c["num_attention_heads"],
        kvH=c["num_key_value_heads"], hd=c["head_dim"],
        Fe=c["moe_intermediate_size"], E=E,
        R=dep.get("num_experts", E), rank=dep.get("rank", 0),
        k=c["num_experts_per_tok"], V=c["vocab_size"],
        L=c["num_hidden_layers"])


def shapes(c: Mapping) -> Dict[str, Any]:
    """The held model's shapes (the held experts as `expert_bank` makes
    them)."""
    z = _sizes(c)
    D, hd = z["D"], z["hd"]
    layer = {"attn_norm": (D,), "wq": (D, z["H"] * hd),
             "wk": (D, z["kvH"] * hd), "wv": (D, z["kvH"] * hd),
             "q_norm": (hd,), "k_norm": (hd,), "wo": (z["H"] * hd, D),
             "ffn_norm": (D,), "router": (D, z["R"]),
             "w_gate": (z["E"], D, z["Fe"]), "w_up": (z["E"], D, z["Fe"]),
             "w_down": (z["E"], z["Fe"], D)}
    return {"embed": (z["V"], D), "layers": [layer] * z["L"],
            "norm_f": (D,), "lm_head": (D, z["V"])}


def _std(c: Mapping) -> float:
    return float(c.get("initializer_range", 0.02))


def init_weights(c: Mapping, seed: int, dtype=jnp.bfloat16) -> Dict[str, Any]:
    """The benchmark's weights: normal(0, initializer_range) matrices,
    unit norm vectors, drawn on the device in one jitted call; for each
    layer what `expert_block` draws its HELD experts from, under
    `experts`: the keys of this rank's blocks among those of all R
    routed experts, so that the ranks of one seed hold different experts
    and together the uncut layer's."""
    z, std = _sizes(c), _std(c)
    sh = shapes(c)
    eb = min(E_BLOCK, z["E"])
    assert z["E"] % eb == 0 and z["R"] % z["E"] == 0, (z["E"], z["R"])
    held = z["E"] // eb

    def make(key):
        k_embed, k_head, k_layers = jax.random.split(key, 3)

        def draw(key, shape):
            return jax.random.normal(key, shape, dtype) \
                * jnp.asarray(std, dtype)

        layers = []
        for i, lk in enumerate(jax.random.split(k_layers, z["L"])):
            ks = dict(zip(LAYER_KEYS, jax.random.split(lk, len(LAYER_KEYS))))
            w = {name: jnp.ones(sh["layers"][i][name], dtype)
                 if name.endswith("norm")
                 else draw(ks[name], sh["layers"][i][name])
                 for name in LAYER_KEYS}
            keys = jax.random.split(jax.random.fold_in(lk, 1 << 20),
                                    z["R"] // eb)
            w["experts"] = {
                "keys": keys[z["rank"] * held:(z["rank"] + 1) * held],
                "like": jnp.zeros((0, eb, z["D"], z["Fe"]), dtype),
                "std": jnp.float32(std)}
            layers.append(w)
        return {"embed": draw(k_embed, sh["embed"]), "layers": layers,
                "norm_f": jnp.ones(sh["norm_f"], dtype),
                "lm_head": draw(k_head, sh["lm_head"])}

    return jax.jit(make)(jax.random.key(seed % (2 ** 32)))


def init_as_trainer(*_a, **_k):
    raise NotImplementedError("blockdiff_moe_decoder has no train cell")


def adamw_trajectory(*_a, **_k):
    raise NotImplementedError("blockdiff_moe_decoder has no train cell")


# ---------------------------------------------------------------- forward

def _f(a):
    return a.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [T, heads, hd], rotate-half: the pair (x[i], x[i + hd/2]) turned
    by pos * theta^(-2i/hd)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    f = _f(pos)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(f), jnp.sin(f)
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def _seen(q_blk, q_state, k_blk, k_state):
    """[q, T] bool.  A FINAL query row sees the final rows of its own
    block and of those before it; a STATE row sees the final rows of the
    blocks before its own and the state rows of its own."""
    q_blk, q_state = q_blk[:, None], q_state[:, None]
    k_blk, k_state = k_blk[None, :], k_state[None, :]
    final = ~q_state & ~k_state & (k_blk <= q_blk)
    state = q_state & jnp.where(k_state, k_blk == q_blk, k_blk < q_blk)
    return final | state


def _attention(q, k, v, blk, state, q_block):
    """Rows under `_seen`'s explicit mask: q, k, v [T, H, hd]."""
    T, H, hd = q.shape
    qb = min(q_block, T)
    assert T % qb == 0, (T, qb)

    def one(args):
        qi, bi, si = args
        s = jnp.einsum("qhd,khd->hqk", qi, k) / math.sqrt(hd)
        mask = _seen(bi, si, blk, state)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
        return jnp.einsum("hqk,khd->qhd", p, v)

    out = lax.map(one, (q.reshape(T // qb, qb, H, hd),
                        blk.reshape(T // qb, qb),
                        state.reshape(T // qb, qb)))
    return out.reshape(T, -1)


def route(c: Mapping, h, router):
    """h [T, D] -> weights [T, R] float32 over ALL routed experts: zero
    where one was not chosen, else its softmax probability over the sum
    of the chosen ones'."""
    z = _sizes(c)
    p = jax.nn.softmax(h @ _f(router), axis=-1)
    left, chosen = p, jnp.zeros_like(p)
    for _ in range(z["k"]):                  # the k largest, one at a time
        pick = jax.nn.one_hot(jnp.argmax(left, -1), z["R"], dtype=p.dtype)
        chosen = chosen + pick
        left = jnp.where(pick > 0, -jnp.inf, left)
    w = p * chosen
    if c.get("norm_topk_prob", True):
        w = w / w.sum(-1, keepdims=True)
    return w


def routed(c: Mapping, h, w):
    """The HELD experts' part of the layer's sum for h [T, D], `T_BLOCK`
    tokens at a time."""
    z = _sizes(c)
    mine = route(c, h, w["router"])[:, z["rank"] * z["E"]:
                                    (z["rank"] + 1) * z["E"]]
    T = h.shape[0]
    return jnp.concatenate([
        _experts(h[t:t + T_BLOCK], mine[t:t + T_BLOCK], w["experts"])
        for t in range(0, T, T_BLOCK)])


def block(c: Mapping, x, w, pos, blk, state, q_block=Q_BLOCK):
    """One decoder block on rows x [T, D] (float32) at positions `pos`,
    of blocks `blk`, state rows where `state`."""
    z = _sizes(c)
    T = x.shape[0]
    H, kvH, hd = z["H"], z["kvH"], z["hd"]
    eps, theta = float(c["rms_norm_eps"]), float(c["rope_theta"])
    u = _rms(x, w["attn_norm"], eps)
    q = _rms((u @ _f(w["wq"])).reshape(T, H, hd), w["q_norm"], eps)
    k = _rms((u @ _f(w["wk"])).reshape(T, kvH, hd), w["k_norm"], eps)
    v = (u @ _f(w["wv"])).reshape(T, kvH, hd)
    q, k = _rope(q, pos, theta), _rope(k, pos, theta)
    k = jnp.repeat(k, H // kvH, axis=1)
    v = jnp.repeat(v, H // kvH, axis=1)
    x = x + _attention(q, k, v, blk, state, q_block) @ _f(w["wo"])
    return x + routed(c, _rms(x, w["ffn_norm"], eps), w)


@partial(jax.jit, static_argnames=("cfg_key",))
def _block_jit(x, w, pos, blk, state, cfg_key):
    with jax.default_matmul_precision(HIGHEST):
        return block(_uncfg(cfg_key), x, w, pos, blk, state)


def _logits(x, norm_f, head, start, c, n):
    """Rows start .. start + n - 1 of x through the final norm and the
    head, `COLUMNS` of the vocabulary at a time (the head's float32
    copy is 1.2 GB whole)."""
    rows = _rms(lax.dynamic_slice_in_dim(x, start, n, 0), norm_f,
                float(c["rms_norm_eps"]))
    return jnp.concatenate([rows @ _f(head[:, a:a + COLUMNS])
                            for a in range(0, head.shape[1], COLUMNS)], -1)


@partial(jax.jit, static_argnames=("cfg_key", "n"))
def _tail_jit(x, norm_f, head, start, cfg_key, n):
    with jax.default_matmul_precision(HIGHEST):
        return _logits(x, norm_f, head, start, _uncfg(cfg_key), n)


@partial(jax.jit, static_argnames=("cfg_key", "n", "mask_id"))
def _predict_jit(x, norm_f, head, start, targets, cfg_key, n, mask_id):
    """What `_predictions` keeps of n rows' logits, which never leave
    this call: ([n] x0, log of its probability, the target's deficit)."""
    with jax.default_matmul_precision(HIGHEST):
        lg = _logits(x, norm_f, head, start, _uncfg(cfg_key), n)
        lg = jnp.where(jnp.arange(lg.shape[1]) == mask_id, -jnp.inf, lg)
        top, x0 = jnp.max(lg, -1), jnp.argmax(lg, -1)
        logc = top - jax.nn.logsumexp(lg, axis=-1)
        want = jnp.where(targets < 0, x0, targets)
        under = top - jnp.take_along_axis(lg, want[:, None], -1)[:, 0]
        return x0, logc, under


_KEEP = ("hidden_size", "num_attention_heads", "num_key_value_heads",
         "head_dim", "moe_intermediate_size", "num_experts",
         "num_experts_per_tok", "vocab_size", "rms_norm_eps", "rope_theta",
         "num_hidden_layers")


def _cfg_key(c: Mapping) -> tuple:
    z = _sizes(c)
    return tuple((k, c[k]) for k in _KEEP) + (
        ("norm_topk_prob", bool(c.get("norm_topk_prob", True))),
        ("deployment", (("num_experts", z["R"]), ("rank", z["rank"]))))


def _uncfg(cfg_key) -> Dict[str, Any]:
    c = dict(cfg_key)
    c["deployment"] = dict(c["deployment"])
    return c


def _padded(n: int) -> int:
    return next((p for p in PAD_LENGTHS if p >= n), -(-n // 1024) * 1024)


def _hidden(weights, c: Mapping, ids, pos, blk, state) -> jax.Array:
    """The rows (`ids` at positions `pos`, of blocks `blk`, state rows
    where `state`; numpy, [T]) through every layer, one full forward
    under `_seen`'s mask: [Tp, D], the rows padded on the right with
    rows that no real row sees."""
    T = len(ids)
    Tp = _padded(T)

    def pad(a, fill, dtype):
        out = np.full((Tp,), fill, dtype)
        out[:T] = a
        return jnp.asarray(out)

    ids, pos = pad(ids, 0, np.int32), pad(pos, 0, np.int32)
    blk, state = pad(blk, _PAD_BLOCK, np.int32), pad(state, False, bool)
    key = _cfg_key(c)
    x = weights["embed"][ids].astype(jnp.float32)
    for w in weights["layers"]:
        x = _block_jit(x, w, pos, blk, state, key)
    return x


def _chunks(x, start: int, n: int):
    """(first row, rows) of whole chunks of `ROWS` that cover rows
    start .. start + n - 1 of x; the surplus is the caller's to cut."""
    return [(s, min(ROWS, x.shape[0] - s))
            for s in range(start, start + n, ROWS)]


def logits_of_rows(weights, c: Mapping, ids, pos, blk, state, start: int,
                   n: int) -> jax.Array:
    """Reference logits [n, V] at rows start .. start + n - 1 of
    `_hidden`'s rows."""
    x = _hidden(weights, c, ids, pos, blk, state)
    return jnp.concatenate([
        _tail_jit(x, weights["norm_f"], weights["lm_head"], jnp.int32(s),
                  _cfg_key(c), m) for s, m in _chunks(x, start, n)])[:n]


def forward(weights, c: Mapping, tokens: Sequence[int]) -> jax.Array:
    """Logits [T, V] of ONE sequence of final rows under the block-causal
    mask; the logits at i are of the token AT i."""
    T, L = len(tokens), int(c["block_length"])
    pos = np.arange(T)
    return logits_of_rows(weights, c, np.asarray(tokens), pos, pos // L,
                          np.zeros((T,), bool), 0, T)


# ------------------------------------------------------------- generation

def transfer_counts(c: Mapping) -> List[int]:
    """n_s, positions a denoising step fixes at least: L / steps spread
    evenly, the remainder to the first steps."""
    base, rem = divmod(int(c["block_length"]), int(c["denoising_steps"]))
    return [base + (s < rem) for s in range(int(c["denoising_steps"]))]


def _rule(c: Mapping) -> Tuple[str, float]:
    rule = c.get("remasking_strategy", REMASKING[0])
    assert rule in REMASKING, rule
    return rule, float(c.get("confidence_threshold", 0.9))


def _rows_of(final: Sequence[int], L: int, states):
    """The rows of a forward: the FINAL tokens `final` at positions 0..,
    then each of `states` [(block index, its L tokens)] as state rows at
    its block's positions."""
    T = len(final)
    ids, pos = [np.asarray(final, np.int64)], [np.arange(T)]
    for b, toks in states:
        ids.append(np.asarray(toks, np.int64))
        pos.append(b * L + np.arange(L))
    ids, pos = np.concatenate(ids), np.concatenate(pos)
    state = np.arange(len(ids)) >= T
    return ids, pos, pos // L, state


def _predictions(weights, c, final, states, targets=None):
    """What the reference makes of every state row, M's own column at
    -inf: (its prediction x0, the log of x0's probability, how far the
    row's logit of `targets`' token lies under its maximum: 0 where no
    target is given or it is negative) [rows].  `ROWS` rows' logits at
    a time: 256 blocks' 1,024 rows of float32 logits would be 0.6 GB,
    and twice that beside the engine's pool."""
    L, M = int(c["block_length"]), int(c["mask_token_id"])
    ids, pos, blk, state = _rows_of(final, L, states)
    x = _hidden(weights, c, ids, pos, blk, state)
    start, n = len(final), L * len(states)
    want = np.full((n + ROWS,), -1, np.int32)
    if targets is not None:
        want[:n] = targets
    out = []
    for s, m in _chunks(x, start, n):
        out.append(_predict_jit(
            x, weights["norm_f"], weights["lm_head"], jnp.int32(s),
            jnp.asarray(want[s - start:s - start + m]), _cfg_key(c), m, M))
    x0, logc, under = (np.concatenate([np.asarray(o[i]) for o in out])[:n]
                       for i in range(3))
    return x0, logc.astype(np.float64), under.astype(np.float64)


def _choose(rule, thr, share, masked, logc):
    """The positions of a block that the RULE fixes at a step, in order:
    `masked` [L] bool, `logc` [L] the log confidence of each position's
    prediction; ties go to the left."""
    at = np.nonzero(masked)[0]
    n = min(share, len(at))
    if rule == "sequential":
        return list(at[:n])
    if rule == "low_confidence_dynamic":
        high = [i for i in at if math.exp(logc[i]) > thr]
        if len(high) >= n:
            return high
    return sorted(at, key=lambda i: (-logc[i], i))[:n]


def generate(weights, c: Mapping, prompt: Sequence[int], max_tokens: int,
             eos_id=None, record=None) -> List[int]:
    """The family's `block_diffusion_generate`, greedy, one full forward
    a step (for the CPU tests' sizes).  `record`, a list, gets (block,
    step, positions fixed) of every step."""
    L, M = int(c["block_length"]), int(c["mask_token_id"])
    rule, thr = _rule(c)
    share = transfer_counts(c)
    P = len(prompt)
    final = list(prompt[:P - P % L])
    toks = list(prompt[P - P % L:]) + [M] * (L - P % L)
    masked = np.arange(L) >= P % L
    out: List[int] = []
    skip = P % L
    while True:
        b, s = len(final) // L, 0
        while masked.any():
            x0, logc, _ = _predictions(weights, c, final, [(b, toks)])
            fix = _choose(rule, thr, share[min(s, len(share) - 1)], masked,
                          logc)
            for i in fix:
                toks[i], masked[i] = int(x0[i]), False
            if record is not None:
                record.append((b, s, [int(i) for i in fix]))
            s += 1
        for t in toks[skip:]:
            out.append(t)
            if len(out) >= max_tokens or t == eos_id:
                return out
        final += toks
        toks, masked, skip = [M] * L, np.ones((L,), bool), 0


def served_token_deficits(weights, c: Mapping, prompt: Sequence[int],
                          served: Sequence[int]) -> np.ndarray:
    """For each served token, how far the reference stands from having
    fixed it when and where the engine did (the module's docstring): 0
    where the reference would have fixed the same token at the same
    step."""
    L, M = int(c["block_length"]), int(c["mask_token_id"])
    rule, thr = _rule(c)
    share = transfer_counts(c)
    P, n = len(prompt), len(served)
    skip = P % L
    whole = (skip + n) // L                 # blocks served whole
    final = list(prompt) + list(served[:whole * L - skip]) if whole \
        else list(prompt[:P - skip])
    first = (P - skip) // L                 # the first generated block
    deficits = np.zeros((n,), np.float64)

    def charge(b, i, d):                    # position i of block b
        deficits[(b - first) * L + i - skip] = d

    def step_of(b, toks, masked, s, x0, logc, under, target):
        """One denoising step of block `b` as the engine must have made
        it, `target` [L] the served tokens (-1: not served), `under` [L]
        each one's deficit."""
        at = np.nonzero(masked)[0]
        known = [i for i in at if target[i] >= 0]
        d = {i: float(under[i]) for i in known}
        want = min(share[min(s, len(share) - 1)], len(at))
        if len(known) < len(at):
            # a block served in part: the rule's own order
            fix = _choose(rule, thr, want, masked, logc)
            for i in fix:
                if i in d:
                    charge(b, i, d[i])
                toks[i] = target[i] if i in d else int(x0[i])
                masked[i] = False
            return
        high = [i for i in at if math.exp(logc[i]) > thr]
        if rule == "low_confidence_dynamic" and len(high) >= want:
            fix, extra = high, {i: 0.0 for i in high}
        elif rule == "sequential":
            fix, extra = list(at[:want]), {i: 0.0 for i in at}
        else:
            # the positions that cost least in all, token and place
            fix, extra, left = [], {}, list(at)
            for _ in range(want):
                most = max(logc[j] for j in left)
                i = min(left, key=lambda j: (d[j] + most - logc[j], j))
                extra[i] = most - logc[i]
                fix.append(i)
                left.remove(i)
        for i in fix:
            charge(b, i, d[i] + extra[i])
            toks[i], masked[i] = target[i], False

    def walk(blocks):
        """`blocks` [(b, target [L])] denoised together, a forward a
        step, against `final`."""
        state = []
        for b, target in blocks:
            ours = b == first
            toks = [int(t) for t in prompt[P - skip:]] + [M] * (L - skip) \
                if ours else [M] * L
            state.append((b, toks, np.arange(L) >= (skip if ours else 0),
                          target))
        s = 0
        while any(m.any() for _, _, m, _ in state):
            x0, logc, under = _predictions(
                weights, c, final, [(b, toks) for b, toks, _, _ in state],
                np.concatenate([t for _, _, _, t in state]))
            for j, (b, toks, masked, target) in enumerate(state):
                if masked.any():
                    rows = slice(j * L, (j + 1) * L)
                    step_of(b, toks, masked, s, x0[rows], logc[rows],
                            under[rows], target)
            s += 1

    def target_of(b):
        lo = (b - first) * L - skip
        return [int(served[lo + i]) if 0 <= lo + i < n else -1
                for i in range(L)]

    if whole:
        walk([(first + g, target_of(first + g)) for g in range(whole)])
    if whole * L - skip < n:                # a trailing block served in part
        walk([(first + whole, target_of(first + whole))])
    return deficits
