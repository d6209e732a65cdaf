"""`models/moe.py::dropless_moe` where the layer holds a SMALL SHARE of
the router's columns (`share=`, `n_zero=`; an eighth or less): only the
picks that have a group here are gathered, multiplied and added back,
`compact_rows` of them a pass and as many passes as they fill
(`_held_picks`), against a plain reference that applies every token's
held experts one by one in float32.

Tolerances and their reasons
----------------------------
* 1e-5 on results of magnitude 1 to 10, float32 against float32 on the
  CPU: the layer and the reference differ in the ORDER of a token's at
  most k float32 terms (and of a product's inner sums) only, which reads
  under 2e-6 here.
* The kernel cases run bf16 at widths that tile through the Pallas
  interpreter against `lax.ragged_dot` on the same compact rows: 2 ulp
  of bf16 at the results' size, as the other both-paths tests hold.
* The counts are integers and equal.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import moe

F32 = jnp.float32
D, F, K = 16, 8, 3          # model width, expert width, picks a token


def _layer(E, shards, Z, gated, stack, dtype=F32, d=D, f=F, bias=None):
    """(params of share 1 of `shards` x E routed experts beside Z
    zero-compute ones (of ONE shard: all E), every routed expert's
    weights for the reference).
    Non-gated experts store `w_up` [E, F, D]; `stack` puts the held bank
    at index 1 of a stack of two."""
    R, rank = shards * E + Z, min(1, shards - 1)
    ks = jax.random.split(jax.random.key(3), 4)

    def draw(key, *shape):
        return jax.random.normal(key, shape, F32) * shape[-2] ** -0.5

    every = {"w_up": draw(ks[1], shards * E, d, f),
             "w_down": draw(ks[2], shards * E, f, d)}
    if gated:
        every["w_gate"] = draw(ks[3], shards * E, d, f)
    held = {n: w[rank * E:(rank + 1) * E] for n, w in every.items()}
    if not gated:
        held["w_up"] = jnp.swapaxes(held["w_up"], 1, 2)
    if stack:
        held = {n: jnp.stack([jnp.zeros_like(w), w])
                for n, w in held.items()}
    p = {"router": draw(ks[0], d, R),
         "router_bias": jnp.zeros((R,)) if bias is None else bias,
         **{n: w.astype(dtype) for n, w in held.items()}}
    return p, every


def _plain(x, p, every, rule, E, shards, Z, live):
    """Every token's held experts applied one by one in float32, its
    zero picks' weights times the token: (y [T, D], the held experts'
    counts and, with Z, the zero picks')."""
    rank = min(1, shards - 1)
    x = np.asarray(x, np.float64)
    logits = jnp.dot(jnp.asarray(x, F32), p["router"].astype(F32),
                     precision=jax.lax.Precision.HIGHEST)
    idx, w = (np.asarray(a) for a in rule(logits, p))
    alive = np.ones(len(x), bool) if live is None else np.asarray(live)
    y = np.zeros_like(x)
    sizes = np.zeros(E + (1 if Z else 0), np.int64)
    for t in np.flatnonzero(alive):
        for e, wt in zip(idx[t], w[t]):
            if e >= shards * E:
                y[t] += wt * x[t]
                sizes[-1] += 1
            elif rank * E <= e < (rank + 1) * E:
                up = x[t] @ np.asarray(every["w_up"][e], np.float64)
                if "w_gate" in every:
                    gate = x[t] @ np.asarray(every["w_gate"][e], np.float64)
                    h = gate / (1 + np.exp(-gate)) * up
                else:
                    h = np.square(np.maximum(up, 0))
                y[t] += wt * (h @ np.asarray(every["w_down"][e], np.float64))
                sizes[e - rank * E] += 1
    return y, sizes


def _run(x, p, rule, E, shards, Z, live, stack):
    kw = {"layer": jnp.int32(1)} if stack else {}
    if shards > 1:
        kw["share"] = (1, shards)
    y, sizes = jax.jit(lambda x, p: moe.dropless_moe(
        x, p, rule, live=live, n_zero=Z, **kw))(x, p)
    return np.asarray(y, np.float64), np.asarray(sizes)


# held experts, shards, zero-compute columns: 2 of 32 but where every
# pick is to be held (a token's picks are distinct: 4 of 64 for top 3)
SHARES = {"share": (2, 16, 0), "n_zero": (2, 1, 30), "both": (2, 8, 16)}


@pytest.mark.parametrize("stack", [False, True], ids=["bank", "stack"])
@pytest.mark.parametrize("rows", ["all", "live"])
@pytest.mark.parametrize("form", ["swiglu", "relu2"])
@pytest.mark.parametrize("held", [
    "share", "n_zero", "both", "every-pick-held", "uniform-16-of-768",
    "kernel", "kernel-every-pick-held"])
def test_held_picks_equal_the_plain_layer(held, form, rows, stack,
                                          monkeypatch):
    """Over what a share can be (other shards' experts, zero-compute
    columns, both) x the expert's form x dead rows x a bank or a stack
    of banks: y to 1e-5 and the counts exactly.  `every-pick-held`: a
    selection bias that sends EVERY pick to this share, more rows than a
    pass has, so `moe_extra_passes` > 0 and nothing is dropped;
    `uniform-16-of-768`: the published router's widths under no bias,
    one pass of under a quarter of the rows.  The `kernel` cases walk
    the same compact rows through the Pallas interpreter (bf16, widths
    of 128) and are held to `lax.ragged_dot`'s result on them."""
    from ray_tpu.ops import attention

    T, d, f, dtype, bias = 64, D, F, F32, None
    kernel = held.startswith("kernel")
    E, shards, Z = SHARES.get(held, SHARES["both"])
    if held == "uniform-16-of-768":
        E, shards, Z = 16, 32, 256
    if kernel:
        d, f, dtype = 128, 128, jnp.bfloat16
    if held.endswith("every-pick-held"):
        E, shards, Z = 4, 8, 32
        bias = jnp.zeros((shards * E + Z,)).at[E:2 * E].set(10.0)  # share 1
    gated = form == "swiglu"
    k = 12 if held == "uniform-16-of-768" else K
    rule = moe.softmax_bias_top_k(k, 6.0) if Z \
        else moe.sigmoid_bias_top_k(k, 2.5)
    p, every = _layer(E, shards, Z, gated, stack, dtype, d, f, bias)
    x = jax.random.normal(jax.random.key(5), (T, d), F32).astype(dtype)
    live = None if rows == "all" else jnp.arange(T) % 3 != 1
    R, n_live = shards * E + Z, T if live is None else int(live.sum())

    if kernel:
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", False)
        want, want_sizes = _run(x, p, rule, E, shards, Z, live, stack)
        monkeypatch.setattr(attention, "FORCE_PALLAS_INTERPRET", True)
        assert moe.grouped_path(moe.compact_rows(T * k, E, R), (E, d, f),
                                dtype) == "kernel"
        tol = 2 ** -7 * np.abs(want).max()
    else:
        want, want_sizes = _plain(x, p, every, rule, E, shards, Z, live)
        tol = 1e-5
    y, sizes = _run(x, p, rule, E, shards, Z, live, stack)
    assert sizes.tolist() == want_sizes.tolist()
    assert np.abs(want).max() > 0.5 and np.abs(y - want).max() <= tol
    if live is not None:
        assert not y[~np.asarray(live)].any()

    walk = {n: int(v) for n, v in moe.walk_counts(
        jnp.asarray(sizes[None, :E]), T * k, R).items()}
    M = moe.compact_rows(T * k, E, R)
    assert walk["moe_rows_dense"] == T * k and M < T * k
    assert walk["moe_rows_walked"] == -(-int(sizes[:E].sum()) // M) * M
    if held.endswith("every-pick-held"):
        assert int(sizes[:E].sum()) == n_live * k > M
        assert walk["moe_extra_passes"] > 0
    if held == "uniform-16-of-768":
        assert M == 32 and 0 < int(sizes[:E].sum()) <= M
        assert walk["moe_extra_passes"] == 0
        assert walk["moe_rows_walked"] < walk["moe_rows_dense"] / 4


@pytest.mark.parametrize("rows, held, routed, m", [
    (12288, 16, 768, 512), (1536, 16, 768, 64),     # longform: insert, tick
    (16384, 64, 256, 16384), (1024, 64, 256, 1024),     # agent: a quarter
    (12288, 32, 128, 12288), (2304, 32, 128, 2304),     # swarm: a quarter
    (16384, 64, 512, 4096), (12288, 32, 256, 3072),     # an eighth
    (192, 2, 32, 32), (192, 4, 64, 32),             # under a tile of 128
    (120, 4, 24, 120), (9, 4, 24, 9),               # M over a quarter
    (16384, 128, 128, 16384), (384, 64, 64, 384)])  # the whole bank
def test_compact_rows_come_from_shapes_alone(rows, held, routed, m):
    """M: twice a uniform router's rows in whole tiles of the grouped
    product (128, or 16 under 128) where that is a quarter of the rows
    or less; else all of them, which is the walk of every pick."""
    assert moe.compact_rows(rows, held, routed) == m


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


def test_a_shortcut_insert_holds_no_row_a_pick_and_no_sort():
    """A rehearsal-size `shortcut_moe` insert (a 64-row bucket, top 3
    of a router 2 held + 30 elsewhere + 4 zero-compute wide): no value,
    in the loop's body or outside it, has a row for every pick
    (`[T * k, D]`, `[T, k, D]`), and no `sort` is left; the same layer
    with the whole bank held still sorts its `T * k` keys (the guard
    reads what it should)."""
    from ray_tpu.models import shortcut_moe as M

    c = M.ShortcutMoEConfig.tiny(n_experts=32, expert_rank=1,
                                 expert_shards=16)
    T, k, Dm = 64, c.top_k, c.dim
    assert (c.n_held_experts, c.router_width) == (2, 36)
    params = jax.eval_shape(lambda: M.init_params(c, jax.random.key(0)))
    hist = {"latent": jax.ShapeDtypeStruct(
        (2 * c.n_layers, 128, c.cache_row), c.dtype)}
    closed = jax.make_jaxpr(
        lambda p, t, h: M.prefill_paged(p, t, 0, h, c, T))(
        params, jax.ShapeDtypeStruct((1, T), jnp.int32), hist)

    def shapes_and_sorts(jaxpr):
        shapes, sorts = set(), []
        for eqn in _eqns(jaxpr):
            shapes |= {tuple(v.aval.shape) for v in eqn.outvars}
            if eqn.primitive.name == "sort":
                sorts.append(tuple(eqn.invars[0].aval.shape))
        return shapes, sorts

    shapes, sorts = shapes_and_sorts(closed.jaxpr)
    assert (T, Dm) in shapes and (T, k) in shapes           # parsed
    assert (moe.compact_rows(T * k, c.n_held_experts, c.router_width),
            Dm) == (32, Dm) in shapes
    assert not {(T * k, Dm), (T, k, Dm), (1, T, k, Dm)} & shapes
    assert not sorts
    whole = jax.make_jaxpr(lambda x, p: moe.dropless_moe(
        x, p, moe.softmax_top_k(k)))(
        jax.ShapeDtypeStruct((T, Dm), F32),
        {"router": jax.ShapeDtypeStruct((Dm, 4), F32),
         **{n: jax.ShapeDtypeStruct((4,) + s, F32) for n, s in (
             ("w_gate", (Dm, 8)), ("w_up", (Dm, 8)), ("w_down", (8, Dm)))}})
    shapes, sorts = shapes_and_sorts(whole.jaxpr)
    assert (T * k, Dm) in shapes and (T * k,) in sorts
