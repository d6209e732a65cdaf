"""What the three latent-attention models' tests share about the
insert's walk of its history (`models/latent_moe.py::_History.attend`):
the cases, and how far a whole `prefill_paged` by the walk lies from
one by the plain form (`attend_expanded` over the padded history)."""

import numpy as np

import jax
import jax.numpy as jnp

CASES = {   # rows of the padded history, start, queries; in tiles of T
    "start_0": lambda T: (4 * T, 0, 16),
    "start_inside_a_tile": lambda T: (4 * T, T // 2 + 3, 16),
    "across_one_edge": lambda T: (4 * T, T - 8, 16),
    "across_two_edges": lambda T: (4 * T, T - 8, T + 16),
    "history_shorter_than_a_tile": lambda T: (64, 20, 16),
    "history_no_multiple_of_the_tile":
        lambda T: (2 * T + T // 4, 2 * T + 8, 16),
    # real keys end in tile 0; the bucket's padding fills tile 1 alone
    "padded_queries_past_the_last_real_key": lambda T: (2 * T, T - 4, 16),
}


def geometry(case):
    """(S_pad, start, Q, n_real) of a case at the program's tile."""
    from ray_tpu.models.serving import HISTORY_TILE

    S_pad, start, Q = CASES[case](HISTORY_TILE)
    assert start + Q <= S_pad
    return S_pad, start, Q, 3 if case.startswith("padded") else Q


def insert_walk_error(monkeypatch, prefill, mc, params, n_pool_layers,
                      case, *state):
    """`prefill(params, tokens, start, hist, mc, n_real, *state)` twice
    over a loud random history: as it is, and with `_History.attend`
    put back to `attend_expanded`.  The largest difference of the
    normed hidden states [Q, D], the padded queries' rows included, as
    a share of their size; every one of the walk's is finite."""
    from ray_tpu.models import latent_moe as LM

    S_pad, start, Q, n_real = geometry(case)
    k_hist, k_tok = jax.random.split(jax.random.key(S_pad + start))
    hist = {"latent": (jax.random.normal(
        k_hist, (n_pool_layers, S_pad, mc.cache_row)) * 3.0).astype(mc.dtype)}
    tokens = jax.random.randint(k_tok, (1, Q), 0, mc.vocab_size)
    tokens = jnp.where(jnp.arange(Q) < n_real, tokens, 0)

    def hidden():      # jitted anew: the form is chosen as it traces
        return np.asarray(jax.jit(prefill, static_argnums=4)(
            params, tokens, jnp.int32(start), hist, mc, jnp.int32(n_real),
            *state)[0][0], np.float32)

    walk = hidden()
    monkeypatch.setattr(LM._History, "attend",
                        staticmethod(LM.attend_expanded))
    plain = hidden()
    assert np.isfinite(walk).all() and np.abs(plain).max() > 0.5
    return np.abs(walk - plain).max() / np.abs(plain).max()
