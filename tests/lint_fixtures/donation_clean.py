"""donation-use-after clean twin: donate-and-rebind, the idiom the
API wants."""

import jax


def _step(state, batch):
    return state


_train = jax.jit(_step, donate_argnums=(0,))


def rebind_idiom(state, batch):
    # The donated name is rebound from the call's result: the old
    # buffer is never read again.
    state = _train(state, batch)
    return state.loss


def loop_with_rebind(state, batches):
    for b in batches:
        state = _train(state, b)
    return state


def read_before_donate(state, batch):
    loss = state.loss          # read happens before the donation
    state = _train(state, batch)
    return state, loss


def no_donation(state, batch):
    # jit without donate_argnums: reads after the call are fine.
    fn = jax.jit(_step)
    out = fn(state, batch)
    return out, state.loss


def both_paths_rebind(state, batch, fast):
    if fast:
        state = _train(state, batch)
    else:
        state = _step(state, batch)
    return state.loss


class Programs:
    """The serve engine's pattern (serve/llm/programs.py): the object
    that wraps a program owns the arrays the program donates."""

    def __init__(self, tick_fn, kv_cache, slots):
        self._cache, self._slots = kv_cache, slots
        self._jit_tick = jax.jit(tick_fn, donate_argnums=(1, 2))

    def tick(self, params, tokens):
        # Both donated attributes are rebound from the result tuple.
        self._cache, self._slots, out = self._jit_tick(
            params, self._cache, self._slots, tokens)
        return out, self._cache["k"].shape
