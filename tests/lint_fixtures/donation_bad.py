"""donation-use-after violations: reads of buffers XLA already owns."""

import jax


def _step(state, batch):
    return state


_train = jax.jit(_step, donate_argnums=(0,))


def read_after_donate(state, batch):
    # donation-use-after: state's HBM was donated to the jit call; the
    # .loss read may see the next step's activations.
    new_state = _train(state, batch)
    return new_state, state.loss


def loop_without_rebind(state, batches):
    # donation-use-after: iteration 2 passes a buffer donated (and
    # freed) in iteration 1.
    outs = []
    for b in batches:
        outs.append(_train(state, b))
    return outs


def local_wrap(step_fn, state, batch):
    # donation-use-after through a locally built jit.
    fn = jax.jit(step_fn, donate_argnums=(0,))
    new = fn(state, batch)
    return new, state.metrics


def donate_on_one_path(state, batch, fast):
    # donation-use-after: the read is unconditional but the donation
    # happens on the fast path — a may-analysis must still flag it.
    if fast:
        out = _train(state, batch)
    else:
        out = state
    return out, state.step


def caller_of_wrapper(state, batch):
    # donation-use-after via the one-level summary: run_step's first
    # parameter flows into _train's donated position.
    new = run_step(state, batch)
    return new, state.opt_state


def run_step(state, batch):
    return _train(state, batch)


class Programs:
    """The serve engine's pattern (serve/llm/programs.py): the object
    that wraps a program owns the arrays the program donates."""

    def __init__(self, tick_fn, kv_cache, slots):
        self._cache, self._slots = kv_cache, slots
        self._jit_tick = jax.jit(tick_fn, donate_argnums=(1, 2))

    def tick(self, params, tokens):
        # donation-use-after: self._cache was donated to the bound jit
        # attribute and only self._slots is rebound from the result;
        # reading the pool afterwards reads reused HBM.
        _, self._slots, out = self._jit_tick(params, self._cache,
                                             self._slots, tokens)
        return out, self._cache["k"].shape
