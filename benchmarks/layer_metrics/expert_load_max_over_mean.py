"""How unevenly the router loads the experts: tokens routed to the
busiest expert over the mean over experts, in the worst expert layer,
from the engine's device counter `expert_tokens` [expert layers, E]
(decode tokens of live slots since the engine started).  1.0 is even;
with random weights and a seeded selection bias this is the baseline a
later skewed-topic cell stands against."""
import numpy as np

import scope_paths as SP


def read(run):
    ctr = SP.counters(run)
    if not ctr:
        return None
    load = np.asarray(ctr["expert_tokens"], np.float64)
    if not load.sum():
        return None
    return float(np.max(load.max(axis=1) / load.mean(axis=1)))
