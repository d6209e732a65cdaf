"""Rows a grouped product's group holds: decode tokens routed to the
experts (`expert_tokens` summed) over the distinct (layer, expert) pairs
the ticks touched (`experts_touched`), both the engine's device counters
over the whole run.  1-2 is the regime of a sparse bank (many experts, a
few streams); tens of rows is a whole bank hit by every tick."""
import numpy as np

import scope_paths as SP


def read(run):
    ctr = SP.counters(run)
    if not ctr or not int(ctr["experts_touched"]):
        return None
    return float(np.asarray(ctr["expert_tokens"], np.float64).sum()
                 / float(ctr["experts_touched"]))
