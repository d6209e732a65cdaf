"""Seconds this process spent in what JAX calls "XLA compilation" of its
tracked programs before the window (`backend_seconds` over the rows of
`setup_clock.record`): LOADING the executable from the persistent cache
on a hit, the compiler on a miss, so a cold run's number is another
thing than a warm one's and `cache_misses` beside it says which
(PR 49).  None for a program that keeps no such rows."""
import setup_clock as SC


def read(run):
    rec = SC.record(run)
    return None if rec is None else SC.stage_sums(rec["programs"])["backend"]
