"""Median device duration of the engine's decode tick program, from the
device trace (the program the `bench:token` markers follow)."""
import stats as S
import trace_reduce as TR


def read(run):
    if run["trace"] is None:
        return None
    d = TR.durations_by_kind(run["trace"], run["kinds"], run["window"])
    ticks = d.get("tick")
    return S.median(ticks) * 1e3 if ticks else None
