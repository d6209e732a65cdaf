"""Bytes of cache blocks a live token costs: the bytes of BOTH kinds'
blocks that the live slots hold (a slot's blocks of the full kind x that
kind's block bytes + its blocks of the window kind, at most a ring of
them while its prompt goes in and the window's own once it decodes, x
that kind's, as `engine.stats()["kv"]` counts them for the whole pool)
over the live slots' rows, both summed by the engine over every tick it
dispatched (`stats()["kv"]["live_bytes"]`, `stats()["live_rows"]`): the
whole run, as `expert_rows_per_group` reads its counters.  Not the
traced interval: its 30-40 streams are a small sample of the mix, and
one 16 k stream in or out of those 3 s moved the quotient between 7,300
and 9,400 on the chip (PERF.md section 6, PR 38).  A request takes its
blocks for prompt AND answer up front, so a stream early in its answer
holds more than its rows; with every layer in one table and no window
the same streams would read
`counts_window_moe.one_table_bytes_per_token` (10,240 here) and more.
Without `live_bytes` (a program with no window kind of pool) it reads
nothing."""


def read(run):
    for rec in run["records"].get("recs", ()):
        engine = getattr(rec.handle, "engine", None)
        if engine is not None:
            stats = engine.stats()
            held, rows = stats["kv"].get("live_bytes"), stats["live_rows"]
            return held / rows if held and rows else None
    return None
