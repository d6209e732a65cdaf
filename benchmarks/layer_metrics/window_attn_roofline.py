"""Both forms of the paged decode-attention kernel (the full layers' walk
of a table by position, the window layers' walk of a ring) against the
bandwidth roofline: the LEAST the ticks of the traced interval had to
read of K and V, over the chip's peak bandwidth, over the device time
the interval's ticks spent under `attn/paged` + `attn/paged_window` (the
kernels and their plans).  The least: what the engine writes on every
`llm_engine.tick_dispatch` span inside the interval, `rows=` (every live
slot's tokens so far) x one row's K and V in every FULL layer +
`window_rows=` (a slot's rows or the window, whichever is less) x one
row's K and V in every WINDOW layer (`counts_window_moe.
paged_attention_bytes`); queries, outputs, tables and plans are not
counted.  It cannot pass 100: a row cannot arrive faster than the peak.
Without `window_rows=` (a program with no window kind of pool: the
parent of PR 38) it reads nothing.

Both sides are of the traced interval, as in `paged_attn_roofline`: the
means over the dispatches are laid on the executions' number."""
import counts_window_moe as K
import program_spans as PS
import scope_paths as SP

DISPATCH = "llm_engine.tick_dispatch"
TICK = "jit_llm_engine_tick"


def read(run):
    if run["trace"] is None or "sliding_window" not in run["config"]:
        return None
    prog = PS.load(run)
    full = SP.program_seconds(run, TICK, "attn", "paged")
    ring = SP.program_seconds(run, TICK, "attn", "paged_window")
    if prog is None or full is None or ring is None:
        return None
    seconds = full[0] + ring[0]
    said = [sp[3] for sp in PS.in_window(prog, run["window"], DISPATCH)
            if "rows" in sp[3] and "window_rows" in sp[3]]
    if not said or not seconds:
        return None
    rows = sum(int(a["rows"]) for a in said) / len(said)
    window_rows = sum(int(a["window_rows"]) for a in said) / len(said)
    need = full[2] * K.paged_attention_bytes(run["config"], rows,
                                             window_rows)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
