"""The reads of the ONE full layer's rows against the bandwidth
roofline: the LEAST the ticks of the traced interval had to read of its
K and V (what the engine writes on every `llm_engine.tick_dispatch`
span inside the interval, `rows=`: every live slot's tokens so far, x
one row's K and V, x the layers that read them, the full layer and
every cross layer: `counts_sambay.shared_attention_bytes`; queries,
outputs, tables and the plan are not counted) over the chip's peak
bandwidth, over the device time the interval's ticks spent under
`attn/paged_shared` (the kernel's calls and their one plan).  It cannot
pass 100: a row cannot arrive faster than the peak, and every reader
reads every row again (nothing is kept on the chip between layers).

Both sides are of the traced interval, as in `window_attn_roofline`: the
mean over the dispatches is laid on the executions' number."""
import counts_sambay as K
import program_spans as PS
import scope_paths as SP

DISPATCH = "llm_engine.tick_dispatch"


def read(run):
    if run["trace"] is None or "mb_per_layer" not in run["config"]:
        return None
    prog = PS.load(run)
    got = SP.program_seconds(run, "jit_llm_engine_tick", "attn",
                             "paged_shared")
    if prog is None or got is None or not got[0]:
        return None
    rows = [int(sp[3]["rows"]) for sp in PS.in_window(
        prog, run["window"], DISPATCH) if "rows" in sp[3]]
    if not rows:
        return None
    seconds, _, n_ticks = got
    need = n_ticks * K.shared_attention_bytes(
        run["config"], sum(rows) / len(rows))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
