"""The paged decode-attention kernel over the one-K/V-head pools of the
family `mamba_mqa_decoder` against the bandwidth roofline: the LEAST the
ticks of the traced interval had to read of K and V (the live rows of
those ticks, as the engine writes them on every
`llm_engine.tick_dispatch` span inside the interval (`rows=`: every live
slot's tokens so far), x one row's K and V in every attention layer,
`counts_mamba_mqa.paged_attention_bytes`: 1,024 B a row at the
published sizes; queries, outputs, the block table and the plan are not
counted) over the chip's peak bandwidth, over the device time the
interval's ticks spent under `attn/paged` (the kernel's calls and their
plan).  It cannot pass 100: a row cannot arrive faster than the peak.

Both sides are of the traced interval, as in `paged_attn_roofline`: the
mean of `rows=` over the dispatches is laid on the executions' number.
None on a program without the scope or a trace without the spans."""
import counts_mamba_mqa as K
import program_spans as PS
import scope_paths as SP

DISPATCH = "llm_engine.tick_dispatch"


def read(run):
    if run["trace"] is None or "attn_layer_period" not in run["config"]:
        return None
    prog = PS.load(run)
    got = SP.program_seconds(run, "jit_llm_engine_tick", "attn", "paged")
    if prog is None or got is None or not got[0]:
        return None
    rows = [int(sp[3]["rows"]) for sp in PS.in_window(
        prog, run["window"], DISPATCH) if "rows" in sp[3]]
    if not rows:
        return None
    seconds, _, n_ticks = got
    need = n_ticks * K.paged_attention_bytes(run["config"],
                                             sum(rows) / len(rows))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
