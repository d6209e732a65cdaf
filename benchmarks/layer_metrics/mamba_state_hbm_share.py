"""The selective scan's decode step against the bandwidth roofline, for
the family `mamba_mqa_decoder`: the LEAST the ticks of the traced
interval had to move of scan state (the live slots of those ticks, as
the engine writes them on every `llm_engine.tick_dispatch` span inside
the interval (`live=`), x every Mamba layer's state of one sequence
read once and written once, `counts_mamba_mqa.step_state_traffic`: the
MATHEMATICS' d_inner x d_state float32, 26 x 2 x 327,680 B at the
published sizes, whatever layout the program keeps; the tail, Delta, x,
B and C are not counted) over the chip's peak bandwidth, over the
device time the interval's ticks spent under `ssm/state` (the step
kernel, the decay's `-exp(A_log)` in front of it and `Dskip x` behind
it).  It cannot pass 100: a state cannot be read and written faster
than the peak.

Both sides are of the traced interval, as in `ssm_state_hbm_share`
(which reads the same scope for `sambay_decoder`'s layers): the mean of
`live=` over the dispatches is laid on the executions' number.  On a
program without the scope, or a trace without the spans, None."""
import counts_mamba_mqa as K
import program_spans as PS
import scope_paths as SP

DISPATCH = "llm_engine.tick_dispatch"


def read(run):
    if run["trace"] is None or "attn_layer_period" not in run["config"]:
        return None
    prog = PS.load(run)
    got = SP.program_seconds(run, "jit_llm_engine_tick", "ssm", "state")
    if prog is None or got is None or not got[0]:
        return None
    live = [int(sp[3]["live"]) for sp in PS.in_window(
        prog, run["window"], DISPATCH) if "live" in sp[3]]
    if not live:
        return None
    seconds, _, n_ticks = got
    need = (sum(live) / len(live)) * n_ticks \
        * K.step_state_traffic(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
