"""The held non-gated experts against the bandwidth roofline: the LEAST
the ticks of the traced interval had to read of expert weights (the
distinct held experts those ticks touched x the bytes of one expert's
TWO matrices, `counts_ssd_moe.expert_bytes`; activations and the sort
are not counted) over the chip's peak bandwidth, over the device time
those ticks spent under `moe/experts`.  It cannot pass 100: an expert's
weights cannot arrive faster than the peak.

Bytes and time are of the SAME interval, as in `sc_expert_hbm_share`:
under a profiler session the engine writes the model's scalar counters,
as each tick left them, on that tick's `llm_engine.emit` span; the
first and the last such span inside the traced window give the experts
touched and the ticks between them, and their mean a tick is laid on
the executions the device time was summed over."""
import counts_ssd_moe as K
import program_spans as PS
import scope_paths as SP

EMIT = "llm_engine.emit"


def read(run):
    if run["trace"] is None or "mlp_hidden_act" not in run["config"]:
        return None
    prog = PS.load(run)
    got = SP.program_seconds(run, "jit_llm_engine_tick", "moe", "experts")
    if prog is None or got is None or not got[0]:
        return None
    ends = [sp[3] for sp in PS.in_window(prog, run["window"], EMIT)
            if "experts_touched" in sp[3] and "ticks" in sp[3]]
    if len(ends) < 2:
        return None
    ticks = int(ends[-1]["ticks"]) - int(ends[0]["ticks"])
    touched = int(ends[-1]["experts_touched"]) \
        - int(ends[0]["experts_touched"])
    if ticks <= 0:
        return None
    seconds, _, n_ticks = got
    need = touched / ticks * n_ticks * K.expert_bytes(run["config"])
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
