"""The routed experts against the bandwidth roofline: the LEAST the
ticks of the traced interval had to read of expert weights (the mean
number of distinct experts a tick touched in a layer, from the engine's
device counter `experts_touched` / `ticks` over the whole run, x the
bytes of one expert x the ticks in the interval; activations and the
sort are not counted) over the chip's peak bandwidth, over the device
time those ticks spent under `moe/experts`.  It cannot pass 100: an
expert's weights cannot arrive faster than the peak."""
import counts_latent_moe as K
import scope_paths as SP


def read(run):
    ctr = SP.counters(run)
    got = SP.program_seconds(run, "jit_llm_engine_tick", "moe", "experts")
    if not ctr or got is None or not int(ctr["ticks"]) or not got[0]:
        return None
    seconds, _, n_ticks = got
    touched_a_tick = float(ctr["experts_touched"]) / float(ctr["ticks"])
    need = touched_a_tick * K.expert_bytes(run["config"]) * n_ticks
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
