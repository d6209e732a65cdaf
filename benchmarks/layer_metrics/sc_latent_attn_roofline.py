"""The paged decode kernel over the shortcut model's latent pool against
the chip's roofline: the LEAST the ticks of the traced interval had to
do (the live rows of those ticks, as the engine writes them on every
`llm_engine.tick_dispatch` span inside the interval, `rows=`: every live
slot's tokens so far; x one cache row as the pool stores it, 640 values
of bf16; x the pool's layers, TWO a layer: a row a token a sublayer,
`counts_shortcut_moe.latent_sublayers`; queries, outputs, the block
table and the plan are not counted), as the larger of its bytes over
the peak bandwidth and its multiply-adds over the peak rate (at 64 heads
on one row 115 FLOP a byte, half the ridge: the bytes bound it), over
the device time the interval's ticks spent under `attn/paged` (the
kernel and its plan).  It cannot pass 100.  Without that scope (the
gather path, the CPU) it reads nothing.

Both sides are of the traced interval, as in `latent_attn_roofline`,
whose reader counts `num_hidden_layers` pool layers and so cannot read
this configuration."""
import counts_shortcut_moe as K
import program_spans as PS
import scope_paths as SP

DISPATCH = "llm_engine.tick_dispatch"


def read(run):
    if run["trace"] is None or "zero_expert_num" not in run["config"]:
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
    c, peaks = run["config"], run["peaks"]
    row_layers = n_ticks * sum(rows) / len(rows) * K.latent_sublayers(c)
    least = max(row_layers * K.cache_row_bytes(c) / peaks["hbm_bytes_per_s"],
                row_layers * K.paged_latent_flops_per_row(c)
                / peaks["bf16_flops_per_s"])
    return 100.0 * least / seconds
