"""The paged decode kernel over the latent pool against the bandwidth
roofline: the LEAST the ticks of the traced interval had to read of the
latent cache (the live rows of those ticks, as the engine writes them
on every `llm_engine.tick_dispatch` span inside the interval (`rows=`:
every live slot's tokens so far), x one cache row as the pool stores it
(latent ‖ shared key, up to whole 128-lane tiles: 640 values of bf16) x
the configuration's latent-attention layers; queries, outputs, the
block table and the plan are not counted) over the chip's peak
bandwidth, over the device time the interval's ticks spent under
`attn/paged` (the kernel and its plan).  It cannot pass 100: a row
cannot arrive faster than the peak.  Without that scope (the gather
path: the parent of PR 36, the CPU) it reads nothing.

Both sides are of the traced interval, as in `paged_attn_roofline`: the
mean of `rows=` over the dispatches is laid on the executions' number."""
import counts_kda_hybrid as KH
import program_spans as PS
import scope_paths as SP

DISPATCH = "llm_engine.tick_dispatch"
BF16, LANES = 2, 128


def latent_layers(c) -> int:
    """Layers that keep a latent row a token: all of a `deepseek_v3`
    stack, the MLA layers of a hybrid."""
    if "linear_attn_config" in c:
        return KH.n_mla_layers(c)
    return c["num_hidden_layers"]


def cache_row_bytes(c) -> int:
    row = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return -(-row // LANES) * LANES * BF16


def read(run):
    if run["trace"] is None or "kv_lora_rank" not in run["config"]:
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
    c = run["config"]
    need = (n_ticks * sum(rows) / len(rows) * cache_row_bytes(c)
            * latent_layers(c))
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / seconds
