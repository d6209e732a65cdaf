"""Share of the inserts' device time under the scope `attn` (norm,
projections, QK-norm, rotary, the gate and the blockwise attention over
the gathered history of both kinds; `models/window_moe.py`): device
seconds of the operations under it over the device seconds of the
`jit_llm_engine_insert` executions of the traced window.  Listed for
the cell whose inserts attend over histories of up to 18,432 rows."""
import scope_paths as SP


def read(run):
    if "sliding_window" not in run["config"]:
        return None
    got = SP.program_seconds(run, "jit_llm_engine_insert", "attn")
    return None if got is None or not got[1] else 100.0 * got[0] / got[1]
