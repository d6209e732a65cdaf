"""Share of the decode tick's device time under the scope `ssd` (the
whole mixer of every Mamba-2 layer: `ssd/proj`, `ssd/conv`,
`ssd/state`, `ssd/norm`; `models/nemotron_h.py`): device seconds of the
operations under it over the device seconds of the
`jit_llm_engine_tick` executions of the traced window."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_tick", "ssd")
    return None if got is None or not got[1] else 100.0 * got[0] / got[1]
