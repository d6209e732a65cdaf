"""Share of the decode tick's device time under the scope `kda` (the
whole mixer of every recurrent layer: norm, `kda/proj`, `kda/conv`,
`kda/gate`, `kda/state`, `kda/out`; `models/kimi_linear.py`): device
seconds of the operations under it over the device seconds of the
`jit_llm_engine_tick` executions of the traced window."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_tick", "kda")
    return None if got is None else 100.0 * got[0] / got[1]
