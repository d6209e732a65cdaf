"""Share of the decode tick's device time under the scope `moe` (the
whole expert half of every expert layer: norm, `moe/router`,
`moe/experts`: sort, grouped products, unsort and combine, and
`moe/shared`; `models/latent_moe.py`): device seconds of the operations
under it over the device seconds of the `jit_llm_engine_tick` executions
of the traced window."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_tick", "moe")
    return None if got is None else 100.0 * got[0] / got[1]
