"""Share of the decode tick's device time under the scope `conv` (the
whole operator of every gated short-convolution layer: norm,
`conv/in_proj`, `conv/mix` with the shift of every slot's tail,
`conv/out_proj`; `models/conv_moe.py`): device seconds of the operations
under it over the device seconds of the `jit_llm_engine_tick` executions
of the traced window."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_tick", "conv")
    return None if got is None else 100.0 * got[0] / got[1]
