"""Share of the decode tick's device time under the scope `gdn` (the
whole mixer of every gated delta-rule layer and its post-norm:
`gdn/proj`, `gdn/conv`, `gdn/gate`, `gdn/state`, `gdn/out`;
`models/gdn_hybrid.py`): device seconds of the operations under it over
the device seconds of the `jit_llm_engine_tick` executions of the traced
window."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_tick", "gdn")
    return None if got is None else 100.0 * got[0] / got[1]
