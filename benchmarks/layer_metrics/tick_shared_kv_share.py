"""Share of the decode tick's device time under `attn/paged_shared`: the
reads of the ONE full layer's K and V rows by that layer and by every
cross layer after it, and their one plan (`models/sambay.py::_Paged`):
device seconds of the operations under it over the device seconds of the
`jit_llm_engine_tick` executions of the traced window."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_tick", "attn",
                             "paged_shared")
    return None if got is None or not got[1] else 100.0 * got[0] / got[1]
