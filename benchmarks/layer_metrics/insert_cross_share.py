"""Share of the inserts' device time under the scope `cross_decoder`
(`models/sambay.py::_stack`: the middle Mamba layer and the full layer's
K and V for every row of the chunk, then that layer's attention and
every layer after it for the last real row alone): device seconds of
the operations under it over the device seconds of the
`jit_llm_engine_insert` executions of the traced window.  With every
row through the second half it would read about two fifths."""
import scope_paths as SP


def read(run):
    got = SP.program_seconds(run, "jit_llm_engine_insert", "cross_decoder")
    return None if got is None or not got[1] else 100.0 * got[0] / got[1]
