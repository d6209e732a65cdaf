"""Share of the decode tick's device time that stands under the scope
`kv_gather` (the block-table gather of every slot's K/V rows and their
GQA repeat, `models/llama.py`): device seconds of the operations under
it over the device seconds of the `jit_llm_engine_tick` executions of
the traced window."""
import program_spans as PS


def read(run):
    return PS.scope_share(run, "jit_llm_engine_tick", "kv_gather")
