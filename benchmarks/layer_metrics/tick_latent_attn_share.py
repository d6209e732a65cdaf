"""Share of the decode tick's device time under the scope `attn` of the
latent-attention model (`models/latent_moe.py`: the q and kv_a
projections, rotary, the absorbed scores and weighted sum over the
gathered latent rows, `wkv_b`'s two halves and `wo`; the gather itself
stands under `kv_gather`): device seconds under it over the device
seconds of the `jit_llm_engine_tick` executions of the traced window."""
import program_spans as PS


def read(run):
    return PS.scope_share(run, "jit_llm_engine_tick", "attn")
