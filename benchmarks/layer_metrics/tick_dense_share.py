"""What the always-on path of the shortcut model's tick costs beside
its experts: the share of the decode tick's device time under `mlp`
(the two dense feed-forwards of every layer and their norms) plus
`attn` less `attn/paged` (both sublayers' projections, norms, rotary,
the absorbed query and output, `wo`: everything of attention but the
kernel that reads the cache), over the device seconds of the
`jit_llm_engine_tick` executions of the traced window."""
import scope_paths as SP

TICK = "jit_llm_engine_tick"


def read(run):
    if "zero_expert_num" not in run["config"]:
        return None
    mlp = SP.program_seconds(run, TICK, "mlp")
    attn = SP.program_seconds(run, TICK, "attn")
    if mlp is None or attn is None:
        return None
    paged = SP.program_seconds(run, TICK, "attn", "paged")
    return 100.0 * (mlp[0] + attn[0] - (paged[0] if paged else 0.0)) / mlp[1]
