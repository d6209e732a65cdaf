"""Share of the block tick's device time that its attention over the
pool takes: the device seconds under `attn/block_write` (the block's L
K/V rows a slot into the pool) + `attn/paged` (the paged kernel at L
queries a sequence, and its plan) over the device seconds of the
`jit_llm_engine_tick` executions of the traced window
(`models/blockdiff_moe.py`)."""
import scope_paths as SP

TICK = "jit_llm_engine_tick"


def read(run):
    if run["trace"] is None or "block_length" not in run["config"]:
        return None
    write = SP.program_seconds(run, TICK, "attn", "block_write")
    read_ = SP.program_seconds(run, TICK, "attn", "paged")
    if write is None or read_ is None or not read_[1]:
        return None
    return 100.0 * (write[0] + read_[0]) / read_[1]
