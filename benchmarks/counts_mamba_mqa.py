"""Parameters, bytes and operations of the decoder of Mamba-1 layers with
normed Delta, B and C beside attention layers of one K/V head, a SwiGLU
a layer and a tied head, from a configuration file's sizes (Hugging Face
key names of `model_type` `jamba`): the yardstick's counts for the
family `mamba_mqa_decoder`.  Nothing here asks the program: the state's
bytes are the MATHEMATICS' (d_inner x d_state float32), whatever layout
the program keeps it in.
"""

from __future__ import annotations

from typing import List, Mapping

BF16 = 2
F32 = 4


def head_dim(c: Mapping) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def d_inner(c: Mapping) -> int:
    return c["mamba_expand"] * c["hidden_size"]


def d_state(c: Mapping) -> int:
    return c["mamba_d_state"]


def d_conv(c: Mapping) -> int:
    return c["mamba_d_conv"]


def dt_rank(c: Mapping) -> int:
    return c["mamba_dt_rank"]


def layer_kinds(c: Mapping) -> List[str]:
    """`attn` where `l % attn_layer_period == attn_layer_offset`, else
    `mamba`."""
    return ["attn" if l % c["attn_layer_period"] == c["attn_layer_offset"]
            else "mamba" for l in range(c["num_hidden_layers"])]


def n_ssm_layers(c: Mapping) -> int:
    return layer_kinds(c).count("mamba")


def n_attn_layers(c: Mapping) -> int:
    return layer_kinds(c).count("attn")


def block_params(c: Mapping) -> int:
    """What every layer has beside its mixer: W_gate, W_up, W_down and
    two RMSNorms."""
    d = c["hidden_size"]
    return 3 * d * c["intermediate_size"] + 2 * d


def mamba_params(c: Mapping) -> int:
    """W_in, the taps and their bias, W_x, the three norms' weights,
    W_dt, b_dt, A_log, Dskip, W_out."""
    d, ci, n, r = c["hidden_size"], d_inner(c), d_state(c), dt_rank(c)
    return (d * 2 * ci + (d_conv(c) + 1) * ci + ci * (r + 2 * n)
            + r + 2 * n + r * ci + ci + n * ci + ci + ci * d)


def attn_params(c: Mapping) -> int:
    """W_q and W_o over every query head, W_k and W_v over the K/V
    heads; no bias, no positions."""
    d, hd = c["hidden_size"], head_dim(c)
    return 2 * d * hd * (c["num_attention_heads"]
                         + c["num_key_value_heads"])


def layer_params(c: Mapping, kind: str) -> int:
    mixer = {"mamba": mamba_params(c), "attn": attn_params(c)}[kind]
    return mixer + block_params(c)


def vocab_params(c: Mapping) -> int:
    """The table, tied with the head."""
    return c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return (sum(layer_params(c, kind) for kind in layer_kinds(c))
            + vocab_params(c) + c["hidden_size"])


def kv_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V in every attention layer."""
    return (n_attn_layers(c) * 2 * c["num_key_value_heads"] * head_dim(c)
            * bytes_per_value)


def paged_attention_bytes(c: Mapping, rows: float) -> float:
    """The LEAST a tick's paged walk reads: `rows` live rows (every live
    slot's tokens so far), K and V, in every attention layer (queries,
    outputs, tables and the plan not counted)."""
    return rows * kv_row_bytes(c)


def state_bytes(c: Mapping, bytes_per_value: int = F32) -> int:
    """One sequence's scan state in ONE Mamba layer."""
    return d_inner(c) * d_state(c) * bytes_per_value


def state_bytes_per_slot(c: Mapping) -> int:
    return n_ssm_layers(c) * state_bytes(c)


def tail_bytes_per_slot(c: Mapping, bytes_per_value: int = BF16) -> int:
    return n_ssm_layers(c) * (d_conv(c) - 1) * d_inner(c) * bytes_per_value


def step_state_traffic(c: Mapping) -> int:
    """The LEAST one token of one sequence must move of scan state in a
    tick: every Mamba layer's state read once and written once (tails,
    Delta, x, B, C not counted)."""
    return 2 * state_bytes_per_slot(c)


def scan_ops(c: Mapping, tokens: float) -> float:
    """Operations of the recurrence for `tokens` real tokens in every
    Mamba layer: a channel a state a token, Delta A, its exponential,
    the decay of h, (Delta x) B, its sum, h C, its sum: 7, and Delta x a
    channel.  The vector unit's, every one; `peaks.json` has the matrix
    unit's rate alone, which no elementwise work can reach."""
    return n_ssm_layers(c) * tokens * d_inner(c) * (7 * d_state(c) + 1)


def scan_bytes(c: Mapping, tokens: float) -> float:
    """The LEAST the insert's scan moves for `tokens` real tokens in
    every Mamba layer: Delta and x read, y written, float32 a channel
    (B, C and the state at both ends are small beside them)."""
    return n_ssm_layers(c) * tokens * 3 * d_inner(c) * F32


def scan_seconds(c: Mapping, tokens: float, peaks: Mapping) -> float:
    """The roofline of the insert's scan: the larger of its bytes' time
    and its operations' time at the chip's peaks."""
    return max(scan_bytes(c, tokens) / peaks["hbm_bytes_per_s"],
               scan_ops(c, tokens) / peaks["bf16_flops_per_s"])


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "mamba_layer_params": layer_params(c, "mamba"),
        "attn_layer_params": layer_params(c, "attn"),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "mamba_mixer_bytes_bf16": n_ssm_layers(c) * mamba_params(c) * BF16,
        "kv_row_bytes_bf16": kv_row_bytes(c),
        "state_bytes_per_slot_f32": state_bytes_per_slot(c),
        "tail_bytes_per_slot_bf16": tail_bytes_per_slot(c),
        "step_state_traffic_bytes": step_state_traffic(c),
    }
