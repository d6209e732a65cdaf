"""Parameters, bytes and operations of the decoder-hybrid-decoder (Mamba
layers, differential attention in a window, one full layer's K and V
read by the cross-decoder, gated memory units), from a configuration
file's sizes (Hugging Face key names beside the `mamba_*` keys the file
assumes): the yardstick's counts for the family `sambay_decoder`.
Nothing here asks the program: the state's bytes are the MATHEMATICS'
(d_inner x d_state float32), whatever layout the program keeps it in.
"""

from __future__ import annotations

from typing import Mapping

BF16 = 2
F32 = 4


def head_dim(c: Mapping) -> int:
    return c["hidden_size"] // c["num_attention_heads"]


def d_inner(c: Mapping) -> int:
    return c.get("mamba_expand", 2) * c["hidden_size"]


def d_state(c: Mapping) -> int:
    return c.get("mamba_d_state", 16)


def d_conv(c: Mapping) -> int:
    return c.get("mamba_d_conv", 4)


def dt_rank(c: Mapping) -> int:
    return c.get("mamba_dt_rank") or -(-c["hidden_size"] // 16)


def kv_width(c: Mapping) -> int:
    return c["num_key_value_heads"] * head_dim(c)


def n_self_pairs(c: Mapping) -> int:
    """(Mamba, window attention) pairs: the first half of the layers."""
    return c["num_hidden_layers"] // 4


def n_cross_pairs(c: Mapping) -> int:
    """(GMU, cross attention) pairs: the second half less the Mamba and
    the full layer it starts with."""
    return c["num_hidden_layers"] // 4 - 1


def n_ssm_layers(c: Mapping) -> int:
    return n_self_pairs(c) + 1


def n_shared_readers(c: Mapping) -> int:
    """Layers that read the ONE full layer's rows: it and every cross
    layer."""
    return n_cross_pairs(c) + 1


def block_params(c: Mapping) -> int:
    """What every layer has beside its mixer: W1 (gate and up), W2, and
    two LayerNorms with weight and bias."""
    d = c["hidden_size"]
    return 3 * d * c["intermediate_size"] + 4 * d


def mamba_params(c: Mapping) -> int:
    """W_in, the taps and their bias, W_x, W_dt, b_dt, A_log, Dskip,
    W_out."""
    d, ci, n, r = c["hidden_size"], d_inner(c), d_state(c), dt_rank(c)
    return (d * 2 * ci + (d_conv(c) + 1) * ci + ci * (r + 2 * n) + r * ci
            + ci + n * ci + ci + ci * d)


def attn_params(c: Mapping, cross: bool = False) -> int:
    """W_qkv and W_o with their biases (a cross layer projects a query
    alone), the four lambda vectors, the sub-norm's weight."""
    d, hd = c["hidden_size"], head_dim(c)
    qkv = d if cross else d + 2 * kv_width(c)
    return d * qkv + qkv + 4 * hd + 2 * hd + d * d + d


def gmu_params(c: Mapping) -> int:
    return 2 * c["hidden_size"] * d_inner(c)


def layer_params(c: Mapping, kind: str) -> int:
    mixer = {"mamba": mamba_params(c), "attn": attn_params(c),
             "gmu": gmu_params(c), "cross": attn_params(c, cross=True)}[kind]
    return mixer + block_params(c)


def vocab_params(c: Mapping) -> int:
    """The table, tied with the head."""
    return c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    n = n_ssm_layers(c)
    return (n * (layer_params(c, "mamba") + layer_params(c, "attn"))
            + n_cross_pairs(c) * (layer_params(c, "gmu")
                                  + layer_params(c, "cross"))
            + vocab_params(c) + 2 * c["hidden_size"])


def full_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V in the ONE full layer."""
    return 2 * kv_width(c) * bytes_per_value


def window_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V in every window layer."""
    return n_self_pairs(c) * full_row_bytes(c, bytes_per_value)


def shared_attention_bytes(c: Mapping, rows: float) -> float:
    """The LEAST a tick's reads of the full layer's rows move: `rows`
    live rows (every live slot's tokens so far), K and V, once for each
    of the layers that read them."""
    return rows * full_row_bytes(c) * n_shared_readers(c)


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
        "gmu_layer_params": layer_params(c, "gmu"),
        "cross_layer_params": layer_params(c, "cross"),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "full_row_bytes_bf16": full_row_bytes(c),
        "window_row_bytes_bf16": window_row_bytes(c),
        "state_bytes_per_slot_f32": state_bytes_per_slot(c),
        "tail_bytes_per_slot_bf16": tail_bytes_per_slot(c),
        "step_state_traffic_bytes": step_state_traffic(c),
    }
