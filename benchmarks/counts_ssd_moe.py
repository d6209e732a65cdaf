"""Parameters, bytes and operations of the hybrid decoder whose every
layer is one mixer (Mamba-2 layers, non-gated experts beside a shared
one, grouped-query attention without positions), from a configuration
file's sizes (Hugging Face key names, and `deployment` for the share):
the yardstick's counts for the family `ssd_moe_decoder`.  Nothing here
asks the program: the state's bytes are the MATHEMATICS' (heads x
head_dim x state size float32), whatever layout the program keeps it
in, and an expert is TWO matrices.
"""

from __future__ import annotations

from typing import Mapping

BF16 = 2
F32 = 4


def pattern(c: Mapping) -> str:
    return c["hybrid_override_pattern"]


def n_layers(c: Mapping, kind: str) -> int:
    """Layers of `kind`: M Mamba-2, E experts, * attention."""
    return pattern(c).count(kind)


def d_inner(c: Mapping) -> int:
    return c["mamba_num_heads"] * c["mamba_head_dim"]


def conv_width(c: Mapping) -> int:
    """X, then B and C of every group."""
    return d_inner(c) + 2 * c["n_groups"] * c["ssm_state_size"]


def shared_width(c: Mapping) -> int:
    return c["n_shared_experts"] * c["moe_shared_expert_intermediate_size"]


def mamba_params(c: Mapping) -> int:
    """W_in (z, xBC, dt), the taps and their bias, A_log, D, dt_bias,
    the gated norm, W_out, the layer's norm."""
    d, ci, h = c["hidden_size"], d_inner(c), c["mamba_num_heads"]
    return (d * (ci + conv_width(c) + h) + (c["conv_kernel"] + 1)
            * conv_width(c) + 3 * h + ci + ci * d + d)


def attn_params(c: Mapping) -> int:
    d, hd = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    return d * (q + 2 * kv) + q * d + d


def expert_params(c: Mapping) -> int:
    """One routed expert: W_up and W_down, no gate."""
    return 2 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Mapping) -> int:
    return 2 * c["hidden_size"] * shared_width(c)


def router_params(c: Mapping) -> int:
    """The router over ALL published experts and its selection bias."""
    e = c.get("deployment", {}).get("n_routed_experts",
                                    c["n_routed_experts"])
    return c["hidden_size"] * e + e


def moe_params(c: Mapping) -> int:
    """An expert layer as this chip holds it."""
    return (c["n_routed_experts"] * expert_params(c) + shared_params(c)
            + router_params(c) + c["hidden_size"])


def vocab_params(c: Mapping) -> int:
    """The table and the untied head."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return (n_layers(c, "M") * mamba_params(c)
            + n_layers(c, "*") * attn_params(c)
            + n_layers(c, "E") * moe_params(c) + vocab_params(c)
            + c["hidden_size"])


def active_params_per_token(c: Mapping) -> int:
    """What one token multiplies by if every chosen expert were held
    (the table is a gather)."""
    return (n_layers(c, "M") * mamba_params(c)
            + n_layers(c, "*") * attn_params(c)
            + n_layers(c, "E") * (c["num_experts_per_tok"] * expert_params(c)
                                  + shared_params(c) + router_params(c))
            + c["vocab_size"] * c["hidden_size"])


def expert_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """What a touched expert costs a tick to read: its TWO matrices."""
    return expert_params(c) * bytes_per_value


def kv_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V in every attention layer."""
    return (n_layers(c, "*") * 2 * c["num_key_value_heads"] * c["head_dim"]
            * bytes_per_value)


def state_bytes(c: Mapping, bytes_per_value: int = F32) -> int:
    """One sequence's state in ONE Mamba-2 layer."""
    return d_inner(c) * c["ssm_state_size"] * bytes_per_value


def state_bytes_per_slot(c: Mapping) -> int:
    return n_layers(c, "M") * state_bytes(c)


def tail_bytes_per_slot(c: Mapping, bytes_per_value: int = BF16) -> int:
    return (n_layers(c, "M") * (c["conv_kernel"] - 1) * conv_width(c)
            * bytes_per_value)


def step_state_traffic(c: Mapping) -> int:
    """The LEAST one token of one sequence must move of state in a
    tick: every Mamba-2 layer's state read once and written once (the
    tail, dt, X, B and C not counted)."""
    return 2 * state_bytes_per_slot(c)


def scan_ops(c: Mapping, tokens: float) -> float:
    """Operations of the chunked matrix form for `tokens` real tokens in
    every Mamba-2 layer, a token: `C B^T` a group and `M (dt X)` a head
    over the (Q + 1) / 2 rows of the chunk at or before it (the causal
    half: the masked half is not the algorithm's), the chunk's own
    state and `Y_inter` a head, `2 N P` each."""
    q, n, p = c.get("chunk_size", 128), c["ssm_state_size"], \
        c["mamba_head_dim"]
    h, g = c["mamba_num_heads"], c["n_groups"]
    per_token = (q + 1) * n * g + (q + 1) * p * h + 4 * n * p * h
    return n_layers(c, "M") * tokens * per_token


def scan_bytes(c: Mapping, tokens: float) -> float:
    """The LEAST the insert's scan moves for `tokens` real tokens in
    every Mamba-2 layer: X, B and C read in bf16, dt in float32, y
    written in float32 (the state at both ends of a call is small
    beside a bucket's rows)."""
    per_token = conv_width(c) * BF16 + c["mamba_num_heads"] * F32 \
        + d_inner(c) * F32
    return n_layers(c, "M") * tokens * per_token


def scan_seconds(c: Mapping, tokens: float, peaks: Mapping) -> float:
    """The roofline of the insert's scan: the larger of its bytes' time
    and its operations' time at the chip's peaks."""
    return max(scan_bytes(c, tokens) / peaks["hbm_bytes_per_s"],
               scan_ops(c, tokens) / peaks["bf16_flops_per_s"])


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "mamba_layer_params": mamba_params(c),
        "attn_layer_params": attn_params(c),
        "expert_params": expert_params(c),
        "shared_expert_params": shared_params(c),
        "router_params": router_params(c),
        "moe_layer_params_held": moe_params(c),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "active_params_per_token": active_params_per_token(c),
        "expert_bytes_bf16": expert_bytes(c),
        "kv_row_bytes_bf16": kv_row_bytes(c),
        "state_bytes_per_layer_f32": state_bytes(c),
        "state_bytes_per_slot_f32": state_bytes_per_slot(c),
        "tail_bytes_per_slot_bf16": tail_bytes_per_slot(c),
        "step_state_traffic_bytes": step_state_traffic(c),
    }
