"""Parameters and bytes of the gated short-convolution / GQA decoder
with routed experts from a configuration file's published sizes
(Hugging Face key names): the yardstick's counts for the family
`conv_moe_decoder`, beside `counts.py` (the dense decoder),
`counts_latent_moe.py` and `counts_kda_hybrid.py`.  Nothing here asks
the program.  Norm vectors and the selection bias (a buffer of E floats
a layer) are in no count: 0.06 M of 4,666 M.
"""

from __future__ import annotations

from typing import List, Mapping

BF16 = 2


def layer_kinds(c: Mapping) -> List[str]:
    """`layer_types` of the layers that are held."""
    return list(c["layer_types"][:c["num_hidden_layers"]])


def n_attention_layers(c: Mapping) -> int:
    return layer_kinds(c).count("full_attention")


def n_conv_layers(c: Mapping) -> int:
    return layer_kinds(c).count("conv")


def n_expert_layers(c: Mapping) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def conv_params(c: Mapping) -> int:
    """in_proj (D -> 3 D), the taps, out_proj of one convolution layer."""
    d = c["hidden_size"]
    return d * 3 * d + c["conv_L_cache"] * d + d * d


def attention_params(c: Mapping) -> int:
    """q, k, v, o projections of one attention layer."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def operator_params(c: Mapping, kind: str) -> int:
    return attention_params(c) if kind == "full_attention" else conv_params(c)


def dense_half_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Mapping) -> int:
    """gate, up, down of ONE routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Mapping) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_half_params(c: Mapping) -> int:
    return c["num_experts"] * expert_params(c) + router_params(c)


def layer_params(c: Mapping, i: int) -> int:
    half = dense_half_params(c) if i < c["num_dense_layers"] \
        else expert_half_params(c)
    return operator_params(c, layer_kinds(c)[i]) + half


def vocab_params(c: Mapping) -> int:
    """The embedding table, which is the head too (tied)."""
    return c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return sum(layer_params(c, i) for i in range(c["num_hidden_layers"])) \
        + vocab_params(c)


def active_params_per_token(c: Mapping) -> int:
    """Parameters one token is multiplied by: every layer's operator,
    the dense feed-forwards, its k experts and the router in each expert
    layer, and the head."""
    return (sum(operator_params(c, k) for k in layer_kinds(c))
            + c["num_dense_layers"] * dense_half_params(c)
            + n_expert_layers(c) * (c["num_experts_per_tok"]
                                    * expert_params(c) + router_params(c))
            + vocab_params(c))


def expert_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    """What one routed expert weighs: the least a tick reads for each
    distinct expert it touches."""
    return expert_params(c) * bytes_per_param


def kv_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V in ONE attention layer, all KV heads: what
    the pool holds and what the paged kernel reads for a live row."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    return n_attention_layers(c) * kv_row_bytes(c, bytes_per_value)


def tail_bytes_per_slot(c: Mapping, bytes_per_value: int = BF16) -> int:
    """What one sequence carries for the convolutions whatever its
    length: `conv_L_cache - 1` rows of B * X in every convolution layer."""
    return (n_conv_layers(c) * (c["conv_L_cache"] - 1) * c["hidden_size"]
            * bytes_per_value)


def paged_attention_bytes(c: Mapping, live_rows: float) -> float:
    """The LEAST the paged kernel reads in one tick: every live row's
    K ‖ V once in every attention layer (queries, outputs and the block
    table are not counted)."""
    return live_rows * kv_bytes_per_token(c)


def tick_least_bytes(c: Mapping, live_slots: int, live_rows: float,
                     experts_touched: float) -> float:
    """The least one decode tick moves through HBM: every operator's,
    dense feed-forward's and router's weights and the head once, the
    touched experts once (`experts_touched` summed over the expert
    layers), the live rows' K ‖ V, and each live slot's tails read and
    written."""
    fixed = (sum(operator_params(c, k) for k in layer_kinds(c))
             + c["num_dense_layers"] * dense_half_params(c)
             + n_expert_layers(c) * router_params(c) + vocab_params(c)) * BF16
    return (fixed + experts_touched * expert_bytes(c)
            + paged_attention_bytes(c, live_rows)
            + 2 * live_slots * tail_bytes_per_slot(c))


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "conv_params_per_layer": conv_params(c),
        "attention_params_per_layer": attention_params(c),
        "dense_half_params": dense_half_params(c),
        "expert_params": expert_params(c),
        "expert_half_params": expert_half_params(c),
        "layer_params": [layer_params(c, i)
                         for i in range(c["num_hidden_layers"])],
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "active_params_per_token": active_params_per_token(c),
        "expert_bytes_bf16": expert_bytes(c),
        "kv_row_bytes_bf16": kv_row_bytes(c),
        "kv_bytes_per_token_bf16": kv_bytes_per_token(c),
        "tail_bytes_per_slot_bf16": tail_bytes_per_slot(c),
    }
