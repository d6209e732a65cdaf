"""Parameters and bytes of the sliding-window / full attention decoder
with routed experts beside a shared one, from a configuration file's
published sizes (Hugging Face key names): the yardstick's counts for
the family `window_moe_decoder`, beside `counts.py` (the dense decoder),
`counts_latent_moe.py`, `counts_kda_hybrid.py` and `counts_conv_moe.py`.
Nothing here asks the program.  Norm vectors (four a layer, two a head)
and the selection bias (a buffer of E floats a layer) are in no count:
0.05 M of 4,241 M.
"""

from __future__ import annotations

from typing import List, Mapping

BF16 = 2


def layer_kinds(c: Mapping) -> List[str]:
    """`layer_types` of the layers that are held."""
    first = int(c.get("first_layer", 0))
    return list(c["layer_types"][first:first + c["num_hidden_layers"]])


def n_full_layers(c: Mapping) -> int:
    return layer_kinds(c).count("full_attention")


def n_window_layers(c: Mapping) -> int:
    return layer_kinds(c).count("sliding_attention")


def n_expert_layers(c: Mapping) -> int:
    return c["num_hidden_layers"] - c["num_dense_layers"]


def attention_params(c: Mapping) -> int:
    """q, o and the output gate (D x H hd each), k and v (D x kvH hd)."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (3 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def dense_half_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def expert_params(c: Mapping) -> int:
    """gate, up, down of ONE routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def shared_params(c: Mapping) -> int:
    return c["num_shared_experts"] * expert_params(c)


def router_params(c: Mapping) -> int:
    return c["hidden_size"] * c["num_experts"]


def expert_half_params(c: Mapping) -> int:
    return (c["num_experts"] * expert_params(c) + shared_params(c)
            + router_params(c))


def layer_params(c: Mapping, i: int) -> int:
    half = dense_half_params(c) if i < c["num_dense_layers"] \
        else expert_half_params(c)
    return attention_params(c) + half


def vocab_params(c: Mapping) -> int:
    """The embedding table and the head (untied)."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return sum(layer_params(c, i) for i in range(c["num_hidden_layers"])) \
        + vocab_params(c)


def published_total_params(c: Mapping) -> int:
    """The whole published model: `published` gives its depth and its
    leading dense layers."""
    whole = dict(c, num_hidden_layers=c["published"]["num_hidden_layers"],
                 num_dense_layers=c["published"]["num_dense_layers"],
                 first_layer=0)
    return total_params(whole)


def active_params_per_token(c: Mapping) -> int:
    """Parameters one token is multiplied by: every layer's attention,
    the dense feed-forwards, the shared expert, its k experts and the
    router in each expert layer, and the head."""
    return (c["num_hidden_layers"] * attention_params(c)
            + c["num_dense_layers"] * dense_half_params(c)
            + n_expert_layers(c) * (c["num_experts_per_tok"]
                                    * expert_params(c) + shared_params(c)
                                    + router_params(c))
            + vocab_params(c) // 2)


def expert_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    """What one routed expert weighs: the least a tick reads for each
    distinct expert it touches."""
    return expert_params(c) * bytes_per_param


def kv_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V in ONE layer, all KV heads: what a pool holds
    and what the paged kernel reads for a live row."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: Mapping, kind: str,
                       bytes_per_value: int = BF16) -> int:
    """Of one kind of pool: "full_attention" or "sliding_attention"."""
    return layer_kinds(c).count(kind) * kv_row_bytes(c, bytes_per_value)


def one_table_bytes_per_token(c: Mapping) -> int:
    """What a live token would hold if every layer kept every row (one
    table, no window): the reading at which the ring bounds nothing."""
    return c["num_hidden_layers"] * kv_row_bytes(c)


def paged_attention_bytes(c: Mapping, rows: float,
                          window_rows: float) -> float:
    """The LEAST both forms of the paged kernel read in one tick: every
    live row's K and V once in every full layer, and the rows inside
    the window once in every window layer (queries, outputs, tables and
    plans are not counted)."""
    return (rows * kv_bytes_per_token(c, "full_attention")
            + window_rows * kv_bytes_per_token(c, "sliding_attention"))


def tick_least_bytes(c: Mapping, rows: float, window_rows: float,
                     experts_touched: float) -> float:
    """The least one decode tick moves through HBM: every attention's,
    dense feed-forward's, shared expert's and router's weights and the
    head once, the touched experts once (`experts_touched` summed over
    the expert layers), and the rows both kernels read."""
    fixed = (c["num_hidden_layers"] * attention_params(c)
             + c["num_dense_layers"] * dense_half_params(c)
             + n_expert_layers(c) * (shared_params(c) + router_params(c))
             + vocab_params(c) // 2) * BF16
    return (fixed + experts_touched * expert_bytes(c)
            + paged_attention_bytes(c, rows, window_rows))


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "attention_params_per_layer": attention_params(c),
        "dense_half_params": dense_half_params(c),
        "expert_params": expert_params(c),
        "expert_half_params": expert_half_params(c),
        "layer_params": [layer_params(c, i)
                         for i in range(c["num_hidden_layers"])],
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "published_total_params": published_total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "active_params_per_token": active_params_per_token(c),
        "expert_bytes_bf16": expert_bytes(c),
        "kv_row_bytes_bf16": kv_row_bytes(c),
        "kv_bytes_per_token_full_bf16":
            kv_bytes_per_token(c, "full_attention"),
        "kv_bytes_per_token_window_bf16":
            kv_bytes_per_token(c, "sliding_attention"),
        "one_table_bytes_per_token_bf16": one_table_bytes_per_token(c),
    }
