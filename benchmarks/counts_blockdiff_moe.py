"""Parameters, bytes and operations of the block-diffusion decoder with
softmax-routed experts, from a configuration file's published sizes
(Hugging Face key names; `deployment` for the share): the yardstick's
counts for the family `blockdiff_moe_decoder`, beside `counts.py` (the
dense decoder) and its siblings.  Nothing here asks the program.  Norm
vectors (two a layer, two a head, one at the end) are in no count:
0.06 M of 2,663 M.
"""

from __future__ import annotations

from typing import Mapping

BF16 = 2


def attention_params(c: Mapping) -> int:
    """q and o (D x H hd each), k and v (D x kvH hd each)."""
    d, hd = c["hidden_size"], c["head_dim"]
    return (2 * d * c["num_attention_heads"] * hd
            + 2 * d * c["num_key_value_heads"] * hd)


def expert_params(c: Mapping) -> int:
    """gate, up, down of ONE routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Mapping) -> int:
    """The router is as wide as ALL routed experts, held or not."""
    routed = c.get("deployment", {}).get("num_experts", c["num_experts"])
    return c["hidden_size"] * routed


def layer_params(c: Mapping) -> int:
    """One layer as this chip holds it: attention, router, the HELD
    experts."""
    return (attention_params(c) + router_params(c)
            + c["num_experts"] * expert_params(c))


def vocab_params(c: Mapping) -> int:
    """Embedding and untied head."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return c["num_hidden_layers"] * layer_params(c) + vocab_params(c)


def published_total_params(c: Mapping) -> int:
    """The whole model: every layer with every routed expert."""
    dep = c.get("deployment", {})
    routed = dep.get("num_experts", c["num_experts"])
    layers = dep.get("num_hidden_layers", c["num_hidden_layers"])
    return layers * (attention_params(c) + router_params(c)
                     + routed * expert_params(c)) + vocab_params(c)


def active_params_per_token(c: Mapping) -> int:
    """What one ROW of a forward multiplies on this chip if all of its
    `num_experts_per_tok` picks were held here (an upper bound: a quarter
    of them are, on average, at 32 of 128)."""
    return (c["num_hidden_layers"]
            * (attention_params(c) + router_params(c)
               + c["num_experts_per_tok"] * expert_params(c))
            + c["vocab_size"] * c["hidden_size"])


def expert_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    """What one routed expert weighs: the least a tick reads for each
    distinct held expert it touches."""
    return expert_params(c) * bytes_per_param


def kv_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """K and V of one token in one layer."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * bytes_per_value


def kv_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    return c["num_hidden_layers"] * kv_row_bytes(c, bytes_per_value)


def paged_attention_bytes(c: Mapping, rows: float,
                          bytes_per_value: int = BF16) -> float:
    """The least a tick's paged attention reads: `rows` K/V rows (every
    live slot's rows up to its open block's last, read ONCE for the
    block's L queries) in every layer.  Queries, outputs, tables and
    plans are not counted."""
    return rows * kv_bytes_per_token(c, bytes_per_value)


def paged_attention_flops(c: Mapping, rows: float) -> float:
    """The multiply-adds x 2 the mathematics needs for `block_length`
    queries a slot over `rows` keys: scores and values, every query head
    against its own KV head's rows (the side-by-side kernel multiplies
    `num_key_value_heads` times as much: zeros)."""
    return (c["num_hidden_layers"] * 2 * 2 * int(c["block_length"])
            * c["num_attention_heads"] * c["head_dim"] * rows)


def forward_weight_bytes(c: Mapping, touched: float,
                         bytes_per_param: int = BF16) -> float:
    """The least ONE forward reads of weights: attention and router of
    every layer, `touched` (layer, held expert) pairs, and the head (the
    embedding is a gather of a few rows)."""
    return bytes_per_param * (
        c["num_hidden_layers"] * (attention_params(c) + router_params(c))
        + touched * expert_params(c) + c["vocab_size"] * c["hidden_size"])


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "attention_params_per_layer": attention_params(c),
        "router_params_per_layer": router_params(c),
        "expert_params": expert_params(c),
        "layer_params_held": layer_params(c),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "published_total_params": published_total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "active_params_per_row": active_params_per_token(c),
        "expert_bytes_bf16": expert_bytes(c),
        "kv_row_bytes_bf16": kv_row_bytes(c),
        "kv_bytes_per_token_bf16": kv_bytes_per_token(c),
        "forward_weight_bytes_all_held_touched_bf16": int(
            forward_weight_bytes(c, c["num_hidden_layers"]
                                 * c["num_experts"])),
    }
