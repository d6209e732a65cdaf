"""Parameters and bytes of the latent-attention, routed-expert decoder
from a configuration file's published sizes (Hugging Face key names):
the yardstick's counts for the family `latent_moe_decoder`, beside
`counts.py`, which knows the dense decoder only.  Nothing here asks the
program.  Norm vectors and the selection bias (a buffer of E floats a
layer) are in no count: 0.03 M of 5,069 M.
"""

from __future__ import annotations

from typing import Mapping

BF16 = 2


def attention_params(c: Mapping) -> int:
    """q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj of one layer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    q = d * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    kv_a = d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
    kv_b = c["kv_lora_rank"] * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
    o = h * c["v_head_dim"] * d
    return q + kv_a + kv_b + o


def expert_params(c: Mapping) -> int:
    """gate, up, down of ONE routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def routed_params(c: Mapping) -> int:
    return c["n_routed_experts"] * expert_params(c)


def shared_params(c: Mapping) -> int:
    return c["n_shared_experts"] * expert_params(c)


def router_params(c: Mapping) -> int:
    return c["hidden_size"] * c["n_routed_experts"]


def expert_layer_params(c: Mapping) -> int:
    return (routed_params(c) + shared_params(c) + router_params(c)
            + attention_params(c))


def dense_layer_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"] + attention_params(c)


def vocab_params(c: Mapping) -> int:
    """Embedding table and untied head."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def n_expert_layers(c: Mapping) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def total_params(c: Mapping) -> int:
    return (c["first_k_dense_replace"] * dense_layer_params(c)
            + n_expert_layers(c) * expert_layer_params(c) + vocab_params(c))


def active_params_per_token(c: Mapping) -> int:
    """Parameters one token is multiplied by: every layer's attention,
    the dense feed-forward, its k experts + the shared ones + the router
    in each expert layer, and the head (not the embedding: a gather)."""
    per_expert_layer = (c["num_experts_per_tok"] * expert_params(c)
                        + shared_params(c) + router_params(c))
    return (c["num_hidden_layers"] * attention_params(c)
            + c["first_k_dense_replace"] * 3 * c["hidden_size"]
            * c["intermediate_size"]
            + n_expert_layers(c) * per_expert_layer
            + c["vocab_size"] * c["hidden_size"])


def latent_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One cache row a layer: the latent and the shared rotary key."""
    return (c["num_hidden_layers"]
            * (c["kv_lora_rank"] + c["qk_rope_head_dim"]) * bytes_per_value)


def per_head_kv_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    """What the same model would cache as K and V per head."""
    return (c["num_hidden_layers"] * c["num_attention_heads"]
            * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
               + c["v_head_dim"]) * bytes_per_value)


def expert_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    """What one routed expert weighs: the least a tick reads for each
    distinct expert it touches."""
    return expert_params(c) * bytes_per_param


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "attention_params_per_layer": attention_params(c),
        "expert_params": expert_params(c),
        "expert_layer_params": expert_layer_params(c),
        "dense_layer_params": dense_layer_params(c),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "active_params_per_token": active_params_per_token(c),
        "latent_bytes_per_token_bf16": latent_bytes_per_token(c),
        "per_head_kv_bytes_per_token_bf16": per_head_kv_bytes_per_token(c),
        "expert_bytes_bf16": expert_bytes(c),
    }
