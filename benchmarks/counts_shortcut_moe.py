"""Parameters, bytes and operations of the shortcut-connected expert
decoder from a configuration file's published sizes (the source's own
key names): the yardstick's counts for the family
`shortcut_moe_decoder`.  Nothing here asks the program.  A layer is a
DOUBLE layer: two latent-attention sublayers, two dense feed-forwards,
one expert layer; the file's `n_routed_experts` is what this chip holds
of `deployment.n_routed_experts`.  Norm vectors and the selection bias
ARE counted (the issue's table counts them).
"""

from __future__ import annotations

from typing import Mapping

BF16, LANES = 2, 128


def mla_params(c: Mapping) -> int:
    """One sublayer's attention: q_a, q_b, kv_a, kv_b, o and the two
    norms of the low-rank paths."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    qr, kr = c["q_lora_rank"], c["kv_lora_rank"]
    q = d * qr + qr * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
    kv = d * (kr + c["qk_rope_head_dim"]) \
        + kr * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
    return q + kv + h * c["v_head_dim"] * d + qr + kr


def dense_ffn_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["ffn_hidden_size"]


def router_width(c: Mapping) -> int:
    """The routed experts AS PUBLISHED plus the zero-compute ones."""
    dep = c.get("deployment", {})
    return dep.get("n_routed_experts", c["n_routed_experts"]) \
        + c["zero_expert_num"]


def router_params(c: Mapping) -> int:
    """The router's matrix and the selection bias."""
    return (c["hidden_size"] + 1) * router_width(c)


def layer_params_without_experts(c: Mapping) -> int:
    """2 MLA, 2 dense feed-forwards, the 4 stream norms, the router."""
    return (2 * mla_params(c) + 2 * dense_ffn_params(c)
            + 4 * c["hidden_size"] + router_params(c))


def expert_params(c: Mapping) -> int:
    """gate, up, down of ONE routed expert."""
    return 3 * c["hidden_size"] * c["expert_ffn_hidden_size"]


def layer_params(c: Mapping) -> int:
    """A layer with the experts held here."""
    return layer_params_without_experts(c) \
        + c["n_routed_experts"] * expert_params(c)


def vocab_params(c: Mapping) -> int:
    """Embedding table, untied head, final norm."""
    return (2 * c["vocab_size"] + 1) * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return c["num_layers"] * layer_params(c) + vocab_params(c)


def expert_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    """What one routed expert weighs: the least a tick reads for each
    distinct held expert it touches."""
    return expert_params(c) * bytes_per_param


def latent_sublayers(c: Mapping) -> int:
    """Pool layers: a row a token for EACH of a layer's two sublayers."""
    return 2 * c["num_layers"]


def cache_row_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One cache row as the pool stores it: latent ‖ rotary key, up to
    whole 128-lane tiles."""
    row = c["kv_lora_rank"] + c["qk_rope_head_dim"]
    return -(-row // LANES) * LANES * bytes_per_value


def latent_bytes_per_token(c: Mapping) -> int:
    return latent_sublayers(c) * cache_row_bytes(c)


def paged_latent_flops_per_row(c: Mapping) -> int:
    """The paged kernel's multiply-adds x 2 for ONE cached row of ONE
    sublayer: every head's score over the whole stored row, and its
    weighted sum over the latent's lanes."""
    h = c["num_attention_heads"]
    return 2 * h * (cache_row_bytes(c, 1) + c["kv_lora_rank"])


def active_params_per_token(c: Mapping, real_picks: float) -> float:
    """Parameters a token is multiplied by in the WHOLE published model
    at `real_picks` routed experts a token a layer (12 less its zero
    picks; 8 in the mean with a fair router), head included."""
    dep = c.get("deployment", {})
    layers = dep.get("num_layers", c["num_layers"])
    vocab = dep.get("vocab_size", c["vocab_size"])
    return layers * (layer_params_without_experts(c)
                     + real_picks * expert_params(c)) \
        + vocab * c["hidden_size"]


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "mla_params_per_sublayer": mla_params(c),
        "dense_ffn_params": dense_ffn_params(c),
        "router_params": router_params(c),
        "layer_params_without_experts": layer_params_without_experts(c),
        "expert_params": expert_params(c),
        "layer_params": layer_params(c),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "expert_bytes_bf16": expert_bytes(c),
        "latent_sublayers": latent_sublayers(c),
        "cache_row_bytes_bf16": cache_row_bytes(c),
        "latent_bytes_per_token_bf16": latent_bytes_per_token(c),
        "paged_latent_flops_per_row": paged_latent_flops_per_row(c),
    }
