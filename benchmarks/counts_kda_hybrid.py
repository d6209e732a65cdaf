"""Parameters, bytes and operations of the KDA / latent-attention
hybrid with a held share of routed experts, from a configuration
file's sizes (Hugging Face key names; `num_experts` is what this chip
HOLDS, `deployment.num_experts` the router's published width): the
yardstick's counts for the family `kda_hybrid_decoder`, beside
`counts_latent_moe.py`.  Nothing here asks the program.  Norm vectors,
the selection bias, `A_log` and `dt_bias` are in no count (0.06 M of
3,772 M).
"""

from __future__ import annotations

from typing import Mapping

BF16 = 2
F32 = 4


def _la(c: Mapping) -> Mapping:
    return c["linear_attn_config"]


def kda_width(c: Mapping) -> int:
    return _la(c)["num_heads"] * _la(c)["head_dim"]


def kda_params(c: Mapping) -> int:
    """q, k, v, o projections, the three convolutions' taps, the two
    low-rank gates (decay, output) and the beta projection of one KDA
    layer."""
    d, w, r = c["hidden_size"], kda_width(c), _la(c)["head_dim"]
    return (4 * d * w + 3 * _la(c)["short_conv_kernel_size"] * w
            + 2 * (d * r + r * w) + d * _la(c)["num_heads"])


def mla_params(c: Mapping) -> int:
    """q_proj, kv_a_proj_with_mqa, kv_b_proj, o_proj of one MLA layer."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    return (d * h * (c["qk_nope_head_dim"] + c["qk_rope_head_dim"])
            + d * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            + c["kv_lora_rank"] * h * (c["qk_nope_head_dim"]
                                       + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def layer_kinds(c: Mapping):
    """'kda' or 'mla' for each held layer (the published lists count
    layers from 1)."""
    return ["kda" if i + 1 in _la(c)["kda_layers"] else "mla"
            for i in range(c["num_hidden_layers"])]


def n_kda_layers(c: Mapping) -> int:
    return layer_kinds(c).count("kda")


def n_mla_layers(c: Mapping) -> int:
    return layer_kinds(c).count("mla")


def mixer_params(c: Mapping, kind: str) -> int:
    return kda_params(c) if kind == "kda" else mla_params(c)


def expert_params(c: Mapping) -> int:
    """gate, up, down of ONE routed expert."""
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: Mapping) -> int:
    """The router keeps its published width."""
    return c["hidden_size"] * c.get("deployment", {}).get(
        "num_experts", c["num_experts"])


def expert_half_params(c: Mapping) -> int:
    """Held routed experts, the shared ones and the router of one layer."""
    return ((c["num_experts"] + c["num_shared_experts"]) * expert_params(c)
            + router_params(c))


def dense_half_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def n_expert_layers(c: Mapping) -> int:
    return c["num_hidden_layers"] - c["first_k_dense_replace"]


def layer_params(c: Mapping, i: int) -> int:
    half = dense_half_params(c) if i < c["first_k_dense_replace"] \
        else expert_half_params(c)
    return mixer_params(c, layer_kinds(c)[i]) + half


def vocab_params(c: Mapping) -> int:
    """Embedding table and untied head over the held vocabulary."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    return sum(layer_params(c, i) for i in range(c["num_hidden_layers"])) \
        + vocab_params(c)


def expert_bytes(c: Mapping, bytes_per_param: int = BF16) -> int:
    """What one routed expert weighs: the least a tick reads for each
    distinct held expert it touches."""
    return expert_params(c) * bytes_per_param


def latent_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One cache row an MLA layer: the latent and the shared key."""
    return (n_mla_layers(c) * (c["kv_lora_rank"] + c["qk_rope_head_dim"])
            * bytes_per_value)


def kda_state_bytes(c: Mapping, bytes_per_value: int = F32) -> int:
    """One sequence's delta-rule state in ONE KDA layer: heads x dk x dv."""
    return _la(c)["num_heads"] * _la(c)["head_dim"] ** 2 * bytes_per_value


def kda_conv_tail_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """The rows of q~, k~, v~ one sequence keeps for the convolution in
    ONE KDA layer."""
    return ((_la(c)["short_conv_kernel_size"] - 1) * 3 * kda_width(c)
            * bytes_per_value)


def kda_step_state_traffic(c: Mapping) -> int:
    """The LEAST one token of one sequence must move of recurrent state
    in a tick: every KDA layer's state read once and written once."""
    return n_kda_layers(c) * 2 * kda_state_bytes(c)


KDA_CHUNK = 64      # tokens a chunk of the chunkwise form, as counted


def kda_chunk_flops(c: Mapping, chunk: int = KDA_CHUNK) -> int:
    """Multiply-adds x 2 of the chunkwise form of the recurrence (the WY
    / UT form; `ops/kda.py::kda_chunked` is the program's) for ONE chunk
    of `chunk` tokens in ONE KDA layer, all heads.  What is counted, a
    head: the two [C, C] decay-weighted products k k^T and q k^T over
    dk channels (2 x 2 C C dk; the program takes them as elementwise
    products and sums, and they are the bulk of the work); the
    unit-lower-triangular solve for [v | k exp(G)] (C C (dv + dk): half
    a full product); W_k S and (q exp(G)) S (2 x 2 C dk dv); the
    lower-triangular A_q u (C C dv); k_out^T u into the state
    (2 C dk dv).  Not counted: exponentials, cumulative sums, masks,
    the decay of S itself, projections, convolution and gates (other
    scopes).  The [C, C] terms make the count a token grow with C, so
    the yardstick keeps `KDA_CHUNK` and does not ask the program for
    its own."""
    la = _la(c)
    C, dk = chunk, la["head_dim"]
    dv = dk
    head = (2 * 2 * C * C * dk + C * C * (dv + dk) + 2 * 2 * C * dk * dv
            + C * C * dv + 2 * C * dk * dv)
    return la["num_heads"] * head


def kda_prefill_flops(c: Mapping, tokens: int) -> float:
    """`kda_chunk_flops` at `KDA_CHUNK` for `tokens` real tokens in every
    KDA layer (whole chunks are not rounded up: padding is no useful
    work)."""
    return n_kda_layers(c) * kda_chunk_flops(c) * tokens / KDA_CHUNK


def active_params_per_token(c: Mapping) -> int:
    """Parameters one token is multiplied by ON THIS CHIP in expectation:
    every layer's mixer, the dense feed-forward, in each expert layer
    the shared experts, the router and the held share of its k experts,
    and the head."""
    share = c["num_experts"] / c.get("deployment", {}).get(
        "num_experts", c["num_experts"])
    per_expert_layer = (
        (c["num_experts_per_token"] * share + c["num_shared_experts"])
        * expert_params(c) + router_params(c))
    return int(sum(mixer_params(c, k) for k in layer_kinds(c))
               + c["first_k_dense_replace"] * dense_half_params(c)
               + n_expert_layers(c) * per_expert_layer
               + c["vocab_size"] * c["hidden_size"])


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "kda_params_per_layer": kda_params(c),
        "mla_params_per_layer": mla_params(c),
        "expert_params": expert_params(c),
        "expert_half_params": expert_half_params(c),
        "layer_params": [layer_params(c, i)
                         for i in range(c["num_hidden_layers"])],
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "active_params_per_token": active_params_per_token(c),
        "expert_bytes_bf16": expert_bytes(c),
        "latent_bytes_per_token_bf16": latent_bytes_per_token(c),
        "kda_state_bytes_per_layer_f32": kda_state_bytes(c),
        "kda_conv_tail_bytes_per_layer_bf16": kda_conv_tail_bytes(c),
        "kda_step_state_traffic_bytes": kda_step_state_traffic(c),
    }
