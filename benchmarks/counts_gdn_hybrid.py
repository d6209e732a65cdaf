"""Parameters, bytes and operations of the gated delta-rule / full
attention hybrid in post-norm blocks, from a configuration file's sizes
(Hugging Face key names): the yardstick's counts for the family
`gdn_hybrid_decoder`, beside `counts_kda_hybrid.py`.  Nothing here asks
the program: the state's bytes are the MATHEMATICS' (heads x dk x dv
float32), whatever layout the program keeps it in.
"""

from __future__ import annotations

from typing import Mapping

BF16 = 2
F32 = 4


def head_dim(c: Mapping) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def key_width(c: Mapping) -> int:
    return c["linear_num_key_heads"] * c["linear_key_head_dim"]


def value_width(c: Mapping) -> int:
    return c["linear_num_value_heads"] * c["linear_value_head_dim"]


def gdn_params(c: Mapping) -> int:
    """One delta-rule mixer: Wq, Wk (to the keys' width), Wv, Wg, Wo (the
    values'), Wa and Wb (a head), the three convolutions' taps, A_log,
    dt_bias and the output norm."""
    d, h = c["hidden_size"], c["linear_num_value_heads"]
    return (2 * d * key_width(c) + 3 * d * value_width(c) + 2 * d * h
            + c["linear_conv_kernel_dim"] * (2 * key_width(c)
                                             + value_width(c))
            + 2 * h + c["linear_value_head_dim"])


def attn_params(c: Mapping) -> int:
    """One attention mixer: Wq, Wo, Wk, Wv and the two QK-norm vectors
    over the whole width."""
    d = c["hidden_size"]
    a = c["num_attention_heads"] * head_dim(c)
    akv = c["num_key_value_heads"] * head_dim(c)
    return 2 * d * a + 2 * d * akv + a + akv


def ff_params(c: Mapping) -> int:
    return 3 * c["hidden_size"] * c["intermediate_size"]


def layer_kinds(c: Mapping):
    return list(c["layer_types"][:c["num_hidden_layers"]])


def n_gdn_layers(c: Mapping) -> int:
    return layer_kinds(c).count("linear_attention")


def n_attn_layers(c: Mapping) -> int:
    return layer_kinds(c).count("full_attention")


def layer_params(c: Mapping, kind: str) -> int:
    """A mixer, the SwiGLU and the block's two norms."""
    mixer = gdn_params(c) if kind == "linear_attention" else attn_params(c)
    return mixer + ff_params(c) + 2 * c["hidden_size"]


def period_params(c: Mapping) -> int:
    """Three delta-rule layers and a full one, as published."""
    return 3 * layer_params(c, "linear_attention") \
        + layer_params(c, "full_attention")


def vocab_params(c: Mapping) -> int:
    """Embedding table and untied head."""
    return 2 * c["vocab_size"] * c["hidden_size"]


def total_params(c: Mapping) -> int:
    """Held: the file's layers, embedding and head (the final norm's
    `hidden_size` values are in no count)."""
    return sum(layer_params(c, k) for k in layer_kinds(c)) + vocab_params(c)


def published_params(c: Mapping) -> int:
    """The whole model: every entry of `layer_types`."""
    return sum(layer_params(c, k) for k in c["layer_types"]) \
        + vocab_params(c)


def kv_bytes_per_token(c: Mapping, bytes_per_value: int = BF16) -> int:
    """One token's K and V rows in every attention layer."""
    return (n_attn_layers(c) * 2 * c["num_key_value_heads"] * head_dim(c)
            * bytes_per_value)


def paged_attention_bytes(c: Mapping, rows: float) -> float:
    """The LEAST a tick's attention reads of the pools: `rows` live rows
    (every live slot's tokens so far), K and V, every attention layer."""
    return rows * kv_bytes_per_token(c)


def state_bytes(c: Mapping, bytes_per_value: int = F32) -> int:
    """One sequence's delta-rule state in ONE layer: heads x dk x dv."""
    return (c["linear_num_value_heads"] * c["linear_key_head_dim"]
            * c["linear_value_head_dim"] * bytes_per_value)


def conv_tail_bytes(c: Mapping, bytes_per_value: int = BF16) -> int:
    """The rows of q~, k~, v~ one sequence keeps for the convolution in
    ONE delta-rule layer."""
    return ((c["linear_conv_kernel_dim"] - 1)
            * (2 * key_width(c) + value_width(c)) * bytes_per_value)


def step_state_traffic(c: Mapping) -> int:
    """The LEAST one token of one sequence must move of recurrent state
    in a tick: every delta-rule layer's state read once and written
    once (tails, q, k, v and gates not counted)."""
    return n_gdn_layers(c) * 2 * state_bytes(c)


CHUNK = 64      # tokens a chunk of the chunkwise form, as counted


def chunk_flops(c: Mapping, chunk: int = CHUNK) -> int:
    """Multiply-adds x 2 of the chunkwise form of the recurrence (the WY
    / UT form; `ops/kda.py::kda_chunked` is the program's) for ONE chunk
    of `chunk` tokens in ONE delta-rule layer, all heads, with ONE decay
    a head.  What is counted, a head: the two [C, C] products k k^T and
    q k^T over dk channels (2 x 2 C C dk: a decay a head comes out of
    the sum, so they are two matrix products); the unit-lower-triangular
    solve for [v | k exp(G)] (C C (dv + dk): half a full product);
    W_k S and (q exp(G)) S (2 x 2 C dk dv); the lower-triangular A_q u
    (C C dv); k_out^T u into the state (2 C dk dv).  Not counted:
    exponentials, cumulative sums, masks, the decay of S itself,
    projections, convolution and gates (other scopes).  The count does
    not change with the arm the program takes; the [C, C] terms make a
    token's count grow with C, so the yardstick keeps `CHUNK` and does
    not ask the program for its own."""
    C, dk, dv = chunk, c["linear_key_head_dim"], c["linear_value_head_dim"]
    head = (2 * 2 * C * C * dk + C * C * (dv + dk) + 2 * 2 * C * dk * dv
            + C * C * dv + 2 * C * dk * dv)
    return c["linear_num_value_heads"] * head


def prefill_flops(c: Mapping, tokens: int) -> float:
    """`chunk_flops` at `CHUNK` for `tokens` real tokens in every
    delta-rule layer (whole chunks are not rounded up: padding is no
    useful work)."""
    return n_gdn_layers(c) * chunk_flops(c) * tokens / CHUNK


def constants(c: Mapping) -> dict:
    """What the configuration file carries beside its sizes."""
    return {
        "gdn_mixer_params": gdn_params(c),
        "attn_mixer_params": attn_params(c),
        "ff_params": ff_params(c),
        "linear_layer_params": layer_params(c, "linear_attention"),
        "full_layer_params": layer_params(c, "full_attention"),
        "period_params": period_params(c),
        "vocab_params": vocab_params(c),
        "total_params": total_params(c),
        "published_params": published_params(c),
        "weight_bytes_bf16": total_params(c) * BF16,
        "kv_bytes_per_token_bf16": kv_bytes_per_token(c),
        "state_bytes_per_layer_f32": state_bytes(c),
        "conv_tail_bytes_per_layer_bf16": conv_tail_bytes(c),
        "step_state_traffic_bytes": step_state_traffic(c),
    }
