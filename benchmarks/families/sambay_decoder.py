"""Family `sambay_decoder`: Mamba layers beside differential attention in
a window, one full-attention layer whose K and V the cross-decoder
reads, gated memory units, LayerNorms with bias, a tied head
(`model_type` `phi4flash`).  Builds the PROGRAM's model config from a
configuration file (Hugging Face key names, and `mamba_*` for what the
published file leaves to its library's defaults) and names the plain
reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "sambay_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `SambaYConfig` at this configuration's sizes.
    Refuses what the program does not compute."""
    import jax.numpy as jnp

    from ray_tpu.models.sambay import SambaYConfig

    L, D = c["num_hidden_layers"], c["hidden_size"]
    state = c.get("precision", {}).get("recurrent_state", "float32")
    refused = {
        f"mb_per_layer {c.get('mb_per_layer')} (a Mamba at every even "
        "layer is 2)": c.get("mb_per_layer", 2) != 2,
        f"a depth of {L} (pairs: a multiple of 4, at least 8)":
            L % 4 != 0 or L < 8,
        "an untied head": not c.get("tie_word_embeddings", True),
        "mlp_bias": bool(c.get("mlp_bias")),
        "lm_head_bias": bool(c.get("lm_head_bias")),
        "query pairs that do not divide over the K/V pairs":
            c["num_key_value_heads"] % 2 != 0
            or c["num_attention_heads"] % c["num_key_value_heads"] != 0,
        "a dt_rank other than ceil(hidden_size / 16)":
            c.get("mamba_dt_rank", -(-D // 16)) != -(-D // 16),
        "a recurrent state kept in " + state: state != "float32",
        "hidden_act " + str(c.get("hidden_act")):
            c.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("the program's Mamba/differential-attention "
                         "decoder has no "
                         + ", ".join(k for k, v in refused.items() if v))
    return SambaYConfig(
        vocab_size=c["vocab_size"], dim=D, n_layers=L,
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], window=c["sliding_window"],
        hidden_dim=c["intermediate_size"],
        d_state=c.get("mamba_d_state", 16), d_conv=c.get("mamba_d_conv", 4),
        expand=c.get("mamba_expand", 2), max_seq_len=max_seq_len,
        norm_eps=float(c["layer_norm_eps"]),
        state_dtype=getattr(jnp, _DTYPES[state]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


def program_params(weights):
    """The program holds what the reference drew, the SAME buffers under
    the same names: a second copy of the whole model does not fit the
    chip."""
    return weights


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (the Mamba's,
    the attention's and the GMU's projections, the feed-forward; not the
    tied table, which is a gather too, nor the convolution taps, the
    norms, the biases, the decays and the lambdas) rounded per output
    channel to int8 and handed back in the weights' own dtype, a stacked
    leaf a layer at a time so that the temporaries are one layer's (the
    sound weights stand beside the rounded ones while this runs: 7.7 +
    6.7 GB).  Jittable.  `correct` has to come out false with these."""
    from ray_tpu.models.sambay import quantize_int8

    return quantize_int8(weights)
