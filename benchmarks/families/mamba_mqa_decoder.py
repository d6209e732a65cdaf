"""Family `mamba_mqa_decoder`: Mamba-1 layers with RMSNorms on Delta, B
and C beside full-attention layers of one K/V head at every
`attn_layer_period`th layer, a SwiGLU after every mixer, RMSNorm
pre-norms, no positions, a tied head (`model_type` `jamba` with
`num_experts` 1).  Builds the PROGRAM's model config from a
configuration file (Hugging Face key names) and names the plain
reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "mamba_mqa_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `JambaConfig` at this configuration's sizes.
    Refuses what the program does not compute."""
    import jax.numpy as jnp

    from ray_tpu.models.jamba import JambaConfig

    D, H = c["hidden_size"], c["num_attention_heads"]
    state = c.get("precision", {}).get("recurrent_state", "float32")
    refused = {
        f"num_experts {c.get('num_experts')} (a router and experts)":
            c.get("num_experts", 1) != 1,
        f"a sliding_window of {c.get('sliding_window')}":
            c.get("sliding_window") is not None,
        "an untied head": not c.get("tie_word_embeddings", True),
        "mamba_proj_bias": bool(c.get("mamba_proj_bias")),
        "a convolution without its bias":
            not c.get("mamba_conv_bias", True),
        "query heads that do not divide over the K/V heads":
            H % c["num_key_value_heads"] != 0,
        "a recurrent state kept in " + state: state != "float32",
        "hidden_act " + str(c.get("hidden_act")):
            c.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("the program's Mamba/one-K/V-head-attention "
                         "decoder has no "
                         + ", ".join(k for k, v in refused.items() if v))
    return JambaConfig(
        vocab_size=c["vocab_size"], dim=D, n_layers=c["num_hidden_layers"],
        attn_period=c["attn_layer_period"],
        attn_offset=c["attn_layer_offset"], n_heads=H,
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim") or D // H,
        hidden_dim=c["intermediate_size"], d_state=c["mamba_d_state"],
        d_conv=c["mamba_d_conv"], expand=c["mamba_expand"],
        dt_rank=c["mamba_dt_rank"], max_seq_len=max_seq_len,
        norm_eps=float(c["rms_norm_eps"]),
        state_dtype=getattr(jnp, _DTYPES[state]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


def program_params(weights):
    """The program holds what the reference drew, the SAME buffers under
    the same names: a second copy of the whole model does not fit the
    chip beside the slots' state."""
    return weights


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (the Mamba's
    and the attention's projections, the feed-forward; not the tied
    table, which is a gather too, nor the convolution taps, the norms,
    the biases and the decays) rounded per output channel to int8 and
    handed back in the weights' own dtype, a stacked leaf a layer at a
    time so that the temporaries are one layer's (the sound weights
    stand beside the rounded ones while this runs: 6.1 + 5.7 GB, before
    the engine is built).  Jittable.  `correct` has to come out false
    with these."""
    from ray_tpu.models.jamba import quantize_int8

    return quantize_int8(weights)
