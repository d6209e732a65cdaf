"""Family `gdn_hybrid_decoder`: gated delta-rule layers (one decay a
head, keys and values of different sizes, write strengths up to 2) and
full multi-head attention layers (QK-norm over the whole width, no
rotary unless the file gives a theta) in the published `layer_types`,
post-norm blocks, dense SwiGLU, untied head (`model_type`
`olmo_hybrid`).  Builds the PROGRAM's model config from a configuration
file (Hugging Face key names) and names the plain reference that judges
it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "gdn_hybrid_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
_KINDS = ("linear_attention", "full_attention")


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `GdnHybridConfig` at this configuration's sizes:
    the first `num_hidden_layers` of `layer_types`.  Refuses what the
    program does not compute."""
    import jax.numpy as jnp

    from ray_tpu.models.gdn_hybrid import GdnHybridConfig

    L = c["num_hidden_layers"]
    kinds = c["layer_types"][:L]
    state = c.get("precision", {}).get("recurrent_state", "float32")
    refused = {
        "a layer_types entry that is neither linear_attention nor "
        "full_attention": any(k not in _KINDS for k in kinds)
        or len(kinds) < L,
        "linear_num_key_heads != linear_num_value_heads":
            c["linear_num_key_heads"] != c["linear_num_value_heads"],
        "attention_bias": bool(c.get("attention_bias")),
        "a tied head": bool(c.get("tie_word_embeddings")),
        "a write strength without its factor 2 (linear_allow_neg_eigval "
        "false)": not c.get("linear_allow_neg_eigval", False),
        "a recurrent state kept in " + state: state != "float32",
        "hidden_act " + str(c.get("hidden_act")):
            c.get("hidden_act", "silu") != "silu",
    }
    if any(refused.values()):
        raise ValueError("the program's delta-rule/attention block has no "
                         + ", ".join(k for k, v in refused.items() if v))
    theta = (c.get("rope_parameters") or {}).get("rope_theta")
    return GdnHybridConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=L,
        attn_layers=tuple(i for i, k in enumerate(kinds)
                          if k == "full_attention"),
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        head_dim=c.get("head_dim")
        or c["hidden_size"] // c["num_attention_heads"],
        gdn_heads=c["linear_num_value_heads"],
        gdn_key_dim=c["linear_key_head_dim"],
        gdn_value_dim=c["linear_value_head_dim"],
        conv_size=c["linear_conv_kernel_dim"],
        hidden_dim=c["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=None if theta is None else float(theta),
        norm_eps=float(c["rms_norm_eps"]),
        state_dtype=getattr(jnp, _DTYPES[state]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


def program_params(weights):
    """The program holds what the reference drew, under the same
    names."""
    return weights


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (the delta
    rule's and the attention's projections, the gates, the feed-forward,
    the head; not the embedding table, a gather, nor the convolution
    taps, the norms and the decays) rounded per output channel to int8
    and handed back in the weights' own dtype.  Jittable.  `correct` has
    to come out false with these."""
    from families.latent_moe_decoder import _round_int8   # the sibling's

    layers = [{k: (_round_int8(v) if v.ndim == 2
                   and not k.startswith("conv_") else v)
               for k, v in w.items()} for w in weights["layers"]]
    return dict(weights, layers=layers,
                lm_head=_round_int8(weights["lm_head"]))
