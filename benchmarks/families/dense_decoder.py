"""Family `dense_decoder`: RMSNorm, rotary, grouped-query attention,
SwiGLU, untied head.  Builds the PROGRAM's model config from a
configuration file (Hugging Face key names) and names the plain
reference that judges it.  A new family is a new file here plus its
reference under `benchmarks/reference/`; no file that exists is edited.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "dense_decoder"     # benchmarks/reference/dense_decoder.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `LlamaConfig` at this configuration's sizes."""
    import jax.numpy as jnp

    from ray_tpu.models.llama import LlamaConfig

    if c.get("sliding_window") is not None:
        raise ValueError("the program has no sliding-window attention")
    if c["hidden_size"] != c["num_attention_heads"] * int(
            c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]):
        raise ValueError("the program derives head_dim as hidden/heads")
    return LlamaConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        hidden_dim=c["intermediate_size"], max_seq_len=max_seq_len,
        rope_theta=float(c["rope_theta"]), norm_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]),
        tie_embeddings=bool(c.get("tie_word_embeddings", False)),
        **overrides)


def program_params(weights):
    """The reference's weight tree in the layout the program reads.  The
    two use the same names, so this is the identity; a family whose
    program stacks or fuses weights differently converts here."""
    return weights


def lower_precision_params(weights):
    """The control for a serving cell: the program's own int8
    weight-only path (per-channel scales), the step a later PR would be
    tempted by.  `correct` has to come out false with these."""
    from ray_tpu.models.llama import quantize_weights_int8

    return quantize_weights_int8(weights)
