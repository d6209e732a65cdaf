"""Family `window_moe_decoder`: sliding-window and full grouped-query
attention layers in the published `layer_types` (QK-norm, an output
gate, rotate-half rotary on the window layers ONLY), four RMS norms a
layer, a muP-scaled embedding, leading dense SwiGLU layers, then
dropless sigmoid-routed experts with a selection bias beside one shared
expert; an untied head (`model_type` `afmoe`).  Builds the PROGRAM's
model config from a configuration file (Hugging Face key names) and
names the plain reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "window_moe_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def _reference():
    # `benchmarks/` is on sys.path wherever a family is loaded (run.py,
    # the tests' conftest)
    from reference import window_moe_decoder

    return window_moe_decoder


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `WindowMoEConfig` at this configuration's sizes:
    `num_hidden_layers` of `layer_types` from `first_layer` on."""
    import jax.numpy as jnp

    from ray_tpu.models.window_moe import WindowMoEConfig

    ref = _reference()
    L = c["num_hidden_layers"]
    kinds = ref.layer_kinds(c)
    refused = {
        "rope_scaling": c.get("rope_scaling") is not None,
        "a limit on expert groups (n_group or topk_group over 1)":
            c.get("n_group", 1) != 1 or c.get("topk_group", 1) != 1,
        "route_norm false": not c.get("route_norm", True),
        "mup_enabled false (an unscaled embedding)":
            not c.get("mup_enabled", True),
        "a tied head": bool(c.get("tie_word_embeddings", False)),
        "a score function other than sigmoid":
            c.get("score_func", "sigmoid") != "sigmoid",
        "a layer_types entry that is neither sliding_attention nor "
        "full_attention": any(k not in ref.KINDS for k in kinds)
            or len(kinds) < L,
    }
    if any(refused.values()):
        raise ValueError("the program's window/full attention and expert "
                         "block has no " + ", ".join(
                             k for k, v in refused.items() if v))
    return WindowMoEConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=L,
        full_layers=tuple(i for i, k in enumerate(kinds)
                          if k == "full_attention"),
        window=c["sliding_window"],
        n_dense_layers=c["num_dense_layers"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        dense_hidden_dim=c["intermediate_size"],
        expert_hidden_dim=c["moe_intermediate_size"],
        shared_hidden_dim=c["moe_intermediate_size"]
        * c["num_shared_experts"],
        n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["route_scale"]),
        max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The reference's expert blocks ARE the sibling's (`expert_block`), so
# the program's copy is made, the control's rounding done, and the
# control's side effect on the sound bank (6.4 GB here: two do not fit
# one chip beside the rest) kept, where the sibling makes them: every
# matmul weight (attention with its gate, feed-forward, router, shared
# and routed experts, head; not the embedding table, a gather, nor the
# norms and the selection bias) rounded per output channel to int8.
from families.latent_moe_decoder import (  # noqa: E402, F401
    _SOUND_BANK, _round_int8, lower_precision_params, program_params)
