"""Family `blockdiff_moe_decoder`: pre-norm grouped-query attention with
QK-norm and rotate-half rotary, softmax-routed SwiGLU experts of which
this chip holds a share, an untied head, under a BLOCK-causal mask, and
generation by diffusion over blocks (`model_type` `sdar_moe`).  Builds
the PROGRAM's model config from a configuration file (Hugging Face key
names; `deployment` for the share; `block_length`, `denoising_steps`,
`remasking_strategy`, `confidence_threshold`, `mask_token_id` for the
generation, which the source's config.json does not carry) and names the
plain reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "blockdiff_moe_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `BlockDiffMoEConfig` at this configuration's sizes
    and share.  Refuses what the program does not compute."""
    import jax.numpy as jnp

    from ray_tpu.models.blockdiff_moe import BlockDiffMoEConfig

    dep = c.get("deployment", {})
    refused = {
        "rope_scaling": c.get("rope_scaling") is not None,
        "attention_bias": bool(c.get("attention_bias")),
        "a sliding window": bool(c.get("use_sliding_window"))
            or c.get("sliding_window") is not None,
        "norm_topk_prob false": not c.get("norm_topk_prob", True),
        "a dense feed-forward layer (mlp_only_layers, or a "
        "decoder_sparse_step other than 1)":
            bool(c.get("mlp_only_layers")) or c.get("decoder_sparse_step",
                                                    1) != 1,
        "hidden_act " + str(c.get("hidden_act")):
            c.get("hidden_act", "silu") != "silu",
        "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
    }
    if any(refused.values()):
        raise ValueError("the program's block-diffusion expert decoder has "
                         "no " + ", ".join(k for k, v in refused.items()
                                           if v))
    published = dep.get("num_experts", c["num_experts"])
    return BlockDiffMoEConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        expert_hidden_dim=c["moe_intermediate_size"], n_experts=published,
        top_k=c["num_experts_per_tok"], expert_rank=dep.get("rank", 0),
        expert_shards=published // c["num_experts"],
        max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        block_length=int(c["block_length"]),
        denoising_steps=int(c["denoising_steps"]),
        remasking=c["remasking_strategy"],
        confidence_threshold=float(c["confidence_threshold"]),
        mask_token_id=int(c["mask_token_id"]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The reference's expert blocks ARE the sibling's (`expert_block`), and
# its tree is a list of layers with an `experts` record each, so the
# program's copy is made, the control's rounding done, and the control's
# side effect on the sound bank kept, where the sibling makes them:
# every matmul weight (attention, router, experts, head; not the
# embedding table, a gather, nor the norms) rounded per output channel
# to int8.
from families.latent_moe_decoder import (  # noqa: E402, F401
    _SOUND_BANK, _round_int8, lower_precision_params, program_params)
