"""Family `conv_moe_decoder`: gated short-convolution layers and
grouped-query attention layers (QK-norm, rotate-half rotary) in the
published `layer_types`, leading dense SwiGLU layers, then a whole bank
of dropless sigmoid-routed experts with a selection bias and no shared
expert; head tied to the embedding (`model_type` `lfm2_moe`).  Builds
the PROGRAM's model config from a configuration file (Hugging Face key
names) and names the plain reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "conv_moe_decoder"      # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}
_KINDS = ("conv", "full_attention")


def _reference():
    # `benchmarks/` is on sys.path wherever a family is loaded (run.py,
    # the tests' conftest)
    from reference import conv_moe_decoder

    return conv_moe_decoder


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `ConvMoEConfig` at this configuration's sizes: the
    first `num_hidden_layers` of `layer_types`."""
    import jax.numpy as jnp

    from ray_tpu.models.conv_moe import ConvMoEConfig

    L = c["num_hidden_layers"]
    kinds = c["layer_types"][:L]
    refused = {
        "conv_bias": bool(c.get("conv_bias")),
        "a layer_types entry that is neither conv nor full_attention":
            any(k not in _KINDS for k in kinds) or len(kinds) < L,
        "norm_topk_prob false": not c.get("norm_topk_prob", True),
        "use_expert_bias false": not c.get("use_expert_bias", True),
        "an untied head": not c.get("tie_word_embeddings", True),
        "rope_scaling": c.get("rope_scaling") is not None,
    }
    if any(refused.values()):
        raise ValueError("the program's convolution/attention/expert block "
                         "has no " + ", ".join(
                             k for k, v in refused.items() if v))
    return ConvMoEConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=L,
        attn_layers=tuple(i for i, k in enumerate(kinds)
                          if k == "full_attention"),
        n_dense_layers=c["num_dense_layers"],
        n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        conv_size=c["conv_L_cache"],
        dense_hidden_dim=c["intermediate_size"],
        expert_hidden_dim=c["moe_intermediate_size"],
        n_experts=c["num_experts"], top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["norm_eps"]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The reference's expert blocks ARE the sibling's (`expert_block`), so
# the program's copy is made, and the control's side effect on the sound
# bank (8.5 GB here: two do not fit one chip) kept, where it makes them.
from families.latent_moe_decoder import (  # noqa: E402, F401
    _SOUND_BANK, _round_int8, program_params)


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (the
    convolution's and the attention's projections, feed-forward, router,
    experts, and the embedding table, which tied is the head: rounded a
    vocabulary row, the head's output channel; not the convolution taps,
    the norms and the selection bias) rounded per output channel to int8
    and handed back in the weights' own dtype.  Jittable; the routed experts
    are drawn and rounded a block at a time.  `correct` has to come out
    false with these.  Side effect, when traced: the bank of sound
    experts that `program_params` made last is DELETED."""
    ref = _reference()
    while _SOUND_BANK:
        _SOUND_BANK.pop().delete()
    layers = []
    for w in weights["layers"]:
        p = {k: (_round_int8(v) if v.ndim == 2 and k != "conv" else v)
             for k, v in w.items() if k != "experts"}
        if "experts" in w:
            blocks = ref.map_expert_blocks(
                lambda b: tuple(_round_int8(x) for x in b), w["experts"])
            p.update((k, b.reshape((-1,) + b.shape[2:]))
                     for k, b in zip(ref.EXPERT_KEYS, blocks))
        layers.append(p)
    return dict(weights, layers=layers,
                embed=_round_int8(weights["embed"].T).T)
