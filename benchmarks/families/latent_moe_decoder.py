"""Family `latent_moe_decoder`: latent attention (MLA) over one cache
row a token, a leading dense layer, then dropless sigmoid-routed
experts with shared experts, untied head (`model_type` `deepseek_v3`,
no query compression).  Builds the PROGRAM's model config from a
configuration file (Hugging Face key names) and names the plain
reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "latent_moe_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def _reference():
    # `benchmarks/` is on sys.path wherever a family is loaded (run.py,
    # the tests' conftest)
    from reference import latent_moe_decoder

    return latent_moe_decoder


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `LatentMoEConfig` at this configuration's sizes."""
    import jax.numpy as jnp

    from ray_tpu.models.latent_moe import LatentMoEConfig

    refused = {
        "q_lora_rank": c.get("q_lora_rank") is not None,
        "rope_scaling": c.get("rope_scaling") is not None,
        "attention_bias": bool(c.get("attention_bias")),
        "n_group / topk_group": (c.get("n_group", 1), c.get("topk_group", 1))
        != (1, 1),
        "scoring_func": c.get("scoring_func", "sigmoid") != "sigmoid",
        "norm_topk_prob": not c.get("norm_topk_prob", True),
        "moe_layer_freq": c.get("moe_layer_freq", 1) != 1,
        "rope_interleave": not c.get("rope_interleave", True),
        "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
    }
    if any(refused.values()):
        raise ValueError("the program's latent/expert block has no "
                         + ", ".join(k for k, v in refused.items() if v))
    return LatentMoEConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_hidden_layers"],
        n_dense_layers=c["first_k_dense_replace"],
        n_heads=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        dense_hidden_dim=c["intermediate_size"],
        expert_hidden_dim=c["moe_intermediate_size"],
        n_experts=c["n_routed_experts"], top_k=c["num_experts_per_tok"],
        n_shared_experts=c["n_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The routed experts `program_params` last made.  `drivers/serve_engine.py`
# builds the sound parameters first and only then, for a control run,
# asks for the lower-precision ones while it still holds the sound set;
# two banks of experts (8.5 GB each at kanana's widths) do not fit one
# chip, so the control gives the sound bank back before it draws its own.
_SOUND_BANK = []


def program_params(weights):
    """The reference keeps, for each layer's routed experts, what to
    draw them from; the program holds them: the same draws, made once,
    as `w_gate`, `w_up` `[E, D, F]` and `w_down` `[E, F, D]`."""
    ref = _reference()
    layers = []
    del _SOUND_BANK[:]
    for w in weights["layers"]:
        p = {k: v for k, v in w.items() if k != "experts"}
        if "experts" in w:
            bank = ref.expert_bank(w["experts"])
            _SOUND_BANK.extend(bank.values())
            p.update(bank)
        layers.append(p)
    return dict(weights, layers=layers)


def _round_int8(w):
    """Per output channel (the last axis) symmetric int8, handed back in
    w's dtype."""
    import jax.numpy as jnp

    w32 = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=-2, keepdims=True)
                        / 127.0, 1e-8)
    return (jnp.clip(jnp.round(w32 / scale), -127, 127)
            * scale).astype(w.dtype)


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (attention,
    feed-forward, router, experts, head; not the embedding table, a
    gather, nor the norms and the selection bias) rounded per output
    channel to int8 and handed back in the weights' own dtype, which
    needs nothing of the program.  Jittable; the routed experts are
    drawn and rounded a block at a time.  `correct` has to come out
    false with these.  Side effect, when traced: the bank of sound
    experts that `program_params` made last is DELETED (see
    `_SOUND_BANK`); whoever still holds those parameters holds dead
    arrays, as the driver does for the one statement before it
    overwrites them."""
    ref = _reference()
    while _SOUND_BANK:
        _SOUND_BANK.pop().delete()
    layers = []
    for w in weights["layers"]:
        p = {k: (_round_int8(v) if v.ndim == 2 else v)
             for k, v in w.items() if k != "experts"}
        if "experts" in w:
            blocks = ref.map_expert_blocks(
                lambda b: tuple(_round_int8(x) for x in b), w["experts"])
            p.update((k, b.reshape((-1,) + b.shape[2:]))
                     for k, b in zip(ref.EXPERT_KEYS, blocks))
        layers.append(p)
    return dict(weights, layers=layers,
                lm_head=_round_int8(weights["lm_head"]))
