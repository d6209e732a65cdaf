"""Family `shortcut_moe_decoder`: two latent-attention sublayers (query
compressed through a low rank, both low-rank paths scaled) and two dense
feed-forwards a layer, one expert layer on a shortcut across them whose
softmax router is as wide as the routed experts PLUS the zero-compute
ones, of which routed experts this chip holds a share; untied head
(`model_type` `longcat_flash`).  Builds the PROGRAM's model config from
a configuration file (the source's own key names, and `deployment` for
the share) and names the plain reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "shortcut_moe_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def _reference():
    # `benchmarks/` is on sys.path wherever a family is loaded (run.py,
    # the tests' conftest)
    from reference import shortcut_moe_decoder

    return shortcut_moe_decoder


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `ShortcutMoEConfig` at this configuration's sizes
    and share."""
    import jax.numpy as jnp

    from ray_tpu.models.shortcut_moe import ShortcutMoEConfig

    dep = c.get("deployment", {})
    refused = {
        "attention_method other than MLA":
            c.get("attention_method", "MLA") != "MLA",
        "attention_bias": bool(c.get("attention_bias")),
        "rope_scaling": c.get("rope_scaling") is not None,
        "q_lora_rank null": c.get("q_lora_rank") is None,
        "one low-rank path scaled and not the other":
            bool(c.get("mla_scale_q_lora")) != bool(
                c.get("mla_scale_kv_lora")),
        "zero_expert_type other than identity":
            c.get("zero_expert_type", "identity") != "identity",
        "norm_topk_prob": bool(c.get("norm_topk_prob", False)),
        "router_bias (a bias term on the router's logits)":
            bool(c.get("router_bias", False)),
        "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
    }
    if any(refused.values()):
        raise ValueError("the program's shortcut expert block has no "
                         + ", ".join(k for k, v in refused.items() if v))
    published = dep.get("n_routed_experts", c["n_routed_experts"])
    return ShortcutMoEConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"],
        n_layers=c["num_layers"], n_heads=c["num_attention_heads"],
        q_lora_rank=c["q_lora_rank"], kv_lora_rank=c["kv_lora_rank"],
        scale_lora=bool(c.get("mla_scale_q_lora")),
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        dense_hidden_dim=c["ffn_hidden_size"],
        expert_hidden_dim=c["expert_ffn_hidden_size"],
        n_experts=published, n_zero_experts=c["zero_expert_num"],
        top_k=c["moe_topk"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        max_seq_len=max_seq_len, rope_theta=float(c["rope_theta"]),
        norm_eps=float(c["rms_norm_eps"]),
        expert_rank=dep.get("rank", 0),
        expert_shards=published // c["n_routed_experts"],
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The routed experts `program_params` last made; see the sibling family
# `latent_moe_decoder`: a control gives the sound bank back before it
# draws its own, because two do not fit one chip.
_SOUND_BANK = []


def program_params(weights):
    """The reference keeps, for each layer's held experts, what to draw
    them from; the program holds them beside the router: the same draws,
    made once, as `w_gate`, `w_up` `[Eh, D, F]` and `w_down`
    `[Eh, F, D]`."""
    ref = _reference()
    layers = []
    del _SOUND_BANK[:]
    for w in weights["layers"]:
        bank = ref.expert_bank(w["experts"])
        _SOUND_BANK.extend(bank.values())
        layers.append({"sub": w["sub"], "moe": dict(w["moe"], **bank)})
    return dict(weights, layers=layers)


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (both
    sublayers' attention and feed-forward, router, experts, head; not
    the embedding table, a gather, nor the norms and the selection
    bias) rounded per output channel to int8 and handed back in the
    weights' own dtype.  Jittable; the routed experts are drawn and
    rounded a block at a time.  `correct` has to come out false with
    these.  Side effect, when traced: the bank of sound experts that
    `program_params` made last is DELETED."""
    from families.latent_moe_decoder import _round_int8   # the sibling's

    ref = _reference()
    while _SOUND_BANK:
        _SOUND_BANK.pop().delete()

    def rounded(group):
        return {k: (_round_int8(v) if v.ndim == 2 else v)
                for k, v in group.items()}

    layers = []
    for w in weights["layers"]:
        blocks = ref.map_expert_blocks(
            lambda b: tuple(_round_int8(x) for x in b), w["experts"])
        bank = {k: b.reshape((-1,) + b.shape[2:])
                for k, b in zip(ref.EXPERT_KEYS, blocks)}
        layers.append({"sub": [rounded(s) for s in w["sub"]],
                       "moe": dict(rounded(w["moe"]), **bank)})
    return dict(weights, layers=layers,
                lm_head=_round_int8(weights["lm_head"]))
