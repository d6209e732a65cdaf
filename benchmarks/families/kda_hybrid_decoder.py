"""Family `kda_hybrid_decoder`: recurrent delta-rule layers (KDA) and
latent-attention layers (MLA, no rotary) in the published period, a
leading dense layer, then dropless sigmoid-routed experts of which this
chip holds a share, plus a shared expert; untied head (`model_type`
`kimi_linear`).  Builds the PROGRAM's model config from a configuration
file (Hugging Face key names, and `deployment` for the share) and names
the plain reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "kda_hybrid_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def _reference():
    # `benchmarks/` is on sys.path wherever a family is loaded (run.py,
    # the tests' conftest)
    from reference import kda_hybrid_decoder

    return kda_hybrid_decoder


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `KimiLinearConfig` at this configuration's sizes
    and share.  The recurrent state is kept in the file's
    `precision.recurrent_state`."""
    import jax.numpy as jnp

    from ray_tpu.models.kimi_linear import KimiLinearConfig

    la, dep = c["linear_attn_config"], c.get("deployment", {})
    L = c["num_hidden_layers"]
    refused = {
        "q_lora_rank": c.get("q_lora_rank") is not None,
        "rope_scaling": c.get("rope_scaling") is not None,
        "rotary on the latent layers (mla_use_nope false)":
            not c.get("mla_use_nope", False),
        "num_expert_group / topk_group":
            (c.get("num_expert_group", 1), c.get("topk_group", 1)) != (1, 1),
        "moe_router_activation_func":
            c.get("moe_router_activation_func", "sigmoid") != "sigmoid",
        "moe_renormalize": not c.get("moe_renormalize", True),
        "moe_layer_freq": c.get("moe_layer_freq", 1) != 1,
        "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
        "a layer that is neither in kda_layers nor in full_attn_layers":
            any((i in la["kda_layers"]) == (i in la["full_attn_layers"])
                for i in range(1, L + 1)),
    }
    if any(refused.values()):
        raise ValueError("the program's KDA/latent/expert block has no "
                         + ", ".join(k for k, v in refused.items() if v))
    published = dep.get("num_experts", c["num_experts"])
    state = c.get("precision", {}).get("recurrent_state", "float32")
    return KimiLinearConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], n_layers=L,
        n_dense_layers=c["first_k_dense_replace"],
        n_heads=c["num_attention_heads"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_head_dim=c["qk_nope_head_dim"],
        qk_rope_head_dim=c["qk_rope_head_dim"], v_head_dim=c["v_head_dim"],
        dense_hidden_dim=c["intermediate_size"],
        expert_hidden_dim=c["moe_intermediate_size"],
        n_experts=published, top_k=c["num_experts_per_token"],
        n_shared_experts=c["num_shared_experts"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        max_seq_len=max_seq_len, norm_eps=float(c["rms_norm_eps"]),
        kda_layers=tuple(i - 1 for i in la["kda_layers"] if i <= L),
        kda_heads=la["num_heads"], kda_head_dim=la["head_dim"],
        conv_size=la["short_conv_kernel_size"],
        state_dtype=getattr(jnp, _DTYPES[state]),
        expert_rank=dep.get("rank", 0),
        expert_shards=published // c["num_experts"],
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The routed experts `program_params` last made; see the sibling family
# `latent_moe_decoder`: a control gives the sound bank back before it
# draws its own, because two do not fit one chip.
_SOUND_BANK = []


def program_params(weights):
    """The reference keeps, for each layer's held experts, what to draw
    them from; the program holds them: the same draws, made once, as
    `w_gate`, `w_up` `[Eh, D, F]` and `w_down` `[Eh, F, D]`."""
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


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (KDA and
    latent projections, feed-forward, router, experts, head; not the
    embedding table, a gather, nor the convolution taps, the norms, the
    decays and the selection bias) rounded per output channel to int8
    and handed back in the weights' own dtype.  Jittable; the routed
    experts are drawn and rounded a block at a time.  `correct` has to
    come out false with these.  Side effect, when traced: the bank of
    sound experts that `program_params` made last is DELETED.  (The
    cell's second control, the recurrent state kept in bf16, is the
    file's `precision.recurrent_state` set to `bfloat16`: the judge's
    audit of the state, `served_state` below, refuses it.)"""
    from families.latent_moe_decoder import _round_int8   # the sibling's

    ref = _reference()
    while _SOUND_BANK:
        _SOUND_BANK.pop().delete()
    layers = []
    for w in weights["layers"]:
        p = {k: (_round_int8(v) if v.ndim == 2 and not k.startswith("conv_")
                 else v) for k, v in w.items() if k != "experts"}
        if "experts" in w:
            blocks = ref.map_expert_blocks(
                lambda b: tuple(_round_int8(x) for x in b), w["experts"])
            p.update((k, b.reshape((-1,) + b.shape[2:]))
                     for k, b in zip(ref.EXPERT_KEYS, blocks))
        layers.append(p)
    return dict(weights, layers=layers,
                lm_head=_round_int8(weights["lm_head"]))


# The sizes at which the judge audits the precision of the recurrent
# state (`reference/kda_hybrid_decoder.py::state_shortfall`): laid over
# the configuration's own file, so the layer kinds, the expert share's
# shape and above all `precision` stay the file's.
AUDIT_SIZES = {
    "hidden_size": 64, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_experts": 2, "num_experts_per_token": 2, "vocab_size": 512,
    "num_hidden_layers": 5,
    "linear_attn_config": {"num_heads": 4, "head_dim": 16},
    "deployment": {"num_experts": 8}}
AUDIT_ENGINE = {"num_slots": 1, "max_seq_len": 256,
                "prefill_buckets": (32, 64), "kv_block_size": 16,
                "num_kv_blocks": 16, "decode_block": 1,
                "prefix_cache": False}


def audit_config(c: Mapping) -> dict:
    """The configuration `c` at `AUDIT_SIZES`."""
    out = dict(c)
    for k, v in AUDIT_SIZES.items():
        out[k] = dict(c.get(k, {}), **v) if isinstance(v, dict) else v
    return out


def served_state(c: Mapping, weights, prompt, max_tokens: int):
    """What the program's ENGINE holds as one request's recurrent state
    once it has served it: `LLMEngine` at `AUDIT_ENGINE` with the
    program's model as `model_config` builds it from `c` (so with the
    state kept as `c` states it), float32 weights and compute at the
    highest matmul precision; the prompt goes in as chunks of the top
    bucket with the state handed on in the slot, the answer a tick a
    token.  Returns (the served tokens, S [KDA layers, H, dk, dv]
    float32): the state has seen the prompt and every served token but
    the last."""
    import jax
    import numpy as np

    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    mc = model_config(c, max_seq_len=AUDIT_ENGINE["max_seq_len"],
                      compute_dtype="float32", param_dtype="float32")
    with jax.default_matmul_precision("highest"):
        engine = LLMEngine(program_params(weights), mc,
                           EngineConfig(**AUDIT_ENGINE), rng_seed=0)
        handle = engine.submit(Request(
            prompt=list(prompt), max_tokens=max_tokens, temperature=0.0,
            chunked_prefill=len(prompt) > max(
                AUDIT_ENGINE["prefill_buckets"])))
        while engine.has_work():
            engine.step()
    assert handle.finish_reason == "length", handle.finish_reason
    return list(handle.tokens), np.asarray(engine.slot_state(0)["S"],
                                           np.float32)
