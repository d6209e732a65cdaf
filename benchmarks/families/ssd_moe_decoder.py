"""Family `ssd_moe_decoder`: every layer ONE mixer by a published pattern
string: Mamba-2 (state-space duality) layers whose state the engine
keeps by slot, non-gated relu² experts of which this chip holds a share
beside a shared one, grouped-query attention without positions; untied
head (`model_type` `nemotron_h`).  Builds the PROGRAM's model config
from a configuration file (Hugging Face key names, and `deployment` for
the share) and names the plain reference that judges it.
"""

from __future__ import annotations

from typing import Any, Mapping

REFERENCE = "ssd_moe_decoder"    # benchmarks/reference/<this>.py

_DTYPES = {"bfloat16": "bfloat16", "float32": "float32"}


def _reference():
    # `benchmarks/` is on sys.path wherever a family is loaded (run.py,
    # the tests' conftest)
    from reference import ssd_moe_decoder

    return ssd_moe_decoder


def model_config(c: Mapping, *, max_seq_len: int, compute_dtype: str,
                 param_dtype: str, **overrides: Any):
    """The program's `NemotronHConfig` at this configuration's sizes and
    share.  Refuses what the program does not compute."""
    import jax.numpy as jnp

    from ray_tpu.models.nemotron_h import NemotronHConfig

    dep = c.get("deployment", {})
    pattern = c["hybrid_override_pattern"]
    state = c.get("precision", {}).get("recurrent_state", "float32")
    refused = {
        f"a pattern of {len(pattern)} layers at num_hidden_layers "
        f"{c['num_hidden_layers']}": len(pattern) != c["num_hidden_layers"],
        "a dense feed-forward layer ('-' in the pattern)": "-" in pattern,
        "n_group / topk_group":
            (c.get("n_group", 1), c.get("topk_group", 1)) != (1, 1),
        "norm_topk_prob false": not c.get("norm_topk_prob", True),
        "mlp_hidden_act " + str(c.get("mlp_hidden_act")):
            c.get("mlp_hidden_act", "relu2") != "relu2",
        "mamba_hidden_act " + str(c.get("mamba_hidden_act")):
            c.get("mamba_hidden_act", "silu") != "silu",
        "a bias on a projection": any(c.get(k) for k in (
            "use_bias", "mlp_bias", "attention_bias", "mamba_proj_bias")),
        "a convolution without its bias": not c.get("use_conv_bias", True),
        "residual_in_fp32": bool(c.get("residual_in_fp32")),
        "tie_word_embeddings": bool(c.get("tie_word_embeddings")),
        "a sliding window": c.get("sliding_window") is not None,
        "norm_eps other than layer_norm_epsilon":
            c.get("norm_eps", c["layer_norm_epsilon"])
            != c["layer_norm_epsilon"],
        "a chunk_size other than ops.ssd.CHUNK (128)":
            c.get("chunk_size", 128) != 128,
        "a recurrent state kept in " + state: state != "float32",
    }
    if any(refused.values()):
        raise ValueError("the program's Mamba-2/expert/attention decoder "
                         "has no "
                         + ", ".join(k for k, v in refused.items() if v))
    published = dep.get("n_routed_experts", c["n_routed_experts"])
    return NemotronHConfig(
        vocab_size=c["vocab_size"], dim=c["hidden_size"], pattern=pattern,
        ssm_heads=c["mamba_num_heads"], ssm_head_dim=c["mamba_head_dim"],
        ssm_groups=c["n_groups"], ssm_state=c["ssm_state_size"],
        conv_size=c["conv_kernel"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], head_dim=c["head_dim"],
        expert_hidden_dim=c["moe_intermediate_size"],
        shared_hidden_dim=c["n_shared_experts"]
        * c["moe_shared_expert_intermediate_size"],
        n_experts=published, top_k=c["num_experts_per_tok"],
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        max_seq_len=max_seq_len, norm_eps=float(c["layer_norm_epsilon"]),
        state_dtype=getattr(jnp, _DTYPES[state]),
        expert_rank=dep.get("rank", 0),
        expert_shards=published // c["n_routed_experts"],
        dtype=getattr(jnp, _DTYPES[compute_dtype]),
        param_dtype=getattr(jnp, _DTYPES[param_dtype]), **overrides)


# The routed experts `program_params` last made; see the sibling family
# `latent_moe_decoder`: a control gives the sound bank back before it
# draws its own, because two do not fit one chip.
_SOUND_BANK = []


def _with_banks(weights, bank_of):
    """The program's tree: every expert layer's `experts` record
    replaced by `bank_of(record)`, a stacked block's a repeat at a time."""
    import jax
    import jax.numpy as jnp

    def one(w, stacked):
        p = {k: v for k, v in w.items() if k != "experts"}
        if "experts" in w:
            ex = w["experts"]
            if stacked:
                banks = [bank_of(jax.tree.map(lambda a: a[r], ex))
                         for r in range(ex["keys"].shape[0])]
                p.update({k: jnp.stack([b[k] for b in banks])
                          for k in banks[0]})
            else:
                p.update(bank_of(ex))
        return p

    return dict(weights, blocks=[one(w, True) for w in weights["blocks"]],
                tail=[one(w, False) for w in weights["tail"]])


def program_params(weights):
    """The reference keeps, for each layer's held experts, what to draw
    them from; the program holds them: the same draws, made once, as
    `w_up` and `w_down` `[Eh, F, D]`.  Everything else is the SAME
    buffers under the same names."""
    ref = _reference()
    del _SOUND_BANK[:]
    out = _with_banks(weights, ref.expert_bank)
    for w in out["blocks"] + out["tail"]:
        _SOUND_BANK.extend(w[k] for k in ref.EXPERT_KEYS if k in w)
    return out


def _round_int8(w, axis=-2):
    """Symmetric int8 per output channel (`axis` is the INPUT channels'),
    handed back in w's dtype."""
    import jax.numpy as jnp

    w32 = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(w32), axis=axis, keepdims=True)
                        / 127.0, 1e-8)
    return (jnp.clip(jnp.round(w32 / scale), -127, 127)
            * scale).astype(w.dtype)


def lower_precision_params(weights):
    """The control for a serving cell: every matmul weight (the Mamba-2
    and attention projections, the router, the shared and the routed
    experts, the head; not the embedding table, a gather, nor the
    convolution taps, the norms, the biases and the decays) rounded per
    output channel to int8 and handed back in the weights' own dtype.
    Jittable; the routed experts are drawn and rounded a block at a
    time (`w_up` lies [F, D]: its output channels are its rows).
    `correct` has to come out false with these.  Side effect, when
    traced: the bank of sound experts that `program_params` made last
    is DELETED."""
    ref = _reference()
    while _SOUND_BANK:
        _SOUND_BANK.pop().delete()

    def bank_of(experts):
        blocks = ref.map_expert_blocks(
            lambda b: (_round_int8(b[0], -1), _round_int8(b[1], -2)),
            experts)
        return {k: b.reshape((-1,) + b.shape[2:])
                for k, b in zip(ref.EXPERT_KEYS, blocks)}

    def matrices(w):     # `w..` and the router; not `conv_w`, `experts`
        return {k: _round_int8(v) if k.startswith("w") or k == "router"
                else v for k, v in w.items()}

    out = _with_banks(
        dict(weights, blocks=[matrices(w) for w in weights["blocks"]],
             tail=[matrices(w) for w in weights["tail"]]), bank_of)
    return dict(out, lm_head=_round_int8(weights["lm_head"]))
