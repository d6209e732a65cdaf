"""Sharding rules for model parameter pytrees.

DP / FSDP / TP / (SP, PP) are mesh-axis annotations over one pjit'd program —
not separate engines (the core TPU-first design decision; contrast the
reference, which delegates TP/PP/FSDP to user libraries — SURVEY §2.7).

GSPMD then derives the collectives: batch sharded over (data, fsdp) gives
gradient psum; params sharded over fsdp gives ZeRO-style all-gather /
reduce-scatter; tensor-axis shards give Megatron-style allreduce — all over
ICI.
"""

from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from ray_tpu.models.llama import LlamaConfig
from ray_tpu.parallel.mesh import mesh_axis_size


def _ax(mesh, name: str) -> Optional[str]:
    """Axis name if present in the mesh with size > 1, else None (replicate)."""
    return name if mesh_axis_size(mesh, name) > 1 else None


def llama_param_specs(config: LlamaConfig, mesh) -> Dict[str, Any]:
    """PartitionSpecs for the stacked Llama param tree.

    Megatron layout on the ``tensor`` axis (attention heads + ffn hidden),
    ZeRO-style on ``fsdp`` (the model dim), replication elsewhere.
    """
    fsdp = _ax(mesh, "fsdp")
    tp = _ax(mesh, "tensor")
    if tp is not None and config.n_kv_heads % mesh_axis_size(mesh, "tensor"):
        raise ValueError(
            f"tensor axis ({mesh_axis_size(mesh, 'tensor')}) must divide "
            f"n_kv_heads ({config.n_kv_heads})")
    ep = _ax(mesh, "expert")
    if ep is not None and config.n_experts \
            and config.n_experts % mesh_axis_size(mesh, "expert"):
        raise ValueError(
            f"expert axis ({mesh_axis_size(mesh, 'expert')}) must divide "
            f"n_experts ({config.n_experts})")
    if config.n_experts:
        # MoE FFN: experts over the "expert" axis (EP), expert-internal
        # dims over tp/fsdp as usual; router tiny -> replicated.
        ffn_specs = {
            "router": P(None, None, None),
            "w_gate": P(None, ep, fsdp, tp),
            "w_up": P(None, ep, fsdp, tp),
            "w_down": P(None, ep, tp, fsdp),
        }
    else:
        ffn_specs = {
            "w_gate": P(None, fsdp, tp),
            "w_up": P(None, fsdp, tp),
            "w_down": P(None, tp, fsdp),
        }
    specs = {
        "embed": P(tp, fsdp),
        "layers": {
            "attn_norm": P(None, None),
            "wq": P(None, fsdp, tp),
            "wk": P(None, fsdp, tp),
            "wv": P(None, fsdp, tp),
            "wo": P(None, tp, fsdp),
            "ffn_norm": P(None, None),
            **ffn_specs,
        },
        "norm_f": P(None),
    }
    if not config.tie_embeddings:
        specs["lm_head"] = P(fsdp, tp)
    return specs


def llama_param_shardings(config: LlamaConfig, mesh) -> Dict[str, Any]:
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        llama_param_specs(config, mesh),
                        is_leaf=lambda x: isinstance(x, P))


def batch_spec(mesh) -> P:
    """Global batch sharded over every data-like axis present."""
    axes = [a for a in ("data", "fsdp") if mesh_axis_size(mesh, a) > 1]
    if not axes:
        return P()
    return P(tuple(axes))


def batch_sharding(mesh) -> NamedSharding:
    return NamedSharding(mesh, batch_spec(mesh))


def shard_params(params, shardings):
    """Place (or re-place) a param tree onto its shardings."""
    return jax.tree.map(jax.device_put, params, shardings)


def replicated(mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def sharded_flash_attention(mesh):
    """Flash attention callable for a GSPMD-partitioned step on `mesh`.

    The compiler cannot partition a Mosaic kernel by itself ("Mosaic
    kernels cannot be automatically partitioned"), so on a mesh of more
    than one device the kernel runs under `shard_map`: attention is
    independent across the batch (the data-like axes) and across heads
    (the ``tensor`` axis), which is exactly how the surrounding program
    shards q, k and v. Plug into ``forward(attn_impl=...)`` /
    ``loss_fn(attn_impl=...)``."""
    from ray_tpu.ops.attention import flash_attention

    batch_axes = tuple(batch_spec(mesh)) or (None,)
    spec = P(*batch_axes, None, _ax(mesh, "tensor"), None)

    def attn(q, k, v, causal=True):
        return jax.shard_map(
            lambda q, k, v: flash_attention(q, k, v, causal),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False)(q, k, v)

    return attn


def context_parallel_attention(mesh, seq_axis: str = "seq",
                               impl: str = "ring"):
    """Attention callable for context-parallel training (SURVEY §7 M11):
    plug into ``LlamaConfig(attn_impl=...)`` / ``forward(attn_impl=...)``
    and the model's attention runs sequence-parallel over
    ``mesh[seq_axis]``. ``impl="ring"`` rotates KV blocks via ppermute;
    ``impl="ulysses"`` all-to-alls into head-sharded full-sequence
    attention (exact, head-count-capped parallelism).
    """
    if impl == "ulysses":
        from ray_tpu.ops.ulysses import ulysses_attention_global as _global
    elif impl == "ring":
        from ray_tpu.ops.ring_attention import (
            ring_attention_global as _global)
    else:
        raise ValueError(f"impl={impl!r}: expected 'ring' or 'ulysses'")

    def attn(q, k, v, causal=True, positions=None):
        return _global(q, k, v, mesh, causal=causal, seq_axis=seq_axis)

    return attn
