"""What the two files of Kimi-Linear tests share (`test_kimi_linear.py`:
the model's mathematics, no engine; `test_kimi_linear_engine.py`: the
cases that serve through `LLMEngine`): the tiny configuration, its
reference and parameters, the tokens, and the bf16 configuration at
which the decode tick's kernel path engages."""

import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

TOL = 5e-6
# two periods less one layer: KDA KDA KDA MLA KDA, layer 1 dense
C = dict(hidden_size=64, num_attention_heads=4, kv_lora_rank=32,
         qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
         intermediate_size=128, moe_intermediate_size=32,
         num_experts=2, num_experts_per_token=2, num_shared_experts=1,
         routed_scaling_factor=2.446, moe_renormalize=True,
         mla_use_nope=True, vocab_size=512, num_hidden_layers=5,
         first_k_dense_replace=1, rms_norm_eps=1e-5,
         router_bias_scale=0.1, initializer_range=0.02,
         linear_attn_config=dict(
             full_attn_layers=[4, 8], kda_layers=[1, 2, 3, 5, 6, 7],
             num_heads=4, head_dim=16, short_conv_kernel_size=4),
         deployment=dict(num_experts=8, rank=1),
         precision=dict(recurrent_state="float32"))
BS = 4            # rows a block
BUCKET = 16       # one prefill bucket


def _build(c, **overrides):
    from families import kda_hybrid_decoder as F
    from reference import kda_hybrid_decoder as R

    mc = F.model_config(c, max_seq_len=64, compute_dtype="float32",
                        param_dtype="float32", **overrides)
    weights = R.init_weights(c, 11, jnp.float32)
    return R, mc, weights, F.program_params(weights)


@pytest.fixture(scope="module")
def model():
    return _build(C)


def _tokens(n, seed=0):
    return [int(t) for t in np.random.RandomState(seed).randint(0, 512, n)]


def _reference_logits(R, weights, toks, start, n, c=C):
    return np.asarray(R.logits_for_positions(weights, c, toks, start, n,
                                             pad_to=64))


KERNEL_BS = 16    # rows a block: a whole packed tile, so the kernel engages


def _tiling():
    """bf16, a latent of one lane tile in a row of two (128 ‖ 8 ‖ zeros
    to 256): the shapes `ops.paged_attention.engages` asks for."""
    from ray_tpu.models import kimi_linear as KL

    c = KL.KimiLinearConfig.tiny(kv_lora_rank=128)
    assert c.cache_row == 256 and c.dtype == jnp.bfloat16
    return KL, c


def _drawn_at_a_tenth(KL, c, seed):
    """Matrices at 0.1, not `init_params`' 0.02: at 0.02 the tiny
    model's best two logits lie closer than bf16 rounding moves them
    and greedy tokens say nothing about the path."""
    return jax.tree.map(lambda x: 5 * x if x.ndim >= 2 else x,
                        KL.init_params(c, jax.random.key(seed)))
