"""The plain reference against the program's `forward` and trainer at a
tiny size on the CPU.

Tolerances and their reasons
----------------------------
* float32 against float32 on the CPU, both at `highest`: the two differ
  only in the order of float32 sums (blocked attention, fused loss), so
  logits agree to 2e-5 and losses to 1e-5 relative.
* On the chip the served tokens are judged by `mean_deficit`: the mean,
  over every served token of the check's two samples (one served on the
  idle engine before the window, one served inside the window under a
  full pool), of how far the token's reference logit lies under the
  reference maximum given the served prefix.  It is 0 where the program
  chose the reference's token and small where bf16 rounding flipped a
  near tie.  The limit in each serve cell's file lies between the
  largest value sound bf16 runs gave and the smallest the int8
  weight-only control gave: for `chat-decode` 0.00286 over 9 seeds (the
  idle sample alone 0.00296 over 24) and 0.01090 over 3, limit 0.006
  (chip runs of PR 23; PERF.md section 2).
* Training is judged by the relative difference of the first reported
  losses against the reference's AdamW trajectory, and by the bytes of
  state per parameter (4 + 8 for float32 parameters and moments): bf16
  parameters fail both: sound runs read 0.0023-0.0128 on `pretrain-1chip`
  over 14 seeds, bf16 parameters 1.01-1.35 over 3 (limit 0.05), and the
  ratio of state bytes is 0.5 exactly (chip runs of PR 23).
"""
import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from reference import dense_decoder as R
from families import dense_decoder as F

C = dict(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
         intermediate_size=128, vocab_size=256, num_hidden_layers=2,
         rms_norm_eps=1e-5, rope_theta=1e6, head_dim=16,
         tie_word_embeddings=False, sliding_window=None)


def test_forward_matches_program():
    from ray_tpu.models.llama import forward

    mc = F.model_config(C, max_seq_len=1024, compute_dtype="float32",
                        param_dtype="float32")
    w = R.init_weights(C, 7, jnp.float32)
    toks = np.random.RandomState(0).randint(0, 256, (700,))
    with jax.default_matmul_precision("highest"):
        want = forward(F.program_params(w), jnp.asarray(toks)[None], mc)[0]
    got = R.logits_for_positions(w, C, list(toks), 650, 50)
    assert float(jnp.max(jnp.abs(want[650:700] - got))) < 2e-5


def test_deficits_zero_for_reference_choice_and_positive_otherwise():
    w = R.init_weights(C, 3, jnp.float32)
    prompt = list(np.random.RandomState(1).randint(0, 256, 40))
    seq = list(prompt)
    served = []
    for _ in range(4):
        lg = R.logits_for_positions(w, C, seq, len(seq) - 1, 1)
        served.append(int(jnp.argmax(lg[0])))
        seq.append(served[-1])
    assert np.all(R.served_token_deficits(w, C, prompt, served) == 0)
    wrong = [(t + 1) % 256 for t in served]
    assert np.all(R.served_token_deficits(w, C, prompt, wrong[:1]) > 0)


def test_trainer_start_and_trajectory_match_program():
    from ray_tpu.models.llama import init_params, loss_fn

    mc = F.model_config(C, max_seq_len=64, compute_dtype="float32",
                        param_dtype="float32")
    ours = R.init_as_trainer(C, 5)
    theirs = init_params(mc, jax.random.key(5))
    assert jax.tree.structure(ours) == jax.tree.structure(theirs)
    for a, b in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        assert float(jnp.max(jnp.abs(a - b))) < 1e-7     # one ulp under jit
    tok = jnp.asarray(np.random.RandomState(5).randint(0, 256, (2, 65)),
                      jnp.int32)
    got = R.adamw_trajectory(C, ours, tok, 2, q_block=32, t_block=64)
    opt = optax.adamw(1e-3)
    st, want = opt.init(theirs), []
    for _ in range(3):
        with jax.default_matmul_precision("highest"):
            l, g = jax.value_and_grad(
                lambda p: loss_fn(p, {"tokens": tok}, mc, fused=False))(theirs)
        want.append(float(l))
        u, st = opt.update(g, st, theirs)
        theirs = optax.apply_updates(theirs, u)
    assert got["losses"] == pytest.approx(want, rel=1e-5)
