"""counts.py against numbers worked by hand for the configurations."""
import json
import os

import pytest

import counts

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


# by hand: wq 4096*4096, wk+wv 2*4096*1024, wo 4096*4096, ffn 3*4096*14336
LAYER = 16777216 + 8388608 + 16777216 + 176160768          # 218,103,808
HEAD = 4096 * 32768                                         # 134,217,728


@pytest.mark.parametrize("name,layers", [
    ("mistral-7b-v0.3-serve", 20), ("mistral-7b-v0.3-train-1chip", 2)])
def test_parameter_counts(name, layers):
    c = cfg(name)
    assert c["num_hidden_layers"] == layers
    assert counts.layer_matmul_params(c) == LAYER
    assert counts.matmul_params(c) == layers * LAYER + HEAD
    norms = (2 * layers + 1) * 4096
    assert counts.total_params(c) == 2 * HEAD + layers * LAYER + norms
    assert c["constants"] == counts.constants(c)


def test_hand_numbers():
    c2, c20 = cfg("mistral-7b-v0.3-train-1chip"), cfg("mistral-7b-v0.3-serve")
    assert counts.total_params(c2) == 704_663_552       # "704 M"
    assert counts.total_params(dict(c2, num_hidden_layers=6)) \
        == 1_577_111_552                                # "1.58 B" at 6 layers
    assert counts.kv_bytes_per_token(c20) == 81_920     # 80 KiB a token
    # one sequence of 4096: 6 * 4096 * (2*LAYER + HEAD) matmul operations
    # + 3 * 2 layers * (4*32*128) * 4096*4097/2 attention operations
    want = 6 * 4096 * (2 * LAYER + HEAD) + 3 * 2 * 16384 * (4096 * 4097 // 2)
    assert counts.train_step_flops(c2, 1, 4096) == pytest.approx(want, rel=1e-12)
    assert counts.train_flops_per_token(c2, 4096) == pytest.approx(3.62e9, rel=2e-3)
    # decode: weights once + the live rows only
    b = counts.decode_step_bytes(c20, [99, 999])
    assert b == (20 * LAYER + HEAD + 41 * 4096) * 2 + 2 * 4096 * 2 + 1100 * 81920


def test_flash_is_compute_bound_at_4096():
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    f = counts.flash_fwd_flops(32, 128, 1, 4096)
    assert f == 4 * 32 * 128 * 4096 * 4097 / 2
    t, bound = counts.least_time(f, counts.flash_fwd_bytes(32, 128, 1, 4096), peaks)
    assert bound == "flops" and t == pytest.approx(f / 197e12)
    assert counts.flash_bwd_dq_flops(32, 128, 1, 4096) == 1.5 * f
    assert counts.flash_bwd_dkv_flops(32, 128, 1, 4096) == 2.0 * f
