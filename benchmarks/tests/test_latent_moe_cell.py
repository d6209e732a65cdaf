"""The second family's files: its counts against numbers worked by hand
(ISSUE 26), its traffic mix through `test_traffic.py`'s checks, the
scope-path helper, and a CPU `--rehearse` of its cell end to end."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_latent_moe as K
import scope_paths as SP
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cfg():
    with open(os.path.join(BENCH, "configs",
                           "kanana-2-30b-a3b-serve.json")) as f:
        return json.load(f)


def test_counts_against_hand_numbers():
    c = cfg()
    # q 2048*6144, kv_a 2048*576, kv_b 512*8192, o 4096*2048
    attn = 12_582_912 + 1_179_648 + 4_194_304 + 8_388_608
    assert K.attention_params(c) == attn == 26_345_472
    routed = 128 * 3 * 2048 * 768                       # 603.98 M
    shared = 3 * 2048 * 1536                            # 9.44 M
    router = 2048 * 128                                 # 0.26 M
    assert K.expert_layer_params(c) == routed + shared + router + attn
    assert round(K.expert_layer_params(c) / 1e6, 1) == 640.0
    assert K.dense_layer_params(c) == 3 * 2048 * 6144 + attn   # 64.1 M
    assert K.vocab_params(c) == 2 * 128256 * 2048
    assert c["num_hidden_layers"] == 8
    assert round(K.total_params(c) / 1e6) == 5070       # "5,069 M", 10.14 GB
    assert K.latent_bytes_per_token(c) == 9216          # 8 layers x 1152 B
    assert K.per_head_kv_bytes_per_token(c) == 8 * 20480
    assert K.per_head_kv_bytes_per_token(c) / K.latent_bytes_per_token(c) \
        == pytest.approx(17.8, abs=0.03)
    assert K.expert_bytes(c) == 9_437_184
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_but_for_depth():
    """Every key of the catalog's `config` under the same key with the
    same value; `num_hidden_layers` alone is reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "kanana-2-30b-a3b-instruct-2601")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(c["reduced"])


@pytest.mark.parametrize("rate", [2.0, 4.0])
def test_assistant_mix(rate):
    check_schedule("assistant", rate, 128, 3584, 32, 512)
    m = traffic.load("assistant")
    lens = sorted(len(r.prompt) for r in traffic.schedule(
        m, rate, 60.0, 5, 128256))
    assert 900 < np.median(lens) < 1150                  # median 1024


def test_scope_paths():
    name = "jit(f)/jit(main)/while/body/moe/experts/ragged_dot_general"
    assert SP.under(name, ("moe",)) and SP.under(name, ("moe", "experts"))
    assert not SP.under(name, ("experts", "moe"))
    assert not SP.under(name, ("moe", "shared"))
    assert SP.under("a/transpose(jvp(moe))/router/x", ("moe", "router"))
    # what the compiler renames stands where the program put it
    assert SP.under("ragged-dot-none", ("moe", "experts"))
    assert SP.under("sort", ("moe",)) and not SP.under("sort", ("attn",))
    ops = [("jit(t)/moe/experts/a", 100, 50), ("jit(t)/moe/shared/b", 150, 30),
           ("jit(t)/attn/c", 180, 20), ("jit(u)/moe/experts/d", 500, 40)]

    class Trace:                 # one program run covering the first three
        pass
    run = {"named_ops": ops, "window": (0, 1000), "trace": None,
           "records": {}}
    orig = SP.PS.program_runs
    SP.PS.program_runs = lambda tr, prog, win: [("jit_t(1)", 90, 120)]
    try:
        assert SP.program_seconds(run, "jit_t", "moe") == (80e-9, 120e-9, 1)
        assert SP.program_seconds(run, "jit_t", "moe", "experts")[0] == 50e-9
        assert SP.program_seconds(run, "jit_t", "nothing") is None
    finally:
        SP.PS.program_runs = orig
    assert SP.counters(run) is None
    assert SP.program_seconds({"trace": None, "window": None}, "jit_t",
                              "moe") is None


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope or counter (the parent
    commit), each new reader returns None and does not raise."""
    import importlib.util

    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": {"hbm_bytes_per_s": 819e9}}
    for name in ("tick_moe_share", "moe_expert_hbm_share",
                 "tick_latent_attn_share", "expert_load_max_over_mean"):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "layer_metrics", name + ".py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read(dict(run)) is None


def test_family_refuses_what_the_block_lacks():
    from families import latent_moe_decoder as F

    with pytest.raises(ValueError, match="q_lora_rank"):
        F.model_config(dict(cfg(), q_lora_rank=1536), max_seq_len=64,
                       compute_dtype="bfloat16", param_dtype="bfloat16")


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "assistant-decode-moe", "--seed", "2500000123", "--seconds", "5",
         "--trace", "1", "--rehearse"],
        capture_output=True, text=True, env=env, timeout=900)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    assert "expert_load_max_over_mean" in line["metrics"]
    assert "cache_misses" in line["metrics"]
