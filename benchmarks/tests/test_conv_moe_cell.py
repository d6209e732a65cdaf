"""The fourth family's files: its counts against numbers worked by hand
(ISSUE 32), its configuration against the catalog row, its traffic mix
through `test_traffic.py`'s checks, the family's model config, the new
readers on a run without their sources and on a made-up trace, and a
CPU `--rehearse` of its cell end to end, sound and control."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_conv_moe as K
import counts_latent_moe as KL
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "compose-decode-conv-moe"
CONFIG = "lfm2-8b-a1b-serve"


def cfg():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_counts_against_hand_numbers():
    c = cfg()
    # in_proj 2048*6144, taps 3*2048, out_proj 2048*2048
    assert K.conv_params(c) == 12_582_912 + 6_144 + 4_194_304 == 16_783_360
    # q and o 2 x 2048*2048, k and v 2 x 2048*512
    assert K.attention_params(c) == 8_388_608 + 2_097_152 == 10_485_760
    assert K.dense_half_params(c) == 3 * 2048 * 7168 == 44_040_192
    assert K.expert_params(c) == 3 * 2048 * 1792 == 11_010_048
    assert K.expert_bytes(c) == 22_020_096 == KL.expert_bytes(c)
    assert K.router_params(c) == 65_536
    assert K.expert_half_params(c) == 32 * 11_010_048 + 65_536
    assert round(32 * K.expert_bytes(c) / 1e9, 3) == 0.705   # a layer's bank
    assert K.layer_kinds(c) == ["conv", "conv"] \
        + ["full_attention", "conv", "conv", "conv"] * 3
    assert (K.n_conv_layers(c), K.n_attention_layers(c),
            K.n_expert_layers(c)) == (11, 3, 12)
    assert K.layer_params(c, 0) == 16_783_360 + 44_040_192
    assert K.layer_params(c, 2) == 10_485_760 + 352_387_072
    assert K.vocab_params(c) == 65536 * 2048 == 134_217_728     # tied: once
    assert K.total_params(c) == 4_667_017_216
    assert round(K.total_params(c) * 2 / 1e9, 2) == 9.33
    # the whole model, 24 layers: 8.34 B tied (the 8.3 B it is sold as)
    whole = dict(c, num_hidden_layers=24)
    assert round(K.total_params(whole) / 1e9, 2) == 8.34
    assert round((K.total_params(whole) + K.vocab_params(c)) / 1e9, 2) == 8.47
    assert round(K.active_params_per_token(whole) / 1e9, 2) == 1.56  # A1.5B
    # K and V of 8 heads of 64 in bf16: no padded lanes
    assert K.kv_row_bytes(c) == 2 * 8 * 64 * 2 == 2048
    assert K.kv_bytes_per_token(c) == 6144
    assert K.tail_bytes_per_slot(c) == 11 * 2 * 2048 * 2 == 90_112
    # every expert touched: 8.46 GB, 10.3 ms at 819 GB/s
    bank = 12 * 32 * K.expert_bytes(c)
    assert round(bank / 1e9, 2) == 8.46
    assert round(bank / 819e9 * 1e3, 1) == 10.3
    assert K.paged_attention_bytes(c, 1000.0) == 6_144_000
    assert K.tick_least_bytes(c, 0, 0.0, 12 * 32) == K.total_params(c) * 2
    assert K.tick_least_bytes(c, 200, 1e5, 12 * 32) \
        == K.total_params(c) * 2 + 1e5 * 6144 + 2 * 200 * 90_112
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_but_for_its_depth():
    """Every key of the catalog's `config` under the same key with the
    same value (`layer_types` whole); the depth alone is reduced."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LFM2-8B-A1B")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(c["reduced"])
    assert {"tie_word_embeddings", "head_dim", "initializer_range",
            "router_bias_scale"} <= set(c["assumed"])
    assert c["head_dim"] * c["num_attention_heads"] == c["hidden_size"]
    assert c["deployment"]["pipeline_stages"] == 2
    assert c["precision"]["router"] == "float32"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers"]
    assert entry["source"] == c["source"]


def test_family_builds_the_config():
    import jax.numpy as jnp

    from families import conv_moe_decoder as F

    c = cfg()
    mc = F.model_config(c, max_seq_len=4096, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.n_dense_layers) \
        == (14, 2048, 65536, 2)
    assert mc.attn_layers == (2, 6, 10)
    assert (mc.n_conv_layers, mc.n_attn_layers, mc.n_moe_layers) \
        == (11, 3, 12)
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.conv_size) \
        == (32, 8, 64, 3)
    assert (mc.n_experts, mc.top_k, mc.expert_hidden_dim,
            mc.dense_hidden_dim) == (32, 4, 1792, 7168)
    assert mc.dtype == jnp.bfloat16 and mc.norm_eps == 1e-5
    model = mc.serving()
    assert model.init_slot_state is not None and model.paged_attention
    for key, bad in (("conv_bias", True), ("norm_topk_prob", False)):
        with pytest.raises(ValueError, match=key):
            F.model_config(dict(c, **{key: bad}), max_seq_len=64,
                           compute_dtype="bfloat16", param_dtype="bfloat16")
    with pytest.raises(ValueError, match="neither"):
        F.model_config(dict(c, layer_types=["conv", "mamba"] * 12),
                       max_seq_len=64, compute_dtype="bfloat16",
                       param_dtype="bfloat16")


@pytest.mark.parametrize("rate", [6.0, 12.0])
def test_compose_mix(rate):
    check_schedule("compose", rate, 32, 3072, 64, 1536)
    m = traffic.load("compose")
    sched = traffic.schedule(m, rate, 60.0, 5, 65536)
    lens = sorted(len(r.prompt) for r in sched)
    outs = sorted(r.max_tokens for r in sched)
    assert 150 < np.median(lens) < 240                   # median 192
    assert 330 < np.median(outs) < 440                   # median 384
    assert np.mean(outs) > 1.3 * np.mean(lens)           # generation-heavy


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["num_kv_blocks"], e["decode_block"], e["prefix_cache"]) \
        == (256, 4096, 16, 16384, 1, False)
    assert e["prefill_buckets"] == [256, 512, 1024, 2048]
    assert cell["warm_start"] == round(cell["rate_per_s"] * 10)
    assert (cell["preroll_s"], cell["drain_s"]) == (15.0, 60.0)
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"]) == (32, 32, 8)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "compose", 1)
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"tick_conv_share", "paged_attn_roofline",
            "expert_rows_per_group", "gap_p50_ms", "decode_step_ms",
            "cache_misses", "admit_stall_ms", "insert_ms", "host_loop_ms",
            "idle_attributed_share", "tick_moe_share",
            "moe_expert_hbm_share", "expert_load_max_over_mean",
            "tick_kv_gather_share"} == listed
    gap = next(m for m in declared["end_to_end"]
               if m["name"] == "gap_mean_ms")
    assert CELL in gap["workloads"]


NEW_READERS = ("tick_conv_share", "paged_attn_roofline",
               "expert_rows_per_group")


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope, span argument or
    counter (the parent commit), each new reader returns None and does
    not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two ticks: the seconds under `conv` and `attn/paged` and the
    counted bytes come out as worked by hand."""
    import program_spans as PS

    ms = 1_000_000
    ops = [("jit(t)/conv/in_proj/a", 11 * ms, 1 * ms),      # tick 1
           ("jit(t)/conv/mix/b", 12 * ms, 1 * ms),
           ("jit(t)/attn/paged/paged_attention", 13 * ms, 1 * ms),
           ("jit(t)/attn/qk_norm/c", 14 * ms, 1 * ms),
           ("jit(t)/moe/experts/e", 15 * ms, 4 * ms),
           ("jit(t)/conv/out_proj/f", 41 * ms, 2 * ms),     # tick 2
           ("jit(t)/attn/paged/paged_attention", 43 * ms, 3 * ms)]
    runs = [("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [("llm_engine.tick_dispatch", 9 * ms, 1000,
              {"live": "150", "rows": "100000"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000,
              {"live": "170", "rows": "140000"}),
             # a tick before the traced interval: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000,
              {"live": "250", "rows": "900000"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(PS, "program_runs",
                        lambda trace, program, window: runs)

    class Handle:
        class engine:
            @staticmethod
            def stats():
                return {"counters": {
                    "ticks": 4, "experts_touched": 4 * 12 * 30,
                    "expert_tokens": np.full((12, 32), 75)}}

    class Rec:
        handle = Handle

    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": [Rec]}, "config": c,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    # conv 4 ms of 20 ms
    assert reader("tick_conv_share").read(run) == pytest.approx(20.0)
    # (100000 + 140000) / 2 rows a tick x 6144 B x 2 ticks over 4 ms
    want = 100 * 120_000 * 6144 * 2 / 819e9 / 4e-3
    assert reader("paged_attn_roofline").read(run) == pytest.approx(want)
    assert want < 100
    # 12 x 32 x 75 assignments over 1440 touched groups
    assert reader("expert_rows_per_group").read(run) == pytest.approx(20.0)


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000123", "--seconds", "5", "--trace", "1",
         "--rehearse", *extra],
        capture_output=True, text=True, env=env, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_runs_the_cell_end_to_end():
    line = _rehearse()
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    for name in ("expert_load_max_over_mean", "cache_misses",
                 "expert_rows_per_group"):
        assert name in line["metrics"], name


def test_rehearsal_control_is_not_correct():
    line = _rehearse("--control")
    assert line["rehearsal"] and not line["correct"]
