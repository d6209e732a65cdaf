"""The seventh family's files: its counts against the issue's table
(ISSUE 44), its configuration against the catalog row, its traffic mix
through `test_traffic.py`'s checks, the family's model config, the new
readers on a run without their sources and on a made-up trace, and a
CPU `--rehearse` of its cell end to end."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_shortcut_moe as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "longform-decode-zero-moe"
CONFIG = "longcat-flash-chat-serve"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def cfg():
    with open(os.path.join(BENCH, "configs", CONFIG + ".json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_counts_reproduce_the_issues_table():
    c = cfg()
    # q_a 6144*1536, q_b 1536*12288, kv_a 6144*576, kv_b 512*16384,
    # o 8192*6144, the two low-rank norms
    mla = 9_437_184 + 18_874_368 + 3_538_944 + 8_388_608 + 50_331_648 + 2048
    assert K.mla_params(c) == mla == 90_572_800              # 0.181 GB
    assert K.dense_ffn_params(c) == 3 * 6144 * 12288 == 226_492_416
    assert K.router_width(c) == 768
    assert K.router_params(c) == 6144 * 768 + 768 == 4_719_360
    assert K.layer_params_without_experts(c) == 638_874_368  # 1.278 GB
    assert K.expert_params(c) == 3 * 6144 * 2048 == 37_748_736
    assert K.expert_bytes(c) == 75_497_472                   # 75.5 MB
    assert K.layer_params(c) == 1_242_854_144                # 2.486 GB
    assert K.vocab_params(c) == 2 * 16384 * 6144 + 6144 == 201_332_736
    assert K.total_params(c) == 5_172_749_312
    assert round(K.total_params(c) * 2 / 1e9, 2) == 10.35
    assert round(K.total_params(c) * 2 / 2 ** 30, 2) == 9.63     # GiB
    # the published model: 560.66 B in all, 27.15 B a token at a mean of
    # 8 real experts ("560B-A27B")
    whole = 28 * (638_874_368 + 512 * 37_748_736) + 2 * 131072 * 6144
    assert round(whole / 1e9, 2) == 560.66
    assert round(K.active_params_per_token(c, 8) / 1e9, 2) == 27.15
    # the cache: a 640-value row a token a SUBLAYER, 8 pool layers
    assert (K.latent_sublayers(c), K.cache_row_bytes(c)) == (8, 1280)
    assert K.latent_bytes_per_token(c) == 10_240
    # 12,288 blocks of 16 rows: 2.01 GB
    assert round(12288 * 16 * K.latent_bytes_per_token(c) / 1e9, 2) == 2.01
    # the paged kernel, a cached row a sublayer: 64 heads x (640 + 512)
    # multiply-adds = 147 kFLOP against 1,280 bytes, 115 FLOP a byte
    assert K.paged_latent_flops_per_row(c) == 147_456
    assert round(147_456 / 1280) == 115
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_but_for_its_three_cuts():
    """Every key of the catalog's `config` under the same key with the
    same value; depth, experts held and vocabulary alone are reduced,
    and the file states the published counts beside them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "LongCat-Flash-Chat")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differs == {"num_layers", "n_routed_experts", "vocab_size"} \
        == set(c["reduced"])
    dep = c["deployment"]
    assert (dep["n_routed_experts"], dep["vocab_size"], dep["num_layers"]) \
        == tuple(row["config"][k] for k in
                 ("n_routed_experts", "vocab_size", "num_layers"))
    assert dep["chips_per_layer"] * c["n_routed_experts"] \
        == dep["n_routed_experts"]
    assert dep["pipeline_stages"] * c["num_layers"] == dep["num_layers"]
    assert c["vocab_size"] * 8 == dep["vocab_size"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == CONFIG)
    assert set(entry["reduced"]) == set(c["reduced"])
    assert entry["source"] == c["source"]


def test_family_builds_the_config_and_refuses_what_the_block_lacks():
    import jax.numpy as jnp

    from families import shortcut_moe_decoder as F

    c = cfg()
    kw = dict(max_seq_len=5120, compute_dtype="bfloat16",
              param_dtype="bfloat16")
    mc = F.model_config(c, **kw)
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.n_heads) \
        == (4, 6144, 16384, 64)
    assert (mc.q_lora_rank, mc.kv_lora_rank, mc.scale_lora,
            mc.cache_row) == (1536, 512, True, 640)
    assert (mc.n_experts, mc.n_held_experts, mc.n_zero_experts,
            mc.router_width, mc.expert_rank, mc.expert_shards, mc.top_k,
            mc.routed_scaling_factor) == (512, 16, 256, 768, 0, 32, 12, 6.0)
    assert mc.dtype == jnp.bfloat16 and mc.rope_theta == 1e7
    fns = mc.serving()
    assert fns.init_slot_state is None and fns.window_kind is None
    for change, named in ((dict(mla_scale_kv_lora=False), "low-rank"),
                          (dict(zero_expert_type="copy"), "identity"),
                          (dict(q_lora_rank=None), "q_lora_rank"),
                          (dict(norm_topk_prob=True), "norm_topk_prob")):
        with pytest.raises(ValueError, match=named):
            F.model_config(dict(c, **change), **kw)


@pytest.mark.parametrize("rate", [3.0, 6.5])
def test_longform_mix(rate):
    check_schedule("longform", rate, 32, 3072, 64, 2048)
    m = traffic.load("longform")
    reqs = traffic.schedule(m, rate, 60.0, 5, 16384)
    lens = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.max_tokens for r in reqs)
    assert 330 < np.median(lens) < 440                   # median 384
    assert 450 < np.median(outs) < 580                   # median 512
    assert np.mean(outs) > np.mean(lens)                 # decode-heavy
    assert sum(n > 1024 for n in lens) >= len(lens) // 10    # chunked


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["num_kv_blocks"], e["decode_block"], e["prefix_cache"]) \
        == (128, 5120, 16, 12288, 1, True)
    assert cell["warm_start"] == round(cell["rate_per_s"] * 10)
    assert (cell["preroll_s"], cell["drain_s"]) == (15.0, 60.0)
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"]) == (32, 32, 8)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "longform", 1)
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) | {"cache_misses", "tick_moe_share",
                               "tick_latent_attn_share"} <= listed
    # the two accepted readers that cannot read this configuration
    assert not {"latent_attn_roofline", "moe_expert_hbm_share"} & listed
    assert CELL in next(m for m in declared["end_to_end"]
                        if m["name"] == "gap_mean_ms")["workloads"]


NEW_READERS = ("sc_latent_attn_roofline", "sc_expert_hbm_share",
               "zero_pick_share", "tick_dense_share")


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope, span argument or
    counter (the parent commit; another family's cell), each new reader
    returns None and does not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": PEAKS}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None
    with open(os.path.join(BENCH, "configs",
                           "kanana-2-30b-a3b-serve.json")) as f:
        other = json.load(f)
    for name in NEW_READERS:
        assert reader(name).read(dict(run, config=other)) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two ticks inside the traced window and one before it: the scopes'
    seconds, the rows of the interval's dispatches and the counters at
    the interval's two ends come out as worked by hand."""
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(t)/attn/a", 10 * ms, 2 * ms),            # tick 1
           ("jit(t)/attn/paged/b", 12 * ms, 1 * ms),
           ("jit(t)/mlp/c", 13 * ms, 3 * ms),
           ("jit(t)/moe/router/d", 16 * ms, 1 * ms),
           ("jit(t)/moe/experts/e", 17 * ms, 2 * ms),
           ("jit(t)/moe/zero/f", 19 * ms, 1 * ms),
           ("jit(t)/attn/paged/g", 40 * ms, 1 * ms),      # tick 2
           ("jit(t)/mlp/h", 41 * ms, 3 * ms),
           ("jit(t)/moe/experts/i", 44 * ms, 2 * ms)]
    runs = [("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [("llm_engine.tick_dispatch", 9 * ms, 1000,
              {"live": "70", "rows": "60000"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000,
              {"live": "74", "rows": "64000"}),
             # a tick before the traced interval, busier: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000,
              {"live": "128", "rows": "200000"}),
             ("llm_engine.emit", -2 * ms, 1000,
              {"ticks": "90", "experts_touched": "3000"}),
             ("llm_engine.emit", 8 * ms, 1000,
              {"ticks": "100", "experts_touched": "4000"}),
             ("llm_engine.emit", 22 * ms, 1000,
              {"ticks": "101", "experts_touched": "4044"}),
             ("llm_engine.emit", 52 * ms, 1000,
              {"ticks": "102", "experts_touched": "4084"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])

    class Handle:
        class engine:
            @staticmethod
            def stats():
                return {"counters": {"zero_picks": 1000, "real_picks": 2000,
                                     "held_picks": 60, "ticks": 102,
                                     "experts_touched": 4084,
                                     "expert_tokens": np.ones((4, 16))}}

    class Rec:
        handle = Handle

    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": [Rec]}, "config": c, "peaks": PEAKS}
    assert reader("zero_pick_share").read(run) == pytest.approx(100 / 3)
    # mlp 6 ms + attn 4 ms less attn/paged 2 ms, of 20 ms
    assert reader("tick_dense_share").read(run) == pytest.approx(40.0)
    # (60,000 + 64,000) / 2 rows a tick x 2 ticks x 8 pool layers x
    # 1,280 B over 2 ms under attn/paged: the bytes bound it
    want = 100 * 62_000 * 2 * 8 * 1280 / 819e9 / 2e-3
    assert reader("sc_latent_attn_roofline").read(run) == pytest.approx(want)
    assert want < 100
    # between the first and the last emit INSIDE the window: 84 experts
    # in 2 ticks, laid on the 2 executions, over 4 ms under moe/experts
    want = 100 * 42 * 2 * 75_497_472 / 819e9 / 4e-3
    assert reader("sc_expert_hbm_share").read(run) == pytest.approx(want)
    # spans that carry no counters (a program before PR 44): nothing
    for sp in spans:
        sp[3].pop("experts_touched", None)
    assert reader("sc_expert_hbm_share").read(run) is None


def test_rehearsal_runs_the_cell_end_to_end():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "3000000123", "--seconds", "5", "--trace", "1",
         "--rehearse"],
        capture_output=True, text=True, env=env, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    assert "zero_pick_share" in line["metrics"]
    assert "expert_load_max_over_mean" in line["metrics"]
    assert "cache_misses" in line["metrics"]
