"""The sixth family's files: its counts against numbers worked by hand
(ISSUE 40), its configuration against the catalog row, its traffic mix
through `test_traffic.py`'s checks, the family's model config, the new
readers on a run without their sources and on a made-up trace, and a
CPU `--rehearse` of its cell end to end, sound and with the control."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_gdn_hybrid as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "reason-decode-gdn-hybrid"
NAME = "olmo-hybrid-7b-serve"


def cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_counts_against_hand_numbers():
    c = cfg()
    # Wq, Wk 2 x 3840*2880; Wv, Wg, Wo 3 x 3840*5760; Wa, Wb 2 x 3840*30;
    # taps 4 x 11520; A_log + dt_bias + the output norm 30 + 30 + 192
    gdn = 2 * 11_059_200 + 3 * 22_118_400 + 2 * 115_200 + 46_080 + 252
    assert K.gdn_params(c) == gdn == 88_750_332
    assert K.attn_params(c) == 4 * 14_745_600 + 7_680 == 58_990_080
    assert K.ff_params(c) == 3 * 3840 * 11008 == 126_812_160
    assert K.layer_params(c, "linear_attention") == 215_570_172
    assert K.layer_params(c, "full_attention") == 185_809_920
    assert K.period_params(c) == 832_520_436
    assert K.vocab_params(c) == 2 * 100352 * 3840 == 770_703_360
    assert K.layer_kinds(c) == (["linear_attention"] * 3
                                + ["full_attention"]) * 2
    # held: two periods and the vocabulary, 2,435.7 M = 4.87 GB in bf16
    assert K.total_params(c) == 2 * 832_520_436 + 770_703_360 \
        == 2_435_744_232
    assert round(K.total_params(c) * 2 / 1e9, 2) == 4.87
    # whole: eight periods, 7.43 B
    assert K.published_params(c) == 8 * 832_520_436 + 770_703_360
    assert round(K.published_params(c) / 1e9, 2) == 7.43
    assert K.kv_bytes_per_token(c) == 2 * 2 * 30 * 128 * 2 == 30_720
    assert K.paged_attention_bytes(c, 1000) == 30_720_000
    assert K.state_bytes(c) == 30 * 96 * 192 * 4 == 2_211_840
    assert K.conv_tail_bytes(c) == 3 * 11520 * 2 == 69_120
    assert K.step_state_traffic(c) == 6 * 2 * 2_211_840 == 26_542_080
    # 128 slots: 1.70 GB of state + 0.05 of tails
    assert round(128 * 6 * (K.state_bytes(c) + K.conv_tail_bytes(c)) / 1e9,
                 2) == 1.75
    # one chunk of 64, a head
    head = (4 * 64 * 64 * 96 + 64 * 64 * 288 + 4 * 64 * 96 * 192
            + 64 * 64 * 192 + 2 * 64 * 96 * 192)
    assert K.chunk_flops(c, 64) == 30 * head
    assert K.prefill_flops(c, 512) == 6 * 30 * head * 8
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_but_for_its_depth():
    """Every key of the catalog's `config` under the same key with the
    same value; depth alone is reduced, `layer_types` is kept whole."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Olmo-Hybrid-7B")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differs == {"num_hidden_layers"} == set(c["reduced"])
    assert c["num_hidden_layers"] == 8 and len(c["layer_types"]) == 32
    assert c["deployment"]["num_hidden_layers"] \
        == row["config"]["num_hidden_layers"]
    assert c["deployment"]["chips_sharing_a_layer"] == 1
    assert c["precision"]["recurrent_state"] == "float32"
    assert {"block", "qk_norm", "head_dim", "rope_theta", "l2_norm_eps",
            "beta", "decay", "initializer_range"} <= set(c["assumed"])
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == NAME)
    assert set(entry["reduced"]) == set(c["reduced"])
    assert entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/" + NAME + ".json"


def test_family_builds_the_config_and_refuses_what_it_cannot_run():
    import jax.numpy as jnp

    from families import gdn_hybrid_decoder as F

    c = cfg()
    build = lambda c: F.model_config(c, max_seq_len=4096,
                                     compute_dtype="bfloat16",
                                     param_dtype="bfloat16")
    mc = build(c)
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.hidden_dim) \
        == (8, 3840, 100352, 11008)
    assert mc.attn_layers == (3, 7)
    assert (mc.n_gdn_layers, mc.n_attn_layers) == (6, 2)
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim) == (30, 30, 128)
    assert (mc.gdn_heads, mc.gdn_key_dim, mc.gdn_value_dim, mc.conv_size,
            mc.heads_a_row) == (30, 96, 192, 4, 2)
    assert mc.rope_theta is None and mc.norm_eps == 1e-6
    assert mc.state_dtype == jnp.float32
    serving = mc.serving()
    assert serving.init_slot_state is not None and serving.init_counts
    for change, said in (
            ({"layer_types": ["conv"] * 32}, "neither"),
            ({"linear_num_key_heads": 15}, "linear_num_key_heads"),
            ({"attention_bias": True}, "attention_bias"),
            ({"tie_word_embeddings": True}, "tied head"),
            ({"precision": {"recurrent_state": "bfloat16"}}, "bfloat16")):
        with pytest.raises(ValueError, match=said):
            build(dict(c, **change))
    assert build(dict(c, rope_parameters={"rope_theta": 5e5})).rope_theta \
        == 5e5


@pytest.mark.parametrize("rate", [3.0, 5.0])
def test_reason_mix(rate):
    check_schedule("reason", rate, 32, 1024, 128, 2560)
    m = traffic.load("reason")
    reqs = traffic.schedule(m, rate, 60.0, 5, 100352)
    lens = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.max_tokens for r in reqs)
    assert 220 < np.median(lens) < 300                   # median 256
    assert 650 < np.median(outs) < 900                   # median 768
    assert 0.08 < sum(n > 512 for n in lens) / len(lens) < 0.25  # chunked
    # generation is three quarters of every request
    assert 0.68 < sum(outs) / (sum(outs) + sum(lens)) < 0.82


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["decode_block"], e["prefix_cache"]) \
        == (128, 4096, 16, 1, False)
    assert e["prefill_buckets"] == [128, 256, 512]
    assert cell["driver"] == "serve_engine" and cell["preroll_s"] == 15.0
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"], cell["check"]["stat"]) \
        == (32, 32, 8, "mean_deficit")
    # the population a steady state holds: rate x a request's lifetime
    assert 15 * cell["rate_per_s"] < cell["warm_start"] \
        < 30 * cell["rate_per_s"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "reason", 1)
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"tick_gdn_share", "gdn_state_hbm_share", "gdn_prefill_roofline",
            "full_attn_roofline", "decode_step_ms", "insert_ms",
            "cache_misses", "tick_kv_gather_share"} <= listed
    assert not {"spill_copy_ms", "spill_land_ms", "decode_hbm_share",
                "tick_moe_share", "tick_kda_share"} & listed
    e2e = {m["name"] for m in declared["end_to_end"]
           if CELL in m.get("workloads", (CELL,))}
    assert e2e == {"gap_mean_ms", "setup_s"}


NEW_READERS = ("tick_gdn_share", "gdn_state_hbm_share",
               "gdn_prefill_roofline", "full_attn_roofline")


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope or span argument (the
    parent's), each new reader returns None and does not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two steps, each with one insert and a tick: the seconds under
    each scope and the counted bytes and operations come out as worked
    by hand."""
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(i)/gdn/state/a", 1 * ms, 2 * ms),        # insert 1
           ("jit(i)/gdn/proj/b", 3 * ms, 1 * ms),
           ("jit(t)/gdn/state/c", 11 * ms, 1 * ms),       # tick 1
           ("jit(t)/gdn/conv/d", 12 * ms, 1 * ms),
           ("jit(t)/attn/paged/e", 13 * ms, 2 * ms),
           ("jit(t)/attn/qk_norm/f", 15 * ms, 1 * ms),
           ("jit(i)/gdn/state/g", 31 * ms, 4 * ms),       # insert 2
           ("jit(t)/gdn/state/h", 41 * ms, 1 * ms),       # tick 2
           ("jit(t)/attn/paged/i", 42 * ms, 2 * ms)]
    runs = [("jit_llm_engine_insert(1)", 1 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_insert(3)", 30 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [(PS.STEP, 0, 25 * ms, {}),
             ("llm_engine.insert_dispatch", ms // 2, 1000,
              {"bucket": "512", "tokens": "512", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 9 * ms, 1000,
              {"live": "90", "rows": "80000"}),
             (PS.STEP, 29 * ms, 25 * ms, {}),
             ("llm_engine.insert_dispatch", 29 * ms + 10, 1000,
              {"bucket": "256", "tokens": "200", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000,
              {"live": "110", "rows": "120000"}),
             # a tick before the traced interval, busier: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000,
              {"live": "128", "rows": "500000"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])
    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": []}, "config": c,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    # tick: gdn 3 ms of 20 ms
    assert reader("tick_gdn_share").read(run) == pytest.approx(15.0)
    # (90 + 110) / 2 live slots a tick of the interval x 26,542,080 B x 2
    # ticks over 2 ms of gdn/state
    want = 100 * 100 * 26_542_080 * 2 / 819e9 / 2e-3
    assert reader("gdn_state_hbm_share").read(run) == pytest.approx(want)
    # 100,000 rows a tick x 30,720 B x 2 ticks over 4 ms of attn/paged
    want = 100 * 100_000 * 30_720 * 2 / 819e9 / 4e-3
    assert reader("full_attn_roofline").read(run) == pytest.approx(want)
    # 712 real tokens, 6 ms under gdn/state in the inserts
    want = 100 * K.prefill_flops(c, 712) / 197e12 / 6e-3
    assert reader("gdn_prefill_roofline").read(run) == pytest.approx(want)


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000123", "--seconds", "5", "--trace", "1",
         "--rehearse", *extra],
        capture_output=True, text=True, env=env, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_runs_the_cell_end_to_end():
    line = _rehearse()
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    assert "cache_misses" in line["metrics"]
    assert "tick_host_ms" in line["metrics"]


def test_rehearsal_with_the_control_is_not_correct():
    line = _rehearse("--control")
    assert line["rehearsal"] and line["control"] and not line["correct"]
