"""The eighth family's files: its counts against numbers worked by hand
(ISSUE 47), its configuration against the catalog row (nothing reduced),
its traffic mix through `test_traffic.py`'s checks, the family's model
config, the control against its docstring, the new readers on a run
without their sources and on a made-up trace, and a CPU `--rehearse` of
its cell end to end, sound and with the control."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_sambay as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "think-decode-ssm-yoco"
NAME = "phi-4-mini-flash-reasoning-serve"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


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
    # W1 2560 x 20480, W2 10240 x 2560, two LayerNorms with bias
    block = 52_428_800 + 26_214_400 + 4 * 2560
    assert K.block_params(c) == block == 78_653_440
    # W_in 2560 x 10240; 4 taps + bias x 5120; W_x 5120 x 192; W_dt
    # 160 x 5120; b_dt, A_log 16 x 5120, Dskip; W_out 5120 x 2560
    mamba = (26_214_400 + 25_600 + 983_040 + 819_200 + 5_120 + 81_920
             + 5_120 + 13_107_200)
    assert K.mamba_params(c) == mamba == 41_241_600
    assert K.layer_params(c, "mamba") == 119_895_040
    # W_qkv 2560 x 5120 + 5120; 4 lambdas x 64; sub-norm 128; W_o + bias
    assert K.attn_params(c) == 13_107_200 + 5_120 + 256 + 128 \
        + 6_553_600 + 2_560 == 19_668_864
    assert K.layer_params(c, "attn") == 98_322_304
    assert K.layer_params(c, "gmu") == 2 * 13_107_200 + block == 104_867_840
    assert K.attn_params(c, cross=True) == 2 * (6_553_600 + 2_560) + 384
    assert K.layer_params(c, "cross") == 91_766_144
    assert K.vocab_params(c) == 200064 * 2560 == 512_163_840
    assert (K.n_self_pairs(c), K.n_cross_pairs(c), K.n_ssm_layers(c),
            K.n_shared_readers(c)) == (8, 7, 9, 8)
    assert K.total_params(c) == 9 * 119_895_040 + 9 * 98_322_304 \
        + 7 * 104_867_840 + 7 * 91_766_144 + 512_163_840 + 5_120 \
        == 3_852_562_944
    assert round(K.total_params(c) * 2 / 1e9, 2) == 7.71
    assert K.full_row_bytes(c) == 2 * 20 * 64 * 2 == 5_120
    assert K.window_row_bytes(c) == 8 * 5_120 == 40_960
    assert K.shared_attention_bytes(c, 1000) == 8 * 5_120_000
    assert K.state_bytes(c) == 5120 * 16 * 4 == 327_680
    assert K.state_bytes_per_slot(c) == 9 * 327_680 == 2_949_120
    assert K.tail_bytes_per_slot(c) == 9 * 3 * 5120 * 2 == 276_480
    assert K.step_state_traffic(c) == 2 * 2_949_120
    # 96 slots: 0.28 GB of state + 0.03 of tails
    assert round(96 * (2_949_120 + 276_480) / 1e9, 2) == 0.31
    # an insert's scan over 1024 rows: 566 MB at 819 GB/s is 0.69 ms,
    # 5.3 G operations at the matrix unit's rate 0.03: the bytes' time
    assert K.scan_bytes(c, 1024) == 9 * 1024 * 3 * 5120 * 4 == 566_231_040
    assert K.scan_ops(c, 1024) == 9 * 1024 * 5120 * 113
    assert K.scan_seconds(c, 1024, PEAKS) == pytest.approx(
        566_231_040 / 819e9)
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_with_nothing_reduced():
    """Every key of the catalog's `config` under the same key with the
    same value; `reduced` is empty, here and in BENCHMARK.json."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Phi-4-mini-flash-reasoning")
    c = cfg()
    assert c["source"] == row["source_url"]
    assert {k for k, v in row["config"].items()
            if c.get(k, "absent") != v} == set() == set(c["reduced"])
    assert c["deployment"]["stages"] == 1 and c["chips"] == 1
    assert c["precision"]["recurrent_state"] == "float32"
    assert {"mamba_sizes", "differential_attention", "biases",
            "layer_kinds", "memory", "feed_forward", "positions", "draws",
            "initializer_range"} <= set(c["assumed"])
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/" + NAME + ".json"


def test_family_builds_the_config_and_refuses_what_it_cannot_run():
    import jax.numpy as jnp

    from families import sambay_decoder as F

    c = cfg()
    build = lambda c: F.model_config(c, max_seq_len=12288,
                                     compute_dtype="bfloat16",
                                     param_dtype="bfloat16")
    mc = build(c)
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.hidden_dim) \
        == (32, 2560, 200064, 10240)
    assert (mc.n_self_pairs, mc.n_cross_pairs, mc.n_ssm_layers) == (8, 7, 9)
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.n_kv_pairs,
            mc.kv_width, mc.window) == (40, 20, 64, 10, 1280, 512)
    assert (mc.d_inner, mc.d_state, mc.d_conv, mc.dt_rank) \
        == (5120, 16, 4, 160)
    assert mc.norm_eps == 1e-5 and mc.state_dtype == jnp.float32
    serving = mc.serving()
    assert serving.init_slot_state and serving.window_kind \
        and serving.init_counts and serving.quantize_int8
    assert serving.window_kind(mc) == (512, ("k_w", "v_w"))
    for change, said in (
            ({"mb_per_layer": 1}, "mb_per_layer"),
            ({"num_hidden_layers": 30}, "a depth of 30"),
            ({"tie_word_embeddings": False}, "untied head"),
            ({"mlp_bias": True}, "mlp_bias"),
            ({"mamba_dt_rank": 128}, "dt_rank"),
            ({"precision": {"recurrent_state": "bfloat16"}}, "bfloat16")):
        with pytest.raises(ValueError, match=said):
            build(dict(c, **change))


def test_the_control_is_what_its_docstring_says():
    """Every matmul weight rounded per output channel to at most 255
    levels, a stacked leaf a layer at a time; the tied table, the taps,
    the vectors, the decays and the lambdas handed back as they are."""
    import jax
    import jax.numpy as jnp

    from families import sambay_decoder as F
    from reference import sambay_decoder as R

    tiny = dict(cfg(), hidden_size=64, num_attention_heads=8,
                num_key_value_heads=4, intermediate_size=128,
                vocab_size=512, num_hidden_layers=8, mamba_d_state=4,
                mamba_dt_rank=4)
    weights = R.init_weights(tiny, 3, jnp.float32)
    assert F.program_params(weights) is weights
    rounded = jax.jit(F.lower_precision_params)(weights)
    flat = jax.tree_util.tree_flatten_with_path(weights)[0]
    changed = set()
    for (path, w), r in zip(flat, jax.tree.leaves(rounded)):
        name = path[-1].key
        if bool(jnp.any(w != r)):
            changed.add(name)
            top = jnp.max(jnp.abs(r), axis=-2, keepdims=True)
            levels = np.unique(np.asarray(jnp.round(r / top * 127, 3)))
            assert len(levels) <= 255 and w.shape == r.shape
    assert changed == {"w1", "w2", "w_in", "w_x", "w_dt", "w_out", "w_qkv",
                       "w_o", "w_g"}


@pytest.mark.parametrize("rate", [1.7, 2.4])
def test_think_mix(rate):
    check_schedule("think", rate, 64, 8192, 256, 3584)
    m = traffic.load("think")
    reqs = traffic.schedule(m, rate, 60.0, 5, 200064)
    lens = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.max_tokens for r in reqs)
    assert 650 < np.median(lens) < 900                   # median 768
    assert 1100 < np.median(outs) < 1450                 # median 1280
    assert 0.3 < sum(n > 1024 for n in lens) / len(lens) < 0.45  # chunked
    assert 900 < np.mean(lens) < 1300 and 1300 < np.mean(outs) < 1650
    assert m["strata_s"] == traffic.load("reason")["strata_s"] == 10.0


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["decode_block"], e["prefix_cache"]) \
        == (96, 12288, 16, 1, False)
    assert e["prefill_buckets"] == [256, 512, 1024]
    assert e["num_kv_blocks"] * 16 >= 600_000
    # 96 decoding rings of 512 / 16 + 1 blocks and the inserts under way
    assert e["num_window_blocks"] >= 96 * 33 + 6 * 96
    assert cell["driver"] == "serve_engine" and cell["preroll_s"] == 15.0 \
        and cell["drain_s"] == 120.0
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"], cell["check"]["stat"]) \
        == (32, 32, 8, "mean_deficit")
    # the population a steady state holds: rate x a request's lifetime
    assert 30 * cell["rate_per_s"] < cell["warm_start"] \
        < 50 * cell["rate_per_s"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "think", 1)
    assert len(w["why"]) <= 200
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) | {
        "gap_p50_ms", "decode_step_ms", "cache_misses", "admit_stall_ms",
        "insert_ms", "host_loop_ms", "tick_kv_gather_share",
        "idle_attributed_share", "tick_readback_ms",
        "tick_launch_notify_ms", "tick_host_ms", "engine_idle_share",
        "tick_overlap_share"} == listed
    for m in declared["per_layer"]:
        if m["name"] in NEW_READERS:
            assert (m["unit"], m["source"], m["layer"], m["moves"],
                    m["workloads"]) == ("%", "device_trace", "model step",
                                        "gap_mean_ms", [CELL])
    e2e = {m["name"] for m in declared["end_to_end"]
           if CELL in m.get("workloads", (CELL,))}
    assert e2e == {"gap_mean_ms", "setup_s"}


NEW_READERS = ("tick_ssm_share", "ssm_state_hbm_share",
               "ssm_prefill_roofline", "tick_shared_kv_share",
               "shared_kv_attn_roofline", "insert_cross_share")


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope or span argument (the
    parent's), each new reader returns None and does not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": PEAKS}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two steps, each with one insert and a tick: the seconds under
    each scope and the counted bytes come out as worked by hand."""
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(i)/self_decoder/while/body/ssm/state/a", 1 * ms, 2 * ms),
           ("jit(i)/self_decoder/while/body/ssm/proj/b", 3 * ms, 1 * ms),
           ("jit(i)/cross_decoder/ssm/state/c", 4 * ms, 1 * ms),
           ("jit(i)/cross_decoder/while/body/mlp/d", 5 * ms, 1 * ms),
           ("jit(t)/self_decoder/while/body/ssm/state/e", 11 * ms, 1 * ms),
           ("jit(t)/self_decoder/while/body/ssm/conv/f", 12 * ms, 1 * ms),
           ("jit(t)/cross_decoder/while/body/attn/paged_shared/g",
            13 * ms, 4 * ms),
           ("jit(t)/self_decoder/while/body/attn/paged_window/h",
            17 * ms, 1 * ms),
           ("jit(i)/self_decoder/while/body/ssm/state/i", 31 * ms, 3 * ms),
           ("jit(i)/cross_decoder/gmu/j", 35 * ms, 2 * ms),
           ("jit(t)/cross_decoder/ssm/state/k", 41 * ms, 1 * ms),
           ("jit(t)/attn/paged_shared/l", 42 * ms, 4 * ms)]
    runs = [("jit_llm_engine_insert(1)", 1 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_insert(3)", 30 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [(PS.STEP, 0, 25 * ms, {}),
             ("llm_engine.insert_dispatch", ms // 2, 1000,
              {"bucket": "1024", "tokens": "1024", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 9 * ms, 1000,
              {"live": "60", "rows": "120000", "window_rows": "30000"}),
             (PS.STEP, 29 * ms, 25 * ms, {}),
             ("llm_engine.insert_dispatch", 29 * ms + 10, 1000,
              {"bucket": "512", "tokens": "300", "state_in": "1"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000,
              {"live": "70", "rows": "160000", "window_rows": "34000"}),
             # a tick before the traced interval, busier: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000,
              {"live": "96", "rows": "500000", "window_rows": "49152"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])
    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": []}, "config": c, "peaks": PEAKS}
    # tick: ssm 3 ms, the shared rows' reads 8 ms, of 20 ms
    assert reader("tick_ssm_share").read(run) == pytest.approx(15.0)
    assert reader("tick_shared_kv_share").read(run) == pytest.approx(40.0)
    # inserts: cross_decoder 4 ms of 16 ms
    assert reader("insert_cross_share").read(run) == pytest.approx(25.0)
    # (60 + 70) / 2 live slots a tick x 5,898,240 B x 2 ticks over 2 ms
    want = 100 * 65 * 5_898_240 * 2 / 819e9 / 2e-3
    assert reader("ssm_state_hbm_share").read(run) == pytest.approx(want)
    # 140,000 rows a tick x 5,120 B x 8 readers x 2 ticks over 8 ms
    want = 100 * 140_000 * 5_120 * 8 * 2 / 819e9 / 8e-3
    assert reader("shared_kv_attn_roofline").read(run) \
        == pytest.approx(want)
    # 1,324 real tokens' bytes over 6 ms under ssm/state in the inserts
    want = 100 * K.scan_bytes(c, 1324) / 819e9 / 6e-3
    assert reader("ssm_prefill_roofline").read(run) == pytest.approx(want)


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
    """Every listed metric is printed, or is one that the CPU's trace
    cannot give (a device trace has no TPU plane here: `null`, left
    out)."""
    line = _rehearse()
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    host = {"gap_p50_ms", "cache_misses", "host_loop_ms", "tick_host_ms",
            "tick_readback_ms", "engine_idle_share", "tick_overlap_share",
            "idle_attributed_share"}
    assert host <= set(line["metrics"])
    # no device plane on the CPU: the device_trace readers read nothing
    assert not set(NEW_READERS) & set(line["metrics"])


def test_rehearsal_with_the_control_is_not_correct():
    line = _rehearse("--control")
    assert line["rehearsal"] and line["control"] and not line["correct"]
