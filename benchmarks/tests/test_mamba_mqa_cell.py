"""The eleventh family's files: its counts against numbers worked by hand
(ISSUE 59), its configuration against the catalog row (nothing reduced),
its traffic mix through `test_traffic.py`'s checks (no prompt of the
schedule over the top bucket), the family's model config, the new
readers on a run without their sources and on a made-up trace, and a CPU
`--rehearse` of its cell end to end, sound and with the control.
Metric lists are held by membership (`<=`), not as exact sets: the next
cell that joins a generic reader's list must not fail this file."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_mamba_mqa as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "tutor-decode-mamba-mqa"
NAME = "ai21-jamba2-3b-serve"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_READERS = ("mamba_state_hbm_share", "mamba_prefill_roofline",
               "mqa_attn_roofline")


def cfg():
    with open(os.path.join(BENCH, "configs", NAME + ".json")) as f:
        return json.load(f)


def cell_file():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_counts_against_hand_numbers():
    c = cfg()
    # W_gate, W_up 2560 x 8192, W_down 8192 x 2560, two RMSNorms
    block = 3 * 20_971_520 + 2 * 2560
    assert K.block_params(c) == block == 62_919_680
    # W_in 2560 x 10240; 4 taps + bias x 5120; W_x 5120 x 192; the three
    # norms 160 + 16 + 16; W_dt 160 x 5120; b_dt, A_log 16 x 5120, Dskip;
    # W_out 5120 x 2560
    mamba = (26_214_400 + 25_600 + 983_040 + 192 + 819_200 + 5_120
             + 81_920 + 5_120 + 13_107_200)
    assert K.mamba_params(c) == mamba == 41_241_792
    assert K.layer_params(c, "mamba") == 104_161_472
    # W_q, W_o 2560 x 2560; W_k, W_v 2560 x 128
    assert K.attn_params(c) == 2 * 6_553_600 + 2 * 327_680 == 13_762_560
    assert K.layer_params(c, "attn") == 76_682_240
    assert K.vocab_params(c) == 65536 * 2560 == 167_772_160
    kinds = K.layer_kinds(c)
    assert [i for i, k in enumerate(kinds) if k == "attn"] == [7, 21]
    assert (K.n_ssm_layers(c), K.n_attn_layers(c)) == (26, 2)
    assert K.total_params(c) == 26 * 104_161_472 + 2 * 76_682_240 \
        + 167_772_160 + 2_560 == 3_029_337_472
    assert round(K.total_params(c) * 2 / 1e9, 2) == 6.06
    assert K.kv_row_bytes(c) == 2 * 2 * 128 * 2 == 1_024
    assert K.paged_attention_bytes(c, 1000) == 1_024_000
    assert K.state_bytes(c) == 5120 * 16 * 4 == 327_680
    assert K.state_bytes_per_slot(c) == 26 * 327_680 == 8_519_680
    assert K.tail_bytes_per_slot(c) == 26 * 3 * 5120 * 2 == 798_720
    assert K.step_state_traffic(c) == 2 * 8_519_680
    # 512 slots: 4.36 GB of state + 0.41 of tails
    assert round(512 * (8_519_680 + 798_720) / 1e9, 2) == 4.77
    # an insert's scan over 1024 rows: 1.64 GB at 819 GB/s is 2.0 ms,
    # 15 G operations at the matrix unit's rate 0.08: the bytes' time
    assert K.scan_bytes(c, 1024) == 26 * 1024 * 3 * 5120 * 4 == 1_635_778_560
    assert K.scan_ops(c, 1024) == 26 * 1024 * 5120 * 113
    assert K.scan_seconds(c, 1024, PEAKS) == pytest.approx(
        1_635_778_560 / 819e9)
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_with_nothing_reduced():
    """Every key of the catalog's `config` under the same key with the
    same value; `reduced` is empty, here and in BENCHMARK.json."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "AI21-Jamba2-3B")
    c = cfg()
    assert c["source"] == row["source_url"]
    assert {k for k, v in row["config"].items()
            if c.get(k, "absent") != v} == set() == set(c["reduced"])
    assert c["deployment"]["stages"] == 1 and c["chips"] == 1
    assert c["deployment"]["num_hidden_layers"] == c["num_hidden_layers"]
    assert c["precision"]["recurrent_state"] == "float32"
    assert {"layer_kinds", "head_dim", "feed_forward", "mamba_norms",
            "positions", "draws", "initializer_range"} <= set(c["assumed"])
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == NAME)
    assert entry["reduced"] == [] and entry["source"] == c["source"]
    assert entry["file"] == "benchmarks/configs/" + NAME + ".json"


def test_family_builds_the_config_and_refuses_what_it_cannot_run():
    import jax.numpy as jnp

    from families import mamba_mqa_decoder as F

    c = cfg()
    build = lambda c: F.model_config(c, max_seq_len=8192,
                                     compute_dtype="bfloat16",
                                     param_dtype="bfloat16")
    mc = build(c)
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.hidden_dim) \
        == (28, 2560, 65536, 8192)
    assert (mc.kinds, mc.mamba_runs) \
        == ("MMMMMMM*MMMMMMMMMMMMM*MMMMMM", [7, 13, 6])
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim) == (20, 1, 128)
    assert (mc.d_inner, mc.d_state, mc.d_conv, mc.dt_rank) \
        == (5120, 16, 4, 160)
    assert mc.norm_eps == 1e-6 and mc.state_dtype == jnp.float32
    serving = mc.serving()
    assert serving.init_slot_state and serving.init_counts \
        and serving.quantize_int8 and serving.window_kind is None
    assert set(serving.init_counts(mc)) == {
        "ticks", "live_slots", "ssm_live_steps", "mqa_rows_read"}
    for change, said in (
            ({"num_experts": 2}, "num_experts 2"),
            ({"sliding_window": 4096}, "sliding_window"),
            ({"tie_word_embeddings": False}, "untied head"),
            ({"precision": {"recurrent_state": "bfloat16"}}, "bfloat16")):
        with pytest.raises(ValueError, match=said):
            build(dict(c, **change))


def test_the_control_is_what_its_docstring_says():
    """Every matmul weight rounded per output channel to at most 255
    levels, a stacked leaf a layer at a time; the tied table, the taps,
    the vectors and the decays handed back as they are."""
    import jax
    import jax.numpy as jnp

    from families import mamba_mqa_decoder as F
    from reference import mamba_mqa_decoder as R

    tiny = dict(cfg(), **cell_file()["rehearsal"]["config"])
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
    assert changed == {"w_gate", "w_up", "w_down", "w_in", "w_x", "w_dt",
                       "w_out", "wq", "wk", "wv", "wo"}


def test_tutor_mix_holds_no_prompt_over_the_top_bucket():
    """At the cell's rate, and at the sweep's ends, every prompt of the
    schedule is ONE piece under the 2048 bucket, while the mix's clip
    (8192) still gives the idle check a chunked cover class."""
    cell = cell_file()
    top = max(cell["engine"]["prefill_buckets"])
    m = traffic.load("tutor")
    assert m["strata_s"] == traffic.load("swarm")["strata_s"] == 10.0
    assert (m["prompt"]["max"], m["prompt"]["min"]) == (8192, 32)
    for rate in (6.0, cell["rate_per_s"], 14.0):
        check_schedule("tutor", rate, 32, 8192, 128, 3072)
        reqs = traffic.schedule(m, rate, 66.0, 5, 65536,
                                warm=cell["warm_start"], splits=[15.0])
        lens = sorted(len(r.prompt) for r in reqs)
        assert lens[-1] <= top == 2048
        assert 220 < np.median(lens) < 300                   # median 256
        assert 280 < np.mean(lens) < 370
    reqs = traffic.schedule(m, cell["rate_per_s"], 60.0, 5, 65536)
    outs = sorted(r.max_tokens for r in reqs)
    assert 900 < np.median(outs) < 1150                      # median 1024
    assert 1050 < np.mean(outs) < 1350 and outs[-1] == 3072


def test_cell_is_what_the_issue_named():
    cell = cell_file()
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["decode_block"],
            e["kv_layout"], e["prefix_cache"]) \
        == (512, 8192, 1, "paged", False)
    assert e["prefill_buckets"] == [256, 512, 1024, 2048]
    assert e["kv_block_size"] in (16, 64, 128)
    assert 1_400_000 <= e["num_kv_blocks"] * e["kv_block_size"] <= 1_700_000
    assert cell["driver"] == "serve_engine" and cell["preroll_s"] >= 15.0 \
        and cell["drain_s"] == 120.0
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"], cell["check"]["stat"]) \
        == (32, 32, 8, "mean_deficit")
    assert cell["trace"] == {"start_frac": 0.5, "seconds": 3.0}
    # the population a steady state holds: rate x a request's lifetime
    # (1.2 k tokens at 45-50 ms a gap), inside the slots
    assert 45 * cell["rate_per_s"] < cell["warm_start"] \
        < 65 * cell["rate_per_s"]
    assert cell["warm_start"] < e["num_slots"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "tutor", 1)
    assert len(w["why"]) <= 200
    c = next(c for c in declared["configs"] if c["name"] == NAME)
    assert c["reduced"] == [] and 1 <= len(c["why"]) <= 200
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) | {
        "tick_ssm_share", "gap_p50_ms", "decode_step_ms", "cache_misses",
        "insert_ms", "host_loop_ms", "idle_attributed_share",
        "tick_readback_ms", "tick_host_ms", "engine_idle_share",
        "tick_overlap_share", "warmup_s", "trace_lower_s",
        "backend_compile_s"} <= listed
    for m in declared["per_layer"]:
        if m["name"] in NEW_READERS:
            assert (m["unit"], m["source"], m["layer"], m["moves"]) \
                == ("%", "device_trace", "model step", "gap_mean_ms")
            assert CELL in m["workloads"]
    e2e = {m["name"] for m in declared["end_to_end"]
           if CELL in m.get("workloads", (CELL,))}
    assert {"gap_mean_ms", "setup_s"} <= e2e


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope or span argument (the
    parent's), and on another family's configuration, each new reader
    returns None and does not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": PEAKS}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None
        assert reader(name).read(dict(
            run, trace=object(), config={"mb_per_layer": 2})) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two steps, each with one insert and a tick: the seconds under
    each scope and the counted bytes come out as worked by hand."""
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(i)/while/body/ssm/state/a", 1 * ms, 2 * ms),
           ("jit(i)/while/body/ssm/proj/b", 3 * ms, 1 * ms),
           ("jit(i)/attn/c", 4 * ms, 1 * ms),
           ("jit(i)/while/body/ffn/d", 5 * ms, 1 * ms),
           ("jit(t)/while/body/ssm/state/e", 11 * ms, 4 * ms),
           ("jit(t)/while/body/ssm/conv/f", 15 * ms, 1 * ms),
           ("jit(t)/attn/paged/g", 16 * ms, 2 * ms),
           ("jit(t)/attn/kv_write/h", 18 * ms, 1 * ms),
           ("jit(i)/while/body/ssm/state/i", 31 * ms, 2 * ms),
           ("jit(i)/ffn/j", 35 * ms, 2 * ms),
           ("jit(t)/while/body/ssm/state/k", 41 * ms, 4 * ms),
           ("jit(t)/attn/paged/l", 45 * ms, 2 * ms)]
    runs = [("jit_llm_engine_insert(1)", 1 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_insert(3)", 30 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [(PS.STEP, 0, 25 * ms, {}),
             ("llm_engine.insert_dispatch", ms // 2, 1000,
              {"bucket": "1024", "tokens": "1000", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 9 * ms, 1000,
              {"live": "150", "rows": "200000"}),
             (PS.STEP, 29 * ms, 25 * ms, {}),
             ("llm_engine.insert_dispatch", 29 * ms + 10, 1000,
              {"bucket": "256", "tokens": "200", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000,
              {"live": "170", "rows": "240000"}),
             # a tick before the traced interval, busier: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000,
              {"live": "500", "rows": "900000"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])
    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": []}, "config": c, "peaks": PEAKS}
    # tick: ssm 9 ms of 20 ms
    assert reader("tick_ssm_share").read(run) == pytest.approx(45.0)
    # (150 + 170) / 2 live slots a tick x 17,039,360 B x 2 ticks over 8 ms
    want = 100 * 160 * 17_039_360 * 2 / 819e9 / 8e-3
    assert reader("mamba_state_hbm_share").read(run) == pytest.approx(want)
    assert want < 100
    # 220,000 rows a tick x 1,024 B x 2 ticks over 4 ms
    want = 100 * 220_000 * 1_024 * 2 / 819e9 / 4e-3
    assert reader("mqa_attn_roofline").read(run) == pytest.approx(want)
    # 1,200 real tokens' bytes over 4 ms under ssm/state in the inserts
    want = 100 * K.scan_bytes(c, 1200) / 819e9 / 4e-3
    assert reader("mamba_prefill_roofline").read(run) == pytest.approx(want)


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
    """Every listed host metric is printed; the three new readers, which
    read a device trace, find no TPU plane on the CPU and are left out
    (`None`: no device operation carries a scope here)."""
    line = _rehearse()
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    host = {"gap_p50_ms", "cache_misses", "host_loop_ms", "tick_host_ms",
            "tick_readback_ms", "engine_idle_share", "tick_overlap_share",
            "idle_attributed_share", "warmup_s", "trace_lower_s",
            "backend_compile_s"}
    assert host <= set(line["metrics"])
    assert not set(NEW_READERS) & set(line["metrics"])


def test_rehearsal_with_the_control_is_not_correct():
    line = _rehearse("--control")
    assert line["rehearsal"] and line["control"] and not line["correct"]
