"""The fifth family's files: its counts against numbers worked by hand
(ISSUE 38), its configuration against the catalog row, its traffic mix
through `test_traffic.py`'s checks, the family's model config, the new
readers on a run without their sources and on a made-up trace, and a
CPU `--rehearse` of its cell end to end, sound and control."""
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import counts_window_moe as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "mixed-decode-window-moe"
CONFIG = "trinity-mini-serve"
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


def test_counts_against_hand_numbers():
    c = cfg()
    # q, o and the gate 3 x 2048*4096, k and v 2 x 2048*512
    assert K.attention_params(c) == 3 * 8_388_608 + 2 * 1_048_576 \
        == 27_262_976
    assert K.dense_half_params(c) == 3 * 2048 * 6144 == 37_748_736
    assert K.expert_params(c) == 3 * 2048 * 1024 == 6_291_456
    assert K.shared_params(c) == 6_291_456 and K.router_params(c) == 262_144
    assert 128 * K.expert_params(c) == 805_306_368
    assert K.layer_kinds(c) == ["sliding_attention", "sliding_attention",
                                "full_attention", "sliding_attention",
                                "sliding_attention"]
    assert (K.n_window_layers(c), K.n_full_layers(c),
            K.n_expert_layers(c)) == (4, 1, 4)
    assert K.layer_params(c, 0) == 65_011_712               # 65.0 M
    assert K.layer_params(c, 1) == 839_122_944              # 839.1 M
    assert K.vocab_params(c) == 2 * 409_993_216
    assert K.total_params(c) == 4_241_489_920               # 4,241.5 M
    assert round(K.total_params(c) * 2 / 1e9, 2) == 8.48
    assert round(K.published_total_params(c) / 1e9, 1) == 26.1
    whole = dict(c, num_hidden_layers=32, num_dense_layers=2, first_layer=0)
    assert round(K.active_params_per_token(whole) / 1e9, 2) == 3.06  # A3B
    # K and V of 4 heads of 128 in bf16
    assert K.kv_row_bytes(c) == 2 * 4 * 128 * 2 == 2048
    assert K.kv_bytes_per_token(c, "full_attention") == 2048
    assert K.kv_bytes_per_token(c, "sliding_attention") == 8192
    assert K.one_table_bytes_per_token(c) == 10_240
    # 1000 rows in the full layer, 700 inside the window in four layers
    assert K.paged_attention_bytes(c, 1000.0, 700.0) \
        == 1000 * 2048 + 700 * 8192
    assert K.tick_least_bytes(c, 0.0, 0.0, 4 * 128) == K.total_params(c) * 2 \
        - K.vocab_params(c)                 # the embedding table is a gather
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_but_for_its_depth():
    """Every key of the catalog's `config` under the same key with the
    same value (`layer_types` whole); the depth and the leading dense
    layers alone are reduced; which layers are held is a key of the
    file's own."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Trinity-Mini")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_dense_layers"} \
        == set(c["reduced"])
    assert c["published"] == {k: row["config"][k] for k in c["reduced"]}
    assert "first_layer" not in row["config"] and c["first_layer"] == 1
    assert {"sliding_window", "rotary", "route_norm", "logits",
            "initializer_range", "router_bias_scale", "first_layer"} \
        <= set(c["assumed"])
    assert c["deployment"]["chips_sharing_a_layer"] == 1
    assert c["precision"]["router"] == "float32"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == CONFIG)
    assert entry["reduced"] == ["num_hidden_layers", "num_dense_layers"]
    assert entry["source"] == c["source"]


def test_family_builds_the_config():
    import jax.numpy as jnp

    from families import window_moe_decoder as F

    c = cfg()
    mc = F.model_config(c, max_seq_len=18432, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.n_dense_layers) \
        == (5, 2048, 200192, 1)
    assert mc.full_layers == (2,) and mc.window == 2048
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim) == (32, 4, 128)
    assert (mc.n_experts, mc.top_k, mc.expert_hidden_dim,
            mc.shared_hidden_dim, mc.dense_hidden_dim,
            mc.routed_scaling_factor) == (128, 8, 1024, 1024, 6144, 2.826)
    assert mc.dtype == jnp.bfloat16 and mc.norm_eps == 1e-5
    model = mc.serving()
    assert model.window_kind(mc) == (2048, ("k_w", "v_w"))
    assert model.init_slot_state is None and model.verify is None
    for key, bad in (("rope_scaling", {"type": "yarn"}), ("n_group", 4),
                     ("route_norm", False), ("mup_enabled", False),
                     ("tie_word_embeddings", True)):
        with pytest.raises(ValueError, match="has no"):
            F.model_config(dict(c, **{key: bad}), max_seq_len=64,
                           compute_dtype="bfloat16", param_dtype="bfloat16")
    with pytest.raises(ValueError, match="neither"):
        F.model_config(dict(c, layer_types=["conv"] * 32), max_seq_len=64,
                       compute_dtype="bfloat16", param_dtype="bfloat16")


@pytest.mark.parametrize("rate", [4.0, 8.0])
def test_mixed_mix(rate):
    check_schedule("mixed", rate, 128, 16384, 64, 1024)
    m = traffic.load("mixed")
    sched = traffic.schedule(m, rate, 120.0, 5, 200192)
    lens = np.asarray(sorted(len(r.prompt) for r in sched))
    outs = sorted(r.max_tokens for r in sched)
    assert 1300 < np.median(lens) < 1800                 # median 1536
    assert 330 < np.median(outs) < 440                   # median 384
    assert 0.33 < np.mean(lens > 2048) < 0.47            # over the window
    assert 0.16 < np.mean(lens > 4096) < 0.28
    assert lens.max() == 16384 and lens.min() < 200
    assert 2500 < np.mean(lens) < 3300


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["decode_block"], e["prefix_cache"]) \
        == (64, 18432, 16, 1, False)
    assert e["prefill_buckets"] == [256, 512, 1024, 2048]
    assert e["num_kv_blocks"] >= 16384 // 16 and e["num_window_blocks"] >= 256
    assert cell["warm_start"] == round(cell["rate_per_s"] * 10)
    assert (cell["preroll_s"], cell["drain_s"]) == (15.0, 60.0)
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"], cell["check"]["stat"]) \
        == (32, 32, 8, "mean_deficit")
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (CONFIG, "mixed", 1)
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    # a SUBSET: a later PR may list the cell under more
    assert {"window_attn_roofline", "kv_pool_bytes_per_token",
            "insert_attn_share", "gap_p50_ms", "decode_step_ms",
            "cache_misses", "admit_stall_ms", "insert_ms", "host_loop_ms",
            "idle_attributed_share", "tick_moe_share",
            "expert_load_max_over_mean", "expert_rows_per_group",
            "tick_readback_ms", "tick_launch_notify_ms", "tick_host_ms",
            "engine_idle_share", "tick_kv_gather_share"} <= listed
    for name in ("window_attn_roofline", "kv_pool_bytes_per_token",
                 "insert_attn_share"):
        m = next(m for m in declared["per_layer"] if m["name"] == name)
        assert m["workloads"] == [CELL] and m["moves"] == "gap_mean_ms"
    gap = next(m for m in declared["end_to_end"]
               if m["name"] == "gap_mean_ms")
    assert CELL in gap["workloads"]


NEW_READERS = ("window_attn_roofline", "kv_pool_bytes_per_token",
               "insert_attn_share")


def test_new_readers_return_nothing_without_their_sources(monkeypatch):
    """On a run with no trace, and on a traced run whose program has no
    such scope or span argument (the parent commit), each new reader
    returns None and does not raise."""
    import program_spans as PS

    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": PEAKS}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None
    ms = 1_000_000
    prog = PS.Program([("llm_engine.tick_dispatch", 9 * ms, 1000,
                        {"live": "5", "rows": "1000"})], [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(PS, "program_runs", lambda trace, program, window: [
        ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms)])
    run = dict(run, trace=object(), window=(0, 60 * ms), named_ops=[
        ("jit(t)/attn/paged/paged_attention", 13 * ms, 1 * ms)])
    assert reader("window_attn_roofline").read(dict(run)) is None
    # an engine whose pool has one kind sums no held bytes
    parent = types.SimpleNamespace(handle=types.SimpleNamespace(
        engine=types.SimpleNamespace(stats=lambda: {
            "live_rows": 5000, "kv": {"used_blocks": 3}})))
    assert reader("kv_pool_bytes_per_token").read(
        dict(run, records={"recs": [parent]})) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two ticks and an insert: the seconds under `attn/paged` +
    `attn/paged_window` and under the insert's `attn`, the counted bytes
    and the engine's held bytes a row come out as worked by hand."""
    import program_spans as PS

    ms = 1_000_000
    ops = [("jit(t)/attn/paged/plan", 11 * ms, 1 * ms),        # tick 1
           ("jit(t)/attn/paged_window/paged_attention", 12 * ms, 2 * ms),
           ("jit(t)/attn/paged/paged_attention", 14 * ms, 1 * ms),
           ("jit(t)/attn/gate/c", 15 * ms, 1 * ms),
           ("jit(t)/moe/experts/e", 16 * ms, 4 * ms),
           ("jit(t)/attn/paged_window/paged_attention", 41 * ms, 3 * ms),
           ("jit(t)/attn/paged/paged_attention", 44 * ms, 1 * ms),  # tick 2
           ("jit(i)/attn/while/body/dot", 71 * ms, 12 * ms),   # the insert
           ("jit(i)/moe/experts/e", 83 * ms, 6 * ms)]
    runs = {"jit_llm_engine_tick": [
        ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
        ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)],
        "jit_llm_engine_insert": [
        ("jit_llm_engine_insert(7)", 70 * ms, 20 * ms)]}
    spans = [("llm_engine.tick_dispatch", 9 * ms, 1000,
              {"live": "40", "rows": "100000", "window_rows": "50000"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000,
              {"live": "42", "rows": "140000", "window_rows": "70000"}),
             # a tick before the traced interval: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000,
              {"live": "60", "rows": "900000", "window_rows": "100000"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(PS, "program_runs",
                        lambda trace, program, window: runs[program])
    # the engine's own sums over every tick of the run
    engine = types.SimpleNamespace(stats=lambda: {
        "live_rows": 240_000, "kv": {"live_bytes": 1_680_000_000}})
    served = types.SimpleNamespace(
        handle=types.SimpleNamespace(engine=engine))
    run = {"trace": object(), "window": (0, 100 * ms), "named_ops": ops,
           "records": {"recs": [served]}, "config": cfg(), "peaks": PEAKS}
    # (120000 rows x 2048 B + 60000 window rows x 8192 B) x 2 ticks over
    # the 8 ms under the two scopes
    want = 100 * (120_000 * 2048 + 60_000 * 8192) * 2 / 819e9 / 8e-3
    assert reader("window_attn_roofline").read(run) == pytest.approx(want)
    assert want < 100
    assert reader("kv_pool_bytes_per_token").read(run) \
        == pytest.approx(1_680_000_000 / 240_000) == 7000
    assert reader("insert_attn_share").read(run) == pytest.approx(60.0)


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
