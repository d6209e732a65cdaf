"""The ninth family's files: its counts against numbers worked by hand
(ISSUE 52) and against parameters counted from a built tree, its
configuration against the catalog row, its traffic mix through
`test_traffic.py`'s checks, the family's model config, the control
against its docstring, the new readers on a run without their sources
and on a made-up trace, and a CPU `--rehearse` of its cell end to end,
sound and with the control."""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import counts_ssd_moe as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "swarm-decode-ssd-moe"
NAME = "nemotron-3-nano-30b-a3b-serve"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_READERS = ("tick_ssd_share", "ssd_state_hbm_share",
               "ssd_prefill_roofline", "relu2_expert_hbm_share")


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
    assert (K.n_layers(c, "M"), K.n_layers(c, "E"), K.n_layers(c, "*")) \
        == (6, 6, 2)
    assert (K.d_inner(c), K.conv_width(c), K.shared_width(c)) \
        == (4096, 6144, 3712)
    # W_in 2688 x 10304; 4 taps + bias x 6144; A_log, D, dt_bias 64
    # each; the gated norm 4096; W_out 4096 x 2688; the layer's norm
    assert K.mamba_params(c) == 27_697_152 + 30_720 + 192 + 4_096 \
        + 11_010_048 + 2_688 == 38_744_896
    # q 2688 x 4096, k and v 2688 x 256 each, o 4096 x 2688, the norm
    assert K.attn_params(c) == 2688 * 4608 + 4096 * 2688 + 2688 \
        == 23_399_040
    assert K.expert_params(c) == 2 * 2688 * 1856 == 9_977_856   # TWO
    assert K.shared_params(c) == 2 * 2688 * 3712 == 19_955_712
    assert K.router_params(c) == 2688 * 128 + 128 == 344_192
    assert K.moe_params(c) == 32 * 9_977_856 + 19_955_712 + 344_192 + 2688
    assert K.vocab_params(c) == 2 * 32768 * 2688 == 176_160_768
    assert K.total_params(c) == 2_492_994_816
    assert round(K.total_params(c) * 2 / 1e9, 2) == 4.99
    assert K.expert_bytes(c) == 19_955_712
    assert K.kv_row_bytes(c) == 2 * 2 * 2 * 128 * 2 == 2_048
    assert K.state_bytes(c) == 64 * 64 * 128 * 4 == 2_097_152
    assert K.state_bytes_per_slot(c) == 12_582_912
    assert K.tail_bytes_per_slot(c) == 6 * 3 * 6144 * 2 == 221_184
    assert K.step_state_traffic(c) == 2 * 12_582_912
    # 384 slots: 4.83 GB of state + 0.08 of tails; 61,440 blocks of K/V
    assert round(384 * (12_582_912 + 221_184) / 1e9, 2) == 4.92
    assert round(61_440 * 16 * 2_048 / 1e9, 2) == 2.01
    # a token of the chunked form in one layer: 129 x 128 x 8 + 129 x
    # 64 x 64 + 4 x 128 x 64 x 64 operations; 6144 bf16 + 64 and 4096
    # float32 moved
    assert K.scan_ops(c, 1) == 6 * (132_096 + 528_384 + 2_097_152)
    assert K.scan_bytes(c, 1) == 6 * (12_288 + 256 + 16_384)
    assert K.scan_seconds(c, 2048, PEAKS) == pytest.approx(
        K.scan_bytes(c, 2048) / 819e9)
    assert c["constants"] == K.constants(c)


def test_counts_are_a_built_trees():
    """The counts module against the parameters of a tree built by the
    program's own `init_params` at the configuration's sizes."""
    import jax

    from families import ssd_moe_decoder as F

    c = cfg()
    mc = F.model_config(c, max_seq_len=12288, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    tree = jax.eval_shape(lambda: mc.serving().init_params(
        mc, jax.random.key(0)))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    assert count(tree) == K.total_params(c)
    block = tree["blocks"]
    assert count(block[0]) == 2 * K.mamba_params(c)
    assert count(block[1]) == 2 * K.moe_params(c)
    assert count(block[5]) == 2 * K.attn_params(c)
    state = jax.eval_shape(lambda: mc.serving().init_slot_state(mc, 1))
    assert state["S"].size * 4 == K.state_bytes_per_slot(c)
    assert state["tail"].size * 2 == K.tail_bytes_per_slot(c)
    pool = jax.eval_shape(lambda: mc.serving().init_pool(mc, 1, 16))
    assert sum(x.size * 2 for x in pool.values()) == 16 * K.kv_row_bytes(c)


def test_config_is_the_catalog_row_less_what_is_reduced():
    """Every number of the catalog's `config` under the same key with the
    same value, but the three keys in `reduced` (and the pattern string
    cut with the depth)."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items()
               if c.get(k, "absent") != v}
    assert differs == set(c["reduced"]) | {"hybrid_override_pattern"}
    assert row["config"]["hybrid_override_pattern"].startswith(
        c["hybrid_override_pattern"])
    assert len(c["hybrid_override_pattern"]) == c["num_hidden_layers"] == 14
    dep = c["deployment"]
    assert (dep["chips_per_layer"], dep["rank"], dep["n_routed_experts"],
            dep["vocab_size"], dep["num_hidden_layers"]) \
        == (4, 0, 128, 131072, 52)
    assert (c["n_routed_experts"], c["vocab_size"]) == (32, 32768)
    assert c["precision"]["recurrent_state"] == "float32" and c["chips"] == 1
    assert {"positions", "expand", "norms", "router", "decay",
            "initializer_range", "recurrent_state",
            "expert_weights"} <= set(c["assumed"])
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) \
        and entry["source"] == c["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/" + NAME + ".json"


def test_family_builds_the_config_and_refuses_what_it_cannot_run():
    import jax.numpy as jnp

    from families import ssd_moe_decoder as F

    c = cfg()
    build = lambda c: F.model_config(c, max_seq_len=12288,
                                     compute_dtype="bfloat16",
                                     param_dtype="bfloat16")
    mc = build(c)
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.layout) \
        == (14, 2688, 32768, ("MEMEM*E", 2, ""))
    assert (mc.ssm_heads, mc.ssm_head_dim, mc.ssm_groups, mc.ssm_state,
            mc.conv_size, mc.d_inner, mc.conv_width, mc.heads_a_row) \
        == (64, 64, 8, 128, 4, 4096, 6144, 2)
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim) == (32, 2, 128)
    assert (mc.n_experts, mc.n_held_experts, mc.expert_rank,
            mc.expert_shards, mc.top_k, mc.expert_hidden_dim,
            mc.shared_hidden_dim, mc.routed_scaling_factor) \
        == (128, 32, 0, 4, 6, 1856, 3712, 2.5)
    assert mc.norm_eps == 1e-5 and mc.state_dtype == jnp.float32
    serving = mc.serving()
    assert serving.init_slot_state and serving.init_counts \
        and serving.quantize_int8 and serving.grouped_matmul \
        and serving.paged_attention and not serving.window_kind
    for change, said in (
            ({"precision": {"recurrent_state": "bfloat16"}}, "bfloat16"),
            ({"mlp_hidden_act": "silu"}, "mlp_hidden_act silu"),
            ({"num_hidden_layers": 52}, "a pattern of 14 layers"),
            ({"chunk_size": 256}, "chunk_size"),
            ({"residual_in_fp32": True}, "residual_in_fp32")):
        with pytest.raises(ValueError, match=said):
            build(dict(c, **change))


def test_the_control_is_what_its_docstring_says():
    """Every matmul weight rounded per output channel to at most 255
    levels (an expert's `w_up` along its rows), the routed experts a
    block at a time; the table, the taps, the vectors and the decays
    handed back as they are."""
    import jax
    import jax.numpy as jnp

    from families import ssd_moe_decoder as F
    from reference import ssd_moe_decoder as R

    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        tiny = dict(cfg(), **json.load(f)["rehearsal"]["config"])
    weights = R.init_weights(tiny, 3, jnp.float32)
    # the control first: tracing it deletes the sound bank made last
    rounded = jax.jit(F.lower_precision_params)(weights)
    sound = F.program_params(weights)
    flat = jax.tree_util.tree_flatten_with_path(sound)[0]
    assert jax.tree.structure(sound) == jax.tree.structure(rounded)
    changed = set()
    for (path, w), r in zip(flat, jax.tree.leaves(rounded)):
        name = path[-1].key
        if bool(jnp.any(w != r)):
            changed.add(name)
            axis = -1 if name == "w_up" and w.ndim >= 3 else -2
            top = jnp.max(jnp.abs(r), axis=axis, keepdims=True)
            levels = np.unique(np.asarray(jnp.round(r / top * 127, 3)))
            assert len(levels) <= 255 and w.shape == r.shape
    assert changed == {"w_in", "w_out", "wq", "wk", "wv", "wo", "router",
                       "w_up", "w_down", "ws_up", "ws_down", "lm_head"}


@pytest.mark.parametrize("rate", [4.0, 8.0])
def test_swarm_mix(rate):
    check_schedule("swarm", rate, 128, 8192, 128, 3072)
    m = traffic.load("swarm")
    reqs = traffic.schedule(m, rate, 60.0, 5, 32768)
    lens = sorted(len(r.prompt) for r in reqs)
    outs = sorted(r.max_tokens for r in reqs)
    assert 900 < np.median(lens) < 1150                  # median 1024
    assert 900 < np.median(outs) < 1150                  # median 1024
    assert 0.12 < sum(n > 2048 for n in lens) / len(lens) < 0.28  # chunked
    assert 1200 < np.mean(lens) < 1600 and 1050 < np.mean(outs) < 1300
    assert m["strata_s"] == traffic.load("reason")["strata_s"] \
        == traffic.load("think")["strata_s"] == 10.0


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["decode_block"], e["prefix_cache"], e["kv_layout"]) \
        == (384, 12288, 16, 1, False, "paged")
    assert e["prefill_buckets"] == [256, 512, 1024, 2048]
    assert e["num_kv_blocks"] * 16 >= 900_000
    assert cell["driver"] == "serve_engine" and cell["preroll_s"] == 15.0 \
        and cell["drain_s"] == 120.0
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"], cell["check"]["stat"]) \
        == (32, 32, 8, "mean_deficit")
    # the population a steady state holds: rate x a request's lifetime
    assert 20 * cell["rate_per_s"] < cell["warm_start"] \
        < 70 * cell["rate_per_s"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "swarm", 1)
    assert len(w["why"]) <= 200 and len(declared["workloads"]) == 10
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    # MEMBERSHIP, not the exact set: a later PR may list the cell in a
    # metric of its own (PERF.md section 7, PR 49's note)
    assert set(NEW_READERS) | {
        "gap_p50_ms", "decode_step_ms", "cache_misses", "insert_ms",
        "host_loop_ms", "tick_host_ms", "tick_readback_ms",
        "engine_idle_share", "tick_overlap_share", "tick_moe_share",
        "expert_load_max_over_mean", "warmup_s"} <= listed
    # its reader counts another family's three matrices; two report
    # nothing in cells of this kind (the ledger's notes, PRs 47 and 49)
    assert not {"moe_expert_hbm_share", "admit_stall_ms",
                "tick_launch_notify_ms"} & listed
    for m in declared["per_layer"]:
        if m["name"] in NEW_READERS:
            assert (m["unit"], m["source"], m["layer"], m["moves"]) \
                == ("%", "device_trace", "model step", "gap_mean_ms")
            assert CELL in m["workloads"]
    e2e = {m["name"] for m in declared["end_to_end"]
           if CELL in m.get("workloads", (CELL,))}
    assert {"gap_mean_ms", "setup_s"} <= e2e


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope or span argument, and
    on another family's configuration, each new reader returns None and
    does not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": PEAKS}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None
        assert reader(name).read(dict(run, config={"mb_per_layer": 2})) \
            is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two steps, each with one insert and a tick: the seconds under
    each scope and the counted bytes come out as worked by hand."""
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(i)/while/body/ssd/state/a", 1 * ms, 2 * ms),
           ("jit(i)/while/body/ssd/proj/b", 3 * ms, 1 * ms),
           ("jit(i)/while/body/moe/experts/c", 4 * ms, 1 * ms),
           ("jit(t)/while/body/ssd/state/jit(_step_live)/d", 11 * ms, 1 * ms),
           ("jit(t)/while/body/ssd/conv/e", 12 * ms, 1 * ms),
           ("jit(t)/while/body/moe/experts/f", 13 * ms, 4 * ms),
           ("jit(t)/while/body/attn/paged/g", 17 * ms, 1 * ms),
           ("jit(i)/while/body/ssd/state/h", 31 * ms, 3 * ms),
           ("jit(i)/while/body/attn/i", 35 * ms, 2 * ms),
           ("jit(t)/while/body/ssd/state/j", 41 * ms, 1 * ms),
           ("jit(t)/while/body/moe/experts/k", 42 * ms, 4 * ms),
           ("jit(t)/while/body/moe/shared/l", 46 * ms, 1 * ms)]
    runs = [("jit_llm_engine_insert(1)", 1 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_insert(3)", 30 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [(PS.STEP, 0, 25 * ms, {}),
             ("llm_engine.insert_dispatch", ms // 2, 1000,
              {"bucket": "2048", "tokens": "2048", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 9 * ms, 1000, {"live": "200"}),
             ("llm_engine.emit", 21 * ms, 1000,
              {"experts_touched": "1000", "ticks": "10"}),
             (PS.STEP, 29 * ms, 25 * ms, {}),
             ("llm_engine.insert_dispatch", 29 * ms + 10, 1000,
              {"bucket": "512", "tokens": "300", "state_in": "1"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000, {"live": "240"}),
             ("llm_engine.emit", 51 * ms, 1000,
              {"experts_touched": "1180", "ticks": "11"}),
             # a tick before the traced interval, busier: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000, {"live": "384"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])
    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": []}, "config": c, "peaks": PEAKS}
    # tick: ssd 3 ms of 20 ms
    assert reader("tick_ssd_share").read(run) == pytest.approx(15.0)
    # (200 + 240) / 2 live slots a tick x 25,165,824 B x 2 ticks over 2 ms
    want = 100 * 220 * 25_165_824 * 2 / 819e9 / 2e-3
    assert reader("ssd_state_hbm_share").read(run) == pytest.approx(want)
    # 180 experts touched in the 1 tick between the two emits, laid on
    # 2 executions, x 19,955,712 B over 8 ms under moe/experts
    want = 100 * 180 * 2 * 19_955_712 / 819e9 / 8e-3
    assert reader("relu2_expert_hbm_share").read(run) == pytest.approx(want)
    # 2,348 real tokens' bytes over 5 ms under ssd/state in the inserts
    want = 100 * K.scan_bytes(c, 2348) / 819e9 / 5e-3
    assert reader("ssd_prefill_roofline").read(run) == pytest.approx(want)


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
    """Every listed host metric is printed; the device_trace readers
    read nothing on the CPU (no TPU plane: `null`, left out)."""
    line = _rehearse()
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    host = {"gap_p50_ms", "cache_misses", "host_loop_ms", "tick_host_ms",
            "tick_readback_ms", "engine_idle_share", "tick_overlap_share",
            "idle_attributed_share", "expert_load_max_over_mean",
            "warmup_s"}
    assert host <= set(line["metrics"])
    assert not set(NEW_READERS) & set(line["metrics"])


def test_rehearsal_with_the_control_is_not_correct():
    line = _rehearse("--control")
    assert line["rehearsal"] and line["control"] and not line["correct"]
