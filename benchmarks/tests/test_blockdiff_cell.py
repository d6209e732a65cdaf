"""The tenth family's files: its counts against numbers worked by hand
(ISSUE 55) and against parameters counted from a built tree, its
configuration against the catalog row, its traffic mix, the family's
model config, the new readers on a run without their sources, on
made-up records and on a made-up trace, the reference's inference of
the order a generation was fixed in, and a CPU `--rehearse` of its cell
end to end, sound and with the control."""
import importlib.util
import json
import os
import subprocess
import sys
import types

import numpy as np
import pytest

import counts_blockdiff_moe as K
import traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "solve-decode-blockdiff-moe"
NAME = "sdar-30b-a3b-chat-serve"
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
NEW_READERS = ("block_gap_p50_ms", "tokens_per_forward",
               "tick_block_attn_share", "block_attn_roofline")


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
    # q and o 2048 x 4096 each, k and v 2048 x 512 each
    assert K.attention_params(c) == 2 * 2048 * 4096 + 2 * 2048 * 512 \
        == 18_874_368
    assert K.router_params(c) == 2048 * 128 == 262_144
    assert K.expert_params(c) == 3 * 2048 * 768 == 4_718_592
    assert K.layer_params(c) == 18_874_368 + 262_144 + 32 * 4_718_592 \
        == 170_131_456
    assert K.vocab_params(c) == 2 * 151_936 * 2048 == 622_329_856
    assert K.total_params(c) == 12 * 170_131_456 + 622_329_856 \
        == 2_663_907_328
    assert round(K.total_params(c) * 2 / 1e9, 2) == 5.33
    # a whole layer, 128 experts: 623 M = 1.246 GB; the model 30.5 B
    assert round((18_874_368 + 262_144 + 128 * 4_718_592) * 2 / 1e9, 3) \
        == 1.246
    assert round(K.published_total_params(c) / 1e9, 1) == 30.5
    assert K.expert_bytes(c) == 9_437_184
    assert K.kv_row_bytes(c) == 2 * 4 * 128 * 2 == 2_048
    assert K.kv_bytes_per_token(c) == 24_576
    # 20,480 blocks of 16 rows: 8.05 GB of K and V
    assert round(20_480 * 16 * 24_576 / 1e9, 2) == 8.05
    assert K.paged_attention_bytes(c, 1000) == 24_576_000
    assert K.paged_attention_flops(c, 1000) \
        == 12 * 2 * 2 * 4 * 32 * 128 * 1000
    # a forward that touches every held expert of every layer: 4.7 GB
    assert round(K.forward_weight_bytes(c, 12 * 32) / 1e9, 2) == 4.71
    assert c["constants"] == K.constants(c)


def test_counts_are_a_built_trees():
    import jax

    from families import blockdiff_moe_decoder as F

    c = cfg()
    mc = F.model_config(c, max_seq_len=3072, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    tree = jax.eval_shape(lambda: mc.serving().init_params(
        mc, jax.random.key(0)))
    count = lambda t: sum(int(np.prod(x.shape)) for x in jax.tree.leaves(t))
    norms = 12 * (2 * 2048 + 2 * 128) + 2048
    assert count(tree) == K.total_params(c) + norms
    pool = jax.eval_shape(lambda: mc.serving().init_pool(mc, 1, 16))
    assert sum(x.size * 2 for x in pool.values()) \
        == 16 * K.kv_bytes_per_token(c)


def test_config_is_the_catalog_row_less_what_is_reduced():
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "SDAR-30B-A3B-Chat")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items()
               if c.get(k, "absent") != v}
    assert differs == set(c["reduced"]) == {"num_hidden_layers",
                                            "num_experts"}
    dep = c["deployment"]
    assert (dep["chips_per_layer"], dep["rank"], dep["num_experts"],
            dep["num_hidden_layers"]) == (4, 0, 128, 48)
    assert (c["num_experts"], c["num_hidden_layers"]) == (32, 12)
    assert (c["block_length"], c["denoising_steps"],
            c["remasking_strategy"], c["confidence_threshold"],
            c["mask_token_id"], c["initializer_range"]) \
        == (4, 4, "low_confidence_dynamic", 0.9, 151669, 0.02)
    assert {"block_length", "denoising_steps", "remasking_strategy",
            "confidence_threshold", "mask_token_id",
            "initializer_range"} <= set(c["assumed"])
    assert c["precision"]["router"] == "float32" and c["chips"] == 1
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"] if e["name"] == NAME)
    assert sorted(entry["reduced"]) == sorted(c["reduced"]) \
        and entry["source"] == c["source"] and len(entry["why"]) <= 200
    assert entry["file"] == "benchmarks/configs/" + NAME + ".json"


def test_family_builds_the_config_and_refuses_what_it_cannot_run():
    from families import blockdiff_moe_decoder as F

    c = cfg()
    build = lambda c: F.model_config(c, max_seq_len=3072,
                                     compute_dtype="bfloat16",
                                     param_dtype="bfloat16")
    mc = build(c)
    assert (mc.n_layers, mc.dim, mc.vocab_size) == (12, 2048, 151936)
    assert (mc.n_heads, mc.n_kv_heads, mc.head_dim, mc.rope_theta,
            mc.norm_eps) == (32, 4, 128, 1e6, 1e-6)
    assert (mc.n_experts, mc.n_held_experts, mc.expert_rank,
            mc.expert_shards, mc.top_k, mc.expert_hidden_dim) \
        == (128, 32, 0, 4, 8, 768)
    assert (mc.block_length, mc.denoising_steps, mc.remasking,
            mc.confidence_threshold, mc.mask_token_id) \
        == (4, 4, "low_confidence_dynamic", 0.9, 151669)
    serving = mc.serving()
    assert serving.block and serving.init_counts and serving.grouped_matmul \
        and serving.paged_attention and not serving.window_kind \
        and not serving.init_slot_state and not serving.verify
    assert tuple(serving.block.spec(mc)) \
        == (4, 4, "low_confidence_dynamic", 0.9, 151669)
    for change, said in (
            ({"mlp_only_layers": [0]}, "dense feed-forward"),
            ({"norm_topk_prob": False}, "norm_topk_prob"),
            ({"tie_word_embeddings": True}, "tie_word_embeddings"),
            ({"use_sliding_window": True}, "sliding window"),
            ({"block_length": 3}, "power of two"),
            ({"remasking_strategy": "random"}, "remasking")):
        with pytest.raises(ValueError, match=said):
            build(dict(c, **change))


@pytest.mark.parametrize("rate", [4.0, 8.0])
def test_solve_mix(rate):
    m = traffic.load("solve")
    for seed in (5, 4000000123):
        reqs = traffic.schedule(m, rate, 60.0, seed, 151936, warm=10)
        due = [r for r in reqs if r.due_s > 0]
        lens = sorted(len(r.prompt) for r in due)
        assert len(due) == round(rate * 60)
        assert lens[0] >= 32 and lens[-1] <= 2048        # none chunked
        assert 220 < np.median(lens) < 300               # median 256
        assert {r.max_tokens for r in due} == {1024}     # ONE length
        # every stretch of 10 s offers the same work, whatever the seed
        per = [sorted(len(r.prompt) for r in due
                      if a <= r.due_s < a + 10) for a in range(0, 60, 10)]
        assert all(p == per[0] for p in per)
    assert m["strata_s"] == traffic.load("reason")["strata_s"] == 10.0


def test_cell_is_what_the_issue_named():
    cell = cell_file()
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["decode_block"], e["prefix_cache"], e["kv_layout"],
            e["num_kv_blocks"]) == (256, 3072, 16, 1, False, "paged", 20480)
    assert e["prefill_buckets"] == [256, 512, 1024, 2048]
    assert cell["driver"] == "serve_engine" and cell["preroll_s"] == 15.0
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"], cell["check"]["stat"]) \
        == (32, 32, 8, "mean_deficit")
    assert cell["check"]["max_tokens"] % 4 == 0     # whole blocks judged
    # the population a steady state holds: rate x a stream's life
    assert 30 * cell["rate_per_s"] < cell["warm_start"] \
        < 60 * cell["rate_per_s"]
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) == (NAME, "solve", 1)
    assert len(w["why"]) <= 200
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert set(NEW_READERS) | {
        "decode_step_ms", "insert_ms", "cache_misses", "host_loop_ms",
        "tick_host_ms", "tick_readback_ms", "engine_idle_share",
        "tick_overlap_share", "tick_moe_share", "moe_expert_hbm_share",
        "expert_load_max_over_mean", "warmup_s"} <= listed
    # the median gap is 0 by construction, and a first token follows a
    # block's last tick, not an insert (ISSUE 55)
    assert not {"gap_p50_ms", "stall_gap_p95_ms",
                "tick_launch_notify_ms"} & listed
    for m in declared["per_layer"]:
        if m["name"] in NEW_READERS:
            assert m["moves"] == "gap_mean_ms" and m["workloads"] == [CELL]
    e2e = {m["name"] for m in declared["end_to_end"]
           if CELL in m.get("workloads", (CELL,))}
    assert {"gap_mean_ms", "setup_s"} <= e2e


def test_new_readers_return_nothing_without_their_sources():
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(), "peaks": PEAKS}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None
        assert reader(name).read(dict(run, config={"mb_per_layer": 2})) \
            is None


def test_host_readers_on_made_up_records():
    """Two streams whose blocks land 40 and 50 ms apart; a prompt of 6
    opens its first block two tokens in."""
    def rec(P, times):
        return types.SimpleNamespace(
            req=types.SimpleNamespace(prompt=[0] * P), times=times,
            handle=types.SimpleNamespace(engine=types.SimpleNamespace(
                stats=lambda: {"counters": {
                    "block_tokens_fixed": np.int32(800),
                    "block_forwards": np.int32(1000)}})))

    a = rec(8, [1.0, 1.0, 1.0, 1.0, 1.04, 1.04, 1.04, 1.04, 1.08])
    b = rec(6, [1.01, 1.01, 1.06, 1.06, 1.06, 1.06, 1.11])
    run = {"trace": None, "window": None, "config": cfg(), "peaks": PEAKS,
           "records": {"window": (1.0, 2.0), "recs": [a, b]}}
    # gaps 40, 40 (a) and 50, 50 (b): the median
    assert reader("block_gap_p50_ms").read(run) == pytest.approx(45.0)
    assert reader("tokens_per_forward").read(run) == pytest.approx(0.8)


def test_new_readers_on_a_made_up_trace(monkeypatch):
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(t)/attn/block_write/a", 11 * ms, 1 * ms),
           ("jit(t)/attn/paged/jit(_call)/b", 12 * ms, 3 * ms),
           ("jit(t)/moe/experts/c", 15 * ms, 4 * ms),
           ("jit(t)/unmask/d", 19 * ms, 1 * ms),
           ("jit(t)/attn/block_write/e", 41 * ms, 1 * ms),
           ("jit(t)/attn/paged/f", 42 * ms, 5 * ms)]
    runs = [("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [(PS.STEP, 0, 25 * ms, {}),
             ("llm_engine.tick_dispatch", 9 * ms, 1000, {"rows": "100000"}),
             (PS.STEP, 29 * ms, 25 * ms, {}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000, {"rows": "120000"}),
             ("llm_engine.tick_dispatch", -5 * ms, 1000, {"rows": "9"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": []}, "config": cfg(), "peaks": PEAKS}
    # block_write 2 ms + paged 8 ms of 20 ms
    assert reader("tick_block_attn_share").read(run) == pytest.approx(50.0)
    # 110,000 rows a tick x 24,576 B x 2 ticks over 8 ms under attn/paged
    want = 100 * 110_000 * 24_576 * 2 / 819e9 / 8e-3
    assert reader("block_attn_roofline").read(run) == pytest.approx(want)


def test_the_reference_recovers_a_recorded_order():
    """A generation whose order the test recorded (`generate`'s
    `record`): judged from its tokens alone, every deficit is 0, and
    the order `served_token_deficits` walks is the recorded one (a
    token moved to another place of its block is charged)."""
    import jax.numpy as jnp

    from reference import blockdiff_moe_decoder as R

    c = dict(cfg(), **cell_file()["rehearsal"]["config"])
    w = R.init_weights(c, 11, jnp.float32)
    prompt = [int(t) for t in np.random.RandomState(2).randint(0, 512, 10)]
    order = []
    served = R.generate(w, c, prompt, 14, record=order)
    assert len(served) == 14 and len(order) >= 14
    # a low-confidence rule does not walk left to right
    assert [p for _, _, p in order] != sorted([p for _, _, p in order])
    assert float(R.served_token_deficits(w, c, prompt, served).max()) == 0.0
    # two tokens of ONE block change places (the prompt's tail holds the
    # first block's first two positions: blocks start at served[2])
    i = next(i for i in range(2, 14, 4) if served[i] != served[i + 1])
    swapped = list(served)
    swapped[i], swapped[i + 1] = swapped[i + 1], swapped[i]
    assert float(R.served_token_deficits(w, c, prompt, swapped).max()) > 0.1


def _rehearse(*extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "4000000123", "--seconds", "4", "--trace", "1",
         "--rehearse", *extra],
        capture_output=True, text=True, env=env, timeout=1500)
    assert out.returncode == 0, out.stderr[-2000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


def test_rehearsal_runs_the_cell_end_to_end():
    line = _rehearse()
    assert line["rehearsal"] and line["correct"] and not line["failed"]
    host = {"cache_misses", "host_loop_ms", "tick_host_ms",
            "tick_readback_ms", "engine_idle_share", "tick_overlap_share",
            "idle_attributed_share", "expert_load_max_over_mean",
            "warmup_s", "block_gap_p50_ms", "tokens_per_forward"}
    assert host <= set(line["metrics"])
    assert 0.79 < line["metrics"]["tokens_per_forward"]["value"] <= 0.8
    assert not {"tick_block_attn_share", "block_attn_roofline"} \
        & set(line["metrics"])


def test_rehearsal_with_the_control_is_not_correct():
    line = _rehearse("--control")
    assert line["rehearsal"] and line["control"] and not line["correct"]
