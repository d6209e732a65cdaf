"""The third family's files: its counts against numbers worked by hand
(ISSUE 30), its configuration against the catalog row, its traffic mix
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

import counts_kda_hybrid as K
import traffic
from test_traffic import test_schedule as check_schedule

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "agent-decode-hybrid"


def cfg():
    with open(os.path.join(BENCH, "configs",
                           "kimi-linear-48b-a3b-serve.json")) as f:
        return json.load(f)


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_counts_against_hand_numbers():
    c = cfg()
    assert K.expert_params(c) == 3 * 2304 * 1024 == 7_077_888
    assert K.expert_bytes(c) == 14_155_776                   # 14.16 MB
    # q, k, v, o 4 x 2304*4096; taps 3 x 4*4096; two low-rank gates
    # 2 x (2304*128 + 128*4096); beta 2304*32
    kda = 37_748_736 + 49_152 + 1_638_400 + 73_728
    assert K.kda_params(c) == kda == 39_510_016              # 39.51 M
    # q 2304*6144, kv_a 2304*576, kv_b 512*8192, o 4096*2304
    mla = 14_155_776 + 1_327_104 + 4_194_304 + 9_437_184
    assert K.mla_params(c) == mla == 29_114_368              # 29.11 M
    assert K.layer_kinds(c) == ["kda"] * 3 + ["mla"] + ["kda"] * 3 + ["mla"]
    assert K.router_params(c) == 2304 * 256                  # 0.59 M
    half = 64 * 7_077_888 + 7_077_888 + 589_824              # held + shared
    assert K.expert_half_params(c) == half
    assert K.layer_params(c, 0) == kda + 3 * 2304 * 9216 == 103_211_008
    assert round(K.layer_params(c, 1) * 2 / 1e9, 3) == 1.000     # KDA, GB
    assert round(K.layer_params(c, 3) * 2 / 1e9, 3) == 0.980     # MLA
    assert K.vocab_params(c) == 2 * 40960 * 2304             # 0.377 GB
    assert K.total_params(c) * 2 == 7_544_602_624            # 7.54 GB
    assert round(K.total_params(c) * 2 / 2 ** 30, 2) == 7.03     # GiB
    # all 256 experts of a layer in bf16: 3.62 GB, which no depth fits
    assert round(256 * K.expert_bytes(c) / 1e9, 2) == 3.62
    assert K.latent_bytes_per_token(c) == 2 * 576 * 2
    assert K.kda_state_bytes(c) == 32 * 128 * 128 * 4 == 2_097_152
    assert K.kda_conv_tail_bytes(c) == 3 * 3 * 4096 * 2 == 73_728
    assert K.kda_step_state_traffic(c) == 6 * 2 * 2_097_152
    # 128 slots: 1.61 GB of state, 3.2 GB read and written a full tick
    assert round(128 * 6 * K.kda_state_bytes(c) / 1e9, 2) == 1.61
    assert round(128 * K.kda_step_state_traffic(c) / 1e9, 1) == 3.2
    # one chunk of 64, a head: 2.10 + 1.05 + 4.19 + 0.52 + 2.10 MFLOP
    head = (4 * 64 * 64 * 128 + 64 * 64 * 256 + 4 * 64 * 128 * 128
            + 64 * 64 * 128 + 2 * 64 * 128 * 128)
    assert K.kda_chunk_flops(c, 64) == 32 * head == 318_767_104
    assert K.kda_prefill_flops(c, 2048) == 6 * 32 * 318_767_104
    assert c["constants"] == K.constants(c)


def test_config_is_the_catalog_row_but_for_its_three_cuts():
    """Every key of the catalog's `config` under the same key with the
    same value; depth, experts held and vocabulary alone are reduced,
    and the file states the published counts beside them."""
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog here")
    row = next(r for r in map(json.loads, open(catalog))
               if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    c = cfg()
    assert c["source"] == row["source_url"]
    differs = {k for k, v in row["config"].items() if c.get(k, "absent") != v}
    assert differs == {"num_hidden_layers", "num_experts", "vocab_size"} \
        == set(c["reduced"])
    dep = c["deployment"]
    assert (dep["num_experts"], dep["vocab_size"], dep["num_hidden_layers"]) \
        == tuple(row["config"][k] for k in
                 ("num_experts", "vocab_size", "num_hidden_layers"))
    assert dep["chips_per_layer"] * c["num_experts"] == dep["num_experts"]
    assert dep["chips_per_layer"] * c["vocab_size"] == dep["vocab_size"]
    assert c["precision"]["recurrent_state"] == "float32"
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    entry = next(e for e in declared["configs"]
                 if e["name"] == "kimi-linear-48b-a3b-serve")
    assert set(entry["reduced"]) == set(c["reduced"])
    assert entry["source"] == c["source"]


def test_family_builds_the_config():
    import jax.numpy as jnp

    from families import kda_hybrid_decoder as F

    c = cfg()
    mc = F.model_config(c, max_seq_len=8192, compute_dtype="bfloat16",
                        param_dtype="bfloat16")
    assert (mc.n_layers, mc.dim, mc.vocab_size, mc.n_dense_layers) \
        == (8, 2304, 40960, 1)
    assert mc.kda_layers == (0, 1, 2, 4, 5, 6)
    assert (mc.n_kda_layers, mc.n_mla_layers, mc.n_moe_layers) == (6, 2, 7)
    assert (mc.n_experts, mc.n_held_experts, mc.expert_rank,
            mc.expert_shards, mc.top_k) == (256, 64, 0, 4, 8)
    assert (mc.kda_heads, mc.kda_head_dim, mc.conv_size) == (32, 128, 4)
    assert mc.state_dtype == jnp.float32 and mc.cache_row == 640
    assert mc.serving().init_slot_state is not None
    with pytest.raises(ValueError, match="mla_use_nope"):
        F.model_config(dict(c, mla_use_nope=False), max_seq_len=64,
                       compute_dtype="bfloat16", param_dtype="bfloat16")
    with pytest.raises(ValueError, match="neither"):
        la = dict(c["linear_attn_config"], kda_layers=[1, 2, 3])
        F.model_config(dict(c, linear_attn_config=la), max_seq_len=64,
                       compute_dtype="bfloat16", param_dtype="bfloat16")


@pytest.mark.parametrize("rate", [1.5, 3.0])
def test_agent_mix(rate):
    check_schedule("agent", rate, 256, 7168, 64, 768)
    m = traffic.load("agent")
    lens = sorted(len(r.prompt) for r in traffic.schedule(
        m, rate, 60.0, 5, 40960))
    assert 1800 < np.median(lens) < 2300                 # median 2048
    assert sum(n > 2048 for n in lens) >= len(lens) // 3     # chunked


def test_cell_is_what_the_issue_named():
    with open(os.path.join(BENCH, "workloads", CELL + ".json")) as f:
        cell = json.load(f)
    e = cell["engine"]
    assert (e["num_slots"], e["max_seq_len"], e["kv_block_size"],
            e["num_kv_blocks"], e["decode_block"], e["prefix_cache"]) \
        == (128, 8192, 16, 32768, 1, False)
    assert e["prefill_buckets"] == [256, 512, 1024, 2048]
    assert cell["warm_start"] == round(cell["rate_per_s"] * 10)
    assert (cell["preroll_s"], cell["drain_s"]) == (15.0, 60.0)
    assert (cell["check"]["requests"], cell["check"]["max_tokens"],
            cell["check"]["window_requests"]) == (32, 32, 8)
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    w = next(w for w in declared["workloads"] if w["name"] == CELL)
    assert (w["config"], w["traffic"], w["chips"]) \
        == ("kimi-linear-48b-a3b-serve", "agent", 1)
    listed = {m["name"] for m in declared["per_layer"]
              if CELL in m.get("workloads", ())}
    assert {"tick_kda_share", "kda_state_hbm_share",
            "kda_prefill_roofline", "cache_misses", "tick_moe_share",
            "moe_expert_hbm_share", "tick_latent_attn_share"} <= listed
    assert not {"spill_copy_ms", "spill_land_ms", "decode_hbm_share"} & listed


NEW_READERS = ("tick_kda_share", "kda_state_hbm_share",
               "kda_prefill_roofline")


def test_new_readers_return_nothing_without_their_sources():
    """On a run whose program has no such scope, span argument or
    counter, each new reader returns None and does not raise."""
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(),
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    for name in NEW_READERS:
        assert reader(name).read(dict(run)) is None


def test_new_readers_on_a_made_up_trace(monkeypatch):
    """Two steps, each with one insert and a tick: the state's seconds
    and the counted bytes and operations come out as worked by hand."""
    import program_spans as PS
    import trace_reduce as TR

    ms = 1_000_000
    ops = [("jit(i)/kda/state/a", 1 * ms, 2 * ms),        # insert 1
           ("jit(i)/kda/proj/b", 3 * ms, 1 * ms),
           ("jit(t)/kda/state/c", 11 * ms, 1 * ms),       # tick 1
           ("jit(t)/kda/conv/d", 12 * ms, 1 * ms),
           ("jit(t)/moe/experts/e", 13 * ms, 2 * ms),
           ("jit(i)/kda/state/f", 31 * ms, 4 * ms),       # insert 2
           ("jit(t)/kda/state/g", 41 * ms, 1 * ms)]       # tick 2
    runs = [("jit_llm_engine_insert(1)", 1 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 10 * ms, 10 * ms),
            ("jit_llm_engine_insert(3)", 30 * ms, 8 * ms),
            ("jit_llm_engine_tick(2)", 40 * ms, 10 * ms)]
    spans = [(PS.STEP, 0, 25 * ms, {}),
             ("llm_engine.insert_dispatch", ms // 2, 1000,
              {"bucket": "2048", "tokens": "2048", "state_in": "0"}),
             ("llm_engine.tick_dispatch", 9 * ms, 1000, {"live": "40"}),
             (PS.STEP, 29 * ms, 25 * ms, {}),
             ("llm_engine.insert_dispatch", 29 * ms + 10, 1000,
              {"bucket": "2048", "tokens": "1024", "state_in": "1"}),
             ("llm_engine.tick_dispatch", 39 * ms, 1000, {"live": "60"}),
             # a tick before the traced interval, busier: not counted
             ("llm_engine.tick_dispatch", -5 * ms, 1000, {"live": "128"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(TR, "first_device", lambda trace: {"m": runs})
    monkeypatch.setattr(TR, "module_runs", lambda lines: lines["m"])

    class Handle:
        class engine:
            @staticmethod
            def stats():
                # the whole run's counters (idler than the interval):
                # the state's reader does not weigh them
                return {"counters": {"live_slots": 80, "ticks": 4,
                                     "expert_tokens": np.ones((7, 64))}}

    class Rec:
        handle = Handle

    c = cfg()
    run = {"trace": object(), "window": (0, 60 * ms), "named_ops": ops,
           "records": {"recs": [Rec]}, "config": c,
           "peaks": {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}}
    # tick: kda 3 ms of 20 ms
    assert reader("tick_kda_share").read(run) == pytest.approx(15.0)
    # (40 + 60) / 2 live slots a tick of the interval x 25,165,824 B x 2
    # ticks over 2 ms of kda/state
    want = 100 * 50 * 25_165_824 * 2 / 819e9 / 2e-3
    assert reader("kda_state_hbm_share").read(run) == pytest.approx(want)
    # 3072 real tokens, 6 ms under kda/state in the inserts
    want = 100 * K.kda_prefill_flops(c, 3072) / 197e12 / 6e-3
    assert reader("kda_prefill_roofline").read(run) == pytest.approx(want)
    # a step whose dispatches and executions do not pair up is left out
    spans.append(("llm_engine.insert_dispatch", 30 * ms, 1000,
                  {"bucket": "256", "tokens": "100", "state_in": "0"}))
    want = 100 * K.kda_prefill_flops(c, 2048) / 197e12 / 2e-3
    assert reader("kda_prefill_roofline").read(run) == pytest.approx(want)


@pytest.mark.parametrize("state, caught", [("float32", False),
                                           ("bfloat16", True)])
def test_judge_audits_the_recurrent_state(state, caught):
    """The cell's second control: a program that keeps the recurrent
    state in bf16 serves tokens the statistic cannot tell from sound
    ones, so the judge holds the ENGINE's state against the reference's
    recurrence in float32 (at the family's audit sizes, the file's
    `precision` kept) and gives every token an infinite deficit where
    it lies further off than `STATE_LIMIT`.  float32 reads 5e-7 here
    (1.3e-5 on the chip), a bf16 state 6e-3: the limit has a decade of
    room on either (PERF.md section 2)."""
    from reference import kda_hybrid_decoder as R

    c = cfg()
    c["precision"] = dict(c["precision"], recurrent_state=state)
    got = R.state_shortfall(c)
    assert (got > 10 * R.STATE_LIMIT) if caught \
        else (got < R.STATE_LIMIT / 10), got
    # and through the one door the harness has
    tiny = json.load(open(os.path.join(
        BENCH, "workloads", CELL + ".json")))["rehearsal"]["config"]
    small = dict(c, **{k: dict(c[k], **v) if isinstance(v, dict) else v
                       for k, v in tiny.items()})
    weights = R.init_weights(small, 5, "float32")
    d = R.served_token_deficits(weights, small, [3, 1, 4, 1, 5], [9, 2, 6])
    assert d.shape == (3,) and bool(np.isinf(d).all()) == caught


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
    assert "expert_load_max_over_mean" in line["metrics"]
    assert "cache_misses" in line["metrics"]
