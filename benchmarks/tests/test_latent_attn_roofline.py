"""`latent_attn_roofline` (PR 36): nothing without its scope, a hand
worked share with it, for both latent configurations."""
import importlib.util
import json
import os

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {"assistant-decode-moe": ("kanana-2-30b-a3b-serve", 8),
         "agent-decode-hybrid": ("kimi-linear-48b-a3b-serve", 2)}
PEAKS = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
MS = 1_000_000


def cfg(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def reader():
    spec = importlib.util.spec_from_file_location(
        "latent_attn_roofline", os.path.join(
            BENCH, "layer_metrics", "latent_attn_roofline.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_declared_for_the_two_latent_cells_only():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        declared = json.load(f)
    m = declared["per_layer"][-1]
    assert m == {"name": "latent_attn_roofline", "unit": "%",
                 "better": "higher", "source": "device_trace",
                 "layer": "model step", "moves": "gap_mean_ms",
                 "workloads": sorted(CELLS, reverse=True)}
    gap = next(e for e in declared["end_to_end"]
               if e["name"] == "gap_mean_ms")
    assert set(CELLS) <= set(gap["workloads"])


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_counts_the_rows_as_the_pool_stores_them(reader, cell):
    name, layers = CELLS[cell]
    c = cfg(name)
    assert reader.latent_layers(c) == layers
    assert reader.cache_row_bytes(c) == 640 * 2        # 512 ‖ 64 ‖ zeros


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_reads_nothing_without_a_trace(reader, cell):
    run = {"trace": None, "window": None, "records": {"recs": []},
           "config": cfg(CELLS[cell][0]), "peaks": PEAKS}
    assert reader.read(run) is None


def _traced(monkeypatch, config, ops):
    """Two ticks of 10 ms inside the window, their dispatches' `rows=`,
    and one dispatch before the window that must not count."""
    import program_spans as PS

    runs = [("jit_llm_engine_tick(2)", 10 * MS, 10 * MS),
            ("jit_llm_engine_tick(2)", 40 * MS, 10 * MS)]
    spans = [("llm_engine.tick_dispatch", 9 * MS, 1000,
              {"live": "20", "rows": "30000"}),
             ("llm_engine.tick_dispatch", 39 * MS, 1000,
              {"live": "22", "rows": "34000"}),
             ("llm_engine.tick_dispatch", -5 * MS, 1000,
              {"live": "60", "rows": "900000"})]
    prog = PS.Program(spans, [])
    monkeypatch.setattr(PS, "load", lambda run: prog)
    monkeypatch.setattr(PS, "program_runs",
                        lambda trace, program, window: runs)
    return {"trace": object(), "window": (0, 60 * MS), "named_ops": ops,
            "records": {"recs": []}, "config": config, "peaks": PEAKS}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_gather_path_reads_nothing(reader, monkeypatch, cell):
    """The parent's tick: `attn` and `kv_gather`, no `attn/paged`."""
    ops = [("jit(t)/kv_gather/gather", 11 * MS, 3 * MS),
           ("jit(t)/attn/dot_general", 14 * MS, 3 * MS),
           ("jit(t)/kv_gather/gather", 41 * MS, 3 * MS),
           ("jit(t)/attn/dot_general", 44 * MS, 3 * MS)]
    run = _traced(monkeypatch, cfg(CELLS[cell][0]), ops)
    assert reader.read(run) is None


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_a_hand_worked_share_under_100(reader, monkeypatch, cell):
    name, layers = CELLS[cell]
    ops = [("jit(t)/attn/paged/cumsum", 11 * MS, 100_000),   # the plan
           ("jit(t)/attn/paged/paged_attention", 12 * MS, 400_000),
           ("jit(t)/attn/dot_general", 13 * MS, 1 * MS),
           ("jit(t)/moe/experts/e", 15 * MS, 4 * MS),
           ("jit(t)/attn/paged/paged_attention", 42 * MS, 500_000)]
    run = _traced(monkeypatch, cfg(name), ops)
    # (30000 + 34000) / 2 rows a tick x 1280 B x layers x 2 ticks, over
    # the 1 ms under attn/paged
    want = 100 * 32_000 * 1280 * layers * 2 / 819e9 / 1e-3
    assert reader.read(run) == pytest.approx(want)
    assert 0 < want <= 100


def test_another_family_reads_nothing(reader, monkeypatch):
    """A dense cell's tick has the scope too (PR 31's kernel) but no
    latent rows: the reader is not for it and says nothing."""
    ops = [("jit(t)/attn/paged/paged_attention", 12 * MS, 400_000)]
    run = _traced(monkeypatch, cfg("mistral-7b-v0.3-serve"), ops)
    assert reader.read(run) is None
