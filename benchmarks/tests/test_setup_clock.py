"""`setup_clock.py` and the three readers of PR 49 on hand-made runs:
what a program that keeps the record gives, and None (and no `SETUP`
line) on the shapes the parent's `stats()`, summary and `records`
have."""
import importlib.util
import json
import os
import types

import pytest

import setup_clock as SC

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
NEW = ("warmup_s", "trace_lower_s", "backend_compile_s")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(os.path.dirname(HERE), "layer_metrics",
                                   name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def row(traces, trace, lower, backend, wall):
    return {"traces": traces, "compiles": traces,
            "compile_seconds_total": wall, "trace_seconds": trace,
            "lower_seconds": lower, "backend_seconds": backend}


# ISSUE 49's reading of `mixed-decode-window-moe`, warm cache
ROWS = {"llm_engine_insert": row(4, 4.32, 4.77, 1.11, 12.1),
        "llm_engine_tick": row(1, 1.77, 0.88, 0.36, 3.2)}
STEP = {"train_step": row(4, 3.0, 2.5, 1.0, 14.0)}
AT_WINDOW = {"hits": 9, "misses": 0,
             "seconds": {"trace": 7.0, "lower": 6.5, "backend": 2.5}}
OLD_AT_WINDOW = {"hits": 9, "misses": 0}


def serving_run(warm=True, at=AT_WINDOW):
    stats = {"loop": {}, "traces": {"tick": 1, "insert": 4, "export": 2}}
    if warm:
        stats["warmup"] = {"seconds": 14.1, "programs": ROWS}
    engine = types.SimpleNamespace(stats=lambda: stats)
    refused = types.SimpleNamespace(handle=types.SimpleNamespace())
    rec = types.SimpleNamespace(handle=types.SimpleNamespace(engine=engine))
    return {"records": {"recs": [refused, rec], "cache_at_window": at}}


def training_run(process=True):
    """The driver keeps the SECOND call's summary."""
    summary = {"steps": 100, "loss": 5.0}
    if process:
        summary["setup_seconds"] = {"init_params": 0.04, "place": 0.02,
                                    "h2d": 0.0, "compile_warmup": 2.0}
        summary["setup_process"] = {
            "calls": 2, "programs": STEP,
            "seconds": {"init_params": 3.5, "place": 0.25, "h2d": 0.01,
                        "compile_warmup": 9.0}}
    return {"records": {"summary": summary, "cache_at_window": AT_WINDOW}}


def test_serving_run_reads_the_three(capsys):
    run = serving_run()
    assert reader("warmup_s")(run) == 14.1
    assert reader("trace_lower_s")(run) == pytest.approx(
        4.32 + 4.77 + 1.77 + 0.88)
    assert reader("backend_compile_s")(run) == pytest.approx(1.47)
    reader("trace_lower_s")(run)                    # one line a run
    out = capsys.readouterr().out
    assert out.count("SETUP ") == 1 and out.count("\n") == 1
    assert "SETUP warmup 14.100 s | by program" in out
    assert "llm_engine_insert 4: 4.320 + 4.770 + 1.110 of 12.100" in out
    assert "tracked programs: trace 6.090 + lower 5.650 + backend 1.470" \
        in out
    assert "process at the window: trace 7.000 + lower 6.500 + " \
        "backend 2.500; 9 hits 0 misses" in out
    assert "under no tracked program: trace 0.910 + lower 0.850 + " \
        "backend 1.030" in out


def test_training_run_reads_all_the_processs_calls(capsys):
    """Both `run_pod_training` calls' phases, not the kept summary's own
    (2.06 s of the 12.76): the first call is the cold one."""
    run = training_run()
    assert reader("warmup_s")(run) == pytest.approx(12.76)
    assert reader("trace_lower_s")(run) == pytest.approx(5.5)
    assert reader("backend_compile_s")(run) == pytest.approx(1.0)
    out = capsys.readouterr().out
    assert ("SETUP run_pod_training set-up, 2 calls: 12.760 s = "
            "init_params 3.500 + place 0.250 + h2d 0.010 + "
            "compile_warmup 9.000 | by program") in out
    assert "train_step 4: 3.000 + 2.500 + 1.000 of 14.000" in out


@pytest.mark.parametrize("name", NEW)
@pytest.mark.parametrize("kind", ["serving", "training", "control"])
def test_the_parents_shapes_read_none(capsys, name, kind):
    """The parent keeps `stats()` without `warmup`, a summary without
    `setup_process` and integer-only `cache_at_window`; a control run
    has no records at all."""
    run = {"serving": serving_run(warm=False, at=OLD_AT_WINDOW),
           "training": training_run(process=False),
           "control": {"records": {}}}[kind]
    assert reader(name)(run) is None
    assert "SETUP" not in capsys.readouterr().out


def test_the_record_is_the_programs_own():
    assert SC.record(serving_run()) == {"seconds": 14.1, "programs": ROWS}
    assert SC.record(serving_run(warm=False)) is None
    rec = SC.record(training_run())
    assert (rec["calls"], rec["programs"]) == (2, STEP)
    assert rec["seconds"] == pytest.approx(sum(rec["phases"].values()))
    assert SC.record(training_run(process=False)) is None
    assert SC.stage_sums(ROWS) == pytest.approx(
        {"trace": 6.09, "lower": 5.65, "backend": 1.47})


def test_the_line_stands_without_the_processs_totals(capsys):
    """A driver that stored the parent's integer-only counts."""
    assert reader("trace_lower_s")(serving_run(
        at=OLD_AT_WINDOW)) == pytest.approx(11.74)
    out = capsys.readouterr().out
    assert "SETUP " in out and "process at the window" not in out


@pytest.mark.parametrize("name", NEW)
def test_the_entry_lists_every_cell(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    m = {m["name"]: m for m in bench["per_layer"]}[name]
    assert set(m["workloads"]) == {w["name"] for w in bench["workloads"]}
    assert (m["layer"], m["moves"], m["source"], m["better"],
            m["unit"]) == ("runtime", "setup_s", "program_counter",
                           "lower", "s")
    assert os.path.exists(os.path.join(
        os.path.dirname(HERE), "layer_metrics", name + ".py"))
