"""The trace reduction on a small recorded trace and on hand-made lines."""
import glob
import os

import pytest

import trace_reduce as TR

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def synthetic():
    ops = [("while", 0, 100), ("fusion.1", 0, 40), ("all-reduce.1", 40, 10),
           ("fusion.2", 50, 30), ("fusion.3", 200, 50)]
    mods = [("jit_probe(1)", 0, 100), ("jit_probe(2)", 200, 50)]
    dev = {"/device:TPU:0": {TR.OPS_LINE: ops, TR.MODULE_LINE: mods}}
    marks = [("bench:first", 105, 0), ("bench:token", 255, 0)]
    return TR.Trace(dev, marks)


def test_leaves_and_union():
    t = synthetic()
    lv = TR.op_events(t.devices["/device:TPU:0"])
    assert [e[0] for e in lv] == ["fusion.1", "all-reduce.1", "fusion.2",
                                  "fusion.3"]
    busy, window = TR.busy_seconds(t, (0, 300))
    assert busy == pytest.approx(130e-9) and window == pytest.approx(300e-9)
    assert TR.union([(0, 10), (5, 25), (30, 40)]) == [(0, 25), (30, 40)]


def test_classify_and_gaps():
    t = synthetic()
    kinds = TR.classify_modules(t, {"bench:token": "tick",
                                    "bench:first": "insert"})
    assert kinds == {"jit_probe(1)": "insert", "jit_probe(2)": "tick"}
    d = TR.durations_by_kind(t, kinds, (0, 300))
    assert d["tick"] == [50e-9] and d["insert"] == [100e-9]
    gaps = TR.idle_gaps(t, kinds, 10, (0, 300))
    assert gaps[0][0].startswith("after insert / before tick")
    assert gaps[0][1] == pytest.approx(120e-9)
    assert TR.top_ops(t, 2, (0, 300))[0] == ["fusion.3", pytest.approx(50e-9)]


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(DATA, "*.json.gz")))
                         or [None])
def test_recorded_trace(path):
    if path is None:
        pytest.skip("no recorded trace in tests/data")
    t = TR.read_dump(path)
    assert t.devices, "a recorded TPU trace has a device plane"
    lo = [m for m in t.markers if m[0] == "bench:trace_begin"]
    window = (lo[0][1], max(m[1] for m in t.markers)) if lo else TR.span(t)
    busy, wsec = TR.busy_seconds(t, window)
    assert 0 < busy <= wsec
    rules = {"bench:token": "tick", "bench:first": "insert",
             "bench:step": "train_step"}
    kinds = TR.classify_modules(t, rules)
    assert kinds, "the markers name at least one program"
    assert sum(len(v) for v in TR.durations_by_kind(t, kinds, window).values())
