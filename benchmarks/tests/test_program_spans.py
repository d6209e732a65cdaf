"""The readers of what the program writes into a trace (program_spans and
the nine layer metrics of PR 24), on hand-made events and on one
recorded traced run of each cell (`data/*_pr24.json.gz`: spans, scopes
and program names as a v5e wrote them; clipped, operation names cut)."""
import glob
import importlib.util
import os

import pytest

import program_spans as PS
import trace_reduce as TR

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
CHAT = ("admit_stall_ms", "spill_copy_ms", "insert_ms", "host_loop_ms",
        "tick_kv_gather_share", "idle_attributed_share")
TRAIN = ("optimizer_share", "loss_head_share", "step_dispatch_ms")


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(os.path.dirname(HERE), "layer_metrics",
                                   name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def sp(name, start, end, **args):
    return (name, start, end - start, {k: str(v) for k, v in args.items()})


def synthetic(admit=True):
    """Two engine steps of 1000 ns.  The first admits: a spill (100-400)
    with the device idle, then an insert (program 450-600), then a tick
    (program 650-950).  The second only ticks (1050-1950)."""
    spans = [sp("llm_engine.step", 0, 1000), sp("llm_engine.ctrl", 0, 10),
             sp("llm_engine.admit", 10, 500, admitted=1),
             sp("llm_engine.tick_dispatch", 620, 660, live=2),
             sp("llm_engine.tick_wait", 660, 960),
             sp("llm_engine.emit", 960, 990),
             sp("llm_engine.step", 1000, 2000),
             sp("llm_engine.admit", 1010, 1020, admitted=0),
             sp("llm_engine.tick_dispatch", 1020, 1060, live=2),
             sp("llm_engine.tick_wait", 1060, 1960),
             sp("llm_engine.emit", 1960, 1990)]
    mods = [("jit_llm_engine_tick(7)", 650, 300),
            ("jit_llm_engine_tick(7)", 1050, 900)]
    ops = [("kv_gather", 650, 200), ("attn", 850, 100),
           ("kv_gather", 1050, 600), ("", 1650, 300)]
    if admit:
        spans += [sp("llm_engine.admit_one", 20, 490),
                  sp("llm_engine.evict", 90, 410, blocks=3),
                  sp("llm_engine.spill", 100, 400, evicted_blocks=3),
                  sp("llm_engine.insert_dispatch", 420, 480, bucket=128),
                  sp("llm_engine.first_token_wait", 500, 610)]
        mods.append(("jit_llm_engine_insert(3)", 450, 150))
        ops.append(("attn", 450, 150))
    spans.sort(key=lambda s: (s[1], -s[2]))
    dev = {"/device:TPU:0": {
        TR.MODULE_LINE: sorted(mods, key=lambda e: e[1]),
        TR.OPS_LINE: sorted((("op", s, d) for _, s, d in ops),
                            key=lambda e: e[1])}}
    return {"trace": TR.Trace(dev, []), "window": (0, 2000), "records": {},
            "program": PS.Program(spans, sorted(ops, key=lambda o: o[1]))}


def test_durations_children_and_steps():
    run = synthetic()
    ms = PS.durations_ms(run["program"], run["window"])
    assert ms["llm_engine.spill"] == [pytest.approx(300e-6)]
    assert len(ms["llm_engine.step"]) == 2
    first = PS.in_window(run["program"], run["window"], PS.STEP)[0]
    kids = [k[0] for k in PS.children(run["program"], first)]
    assert kids[:4] == ["llm_engine.ctrl", "llm_engine.admit",
                        "llm_engine.admit_one", "llm_engine.evict"]
    a, b = PS.engine_steps(run["program"], run["window"])
    assert a["admitted"] and not b["admitted"]
    assert a["to_dispatch_ms"] == pytest.approx(660e-6)
    assert b["host_ms"] == pytest.approx(100e-6)
    # a window that cuts the second step keeps only the first
    assert len(PS.engine_steps(run["program"], (0, 1500))) == 1


def test_idle_is_laid_to_the_innermost_span_and_split_when_it_straddles():
    run = synthetic()
    by = PS.idle_by_span(run["program"], run["trace"], run["window"])
    # busy: insert 450-600, ticks 650-950 and 1050-1950.  Idle 0-450: ctrl
    # 10, admit 10, admit_one 70 + 10 (410-420, between evict's end and
    # insert_dispatch), evict 10 + 10, spill 300, insert_dispatch 30
    assert by["llm_engine.spill"] == pytest.approx(300e-9)
    assert by["llm_engine.evict"] == pytest.approx(20e-9)
    assert by["llm_engine.admit_one"] == pytest.approx(80e-9)
    assert by["llm_engine.insert_dispatch"] == pytest.approx(30e-9)
    # the gap 600-650 straddles first_token_wait (to 610), the bare step
    # (610-620) and tick_dispatch (620-650); 950-1050 straddles two steps
    assert by["llm_engine.first_token_wait"] == pytest.approx(10e-9)
    assert by["llm_engine.step"] == pytest.approx(40e-9)
    assert by["llm_engine.tick_dispatch"] == pytest.approx(60e-9)
    assert sum(by.values()) == pytest.approx(650e-9)
    share = reader("idle_attributed_share")(run)
    assert share == pytest.approx(100.0 * (650 - 40) / 650)


def test_scopes_and_programs_by_name():
    run = synthetic()
    ticks = PS.program_runs(run["trace"], "jit_llm_engine_tick", (0, 2000))
    assert [r[1] for r in ticks] == [650, 1050]
    by = PS.scope_seconds(run["program"], ticks)
    assert by == {"kv_gather": pytest.approx(800e-9),
                  "attn": pytest.approx(100e-9), "": pytest.approx(300e-9)}
    assert reader("tick_kv_gather_share")(run) == pytest.approx(
        100.0 * 800 / 1200)
    assert reader("insert_ms")(run) == pytest.approx(150e-6)
    assert PS.scope_of("jit(f)/while/body/kv_gather/gather") == "kv_gather"
    assert PS.scope_of("jit(f)/transpose(jvp(layers))/while/body/attn/mul"
                       ) == "attn"
    assert PS.scope_of("jit(f)/transpose(jvp(loss_head))/mul") == "loss_head"
    assert PS.scope_of("jit(f)/jvp(layers)/while/body/squeeze") == "layers"
    assert PS.scope_of("jit(f)/add") == PS.scope_of("") == ""


def test_a_window_without_admission():
    run = synthetic(admit=False)
    assert reader("spill_copy_ms")(run) is None
    assert reader("admit_stall_ms")(run) is None
    assert reader("insert_ms")(run) is None
    # both steps count: 1000 - 300 and 1000 - 900 ns, the median of two
    assert reader("host_loop_ms")(run) == pytest.approx(400e-6)
    admitted = synthetic()
    assert reader("host_loop_ms")(admitted) == pytest.approx(100e-6)
    assert reader("spill_copy_ms")(admitted) == pytest.approx(300e-6)
    assert reader("admit_stall_ms")(admitted) == pytest.approx(660e-6)
    no_spill = synthetic()
    no_spill["program"].spans = [s for s in no_spill["program"].spans
                                 if s[0] != "llm_engine.spill"]
    assert reader("spill_copy_ms")(no_spill) == 0.0


@pytest.mark.parametrize("name", CHAT + TRAIN)
def test_readers_return_none_without_program_spans(name):
    """A trace of the parent commit: programs are `jit_probe`, no span,
    no scope.  Every reader says None; none raises."""
    run = synthetic()
    dev = run["trace"].devices["/device:TPU:0"]
    dev[TR.MODULE_LINE] = [("jit_probe(1)", s, d)
                           for _, s, d in dev[TR.MODULE_LINE]]
    for program in (PS.Program([], []), None):
        run["program"] = program
        assert reader(name)(run) is None
    assert reader(name)({"trace": None, "window": None, "records": {}}) is None


# ------------------------------------------------- the file's wire format

def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _f(field, value):
    if isinstance(value, int):
        return _varint(field << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(field << 3 | 2) + _varint(len(value)) + value


def test_device_ops_reads_the_op_name_of_the_events_metadata():
    stat_meta = _f(5, _f(1, 9) + _f(2, _f(1, 9) + _f(2, "tf_op")))
    ref_meta = _f(5, _f(1, 4) + _f(2, _f(1, 4) + _f(2, "jit(t)/mlp/dot")))

    def event_meta(i, name, stat):
        return _f(4, _f(1, i) + _f(2, _f(1, i) + _f(2, name)
                                    + _f(5, _f(1, 9) + stat)))

    metas = (event_meta(1, "%while", _f(5, "jit(t)/layers/while"))
             + event_meta(2, "%gather", _f(5, "jit(t)/layers/while/body/"
                                           "kv_gather/gather"))
             + event_meta(3, "%dot", _f(7, 4)))            # a ref_value
    events = (_f(4, _f(1, 1) + _f(2, 1_000_000) + _f(3, 900_000))
              + _f(4, _f(1, 2) + _f(2, 1_000_000) + _f(3, 500_000))
              + _f(4, _f(1, 3) + _f(2, 1_500_000) + _f(3, 400_000)))
    line = _f(3, _f(2, "XLA Ops") + _f(3, 7) + events)
    other = _f(3, _f(2, "XLA Modules") + _f(3, 7) + _f(
        4, _f(1, 1) + _f(2, 1_000_000) + _f(3, 900_000)))
    plane = _f(1, _f(2, "/device:TPU:0") + other + line + metas + stat_meta
               + ref_meta)
    host = _f(1, _f(2, "/host:CPU"))
    assert PS.device_ops(host + plane) == [("kv_gather", 1007, 500),
                                           ("mlp", 1507, 400)]
    assert PS.device_ops(host) == []


# ------------------------------------------------------- recorded traces

def recorded(cell):
    path = os.path.join(DATA, cell + "_pr24.json.gz")
    if not os.path.exists(path):
        pytest.skip("no recorded PR 24 trace of " + cell)
    trace, prog = PS.read_dump(path)
    begin = [m[1] for m in trace.markers if m[0] == "bench:trace_begin"][0]
    return {"trace": trace, "program": prog, "records": {},
            "window": (begin, TR.span(trace)[1])}


def test_recorded_chat_decode():
    run = recorded("chat-decode")
    values = {n: reader(n)(run) for n in CHAT}
    assert all(v is not None for v in values.values()), values
    assert values["idle_attributed_share"] >= 95.0
    assert 100 < values["spill_copy_ms"] < 1000        # F13: 240-300 ms
    assert values["admit_stall_ms"] >= values["spill_copy_ms"]
    assert 0.2 < values["host_loop_ms"] < 10
    assert 5 < values["insert_ms"] < 100
    assert 20 < values["tick_kv_gather_share"] < 95
    ticks = PS.program_runs(run["trace"], "jit_llm_engine_tick",
                            run["window"])
    by = PS.scope_seconds(run["program"], ticks)
    assert sum(by.values()) <= sum(r[2] for r in ticks) / 1e9 * 1.0001
    assert by.get("", 0.0) / sum(by.values()) < 0.10   # PERF.md section 5
    idle = PS.idle_by_span(run["program"], run["trace"], run["window"])
    assert max(idle, key=idle.get) == "llm_engine.spill"
    off = PS.tick_clock_offsets_us(run["program"], run["trace"],
                                   run["window"])
    assert len(off["wait_end_after_device_end"]) == len(ticks)
    assert all(0 < x < 20_000 for x in off["wait_end_after_device_end"])
    # every step's children lie inside it and in start order
    for st in PS.in_window(run["program"], run["window"], PS.STEP):
        kids = PS.children(run["program"], st)
        assert kids and all(k[1] >= st[1] for k in kids)


def test_recorded_pretrain_1chip():
    run = recorded("pretrain-1chip")
    values = {n: reader(n)(run) for n in TRAIN}
    assert all(v is not None for v in values.values()), values
    assert 2 < values["optimizer_share"] < 15
    assert 5 < values["loss_head_share"] < 40
    assert 0 < values["step_dispatch_ms"] < 20
    steps = PS.program_runs(run["trace"], "jit_train_step", run["window"])
    by = PS.scope_seconds(run["program"], steps)
    assert {"attn", "mlp", "loss_head", "optimizer", "layers"} <= set(by)
    names = {s[0] for s in run["program"].spans}
    assert {"train.step", "train.compute", "train.weight_publish"} <= names


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    DATA, "*_pr24.json.gz"))) or [None])
def test_recorded_dump_also_serves_trace_reduce(path):
    if path is None:
        pytest.skip("no recorded PR 24 trace")
    trace = TR.read_dump(path)
    assert trace.devices and trace.markers
    assert all(r[0].startswith("jit_") and not r[0].startswith("jit_probe")
               for r in TR.module_runs(TR.first_device(trace)))
