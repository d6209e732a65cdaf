"""`tick_gap.py` and the four readers of PR 34 on a synthetic trace whose
two clocks are a KNOWN distance apart (what is measured on one clock
must not move with it at all), on the recorded run of PR 24 (a program
that writes none of the new spans) and on a recorded run of PR 34."""
import importlib.util
import os
import types

import pytest

import program_spans as PS
import tick_gap as TG
import trace_reduce as TR

HERE = os.path.dirname(os.path.abspath(__file__))
DATA = os.path.join(HERE, "data")
NEW = ("tick_readback_ms", "tick_launch_notify_ms", "tick_host_ms",
       "engine_idle_share")
US, MS = 1_000, 1_000_000


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(os.path.dirname(HERE), "layer_metrics",
                                   name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# What the synthetic engine does, in ns of TRUE time; the host plane
# shows true time, the device plane true time - `offset`.
TICK, INSERT_NS = 14 * MS, 30 * MS
LAUNCH, NOTIFY = 700 * US, 900 * US       # dispatch start -> first op;
#                                           last op -> tick_ready returns
DISPATCH, READBACK, EMIT, GAUGES = 400 * US, 250 * US, 120 * US, 90 * US
CTRL, ADMIT, TURN = 10 * US, 20 * US, 30 * US
H_NS = READBACK + EMIT + GAUGES + TURN + CTRL + ADMIT
G_NS = H_NS + LAUNCH + NOTIFY


def synthetic(offset, ready=True, stranger=False):
    """Six steps that tick (the third admits: an insert before its
    tick), then the engine stands empty for five waits of 20 ms (a step
    that does nothing between two), then three more ticking steps.
    `stranger` puts another program between the last two ticks."""
    spans, mods = [], []

    def sp(name, t0, t1, **args):
        spans.append((name, t0, t1 - t0, {k: str(v) for k, v in args.items()}))
        return t1

    def tick_step(t, admits=False):
        t0 = t
        t = sp("llm_engine.ctrl", t, t + CTRL)
        a0 = t
        if admits:
            d0 = t + 5 * US
            sp("llm_engine.admit_one", d0, d0 + 2 * MS)
            sp("llm_engine.insert_dispatch", d0 + US, d0 + 1 * MS, bucket=256)
            mods.append(("jit_llm_engine_insert(3)", d0 + US + LAUNCH,
                         INSERT_NS))
            t = sp("llm_engine.admit", a0, d0 + 2 * MS + US, admitted=1)
            t = sp("llm_engine.first_token_wait", t,
                   d0 + US + LAUNCH + INSERT_NS + NOTIFY)
        else:
            t = sp("llm_engine.admit", a0, a0 + ADMIT, admitted=0)
        d0 = t
        t = sp("llm_engine.tick_dispatch", d0, d0 + DISPATCH, live=2)
        mods.append(("jit_llm_engine_tick(7)", d0 + LAUNCH, TICK))
        done = d0 + LAUNCH + TICK + NOTIFY
        if ready:
            sp("llm_engine.tick_ready", t, done)
            sp("llm_engine.tick_readback", done, done + READBACK, bytes=128)
        t = sp("llm_engine.tick_wait", t, done + READBACK)
        t = sp("llm_engine.emit", t, t + EMIT)
        t = sp("llm_engine.gauges", t, t + GAUGES)
        sp("llm_engine.step", t0, t)
        return t + TURN

    t = 5 * MS
    for k in range(6):
        t = tick_step(t, admits=(k == 2))
    for _ in range(5):
        t0 = t
        t = sp("llm_engine.ctrl", t, t + CTRL)
        t = sp("llm_engine.admit", t, t + ADMIT, admitted=0)
        t = sp("llm_engine.gauges", t, t + GAUGES)
        sp("llm_engine.step", t0, t)
        t = sp("llm_engine.idle", t + TURN, t + TURN + 20 * MS, queued=0,
               live=0) + TURN
    for k in range(3):
        if stranger and k == 2:
            mods.append(("jit_llm_engine_export(9)", t - TURN - GAUGES,
                         50 * US))
        t = tick_step(t)
    window = (0, t + MS)
    spans.sort(key=lambda s: (s[1], -s[2]))
    mods = sorted(((n, s - offset, d) for n, s, d in mods),
                  key=lambda e: e[1])
    dev = {"/device:TPU:0": {TR.MODULE_LINE: mods,
                             TR.OPS_LINE: [("op", s, d) for _, s, d in mods]}}
    return {"trace": TR.Trace(dev, []), "window": window, "records": {},
            "program": PS.Program(spans, [])}


@pytest.mark.parametrize("offset_ms", [-2, 0, 2])
def test_one_clock_readers_do_not_move_with_the_skew(offset_ms, capsys):
    run = synthetic(offset_ms * MS)
    pairs = TG.quiet_pairs(run["program"], run["trace"], run["window"])
    # steps 2, 4, 5, 6 and the last two follow a tick and admit nothing;
    # the third admits, the seventh follows the empty stretch
    assert len(pairs) == 6
    for p in pairs:
        assert p["G"] == G_NS / 1e6 and p["H"] == H_NS / 1e6
        assert p["launch_notify"] == pytest.approx((LAUNCH + NOTIFY) / 1e6)
        assert p["turn"] == pytest.approx(TURN / 1e6)
        assert p["readback"] == READBACK / 1e6
        assert p["dispatch"] == DISPATCH / 1e6 and p["tick"] == TICK / 1e6
    assert reader("tick_readback_ms")(run) == READBACK / 1e6
    assert reader("tick_launch_notify_ms")(run) == pytest.approx(
        (LAUNCH + NOTIFY) / 1e6)
    lo, hi, n = TG.skew_bounds(run["program"], run["trace"], run["window"])
    assert lo <= offset_ms <= hi and n == 10          # 9 ticks, 1 insert
    assert lo == pytest.approx(offset_ms - LAUNCH / 1e6)
    assert hi == pytest.approx(offset_ms + NOTIFY / 1e6)
    out = capsys.readouterr().out
    assert "CLOCK host-device skew in [%.3f, %.3f] ms over 10 pairs" % (
        lo, hi) in out
    assert "TICK GAP (ms, median of 6 quiet pairs): G %.3f = readback" % (
        G_NS / 1e6) in out and "the parts sum to %.3f;" % (G_NS / 1e6) in out
    assert out.count("TICK GAP") == 1                  # printed once a run


def test_engine_idle_share_moves_little_with_the_skew():
    """The one new reader that lays device idle to a host span: five
    waits of 20 ms in one stretch, clocks 2 ms apart either way."""
    got = {ms: reader("engine_idle_share")(synthetic(ms * MS))
           for ms in (-2, 0, 2)}
    assert 70.0 < got[0] < 95.0
    assert abs(got[-2] - got[0]) < 2.0 and abs(got[2] - got[0]) < 2.0


def test_pairs_left_out():
    """Another program between the two executions, and a trace of a
    program that writes no `tick_ready`."""
    run = synthetic(0, stranger=True)
    assert len(TG.quiet_pairs(run["program"], run["trace"],
                              run["window"])) == 5
    old = synthetic(MS, ready=False)
    pairs = TG.quiet_pairs(old["program"], old["trace"], old["window"])
    assert len(pairs) == 6 and pairs[0]["readback"] == 0.0
    assert pairs[0]["H"] == (H_NS - READBACK) / 1e6
    assert reader("tick_launch_notify_ms")(old) is None
    assert reader("tick_readback_ms")(old) is None
    assert TG.report(old)["launch_notify"] == pytest.approx(
        (LAUNCH + NOTIFY + READBACK) / 1e6)


def test_tick_host_ms_reads_the_engines_clock():
    seconds = dict.fromkeys(("ctrl", "admit", "first_token_wait",
                             "tick_dispatch", "spill_land", "tick_ready",
                             "tick_readback", "emit", "gauges", "idle"), 1.0)
    seconds.update(ctrl=0.1, tick_dispatch=0.8, tick_readback=0.3, emit=0.2,
                   gauges=0.1)
    loop = {"steps": 1200, "ticks": 1000, "seconds": seconds,
            "calls": dict.fromkeys(seconds, 1000)}

    def run(stats):
        engine = types.SimpleNamespace(stats=lambda: stats)
        rec = types.SimpleNamespace(handle=types.SimpleNamespace(
            engine=engine))
        return {"records": {"recs": [rec]}}

    assert reader("tick_host_ms")(run({"loop": loop})) == pytest.approx(1.5)
    assert reader("tick_host_ms")(run({"completed": 3})) is None   # parent
    assert reader("tick_host_ms")({"records": {}}) is None


def recorded(name):
    trace, prog = PS.read_dump(os.path.join(DATA, name))
    begin = next(s[1] for s in prog.spans if s[0] == PS.BEGIN)
    return {"trace": trace, "program": prog, "records": {},
            "window": (begin, TR.span(trace)[1])}


def test_recorded_pr24_has_none_of_the_new_spans(monkeypatch):
    """A v5e run of a program before PR 34: the readers give None, the
    one-clock numbers can still be read with `tick_wait` in
    `tick_ready`'s place, and the two planes are 1.21-3.04 ms apart
    where the gap between two ticks is 2.66."""
    run = recorded("chat-decode_pr24.json.gz")
    assert reader("engine_idle_share")(run) == 0.0     # this tree's engine
    monkeypatch.setattr(TG, "program_writes", lambda span: False)
    for name in NEW:
        assert reader(name)(run) is None, name
    lo, hi, _ = TG.skew_bounds(run["program"], run["trace"], run["window"])
    assert (round(lo, 2), round(hi, 2)) == (1.21, 3.04)
    got = TG.report(run)
    assert got["n"] == 11 and not got["ready"]
    # (ISSUE 34's 0.39 ms for H is the median over the dump's 12 pairs,
    # its 2.66 for G the one over these 11)
    assert round(got["G"], 2) == 2.66 and round(got["H"], 2) == 0.37
    # what `idle_by_span` lays under `tick_wait` is shorter than the
    # distance between the clocks it subtracts
    assert got["G"] < hi and hi - lo > 0.5 * got["G"]


def test_recorded_pr34_every_new_reader_reads(capsys):
    """0.9 s of a traced `chat-decode` run of PR 34's final tree on a v5e
    (seed 3400300001; clipped, operation names cut): 50 ticks, one
    admission, one sampled fence, the engine never empty."""
    run = recorded("chat-decode_pr34.json.gz")
    for name in NEW:
        if name != "tick_host_ms":         # the engine's counter: no trace
            assert isinstance(reader(name)(run), float), name
    got = TG.report(run)
    assert got["ready"] and got["n"] >= 10
    lo, hi, _ = TG.skew_bounds(run["program"], run["trace"], run["window"])
    assert lo <= hi
    parts = sum(got[k] for k in TG.PARTS)
    assert abs(parts - got["G"]) < 0.05 * got["G"]
    assert abs(got["tick"] + got["G"] - got["step"]) < 0.2
    out = capsys.readouterr().out
    assert "TICK GAP" in out and "CLOCK" in out
    assert "WALL SAMPLE (jit.wall_sample): 1 in the trace, 1 inside" in out
