"""Names in the profiler's trace (PR 24): each tracked program lowers
under its own name, the engine step and the train loop write host spans
through `observability.profiling.trace_span`, and the model's operations
carry scope names that change nothing in the compiled program."""

import contextlib
import glob
import os
import re
import threading
import time

import pytest

STEP_CHILDREN = ["ctrl", "admit", "first_token_wait", "settle",
                 "tick_dispatch", "spill_land", "tick_wait", "emit", "gauges"]
LOOP_PHASES = ["ctrl", "admit", "first_token_wait", "settle",
               "tick_dispatch", "spill_land", "tick_ready", "tick_readback",
               "emit", "gauges", "idle"]


# ------------------------------------------------------- program names

@pytest.mark.parametrize("name, module", [
    ("x", "jit_x"), ("llm_engine_tick", "jit_llm_engine_tick"),
    ("a b/c", "jit_a_b_c")])
def test_tracked_jit_lowers_under_its_own_name(name, module):
    import jax.numpy as jnp

    from ray_tpu.observability import tracked_jit

    f = tracked_jit(lambda x: x * 2, name=name)
    g = tracked_jit(lambda x: x + 1, name=name + "_other")
    x = jnp.ones((4,))
    assert f"module @{module} " in f.lower(x).as_text()
    assert f"module @{module}_other " in g.lower(x).as_text()
    f.clear_cache()
    before = f.traces
    f(x), f(x)
    assert f.traces == before + 1          # one trace per program


# ------------------------------------------------------------ host spans

MINE = "trace_names.mine"


def _host_events(trace_dir, prefixes, marker=None):
    """The host's events named by `prefixes`; with `marker`, those of
    the threads alone that wrote an event of that name (a worker that
    ran other files before this one may still hold their engines'
    scheduler threads, which idle and write `llm_engine.idle` and
    `llm_engine.step` of their own into any trace of the process)."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                if marker and not any(e.name == marker for e in line.events):
                    continue
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith(prefixes)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


@contextlib.contextmanager
def _profiled(trace_dir):
    import jax

    # as the benchmark traces (benchmarks/run.py): host spans, no Python
    # tracer, whose cost a call would stand between two spans
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def engine_traces(tmp_path_factory):
    """One tiny paged engine, traced twice: two requests in a roomy pool
    (nothing evicted), then distinct prompts until the prefix cache has
    to give blocks back (evict + spill); then `run` on the empty engine,
    traced ("idle").  Before them one untraced drain with the engine's
    phase clock read on either side ("loop": before, after, the
    drain's wall seconds).  First of all `warmup()`, which compiles
    ("warmup": `stats()` after it, its wall)."""
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    config = LlamaConfig.tiny()
    engine = LLMEngine(init_params(config, jax.random.key(0)), config,
                       EngineConfig(num_slots=2, max_seq_len=64,
                                    prefill_buckets=(16,), kv_layout="paged",
                                    kv_block_size=8, num_kv_blocks=12,
                                    decode_block=1))

    def serve(base, n):
        hs = [engine.submit(Request(
            prompt=[(base + 7 * i + j) % 200 + 1 for j in range(16)],
            max_tokens=6)) for i in range(n)]
        t0 = time.monotonic()
        engine.drain()
        wall = time.monotonic() - t0
        assert all(h.finish_reason == "length" for h in hs)
        return wall

    t0 = time.monotonic()
    engine.warmup()                                # compiles, untraced
    out = {"warmup": (engine, engine.stats(), time.monotonic() - t0)}
    before = engine.stats()["loop"]
    wall = serve(25, 2)
    out["loop"] = (before, engine.stats()["loop"], wall)
    for case, base, n in (("roomy", 50, 2), ("evicting", 100, 8)):
        d = tmp_path_factory.mktemp(case)
        ev0 = engine.stats()["prefix_cache"]["evictions"]
        with _profiled(d), jax.profiler.TraceAnnotation(MINE):
            serve(base, n)
        out[case] = (_host_events(str(d), ("llm_engine.",), MINE),
                     engine.stats()["prefix_cache"]["evictions"] - ev0)
    d, stop = tmp_path_factory.mktemp("idle"), threading.Event()

    def run():
        with jax.profiler.TraceAnnotation(MINE):
            engine.run(stop)

    with _profiled(d):
        th = threading.Thread(target=run)
        th.start()
        time.sleep(0.1)
        stop.set()
        th.join()
    out["idle"] = _host_events(str(d), ("llm_engine.",), MINE)
    return out


def test_engine_source_writes_the_spans_the_benchmark_looks_for():
    """Two readers of the benchmark open `serve/llm/engine.py` and look
    for a span's name in its SOURCE, double-quoted, to tell a program
    that writes the span from a window that holds none
    (`benchmarks/tick_gap.py::program_writes`,
    `benchmarks/layer_metrics/spill_land_ms.py`): a span whose literal
    leaves that file turns `spill_land_ms` and `engine_idle_share` to
    `null` in silence. Every span name those files read is there, as
    they look for it."""
    import sys

    from ray_tpu.serve.llm import engine

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    for d in (bench, os.path.join(bench, "layer_metrics")):
        if d not in sys.path:
            sys.path.insert(0, d)
    import program_spans as PS
    import spill_land_ms
    import tick_gap as TG

    with open(engine.__file__) as f:
        source = f.read()
    names = {PS.STEP, TG.READY, TG.IDLE, spill_land_ms.SPAN} | {
        n for n in TG.QUIET if n.startswith(TG.P)}
    assert len(names) >= 11 and all(n.startswith("llm_engine.")
                                    for n in names)
    assert not sorted(n for n in names if f'"{n}"' not in source)
    assert spill_land_ms._program_lands()
    assert all(TG.program_writes(n) for n in names)


@pytest.mark.parametrize("case", ["roomy", "evicting"])
def test_engine_step_spans_nest_and_cover(engine_traces, case):
    events, evicted = engine_traces[case]
    steps = [e for e in events if e[0] == "llm_engine.step"]
    assert len(steps) >= 4
    covered = total = 0.0
    for _, s0, s1, _ in steps:
        inside = [e for e in events if e[1] >= s0 and e[2] <= s1
                  and e[0] != "llm_engine.step"]
        # direct children: those no other span of the step encloses
        kids = [e for e in inside if not any(
            o is not e and o[1] <= e[1] and e[2] <= o[2] for o in inside)]
        order = [e[0].split(".", 1)[1] for e in kids]
        assert order == [n for n in STEP_CHILDREN if n in order], order
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        if "tick_dispatch" in order:
            covered += sum(e[2] - e[1] for e in kids)
            total += s1 - s0
    assert total and 1.0 - covered / total < 0.05
    names = {e[0] for e in events}
    assert {"llm_engine.admit_one", "llm_engine.insert_dispatch"} <= names
    # one tick in flight (PR 41): a step's `tick_wait` and `emit` are
    # those of the tick dispatched a step before; the first tick of a
    # wave of requests finds none in flight (`in_flight=0`) and waits
    # for none, and the wave's last is read back by the step that finds
    # no slot to tick, under `settle` (`cause=empty`), in
    # `tick_dispatch`'s place (the requests of a wave end together)
    ticks = [e for e in events if e[0] == "llm_engine.tick_dispatch"]
    waves = 1 if case == "roomy" else 4
    assert [int(e[3]["in_flight"]) for e in ticks].count(0) == waves
    assert int(ticks[0][3]["in_flight"]) == 0
    settles = [e for e in events if e[0] == "llm_engine.settle"]
    assert [e[3]["cause"] for e in settles] == ["empty"] * waves
    for st in settles:
        # (by name: another engine's idle loop, left running by a test
        # of this process, may write its own steps meanwhile)
        want = ["llm_engine.tick_wait", "llm_engine.tick_ready",
                "llm_engine.tick_readback", "llm_engine.emit"]
        assert [e[0] for e in events if st[1] <= e[1] and e[2] <= st[2]
                and e[0] in want] == want
    for name in ("tick_wait", "emit"):
        assert sum(e[0] == "llm_engine." + name for e in events) == len(ticks)
    admits = [e for e in events if e[0] == "llm_engine.admit"]
    assert sum(int(e[3]["admitted"]) for e in admits) >= 2
    assert ("llm_engine.spill" in names) == (case == "evicting")
    assert ("llm_engine.spill_land" in names) == (case == "evicting")
    assert ("llm_engine.evict" in names) == (evicted > 0) == (case == "evicting")
    spills = [e for e in events if e[0] == "llm_engine.spill"]
    for sp in spills:
        assert int(sp[3]["evicted_blocks"]) > 0 and int(sp[3]["bytes"]) > 0
        assert any(e[0] == "llm_engine.evict" and e[1] <= sp[1]
                   and sp[2] <= e[2] for e in events)
    # every spilled block lands, in the step that spilled it: behind the
    # dispatched tick (a child of the step) or before a later admission
    # of the same step looks the tier up (under its admit_one)
    lands = [e for e in events if e[0] == "llm_engine.spill_land"]
    assert sum(int(e[3]["blocks"]) for e in lands) == sum(
        int(e[3]["evicted_blocks"]) for e in spills)
    assert sum(int(e[3]["bytes"]) for e in lands) == sum(
        int(e[3]["bytes"]) for e in spills)
    for ld in lands:
        assert int(ld[3]["blocks"]) > 0 and int(ld[3]["ready"]) in (0, 1)
        step = next(s for s in steps if s[1] <= ld[1] and ld[2] <= s[2])
        assert any(step[1] <= e[1] < ld[1] for e in spills)


@pytest.mark.parametrize("case", ["roomy", "evicting"])
def test_tick_dispatch_carries_its_live_rows(engine_traces, case):
    """`rows=` beside `live=`: the KV rows the tick about to go out has
    to read, here 16 prompt tokens + the 1..6 emitted of every live
    slot (the yardstick of a reader for the paged-attention kernel)."""
    ticks = [e[3] for e in engine_traces[case][0]
             if e[0] == "llm_engine.tick_dispatch"]
    assert ticks
    for args in ticks:
        live, rows = int(args["live"]), int(args["rows"])
        assert 1 <= live <= 2 and 17 * live <= rows <= 22 * live


def _bench_reader(monkeypatch, name):
    import importlib.util

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    monkeypatch.syspath_prepend(bench)
    spec = importlib.util.spec_from_file_location(
        "lm_" + name, os.path.join(bench, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("case", ["roomy", "evicting"])
def test_tick_wait_is_ready_then_readback(engine_traces, case):
    """`llm_engine.tick_wait` keeps its extent (the accepted readers
    read it) and holds two children inside it, one after the other and
    nothing else: `tick_ready`, until the tick's output is defined, and
    `tick_readback` (`bytes=`), its copy to the host.  (What share of
    the wait the two cover is the host's clock under whatever else the
    machine runs: 0.77 against a bound of 0.8 failed PR 44's run.)"""
    events = engine_traces[case][0]
    waits = [e for e in events if e[0] == "llm_engine.tick_wait"]
    assert len(waits) >= 4
    for _, w0, w1, _ in waits:
        kids = [e for e in events if w0 <= e[1] and e[2] <= w1
                and e[0] != "llm_engine.tick_wait"]
        assert [e[0] for e in kids] == ["llm_engine.tick_ready",
                                        "llm_engine.tick_readback"]
        ready, back = kids
        assert ready[2] <= back[1]
        assert int(back[3]["bytes"]) == 2 * 4       # [K=1, B=2] int32
    # one of each a tick, and nowhere else
    for name in ("tick_ready", "tick_readback"):
        assert sum(e[0] == "llm_engine." + name for e in events) == len(waits)


def test_empty_engine_waits_under_idle(engine_traces):
    """`LLMEngine.run` with nothing queued and nothing live: the wait
    for work is `llm_engine.idle` (`queued=0`, `live=0`), beside the
    steps that found nothing to do and inside none."""
    events = engine_traces["idle"]
    idles = [e for e in events if e[0] == "llm_engine.idle"]
    steps = [e for e in events if e[0] == "llm_engine.step"]
    assert len(idles) >= 3 and len(steps) >= 3
    for _, i0, i1, args in idles:
        assert int(args["queued"]) == 0 and int(args["live"]) == 0
        assert not any(s[1] <= i0 and i1 <= s[2] for s in steps)
    # the 100 ms the engine stood empty lie under the span (waits of
    # 20 ms each, the last cut short by the stop)
    assert 0.05e9 < sum(e[2] - e[1] for e in idles) < 0.2e9
    assert not any(e[0] == "llm_engine.tick_dispatch" for e in events)


@pytest.mark.parametrize("key", ["phases", "calls", "seconds"])
def test_engine_keeps_a_phase_clock(engine_traces, key):
    """`engine.stats()["loop"]`: seconds and calls of every top-level
    phase of the scheduler loop, always on (here with no profiler)."""
    before, after, wall = engine_traces["loop"]
    d = {k: {p: after[k][p] - before[k][p] for p in LOOP_PHASES}
         for k in ("seconds", "calls")}
    steps, ticks = (after[k] - before[k] for k in ("steps", "ticks"))
    if key == "phases":
        assert set(after) == {"steps", "ticks", "overlapped", "settles",
                              "seconds", "calls"}
        assert list(after["seconds"]) == list(after["calls"]) == LOOP_PHASES
    elif key == "calls":
        # two requests of 6 tokens admitted in one step: 5 ticks, and
        # the step that finds no slot left to tick reads the last back
        assert (steps, ticks) == (6, 5)
        c = d["calls"]
        assert c["tick_ready"] == c["tick_readback"] == ticks
        assert c["tick_dispatch"] == c["emit"] == ticks
        assert c["ctrl"] == c["admit"] == c["gauges"] == steps
        assert c["first_token_wait"] == 1 and c["idle"] == 0
        # every tick but the first went out while one was in flight
        overlapped = after["overlapped"] - before["overlapped"]
        assert 0 < overlapped <= ticks and overlapped == ticks - 1
        assert c["settle"] == 1
        assert after["settles"]["empty"] - before["settles"]["empty"] == 1
    else:
        # the phases cover the drain and never count an instant twice
        # (a loose floor: a CPU step is short beside its loop's turn)
        total = sum(d["seconds"].values())
        assert all(v >= 0 for v in d["seconds"].values())
        assert 0.8 * wall <= total <= wall, (total, wall)


@pytest.mark.parametrize("key", ["seconds", "programs", "anew", "copies"])
def test_warmup_keeps_what_it_measured(engine_traces, key):
    """`stats()["warmup"]`: the wall of `warmup()` and the rows it added
    to `jit_stats()`; the engine's phase clock starts anew behind it."""
    engine, st, wall = engine_traces["warmup"]
    w = st["warmup"]
    if key == "seconds":
        assert set(w) == {"seconds", "programs"}
        assert 0.5 * wall < w["seconds"] <= wall
    elif key == "programs":
        # the engine's own programs' first calls, by stage, as the
        # warm-up left them: under its wall together
        rows = w["programs"]
        assert {"llm_engine_tick", "llm_engine_insert"} <= set(rows)
        assert rows["llm_engine_tick"]["traces"] == st["traces"]["tick"] == 1
        for r in rows.values():
            stages = (r["trace_seconds"], r["lower_seconds"],
                      r["backend_seconds"])
            assert all(s > 0 for s in stages)
            assert sum(stages) <= r["compile_seconds_total"]
        assert sum(r["compile_seconds_total"] for r in rows.values()) \
            <= w["seconds"]
    elif key == "anew":
        loop = st["loop"]
        assert loop["steps"] == loop["ticks"] == 0
        assert not any(loop["seconds"].values())
        assert not any(loop["calls"].values())
    else:
        # what a reader does to the dict it was handed changes nothing,
        # nor does what the engine serves after its warm-up
        mine = engine.stats()["warmup"]
        mine["programs"]["llm_engine_tick"]["trace_seconds"] += 1e6
        mine["seconds"] = -1.0
        again = engine.stats()["warmup"]
        assert again == w
        assert again["seconds"] > 0
        assert again["programs"]["llm_engine_tick"]["trace_seconds"] < 1e5


@pytest.mark.parametrize("who", ["fence", "owner", "engine"])
def test_sampled_fence_stands_in_the_trace(tmp_path, who):
    """`TrackedJit` fences every `xla_wall_sample_every`-th call with
    `block_until_ready` inside the call: the fence is `jit.wall_sample`
    (`fn=`), under whatever span holds the call ("fence").  An owner
    that keeps its calls in flight (`fence_samples=False`) is handed the
    sampled call's mark and gives the wall it measured where it waits:
    `jit.wall_sample` is then an instant there, and no call is fenced
    ("owner").  The engine's tick is such an owner: its samples stand
    where the tick that was waited for is accounted, under
    `llm_engine.emit`, none inside a `tick_dispatch`, while the ticks
    keep overlapping ("engine")."""
    import jax.numpy as jnp

    from ray_tpu.observability import tracked_jit
    from ray_tpu.observability.profiling import trace_span

    if who == "engine":
        import jax

        from ray_tpu.models.llama import LlamaConfig, init_params
        from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

        config = LlamaConfig.tiny()
        engine = LLMEngine(init_params(config, jax.random.key(0)), config,
                           EngineConfig(num_slots=2, max_seq_len=32,
                                        prefill_buckets=(8,), kv_block_size=8))
        engine.submit(Request(prompt=[1, 2, 3], max_tokens=2))
        engine.drain()                  # compiles: never a sample
        f = engine._programs._jit_tick
        f._sample_every, f.calls = 2, 0
        walls = []
        record = f.record_wall
        f.record_wall = lambda due, wall: (walls.append(wall),
                                           record(due, wall))[1]
        with _profiled(tmp_path):
            engine.submit(Request(prompt=[4, 5, 6], max_tokens=9))
            engine.drain()              # 8 ticks
        loop = engine.stats()["loop"]
        assert loop["overlapped"] == loop["ticks"] - 2
        assert len(walls) == 4 and all(0 < w < 1.0 for w in walls)
        name, dispatch, wait = ("llm_engine_tick", "llm_engine.tick_dispatch",
                                "llm_engine.emit")
    else:
        name, dispatch, wait = ("trace_names_sampled_" + who,
                                "llm_engine.tick_dispatch",
                                "llm_engine.tick_wait")
        f = tracked_jit(lambda x: x * 3, name=name,
                        fence_samples=who == "fence")
        f(jnp.ones((3,)))                   # compiles: never a sample
        f._sample_every, f.calls = 2, 0
        with _profiled(tmp_path):
            for _ in range(4):
                with trace_span(dispatch, live=1):
                    out = f(jnp.ones((3,)))
                due = f.take_sample()
                with trace_span(wait):
                    out.block_until_ready()
                    if due is not None:
                        f.record_wall(due, 0.001)
    ev = _host_events(str(tmp_path), ("jit.wall_sample", dispatch, wait))
    samples = [e for e in ev if e[0] == "jit.wall_sample"]
    holders = [e for e in ev if e[0] == (
        dispatch if who == "fence" else wait)]
    others = [e for e in ev if e[0] == (
        wait if who == "fence" else dispatch)]
    assert len(samples) == (4 if who == "engine" else 2)
    assert len(others) >= 4
    for s in samples:
        assert s[3]["fn"] == name
        assert any(h[1] <= s[1] and s[2] <= h[2] for h in holders)
        assert not any(o[1] <= s[1] and s[2] <= o[2] for o in others)


def _tick_trace(order):
    """A trace built here, times in us, the device's clock 2 ms behind
    the host's; ticks of 10 ms whose executions are known to the host
    1 ms after their end; a readback of 0.25 ms, 0.5 ms of host work
    between knowing a tick done and the next dispatch's start; then an
    empty engine for 2.5 ms.

    "own_step" (before PR 41): two quiet steps that each dispatch a
    tick, whose execution starts 0.5 ms later, and wait for it.
    "in_flight": step k dispatches tick k while tick k-1 runs, then
    waits for tick k-1; the executions stand back to back; step 0 waits
    for none and step 4, which finds no slot to tick, reads tick 3 back
    under `settle`."""
    us, skew = 1_000, 2_000_000
    spans, mods = [], []

    def step(t, wait_end, dispatch=True, first=False):
        """One step from `t`; its wait ends (readback included) at
        `wait_end`; returns where the next begins."""
        spans.extend([("llm_engine.ctrl", t, 10 * us, {}),
                      ("llm_engine.admit", t + 10 * us, 10 * us,
                       {"admitted": "0"})])
        at = t + 20 * us
        if dispatch:
            spans.append(("llm_engine.tick_dispatch", at, 300 * us,
                          {"live": "1", "in_flight": str(int(not first))}))
            at += 300 * us
        if not first:
            inner = [("llm_engine.tick_wait", at, wait_end - at, {}),
                     ("llm_engine.tick_ready", at, wait_end - 250 * us - at,
                      {}),
                     ("llm_engine.tick_readback", wait_end - 250 * us,
                      250 * us, {"bytes": "4"}),
                     ("llm_engine.emit", wait_end, 100 * us, {})]
            if not dispatch:
                spans.append(("llm_engine.settle", at,
                              wait_end + 100 * us - at, {"cause": "empty"}))
            spans.extend(inner)
            at = wait_end + 100 * us
        spans.extend([("llm_engine.gauges", at, 80 * us, {}),
                      ("llm_engine.step", t, at + 80 * us - t, {})])
        return at + 130 * us

    if order == "own_step":
        for t in (0, 12_000 * us):
            step(t, t + 11_770 * us)
            mods.append(("jit_llm_engine_tick(1)", t + 520 * us - skew,
                         10_000 * us))
        end = 24_000 * us
        loop = {"ticks": 2}
    else:
        t = step(0, None, first=True)
        for k in range(4):              # execution k: 10 ms from 0.52 ms
            mods.append(("jit_llm_engine_tick(1)",
                         (520 + 10_000 * k) * us - skew, 10_000 * us))
            t = step(t, (520 + 10_000 * (k + 1) + 1_250) * us,
                     dispatch=k < 3)
        end = t
        loop = {"ticks": 4, "overlapped": 3, "settles": {"empty": 1}}
    spans.append(("llm_engine.idle", end, 2_500 * us,
                  {"queued": "0", "live": "0"}))
    loop.update(steps=5, calls={}, seconds={
        "ctrl": 0.0002, "tick_dispatch": 0.002, "tick_readback": 0.001,
        "emit": 0.0005, "gauges": 0.0003, "tick_ready": 0.02, "idle": 1.0})
    return spans, mods, loop, (-skew, end + 2_500 * us)


@pytest.mark.parametrize("order", ["own_step", "in_flight"])
@pytest.mark.parametrize("name, want", [
    ("tick_readback_ms", (0.25, 0.25)),
    ("tick_launch_notify_ms", (1.5, -0.5)),
    ("tick_host_ms", (2.0, 1.0)),
    ("host_loop_ms", (0.5, 0.5)),
    ("engine_idle_share", (100 * 2.5 / (2.0 + 5.98), 100 * 2.5 / 5.98)),
    ("tick_overlap_share", (None, 75.0))])
def test_tick_gap_readers_load_and_read(monkeypatch, name, want, order):
    """The readers of the time between two ticks
    (`benchmarks/layer_metrics/`, on `benchmarks/tick_gap.py` and
    `program_spans.py`), loaded by path as the harness loads them, on
    `_tick_trace`'s two step orders: each reads a number, or None, and
    none raises.  With a tick in flight the executions stand back to
    back (`G` = 0), so `tick_launch_notify_ms` = `G - H` reads MINUS the
    host's 0.5 ms between `tick_ready`'s end and the next dispatch;
    `tick_readback_ms`, `host_loop_ms` and `tick_host_ms` still read the
    host's own work, which the device no longer waits for.  The reported
    window of "own_step" holds 2 ms of device idle between its two ticks
    and 5.98 after the second, of "in_flight" 5.98 after the fourth; 2.5
    of them under the idle span.  `tick_overlap_share` reads a recorded
    `loop`: 3 of 4 ticks dispatched behind one in flight.  A program
    without the spans or the counts reads None."""
    import types

    mod = _bench_reader(monkeypatch, name)
    import program_spans as PS
    import tick_gap as TG
    import trace_reduce as TR

    assert TG.program_writes("llm_engine.idle")      # this tree's engine
    spans, mods, loop, window = _tick_trace(order)
    engine = types.SimpleNamespace(stats=lambda: {"loop": loop})
    run = {"window": window, "records": {"recs": [
        types.SimpleNamespace(handle=types.SimpleNamespace(engine=engine))]},
        "trace": TR.Trace({"/device:TPU:0": {
            TR.MODULE_LINE: mods,
            TR.OPS_LINE: [("op", s, d) for _, s, d in mods]}}, []),
        "program": PS.Program(sorted(spans, key=lambda s: (s[1], -s[2])),
                              [])}
    want = want[order == "in_flight"]
    got = mod.read(run)
    assert (got is None) if want is None else got == pytest.approx(want)
    bare = {"window": run["window"], "trace": run["trace"], "records": {},
            "program": PS.Program([s for s in spans if s[0] not in (
                "llm_engine.tick_ready", "llm_engine.tick_readback",
                "llm_engine.idle")], [])}
    monkeypatch.setattr(TG, "program_writes", lambda span: False)
    if name == "host_loop_ms":          # PR 24's: steps and waits suffice
        assert mod.read(bare) == pytest.approx(0.5)
    else:
        assert mod.read(bare) is None


@pytest.mark.parametrize("case, want", [
    ("landed", 3.0),            # landings of 2, 3 and 40 ms: the median
    ("no_admission", 0.0),      # a window of ticks alone still reports
    ("spill_cut", 0.0),         # the trace ended between spill and landing
    ("parent", None),           # a program that never writes the span
    ("no_spans", None)])
def test_spill_land_reader_reports_every_window(monkeypatch, case, want):
    """`benchmarks/layer_metrics/spill_land_ms.py`: every traced window
    of a program that lands its spills gives a number (the driver holds
    the change to that), and the parent's gives none."""
    mod = _bench_reader(monkeypatch, "spill_land_ms")
    import program_spans as PS

    assert mod._program_lands()             # this tree writes mod.SPAN
    ms = 1_000_000
    spans = [("llm_engine.step", 0, 100 * ms, {}),
             ("llm_engine.tick_dispatch", 10 * ms, ms, {"live": "2"}),
             ("llm_engine.tick_wait", 60 * ms, 30 * ms, {})]
    if case in ("landed", "parent"):
        spans += [("llm_engine.admit_one", ms, 8 * ms, {}),
                  ("llm_engine.spill", 2 * ms, ms, {"evicted_blocks": "3"})]
    if case == "landed":
        spans += [(mod.SPAN, t * ms, d * ms, {"blocks": "3"})
                  for t, d in ((11, 2), (15, 3), (19, 40))]
    if case == "spill_cut":
        spans = [("llm_engine.admit_one", ms, 8 * ms, {}),
                 ("llm_engine.spill", 2 * ms, ms, {"evicted_blocks": "3"})]
    if case == "parent":
        monkeypatch.setattr(mod, "_program_lands", lambda: False)
    run = {"window": (0, 200 * ms), "trace": object(),
           "program": None if case == "no_spans" else PS.Program(
               sorted(spans, key=lambda s: (s[1], -s[2])), [])}
    got = mod.read(run)
    assert (got is None) if want is None else got == pytest.approx(want)


def test_step_phases_write_train_spans(tmp_path):
    from ray_tpu.observability.goodput import StepPhases

    with _profiled(tmp_path):
        sp = StepPhases(step=7, worker="t")
        with sp.phase("compute"):
            time.sleep(0.001)
        with sp.phase("weight_publish"):
            pass
        row = sp.finish(publish=False)
    assert row["phases"]["compute"] > 0
    ev = _host_events(str(tmp_path), ("train.",))
    assert [e[0] for e in ev] == ["train.step", "train.compute",
                                  "train.weight_publish"]
    step = ev[0]
    assert int(step[3]["step"]) == 7
    assert all(step[1] <= e[1] and e[2] <= step[2] for e in ev[1:])


def test_compile_stands_in_the_trace(tmp_path):
    import jax.numpy as jnp

    from ray_tpu.observability import tracked_jit

    f = tracked_jit(lambda x: x - 1, name="trace_names_compile")
    with _profiled(tmp_path):
        f(jnp.ones((3,)))
        f(jnp.ones((3,)))
    ev = _host_events(str(tmp_path), ("jit.compile",))
    assert len(ev) == 1 and ev[0][3]["fn"] == "trace_names_compile"
    assert float(ev[0][3]["seconds"]) > 0


def test_trace_span_off_is_cheap():
    """No profiler session: an entry is the annotation object and a flag
    test (about 1.4 us here; 5 us is generous for a shared runner)."""
    from ray_tpu.observability.profiling import trace_span

    n = 10_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with trace_span("llm_engine.tick_dispatch", live=3):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, best


# ---------------------------------------------------------------- scopes

def _strip_metadata(hlo_text):
    """Compiled text without what names carry: per-instruction metadata,
    the stack-frame tables and the module's name."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(.+\n)*", "\n", text)
    return re.sub(r"^HloModule \S+", "HloModule m", text)


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _compile_with_and_without_scopes(build, monkeypatch):
    import jax

    scoped = build().compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    return scoped, build().compile().as_text()


def _has_scope(op_names, scope):
    return any(scope in re.split(r"[/()]", n) for n in op_names)


def test_decode_step_scopes_are_metadata_only(monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (LlamaConfig, decode_step_paged,
                                      init_paged_kv_cache, init_params)

    c = LlamaConfig.tiny()
    params = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    pools = jax.eval_shape(lambda: init_paged_kv_cache(c, 16, 4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731

    def build():
        return jax.jit(lambda p, kv, t, tok, pos: decode_step_paged(
            p, kv, t, tok, pos, c)).lower(params, pools, i32(2, 8), i32(2),
                                          i32(2))

    scoped, bare = _compile_with_and_without_scopes(build, monkeypatch)
    names = _op_names(scoped)
    for scope in ("layers", "kv_gather", "kv_write", "attn", "mlp",
                  "lm_head"):
        assert _has_scope(names, scope), scope
    assert not _has_scope(_op_names(bare), "kv_gather")
    assert _strip_metadata(scoped) == _strip_metadata(bare)


def test_engine_tick_carries_the_sample_scope():
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    c = LlamaConfig.tiny()
    e = LLMEngine(init_params(c, jax.random.key(0)), c, EngineConfig(
        num_slots=2, max_seq_len=32, prefill_buckets=(8,),
        kv_layout="paged", kv_block_size=8))
    p = e._programs
    lowered = p._jit_tick.lower(
        e.params, p._cache, e._tables.copy(), p._tok, p._pos,
        e._active.copy(), e._temp.copy(), p._key)
    assert "module @jit_llm_engine_tick " in lowered.as_text()
    names = _op_names(lowered.compile().as_text())
    for scope in ("sample", "layers", "kv_gather", "attn"):
        assert _has_scope(names, scope), scope


def test_train_step_scopes_are_metadata_only(monkeypatch):
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel.train_step import (build_train_step,
                                             create_train_state)

    c = LlamaConfig.tiny()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    opt = optax.adamw(1e-3)
    state = create_train_state(init_params(c, jax.random.key(0)), opt)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}

    def build():
        step = build_train_step(partial(loss_fn, config=c), opt, mesh, None,
                                NamedSharding(mesh, P()))
        return step.lower(state, batch)

    assert "module @jit_train_step " in build().as_text()
    scoped, bare = _compile_with_and_without_scopes(build, monkeypatch)
    names = _op_names(scoped)
    for scope in ("layers", "attn", "mlp", "loss_head", "optimizer"):
        assert _has_scope(names, scope), scope
    # the backward pass keeps the scopes
    assert any("transpose(jvp(loss_head))" in n for n in names)
    assert any("transpose(jvp(layers))" in n and "/attn/" in n
               for n in names)
    assert not _has_scope(_op_names(bare), "optimizer")
    assert _strip_metadata(scoped) == _strip_metadata(bare)
