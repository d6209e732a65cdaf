"""The time between two decode ticks, measured where it passes (PR 34).

The host's spans and the device's events share a file, not a clock: in
the recorded v5e trace `tests/data/chat-decode_pr24.json.gz` the host
plane leads the device plane by something between 1.21 and 3.04 ms, and
the gap between two ticks is 2.66 ms.  So nothing here subtracts a host
time from a device time, with the one documented exception of the sum
below.  Everything works per QUIET tick pair: step k ran a tick, step
k+1 follows it at once, admitted nothing (its children are `ctrl`,
`admit`, `tick_dispatch`, `tick_wait`, `emit`, `gauges` and nothing
else) and ran a tick, and no other program ran between the two
executions.  For such a pair

- `G` = start of `jit_llm_engine_tick` execution k+1 - end of execution
  k: the device's clock alone;
- `H` = start of `llm_engine.tick_dispatch` k+1 - end of
  `llm_engine.tick_ready` k: the host's clock alone.  It is
  `tick_readback` + `emit` + `gauges` of step k, the loop's turn
  (whatever lies under no phase: closing and opening `llm_engine.step`,
  `LLMEngine.run`'s test, the live rows' count), `ctrl` + `admit` of
  step k+1;
- `G - H` = notify (execution k ends -> `tick_ready` returns) + launch
  (`tick_dispatch` begins -> the first operation of execution k+1; the
  dispatch call's own host time lies inside it).  Each side is a
  difference on ONE clock, so the sum is free of the planes' offset.
  Its SPLIT into notify and launch is not: that needs the offset, which
  a trace bounds (`skew_bounds`) and does not give.

A trace whose program writes no `llm_engine.tick_ready` (before PR 34)
is read with `llm_engine.tick_wait`'s end in its place: `H` then starts
after the readback and `G - H` holds the readback too.

An execution is laid to its step by its MIDPOINT falling between the
step's `tick_dispatch` start and its wait's end.  That comparison does
cross the clocks, to pair and not to measure: it holds as long as the
offset is under half a tick.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

import program_spans as PS
import stats as S
import trace_reduce as TR

P = "llm_engine."
TICK, INSERT = "jit_llm_engine_tick", "jit_llm_engine_insert"
READY, IDLE = P + "tick_ready", P + "idle"
WALL_SAMPLE = "jit.wall_sample"
QUIET = {P + n for n in ("ctrl", "admit", "tick_dispatch", "tick_wait",
                         "tick_ready", "tick_readback", "emit", "gauges")
         } | {WALL_SAMPLE}
PARTS = ("readback", "emit", "gauges", "turn", "ctrl", "admit",
         "launch_notify")


def _end(span) -> int:
    return span[1] + span[2]


def program_writes(span: str) -> bool:
    """Whether the engine's source writes `span`.  A window that holds
    none cannot tell a program that writes it from one that does not
    (`layer_metrics/spill_land_ms.py` shows the way)."""
    try:
        from ray_tpu.serve.llm import engine
        with open(engine.__file__) as f:
            return f'"{span}"' in f.read()
    except Exception:
        return False


def tick_steps(prog: PS.Program, window, trace: Optional[TR.Trace] = None
               ) -> List[Dict]:
    """Every `llm_engine.step` inside the window that ran a tick, in
    order: its span, its first child of each name, whether it is quiet,
    and `ended` = the end of `tick_ready` (of `tick_wait` where the
    program writes no `tick_ready`).  Given the trace, also `run`: the
    tick execution whose midpoint lies between the step's
    `tick_dispatch` start and its `tick_wait` end (absent if none)."""
    out = []
    for st in PS.in_window(prog, window, PS.STEP):
        kids, names = {}, set()
        for k in PS.children(prog, st):
            kids.setdefault(k[0][len(P):] if k[0].startswith(P) else k[0], k)
            names.add(k[0])
        if "tick_dispatch" not in kids or "tick_wait" not in kids:
            continue
        out.append({"span": st, "kids": kids, "quiet": names <= QUIET,
                    "ended": _end(kids.get("tick_ready")
                                  or kids["tick_wait"])})
    starts = [s["kids"]["tick_dispatch"][1] for s in out]
    for r in PS.program_runs(trace, TICK, window) if trace else ():
        mid = r[1] + r[2] // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid <= _end(out[i]["kids"]["tick_wait"]):
            out[i].setdefault("run", r)
    return out


def quiet_pairs(prog: PS.Program, trace: TR.Trace, window) -> List[Dict]:
    """One entry a quiet pair, every value in ms: `G`, `H`, the parts of
    `H` (`readback`, `emit`, `gauges`, `turn`, `ctrl`, `admit`),
    `launch_notify` = `G - H`, and beside them `dispatch` (the duration
    of step k+1's `tick_dispatch`), `tick` (execution k+1's duration)
    and `step` (step k+1's)."""
    steps = tick_steps(prog, window, trace)
    every = sorted(r[1] for r in TR.module_runs(TR.first_device(trace)))
    spans_at = [s[1] for s in prog.spans]
    out = []
    for a, b in zip(steps, steps[1:]):
        ra, rb = a.get("run"), b.get("run")
        if not b["quiet"] or ra is None or rb is None:
            continue
        # b is the span that follows a at once: no other step (one
        # that ran no tick) and no idle wait begins between them
        lo = bisect.bisect_left(spans_at, _end(a["span"]))
        hi = bisect.bisect_left(spans_at, b["span"][1])
        if any(s[0] in (PS.STEP, IDLE) for s in prog.spans[lo:hi]):
            continue
        # and no other program started between the two executions
        if bisect.bisect_left(every, rb[1]) - bisect.bisect_right(
                every, ra[1]) != 0:
            continue
        ka, kb = a["kids"], b["kids"]
        dur = lambda k, n: k[n][2] / 1e6 if n in k else 0.0   # noqa: E731
        p = {"G": (rb[1] - _end(ra)) / 1e6,
             "H": (kb["tick_dispatch"][1] - a["ended"]) / 1e6,
             "readback": dur(ka, "tick_readback"),
             "emit": dur(ka, "emit"), "gauges": dur(ka, "gauges"),
             "ctrl": dur(kb, "ctrl"), "admit": dur(kb, "admit"),
             "dispatch": dur(kb, "tick_dispatch"), "tick": rb[2] / 1e6,
             "step": b["span"][2] / 1e6}
        p["turn"] = p["H"] - sum(p[n] for n in ("readback", "emit", "gauges",
                                                "ctrl", "admit"))
        p["launch_notify"] = p["G"] - p["H"]
        out.append(p)
    return out


def skew_bounds(prog: PS.Program, trace: TR.Trace, window
                ) -> Optional[Tuple[float, float, int]]:
    """(lo, hi, n): the host plane leads the device plane by an offset
    in [lo, hi] ms, from n (span, execution) pairs.  An execution cannot
    start before its dispatch began, so offset >= dispatch start -
    execution start, each on its own clock; the host cannot learn of its
    end before it ended, so offset <= wait's end - execution end.  Taken
    over ticks (`tick_dispatch` / `tick_ready`) and inserts
    (`insert_dispatch` / `first_token_wait`, in steps where as many
    executions lie as dispatches).  lo > hi would mean the causal model
    is wrong (or the offset moved inside the window)."""
    los, his = [], []
    for s in tick_steps(prog, window, trace):
        if "run" in s:
            los.append(s["kids"]["tick_dispatch"][1] - s["run"][1])
            his.append(s["ended"] - _end(s["run"]))
    inserts = PS.program_runs(trace, INSERT, window)
    for st in PS.in_window(prog, window, PS.STEP):
        kids = PS.children(prog, st)
        disp = [k for k in kids if k[0] == P + "insert_dispatch"]
        wait = [k for k in kids if k[0] == P + "first_token_wait"]
        if not disp:
            continue
        t1 = _end(wait[0]) if wait else _end(st)
        mine = [r for r in inserts if disp[0][1] <= r[1] + r[2] // 2 <= t1]
        if len(mine) != len(disp):
            continue
        los += [d[1] - r[1] for d, r in zip(disp, mine)]
        if wait:
            his.append(t1 - _end(mine[-1]))
    if not los or not his:
        return None
    return max(los) / 1e6, min(his) / 1e6, len(los)


def wall_samples(run, prog: PS.Program) -> List[PS.Span]:
    """The `jit.wall_sample` spans of the run's trace (`TrackedJit`'s
    sampled fence, every 64th call of a program).  `program_spans.parse`
    keeps `jit.compile` only of `jit.*`, so they are read from the run's
    `.xplane.pb` here (found and checked against the run's
    `bench:trace_begin` as `program_spans.load` does); a recorded dump
    carries them among its spans."""
    got = [s for s in prog.spans if s[0] == WALL_SAMPLE]
    dirs = sorted(glob.glob(os.path.join(PS.ROOT, ".bench_trace", "*")),
                  key=os.path.getmtime)
    path = TR.find_xplane(dirs[-1]) if dirs else None
    if got or path is None:
        return got
    from jax.profiler import ProfileData

    mine = False
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WALL_SAMPLE:
                        got.append((e.name, int(e.start_ns),
                                    int(e.duration_ns),
                                    {k: str(v) for k, v in e.stats}))
                    elif e.name == PS.BEGIN:
                        mine |= int(e.start_ns) == run["window"][0]
    return sorted(got, key=lambda s: s[1]) if mine else []


def report(run) -> Optional[Dict]:
    """Medians over the quiet pairs of the traced window, each part's
    own (`n`, `G`, `H`, the parts, `dispatch`, `tick`, `step`, `ready`:
    whether `H` starts at `tick_ready`'s end), printed once a run as the
    `CLOCK` and `TICK GAP` lines; None without spans or quiet pairs."""
    if "tick_gap" in run:
        return run["tick_gap"]
    run["tick_gap"] = None
    prog = PS.load(run)
    if prog is None or run.get("trace") is None:
        return None
    trace, window = run["trace"], run["window"]
    skew = skew_bounds(prog, trace, window)
    if skew is not None:
        print("CLOCK host-device skew in [%.3f, %.3f] ms over %d pairs%s"
              % (*skew, "" if skew[0] <= skew[1] else
                 " (lo > hi: the causal model fails in this window)"),
              flush=True)
    pairs = quiet_pairs(prog, trace, window)
    if not pairs:
        return None
    med = {k: S.median([p[k] for p in pairs]) for k in pairs[0]}
    med["n"] = len(pairs)
    med["ready"] = bool(PS.in_window(prog, window, READY))
    parts = sum(med[k] for k in PARTS)
    print(("TICK GAP (ms, median of %d quiet pairs): G %.3f = "
           % (med["n"], med["G"]))
          + " + ".join("%s %.3f" % ("[launch+notify]" if k == "launch_notify"
                                    else k, med[k]) for k in PARTS)
          + " (of which the dispatch call <= %.3f); the parts sum to %.3f; "
            "tick %.3f + G = %.3f against llm_engine.step %.3f%s"
          % (med["dispatch"], parts, med["tick"], med["tick"] + med["G"],
             med["step"], "" if med["ready"] else
             "; no tick_ready in this trace: H starts at tick_wait's end "
             "and [launch+notify] holds the readback"), flush=True)
    samples = wall_samples(run, prog)
    if samples:
        disp = PS.in_window(prog, window, P + "tick_dispatch")
        inside = [s[2] / 1e6 for s in samples if any(
            d[1] <= s[1] and _end(s) <= _end(d) for d in disp)]
        print("WALL SAMPLE (jit.wall_sample): %d in the trace, %d inside an "
              "llm_engine.tick_dispatch of the window (ms: %s)"
              % (len(samples), len(inside),
                 ", ".join("%.3f" % x for x in inside) or "-"), flush=True)
    run["tick_gap"] = med
    return med


def loop_stats(run) -> Optional[Dict]:
    """`engine.stats()["loop"]` of the engine that served the run's
    requests (the scheduler thread's seconds and calls by phase since
    its `warmup` ended), or None for a program that keeps no such
    clock."""
    for rec in run["records"].get("recs", ()):
        engine = getattr(rec.handle, "engine", None)
        if engine is not None:
            return engine.stats().get("loop")
    return None
