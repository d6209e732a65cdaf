"""What the PROGRAM says about itself in a profiler trace (PR 24): the
host spans it writes on the profiler's clock, its programs' own names,
and the scope names on its operations.  `trace_reduce` reads a trace
from outside (markers, votes); this module reads what is written in it,
and every reader under `layer_metrics/` that needs these goes through
here.

What the program writes (`ray_tpu/observability/profiling.py::trace_span`
is the one helper; the call sites are named in PERF.md section 3):

- host spans, on the host plane of the same `.xplane.pb` as the device's
  `XLA Ops`: `llm_engine.step` and its children `llm_engine.ctrl`,
  `.admit` (`.admit_one` per request, under it `.evict`, `.spill`,
  `.promote`, `.insert_dispatch`), `.first_token_wait`,
  `.tick_dispatch`, `.tick_wait`, `.emit`, `.gauges` (serve/llm/engine.py);
  `train.step` and `train.<phase>` (observability/goodput.py);
  `jit.compile` (observability/jit.py).  Counts ride as arguments;
- program names: `XLA Modules` events read `jit_<name>(<fingerprint>)`
  with the name `tracked_jit(..., name=...)` was given
  (`jit_llm_engine_tick`, `jit_llm_engine_insert`, `jit_train_step`);
- scopes: `jax.named_scope` names (`kv_gather`, `attn`, `kv_write`, `mlp`,
  `lm_head`, `sample`; `loss_head`, `optimizer`; `layers` for what the
  layer scan does around its body) are path components of
  an operation's `op_name`, which the TPU trace carries as a stat of
  each `XLA Ops` event (see `_scope_of`).  A fusion has the `op_name` of
  its root.

`load(run)` finds the run's `.xplane.pb` itself (the newest directory
under `.bench_trace/`, checked against the run's `bench:trace_begin`),
because `trace_reduce.load` keeps only `bench:*` events.  On a trace of
a program that writes none of this (the parent commit) every function
here returns None or an empty result and nothing raises.

The flat form (`to_json` / `dump` / `read_dump`) is a superset of
`trace_reduce`'s, so one recorded file serves both modules' tests.
"""

from __future__ import annotations

import bisect
import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

import trace_reduce as TR

Span = Tuple[str, int, int, Dict[str, str]]     # name, start, dur, arguments
Op = Tuple[str, int, int]                        # scope ("" if none), start, dur

BEGIN = "bench:trace_begin"          # kept too: it says whose trace this is
PREFIXES = ("llm_engine.", "train.", "jit.compile", BEGIN)
SCOPES = ("layers", "kv_gather", "kv_write", "attn", "mlp", "lm_head",
          "sample", "loss_head", "optimizer")
STEP = "llm_engine.step"
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Program:
    """spans: host events the program wrote, sorted by start; ops: the
    first chip's leaf operations with the scope each stands under."""

    def __init__(self, spans: List[Span], ops: List[Op]):
        self.spans, self.ops = spans, ops

    def to_json(self) -> dict:
        return {"spans": self.spans, "ops": self.ops}

    @staticmethod
    def from_json(d: dict) -> "Program":
        return Program([(n, s, t, dict(a)) for n, s, t, a in d.get("spans", [])],
                       [tuple(o) for o in d.get("ops", [])])


def scope_of(op_name: str) -> str:
    """The innermost scope of SCOPES among the path components of an
    `op_name` (`jit(x)/while/body/kv_gather/gather`; under
    differentiation a component reads `transpose(jvp(attn))`)."""
    for part in reversed(op_name.split("/")):
        part = part.rsplit("(", 1)[-1].rstrip(")")
        if part in SCOPES:
            return part
    return ""


# The TPU trace keeps an operation's `op_name` as the stat `tf_op` of the
# event's METADATA (one per HLO instruction), which `ProfileData` does not
# show (it lists an event's own stats: offsets and durations).  So the
# operations are read from the file's protobuf wire format, with the few
# field numbers of tsl's xplane.proto that this needs.

def _varint(b, i):
    x = shift = 0
    while True:
        c = b[i]
        i += 1
        x |= (c & 0x7F) << shift
        if c < 0x80:
            return x, i
        shift += 7


def _fields(b):
    """(field number, value) of one message: an int for a varint, a
    slice of `b` for a length-delimited field."""
    i = 0
    while i < len(b):
        key, i = _varint(b, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        else:                           # fixed 64 / fixed 32
            n = 8 if wire == 1 else 4
            v, i = b[i:i + n], i + n
        yield key >> 3, v


def _message(b) -> Dict[int, list]:
    out: Dict[int, list] = defaultdict(list)
    for f, v in _fields(b):
        out[f].append(v)
    return out


def _text(v) -> str:
    return bytes(v).decode("utf-8", "replace")


def device_ops(xspace: bytes) -> List[Op]:
    """Leaf events of the first TPU plane's `XLA Ops` line, each with the
    scope its instruction's `op_name` stands under."""
    planes = [_message(p) for p in _message(memoryview(xspace))[1]]
    tpu = sorted((_text(p[2][0]), p) for p in planes
                 if p[2] and _text(p[2][0]).startswith("/device:TPU:"))
    if not tpu:
        return []
    plane = tpu[0][1]
    # XPlane: 3 lines, 4 event_metadata (map), 5 stat_metadata (map)
    stat_names = {}
    for entry in plane[5]:
        e = _message(entry)
        m = _message(e[2][0])                        # XStatMetadata: 2 name
        stat_names[e[1][0]] = _text(m[2][0]) if m[2] else ""
    scope_by_meta: Dict[int, str] = {}
    for entry in plane[4]:
        e = _message(entry)
        for st in _message(e[2][0])[5]:               # XEventMetadata: 5 stats
            st = _message(st)                         # XStat: 1 id, 5 str, 7 ref
            if stat_names.get(st[1][0]) == "tf_op":
                name = _text(st[5][0]) if st[5] else stat_names.get(
                    st[7][0] if st[7] else -1, "")
                scope_by_meta[e[1][0]] = scope_of(name)
    ops: List[Op] = []
    for line in plane[3]:
        ln = _message(line)                           # XLine: 2 name, 3 ns, 4 events
        if not ln[2] or _text(ln[2][0]) != TR.OPS_LINE:
            continue
        base_ps = (ln[3][0] if ln[3] else 0) * 1000
        for ev in ln[4]:
            ev = _message(ev)                         # XEvent: 1 meta, 2 offset, 3 dur
            ops.append((scope_by_meta.get(ev[1][0], ""),
                        (base_ps + (ev[2][0] if ev[2] else 0)) // 1000,
                        (ev[3][0] if ev[3] else 0) // 1000))
    return TR.leaves(ops)


def parse(path: str) -> Program:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        raw = f.read()
    spans: List[Span] = []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(PREFIXES):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns),
                                      {k: str(v) for k, v in e.stats}))
    spans.sort(key=lambda s: (s[1], -s[2]))
    return Program(spans, device_ops(raw))


def load(run) -> Optional[Program]:
    """The program's own events of this run, or None.  A test hands a
    recorded one in as `run["program"]`; a run is looked up once."""
    if "program" in run:
        return run["program"]
    run["program"] = None
    if run.get("trace") is None or run.get("window") is None:
        return None
    dirs = sorted(glob.glob(os.path.join(ROOT, ".bench_trace", "*")),
                  key=os.path.getmtime)
    path = TR.find_xplane(dirs[-1]) if dirs else None
    if path is None:
        return None
    prog = parse(path)
    if (BEGIN, run["window"][0]) not in {(s[0], s[1]) for s in prog.spans}:
        return None                    # another run's trace
    run["program"] = prog
    return prog


def dump(trace: TR.Trace, prog: Program, path: str) -> None:
    with gzip.open(path, "wt") as f:
        json.dump({**trace.to_json(), **prog.to_json()}, f)


def read_dump(path: str) -> Tuple[TR.Trace, Program]:
    with gzip.open(path, "rt") as f:
        d = json.load(f)
    return TR.Trace.from_json(d), Program.from_json(d)


# ------------------------------------------------------------ host spans

def in_window(prog: Program, window: Tuple[int, int],
              name: Optional[str] = None) -> List[Span]:
    """Spans that lie whole inside the window (of one name, if given)."""
    t0, t1 = window
    return [s for s in prog.spans if s[1] >= t0 and s[1] + s[2] <= t1
            and (name is None or s[0] == name)]


def durations_ms(prog: Program, window) -> Dict[str, List[float]]:
    out: Dict[str, List[float]] = defaultdict(list)
    for n, _, d, _ in in_window(prog, window):
        out[n].append(d / 1e6)
    return out


def children(prog: Program, parent: Span) -> List[Span]:
    """Spans that lie inside `parent` (at any depth), in start order."""
    _, s, d, _ = parent
    lo = bisect.bisect_left(prog.spans, s, key=lambda x: x[1])
    out = []
    for x in prog.spans[lo:]:
        if x[1] >= s + d:
            break
        if x is not parent and x[1] + x[2] <= s + d:
            out.append(x)
    return out


def engine_steps(prog: Program, window) -> List[Dict]:
    """One entry per `llm_engine.step` inside the window that ran a
    tick: {"span", "admitted" (it holds an `llm_engine.admit_one`),
    "to_dispatch_ms" (step start to the end of `tick_dispatch`),
    "host_ms" (the step less `tick_wait`)}."""
    out = []
    for st in in_window(prog, window, STEP):
        kids = {}
        for k in children(prog, st):
            kids.setdefault(k[0], k)
        td, tw = kids.get("llm_engine.tick_dispatch"), kids.get(
            "llm_engine.tick_wait")
        if td is None or tw is None:
            continue
        out.append({"span": st, "admitted": "llm_engine.admit_one" in kids,
                    "to_dispatch_ms": (td[1] + td[2] - st[1]) / 1e6,
                    "host_ms": (st[2] - tw[2]) / 1e6})
    return out


# ------------------------------------------------------ device, by names

def program_runs(trace: TR.Trace, name: str, window) -> List[TR.Event]:
    """Executions of the program `jit_<name>(...)` on the first chip that
    lie whole inside the window: by name, no votes."""
    t0, t1 = window
    return [r for r in TR.module_runs(TR.first_device(trace))
            if r[0].split("(", 1)[0] == name and r[1] >= t0
            and r[1] + r[2] <= t1]


def scope_seconds(prog: Program, runs: Sequence[TR.Event]
                  ) -> Dict[str, float]:
    """Device seconds of leaf operations inside `runs`, by scope ("" for
    an operation under no scope of SCOPES)."""
    out: Dict[str, float] = defaultdict(float)
    starts = [o[1] for o in prog.ops]
    for _, s, d in runs:
        i = bisect.bisect_left(starts, s)
        while i < len(prog.ops) and prog.ops[i][1] < s + d:
            out[prog.ops[i][0]] += prog.ops[i][2] / 1e9
            i += 1
    return dict(out)


def scope_share(run, program: str, scope: str) -> Optional[float]:
    """100 x device seconds under `scope` / device seconds of the runs of
    `program` in the traced window.  None where the trace carries no
    scope at all for that program (the parent commit)."""
    prog = load(run)
    if prog is None:
        return None
    runs = program_runs(run["trace"], program, run["window"])
    by = scope_seconds(prog, runs)
    if not runs or not any(k for k in by):
        return None
    return 100.0 * by.get(scope, 0.0) / (sum(r[2] for r in runs) / 1e9)


# ------------------------------------------------------------ idle time

def reported_window(prog: Program, window) -> Optional[Tuple[int, int]]:
    """The part of the window between the first and the last instant any
    program span covers.  The profiler records a span when it ENDS: of a
    step that the trace's end cuts, the spans still open (a spill under
    way, and every span around it) are not in the file, so nothing after
    the last recorded end can be laid to a span."""
    inside = [s for s in prog.spans if s[0] != BEGIN
              and s[1] + s[2] > window[0] and s[1] < window[1]]
    if not inside:
        return None
    return (max(window[0], min(s[1] for s in inside)),
            min(window[1], max(s[1] + s[2] for s in inside)))


def idle_by_span(prog: Program, trace: TR.Trace, window
                 ) -> Dict[str, float]:
    """Idle seconds of the first chip inside the window, by the innermost
    program span that covers them ("" where none does).  An idle interval
    is cut at every span boundary, so a gap that straddles two spans is
    shared between them."""
    t0, t1 = window
    busy = TR.union(TR.clip(TR.op_events(TR.first_device(trace)), t0, t1))
    idle, prev = [], t0
    for s, e in busy + [(t1, t1)]:
        if s > prev:
            idle.append((prev, s))
        prev = max(prev, e)
    spans = [s for s in prog.spans if s[1] + s[2] > t0 and s[1] < t1
             and s[0] != BEGIN]
    cuts = sorted({t0, t1} | {min(max(b, t0), t1) for _, s, d, _ in spans
                              for b in (s, s + d)})
    names = []
    for a, b in zip(cuts, cuts[1:]):
        cover = [s for s in spans if s[1] <= (a + b) / 2 < s[1] + s[2]]
        names.append(max(cover, key=lambda s: (s[1], -s[2]))[0]
                     if cover else "")
    out: Dict[str, float] = defaultdict(float)
    for s, e in idle:
        i = bisect.bisect_right(cuts, s) - 1
        while i < len(names) and cuts[i] < e:
            out[names[i]] += (min(e, cuts[i + 1]) - max(s, cuts[i])) / 1e9
            i += 1
    return dict(out)


# ---------------------------------------------------- host against device

def tick_clock_offsets_us(prog: Program, trace: TR.Trace, window
                          ) -> Dict[str, List[float]]:
    """For every `jit_llm_engine_tick` execution in the window whose step
    is in the trace: how long after the program's last instant on the
    device's clock `llm_engine.tick_wait` ended on the host's
    (`wait_end_after_device_end`: readback plus skew), and how long
    after `llm_engine.tick_dispatch` began the program started
    (`device_start_after_dispatch`: dispatch plus skew, negative where
    the device's clock runs behind).  The host clock leads the device's
    by at least -min(second) and at most min(first)."""
    runs = program_runs(trace, "jit_llm_engine_tick", window)
    waits = in_window(prog, window, "llm_engine.tick_wait")
    disps = in_window(prog, window, "llm_engine.tick_dispatch")
    out: Dict[str, List[float]] = {"wait_end_after_device_end": [],
                                   "device_start_after_dispatch": []}
    for _, s, d in runs:
        # the wait that ends first after the program's end, and the
        # dispatch that began last before that wait
        w = min((x for x in waits if x[1] + x[2] >= s + d),
                key=lambda x: x[1] + x[2], default=None)
        if w is None or w[1] + w[2] - (s + d) > 50e6:
            continue
        p = max((x for x in disps if x[1] <= w[1]), key=lambda x: x[1],
                default=None)
        out["wait_end_after_device_end"].append((w[1] + w[2] - s - d) / 1e3)
        if p is not None:
            out["device_start_after_dispatch"].append((s - p[1]) / 1e3)
    return out
