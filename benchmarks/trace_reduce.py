"""From a profiler trace to numbers: the one place that reads a trace.

`jax.profiler` writes `<dir>/plugins/profile/<time>/*.xplane.pb`;
`load()` flattens it (with nothing but JAX) into plain lists, and every
function below works on that flat form, so the tests run the same code
on a small recorded trace (`tests/data/*.json`, written by `dump()`).

What a TPU trace looks like (looked at by hand on a v5e, PR 23):

- one plane per chip, `/device:TPU:<n>`; its line `XLA Modules` has one
  event per execution of a compiled program, named
  `<jit name>(<fingerprint>)`; its line `XLA Ops` has the operations,
  nested (a `while` encloses its body's operations), so only LEAF events
  count as time in which an operation ran;
- the program under test wraps every jitted function in one closure
  called `probe` (observability/jit.py), so ALL its programs are named
  `jit_probe(<fingerprint>)`: the fingerprint is the only thing that
  tells the decode tick from an insert.  The drivers therefore write
  markers into the host plane (`bench:*` TraceAnnotations, from the
  engine's own scheduler thread through `Request.on_token`, or from the
  trainer's `report`), and `classify_modules()` learns from them which
  fingerprint is which program;
- host planes (`/host:CPU`) carry the markers as events on the line of
  the thread that wrote them.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Event = Tuple[str, int, int]            # name, start_ns, duration_ns
MARK = "bench:"
MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"


class Trace:
    """devices: {plane: {line: [Event sorted by start]}};
    markers: [Event] from every host plane, sorted by start."""

    def __init__(self, devices: Dict[str, Dict[str, List[Event]]],
                 markers: List[Event]):
        self.devices = devices
        self.markers = markers

    def to_json(self) -> dict:
        return {"devices": self.devices, "markers": self.markers}

    @staticmethod
    def from_json(d: dict) -> "Trace":
        dev = {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
               for p, lines in d["devices"].items()}
        return Trace(dev, [tuple(e) for e in d["markers"]])


def find_xplane(trace_dir: str) -> Optional[str]:
    hits = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return hits[-1] if hits else None


def load(trace_dir: str) -> Optional[Trace]:
    path = find_xplane(trace_dir)
    if path is None:
        return None
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: Dict[str, Dict[str, List[Event]]] = {}
    markers: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            lines: Dict[str, List[Event]] = {}
            for line in plane.lines:
                evs = [(e.name, int(e.start_ns), int(e.duration_ns))
                       for e in line.events]
                evs.sort(key=lambda e: e[1])
                lines[line.name] = evs
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(MARK):
                        markers.append((e.name, int(e.start_ns),
                                        int(e.duration_ns)))
    markers.sort(key=lambda e: e[1])
    return Trace(devices, markers)


def dump(trace: Trace, path: str) -> None:
    """Write the flat form (gzip JSON): a recorded trace for the tests."""
    with gzip.open(path, "wt") as f:
        json.dump(trace.to_json(), f)


def read_dump(path: str) -> Trace:
    with gzip.open(path, "rt") as f:
        return Trace.from_json(json.load(f))


# ------------------------------------------------------------- intervals

def leaves(events: Sequence[Event]) -> List[Event]:
    """Events that enclose no other event of the line.  A `while` or a
    `conditional` spans its body; the body's operations are what ran.
    Events of one line are nested or disjoint, so, ordered by start with
    the longer first, an event is a leaf when the next one starts at or
    after its end."""
    evs = sorted(events, key=lambda e: (e[1], -e[2]))
    out: List[Event] = []
    for i, (name, s, d) in enumerate(evs):
        if i + 1 == len(evs) or evs[i + 1][1] >= s + d:
            out.append((name, s, d))
    return out


def union(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(e - s for s, e in intervals)


def clip(events: Sequence[Event], t0: int, t1: int) -> List[Tuple[int, int]]:
    return [(max(s, t0), min(s + d, t1)) for _, s, d in events
            if s + d > t0 and s < t1]


def span(trace: Trace) -> Tuple[int, int]:
    """[first start, last end] over every device line."""
    lo, hi = None, None
    for lines in trace.devices.values():
        for evs in lines.values():
            if evs:
                lo = evs[0][1] if lo is None else min(lo, evs[0][1])
                end = max(s + d for _, s, d in evs)
                hi = end if hi is None else max(hi, end)
    return (lo or 0, hi or 0)


def op_events(lines: Dict[str, List[Event]]) -> List[Event]:
    evs = lines.get(OPS_LINE)
    if evs is None:       # fall back: module executions are device time too
        evs = lines.get(MODULE_LINE, [])
    return leaves(evs)


def busy_seconds(trace: Trace, window: Optional[Tuple[int, int]] = None
                 ) -> Tuple[float, float]:
    """(mean over chips of the seconds in which an operation ran,
    window seconds)."""
    t0, t1 = window or span(trace)
    if not trace.devices or t1 <= t0:
        return 0.0, 0.0
    per = [total(union(clip(op_events(lines), t0, t1)))
           for lines in trace.devices.values()]
    return sum(per) / len(per) / 1e9, (t1 - t0) / 1e9


# --------------------------------------------------------------- modules

def module_runs(lines: Dict[str, List[Event]]) -> List[Event]:
    return lines.get(MODULE_LINE, [])


def first_device(trace: Trace) -> Dict[str, List[Event]]:
    return trace.devices[sorted(trace.devices)[0]] if trace.devices else {}


def classify_modules(trace: Trace, rules: Dict) -> Dict[str, str]:
    """{module name -> kind}.  `rules` maps a marker name to the kind of
    the module execution that ENDED last before that marker: a driver
    writes `bench:token` right after the engine read a decode tick's
    tokens back, so the module that ended last before it is the tick.
    Each module takes the kind it was seen as most often.  Host and
    device clocks agree only to a few milliseconds, so a short program
    that follows at once (the export of a spill, 1 ms) can collect a few
    stray votes: a kind listed under `rules["unique"]` is ONE program
    (the engine compiles one tick), and only the module with most votes
    keeps it.  Modules left without a kind take `rules["*"]` if given."""
    import bisect

    runs = module_runs(first_device(trace))
    ends = sorted((s + d, name) for name, s, d in runs)
    keys = [e for e, _ in ends]
    votes: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for mname, ms, _ in trace.markers:
        kind = rules.get(mname)
        if not isinstance(kind, str):
            continue
        i = bisect.bisect_right(keys, ms) - 1
        if i >= 0:
            votes[ends[i][1]][kind] += 1
    for kind in rules.get("unique", ()):
        holders = sorted(((v.get(kind, 0), n) for n, v in votes.items()
                          if v.get(kind)), reverse=True)
        for _, n in holders[1:]:
            del votes[n][kind]
    out: Dict[str, str] = {}
    for name in {n for n, _, _ in runs}:
        if votes.get(name):
            out[name] = max(votes[name].items(), key=lambda kv: kv[1])[0]
        elif isinstance(rules.get("*"), str):
            out[name] = rules["*"]
    return out


def durations_by_kind(trace: Trace, kinds: Dict[str, str],
                      window: Optional[Tuple[int, int]] = None
                      ) -> Dict[str, List[float]]:
    """{kind: [device seconds of each execution]} on the first chip."""
    t0, t1 = window or span(trace)
    out: Dict[str, List[float]] = defaultdict(list)
    for name, s, d in module_runs(first_device(trace)):
        if s >= t0 and s + d <= t1 and name in kinds:
            out[kinds[name]].append(d / 1e9)
    return out


# ------------------------------------------------------------ operations

def top_ops(trace: Trace, n: int = 10,
            window: Optional[Tuple[int, int]] = None) -> List[List]:
    t0, t1 = window or span(trace)
    acc: Dict[str, float] = defaultdict(float)
    for name, s, d in op_events(first_device(trace)):
        if s + d > t0 and s < t1:
            acc[name] += d / 1e9
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(trace: Trace, kinds: Dict[str, str], n: int = 10,
              window: Optional[Tuple[int, int]] = None) -> List[List]:
    """Idle time on the first chip, summed by what stood on either side:
    `after <kind> / before <kind>` of the module executions around each
    gap (the host's work between two programs), and the markers that
    fell inside it."""
    t0, t1 = window or span(trace)
    lines = first_device(trace)
    busy = union(clip(op_events(lines), t0, t1))
    runs = [r for r in module_runs(lines) if r[1] + r[2] > t0 and r[1] < t1]
    starts = sorted((s, kinds.get(nm, "other")) for nm, s, d in runs)
    import bisect

    sk = [s for s, _ in starts]
    mk = [m[1] for m in trace.markers]
    acc: Dict[str, float] = defaultdict(float)
    prev = t0
    for s, e in busy + [(t1, t1)]:
        if s > prev:
            # the program that ran last before the gap, and the one that
            # runs when the device resumes (a program's event starts at
            # or before its first operation and ends at or after its last)
            i = bisect.bisect_right(sk, max(prev - 1, t0)) - 1
            j = bisect.bisect_right(sk, s) - 1
            before = starts[i][1] if i >= 0 else "start"
            after = starts[j][1] if j >= 0 else "start"
            if s >= t1:
                after = "end"
            inside = {trace.markers[k][0][len(MARK):] for k in range(
                bisect.bisect_left(mk, prev), bisect.bisect_right(mk, s))}
            label = (f"inside {before}" if i == j and s < t1
                     else f"after {before} / before {after}")
            if inside:
                label += " [" + "+".join(sorted(inside)) + "]"
            acc[label] += (s - prev) / 1e9
        prev = max(prev, e)
    return [[k, v] for k, v in sorted(acc.items(), key=lambda kv: -kv[1])[:n]]
