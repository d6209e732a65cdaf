"""Device time under a PATH of scope names (`moe`, then `experts`), and
the model's own counters: what the readers of the expert layer's metrics
share.  `program_spans.scope_share` knows a fixed list of single names
and gives an operation its innermost one; an expert layer's scopes nest
(`moe/router`, `moe/experts`, `moe/shared`), so this module keeps each
operation's whole `op_name` and asks for components in order.  It reads
the same `.xplane.pb` the same way (`program_spans`' wire-format
helpers).  On a trace or a run that carries none of this every function
returns None and nothing raises.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Dict, List, Optional, Tuple

import program_spans as PS
import trace_reduce as TR

Op = Tuple[str, int, int]                     # op_name, start ns, dur ns


# What XLA:TPU rewrites into a call of its own loses its `op_name`, and
# with it the scope it stood under: a grouped product (`lax.ragged_dot`)
# becomes the kernel `ragged-dot-*` with its `ragged-dot-metadata`, an
# argsort becomes `sort`.  In the programs read here both stand under
# `moe/experts` and nowhere else.
RENAMED = {"ragged-dot": ["moe", "experts"], "sort": ["moe", "experts"]}


def _components(op_name: str) -> List[str]:
    if "/" not in op_name:
        for prefix, path in RENAMED.items():
            if op_name.startswith(prefix):
                return path + [op_name]
    return [part.rsplit("(", 1)[-1].rstrip(")")
            for part in op_name.split("/")]


def under(op_name: str, path: Tuple[str, ...]) -> bool:
    """`path`'s names appear among the components of `op_name`, in order."""
    parts = iter(_components(op_name))
    return all(name in parts for name in path)


def named_ops(xspace: bytes) -> List[Op]:
    """Leaf events of the first TPU plane's `XLA Ops` line, each with
    its instruction's whole `op_name`: `program_spans.device_ops` with
    the step that cuts an `op_name` down to one scope taken out (that
    module may not be edited here, and its parser of the wire format is
    not worth a second copy)."""
    cut, PS.scope_of = PS.scope_of, (lambda op_name: op_name)
    try:
        return PS.device_ops(xspace)
    finally:
        PS.scope_of = cut


def load(run) -> Optional[List[Op]]:
    """This run's operations by name, or None; looked up once.  A test
    hands recorded ones in as `run["named_ops"]`."""
    if "named_ops" in run:
        return run["named_ops"]
    run["named_ops"] = None
    if PS.load(run) is None:                   # also: is it THIS run's trace
        return None
    dirs = sorted(glob.glob(os.path.join(PS.ROOT, ".bench_trace", "*")),
                  key=os.path.getmtime)
    path = TR.find_xplane(dirs[-1]) if dirs else None
    if path is None:
        return None
    with open(path, "rb") as f:
        run["named_ops"] = named_ops(f.read())
    return run["named_ops"]


def program_seconds(run, program: str, *path: str
                    ) -> Optional[Tuple[float, float, int]]:
    """(device seconds of operations under `path`, device seconds of the
    program's executions, their number) over the executions of
    `jit_<program>` that lie whole inside the traced window.  None where
    the trace names no operation of that program under `path[0]`."""
    ops = load(run)
    if not ops:
        return None
    runs = PS.program_runs(run["trace"], program, run["window"])
    starts = [o[1] for o in ops]
    inside = 0.0
    seen = False
    for _, s, d in runs:
        i = bisect.bisect_left(starts, s)
        while i < len(ops) and ops[i][1] < s + d:
            if under(ops[i][0], path[:1]):
                seen = True
                if under(ops[i][0], path):
                    inside += ops[i][2] / 1e9
            i += 1
    if not runs or not seen:
        return None
    return inside, sum(r[2] for r in runs) / 1e9, len(runs)


def counters(run) -> Optional[Dict]:
    """`engine.stats()["counters"]` of the engine that served the run's
    requests (summed on the device since the engine started), or None."""
    for rec in run["records"].get("recs", ()):
        engine = getattr(rec.handle, "engine", None)
        if engine is not None:
            return engine.stats().get("counters")
    return None
