#!/usr/bin/env python3
"""Run ONE cell of the benchmark once, in this process, and print the
result as the last line of standard output.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell, configuration, family, driver or
per-layer metric is a file found by the name `BENCHMARK.json` gives it
(see benchmarks/README.md); this file knows none of them.

- refuses (exit 3, no result line) unless JAX's platform is `tpu` and
  JAX sees at least the chips the cell asks for.  `--rehearse` is the
  one way round: it swaps in the cell's `rehearsal` overrides (tiny
  sizes) and allows the CPU, and its line says `"rehearsal": true`; the
  numbers of such a run are not device numbers and go nowhere;
- `--control` hands the program lower-precision parameters (the cell's
  control): `correct` has to come out false.  Never used by a check.
"""

from __future__ import annotations

import time

_T_PROCESS = time.monotonic()

import argparse
import importlib.util
import json
import os
import shutil
import sys
import threading
import types

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)          # the benchmark's own modules, flat
sys.path.insert(1, ROOT)          # the program under test: `ray_tpu`


def load_module(kind: str, name: str):
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise SystemExit(f"benchmark: no {kind}/{name}.py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def merge(base: dict, over: dict) -> dict:
    out = dict(base)
    for k, v in over.items():
        out[k] = merge(out[k], v) if isinstance(v, dict) and isinstance(
            out.get(k), dict) else v
    return out


class Tracer:
    """Profiles `seconds` of the window, starting `offset` seconds after
    `start(base)`, from a thread of its own (serving) or from calls the
    driver makes between steps (training: `begin()` / `end()`)."""

    def __init__(self, trace_dir, offset, seconds, marks):
        self.dir, self.offset, self.seconds = trace_dir, offset, seconds
        self.marks = marks
        self.host_window = None
        self._thread = None
        self._t0 = None

    def begin(self):
        import jax

        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.marks["on"] = True
        with jax.profiler.TraceAnnotation("bench:trace_begin"):
            pass
        self._t0 = time.monotonic()

    def end(self):
        import jax

        t1 = time.monotonic()
        with jax.profiler.TraceAnnotation("bench:trace_end"):
            pass
        self.marks["on"] = False
        jax.profiler.stop_trace()
        self.host_window = (self._t0, t1)

    def start(self, base):
        def body():
            time.sleep(max(0.0, base + self.offset - time.monotonic()))
            self.begin()
            time.sleep(self.seconds)
            self.end()

        self._thread = threading.Thread(target=body, name="bench-tracer",
                                        daemon=True)
        self._thread.start()

    def finish(self):
        if self._thread is not None:
            self._thread.join(timeout=300)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--keep-trace", default="")
    ap.add_argument("--dump-raw", default="",
                    help="write every request's timestamps here (trials)")
    ap.add_argument("--set", action="append", default=[],
                    help="cell-file override key.path=json, for sweeps "
                         "and trials only; a check never passes it")
    args = ap.parse_args()

    bench = load_json(ROOT, "BENCHMARK.json")
    wl = next((w for w in bench["workloads"] if w["name"] == args.workload),
              None)
    if wl is None:
        raise SystemExit(f"benchmark: no workload {args.workload!r} in "
                         "BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"] if c["name"] == wl["config"])
    config = load_json(ROOT, cfg_entry["file"])
    cell = load_json(HERE, "workloads", wl["name"] + ".json")
    if args.rehearse:
        config = merge(config, cell.get("rehearsal", {}).get("config", {}))
        cell = merge(cell, cell.get("rehearsal", {}).get("cell", {}))

    for item in vars(args)["set"]:
        path, _, raw = item.partition("=")
        over = json.loads(raw)
        for k in reversed(path.split(".")):
            over = {k: over}
        cell = merge(cell, over)

    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if not args.rehearse and (platform != "tpu" or len(devs) < wl["chips"]):
        sys.stderr.write(
            f"benchmark: cell {wl['name']} needs {wl['chips']} TPU chip(s); "
            f"JAX sees {len(devs)} x {platform}. Refusing to run.\n")
        return 3
    kind = devs[0].device_kind
    peaks_all = load_json(HERE, "peaks.json")
    if kind not in peaks_all and not args.rehearse:
        sys.stderr.write(f"benchmark: device kind {kind!r} is not in "
                         "benchmarks/peaks.json\n")
        return 3
    peaks = peaks_all.get(kind) or peaks_all["TPU v5 lite"]

    ctx = types.SimpleNamespace()
    ctx.workload, ctx.cell, ctx.config = wl, cell, config
    ctx.seed, ctx.seconds, ctx.trace = args.seed, args.seconds, bool(args.trace)
    ctx.control, ctx.rehearse, ctx.chips = args.control, args.rehearse, wl["chips"]
    ctx.peaks = peaks
    ctx.raw_path = args.dump_raw
    ctx.family = load_module("families", config["family"])
    ctx.reference = load_module("reference", ctx.family.REFERENCE)
    ctx.since_start = lambda: time.monotonic() - _T_PROCESS
    ctx.log = lambda m: print(f"[bench +{ctx.since_start():7.1f}s] {m}",
                              flush=True)
    opened = {}
    ctx.window_opens = lambda t: opened.setdefault("t", t)
    trace_dir = os.path.join(ROOT, ".bench_trace",
                             f"{wl['name']}-{args.seed}")
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.make_tracer = lambda off, secs, marks: Tracer(trace_dir, off, secs,
                                                      marks)
    ctx.log(f"cell {wl['name']} config {wl['config']} seed {args.seed} "
            f"seconds {args.seconds} trace {args.trace} on {len(devs)} x "
            f"{kind}")

    driver = load_module("drivers", cell["driver"])
    out = driver.run(ctx)

    e2e = dict(out["e2e"])
    if "t" in opened:              # a control run has no window
        e2e["setup_s"] = opened["t"] - _T_PROCESS
    peak_mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devs[: max(1, wl["chips"])])
    print(f"PEAK_HBM_BYTES {peak_mem}", flush=True)
    device = {"platform": platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(peak_mem)}

    def applies(m):
        return "workloads" not in m or wl["name"] in m["workloads"]

    result = {"correct": bool(out["correct"]), "attempted": out["attempted"],
              "failed": out["failed"]}
    metrics = {}
    if not args.trace:
        for m in bench["end_to_end"]:
            if applies(m) and e2e.get(m["name"]) is not None:
                metrics[m["name"]] = {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
    else:
        import trace_reduce as TR

        trace = TR.load(out["trace_dir"]) if out.get("trace_dir") else None
        run = {"trace": trace, "records": out["records"], "cell": cell,
               "config": config, "peaks": peaks, "chips": wl["chips"],
               "e2e": e2e, "window": None, "kinds": {}}
        if trace is not None:
            names = {m[0]: m for m in trace.markers}
            lo = names.get("bench:trace_begin")
            hi = names.get("bench:trace_end")
            run["window"] = (lo[1], hi[1]) if lo and hi else TR.span(trace)
            run["kinds"] = TR.classify_modules(
                trace, out["records"].get("marker_rules", {}))
            busy, wsec = TR.busy_seconds(trace, run["window"])
            device["busy_s"], device["window_s"] = busy, wsec
            result["breakdown"] = {
                "device_ops": TR.top_ops(trace, 10, run["window"]),
                "idle_gaps": TR.idle_gaps(trace, run["kinds"], 10,
                                          run["window"])}
            if args.keep_trace:
                os.makedirs(os.path.dirname(args.keep_trace) or ".",
                            exist_ok=True)
                TR.dump(trace, args.keep_trace)
        for m in bench["per_layer"]:
            if not applies(m):
                continue
            value = load_module("layer_metrics", m["name"]).read(run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        print("E2E (traced run, not judged): " + json.dumps(e2e), flush=True)
    result["metrics"] = metrics
    result["device"] = device
    if args.rehearse:
        result["rehearsal"] = True
    if args.control:
        result["control"] = True
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
