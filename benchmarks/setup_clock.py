"""What set-up was made of, as the program itself counted it (PR 49).

`setup_s` runs from the process's start to the window's first instant.
The benchmark owns part of it (import, opening the chip, the reference's
weights, the check's idle sample, the pre-roll); the program owns the
engine's warm-up or the trainer's set-up, and inside them the compiles.
The program keeps ONE record of that part (`record`):

- a serving run: `engine.stats()["warmup"]` = `seconds`, the wall of
  `LLMEngine.warmup()`, and `programs`, the rows the warm-up added to
  `observability.jit_stats()`;
- a training run: the summary's `setup_process` = `calls`, `seconds` by
  phase and `programs`, over ALL `run_pod_training` calls of the
  process (the driver makes two and keeps the second's summary: the
  first finds nothing compiled and costs several times the second).

A row of `programs` is one tracked program's `traces`, its first-call
wall (`compile_seconds_total`) and that wall's stages `trace_seconds`,
`lower_seconds`, `backend_seconds` (the compiler on a persistent-cache
miss, loading on a hit).  Beside the record, `compile_cache.stats()` as
the drivers stored it when the window opened
(`records["cache_at_window"]`): the PROCESS's `seconds` of the same
stages, whoever compiled.

Everything here returns None for a program that keeps no such record.
"""

from __future__ import annotations

from typing import Dict, Optional

STAGES = ("trace", "lower", "backend")


def record(run) -> Optional[Dict]:
    """`{"seconds": the program's set-up wall, "programs": rows}` and,
    of a training run, `"calls"` and `"phases"` (seconds by phase)."""
    for rec in run["records"].get("recs", ()):
        engine = getattr(rec.handle, "engine", None)
        if engine is not None:      # the engine that served the requests
            return engine.stats().get("warmup")
    job = (run["records"].get("summary") or {}).get("setup_process")
    if not job:
        return None
    return {"seconds": float(sum(job["seconds"].values())),
            "programs": job["programs"], "calls": job["calls"],
            "phases": job["seconds"]}


def stage_sums(rows) -> Dict[str, float]:
    return {s: sum(r[s + "_seconds"] for r in rows.values())
            for s in STAGES}


def report(run) -> Optional[Dict]:
    """Prints the run's one `SETUP` line and returns the tracked
    programs' stage sums; None (and no line) without a record."""
    if "setup_clock" in run:
        return run["setup_clock"]
    run["setup_clock"] = None
    rec = record(run)
    if rec is None:
        return None
    rows = rec["programs"]
    sums = stage_sums(rows)
    if "phases" in rec:
        parts = ["run_pod_training set-up, %d calls: %.3f s = " % (
            rec["calls"], rec["seconds"]) + " + ".join(
                "%s %.3f" % kv for kv in rec["phases"].items())]
    else:
        parts = ["warmup %.3f s" % rec["seconds"]]
    parts.append("by program (traces: trace + lower + backend of "
                 "first-call wall s): " + "; ".join(
                     "%s %d: %.3f + %.3f + %.3f of %.3f" % (
                         name, r["traces"], r["trace_seconds"],
                         r["lower_seconds"], r["backend_seconds"],
                         r["compile_seconds_total"])
                     for name, r in sorted(rows.items())))
    parts.append("tracked programs: " + " + ".join(
        "%s %.3f" % (s, sums[s]) for s in STAGES))
    at = run["records"].get("cache_at_window") or {}
    if "seconds" in at:
        sec = at["seconds"]
        parts.append(
            "process at the window: " + " + ".join(
                "%s %.3f" % (s, sec[s]) for s in STAGES)
            + "; %d hits %d misses" % (at["hits"], at["misses"])
            + "; under no tracked program: " + " + ".join(
                "%s %.3f" % (s, sec[s] - sums[s]) for s in STAGES))
    print("SETUP " + " | ".join(parts), flush=True)
    run["setup_clock"] = sums
    return sums
