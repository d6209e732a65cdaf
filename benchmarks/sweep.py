#!/usr/bin/env python3
"""Find a serving cell's knee once: run the cell at several fixed rates
(one process each, one after another: a chip belongs to one process)
and print, per rate, what each run printed.  The knee is the highest
rate at which the backlog at the window's end stays near empty and the
time to first token stays flat; the cell's file then gets 0.8 x (a cell
judged on tails) or 1.25 x (a cell judged on tokens/s) of it, written
as a number.  Never run by a check.

    python3 benchmarks/sweep.py <cell> <seconds> <lifetime_s> <rate> [<rate> ...]

`lifetime_s` is a request's expected time in the engine at that load;
each rate runs with `warm_start = round(rate x lifetime_s)` (0 for none).
"""
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
KEEP = ("BACKLOG", "GENERATOR", "TABLE", "COMPARED", "PEAK", "{")


def main() -> None:
    cell, seconds, lifetime = sys.argv[1], sys.argv[2], float(sys.argv[3])
    for i, rate in enumerate(sys.argv[4:]):
        out = subprocess.run(
            [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
             "--seed", str(7001 + i), "--seconds", seconds, "--trace", "0",
             "--set", f"rate_per_s={rate}",
             "--set", f"warm_start={round(float(rate) * lifetime)}"],
            capture_output=True, text=True)
        print(f"=== rate {rate} rc={out.returncode}")
        print("\n".join(ln for ln in out.stdout.splitlines()
                        if ln.startswith(KEEP)), flush=True)
        if out.returncode:
            print(out.stderr[-3000:], flush=True)


if __name__ == "__main__":
    main()
