"""Seconds this process spent tracing (Python to jaxpr) and lowering
(jaxpr to MLIR) its tracked programs before the window: the part of a
first call that NO compilation cache saves, paid by every process, warm
or cold (PR 49).  Summed over the rows of `setup_clock.record`
(`trace_seconds` + `lower_seconds`, each the calling thread's outermost
stages during the call that traced): what the engine's warm-up, or the
process's `run_pod_training` calls, added to `jit_stats()`.  The two
stages are split where JAX's events split them and work shifts between
them from run to run (a jaxpr traced under one and found cached under
the other): their SUM is the steady number.  Prints the run's `SETUP`
line: by program its traces, stages and first-call wall; the process's
totals when the window opened and what of them no tracked program
accounts for (eager one-operation programs, the benchmark's own
reference).  None for a program that keeps no such rows."""
import setup_clock as SC


def read(run):
    sums = SC.report(run)
    return None if sums is None else sums["trace"] + sums["lower"]
