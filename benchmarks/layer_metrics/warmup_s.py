"""The part of set-up the program owns, on its own clock (PR 49).  A
serving cell: `engine.stats()["warmup"]["seconds"]`, the wall of
`LLMEngine.warmup()` (one insert a bucket and the tick, traced, lowered,
compiled or loaded, and run once; the export rows).  `pretrain-1chip`:
the summary's `setup_process["seconds"]` summed: the four phases
(`init_params`, `place`, `h2d`, `compile_warmup`) of BOTH
`run_pod_training` calls the driver makes before the window; a step
traced again in a call's first timed step is in `trace_lower_s` and in
no phase.  None for a program that keeps neither."""
import setup_clock as SC


def read(run):
    rec = SC.record(run)
    return None if rec is None else float(rec["seconds"])
