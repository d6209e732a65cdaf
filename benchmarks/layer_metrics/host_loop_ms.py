"""The scheduler thread's own work in one engine step: median, over the
steps of the traced window that admitted nothing, of `llm_engine.step`
less its `llm_engine.tick_wait` (control, the empty admit, the tick's
dispatch, the emits with their callbacks, the gauges).  Host spans on
the profiler's clock."""
import program_spans as PS
import stats as S


def read(run):
    prog = PS.load(run)
    if prog is None:
        return None
    return S.median([s["host_ms"] for s in PS.engine_steps(prog, run["window"])
                     if not s["admitted"]])
