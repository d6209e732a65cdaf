"""How long every live slot waits when a request comes in: median, over
the engine steps of the traced window that admitted (they hold an
`llm_engine.admit_one` span), of the time from the start of
`llm_engine.step` to the end of its `llm_engine.tick_dispatch` (evict,
spill, insert dispatch and the wait for the first token all lie before
the tick goes out).  Host spans on the profiler's clock."""
import program_spans as PS
import stats as S


def read(run):
    prog = PS.load(run)
    if prog is None:
        return None
    waits = [s["to_dispatch_ms"] for s in PS.engine_steps(prog, run["window"])
             if s["admitted"]]
    return S.median(waits)
