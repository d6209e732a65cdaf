"""Host time of one landing: median duration of `llm_engine.spill_land`
(`LLMEngine._land_spills`: reading the exported rows on the host, whose
transfer `_spill_evicted` started before the insert and the tick were
dispatched, the per-block copies, the tier manager) in the traced
window.  Beside `spill_copy_ms`, which since PR 27 is the dispatch
alone, it says whether the copy was hidden behind the tick or only
moved.

0.0 when nothing landed in the window, whether its admissions evicted
nothing or it admitted nothing at all (about one traced window in ten
of `chat-decode`: 3 s at 0.8 requests/s): every traced run of a program
that lands its spills reports the metric.  None when the trace has no
program spans, and for a program that never writes the span (the parent
of PR 27).  A window without a landing cannot tell those two programs
apart, so the reader asks the engine's source for the span's name."""
import program_spans as PS
import stats as S

SPAN = "llm_engine.spill_land"


def _program_lands():
    try:
        from ray_tpu.serve.llm import engine
        with open(engine.__file__) as f:
            return f'"{SPAN}"' in f.read()
    except Exception:
        return False


def read(run):
    prog = PS.load(run)
    if prog is None or not _program_lands():
        return None
    return S.median(PS.durations_ms(prog, run["window"]).get(SPAN) or [0.0])
