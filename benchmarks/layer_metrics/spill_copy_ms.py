"""Host time of one spill: median duration of `llm_engine.spill` (all of
`LLMEngine._spill_evicted`: the export program's dispatch, the two
copies of its row to the host, the per-block copies, the tier manager)
in the traced window.  0.0 when the window admitted and nothing
spilled; None when it admitted nothing (or the trace has no program
spans)."""
import program_spans as PS
import stats as S


def read(run):
    prog = PS.load(run)
    if prog is None:
        return None
    ms = PS.durations_ms(prog, run["window"])
    if not ms.get("llm_engine.admit_one"):
        return None
    return S.median(ms.get("llm_engine.spill", [0.0]))
