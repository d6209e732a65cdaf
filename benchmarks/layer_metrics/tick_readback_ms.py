"""How long after the host knows a tick is done its `[K, B]` tokens are
on the host: median duration of `llm_engine.tick_readback`
(`LLMEngine._read_back`: `np.asarray` of outputs that
`llm_engine.tick_ready` has already waited for; argument `bytes=`) over
the steps of the traced window that admitted nothing.  Host spans on the
host's clock alone.  The program asks for the copy before it waits, so
this is what is left of the copy once the tick is known done: what only
a tick in flight can hide.  None for a program that writes no such
span."""
import program_spans as PS
import stats as S
import tick_gap as TG


def read(run):
    prog = PS.load(run)
    if prog is None:
        return None
    return S.median([s["kids"]["tick_readback"][2] / 1e6
                     for s in TG.tick_steps(prog, run["window"])
                     if s["quiet"] and "tick_readback" in s["kids"]])
