"""Idle for want of traffic: the share of the first chip's idle seconds
in the traced window that fall under `llm_engine.idle`, the wait in
`LLMEngine.run` when a step did nothing and nothing is queued or live
(the window cut to what the program reported, and the idle laid to the
innermost span, as `idle_attributed_share` does).  The rest of the idle
is the host's doing.

This is the one reader of PR 34 that lays DEVICE idle to a HOST span,
across two clocks 1-3 ms apart: each stretch in which the engine stood
empty has its two ends misplaced by the offset at most, so the share
errs by at most offset x 2 x stretches / idle seconds; a stretch is
tens of milliseconds to a second long (the waits are 20 ms each, back
to back), where the gaps between two ticks are 2-3 ms.

0.0 where the window holds no such span but the engine's source writes
it (every traced run of such a program reports the metric); None
without program spans, and for a program that never writes the span."""
import program_spans as PS
import tick_gap as TG


def read(run):
    prog = PS.load(run)
    if prog is None or run.get("trace") is None or not TG.program_writes(
            TG.IDLE):
        return None
    window = PS.reported_window(prog, run["window"]) or run["window"]
    by = PS.idle_by_span(prog, run["trace"], window)
    idle = sum(by.values())
    return 100.0 * by.get(TG.IDLE, 0.0) / idle if idle else 0.0
