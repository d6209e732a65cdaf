"""Share of the device's idle seconds in the traced window that fall
inside a named child of `llm_engine.step` (so the host's part in every
gap has a name): idle time is cut at span boundaries and laid to the
innermost program span that covers it; what lies under no span, or
under `llm_engine.step` alone, is not attributed.  The window is cut to
what the program reported (`program_spans.reported_window`: a span is
written when it ends, so a step that the trace's end cuts leaves no
span).  The line printed names the largest shares."""
import program_spans as PS


def read(run):
    prog = PS.load(run)
    if prog is None or not PS.in_window(prog, run["window"], PS.STEP):
        return None
    by = PS.idle_by_span(prog, run["trace"],
                         PS.reported_window(prog, run["window"]))
    idle = sum(by.values())
    if not idle:
        return None
    print("IDLE by program span (s): " + ", ".join(
        f"{k or 'no span'} {v:.4f}" for k, v in sorted(
            by.items(), key=lambda kv: -kv[1])[:8]), flush=True)
    return 100.0 * sum(v for k, v in by.items()
                       if k not in ("", PS.STEP)) / idle
