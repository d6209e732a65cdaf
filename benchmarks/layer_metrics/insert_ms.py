"""Mean device duration of the prefill programs in the traced window:
the executions named `jit_llm_engine_insert(<fingerprint>)` (one
fingerprint a bucket), by name, no votes."""
import program_spans as PS


def read(run):
    if run["trace"] is None:
        return None
    runs = PS.program_runs(run["trace"], "jit_llm_engine_insert",
                           run["window"])
    return sum(r[2] for r in runs) / len(runs) / 1e6 if runs else None
