"""The insert's selective scan against its roofline, for the family
`mamba_mqa_decoder`, as `ssm_prefill_roofline` reads `sambay_decoder`'s:
the larger of the time its bytes and its operations take at the chip's
peaks (`counts_mamba_mqa.scan_seconds`: Delta and x read and y written
in float32 a channel, 7 operations a channel a state, in every Mamba
layer; the bytes' time is the larger here, because `peaks.json` has the
matrix unit's rate alone and this work is the vector unit's) for the
REAL tokens the inserts of the traced interval prefilled, over the
device time those `jit_llm_engine_insert` executions spent under
`ssm/state`.

Which execution prefilled how many real tokens: the engine writes
`tokens=` on every `llm_engine.insert_dispatch` span, and a step waits
for its own tick, so whatever a step dispatched ran on the device
inside that step's span.  Steps whole inside the window are taken one
by one: an insert execution belongs to the step that holds its
midpoint, and a step counts when it has as many executions as
dispatches.  None where the trace holds no such step."""
import bisect

import counts_mamba_mqa as K
import program_spans as PS
import scope_paths as SP
import trace_reduce as TR

PROGRAM = "jit_llm_engine_insert"
DISPATCH = "llm_engine.insert_dispatch"


def read(run):
    if run["trace"] is None or "attn_layer_period" not in run["config"]:
        return None
    prog, ops = PS.load(run), SP.load(run)
    if prog is None or not ops:
        return None
    execs = [r for r in TR.module_runs(TR.first_device(run["trace"]))
             if r[0].split("(", 1)[0] == PROGRAM]
    starts = [o[1] for o in ops]
    tokens, seconds = 0, 0.0
    for _, s0, d0, _ in PS.in_window(prog, run["window"], PS.STEP):
        sent = [sp for sp in prog.spans if sp[0] == DISPATCH
                and s0 <= sp[1] < s0 + d0 and "tokens" in sp[3]]
        ran = [r for r in execs if s0 <= r[1] + r[2] // 2 < s0 + d0]
        if not sent or len(sent) != len(ran):
            continue
        tokens += sum(int(sp[3]["tokens"]) for sp in sent)
        for _, s, d in ran:
            i = bisect.bisect_left(starts, s)
            while i < len(ops) and ops[i][1] < s + d:
                if SP.under(ops[i][0], ("ssm", "state")):
                    seconds += ops[i][2] / 1e9
                i += 1
    if not tokens or not seconds:
        return None
    return 100.0 * K.scan_seconds(run["config"], tokens, run["peaks"]) \
        / seconds
