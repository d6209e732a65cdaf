"""What a train step costs beyond its program: median, over the
`train.step` spans of the traced window, of the span less the device
duration of the `jit_train_step` executions that start inside it
(dispatch, the fence, the report, the goodput bookkeeping)."""
import program_spans as PS
import stats as S


def read(run):
    prog = PS.load(run)
    if prog is None:
        return None
    runs = PS.program_runs(run["trace"], "jit_train_step", run["window"])
    out = []
    for _, s, d, _ in PS.in_window(prog, run["window"], "train.step"):
        held = [r[2] for r in runs if s <= r[1] < s + d]
        if held:
            out.append((d - sum(held)) / 1e6)
    return S.median(out)
