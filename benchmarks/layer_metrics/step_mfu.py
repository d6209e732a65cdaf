"""Model FLOP/s utilisation of the train step from the device trace:
operations one step needs (counts.train_step_flops: matmul parameters
without the embedding table, causal attention, no recomputation) over
the median device duration of the `train_step` program, over chips x the
bf16 peak."""
import stats as S
import trace_reduce as TR


def read(run):
    rec, tr = run["records"], run["trace"]
    if tr is None or "step_flops" not in rec:
        return None
    steps = TR.durations_by_kind(tr, run["kinds"], run["window"]).get(
        "train_step")
    if not steps:
        return None
    return 100.0 * rec["step_flops"] / S.median(steps) / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
