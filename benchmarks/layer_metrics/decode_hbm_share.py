"""The decode tick against the bandwidth roofline: bytes the ticks of
the traced interval HAD to read (weights once a tick, and the live KV
rows of every token they produced: counts.decode_step_bytes) over the
chip's peak bandwidth, over the device time those ticks took."""
import counts
import trace_reduce as TR


def read(run):
    rec, tr = run["records"], run["trace"]
    if tr is None or not rec.get("trace_host_window"):
        return None
    ticks = TR.durations_by_kind(tr, run["kinds"], run["window"]).get("tick")
    if not ticks:
        return None
    h0, h1 = rec["trace_host_window"]
    c = run["config"]
    # every decode token emitted in the interval was produced by a tick
    # that read that sequence's rows 0 .. prompt + k - 1 and wrote one
    rows = 0
    for r in rec["recs"]:
        p = len(r.req.prompt)
        rows += sum(p + k + 1 for k, t in enumerate(r.times)
                    if k > 0 and h0 <= t < h1)
    per_tick = counts.decode_step_bytes(c, [])
    need = len(ticks) * per_tick + rows * counts.kv_bytes_per_token(c)
    return 100.0 * need / run["peaks"]["hbm_bytes_per_s"] / sum(ticks)
