"""Driver `serve_engine`: one `LLMEngine` in this process, its own
`run()` loop on a scheduler thread, load offered open-loop from the main
thread at the fixed rate the cell's file states.

The driver adds no decode loop: it calls `LLMEngine.warmup`, `submit`
and `run`, and listens through `Request.on_token`.  Times are taken by
the benchmark (`time.monotonic()` in `on_token`, on the engine's own
scheduler thread), from the instant a request was DUE, not from when the
generator managed to submit it.

Cell file keys this driver reads: `engine` (EngineConfig fields),
`rate_per_s`, `preroll_s` (offered load before the window opens, so that
the window starts on a busy engine; counted as set-up), `warm_start`
(requests due at the pre-roll's first instant with answers cut to a
spread of remaining lengths: the population a steady state would hold,
so that the pre-roll need not last a whole request lifetime), `drain_s`
(how long after the window a request due inside it may still finish),
`check` (`requests` x `max_tokens` served on the idle engine before the
window, `window_requests` sampled from those served INSIDE the window,
under a full pool beside live decodes; `stat`, `limit`), `trace`
(`start_frac`, `seconds`).

`correct`: the reference judges both samples after the window (the
comparison needs no timed window and should not lengthen set-up), by
the tokens alone: each served token's reference logit against the
reference maximum, given the served prefix.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, NamedTuple

import numpy as np

import stats as S
import traffic as T


class Rec:
    __slots__ = ("req", "due", "submitted", "times", "handle", "in_window")

    def __init__(self, req, due):
        self.req = req
        self.due = due
        self.submitted = None
        self.times: List[float] = []
        self.handle = None
        self.in_window = False


class Served(NamedTuple):
    """A judged request and its served tokens, free of the engine."""
    req: T.Req
    tokens: List[int]


class _Refused:
    """Stands in for the handle of a request the engine refused."""
    tokens: List[int] = []
    admitted_at = finished_at = None
    finish_reason = "refused"

    def done(self):
        return True

    def cancel(self):
        return False


def _check_stat(deficits: np.ndarray) -> Dict[str, float]:
    return {"mean_deficit": float(np.mean(deficits)),
            "rms_deficit": float(np.sqrt(np.mean(deficits ** 2))),
            "max_deficit": float(np.max(deficits)),
            "flip_share": float(np.mean(deficits > 0))}


def run(ctx) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    from ray_tpu._private import compile_cache
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    cell, c, fam, ref = ctx.cell, ctx.config, ctx.family, ctx.reference
    log = ctx.log
    compile_cache.configure()
    ecfg = dict(cell["engine"])
    ecfg["prefill_buckets"] = tuple(ecfg["prefill_buckets"])
    top = max(ecfg["prefill_buckets"])
    mix = T.load(ctx.workload["traffic"])
    mc = fam.model_config(c, max_seq_len=ecfg["max_seq_len"],
                          compute_dtype="bfloat16", param_dtype="bfloat16")

    # ---- set-up: weights from the seed, on the device, one jitted call
    weights = ref.init_weights(c, ctx.seed, jnp.bfloat16)
    params = fam.program_params(weights)
    if ctx.control:
        params = jax.jit(fam.lower_precision_params)(weights)
        weights = None
    jax.block_until_ready(params)
    log(f"weights on device at +{ctx.since_start():.1f}s")
    engine = LLMEngine(params, mc, EngineConfig(**ecfg),
                       rng_seed=ctx.seed % (2 ** 31 - 1))
    engine.warmup()
    log(f"engine warm at +{ctx.since_start():.1f}s "
        f"(programs traced: {engine.trace_count})")

    marks = {"on": False}

    def make_req(rec: Rec) -> Request:
        times = rec.times

        def on_token(_rid, _tok):
            times.append(time.monotonic())
            if marks["on"]:
                with jax.profiler.TraceAnnotation(
                        "bench:first" if len(times) == 1 else "bench:token"):
                    pass

        return Request(prompt=list(rec.req.prompt),
                       max_tokens=rec.req.max_tokens, temperature=0.0,
                       on_token=on_token,
                       chunked_prefill=len(rec.req.prompt) > top)

    stop = threading.Event()
    thread = threading.Thread(target=engine.run, args=(stop,),
                              name="engine-scheduler", daemon=True)
    thread.start()
    try:
        out = _measure(ctx, engine, make_req, marks, mix, top)
    finally:
        stop.set()
        thread.join(timeout=60)
    if ctx.control:
        # the control's parameters and the reference's do not fit the
        # chip together: the engine goes, the reference draws its own
        # again (same seed, same weights)
        engine.params = None             # the pool may stay; these may not
        out["records"] = {}              # handles of a dead engine
        del engine, params, make_req, thread
        weights = ref.init_weights(c, ctx.seed, jnp.bfloat16)
    out["correct"], out["records"]["check"] = _judge(
        ctx, ref, weights, out.pop("samples"))
    return out


def _judge(ctx, ref, weights, samples):
    """The served tokens of both samples against the reference: ONE
    statistic over all of them, and each sample's own printed beside
    it.  A request that was not served to its full length fails."""
    ck, c = ctx.cell["check"], ctx.config
    t0 = time.monotonic()
    parts = {name: np.concatenate([
        ref.served_token_deficits(weights, c, r.req.prompt, r.tokens)
        for r in recs]) for name, recs in samples.items() if recs}
    stat = _check_stat(np.concatenate(list(parts.values())))
    value = stat[ck["stat"]]
    whole = all(len(r.tokens) == r.req.max_tokens
                for recs in samples.values() for r in recs)
    correct = bool(value <= ck["limit"]) and whole and all(
        samples.values())
    ctx.log(f"reference for {sum(p.size for p in parts.values())} served "
            f"tokens in {time.monotonic() - t0:.1f}s")
    print(f"COMPARED {ck['stat']}={value!r} limit={ck['limit']!r} "
          f"served_whole={whole} (also {stat}; by sample: " + "; ".join(
              f"{name} {len(samples[name])} requests {p.size} tokens "
              f"{ck['stat']}={_check_stat(p)[ck['stat']]!r}"
              for name, p in parts.items()) + ")", flush=True)
    return correct, stat


def _measure(ctx, engine, make_req, marks, mix, top):
    from ray_tpu._private import compile_cache

    cell, c, log = ctx.cell, ctx.config, ctx.log
    ecfg = cell["engine"]
    vocab = c["vocab_size"]

    # ---- output check, first sample: the idle engine, before the window
    ck = cell["check"]
    cover = sorted(set(list(ecfg["prefill_buckets"])
                       + [min(int(mix["prompt"].get("max", top)),
                              ecfg["max_seq_len"] - ck["max_tokens"] - 1)]))
    cover = [b for b in cover if b >= int(mix["prompt"].get("min", 1))]
    sample = T.check_sample(mix, ck["requests"], cover, ck["max_tokens"],
                            ctx.seed, vocab)
    idle = [Rec(r, 0.0) for r in sample]
    t0 = time.monotonic()
    for r in idle:
        r.handle = engine.submit(make_req(r))
    for r in idle:
        r.handle.result(timeout=600)
    served_s = time.monotonic() - t0
    if ecfg.get("kv_layout") == "paged" and ecfg.get("prefix_cache", True):
        # the spill path of the prefix cache gathers evicted blocks with
        # the engine's export program: run it once so that it compiles
        # here and not at the first eviction inside the window
        engine.call_on_scheduler(
            lambda: engine.export_prefix(list(sample[0].prompt),
                                         max_blocks=1), timeout_s=600.0)
    log(f"check: {len(idle)} requests x {ck['max_tokens']} tokens served in "
        f"{served_s:.1f}s, lengths {sorted(len(r.req.prompt) for r in idle)}")

    # ---- the window
    seconds = float(ctx.seconds)
    pre = float(cell.get("preroll_s", 0.0))
    drain_s = float(cell.get("drain_s", 0.0))
    sched = T.schedule(mix, float(cell["rate_per_s"]), pre + seconds,
                       ctx.seed, vocab, warm=int(cell.get("warm_start", 0)),
                       splits=[pre])
    recs = [Rec(r, r.due_s) for r in sched]
    cache0 = compile_cache.stats()
    tr = cell.get("trace", {})
    tracer = None
    if ctx.trace:
        tracer = ctx.make_tracer(
            pre + seconds * float(tr.get("start_frac", 0.5)),
            float(tr.get("seconds", 3.0)), marks)
    log(f"set-up done at +{ctx.since_start():.1f}s; offering "
        f"{len(recs)} requests at {cell['rate_per_s']}/s "
        f"({pre}s pre-roll + {seconds}s window)")
    base = time.monotonic()
    if tracer:
        tracer.start(base)
    w0, w1 = base + pre, base + pre + seconds
    ctx.window_opens(w0)
    evicted0 = None
    for r in recs:
        due = base + r.due
        r.due = due
        r.in_window = w0 <= due < w1
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        if r.in_window and evicted0 is None:
            evicted0 = _evictions(engine)
        try:
            r.handle = engine.submit(make_req(r))
        except ValueError as e:          # refused at submit: a failure
            log(f"refused at submit: {e}")
            r.handle = _Refused()
        r.submitted = time.monotonic()
    rest = w1 - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    backlog = engine.stats()
    evicted1 = _evictions(engine)
    deadline = w1 + drain_s
    for r in recs:
        if r.in_window and not r.handle.done():
            try:
                r.handle.result(max(0.0, deadline - time.monotonic()))
            except TimeoutError:
                pass
    t_end = time.monotonic()
    unfinished = [r for r in recs if not r.handle.done()]
    for r in unfinished:
        r.handle.cancel()
    if tracer:
        tracer.finish()
    cache1 = compile_cache.stats()
    print(f"BACKLOG at window end: queued={backlog['queued']} "
          f"active={backlog['active_slots']}; unfinished at "
          f"+{t_end - w1:.1f}s after the window: {len(unfinished)}; "
          f"prefix-cache evictions inside the window: "
          f"{(evicted1 or 0) - (evicted0 or 0)}", flush=True)

    # ---- reduce
    win = [r for r in recs if r.in_window]
    failed = sum(1 for r in win if r.handle.finish_reason != "length")
    done_in_window = [r for r in recs if r.handle.finished_at is not None
                      and r.handle.finish_reason == "length"
                      and w0 <= r.handle.finished_at < w1]
    tokens_done = sum(len(r.handle.tokens) for r in done_in_window)
    ttft = [(r.times[0] - r.due) * 1e3 for r in win if r.times]
    # every gap between two consecutive tokens of one request that ENDS
    # inside the window, whichever request it belongs to
    gaps = [(b - a) * 1e3 for r in recs
            for a, b in zip(r.times, r.times[1:]) if w0 <= b < w1]
    late = [(r.submitted - r.due) * 1e3 for r in recs]
    wait = [(r.handle.admitted_at - r.due) * 1e3 for r in win
            if r.handle.admitted_at is not None]
    print(f"GENERATOR late p50={S.percentile(late, 50):.3f}ms "
          f"p95={S.percentile(late, 95):.3f}ms max={max(late):.3f}ms; "
          f"ttft n={len(ttft)} gaps n={len(gaps)}; compiles in window: "
          f"{cache1['misses'] - cache0['misses']} misses, "
          f"{cache1['hits'] - cache0['hits']} hits", flush=True)
    emitted = sum(1 for r in recs for t in r.times if w0 <= t < w1)
    qs = (50, 75, 90, 95, 99)
    print("TABLE ttft_ms " + " ".join(
        f"p{q}={S.percentile(ttft, q):.1f}" for q in qs)
        + f" | queue_wait_ms p50={S.percentile(wait, 50):.1f} | gap_ms "
        + " ".join(f"p{q}={S.percentile(gaps, q):.2f}" for q in qs)
        + f" mean={sum(gaps) / max(1, len(gaps)):.2f} | tokens emitted in "
        f"window {emitted} ({emitted / seconds:.2f}/s), of requests "
        f"completed in window {tokens_done} ({tokens_done / seconds:.2f}/s)",
        flush=True)
    if ctx.raw_path:
        import json
        import os

        os.makedirs(os.path.dirname(ctx.raw_path) or ".", exist_ok=True)
        with open(ctx.raw_path, "w") as f:
            json.dump({"window": [0.0, seconds], "requests": [
                {"due": r.due - w0, "prompt": len(r.req.prompt),
                 "max_tokens": r.req.max_tokens,
                 "admitted": None if r.handle.admitted_at is None
                 else r.handle.admitted_at - w0,
                 "times": [t - w0 for t in r.times]} for r in recs]}, f)

    # ---- output check, second sample: requests served INSIDE the
    # window, admitted under a full pool beside live decodes (seeded
    # choice among those due in the window that ran to their length)
    whole = [r for r in win if r.handle.finish_reason == "length"]
    pick = np.random.RandomState((ctx.seed + 1543) % (2 ** 32)).permutation(
        len(whole))[: int(ck.get("window_requests", 0))]
    samples = {
        "idle": [Served(r.req, list(r.handle.tokens)) for r in idle],
        "window": [Served(whole[i].req, list(whole[i].handle.tokens))
                   for i in sorted(pick)]}
    records = {
        "window": (w0, w1), "recs": recs, "gaps_ms": gaps,
        "cache_at_window": cache0, "cache_after": cache1,
        "trace_host_window": tracer.host_window if tracer else None,
        "marker_rules": {"bench:token": "tick", "bench:first": "insert",
                         "unique": ["tick"], "*": "other"},
    }
    return {"attempted": len(win), "failed": failed, "samples": samples,
            "e2e": {"gap_mean_ms": sum(gaps) / len(gaps) if gaps else None},
            "records": records,
            "trace_dir": tracer.dir if tracer else None}


def _evictions(engine):
    return engine.stats().get("prefix_cache", {}).get("evictions")
