"""Benchmark: flagship Llama training-step throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N}

Metric: training tokens/sec/chip for a ~1B-param Llama-family decoder
(bf16 params+compute, AdamW, flash-attention pallas kernel, dots-policy
remat, donated train state, 4 steps per dispatch via lax.scan).

Baseline normalization: the reference stack publishes no absolute
samples/sec (BASELINE.md) — its northstar is "matching NCCL-GPU
samples/sec/chip". Chips differ in peak FLOPs (A100 312 bf16 TFLOPs vs
v5e 197), so the hardware-normalized framework-efficiency comparison is
MFU: a tuned torch-DDP/FSDP A100 run sustains ~40% MFU, hence

  vs_baseline = our_mfu / 0.40.

vs_baseline > 1.0 means this framework extracts a larger fraction of its
chip than the reference extracts of its GPU on the same workload class.
The absolute cross-silicon ratio (tokens/s vs a 40%-MFU A100) is also
reported in detail as `vs_a100_tokens`.
"""

from __future__ import annotations

import json
import os
import time

REFERENCE_MFU = 0.40
A100_PEAK_FLOPS = 312e12

def _bench_config(on_tpu: bool):
    from ray_tpu.models.llama import LlamaConfig

    if on_tpu:
        import jax.numpy as jnp

        # ~1B-param Llama (llama2 width, 4 layers): large matmuls saturate
        # the MXU; remat + donation keep HBM under the 16 GiB budget at
        # batch 16.
        # remat="dots" (keep matmul outputs, recompute elementwise) beats
        # full per-layer remat by ~2.5 MFU points at the same batch 16
        # (full remat at batch 20/24 is slower than dots at 16 — see
        # PERF.md round-2 sweep).
        import os

        # At this geometry (V=32k, D=4096) the fused blockwise loss is a
        # measured net LOSS (64.3% vs 69.2% MFU): its backward recompute
        # of block logits costs ~4.5% extra FLOPs to save only ~3GB of
        # loss-stage HBM traffic, and batch 16 fits without it. It exists
        # for geometries where logits don't fit (128k vocab, long seq) —
        # see PERF.md round-4 notes.
        os.environ.setdefault("RAY_TPU_FUSED_LOSS", "0")
        batch = int(os.environ.get("RAY_TPU_BENCH_BATCH", "16"))
        steps = int(os.environ.get("RAY_TPU_BENCH_STEPS", "4"))
        return LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=4, n_heads=32,
            n_kv_heads=8, hidden_dim=11008, max_seq_len=1024,
            attn_impl="flash", remat="dots",
            param_dtype=jnp.bfloat16), batch, 1024, steps
    return LlamaConfig.tiny(), 4, 64, 2


def _bench_decode(train_config, on_tpu: bool, device_kind: str) -> dict:
    """KV-cache greedy decode throughput on one chip: prefill a prompt,
    then K scanned decode_step iterations per dispatch (decode is
    HBM-bandwidth-bound — the metric that matters for Serve latency)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import lax

    from ray_tpu.models.llama import (
        decode_step, init_kv_cache, init_params, prefill,
    )

    config = train_config
    if on_tpu:
        batch, prompt, steps, rounds = 8, 128, 64, 3
        max_len = 512
    else:
        batch, prompt, steps, rounds = 2, 8, 4, 1
        max_len = 64

    params = init_params(config, jax.random.key(1))
    rng = np.random.RandomState(1)
    prompt_toks = jnp.asarray(
        rng.randint(0, config.vocab_size, (batch, prompt)).astype("int32"))

    jit_prefill = jax.jit(
        lambda p, t: prefill(p, t, config, max_len=max_len))

    def decode_k(params, cache, tok, pos):
        def body(carry, _):
            cache, tok, pos = carry
            logits, cache = decode_step(params, cache, tok, pos, config)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return (cache, nxt, pos + 1), nxt

        (cache, tok, pos), toks = lax.scan(
            body, (cache, tok, pos), None, length=steps)
        return cache, tok, pos, toks

    jit_decode = jax.jit(decode_k, donate_argnums=(1,))

    def time_decode(p) -> float:
        """Warmup + timed rounds for one weight set; returns best
        seconds per call. Sync via a scalar fetch, which cannot return
        before the computation has landed."""
        logits, cache = jit_prefill(p, prompt_toks)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        pos = jnp.full((batch,), prompt, jnp.int32)
        cache, tok, pos, _ = jit_decode(p, cache, tok, pos)
        int(tok[0])
        times = []
        for _ in range(rounds):
            t0 = time.perf_counter()
            cache, tok, pos, toks = jit_decode(p, cache, tok, pos)
            int(tok[0])
            times.append(time.perf_counter() - t0)
        return min(times)

    per_call = time_decode(params)
    tok_s = batch * steps / per_call
    step_ms = per_call / steps * 1000

    # Prefill throughput too (one timed call).
    t0 = time.perf_counter()
    logits2, cache2 = jit_prefill(params, prompt_toks)
    float(logits2[0, 0])
    prefill_s = time.perf_counter() - t0

    detail = {
        "device": device_kind, "batch": batch, "prompt": prompt,
        "decode_steps": steps,
        "per_token_latency_ms": round(step_ms, 3),
        "prefill_tokens_per_sec": round(
            batch * prompt / prefill_s, 2),
        "note": "greedy KV-cache decode, bf16, single chip "
                "(serve replica inference path)",
    }

    if on_tpu:
        # Weight-only int8 serving config: decode is weight-HBM-bound,
        # so halving weight bytes buys real throughput (measured 1.30x
        # at this geometry; logits corr 0.9999, greedy tokens
        # unchanged on the correctness check in tests/test_llama_decode).
        from ray_tpu.models.llama import quantize_weights_int8

        qp = quantize_weights_int8(params)
        del params
        q_per = time_decode(qp)
        detail["int8_tokens_per_sec"] = round(batch * steps / q_per, 2)
        detail["int8_per_token_latency_ms"] = round(
            q_per / steps * 1000, 3)
        detail["int8_vs_bf16"] = round(per_call / q_per, 3)

    return {
        "metric": "llama_decode_tokens_per_sec",
        "value": round(tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": detail,
    }


def _bench_serve(train_config, on_tpu: bool, device_kind: str) -> dict:
    """Serving throughput: the continuous-batching engine
    (serve/llm/engine.py) vs lockstep static batching on the SAME
    geometry and the same Poisson-arrival mixed-length workload.

    Continuous: slot pool fed as requests arrive; aggregate tokens/s is
    total generated tokens over the span from first arrival to last
    completion, plus per-request TTFT (p50/p99) and per-output-token
    latency. Static: groups of `num_slots` requests in arrival order,
    prompts padded to the largest bucket, every group decoding to the
    workload max — batch k's clock starts at max(prev batch end, last
    arrival in the group), which is exactly the deficiency the engine
    removes. On CPU the geometry shrinks to a smoke configuration
    (tests assert correctness only; the TPU target is >= 1.5x static).
    """
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import (
        EngineConfig, LLMEngine, Request, static_batch_generate,
    )

    if on_tpu:
        import jax.numpy as jnp

        config = LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=4, n_heads=32,
            n_kv_heads=8, hidden_dim=11008, max_seq_len=1024,
            param_dtype=jnp.bfloat16)
        slots, buckets, max_len = 8, (64, 128, 256), 512
        n_requests = 48
        p_lo, p_hi, o_lo, o_hi = 16, 256, 16, 128
        # Pay the host's per-tick work (dispatch, readback, bookkeeping)
        # once per 16 decode steps — still one program. Unmeasured on a
        # local chip.
        decode_block = 16
    else:
        config = LlamaConfig.tiny()
        slots, buckets, max_len = 4, (8, 16), 64
        n_requests = 12
        p_lo, p_hi, o_lo, o_hi = 2, 16, 2, 8
        decode_block = 4

    import jax

    params = init_params(config, jax.random.key(1))
    rng = np.random.RandomState(7)
    requests = [
        Request(
            prompt=rng.randint(0, config.vocab_size,
                               rng.randint(p_lo, p_hi + 1)).tolist(),
            max_tokens=int(rng.randint(o_lo, o_hi + 1)))
        for _ in range(n_requests)
    ]
    total_tokens = sum(r.max_tokens for r in requests)
    max_steps = max(r.max_tokens for r in requests)

    # --- static baseline first (also calibrates the arrival rate).
    _, batch_secs = static_batch_generate(
        params, config, requests, batch_size=slots, pad_to=buckets[-1],
        steps=max_steps)
    static_compute_s = sum(batch_secs)
    static_tok_s = total_tokens / static_compute_s

    # Poisson arrivals at 2x the request rate static sustains: a load
    # the lockstep path cannot keep up with, so the comparison measures
    # engine capacity, not arrival starvation.
    mean_out = total_tokens / n_requests
    rate = 2.0 * static_tok_s / mean_out                 # req/s
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    arrivals -= arrivals[0]                              # first at t=0

    # Static under the same trace (simulated from measured batch times):
    # batch k starts when its last request has arrived AND the previous
    # batch finished; its requests' first tokens land at batch end
    # (lockstep results return together).
    static_ttft = []
    clock = 0.0
    for k, bsec in enumerate(batch_secs):
        group = slice(k * slots, min((k + 1) * slots, n_requests))
        clock = max(clock, float(arrivals[group][-1])) + bsec
        static_ttft.extend((clock - a) for a in arrivals[group])
    static_span = clock - float(arrivals[0])
    static_trace_tok_s = total_tokens / static_span

    # --- continuous engine on the same trace (real wall clock).
    engine = LLMEngine(params, config, EngineConfig(
        num_slots=slots, max_seq_len=max_len, prefill_buckets=buckets,
        decode_block=decode_block))
    warm = [engine.submit(Request(prompt=[1] * b, max_tokens=2))
            for b in buckets]
    engine.drain()
    assert all(w.done() for w in warm)

    handles = []
    start = time.monotonic()
    next_i = 0
    while len(handles) < n_requests or engine.has_work():
        now = time.monotonic() - start
        while next_i < n_requests and arrivals[next_i] <= now:
            h = engine.submit(requests[next_i])
            h.submitted_at = start + float(arrivals[next_i])
            handles.append(h)
            next_i += 1
        if not engine.step() and next_i < n_requests:
            time.sleep(min(0.001, max(0.0,
                                      arrivals[next_i] - (
                                          time.monotonic() - start))))
    gen_tokens = sum(len(h.tokens) for h in handles)
    span = max(h.finished_at for h in handles) - start
    cont_tok_s = gen_tokens / span

    ttft = np.asarray([h.ttft_s for h in handles]) * 1000
    tpot = np.asarray([h.tpot_s for h in handles]) * 1000
    st = engine.stats()
    detail = {
        "device": device_kind, "num_slots": slots,
        "prefill_buckets": list(buckets), "max_seq_len": max_len,
        "decode_block": decode_block,
        "requests": n_requests, "completed": st["completed"] - len(warm),
        "arrival_rate_req_s": round(rate, 3),
        "prompt_len_range": [p_lo, p_hi],
        "output_len_range": [o_lo, o_hi],
        "generated_tokens": gen_tokens,
        "static_tokens_per_sec": round(static_trace_tok_s, 2),
        "static_compute_tokens_per_sec": round(static_tok_s, 2),
        "continuous_vs_static": round(cont_tok_s / static_trace_tok_s,
                                      3),
        "ttft_p50_ms": round(float(np.percentile(ttft, 50)), 2),
        "ttft_p99_ms": round(float(np.percentile(ttft, 99)), 2),
        "static_ttft_p50_ms": round(
            float(np.percentile(static_ttft, 50)) * 1000, 2),
        "static_ttft_p99_ms": round(
            float(np.percentile(static_ttft, 99)) * 1000, 2),
        "tpot_mean_ms": round(float(tpot.mean()), 3),
        "engine_traces": st["trace_count"],
        "note": "continuous batching (slot pool, bucketed prefill) vs "
                "lockstep static batching, Poisson arrivals at 2x "
                "static capacity, mixed prompt/output lengths",
    }
    return {
        "metric": "llama_serve_tokens_per_sec",
        "value": round(cont_tok_s, 2),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": detail,
    }


def _bench_serve_paged(on_tpu: bool, device_kind: str) -> dict:
    """Paged KV + prefix cache + routing at 4x the PR-1 arrival rate
    with a 60% shared system prompt (the chat/RAG shape both levers are
    built for). Three runs over the SAME Poisson trace:

    - dense engine (PR-1 layout) — the baseline;
    - paged engine, 1 replica — prefix hits skip the shared prompt's
      prefill, so TTFT drops and the pool holds more concurrency;
    - paged engines, 2 replicas behind the router's queue-depth-aware
      power-of-two-choices pick (in-process: the policy function is the
      same one the LLMRouter deployment runs) — p99 TTFT must come in
      under the 1-replica value at this load.

    Reported alongside the serve leg's fields: sustained tokens/s,
    p99 TTFT per configuration, and the prefix-cache hit rate.
    """
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import (
        EngineConfig, LLMEngine, Request, static_batch_generate,
    )
    from ray_tpu.serve.llm.router import p2c_pick

    if on_tpu:
        import jax.numpy as jnp

        config = LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=4, n_heads=32,
            n_kv_heads=8, hidden_dim=11008, max_seq_len=1024,
            param_dtype=jnp.bfloat16)
        slots, buckets, max_len = 8, (64, 128, 256), 512
        n_requests, block_size, sys_len = 48, 16, 96
        t_lo, t_hi, o_lo, o_hi = 16, 128, 16, 128
        decode_block = 16
    else:
        config = LlamaConfig.tiny()
        slots, buckets, max_len = 4, (8, 16), 64
        n_requests, block_size, sys_len = 48, 4, 8
        t_lo, t_hi, o_lo, o_hi = 2, 8, 2, 8
        decode_block = 4

    import jax

    params = init_params(config, jax.random.key(1))
    rng = np.random.RandomState(11)
    system_prompt = rng.randint(1, config.vocab_size, sys_len).tolist()
    requests = []
    for i in range(n_requests):
        tail = rng.randint(1, config.vocab_size,
                           rng.randint(t_lo, t_hi + 1)).tolist()
        prompt = (system_prompt + tail if rng.rand() < 0.6 else
                  rng.randint(1, config.vocab_size,
                              sys_len + len(tail)).tolist())
        requests.append(Request(prompt=prompt[:buckets[-1]],
                                max_tokens=int(rng.randint(o_lo,
                                                           o_hi + 1))))
    total_tokens = sum(r.max_tokens for r in requests)
    max_steps = max(r.max_tokens for r in requests)

    # Calibrate against the static lockstep path, then load at 4x the
    # PR-1 bench's 2x multiple — a rate where prefill work dominates a
    # single dense replica.
    _, batch_secs = static_batch_generate(
        params, config, requests, batch_size=slots, pad_to=buckets[-1],
        steps=max_steps)
    static_tok_s = total_tokens / sum(batch_secs)
    mean_out = total_tokens / n_requests
    rate = 4.0 * static_tok_s / mean_out                 # req/s
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    arrivals -= arrivals[0]

    def _mk_engine(layout):
        eng = LLMEngine(params, config, EngineConfig(
            num_slots=slots, max_seq_len=max_len,
            prefill_buckets=buckets, decode_block=decode_block,
            kv_layout=layout, kv_block_size=block_size))
        eng.warmup()        # compiles the tick + one insert per bucket
        assert eng.trace_count == len(buckets) + 1
        return eng

    pick_rng = __import__("random").Random(3)

    def _drive(engines, sim_tick_s=0.0):
        """Replay the trace: one scheduler thread per engine (the
        deployment shape); submissions go to the p2c-lighter engine
        (probed queue+active, the router's score). `sim_tick_s` adds a
        sleep per scheduler step standing in for device time: replicas
        in production own separate accelerators, so their step time
        overlaps — in-process engines share this host's cores and
        would otherwise serialize, hiding exactly the scaling a second
        replica buys."""
        import threading

        stop = threading.Event()

        def _loop(e):
            while not stop.is_set():
                worked = e.step()
                if sim_tick_s:
                    time.sleep(sim_tick_s)
                elif not worked:
                    time.sleep(0.0002)

        threads = [threading.Thread(target=_loop, args=(e,), daemon=True)
                   for e in engines]
        for t in threads:
            t.start()
        handles = []
        start = time.monotonic()
        for i in range(n_requests):
            wait = start + float(arrivals[i]) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            load = {e: e.stats()["queued"] + e.stats()["active_slots"]
                    for e in engines}
            eng = p2c_pick(engines, load, pick_rng)
            h = eng.submit(requests[i])
            h.submitted_at = start + float(arrivals[i])
            handles.append(h)
        while any(h.finished_at is None for h in handles):
            time.sleep(0.0005)
        stop.set()
        for t in threads:
            t.join()
        span = max(h.finished_at for h in handles) - start
        toks = sum(len(h.tokens) for h in handles)
        ttft = np.asarray([h.ttft_s for h in handles]) * 1000
        return {
            "tokens_per_sec": toks / span,
            "ttft_p50_ms": float(np.percentile(ttft, 50)),
            "ttft_p99_ms": float(np.percentile(ttft, 99)),
        }

    dense = _drive([_mk_engine("dense")])
    paged_engine = _mk_engine("paged")
    paged = _drive([paged_engine])
    pstats = paged_engine.stats()
    # Prefix-hit TTFT: for each fresh system prompt, the first request
    # prefills everything (cold), the second shares the prefix and
    # prefills only the suffix bucket (warm).
    cold_ms, warm_ms = [], []
    for _ in range(8):
        sysk = rng.randint(1, config.vocab_size, sys_len).tolist()
        for out in (cold_ms, warm_ms):
            tail = rng.randint(1, config.vocab_size,
                               buckets[-1] - sys_len).tolist()
            h = paged_engine.submit(Request(prompt=sysk + tail,
                                            max_tokens=2))
            paged_engine.drain()
            out.append(h.ttft_s * 1000)
    # Replica scaling: both legs pace steps with the same simulated
    # device latency so the comparison isolates queueing/routing (the
    # thing a second replica changes) from host-core contention.
    sim_tick_s = 0.004
    one = _drive([_mk_engine("paged")], sim_tick_s=sim_tick_s)
    two = _drive([_mk_engine("paged"), _mk_engine("paged")],
                 sim_tick_s=sim_tick_s)

    pc = pstats.get("prefix_cache", {})
    lookups = pc.get("hits", 0) + pc.get("misses", 0)
    detail = {
        "device": device_kind, "num_slots": slots,
        "prefill_buckets": list(buckets), "max_seq_len": max_len,
        "decode_block": decode_block, "kv_block_size": block_size,
        "requests": n_requests,
        "arrival_rate_req_s": round(rate, 3),
        "arrival_multiple": 4.0,
        "shared_prompt_fraction": 0.6,
        "system_prompt_len": sys_len,
        "dense_tokens_per_sec": round(dense["tokens_per_sec"], 2),
        "paged_tokens_per_sec": round(paged["tokens_per_sec"], 2),
        "paged_vs_dense": round(
            paged["tokens_per_sec"] / dense["tokens_per_sec"], 3),
        "dense_ttft_p99_ms": round(dense["ttft_p99_ms"], 2),
        "paged_ttft_p99_ms": round(paged["ttft_p99_ms"], 2),
        "router_sim_tick_ms": sim_tick_s * 1000,
        "one_replica_tokens_per_sec": round(one["tokens_per_sec"], 2),
        "one_replica_ttft_p99_ms": round(one["ttft_p99_ms"], 2),
        "two_replica_tokens_per_sec": round(two["tokens_per_sec"], 2),
        "two_replica_ttft_p99_ms": round(two["ttft_p99_ms"], 2),
        "two_vs_one_p99": round(
            two["ttft_p99_ms"] / one["ttft_p99_ms"], 3),
        "prefix_hit_rate": round(pc.get("hits", 0) / lookups, 3)
        if lookups else None,
        "prefix_hit_tokens": pc.get("hit_tokens", 0),
        "prefix_ttft_cold_ms": round(float(np.median(cold_ms)), 3),
        "prefix_ttft_warm_ms": round(float(np.median(warm_ms)), 3),
        "kv_blocks": pstats.get("kv", {}),
        "engine_traces": pstats["trace_count"],
        "note": "dense vs paged KV (prefix cache on) with real compute; "
                "1-vs-2 paged replicas under router p2c paced by a "
                "simulated per-step device latency (replicas own "
                "separate accelerators in production). Poisson arrivals "
                "at 4x static capacity, 60% shared system prompt",
    }
    return {
        "metric": "llama_serve_paged",
        "value": round(paged["tokens_per_sec"], 2),
        "unit": "tokens/s",
        "vs_baseline": None,
        "detail": detail,
    }


def _bench_serve_disagg(on_tpu: bool, device_kind: str) -> dict:
    """Disaggregated prefill/decode under a bimodal Poisson mix: 10%
    long-prefill requests (the 4k-RAG shape; "batch" lane) riding on
    90% short chat traffic ("interactive" lane). Three legs over the
    SAME arrival trace at the same engine count:

    - chat-only: one monolithic paged engine serving just the chat
      stream — the healthy reference for chat-lane TTFT;
    - monolithic mixed: two paged engines behind p2c serving the full
      mix — each long prefill stalls a shared engine for the whole
      prompt, so co-resident chat TTFT degrades;
    - disagg: one prefill engine (chunked admission through the prefix
      cache) + one decode engine (same two-engine budget). Long
      requests prefill on the prefill engine, export KV, and are
      adopted batch-lane into the decode pool (KVImporter — the same
      calls the PrefillServer/DecodeServer deployments wrap); chat
      goes straight to decode. Chat-lane p99 TTFT should hold within
      ~1.1x of the chat-only leg while monolithic mixed degrades.

    Off-TPU, per-step device time is simulated from admitted prefill
    tokens (a long prefill occupies its engine for prompt_len *
    per-token cost — the stall disaggregation removes); on TPU the
    compute is real and no pacing is added. Reports per-lane p50/p99
    TTFT and TPOT for every leg; headline value is disagg chat p99
    TTFT / chat-only chat p99 TTFT.
    """
    import dataclasses
    import threading

    import jax
    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.disagg import KVImporter
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.serve.llm.router import p2c_pick

    if on_tpu:
        import jax.numpy as jnp

        config = LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=4, n_heads=32,
            n_kv_heads=8, hidden_dim=11008, max_seq_len=4608,
            param_dtype=jnp.bfloat16)
        slots, block_size, dblock = 8, 16, 16
        chat_buckets, long_len = (128, 256), 4096
        mono_buckets, max_len = (128, 256, 4096), 4352
        c_lo, c_hi, co_lo, co_hi, long_out = 32, 192, 16, 64, 32
        n_requests, rate = 48, 6.0
        n_blocks = slots * (max_len // block_size) + 256
        sim_decode_s, sim_prefill_tok_s = 0.0, 0.0
    else:
        config = LlamaConfig.tiny()
        slots, block_size, dblock = 4, 4, 2
        chat_buckets, long_len = (8, 16), 48
        mono_buckets, max_len = (8, 48), 64
        c_lo, c_hi, co_lo, co_hi, long_out = 3, 8, 3, 8, 4
        n_requests, rate = 60, 15.0
        n_blocks = 96
        # Simulated device time: ~per-dispatch decode cost plus a
        # per-prefill-token cost, so a 48-token prefill stalls its
        # engine ~6x longer than a chat admission — the ratio the
        # disagg split is built to hide.
        sim_decode_s, sim_prefill_tok_s = 0.002, 0.0015

    params = init_params(config, jax.random.key(2))
    rng = np.random.RandomState(17)

    # Bimodal trace: exactly 10% long-prefill requests, Poisson
    # arrivals shared by every leg.
    long_slots = set(rng.choice(n_requests, n_requests // 10,
                                replace=False).tolist())
    trace = []
    for i in range(n_requests):
        if i in long_slots:
            prompt = rng.randint(1, config.vocab_size, long_len).tolist()
            trace.append(("long", Request(prompt=prompt,
                                          max_tokens=long_out,
                                          slo="batch")))
        else:
            prompt = rng.randint(
                1, config.vocab_size,
                rng.randint(c_lo, c_hi + 1)).tolist()
            trace.append(("chat", Request(
                prompt=prompt,
                max_tokens=int(rng.randint(co_lo, co_hi + 1)),
                slo="interactive")))
    arrivals = np.cumsum(rng.exponential(1.0 / rate, n_requests))
    arrivals -= arrivals[0]

    def _mk(buckets, *, preempt=False):
        eng = LLMEngine(params, config, EngineConfig(
            num_slots=slots, max_seq_len=max_len,
            prefill_buckets=buckets, decode_block=dblock,
            kv_layout="paged", kv_block_size=block_size,
            num_kv_blocks=n_blocks,
            preempt_hold_s=0.05 if preempt else None,
            preempt_cooldown_s=0.25 if preempt else None))
        eng.warmup()
        return eng

    pick_rng = __import__("random").Random(7)

    def _run_leg(engines, route, leg_trace, leg_arrivals):
        """Step `engines` on scheduler threads (paced by the simulated
        per-step device cost) and replay the trace through `route`,
        which owns per-request submission and returns a record dict
        carrying "ttft"/"tpot"/"done" (possibly filled by a worker
        thread for the two-hop path)."""
        stop = threading.Event()
        pend_lock = threading.Lock()
        # Handles whose prefill has not landed yet, per engine: the
        # step that produces a handle's first token ran its prefill,
        # and sleeps that engine for the simulated prefill cost.
        pending = {id(e): [] for e in engines}

        def _track(eng, handle):
            if sim_prefill_tok_s:
                with pend_lock:
                    pending[id(eng)].append(handle)
            return handle

        def _loop(e):
            key = id(e)
            while not stop.is_set():
                worked = e.step()
                cost = sim_decode_s
                if sim_prefill_tok_s:
                    with pend_lock:
                        lst = pending[key]
                        landed = [h for h in lst
                                  if h.tokens or h.done()]
                        for h in landed:
                            lst.remove(h)
                            cost += (len(h.request.prompt)
                                     * sim_prefill_tok_s)
                if cost:
                    time.sleep(cost)
                elif not worked:
                    time.sleep(0.0002)

        threads = [threading.Thread(target=_loop, args=(e,), daemon=True)
                   for e in engines]
        for t in threads:
            t.start()
        recs, workers = [], []
        start = time.monotonic()
        for i, (kind, req) in enumerate(leg_trace):
            wait = start + float(leg_arrivals[i]) - time.monotonic()
            if wait > 0:
                time.sleep(wait)
            recs.append(route(kind, req, _track, workers))
        deadline = time.monotonic() + 300.0
        while time.monotonic() < deadline:
            if all(r.get("done") for r in recs):
                break
            time.sleep(0.002)
        for w in workers:
            w.join(timeout=10.0)
        stop.set()
        for t in threads:
            t.join()
        return recs

    def _watch(rec, handle):
        """Chat-path record: latency comes straight off the handle."""
        def _poll():
            handle.result(timeout=290.0)
            rec["ttft"] = handle.ttft_s
            rec["tpot"] = handle.tpot_s
            rec["done"] = True
        threading.Thread(target=_poll, daemon=True).start()
        return rec

    def _mono_route(engines):
        def route(kind, req, track, workers):
            load = {e: e.stats()["queued"] + e.stats()["active_slots"]
                    for e in engines}
            eng = p2c_pick(engines, load, pick_rng)
            return _watch({"kind": kind, "done": False},
                          track(eng, eng.submit(req)))
        return route

    def _lane(recs, kind):
        sel = [r for r in recs if r["kind"] == kind
               and r.get("ttft") is not None]
        if not sel:
            return {}
        tt = np.asarray([r["ttft"] for r in sel]) * 1000
        tp = np.asarray([r.get("tpot") or 0.0 for r in sel]) * 1000
        return {"n": len(sel),
                "ttft_p50_ms": round(float(np.percentile(tt, 50)), 2),
                "ttft_p99_ms": round(float(np.percentile(tt, 99)), 2),
                "tpot_p50_ms": round(float(np.percentile(tp, 50)), 3),
                "tpot_p99_ms": round(float(np.percentile(tp, 99)), 3)}

    # --- leg 1: chat-only reference (one engine, chat stream only) ---
    chat_idx = [i for i, (k, _) in enumerate(trace) if k == "chat"]
    chat_trace = [trace[i] for i in chat_idx]
    chat_arrivals = arrivals[chat_idx]
    ref_eng = _mk(chat_buckets)
    ref = _run_leg([ref_eng], _mono_route([ref_eng]),
                   chat_trace, chat_arrivals)

    # --- leg 2: monolithic mixed (two engines, p2c, full mix) ---
    mono = [_mk(mono_buckets), _mk(mono_buckets)]
    mixed = _run_leg(mono, _mono_route(mono), trace, arrivals)

    # --- leg 3: disagg (prefill engine + decode engine, full mix) ---
    pre_eng = _mk(chat_buckets)
    dec_eng = _mk(chat_buckets, preempt=True)
    importer = KVImporter(dec_eng)
    # Pre-warm the migration programs (export on the prefill engine,
    # adopt on the decode engine) so first-use compiles don't stall
    # the decode loop mid-trace.
    warm = Request(prompt=list(range(1, chat_buckets[0] + 1)),
                   max_tokens=2, slo="batch", prefill_only=True)
    hw = pre_eng.submit(warm)
    pre_eng.drain()
    if hw.kv_state is not None:
        importer.adopt(dataclasses.replace(warm, prefill_only=False),
                       hw.kv_state)
        dec_eng.drain()

    def _disagg_route(kind, req, track, workers):
        rec = {"kind": kind, "done": False}
        if kind == "chat":
            return _watch(rec, track(dec_eng, dec_eng.submit(req)))

        def _two_hop():
            # Prefill hop: chunked admission keeps the prefill engine's
            # own lane fair; the exported checkpoint carries the first
            # token (lane TTFT is prefill-side by construction).
            pre_req = dataclasses.replace(
                req, prefill_only=True,
                chunked_prefill=len(req.prompt) > chat_buckets[-1])
            h_pre = track(pre_eng, pre_eng.submit(pre_req))
            h_pre.result(timeout=290.0)
            rec["ttft"] = h_pre.ttft_s
            if h_pre.kv_state is None:      # finished at first token
                rec["tpot"] = 0.0
                rec["done"] = True
                return
            h_dec = importer.adopt(req, h_pre.kv_state)
            h_dec.result(timeout=290.0)
            rec["tpot"] = h_dec.tpot_s
            rec["done"] = True

        w = threading.Thread(target=_two_hop, daemon=True)
        w.start()
        workers.append(w)
        return rec

    disagg = _run_leg([pre_eng, dec_eng], _disagg_route, trace, arrivals)
    dec_stats = dec_eng.stats()

    ref_chat = _lane(ref, "chat")
    mono_chat = _lane(mixed, "chat")
    dis_chat = _lane(disagg, "chat")
    base_p99 = ref_chat.get("ttft_p99_ms") or None
    ratio = (round(dis_chat["ttft_p99_ms"] / base_p99, 3)
             if base_p99 and dis_chat.get("ttft_p99_ms") is not None
             else None)
    detail = {
        "device": device_kind, "num_slots": slots,
        "decode_block": dblock, "kv_block_size": block_size,
        "requests": n_requests, "long_fraction": 0.1,
        "long_prompt_len": long_len, "chat_prompt_len": [c_lo, c_hi],
        "arrival_rate_req_s": rate,
        "sim_decode_ms": sim_decode_s * 1000,
        "sim_prefill_tok_ms": sim_prefill_tok_s * 1000,
        "chat_only": ref_chat,
        "mono_mixed_chat": mono_chat,
        "mono_mixed_long": _lane(mixed, "long"),
        "disagg_chat": dis_chat,
        "disagg_long": _lane(disagg, "long"),
        "mono_chat_p99_vs_chat_only": round(
            mono_chat["ttft_p99_ms"] / base_p99, 3)
        if base_p99 and mono_chat.get("ttft_p99_ms") is not None
        else None,
        "disagg_chat_p99_vs_chat_only": ratio,
        "kv_migration": dec_stats.get("migration", {}),
        "decode_preemptions": dec_stats.get("preempted", 0),
        "note": "bimodal Poisson (10% long prefills on the batch lane, "
                "90% chat on the interactive lane), same trace and "
                "two-engine budget per mixed leg; chat-lane p99 TTFT "
                "of disagg (prefill+decode pools, KV migration) vs a "
                "chat-only reference, with monolithic-mixed as the "
                "degraded comparator",
    }
    return {
        "metric": "llama_serve_disagg",
        "value": ratio,
        "unit": "chat_p99_ttft_ratio",
        "vs_baseline": None,
        "detail": detail,
    }


def _bench_serve_kv_tiering(on_tpu: bool, device_kind: str) -> dict:
    """Cluster-wide KV memory hierarchy vs per-replica caches, on a
    Zipf-popular prefix mix over 4 replicas (the multi-tenant chat
    shape: a few hot system prompts, a long cold tail). Two legs over
    the SAME trace and engine budget, every engine running tiered
    spill (undersized HBM pool -> host tier):

    - per_replica: plain p2c on probed load — a hot prefix's KV only
      helps if the pick happens to land on the replica that has it;
    - cluster: cache-aware p2c (load - weight * expected prefix-hit
      blocks scored against each engine's published stable hash-chain
      heads) plus peer pull — when another replica holds enough more of
      the prefix, its chain moves donor -> chosen host tier first
      (export_prefix/import_prefix) and admission promotes it through
      the adopt scatter instead of re-prefilling.

    Reports warm-TTFT (requests whose prefix family was seen anywhere
    in the cluster before) and prefill-FLOPs-avoided (1 - actually
    prefilled / total prompt tokens, via RequestHandle.prefilled_tokens)
    per leg, tier spill/promote traffic, and the PromoteCostModel
    crossover (smallest chain length where re-adopt beats recompute).
    The acceptance bar: the cluster leg strictly improves BOTH warm
    TTFT and FLOPs-avoided.
    """
    import random as _random
    import threading

    import numpy as np

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.serve.llm.kv_cache import stable_hash_prefix
    from ray_tpu.serve.llm.router import p2c_pick

    if on_tpu:
        import jax.numpy as jnp

        config = LlamaConfig(
            vocab_size=32000, dim=4096, n_layers=4, n_heads=32,
            n_kv_heads=8, hidden_dim=11008, max_seq_len=1024,
            param_dtype=jnp.bfloat16)
        slots, buckets, max_len = 8, (128, 256), 512
        block_size, pool_blocks = 16, 96
        n_requests, n_families, fam_len = 64, 8, 96
        t_lo, t_hi, o_lo, o_hi = 16, 96, 8, 32
        gap_s, pull_min = 0.020, 4
        # TPU: the GlobalConfig defaults (2ms fixed adopt, 0.05ms/token
        # prefill) already describe the hardware.
        cost = {}
    else:
        config = LlamaConfig.tiny()
        slots, buckets, max_len = 4, (4, 8, 16), 32
        block_size, pool_blocks = 4, 20
        # 3-block families over a 4-token suffix bucket: a full warm
        # hit prefills 4 tokens where a cold admission prefills 16.
        n_requests, n_families, fam_len = 64, 8, 12
        t_lo, t_hi, o_lo, o_hi = 2, 4, 2, 6
        # Paced under saturation: at this arrival rate TTFT measures
        # prefill work, not queue depth — the thing tiering changes.
        gap_s, pull_min = 0.030, 1
        # CPU: prefill is ~ms/token, so re-adopt wins from chain length
        # 1 — without this the TPU-tuned defaults never promote and the
        # tier path would go unexercised on the CPU tier.
        cost = {"kv_adopt_cost_fixed_ms": 1.0,
                "kv_adopt_cost_per_block_ms": 0.1,
                "kv_prefill_cost_per_token_ms": 1.0}
    # Affinity as a TIE-BREAK, not an override: a cached block must not
    # outweigh a whole queued request, or the hot family's replica
    # saturates and queue wait eats the prefill savings.
    cache_weight = 0.25

    import jax

    params = init_params(config, jax.random.key(1))
    rng = np.random.RandomState(23)
    families = [rng.randint(1, config.vocab_size, fam_len).tolist()
                for _ in range(n_families)]
    # Zipf popularity over the families; 25% of traffic is unique cold
    # prompts — they churn the undersized pool so eviction->spill runs.
    reqs = []                       # (family_idx | None, Request)
    fam_draw = np.minimum(rng.zipf(1.3, n_requests) - 1,
                          n_families - 1)
    for i in range(n_requests):
        tail = rng.randint(1, config.vocab_size,
                           rng.randint(t_lo, t_hi + 1)).tolist()
        if rng.rand() < 0.25:
            fam, prompt = None, rng.randint(
                1, config.vocab_size, fam_len + len(tail)).tolist()
        else:
            fam = int(fam_draw[i])
            prompt = families[fam] + tail
        reqs.append((fam, Request(
            prompt=prompt[:buckets[-1]],
            max_tokens=int(rng.randint(o_lo, o_hi + 1)))))
    gaps = rng.exponential(gap_s, n_requests)
    prompt_tokens = sum(len(r.prompt) for _, r in reqs)

    def _mk_engines(n=4):
        engines = []
        for _ in range(n):
            e = LLMEngine(params, config, EngineConfig(
                num_slots=slots, max_seq_len=max_len,
                prefill_buckets=buckets, kv_layout="paged",
                kv_block_size=block_size, num_kv_blocks=pool_blocks,
                kv_spill=True, **cost))
            e.warmup()
            engines.append(e)
        return engines

    def _expected(eng, prompt):
        heads = {h for h, _d in eng.prefix_index_heads()}
        n = 0
        for j in range(1, (len(prompt) - 1) // block_size + 1):
            if stable_hash_prefix(prompt[:j * block_size]) not in heads:
                break
            n += 1
        return n

    def _drive(engines, cache_aware):
        stop = threading.Event()

        def _loop(e):
            while not stop.is_set():
                if not e.step():
                    time.sleep(0.0002)

        threads = [threading.Thread(target=_loop, args=(e,),
                                    daemon=True) for e in engines]
        for t in threads:
            t.start()
        pick_rng = _random.Random(7)
        handles, warm, pulls = [], [], 0
        seen = set()                # families seen anywhere in cluster
        for i, (fam, req) in enumerate(reqs):
            time.sleep(float(gaps[i]))
            load = {e: e.stats()["queued"] + e.stats()["active_slots"]
                    for e in engines}
            if cache_aware:
                exp = {e: _expected(e, req.prompt) for e in engines}
                adj = {e: load[e] - cache_weight * exp[e]
                       for e in engines}
                eng = p2c_pick(engines, adj, pick_rng)
                best = max(engines, key=lambda e: exp[e])
                if (best is not eng
                        and exp[best] - exp[eng] >= pull_min):
                    try:
                        chain = best.call_on_scheduler(
                            lambda b=best, p=req.prompt:
                            b.export_prefix(p), timeout_s=30.0)
                        if chain and eng.import_prefix(chain):
                            pulls += 1
                    except Exception:
                        pass        # pull is best-effort, like the router
            else:
                eng = p2c_pick(engines, load, pick_rng)
            h = eng.submit(req)
            handles.append(h)
            warm.append(fam is not None and fam in seen)
            if fam is not None:
                seen.add(fam)
        while any(h.finished_at is None for h in handles):
            time.sleep(0.0005)
        stop.set()
        for t in threads:
            t.join()
        prefilled = sum(h.prefilled_tokens for h in handles)
        warm_ttft = [h.ttft_s * 1000 for h, w in zip(handles, warm) if w]
        tiers = [e.stats().get("kv_tiers", {}) for e in engines]
        return {
            "warm_requests": len(warm_ttft),
            "warm_ttft_p50_ms": round(
                float(np.percentile(warm_ttft, 50)), 3),
            "warm_ttft_p99_ms": round(
                float(np.percentile(warm_ttft, 99)), 3),
            "prefilled_tokens": prefilled,
            "flops_avoided_frac": round(
                1.0 - prefilled / prompt_tokens, 4),
            "peer_pulls": pulls,
            "spilled_blocks": sum(
                t.get("host", {}).get("spills", 0) for t in tiers),
            "promoted_blocks": sum(
                t.get("promoted_blocks", 0) for t in tiers),
            "promote_skips": sum(
                t.get("promote_skips", 0) for t in tiers),
        }

    local = _drive(_mk_engines(), cache_aware=False)
    cluster_engines = _mk_engines()
    cluster = _drive(cluster_engines, cache_aware=True)

    # Cost-model crossover: smallest chain length (blocks) where
    # re-adopting spilled KV beats recomputing its prefill.
    cm = cluster_engines[0]._cost_model
    crossover = next(
        (n for n in range(1, max_len // block_size + 1)
         if cm.should_promote(n, block_size)), None)

    ratio = (cluster["warm_ttft_p50_ms"] / local["warm_ttft_p50_ms"]
             if local["warm_ttft_p50_ms"] else None)
    detail = {
        "device": device_kind, "replicas": 4, "num_slots": slots,
        "prefill_buckets": list(buckets), "kv_block_size": block_size,
        "pool_blocks": pool_blocks, "requests": n_requests,
        "prefix_families": n_families, "family_len": fam_len,
        "zipf_a": 1.3, "cold_fraction": 0.25,
        "peer_pull_min_blocks": pull_min,
        "per_replica": local,
        "cluster": cluster,
        "cluster_vs_local_warm_ttft_p50": round(ratio, 3)
        if ratio is not None else None,
        "flops_avoided_delta": round(
            cluster["flops_avoided_frac"]
            - local["flops_avoided_frac"], 4),
        "promote_crossover_blocks": crossover,
        "note": "4 tiered paged replicas (undersized pool, host-tier "
                "spill) on a Zipf shared-prefix mix; cache-aware p2c "
                "over published stable hash-chain heads + peer KV pull "
                "vs plain p2c, same trace. Warm = prefix family seen "
                "anywhere in the cluster before",
    }
    return {
        "metric": "llama_serve_kv_tiering",
        "value": round(ratio, 3) if ratio is not None else None,
        "unit": "warm_ttft_p50_ratio",
        "vs_baseline": None,
        "detail": detail,
    }


def main() -> None:
    """One process, one chip: everything below runs in this process, which
    holds the chip from its first JAX call. Nothing is caught: a phase that
    fails ends the run with its traceback and a non-zero exit code."""
    import jax
    import numpy as np
    import optax
    from jax import lax

    from ray_tpu.models.llama import flops_per_token, init_params, loss_fn
    from ray_tpu.observability import chipspec
    from ray_tpu.parallel import (
        create_train_state, llama_param_shardings, make_mesh, shard_params,
    )
    from ray_tpu.parallel.train_step import TrainState

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures the chip; JAX's platform is "
            f"{device.platform!r}. A CPU run is never printed under a "
            "device metric's name: refusing to run.")
    device_kind = device.device_kind
    peak = chipspec.lookup(device_kind).peak_flops  # unknown kind: raises
    on_tpu = True
    config, batch, seq, timed_rounds = _bench_config(on_tpu)
    # Steps per jit call: the host's per-dispatch work is paid once per
    # scan instead of once per step. What that buys on a local chip has
    # not been measured.
    steps_per_call = 4

    mesh = make_mesh({"data": -1})
    optimizer = optax.adamw(1e-4)
    state = create_train_state(
        shard_params(init_params(config, jax.random.key(0)),
                     llama_param_shardings(config, mesh)), optimizer)

    def one_step(st, toks):
        loss, grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": toks}, config))(st.params)
        updates, new_opt = optimizer.update(grads, st.opt_state, st.params)
        return TrainState(optax.apply_updates(st.params, updates), new_opt,
                          st.step + 1), loss

    multi_step = jax.jit(
        lambda st, toks_k: lax.scan(one_step, st, toks_k),
        donate_argnums=(0,))

    rng = np.random.RandomState(0)
    toks = jax.numpy.asarray(
        rng.randint(0, config.vocab_size,
                    (steps_per_call, batch, seq)).astype("int32"))

    # Warmup: compile + first-call allocation anomaly. The scalar fetch
    # synchronizes with the device.
    for _ in range(2):
        state, losses = multi_step(state, toks)
        last_loss = float(losses[-1])

    times = []
    for _ in range(timed_rounds):
        t0 = time.perf_counter()
        state, losses = multi_step(state, toks)
        last_loss = float(losses[-1])
        times.append((time.perf_counter() - t0) / steps_per_call)
    step_s = min(times)

    tokens_per_step = batch * (seq - 1)
    tokens_per_sec = tokens_per_step / step_s
    fpt = flops_per_token(config, seq)
    mfu = tokens_per_sec * fpt / peak

    # Secondary legs, printed FIRST so a reader of the LAST line still
    # picks the primary training metric. Free the training working set
    # first — params + Adam moments + token buffers would otherwise sit
    # in HBM under the decode leg's second parameter set and KV cache.
    # Every leg here runs in this process on the chip it already holds.
    # Legs that need other processes (a local cluster, a multi-device
    # collective run) are not launched from a process that owns the
    # chip; the benchmark that ROADMAP.md asks for rebuilds them as cells.
    del state, toks, losses
    print(json.dumps(_bench_decode(config, on_tpu, device_kind)))
    print(json.dumps(_bench_serve(config, on_tpu, device_kind)))
    print(json.dumps(_bench_serve_paged(on_tpu, device_kind)))
    print(json.dumps(_bench_serve_disagg(on_tpu, device_kind)))
    print(json.dumps(_bench_serve_kv_tiering(on_tpu, device_kind)))

    vs_baseline = mfu / REFERENCE_MFU
    a100_tokens = REFERENCE_MFU * A100_PEAK_FLOPS / fpt
    result = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 2),
        "unit": "tokens/s",
        "vs_baseline": round(vs_baseline, 4),
        "detail": {
            "device": device_kind,
            "model_params": config.num_params(),
            "batch": batch, "seq": seq,
            "loss": round(last_loss, 4),
            "mfu": round(mfu, 4),
            "step_ms": round(step_s * 1000, 2),
            "vs_a100_tokens": round(tokens_per_sec / a100_tokens, 4),
            "baseline": "reference torch-DDP/FSDP at 40% MFU "
                        "(vs_baseline = mfu/0.40; hardware-normalized)",
        },
    }
    print(json.dumps(result))


if __name__ == "__main__":
    main()
