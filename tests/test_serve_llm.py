"""Continuous-batching LLM engine (serve/llm): greedy parity with the
static `generate` path, slot recycling under staggered arrivals, the
compile-count guard, and the Serve deployment integration.

Compile budget: the tiny model still traces a full scan per program, so
the module caches the params, the per-(prompt, n) static references,
and ONE default-geometry engine shared by every test that doesn't need
special slots/buckets (each extra engine instance re-jits its tick +
touched insert buckets).
"""

import time

import numpy as np
import pytest

_CACHE = {}


def _model():
    if "model" not in _CACHE:
        import jax

        from ray_tpu.models.llama import LlamaConfig, init_params

        config = LlamaConfig.tiny()
        _CACHE["model"] = (config, init_params(config, jax.random.key(0)))
    return _CACHE["model"]


def _engine(slots=4, buckets=(8, 16), S=64, **kw):
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    config, params = _model()
    kw.setdefault("kv_block_size", 4)       # divides the tiny buckets
    return LLMEngine(params, config, EngineConfig(
        num_slots=slots, max_seq_len=S, prefill_buckets=buckets, **kw))


def _shared_engine():
    """Single-step engine reused across tests (drained between); 2
    slots so queueing paths get constant exercise."""
    if "engine" not in _CACHE:
        _CACHE["engine"] = _engine(slots=2)
    return _CACHE["engine"]


def _shared_engine_multi():
    """Multi-step (decode_block=2) engine shared by the multi-step
    parity and recycling tests."""
    if "engine_multi" not in _CACHE:
        _CACHE["engine_multi"] = _engine(slots=3, decode_block=2)
    return _CACHE["engine_multi"]


def _specs(seed, pairs):
    config, _ = _model()
    rng = np.random.RandomState(seed)
    return [(rng.randint(0, config.vocab_size, p).tolist(), n)
            for p, n in pairs]


# One spec list for every parity test: reference shapes are cached, so
# reuse keeps the number of traced `generate` programs minimal.
_PARITY_PAIRS = [(3, 6), (8, 2), (11, 8), (16, 4), (5, 1), (7, 7)]


def _reference(prompt, n):
    """Per-request static path: the parity oracle (cached per shape —
    every distinct (len(prompt), n) traces a whole generate scan)."""
    key = (tuple(prompt), n)
    refs = _CACHE.setdefault("refs", {})
    if key not in refs:
        import jax.numpy as jnp

        from ray_tpu.models.llama import generate

        config, params = _model()
        out = generate(params, jnp.asarray([prompt], jnp.int32), config,
                       max_new_tokens=n)
        refs[key] = np.asarray(out)[0].tolist()
    return list(refs[key])


@pytest.mark.parametrize("decode_block", [1, 2])
def test_greedy_parity_mixed_lengths(decode_block):
    """Engine output is token-identical to per-request `generate` for
    mixed prompt/output lengths submitted together — including with
    multi-step decode blocks, where post-stop speculative tokens are
    computed on device but truncated host-side."""
    from ray_tpu.serve.llm.engine import Request

    engine = (_shared_engine() if decode_block == 1
              else _shared_engine_multi())
    specs = _specs(0, _PARITY_PAIRS)
    handles = [engine.submit(Request(prompt=p, max_tokens=n))
               for p, n in specs]
    engine.drain()
    for (p, n), h in zip(specs, handles):
        assert h.finish_reason == "length"
        assert h.tokens == _reference(p, n), (p, n)


def test_greedy_parity_any_arrival_order():
    """Same requests, staggered arrival: tokens are identical no matter
    when a request joins the running batch (slot state is isolated;
    the 2-slot shared engine forces queueing too)."""
    from ray_tpu.serve.llm.engine import Request

    specs = _specs(0, _PARITY_PAIRS)[:5]
    expected = [_reference(p, n) for p, n in specs]

    engine = _shared_engine()
    handles = []
    for i, (p, n) in enumerate(specs):
        handles.append(engine.submit(Request(prompt=p, max_tokens=n)))
        # Interleave arrivals with decode progress.
        for _ in range(i + 1):
            engine.step()
    engine.drain()
    for h, exp in zip(handles, expected):
        assert h.tokens == exp


def test_slot_recycling_under_staggered_arrivals():
    """More requests than slots: slots are evicted on completion and
    recycled for queued requests; everything completes."""
    from ray_tpu.serve.llm.engine import Request

    config, _ = _model()
    engine = _shared_engine_multi()        # 3 slots, decode_block=2
    base = engine.stats()
    rng = np.random.RandomState(2)
    handles = []
    for i in range(10):
        p = rng.randint(0, config.vocab_size, rng.randint(2, 16)).tolist()
        handles.append(engine.submit(
            Request(prompt=p, max_tokens=int(rng.randint(1, 6)))))
    engine.drain()
    st = engine.stats()
    assert st["completed"] == base["completed"] + 10
    assert st["active_slots"] == 0 and st["queued"] == 0
    assert st["slot_reuses"] >= base["slot_reuses"] + 7   # 10 reqs / 3 slots
    for h in handles:
        assert h.done() and len(h.tokens) >= 1


def test_compile_count_guard():
    """A mixed workload traces ONE tick and at most one insert a
    prefill bucket — no per-request or per-shape recompiles. Beyond
    those n_prefill_buckets + 1 the engine has only the export gather
    (one trace a row length of `export_rows`, spent when something is
    evicted, checkpointed or pulled) and the one adopt scatter; with a
    pool that holds every prompt this workload spends neither."""
    from ray_tpu.serve.llm.engine import Request

    config, _ = _model()
    engine = _engine(slots=4, buckets=(8, 16))
    assert engine.config.kv_layout == "paged" and engine.config.kv_spill
    rng = np.random.RandomState(3)
    for i in range(12):                     # both buckets, varied lengths
        p = rng.randint(0, config.vocab_size, rng.randint(1, 16)).tolist()
        engine.submit(Request(prompt=p, max_tokens=int(rng.randint(1, 7)),
                              temperature=float(i % 2) * 0.7))
        engine.step()
    engine.drain()
    traces = engine.stats()["traces"]
    assert traces["tick"] == 1, traces
    assert traces["insert"] <= len(engine.config.prefill_buckets), traces
    assert traces["export"] == traces["adopt"] == 0, traces
    assert engine.trace_count == sum(traces.values())
    assert engine.trace_count <= len(engine.config.prefill_buckets) + 1, \
        engine.stats()


def test_eos_and_stop_tokens():
    """EOS halts and is emitted; stop tokens halt without being
    emitted; max_tokens bounds generation."""
    from ray_tpu.serve.llm.engine import Request

    prompt = list(range(1, 9))
    ref = _reference(prompt, 8)

    # Pick the reference's 3rd token as eos/stop so it actually fires.
    t3 = ref[2]
    eng = _engine(eos_id=t3)
    h = eng.submit(Request(prompt=prompt, max_tokens=8))
    eng.drain()
    assert h.finish_reason == "eos" and h.tokens == ref[:3]

    eng2 = _shared_engine()                # stop is per-request
    h2 = eng2.submit(Request(prompt=prompt, max_tokens=8, stop=(t3,)))
    eng2.drain()
    assert h2.finish_reason == "stop" and h2.tokens == ref[:2]


def test_streaming_callback_and_latency_fields():
    from ray_tpu.serve.llm.engine import Request

    engine = _shared_engine()
    seen = []
    h = engine.submit(Request(
        prompt=[1, 2, 3], max_tokens=5,
        on_token=lambda rid, tok: seen.append((rid, tok))))
    engine.drain()
    assert [t for _, t in seen] == h.tokens and len(h.tokens) == 5
    assert all(rid == h.request_id for rid, _ in seen)
    assert h.ttft_s is not None and h.ttft_s >= 0
    assert h.tpot_s is not None and h.tpot_s >= 0


def test_sampled_decode_respects_temperature():
    """Temperature > 0 goes through the categorical path and still
    terminates correctly (no parity claim)."""
    from ray_tpu.serve.llm.engine import Request

    config, _ = _model()
    engine = _shared_engine()
    h = engine.submit(Request(prompt=[5, 6, 7], max_tokens=6,
                              temperature=0.9))
    engine.drain()
    assert len(h.tokens) == 6
    assert all(0 <= t < config.vocab_size for t in h.tokens)


def test_submit_validation():
    from ray_tpu.serve.llm.engine import Request

    from ray_tpu.serve.llm.engine import EngineConfig

    engine = _engine(buckets=(8,))         # never stepped: no compiles
    with pytest.raises(ValueError):
        engine.submit(Request(prompt=[], max_tokens=1))
    with pytest.raises(ValueError):
        engine.submit(Request(prompt=[1] * 9, max_tokens=1))  # > bucket
    with pytest.raises(ValueError):
        engine.submit(Request(prompt=[1], max_tokens=0))
    # one layout: the default, and the only value the field takes
    assert EngineConfig().kv_layout == "paged" and EngineConfig().kv_spill
    with pytest.raises(ValueError, match="removed at PR 28"):
        EngineConfig(kv_layout="dense")


# --------------------------------------------------------------- paged KV


def _shared_paged():
    """Engine shared by the block-table parity/prefix tests (every
    extra engine instance re-jits its tick + insert buckets)."""
    if "engine_paged" not in _CACHE:
        _CACHE["engine_paged"] = _engine(slots=3)
    return _CACHE["engine_paged"]


def test_paged_greedy_parity_and_compile_count():
    """Paged attention (block tables + pool gather) is token-exact
    against the static reference for mixed lengths, inside the
    same compile budget: n_prefill_buckets + 1 programs."""
    from ray_tpu.serve.llm.engine import Request

    engine = _shared_paged()
    specs = _specs(0, _PARITY_PAIRS)
    handles = [engine.submit(Request(prompt=p, max_tokens=n))
               for p, n in specs]
    engine.drain()
    for (p, n), h in zip(specs, handles):
        assert h.finish_reason == "length"
        assert h.tokens == _reference(p, n), (p, n)
    # nothing evicted, checkpointed or pulled: none of the export's
    # len(export_rows) traces, nor the adopt's one, is spent
    assert engine.trace_count <= len(engine.config.prefill_buckets) + 1, \
        engine.stats()
    assert engine.stats()["traces"]["export"] == 0


def test_paged_prefix_hit_skips_prefill_and_keeps_parity():
    """A second request sharing a block-aligned prompt prefix hits the
    prefix cache — its cached blocks skip prefill — and the output is
    still token-identical to the full static path."""
    from ray_tpu.serve.llm.engine import Request

    config, _ = _model()
    engine = _shared_paged()
    rng = np.random.RandomState(7)
    sys_p = rng.randint(0, config.vocab_size, 8).tolist()
    p1 = sys_p + rng.randint(0, config.vocab_size, 4).tolist()
    p2 = sys_p + rng.randint(0, config.vocab_size, 5).tolist()
    before = engine.stats()["prefix_cache"]
    h1 = engine.submit(Request(prompt=p1, max_tokens=4))
    engine.drain()                           # p1's blocks now cached
    h2 = engine.submit(Request(prompt=p2, max_tokens=4))
    engine.drain()
    after = engine.stats()["prefix_cache"]
    assert h1.tokens == _reference(p1, 4)
    assert h2.tokens == _reference(p2, 4)
    assert after["hits"] >= before["hits"] + 1
    assert after["hit_tokens"] >= before["hit_tokens"] + len(sys_p)


def test_paged_pool_exhaustion_queues_not_crash():
    """Block demand beyond the pool: admission parks requests in the
    queue and completes them as finishing sequences free blocks; only a
    request that can NEVER fit is rejected, at submit time."""
    from ray_tpu.serve.llm.engine import Request

    config, _ = _model()
    engine = _engine(slots=4, buckets=(8,), S=32, num_kv_blocks=6,
                     prefix_cache=False)
    with pytest.raises(ValueError):          # worst case 8 blocks > 6
        engine.submit(Request(prompt=[1] * 8, max_tokens=32))
    rng = np.random.RandomState(5)
    handles = [engine.submit(Request(
        prompt=rng.randint(0, config.vocab_size, 8).tolist(),
        max_tokens=4)) for _ in range(5)]    # 3 blocks each, pool of 6
    engine.step()
    st = engine.stats()
    assert st["queued"] >= 1                 # exhaustion queued, no crash
    assert st["kv"]["used_blocks"] <= 6
    engine.drain()
    assert all(h.done() and len(h.tokens) == 4 for h in handles)
    assert engine.stats()["kv"]["used_blocks"] == 0


def test_llm_server_quantize_default_and_optout():
    """The serve config defaults to weight-only int8 decode; "bf16"
    opts out; anything else is rejected before weights load."""
    from ray_tpu.serve.llm.deployment import LLMServer

    config, _ = _model()
    econf = {"num_slots": 2, "max_seq_len": 32, "prefill_buckets": (8,),
             "kv_block_size": 4}
    srv = LLMServer(model_config=config, engine_config=econf)
    assert srv.quantize == "int8"
    assert srv.stats()["quantize"] == "int8"
    assert set(srv.load()) == {"queued", "active_slots", "free_slots",
                               "lanes", "index_id"}
    srv_bf16 = LLMServer(model_config=config, engine_config=econf,
                         quantize="bf16")
    assert srv_bf16.quantize == "bf16"
    with pytest.raises(ValueError):
        LLMServer(model_config=config, engine_config=econf,
                  quantize="fp4")


# ----------------------------------------------------------------- router


def test_p2c_pick_prefers_light_replicas():
    import random as _random

    from ray_tpu.serve.llm.router import p2c_pick

    rng = _random.Random(0)
    load = {"light": 0.0, "heavy": 5.0}
    picks = [p2c_pick(["light", "heavy"], load, rng) for _ in range(40)]
    assert picks.count("light") == 40        # 2 replicas: always compared


def test_router_stalled_replica_sheds_traffic():
    """A replica whose load probe fails scores float('inf'), so p2c
    assignment shifts all traffic to the live replica."""
    import random as _random
    import threading

    from ray_tpu.serve.llm.router import LLMRouter, p2c_pick

    r = LLMRouter.__new__(LLMRouter)         # policy only: no controller
    r._lock = threading.Lock()
    r._replicas = ["live", "stalled"]
    r._inflight = {"live": 3, "stalled": 0}
    r._depth = {"live": 2.0, "stalled": float("inf")}
    replicas, load = r._score()
    assert load["stalled"] == float("inf")
    rng = _random.Random(1)
    assert all(p2c_pick(replicas, load, rng) == "live"
               for _ in range(25))


def test_routed_llm_two_replicas_smoke(ray_start_regular):
    """Router over two LLM replicas: results match the static
    reference and traffic spreads across both replicas."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_routed_llm_app

    config, _ = _model()
    try:
        handle = serve.run(build_routed_llm_app(
            model_config=config,
            engine_config={"num_slots": 2, "max_seq_len": 64,
                           "prefill_buckets": (8, 16),
                           "kv_block_size": 4},
            num_replicas=2, num_tpus=0, quantize="bf16",
            max_ongoing_requests=8,
            probe_interval_s=0.1), name="llm-routed")

        # `serve.run` returns with both replica actors created, not
        # constructed. One still importing JAX and building its engine
        # answers no load probe, scores inf, and the router sends every
        # request to the other: wait until both have answered probes
        # for longer than a whole probe cycle (two 0.5 s timeouts and
        # the interval).
        def _both_answer():
            st = handle.stats.remote().result(timeout=60)
            return st["replicas"] == 2 and len(st["depth"]) == 2 and all(
                d != float("inf") for d in st["depth"].values())

        deadline, since = time.monotonic() + 300, None
        while since is None or time.monotonic() - since < 1.5:
            assert time.monotonic() < deadline, \
                handle.stats.remote().result(timeout=60)
            if not _both_answer():
                since = None
            elif since is None:
                since = time.monotonic()
            time.sleep(0.2)
        rng = np.random.RandomState(4)       # same trace as the plain
        prompts = [rng.randint(0, config.vocab_size,  # smoke: refs cached
                               rng.randint(2, 16)).tolist()
                   for _ in range(6)]
        resps = [handle.remote({"prompt": p, "max_tokens": 4})
                 for p in prompts]
        for p, r in zip(prompts, resps):
            out = r.result(timeout=300)
            assert out["tokens"] == _reference(p, 4)
        st = handle.stats.remote().result(timeout=60)
        assert st["replicas"] == 2
        assert sum(st["routed"].values()) == len(prompts)
        assert len(st["routed"]) == 2        # both replicas took traffic
    finally:
        serve.shutdown()


def test_serve_llm_deployment_smoke(ray_start_regular):
    """Fast tier-1 smoke: the engine behind a Serve deployment (tiny
    config, 4 slots, 2 buckets); concurrent handle calls return the
    same tokens as the static reference. quantize="bf16" keeps
    bit-parity with the bf16 reference (int8 is the serve default)."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_llm_app

    config, _ = _model()
    try:
        handle = serve.run(build_llm_app(
            model_config=config,
            engine_config={"num_slots": 4, "max_seq_len": 64,
                           "prefill_buckets": (8, 16),
                           "kv_block_size": 4},
            num_tpus=0, init_seed=0, quantize="bf16",
            max_ongoing_requests=8),
            name="llm")
        rng = np.random.RandomState(4)
        prompts = [rng.randint(0, config.vocab_size,
                               rng.randint(2, 16)).tolist()
                   for _ in range(6)]
        resps = [handle.remote({"prompt": p, "max_tokens": 4})
                 for p in prompts]
        for p, r in zip(prompts, resps):
            out = r.result(timeout=120)
            assert out["tokens"] == _reference(p, 4)
            assert out["num_tokens"] == 4
            assert out["finish_reason"] == "length"
    finally:
        serve.shutdown()
