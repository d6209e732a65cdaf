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


# ------------------------------------------------ one tick in flight

_LEN8 = list(range(1, 9))              # its reference's 3rd token is the eos


def _pipe_engine():
    """Two slots, `eos_id` = the third token the reference gives
    `_LEN8`; shared by the tests of the pipelined loop (drained between)."""
    if "engine_pipe" not in _CACHE:
        _CACHE["engine_pipe"] = _engine(slots=2,
                                        eos_id=_reference(_LEN8, 8)[2])
    return _CACHE["engine_pipe"]


def _len8(seed):
    return _specs(seed, [(8, 8)])[0][0]


def _loop(engine):
    return engine.stats()["loop"]


def _step_until(engine, cond, limit=200):
    for _ in range(limit):
        if cond():
            return
        engine.step()
    raise AssertionError("condition never held")


def _mixed_batch():
    """One batch on the pipelined loop whose requests end three ways,
    served once: by eos (found one tick late: the slot is computed once
    more and that row dropped), by a stop token, by length."""
    if "mixed_batch" not in _CACHE:
        from ray_tpu.serve.llm.engine import Request

        engine = _pipe_engine()
        eos = engine.config.eos_id
        refs = {"eos": _reference(_LEN8, 8), "stop": _reference(_len8(3), 8),
                "length": _reference(_len8(2), 8)}
        assert eos not in refs["stop"] + refs["length"] + refs["eos"][:2]
        stop = refs["stop"][4]
        assert stop not in refs["stop"][:4]
        before = _loop(engine)
        handles = {
            "eos": engine.submit(Request(prompt=_LEN8, max_tokens=8)),
            "stop": engine.submit(Request(prompt=_len8(3), max_tokens=8,
                                          stop=(stop,))),
            "length": engine.submit(Request(prompt=_len8(2), max_tokens=8))}
        engine.drain()
        want = {"eos": refs["eos"][:3], "stop": refs["stop"][:4],
                "length": refs["length"]}
        _CACHE["mixed_batch"] = (handles, want, before, _loop(engine))
    return _CACHE["mixed_batch"]


@pytest.mark.parametrize("ends_by", ["length", "eos", "stop"])
def test_pipelined_loop_serves_the_synchronous_tokens(ends_by):
    """The tokens of the static per-request path, token for token,
    however a request ends; the ticks of the batch overlapped."""
    handles, want, before, after = _mixed_batch()
    h = handles[ends_by]
    assert h.finish_reason == ends_by and h.tokens == want[ends_by]
    ticks = after["ticks"] - before["ticks"]
    overlapped = after["overlapped"] - before["overlapped"]
    assert 0 < overlapped < ticks
    assert after["calls"]["emit"] == after["calls"]["tick_dispatch"] \
        == after["ticks"]


@pytest.mark.parametrize("freed_by", ["eos", "cancel"])
def test_a_refilled_slot_never_gets_the_stale_row(freed_by):
    """A slot released while a tick that holds its old request is in
    flight (by an eos read one tick late, or by `cancel`) and filled
    again: the tick's row goes to no handle, the new request's tokens
    are its own."""
    from ray_tpu.serve.llm.engine import Request

    engine = _pipe_engine()
    # the other slot stays busy, so the freed one is the one refilled
    keep = engine.submit(Request(prompt=_len8(4), max_tokens=24))
    old = engine.submit(Request(prompt=_LEN8, max_tokens=8))
    new = engine.submit(Request(prompt=_len8(2), max_tokens=8))
    _step_until(engine, lambda: len(old.tokens) >= 2)
    slot = next(i for i, st in enumerate(engine._slots) if st.handle is old)
    if freed_by == "cancel":
        assert old.cancel()
    else:
        _step_until(engine, old.done)
        assert old.finish_reason == "eos"
    # the tick in flight was dispatched with the old request in the slot
    tick, = engine._flying
    assert old in tick.handles and slot in tick.live
    seen = len(old.tokens)
    engine.step()                       # frees (cancel), refills, lands it
    assert engine._slots[slot].handle is new
    assert len(old.tokens) == seen and len(new.tokens) == 1
    engine.drain()
    assert new.tokens == _reference(_len8(2), 8)
    assert keep.tokens == _reference(_len8(4), 24)
    assert old.tokens == _reference(_LEN8, 8)[:seen]


@pytest.mark.parametrize("what", ["preempt", "ctrl", "prefill_only",
                                  "adopt", "spec"])
def test_whatever_reads_a_slots_state_settles_first(what):
    """`preempt`, a `call_on_scheduler` body (`export_prefix`), a
    `prefill_only` request's export, a `submit_adopted` request's
    admission and a speculative round each read the tick in flight back
    first (`stats()["loop"]["settles"]`, by cause), and every request's
    tokens are the synchronous run's."""
    import threading

    from ray_tpu.serve.llm.engine import Request

    if what == "spec":
        if "engine_spec" not in _CACHE:
            from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

            config, params = _model()
            _CACHE["engine_spec"] = LLMEngine(
                params, config, EngineConfig(
                    num_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
                    kv_block_size=4, spec_k=3),
                draft_params=params, draft_config=config)
        engine = _CACHE["engine_spec"]
    else:
        engine = _shared_engine()
    settles = lambda: _loop(engine)["settles"].get(what, 0)   # noqa: E731
    before = settles()
    busy = engine.submit(Request(prompt=_len8(4), max_tokens=24,
                                 temperature=0.7 if what == "spec" else 0.0))
    _step_until(engine, lambda: len(busy.tokens) >= 2)
    assert len(engine._flying) == 1     # a plain tick is in flight
    others = []
    if what == "preempt":
        slot = next(i for i, st in enumerate(engine._slots)
                    if st.handle is busy)
        engine.preempt(slot)
        # the checkpoint's pending token is one the client has
        assert busy.kv_state.tokens == busy.tokens
        assert busy.kv_state.next_tok == busy.tokens[-1]
    elif what == "ctrl":
        seen = []

        def body():
            seen.append(len(engine._flying))
            return engine.export_prefix(_len8(4))

        th = threading.Thread(
            target=lambda: seen.append(engine.call_on_scheduler(body)))
        th.start()
        _step_until(engine, lambda: len(seen) == 2)
        th.join()
        assert seen[0] == 0 and len(seen[1]) == 2   # settled; 8 tokens
    elif what in ("prefill_only", "adopt"):
        pre = engine.submit(Request(prompt=_len8(2), max_tokens=8,
                                    prefill_only=True))
        _step_until(engine, pre.done)
        assert pre.finish_reason == "prefill"
        assert pre.tokens == _reference(_len8(2), 8)[:1]
        if what == "adopt":
            # (a checkpoint with no row left to decode would never tick)
            with pytest.raises(ValueError, match="already holds max_seq_len"):
                _engine(slots=1, buckets=(8,), S=8).submit_adopted(
                    Request(prompt=_len8(2), max_tokens=8), pre.kv_state)
            assert len(engine._flying) == 1
            before = settles()
            others.append((engine.submit_adopted(
                Request(prompt=_len8(2), max_tokens=8), pre.kv_state),
                _reference(_len8(2), 8)))
            engine.step()
    else:
        # a greedy request beside the sampled one: plain ticks; alone,
        # once the sampled one is cancelled: rounds, the first of which
        # finds the last plain tick in flight
        others.append((engine.submit(Request(prompt=_len8(2), max_tokens=8)),
                       _reference(_len8(2), 8)))
        _step_until(engine, lambda: len(others[0][0].tokens) >= 2)
        rounds = engine.stats()["spec"]["rounds"]
        busy.cancel()
        engine.drain()
        assert engine.stats()["spec"]["rounds"] > rounds
    assert settles() == before + 1
    engine.drain()
    if what != "spec":
        assert busy.tokens == _reference(_len8(4), 24)
    for h, ref in others:
        assert h.finish_reason == "length" and h.tokens == ref
    assert not engine._flying


@pytest.mark.parametrize("ended_by", ["stop", "eos"])
def test_a_round_refused_after_its_settle_ticks_who_is_left(ended_by):
    """With a draft model, a step whose live slots all qualify for a
    round settles first; the tick it lands can end a slot by a stop
    token or by eos, and the round can then still be refused because
    another slot stands within `spec_k` of the sequence limit.  The
    plain tick that goes out instead holds the slots that are left, not
    the released one (whose handle is gone)."""
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    (short, _), = _specs(0, _PARITY_PAIRS[:1])          # 3 tokens
    ref_short, ref_long = _reference(short, 6), _reference(_len8(3), 8)
    assert ref_short[1] not in ref_short[:1] + ref_long[:4]
    if "engine_spec_limit" not in _CACHE:
        config, params = _model()
        # 12 rows a sequence, rounds of 5: a prompt of 8 is refused a
        # round from its first tick to its last
        _CACHE["engine_spec_limit"] = LLMEngine(
            params, config, EngineConfig(
                num_slots=2, max_seq_len=12, prefill_buckets=(8,),
                kv_block_size=4, spec_k=5, eos_id=ref_short[1],
                prefix_cache=False),        # its six blocks are theirs
            draft_params=params, draft_config=config)
    engine = _CACHE["engine_spec_limit"]
    before, rounds = _loop(engine)["settles"]["spec"], \
        engine.stats()["spec"]["rounds"]
    long = engine.submit(Request(prompt=_len8(3), max_tokens=4))
    ends = engine.submit(Request(
        prompt=short, max_tokens=6,
        stop=(ref_short[1],) if ended_by == "stop" else ()))
    engine.step()                       # both admitted, a plain tick out
    tick, = engine._flying
    assert not tick.spec and {*tick.handles} == {long, ends}
    engine.step()       # settles for a round: `ends` ends; refused: `long`
    assert ends.finish_reason == ended_by and not long.done()
    tick, = engine._flying
    assert not tick.spec and tick.handles == [long]
    engine.drain()
    assert long.finish_reason == "length" and long.tokens == ref_long[:4]
    assert ends.tokens == ref_short[:1 + (ended_by == "eos")]
    assert _loop(engine)["settles"]["spec"] - before == 2
    assert engine.stats()["spec"]["rounds"] == rounds


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


# ---------------------------------------------------------------------------
# Two kinds of pool in one engine: a table by position for a model's
# full-attention leaves, a ring of blocks for its window leaves
# (models/serving.py, kv_cache.WindowRing)
# ---------------------------------------------------------------------------

def _window_engine(**kw):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.window_moe import WindowMoEConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    if "window_model" not in _CACHE:
        config = WindowMoEConfig.tiny(dtype=jnp.float32,
                                      param_dtype=jnp.float32)
        _CACHE["window_model"] = (config,
                                  init_params(config, jax.random.key(0)))
    config, params = _CACHE["window_model"]
    cfg = dict(num_slots=3, max_seq_len=96, prefill_buckets=(8, 16),
               kv_block_size=4, num_kv_blocks=60, num_window_blocks=14,
               prefix_cache=False)
    extra = {k: kw.pop(k) for k in ("draft_params", "draft_config")
             if k in kw}
    cfg.update(kw)
    return LLMEngine(params, config, EngineConfig(**cfg), **extra)


def _held(engine):
    """(blocks of the full kind, of the window kind) each slot holds."""
    return [(len(a), len(b)) for a, b in zip(
        engine._slot_blocks, engine._ring.slot_blocks)]


def test_window_ring_bounds_a_long_stream_beside_a_short_one():
    """Window 8, buckets to 16, blocks of 4: a ring of 6 blocks.  A
    3-token request and one whose 55-token prompt and 30-token answer
    wrap the ring three times, side by side: the long one holds a block
    for each 4 of its rows in the full kind and SIX in the window kind,
    the short one the same few of both; everything is given back."""
    from ray_tpu.serve.llm.engine import Request

    engine = _window_engine()
    ring = engine._ring
    assert (ring.window, ring.ring, ring.tables.shape) == (8, 6, (3, 6))
    rng = np.random.RandomState(0)
    short = engine.submit(Request(prompt=rng.randint(1, 500, 3).tolist(),
                                  max_tokens=4))
    long = engine.submit(Request(prompt=rng.randint(1, 500, 55).tolist(),
                                 max_tokens=30, chunked_prefill=True))
    engine.step()
    assert sorted(_held(engine)) == [(0, 0), (2, 2), (22, 6)]
    kv = engine.stats()["kv"]
    assert (kv["used_blocks"], kv["window"]["used_blocks"]) == (24, 8)
    assert kv["window"]["ring_blocks"] == 6
    # the ring's table: position t in entry (t // 4) % 6
    slot = _held(engine).index((22, 6))
    assert list(ring.block_ids(slot, 16, 16)) == [
        ring.tables[slot, i % 6] for i in range(4, 8)]
    # its prompt in, the long one keeps the window's blocks and the one
    # it writes: (8 - 1) // 4 + 2 = 3, handed on as it decodes
    while len(long.tokens) < 2:
        engine.step()
    assert ring.keep == 3 and _held(engine)[slot] == (22, 3)
    assert engine.stats()["kv"]["window"]["used_blocks"] <= 3 + 2
    engine.drain()
    assert short.finish_reason == long.finish_reason == "length"
    assert len(long.tokens) == 30
    kv = engine.stats()["kv"]
    assert kv["used_blocks"] == kv["window"]["used_blocks"] == 0
    assert _held(engine) == [(0, 0)] * 3


@pytest.mark.parametrize("kind", ["full", "window"])
def test_either_kind_running_dry_queues_and_never_crashes(kind):
    """Three 40-row requests into a pool one kind of which holds two:
    the third waits at the head of its lane with nothing taken, and is
    served when a slot's blocks come back."""
    from ray_tpu.serve.llm.engine import Request

    engine = _window_engine(**(
        {"num_kv_blocks": 25} if kind == "full"
        else {"num_window_blocks": 13}))
    rng = np.random.RandomState(1)
    handles = [engine.submit(Request(
        prompt=rng.randint(1, 500, 30).tolist(), max_tokens=10,
        chunked_prefill=True)) for _ in range(3)]
    for _ in range(3):                  # a first chunk a step
        engine.step()
    # the first is decoding and keeps 3 of its 6; what it gave back is
    # not enough for the third
    assert sorted(_held(engine)) == [(0, 0), (10, 3), (10, 6)]
    assert engine.stats()["queued"] == 1
    engine.drain()
    assert all(h.finish_reason == "length" and len(h.tokens) == 10
               for h in handles)
    # a request no pool of that kind could ever hold is refused at submit
    with pytest.raises(ValueError, match="KV blocks|window kind"):
        _window_engine(num_kv_blocks=5, num_window_blocks=5).submit(
            Request(prompt=[1] * 16, max_tokens=20))


def test_a_16k_stream_holds_a_ring_in_the_window_kind():
    """At the benchmark cell's geometry (64 slots x 18,432, blocks of 16,
    buckets to 2048, window 2048; tiny widths): a 16,384-token prompt
    with a 1,024-token answer takes 1,088 blocks of the full kind, its
    full length, and 256 of the window kind: 4,096 rows in each window
    layer, whatever its length; once its prompt is in, 129 of them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.window_moe import WindowMoEConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    config = WindowMoEConfig.tiny(window=2048, max_seq_len=18432,
                                  prefill_key_block=1024,
                                  dtype=jnp.float32, param_dtype=jnp.float32)
    engine = LLMEngine(
        init_params(config, jax.random.key(0)), config, EngineConfig(
            num_slots=64, max_seq_len=18432,
            prefill_buckets=(256, 512, 1024, 2048), kv_block_size=16,
            num_kv_blocks=2048, num_window_blocks=512, prefix_cache=False))
    ring = engine._ring
    assert ring.ring == 256 and ring.ring * 16 <= 4096 + 16
    handle = engine.submit(Request(prompt=[7] * 16384, max_tokens=1024,
                                   chunked_prefill=True))
    engine.step()                       # the first chunk takes every block
    assert (16384 + 1024) // 16 == 1088
    assert _held(engine)[0] == (1088, 256)
    assert engine.stats()["kv"]["window"]["used_blocks"] == 256
    while len(handle.tokens) < 2:       # the other seven chunks, a tick
        engine.step()
    # decoding, it keeps the window's blocks and the one it writes
    assert ring.keep == 129 and _held(engine)[0] == (1088, 129)
    assert engine.stats()["kv"]["window"]["used_blocks"] == 129
    handle.cancel()
    engine.step()
    assert _held(engine)[0] == (0, 0)


@pytest.mark.parametrize("n_blocks", [5, 9, 12, 13, 40])
def test_window_ring_cover_keeps_the_window_and_no_more(n_blocks):
    """`WindowRing.cover` on its own, window 16, blocks of 4, a ring of
    12, two positions a dispatch (`keep` 6): a sequence of `n_blocks`
    blocks, its prompt written through the ring as chunks write it,
    then decoded to its end.  Before every dispatch each block of the
    window and of the positions written is in the table, owned, and
    holds the rows last written at those positions; a sequence that
    took more than `keep` blocks holds `keep` from its first dispatch
    on, and everything comes back."""
    from ray_tpu.serve.llm.kv_cache import BlockAllocator, WindowRing

    bs, W, n_ring, ahead = 4, 16, 12, 2
    allocator = BlockAllocator(64, bs, block_bytes=1)
    ring = WindowRing(W, n_ring, allocator, 2, lookahead=ahead)
    assert ring.keep == 6
    assert ring.take(1, n_blocks)
    took = min(n_blocks, n_ring)
    assert allocator.stats()["used_blocks"] == took
    total = n_blocks * bs
    prompt = total - 11
    wrote = {}                          # physical block -> block by position
    for b in range(-(-prompt // bs)):
        wrote[int(ring.tables[1, b % n_ring])] = b
    for first in range(prompt, total, ahead):
        last = min(first + ahead - 1, total - 1)
        ring.cover(1, first, last)
        held = ring.slot_blocks[1]
        assert len(held) == (took if took <= ring.keep else ring.keep)
        assert len(set(held)) == len(held)
        assert allocator.stats()["used_blocks"] == len(held)
        for pos in range(first, last + 1):
            wrote[int(ring.tables[1, (pos // bs) % n_ring])] = pos // bs
        for b in range(max(first - W + 1, 0) // bs, last // bs + 1):
            block = int(ring.tables[1, b % n_ring])
            assert block in held and wrote[block] == b, (first, b)
    ring.release(1)
    assert allocator.stats()["used_blocks"] == 0


@pytest.mark.parametrize("decode_block", [1, 2])
def test_window_ring_is_covered_for_the_rows_dispatched(decode_block):
    """`WindowRing.cover` under the engine's one-deep pipeline: the
    first position a dispatch writes is counted from the rows DISPATCHED
    (`_rows`), which with a tick in flight is `decode_block` past what
    the handle's emitted tokens say; every covering call says exactly
    the positions its tick writes."""
    from ray_tpu.serve.llm.engine import Request

    engine = _window_engine(decode_block=decode_block)
    ring, calls = engine._ring, []
    cover = ring.cover

    def recording(slot, first, last):
        h = engine._slots[slot].handle
        calls.append((first, last, len(h.request.prompt) + len(h.tokens),
                      sum(slot in t.live for t in engine._flying)))
        return cover(slot, first, last)

    ring.cover = recording
    h = engine.submit(Request(prompt=[5] * 21, max_tokens=33,
                              chunked_prefill=True))
    engine.drain()
    assert len(h.tokens) == 33 and len(calls) == -(-32 // decode_block)
    for i, (first, last, emitted, in_flight) in enumerate(calls):
        assert first == 21 + i * decode_block   # the pending token's row
        assert last == first + decode_block - 1
        assert first == emitted - 1 + in_flight * decode_block
    assert [c[3] for c in calls] == [0] + [1] * (len(calls) - 1)


@pytest.mark.parametrize("what", [
    "prefix_cache", "kv_spill", "export_prefix", "submit_adopted",
    "prefill_only", "preempt", "draft_model"])
def test_window_kind_refuses_by_name_what_moves_rows(what):
    """Whatever moves rows without telling a ring from a table is
    refused, and the refusal names the model."""
    from ray_tpu.serve.llm.engine import Request
    from ray_tpu.serve.llm.kv_cache import KVState

    with pytest.raises(ValueError, match="models/window_moe.py"):
        if what == "prefix_cache":
            _window_engine(prefix_cache=True)
        elif what == "kv_spill":
            _window_engine(prefix_cache=True, kv_spill=True)
        elif what == "draft_model":
            config, params = _CACHE.get("window_model") or (
                _window_engine() and _CACHE["window_model"])
            _window_engine(draft_params=params, draft_config=config)
        else:
            engine = _window_engine()
            if what == "export_prefix":
                engine.export_prefix([1] * 8)
            elif what == "prefill_only":
                engine.submit(Request(prompt=[1] * 5, max_tokens=2,
                                      prefill_only=True))
            elif what == "preempt":
                engine.submit(Request(prompt=[1] * 5, max_tokens=4))
                engine.step()
                engine.preempt(0)
            else:
                engine.submit_adopted(
                    Request(prompt=[1, 2], max_tokens=4),
                    KVState(prompt=[1, 2], tokens=[3], next_tok=3, pos=2,
                            temperature=0.0, block_size=4, blocks={}))


def test_dispatch_and_admission_spans_say_both_kinds():
    """`llm_engine.tick_dispatch` carries `rows=` and `window_rows=`,
    and the bytes the live slots hold are summed beside their rows; the
    span that holds the allocation says `blocks_full=` and
    `blocks_window=`; a model with one kind says `rows=` alone."""
    from ray_tpu.serve.llm.engine import Request

    engine = _window_engine()
    engine.submit(Request(prompt=[3] * 30, max_tokens=4,
                          chunked_prefill=True))
    engine.step(), engine.step()
    before = engine.stats()["kv"]["live_bytes"]
    said = engine._live_rows([0])
    assert said == {"rows": 32, "window_rows": 8}
    kv = engine.stats()["kv"]
    assert kv["live_bytes"] - before == 9 * kv["block_bytes"] \
        + 3 * kv["window"]["block_bytes"]       # decoding: `keep` of 6

    class Span:
        def set_metadata(self, **kw):
            self.said = kw

    span = Span()
    assert engine._take_blocks(1, 9, span) is not None
    assert span.said == {"blocks_full": 9, "blocks_window": 6}
    assert set(_shared_engine()._live_rows([])) == {"rows"}
    assert "live_bytes" not in _shared_engine().stats()["kv"]


# ---------------------------------------------------------------------------
# The engine's device half is ONE object (serve/llm/programs.py): the
# scheduler holds no array of the device's and donates nothing itself
# ---------------------------------------------------------------------------

def _tiny_of(form):
    """(model config, fresh parameters, engine options) of a tiny model
    of each form the engine's programs take."""
    import jax
    import jax.numpy as jnp

    f32 = dict(dtype=jnp.float32, param_dtype=jnp.float32)
    if form == "token":
        from ray_tpu.models.llama import LlamaConfig as Config, init_params

        config, options = Config.tiny(), {}
    elif form == "slot state":
        from ray_tpu.models.conv_moe import ConvMoEConfig, init_params

        config, options = ConvMoEConfig.tiny(**f32), dict(prefix_cache=False)
    elif form == "window kind":
        from ray_tpu.models.window_moe import WindowMoEConfig, init_params

        config, options = WindowMoEConfig.tiny(**f32), dict(
            prefix_cache=False, num_window_blocks=14)
    else:
        from ray_tpu.models.blockdiff_moe import (BlockDiffMoEConfig,
                                                  init_params)

        config, options = BlockDiffMoEConfig(
            vocab_size=512, dim=64, n_layers=2, n_heads=4, n_kv_heads=2,
            head_dim=16, expert_hidden_dim=32, n_experts=8, top_k=2,
            max_seq_len=128, mask_token_id=511, prefill_key_block=16,
            **f32), dict(prefix_cache=False)
    return config, init_params(config, jax.random.key(0)), options


def _device_values_of(engine):
    """The attributes of `engine`, `params` and `_draft` apart, that are
    or hold a `jax.Array`."""
    import collections

    import jax

    def holds(v):
        if isinstance(v, (collections.deque, set, frozenset)):
            v = list(v)
        return any(isinstance(x, jax.Array) for x in jax.tree.leaves(v))

    return sorted(name for name, v in vars(engine).items()
                  if name not in ("params", "_draft") and holds(v))


@pytest.mark.parametrize("form", ["token", "slot state", "window kind",
                                  "block"])
def test_scheduler_holds_no_device_value_and_donates_nothing(form):
    """Whatever the form of the model's programs: after `LLMEngine(...)`
    and after a request served, no attribute of the engine but `params`
    and `_draft` is or holds a `jax.Array` (they are the `Programs`
    object's, serve/llm/programs.py), `engine.py` donates nothing, and
    the weights are the engine's `params` alone: `engine.params = None`
    and the caller's reference gone leave nothing that keeps them (the
    benchmark's driver frees its control's weights so)."""
    import gc
    import inspect
    import weakref

    import jax

    from ray_tpu.serve.llm import engine as E
    from ray_tpu.serve.llm import programs as P

    config, params, options = _tiny_of(form)
    engine = E.LLMEngine(params, config, E.EngineConfig(
        num_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
        kv_block_size=4, **options))
    assert isinstance(engine._programs, P.BlockPrograms) == (form == "block")
    assert type(engine._programs) in (P.Programs, P.BlockPrograms)
    assert _device_values_of(engine) == []
    handle = engine.submit(E.Request(prompt=[3, 1, 4, 1, 5, 9, 2, 6, 5],
                                     max_tokens=6))
    engine.drain()
    assert handle.finish_reason == "length" and len(handle.tokens) == 6
    assert _device_values_of(engine) == []
    source = inspect.getsource(E)
    assert "donate_argnums" not in source and "tracked_jit(" not in source
    weights = [weakref.ref(x) for x in jax.tree.leaves(params)]
    engine.params = None
    del params
    gc.collect()
    assert weights and all(ref() is None for ref in weights)
