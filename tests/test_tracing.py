"""Request-scoped distributed tracing (PR 11): context propagation
across remote calls, causal-tree reconstruction, tail-sampling,
critical-path analysis, and exemplar linkage.

The e2e test routes concurrent requests through the PR-6 routed LLM app
(2 replicas) and asserts each request reconstructs into a single
parent-linked tree router -> replica -> engine phases, with the TTFT
histogram's exemplar pointing back at a retrievable trace.
"""

import time

import numpy as np
import pytest

import ray_tpu

_CACHE = {}


def _model():
    if "model" not in _CACHE:
        import jax

        from ray_tpu.models.llama import LlamaConfig, init_params

        config = LlamaConfig.tiny()
        _CACHE["model"] = (config, init_params(config, jax.random.key(0)))
    return _CACHE["model"]


@pytest.fixture(scope="module")
def traced_cluster():
    """Cluster with head-sampling disabled (sample_rate=1.0) so every
    completed trace is kept; env must be set before init — the GCS reads
    the knob when it constructs its TraceStore."""
    import os

    os.environ["RAY_TPU_trace_sample_rate"] = "1.0"
    try:
        info = ray_tpu.init(num_cpus=8, num_tpus=0,
                            object_store_memory=256 * 1024 * 1024,
                            ignore_reinit_error=True)
        yield info
        ray_tpu.shutdown()
    finally:
        os.environ.pop("RAY_TPU_trace_sample_rate", None)


def _poll_trace(trace_id, want_names=(), timeout=20.0):
    """Poll util.state.get_trace until the trace is kept and every name
    in `want_names` has arrived (processes flush spans on their own
    debounced cadence, so a kept trace can briefly miss late hops)."""
    from ray_tpu.util import state

    deadline = time.monotonic() + timeout
    tree = None
    while time.monotonic() < deadline:
        tree = state.get_trace(trace_id)
        if tree is not None and tree.get("complete") and tree.get("root"):
            names = _all_names(tree["root"])
            for o in tree.get("orphans", []):
                names |= _all_names(o)
            if set(want_names) <= names:
                return tree
        time.sleep(0.2)
    raise AssertionError(f"trace {trace_id} incomplete after {timeout}s: "
                         f"{tree}")


def _all_names(node):
    out = {node["name"]}
    for c in node["children"]:
        out |= _all_names(c)
    return out


def _child(node, name):
    matches = [c for c in node["children"] if c["name"] == name]
    assert matches, (f"no child {name!r} under {node['name']!r}; have "
                     f"{[c['name'] for c in node['children']]}")
    return matches[0]


# ------------------------------------------------------------- pure context


class TestTraceContext:
    def test_wire_roundtrip_drops_parent(self):
        from ray_tpu.util.tracing import TraceContext

        tc = TraceContext(trace_id="t1", span_id="s1",
                          parent_span_id="p0", baggage={"slo": "gold"})
        wire = tc.to_wire()
        assert wire == {"t": "t1", "s": "s1", "b": {"slo": "gold"}}
        back = TraceContext.from_wire(wire)
        # The receiver parents to the *sender's* span, so the sender's
        # own parent link never travels.
        assert back.trace_id == "t1" and back.span_id == "s1"
        assert back.parent_span_id is None
        assert back.baggage == {"slo": "gold"}
        assert TraceContext.from_wire(None) is None

    def test_child_context_parents_under_ambient(self):
        from ray_tpu.util import tracing

        assert tracing.current_trace() is None
        assert tracing.child_context() is None
        with tracing.trace_root("unit.root", baggage={"k": "v"}) as tc:
            active = tracing.current_trace()
            assert active is tc
            child = tracing.child_context()
            assert child.trace_id == tc.trace_id
            assert child.parent_span_id == tc.span_id
            assert child.span_id != tc.span_id
            assert child.baggage == {"k": "v"}
            with tracing.span("unit.step"):
                nested = tracing.current_trace()
                assert nested.trace_id == tc.trace_id
                assert nested.parent_span_id == tc.span_id
            # span() restores the outer context on exit.
            assert tracing.current_trace() is tc
        assert tracing.current_trace() is None


# -------------------------------------------------- tree / critical path


def _span(name, span_id, parent, ts, dur, **attrs):
    return {"trace_id": "T", "span_id": span_id, "parent_span_id": parent,
            "name": name, "ts": ts, "dur": dur, "attrs": attrs}


class TestTreeAnalysis:
    def test_build_tree_and_critical_path(self):
        from ray_tpu.util.tracing import build_trace_tree, critical_path

        spans = [
            _span("serve.request", "r", None, 0.0, 1.0, trace_root=True),
            _span("llm.server_call", "c", "r", 0.02, 0.9),
            _span("llm.request", "q", "c", 0.05, 0.85),
            _span("llm.queued", "p1", "q", 0.05, 0.05),
            _span("llm.prefill", "p2", "q", 0.10, 0.20),
            _span("llm.decode", "p3", "q", 0.30, 0.60),
        ]
        tree = build_trace_tree(spans)
        assert tree["num_spans"] == 6 and not tree["orphans"]
        root = tree["root"]
        assert root["name"] == "serve.request"
        call = _child(root, "llm.server_call")
        req = _child(call, "llm.request")
        assert [c["name"] for c in req["children"]] == \
            ["llm.queued", "llm.prefill", "llm.decode"]
        cp = critical_path(tree)
        assert [h["name"] for h in cp["path"]] == \
            ["serve.request", "llm.server_call", "llm.request",
             "llm.decode"]
        assert cp["dominant"] == "llm.decode"
        assert cp["dominant_self_s"] == pytest.approx(0.6)
        assert cp["total_s"] == pytest.approx(1.0)

    def test_orphan_spans_surface(self):
        from ray_tpu.util.tracing import build_trace_tree

        spans = [
            _span("root", "r", None, 0.0, 1.0, trace_root=True),
            _span("lost-hop-child", "x", "never-arrived", 0.2, 0.1),
        ]
        tree = build_trace_tree(spans)
        assert tree["root"]["name"] == "root"
        assert [o["name"] for o in tree["orphans"]] == ["lost-hop-child"]

    def test_span_tree_orphan_spans_not_dropped(self, monkeypatch):
        """SPAN events whose task node fell out of the lifecycle ring
        surface as an orphan root instead of vanishing."""
        from ray_tpu.util.tracing import span_tree

        events = [
            {"task_id": b"t1", "name": "f", "state": "PENDING", "ts": 1.0},
            {"task_id": b"t1", "name": "inner", "state": "SPAN",
             "ts": 1.1, "dur": 0.2, "attrs": {}},
            {"task_id": b"gone", "name": "lost", "state": "SPAN",
             "ts": 2.0, "dur": 0.1, "attrs": {}},
        ]
        monkeypatch.setattr(ray_tpu, "task_events", lambda: events)
        roots = span_tree()
        orphans = [r for r in roots if r.get("orphan")]
        assert len(orphans) == 1
        assert orphans[0]["name"] == "(orphaned-spans)"
        assert orphans[0]["spans"][0]["name"] == "lost"
        assert orphans[0]["spans"][0]["attrs"]["orphan"] is True
        attached = next(r for r in roots if r["task_id"] == b"t1".hex())
        assert [s["name"] for s in attached["spans"]] == ["inner"]


# ------------------------------------------------------------ trace store


class _FixedRng:
    def __init__(self, value):
        self._value = value

    def random(self):
        return self._value


def _feed(store, trace_id, root_dur, error=False):
    store.add_span(_span("hop", f"{trace_id}-h", f"{trace_id}-r", 0.0,
                         root_dur / 2, **({"error": "ValueError"}
                                          if error else {}))
                   | {"trace_id": trace_id})
    store.add_span(_span("root", f"{trace_id}-r", None, 0.0, root_dur,
                         trace_root=True) | {"trace_id": trace_id})


class TestTraceStore:
    def test_tail_sampling_keeps_slow_and_errors(self):
        from ray_tpu.observability.traces import TraceStore

        store = TraceStore(maxlen=8, keep_threshold_s=0.5,
                           sample_rate=0.0, rng=_FixedRng(0.99))
        _feed(store, "slow", root_dur=0.8)
        _feed(store, "fast", root_dur=0.01)
        _feed(store, "bad", root_dur=0.01, error=True)
        assert store.get("slow")["keep_reason"] == "slow"
        assert store.get("bad")["keep_reason"] == "error"
        assert store.get("bad")["error"] is True
        assert store.get("fast") is None         # sampled out
        assert store.sampled_out == 1 and store.kept == 2

    def test_sample_rate_keeps_fast_traces(self):
        from ray_tpu.observability.traces import TraceStore

        store = TraceStore(maxlen=8, keep_threshold_s=0.5,
                           sample_rate=1.0, rng=_FixedRng(0.5))
        _feed(store, "fast", root_dur=0.01)
        got = store.get("fast")
        assert got["keep_reason"] == "sampled" and got["complete"]
        assert len(got["spans"]) == 2
        assert store.summaries()[0]["trace_id"] == "fast"

    def test_pending_get_and_eviction(self):
        from ray_tpu.observability.traces import TraceStore

        store = TraceStore(maxlen=2, pending_max=2, sample_rate=1.0)
        store.add_span(_span("hop", "h1", None, 0.0, 0.1)
                       | {"trace_id": "inflight"})
        got = store.get("inflight")
        assert got is not None and got["complete"] is False
        # Two more rootless traces push the oldest pending out.
        store.add_span(_span("hop", "h2", None, 0.0, 0.1)
                       | {"trace_id": "t2"})
        store.add_span(_span("hop", "h3", None, 0.0, 0.1)
                       | {"trace_id": "t3"})
        assert store.evicted_pending == 1
        assert store.get("inflight") is None
        assert store.stats()["pending"] == 2


# -------------------------------------------------------------- exemplars


def test_histogram_exemplar_tracks_slowest():
    from ray_tpu.util.metrics import Histogram

    h = Histogram("tracing_test_exemplar_seconds",
                  boundaries=[0.1, 1.0, 10.0])
    h.observe(0.5, trace_id="mid")
    h.observe(0.1, trace_id="small")             # smaller: not replaced
    assert h._snapshot()["exemplars"][""]["trace_id"] == "mid"
    h.observe(0.9, trace_id="big")               # >= stored: replaced
    ex = h._snapshot()["exemplars"][""]
    assert ex["trace_id"] == "big" and ex["value"] == pytest.approx(0.9)


# ------------------------------------------------------------ propagation


class TestPropagation:
    def test_remote_task_inherits_caller_context(self, traced_cluster):
        from ray_tpu.util import tracing

        @ray_tpu.remote
        def _whoami():
            tc = tracing.current_trace()
            return (tc.trace_id, tc.span_id) if tc else None

        assert ray_tpu.get(_whoami.remote(), timeout=60) is None
        with tracing.trace_root("prop.root") as tc:
            got = ray_tpu.get(_whoami.remote(), timeout=60)
        # The worker's restored identity IS the caller's active span.
        assert got == (tc.trace_id, tc.span_id)

    def test_concurrent_actor_requests_stay_separated(self, traced_cluster):
        from ray_tpu.util import tracing

        @ray_tpu.remote(max_concurrency=4)
        class _Echo:
            async def tid(self, delay):
                import asyncio

                await asyncio.sleep(delay)
                tc = tracing.current_trace()
                return tc.trace_id if tc else None

        a = _Echo.remote()
        ray_tpu.get(a.tid.remote(0.0), timeout=60)   # warm up creation
        with tracing.trace_root("req.a") as ta:
            ref_a = a.tid.remote(0.4)
        with tracing.trace_root("req.b") as tb:
            ref_b = a.tid.remote(0.4)
        # Both coroutines sleep concurrently inside one actor; the
        # contextvar keeps their trace identities apart.
        got_a, got_b = ray_tpu.get([ref_a, ref_b], timeout=60)
        assert got_a == ta.trace_id
        assert got_b == tb.trace_id
        assert ta.trace_id != tb.trace_id

    def test_driver_trace_tree_via_state(self, traced_cluster):
        from ray_tpu.util import tracing

        @ray_tpu.remote
        def _leaf():
            with tracing.span("remote.work"):
                time.sleep(0.01)
            return 1

        with tracing.trace_root("req.root") as tc:
            with tracing.span("step.local"):
                assert ray_tpu.get(_leaf.remote(), timeout=60) == 1
        tree = _poll_trace(tc.trace_id,
                           want_names=("req.root", "step.local",
                                       "remote.work"))
        root = tree["root"]
        assert root["name"] == "req.root"
        assert root["attrs"].get("trace_root") is True
        step = _child(root, "step.local")
        # The remote span parents under the span active at submit time.
        work = _child(step, "remote.work")
        assert work["parent_span_id"] == step["span_id"]
        assert step["parent_span_id"] == root["span_id"]
        from ray_tpu.util import state

        summaries = state.list_traces()
        assert any(s["trace_id"] == tc.trace_id for s in summaries)


# -------------------------------------------------------------- serve e2e


def test_routed_llm_tracing_e2e(traced_cluster):
    """Acceptance: concurrent requests through the 2-replica routed app
    come back with x-trace-id; each reconstructs into one causal tree
    router -> replica -> engine phases; the critical path of the slowest
    request names an engine phase; the TTFT exemplar resolves to a
    retrievable trace."""
    from ray_tpu import serve
    from ray_tpu.serve.llm import build_routed_llm_app
    from ray_tpu.util import state

    config, _ = _model()
    try:
        handle = serve.run(build_routed_llm_app(
            model_config=config,
            engine_config={"num_slots": 2, "max_seq_len": 64,
                           "prefill_buckets": (8, 16),
                           "kv_block_size": 8},
            num_replicas=2, num_tpus=0, quantize="bf16",
            max_ongoing_requests=8,
            probe_interval_s=0.1), name="llm-traced")
        rng = np.random.RandomState(7)
        prompts = [rng.randint(0, config.vocab_size,
                               rng.randint(2, 16)).tolist()
                   for _ in range(6)]
        # Warm-up: pay replica init + jit compile outside the measured
        # traces, so the measured requests are steady-state and their
        # latency lives in the engine phases.
        warm_ids = [
            handle.remote({"prompt": p, "max_tokens": 2}).result(
                timeout=180)["x-trace-id"]
            for p in prompts[4:]]

        resps = [handle.remote({"prompt": p, "max_tokens": 16})
                 for p in prompts[:4]]
        outs = [r.result(timeout=180) for r in resps]

        trace_ids = [o["x-trace-id"] for o in outs]
        assert len(set(trace_ids)) == 4          # disjoint traces

        trees = {}
        for tid in trace_ids:
            tree = _poll_trace(tid, want_names=(
                "serve.request", "serve.replica_call", "llm.server_call",
                "llm.request", "llm.decode"))
            root = tree["root"]
            assert root["name"] == "serve.request"
            hop = _child(root, "serve.replica_call")
            call = _child(hop, "llm.server_call")
            req = _child(call, "llm.request")
            phases = {c["name"] for c in req["children"]}
            assert "llm.queued" in phases and "llm.decode" in phases
            # Parent links hop by hop.
            assert hop["parent_span_id"] == root["span_id"]
            assert call["parent_span_id"] == hop["span_id"]
            assert req["parent_span_id"] == call["span_id"]
            trees[tid] = tree

        # Critical path: the slowest request (it paid queueing and/or
        # compile) is dominated by an engine phase, not glue code.
        slowest = max(trees.values(), key=lambda t: t["dur"] or 0.0)
        cp = state.trace_critical_path(slowest)
        assert cp["path"][0]["name"] == "serve.request"
        assert cp["dominant"] in {"llm.queued", "llm.prefill",
                                  "llm.decode"}
        assert cp["dominant_self_s"] > 0.0
        # trace_critical_path also accepts the bare trace_id.
        by_id = state.trace_critical_path(slowest["trace_id"])
        assert by_id["dominant"] == cp["dominant"]

        # Exemplar linkage: the TTFT histogram's exemplar names one of
        # this run's traces (the slowest TTFT — usually a warm-up
        # request that paid compile), and that trace is retrievable.
        ex = _poll_ttft_exemplar()
        assert ex["trace_id"] in set(trace_ids) | set(warm_ids)
        linked = state.get_trace(ex["trace_id"])
        assert linked is not None
        assert linked["root"]["name"] == "serve.request"
    finally:
        serve.shutdown()


def _poll_ttft_exemplar(timeout=30.0):
    """The replicas push metric snapshots on a ~2s cadence; poll the GCS
    aggregate until serve_ttft_seconds carries an exemplar."""
    from ray_tpu.util.state import _gcs

    deadline = time.monotonic() + timeout
    last = None
    while time.monotonic() < deadline:
        reply = _gcs().call("user_metrics_summary", prefixes=["serve_"],
                            timeout=10)
        last = (reply or {}).get("serve_ttft_seconds")
        exemplars = (last or {}).get("exemplars") or {}
        if exemplars:
            return next(iter(exemplars.values()))
        time.sleep(0.5)
    raise AssertionError(f"no TTFT exemplar after {timeout}s: {last}")
