"""Log aggregation + memory monitor (reference: `_private/log_monitor.py`,
`memory_monitor.h` + `worker_killing_policy.h`)."""

import os
import sys
import time

import pytest


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait_for(pred, timeout=30.0, period=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(period)
    return False


def test_log_monitor_scan_units(tmp_path):
    from ray_tpu._private.log_monitor import LogMonitor

    d = tmp_path / "logs"
    d.mkdir()
    f = d / "worker-abc123def456.out"
    f.write_bytes(b"hello\npartial")
    mon = LogMonitor(str(d), pid_of=lambda w: 42 if w else None)
    msgs = mon.scan()
    assert len(msgs) == 1
    assert msgs[0]["lines"] == ["hello"]
    assert msgs[0]["pid"] == 42
    assert msgs[0]["worker_id"] == "abc123def456"
    # Nothing new -> nothing published; the partial line stays buffered.
    assert mon.scan() == []
    with open(f, "ab") as fh:
        fh.write(b"-done\nWARNING:x:jax._src.xla_bridge:1: Platform 'xyz'"
                 b" is experimental\n")
    msgs = mon.scan()
    assert msgs[0]["lines"] == ["partial-done"]  # noise line filtered


def test_task_print_reaches_driver(tmp_path):
    """A print() inside a remote task shows up on the driver's stderr."""
    import subprocess

    script = tmp_path / "driver.py"
    script.write_text(
        "import time\n"
        "import ray_tpu\n"
        "ray_tpu.init(num_cpus=2)\n"
        "@ray_tpu.remote\n"
        "def noisy():\n"
        "    print('marker-from-remote-task')\n"
        "    return 1\n"
        "assert ray_tpu.get(noisy.remote(), timeout=60) == 1\n"
        "time.sleep(2.5)\n"
        "ray_tpu.shutdown()\n")
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": _repo_root()})
    assert proc.returncode == 0, proc.stderr[-2000:]
    echoed = [ln for ln in proc.stderr.splitlines()
              if "marker-from-remote-task" in ln and "ip=" in ln]
    assert echoed, proc.stderr[-2000:]
    assert echoed[0].startswith("(pid=")


def test_memory_monitor_units(tmp_path):
    from ray_tpu._private import memory_monitor

    usage = tmp_path / "usage"
    usage.write_text("0.42")
    assert memory_monitor.usage_fraction(str(usage)) == pytest.approx(0.42)

    class H:
        _n = 0

        def __init__(self, actor, ts):
            self.lease = {}
            self.is_actor = actor
            self.lease_ts = ts
            H._n += 1
            self.worker_id = b"w%d" % H._n

    task_old, task_new, actor = H(False, 1.0), H(False, 2.0), H(True, 3.0)
    # Task workers beat actors even when the actor lease is newer.
    assert memory_monitor.pick_victim([task_old, actor, task_new]) is task_new
    assert memory_monitor.pick_victim([actor]) is actor
    idle = H(False, 0.0)
    idle.lease = None
    assert memory_monitor.pick_victim([idle]) is None
    # A busy (executing) task worker beats an idle-leased newer one:
    # killing a pool-idle worker frees no task memory.
    busy = {task_old.worker_id}
    assert memory_monitor.pick_victim(
        [task_old, task_new], busy_ids=busy) is task_old
    # ...but actors stay last-resort even when busy.
    assert memory_monitor.pick_victim(
        [task_old, actor], busy_ids={actor.worker_id}) is task_old


def test_actor_churn_does_not_wedge_cluster(tmp_path):
    """Regression: waves of actor create/kill used to stall the GCS event
    loop (sync RpcClient.close() from the loop thread blocked 2s per
    close) until heartbeats lapsed and the only node was declared dead."""
    import subprocess

    script = tmp_path / "churn.py"
    script.write_text(
        "import time\n"
        "import ray_tpu\n"
        "ray_tpu.init(num_cpus=8)\n"
        "@ray_tpu.remote\n"
        "class A:\n"
        "    def ping(self): return 'pong'\n"
        "for wave in range(3):\n"
        "    actors = [A.remote() for _ in range(4)]\n"
        "    out = ray_tpu.get([a.ping.remote() for a in actors],\n"
        "                      timeout=40)\n"
        "    assert out == ['pong'] * 4, (wave, out)\n"
        "    for a in actors:\n"
        "        ray_tpu.kill(a)\n"
        "    time.sleep(0.5)\n"
        "ray_tpu.shutdown()\n"
        "print('CHURN-OK')\n")
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=150, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "PYTHONPATH": _repo_root()})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "CHURN-OK" in proc.stdout


def test_oom_kill_and_retry(tmp_path):
    """Over-threshold memory -> raylet kills the leased task worker; the
    task retries and completes once pressure clears."""
    import subprocess

    usage = tmp_path / "usage"
    usage.write_text("0.10")
    attempts = tmp_path / "attempts"
    script = tmp_path / "driver.py"
    script.write_text(f"""
import os, time
import ray_tpu
ray_tpu.init(num_cpus=2, _system_config={{
    "memory_monitor_test_usage_path": {str(usage)!r},
    "memory_usage_threshold": 0.9,
    "memory_monitor_refresh_ms": 100,
}})

@ray_tpu.remote
def hog():
    with open({str(attempts)!r}, "a") as f:
        f.write(str(os.getpid()) + chr(10))
    time.sleep(4.0)
    return "done"

ref = hog.options(max_retries=3).remote()
# Wait until the first attempt is running, then spike memory.
while not os.path.exists({str(attempts)!r}):
    time.sleep(0.05)
with open({str(usage)!r}, "w") as f:
    f.write("0.99")
time.sleep(1.0)   # give the monitor a poll cycle to kill
with open({str(usage)!r}, "w") as f:
    f.write("0.10")
print("RESULT:" + ray_tpu.get(ref, timeout=90))
ray_tpu.shutdown()
""")
    proc = subprocess.run(
        [sys.executable, str(script)], capture_output=True, text=True,
        timeout=180, env={**os.environ, "JAX_PLATFORMS": "cpu",
             "PYTHONPATH": _repo_root()})
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "RESULT:done" in proc.stdout
    # >= 2 attempt pids proves the monitor killed attempt 1 mid-sleep
    # (without a kill the 4s first attempt completes and writes once).
    pids = [p for p in attempts.read_text().split() if p]
    assert len(pids) >= 2, (pids, proc.stderr[-2000:])


# ---------------------------------------------------------------- metrics

def test_user_metrics_exported(ray_start_regular):
    """Counter/Gauge/Histogram recorded in tasks surface on the GCS
    prometheus endpoint (reference: `ray.util.metrics` -> MetricsAgent ->
    Prometheus scrape)."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import metrics

    @ray_tpu.remote
    def work(i):
        from ray_tpu.util import metrics as m
        c = m.Counter("obs_requests", description="requests served",
                      tag_keys=("route",))
        c.inc(1.0, tags={"route": "/predict"})
        c.inc(2.0, tags={"route": "/health"})
        g = m.Gauge("obs_queue_depth", tag_keys=())
        g.set(float(i))
        h = m.Histogram("obs_latency", boundaries=[0.1, 1.0, 10.0])
        h.observe(0.05)
        h.observe(5.0)
        assert m.flush()
        return i

    assert sorted(ray_tpu.get([work.remote(i) for i in range(2)],
                              timeout=60)) == [0, 1]
    # Driver-side metric too.
    metrics.Counter("obs_driver_side").inc(3.0)
    assert metrics.flush()
    text = global_worker().gcs.call("metrics_text", timeout=30)
    assert 'rtpu_obs_requests{route="/predict"} 2.0' in text
    assert 'rtpu_obs_requests{route="/health"} 4.0' in text
    assert "# TYPE rtpu_obs_requests counter" in text
    assert "rtpu_obs_driver_side 3.0" in text
    # Gauges per-process, never summed.
    assert "# TYPE rtpu_obs_queue_depth gauge" in text
    assert 'rtpu_obs_queue_depth{pid="' in text
    # Histogram buckets are cumulative; each task saw 1 obs <= 0.1
    # and 2 obs <= +Inf.
    assert 'rtpu_obs_latency_bucket{le="0.1"} 2.0' in text
    assert 'rtpu_obs_latency_bucket{le="+Inf"} 4.0' in text
    assert "rtpu_obs_latency_count 4.0" in text


def test_metric_tag_validation():
    from ray_tpu.util.metrics import Counter, Histogram

    c = Counter("obs_tags", tag_keys=("a",))
    with pytest.raises(ValueError):
        c.inc(1.0, tags={"bogus": "x"})
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(ValueError):
        Histogram("obs_badbounds", boundaries=[-1.0])


# --------------------------------------------------------------- timeline

def test_timeline_and_span_tree(ray_start_regular):
    """Chrome-trace dump + cross-task span tree from parent_task_id links
    (reference: `ray timeline` + tracing_helper context propagation)."""
    import json

    import ray_tpu
    from ray_tpu.util import tracing

    @ray_tpu.remote
    def leaf():
        with tracing.span("leaf-work", attrs={"k": 1}):
            time.sleep(0.01)
        return 1

    @ray_tpu.remote
    def parent():
        return ray_tpu.get([leaf.remote() for _ in range(2)], timeout=30)

    assert ray_tpu.get(parent.options(name="obs_parent").remote(),
                       timeout=60) == [1, 1]
    global_worker = __import__(
        "ray_tpu._private.worker", fromlist=["global_worker"]).global_worker
    global_worker().flush_task_events()
    # Worker-side events (the leaf tasks + spans) flush on a 2s cadence.
    def _all_arrived():
        events = ray_tpu.timeline()
        names = {e["name"] for e in events}
        # Both leaf workers must have flushed their span buffers, not
        # just one — the span-tree assertions below inspect each leaf.
        n_spans = sum(1 for e in events if e["name"] == "leaf-work")
        return "obs_parent" in names and n_spans >= 2

    assert _wait_for(_all_arrived, timeout=15), \
        {e["name"] for e in ray_tpu.timeline()}

    out = os.path.join(os.path.dirname(__file__), "..", "_timeline_test.json")
    try:
        trace = ray_tpu.timeline(filename=out)
        with open(out) as f:
            assert json.load(f) == trace
    finally:
        if os.path.exists(out):
            os.remove(out)
    names = {e["name"] for e in trace}
    assert "obs_parent" in names
    assert "leaf-work" in names            # user span surfaced
    complete = [e for e in trace if e["cat"] == "task"]
    assert all(e["ph"] == "X" and e["dur"] >= 0 for e in complete)
    # All three chrome-trace event families render: task executions,
    # submit flow arrows, and user spans.
    assert {"task", "submit", "span"} <= {e["cat"] for e in trace}

    roots = tracing.span_tree()
    # The driver-submitted parent task has the two leaves as children.
    def find(nodes, name):
        for n in nodes:
            if n["name"] == name:
                return n
            got = find(n["children"], name)
            if got:
                return got
        return None

    pnode = find(roots, "obs_parent")
    assert pnode is not None
    assert len([c for c in pnode["children"] if c["name"] == "leaf"]) == 2
    leaf_node = find(pnode["children"], "leaf")
    assert any(s["name"] == "leaf-work" for s in leaf_node["spans"])


# ------------------------------------------------------- telemetry plane

def _tiny_engine(buckets=(8,), slots=2, S=32):
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(0))
    return config, LLMEngine(params, config, EngineConfig(
        num_slots=slots, max_seq_len=S, prefill_buckets=buckets,
        kv_block_size=8))


def test_tracked_jit_counts_and_warns():
    """TrackedJit counts traced programs exactly (probe runs only under
    tracing) and warns ONCE past the trace budget."""
    import warnings

    import jax.numpy as jnp

    from ray_tpu.observability import (
        RecompileWarning, jit_stats, tracked_jit)

    @tracked_jit(name="obs_tracked_fn", trace_budget=1)
    def f(x):
        return x * 2

    assert float(f(jnp.ones((4,))).sum()) == 8.0
    f(jnp.ones((4,)))                    # cache hit: no new trace
    assert f.traces == 1
    with pytest.warns(RecompileWarning, match="obs_tracked_fn"):
        f(jnp.ones((8,)))                # new shape -> re-trace > budget
    assert f.traces == 2
    with warnings.catch_warnings():
        warnings.simplefilter("error")   # warned once, never again
        f(jnp.ones((16,)))
    assert f.traces == 3
    st = jit_stats()["obs_tracked_fn"]
    assert st["traces"] >= 3 and st["compiles"] >= 3
    assert st["compile_seconds_total"] > 0


@pytest.mark.parametrize("case", ["stages", "again", "nested"])
def test_tracked_jit_splits_its_first_call(case):
    """`jit_stats()[name]` splits the first-call wall it always took:
    trace, lower and backend seconds of the CALLING thread during the
    call that traced (what is left over them is the first dispatch and
    run)."""
    import jax.numpy as jnp

    from ray_tpu.observability import jit_stats, tracked_jit

    name = f"obs_stage_fn_{case}"
    f = tracked_jit(lambda x: jnp.tanh(x @ x.T).sum() + jnp.arange(3.0)[1],
                    name=name)
    x = jnp.ones((32, 32))
    if case == "stages":
        f(x).block_until_ready()
        st = jit_stats()[name]
        parts = [st[k] for k in ("trace_seconds", "lower_seconds",
                                 "backend_seconds")]
        assert all(p > 0 for p in parts)
        assert sum(parts) <= st["compile_seconds_total"]
        assert st["traces"] == st["compiles"] == 1
    elif case == "again":
        f(x).block_until_ready()
        first = jit_stats()[name]
        f(x).block_until_ready()            # the same shapes: no stage
        assert jit_stats()[name] == first
        f(jnp.ones((8, 8)))                 # new shapes: they add
        second = jit_stats()[name]
        assert second["traces"] == 2
        assert second["trace_seconds"] > first["trace_seconds"]
        assert (second["trace_seconds"] + second["lower_seconds"]
                + second["backend_seconds"]
                <= second["compile_seconds_total"])
    else:
        # a tracked program traced inside another's trace: its seconds
        # are the outer program's, not counted twice
        outer = tracked_jit(lambda y: f(y) * 2, name=name + "_outer")
        outer(x).block_until_ready()
        inner_st, outer_st = jit_stats()[name], jit_stats()[name + "_outer"]
        assert inner_st["traces"] == 1
        assert inner_st["trace_seconds"] == inner_st["backend_seconds"] == 0
        assert outer_st["trace_seconds"] > 0


def test_engine_recompile_detector_fires():
    """Deliberately violating the engine's prefill bucket guard (a pad
    length that is not a configured bucket) re-traces the insert program
    past its budget and fires the detector."""
    import numpy as np

    from ray_tpu.observability import RecompileWarning

    _, engine = _tiny_engine(buckets=(8,))   # insert budget == 1
    from ray_tpu.serve.llm.engine import Request

    h = engine.submit(Request(prompt=[1, 2, 3], max_tokens=2))
    engine.drain()
    assert h.finish_reason == "length"
    assert engine._programs.traces()["insert"] == 1
    with pytest.warns(RecompileWarning, match="llm_engine_insert"):
        # a 16-token suffix into two fresh blocks of 8: no such bucket
        engine._programs.insert(
            engine.params, 0, engine._tables[0].copy(), 0,
            np.zeros((16,), np.int32), 3, np.asarray([0, 1], np.int32), 0.0)
    assert engine._programs.traces()["insert"] == 2


def test_serve_telemetry_end_to_end(ray_start_regular):
    """Acceptance: a short serve run exports the serving histograms and
    jit counters on /metrics, and the timeline carries per-request
    lifecycle spans plus jit-compile spans."""
    import numpy as np

    import ray_tpu
    from ray_tpu._private.worker import global_worker
    from ray_tpu.serve.llm.engine import Request
    from ray_tpu.util import metrics

    config, engine = _tiny_engine(buckets=(8,))
    rng = np.random.RandomState(7)
    handles = [engine.submit(Request(
        prompt=rng.randint(0, config.vocab_size, 5).tolist(),
        max_tokens=4)) for _ in range(3)]
    engine.drain()
    assert all(h.finish_reason == "length" for h in handles)
    st = engine.stats()
    assert st["trace_count"] == sum(st["traces"].values())
    assert st["traces"]["tick"] == st["traces"]["insert"] == 1

    assert metrics.flush()
    w = global_worker()
    text = w.gcs.call("metrics_text", timeout=30)
    assert "rtpu_serve_ttft_seconds_bucket" in text
    assert "rtpu_serve_ttft_seconds_sum" in text
    assert "rtpu_serve_ttft_seconds_count" in text
    assert "rtpu_serve_e2e_seconds_bucket" in text
    assert 'rtpu_serve_requests_total{finish_reason="length"}' in text
    assert "rtpu_serve_tokens_total" in text
    assert 'rtpu_jit_traces_total{fn="llm_engine_tick"}' in text
    assert 'rtpu_jit_traces_total{fn="llm_engine_insert"}' in text
    assert "rtpu_jit_compile_seconds_bucket" in text
    # Gauges export per-process with a pid label.
    assert 'rtpu_serve_queue_depth{pid="' in text
    assert 'rtpu_serve_batch_utilization{pid="' in text

    w.flush_task_events()

    def _requests(trace):
        # THIS run's requests: the module's cluster already holds the
        # spans of the tests above (2-token requests), which satisfy a
        # wait on names alone while this flush is still in flight.
        return [e for e in trace if e["name"] == "llm.request"
                and e["args"].get("tokens") == 4]

    def _spans_arrived():
        trace = ray_tpu.timeline()
        names = {e["name"] for e in trace}
        return len(_requests(trace)) >= 3 and {
            "jit_compile", "llm.queued", "llm.prefill",
            "llm.decode"} <= names

    assert _wait_for(_spans_arrived, timeout=120), \
        {e["name"] for e in ray_tpu.timeline()}
    trace = ray_tpu.timeline()
    req_spans = _requests(trace)
    assert len(req_spans) >= 3
    assert all(e["cat"] == "span" for e in req_spans)
    assert all(e["args"].get("finish_reason") == "length"
               for e in req_spans)
    names = {e["name"] for e in trace}
    assert {"llm.queued", "llm.prefill", "llm.decode"} <= names


def test_span_error_tagging(ray_start_regular):
    """A raising span body still records the span, tagged with the
    exception type."""
    import ray_tpu
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import tracing

    with pytest.raises(ValueError):
        with tracing.span("obs-err-span", attrs={"k": "v"}):
            raise ValueError("boom")
    global_worker().flush_task_events()

    def _arrived():
        return any(e["name"] == "obs-err-span"
                   for e in ray_tpu.timeline())

    assert _wait_for(_arrived, timeout=15)
    ev = [e for e in ray_tpu.timeline() if e["name"] == "obs-err-span"][0]
    assert ev["args"]["error"] == "ValueError"
    assert ev["args"]["k"] == "v"            # user attrs preserved


def test_device_sampler_units():
    """Device HBM/count gauges sample only already-live jax backends."""
    import jax

    from ray_tpu.observability.device import sample_device_metrics

    jax.devices()                            # force backend init (cpu)
    assert sample_device_metrics() >= 1
    from ray_tpu.util.metrics import _registry
    assert "device_count" in _registry


def test_gcs_metric_tombstones():
    """Expired sources' counters/histograms fold into the tombstone
    accumulator (totals never go backwards on worker exit); their
    gauges are pruned."""
    import asyncio

    from ray_tpu._private.gcs_server import GcsServer

    gcs = GcsServer()                        # no socket until start()
    recs = [
        {"name": "tomb_requests", "type": "counter", "description": "",
         "tag_keys": (), "default_tags": {}, "data": {"": 5.0}},
        {"name": "tomb_depth", "type": "gauge", "description": "",
         "tag_keys": (), "default_tags": {}, "data": {"": 7.0}},
        {"name": "tomb_lat", "type": "histogram", "description": "",
         "tag_keys": (), "boundaries": (1.0,), "default_tags": {},
         "data": {"": [2.0, 3.0, 4.5, 3.0]}},
    ]
    asyncio.run(gcs._h_push_metrics("111@aa", recs))
    live = "\n".join(gcs._render_user_metrics())
    assert "rtpu_tomb_requests 5.0" in live
    assert 'rtpu_tomb_depth{pid="111@aa"} 7.0' in live

    # Expire the source, then a fresh worker pushes its own counts.
    ts, r = gcs.user_metrics["111@aa"]
    gcs.user_metrics["111@aa"] = (ts - 1e6, r)
    asyncio.run(gcs._h_push_metrics("222@bb", [
        {"name": "tomb_requests", "type": "counter", "description": "",
         "tag_keys": (), "default_tags": {}, "data": {"": 2.0}}]))
    text = "\n".join(gcs._render_user_metrics())
    assert "rtpu_tomb_requests 7.0" in text   # 5 retained + 2 live
    assert "tomb_depth" not in text           # gauge pruned with source
    assert "rtpu_tomb_lat_count 3.0" in text  # histogram retained
    # Idempotent: tombstones never double-fold across renders.
    text2 = "\n".join(gcs._render_user_metrics())
    assert "rtpu_tomb_requests 7.0" in text2

    summary = asyncio.run(gcs._h_user_metrics_summary(
        prefixes=["tomb_"]))
    assert summary["tomb_requests"]["data"][""] == 7.0
    assert summary["tomb_lat"]["data"][""]["count"] == 3.0


def test_check_metrics_lint(tmp_path):
    """The AST metric lint: the shipped package passes clean; bad names
    and conflicting redeclarations are flagged; import provenance keeps
    non-metric Counter classes out."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_metrics",
        os.path.join(_repo_root(), "scripts", "check_metrics.py"))
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)

    assert cm.check_paths(os.path.join(_repo_root(), "ray_tpu")) == []

    bad = tmp_path / "bad.py"
    bad.write_text(
        "from ray_tpu.util.metrics import Counter, Histogram\n"
        "from collections import Counter as CC\n"
        "c1 = Counter('BadName')\n"
        "c2 = Counter('rtpu_double')\n"
        "h1 = Histogram('dup_hist', boundaries=[1.0])\n"
        "h2 = Histogram('dup_hist', boundaries=[2.0])\n"
        "ok = CC()\n"
        "d = Counter('dup2', tag_keys=('a',))\n"
        "e = Counter('dup2')\n")
    problems = cm.check_paths(str(tmp_path))
    joined = "\n".join(problems)
    assert "BadName" in joined
    assert "rtpu_double" in joined
    assert "dup_hist" in joined and "boundaries" in joined
    assert "dup2" in joined and "tag_keys" in joined
    assert "CC" not in joined                # provenance-filtered
