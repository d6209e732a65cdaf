"""Live profiling plane (reference: `ray stack` / py-spy-backed
`dashboard/modules/reporter/profile_manager.py`): the in-process
StackSampler, cluster stack dumps + flamegraphs via util.state, the
SIGUSR2 all-thread dump, and the scheduling-latency phase breakdown."""

import io
import os
import signal
import threading
import time

import pytest

import ray_tpu
from ray_tpu.observability.profiling import (
    SCHED_PHASES,
    SCHED_SEGMENT_LABELS,
    StackSampler,
    collapse,
    render_speedscope,
)


def _repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _wait_for(pred, timeout=30.0, period=0.2):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(period)
    return False


# ---------------------------------------------------------------------------
# StackSampler units (no cluster)
# ---------------------------------------------------------------------------

def test_sampler_attributes_busy_thread():
    """A busy-spinning thread gets >=80% of its samples attributed to
    the spin function, and the aggregate renders to collapsed-stack
    text and valid speedscope JSON."""
    stop = threading.Event()

    def _busy_marker_spin():
        while not stop.is_set():
            pass

    t = threading.Thread(target=_busy_marker_spin, name="busy-spin",
                         daemon=True)
    t.start()
    try:
        s = StackSampler(hz=200, max_unique_stacks=10_000).start()
        time.sleep(0.8)
        snap = s.stop()
    finally:
        stop.set()
        t.join(timeout=5)

    busy = snap["counts"].get("busy-spin", {})
    total = sum(busy.values())
    assert total >= 10, snap
    marked = sum(n for folded, n in busy.items()
                 if "_busy_marker_spin" in folded)
    assert marked / total >= 0.8, busy
    assert snap["samples"] == sum(
        n for per in snap["counts"].values() for n in per.values())
    assert snap["duration_s"] >= 0.7

    folded_text = collapse(snap["counts"])
    assert "busy-spin;" in folded_text
    # hottest-first: every line is "thread;frame;...;frame count"
    first = folded_text.splitlines()[0]
    assert first.rsplit(" ", 1)[1].isdigit()

    sco = render_speedscope(snap["counts"], name="unit test")
    assert sco["$schema"].startswith("https://www.speedscope.app/")
    prof = {p["name"]: p for p in sco["profiles"]}["busy-spin"]
    assert prof["type"] == "sampled"
    assert sum(prof["weights"]) == total
    assert len(prof["samples"]) == len(prof["weights"])
    frames = [f["name"] for f in sco["shared"]["frames"]]
    assert any("_busy_marker_spin" in n for n in frames)
    # sample rows index into the shared frame table
    for row in prof["samples"]:
        assert all(0 <= i < len(frames) for i in row)


def test_sampler_bounded_memory_drops_not_allocates():
    """A workload generating unboundedly many distinct stacks cannot
    grow the count table past max_unique_stacks: overflow lands in
    `dropped`."""
    stop = time.monotonic() + 0.6

    def _deep(n):
        if n <= 0:
            until = time.monotonic() + 0.002
            while time.monotonic() < until:
                pass
            return
        _deep(n - 1)

    def _churn():
        d = 0
        while time.monotonic() < stop:
            _deep(d % 40 + 1)
            d += 1

    t = threading.Thread(target=_churn, name="stack-churn", daemon=True)
    t.start()
    s = StackSampler(hz=250, max_unique_stacks=4).start()
    t.join()
    snap = s.stop()
    unique = sum(len(per) for per in snap["counts"].values())
    assert unique <= 4, snap["counts"]
    assert snap["dropped"] > 0
    assert snap["samples"] == sum(
        n for per in snap["counts"].values() for n in per.values())


def test_sampler_idle_overhead_bounded():
    """Sampling an idle process at the default-ish rate costs a small
    fraction of a CPU (the sampler must be safe to leave running
    against a live worker)."""
    # Idle means idle: a file that ran earlier in this process may have
    # left compile captures queued on the `xla-capture` worker.
    from ray_tpu.observability import flush_captures

    flush_captures(timeout=120.0)
    window = 1.0
    cpu0 = time.process_time()
    s = StackSampler(hz=100).start()
    time.sleep(window)
    snap = s.stop()
    cpu = time.process_time() - cpu0
    # Generous bound: the whole process (sampler included) stays under
    # half a core while idle. Typical observed cost is a few percent.
    assert cpu < 0.5 * window, f"sampler burned {cpu:.3f}s CPU in {window}s"
    assert snap["samples"] > 0
    # Re-start is a programming error, not silent corruption.
    with pytest.raises(RuntimeError):
        s.start()


def test_sampler_hz_clamped_and_snapshot_while_running():
    s = StackSampler(hz=10_000)
    assert s.hz == 1000.0
    assert StackSampler(hz=0.01).hz == 1.0
    s = StackSampler(hz=100).start()
    try:
        time.sleep(0.3)
        live = s.snapshot()  # partial profiles of a dying worker use this
        assert live["samples"] > 0
        assert live["duration_s"] > 0
    finally:
        s.stop()


# ---------------------------------------------------------------------------
# SIGUSR2 all-thread dump (satellite a)
# ---------------------------------------------------------------------------

def test_dump_thread_stacks_direct():
    from ray_tpu._private import rpc as rpc_mod

    buf = io.StringIO()
    rpc_mod.dump_thread_stacks(file=buf)
    text = buf.getvalue()
    assert "Python thread stacks" in text
    assert "--- thread MainThread" in text
    # the dump sees *this* frame on the main thread
    assert "test_dump_thread_stacks_direct" in text


def test_sigusr2_dumps_coroutines_and_threads(capsys):
    from ray_tpu._private import rpc as rpc_mod

    old = signal.getsignal(signal.SIGUSR2)
    try:
        rpc_mod.install_coroutine_dump_signal()
        os.kill(os.getpid(), signal.SIGUSR2)
        time.sleep(0.1)  # handler runs between bytecodes on main thread
        err = capsys.readouterr().err
        assert "Python thread stacks" in err
        assert "MainThread" in err
    finally:
        signal.signal(signal.SIGUSR2, old)


# ---------------------------------------------------------------------------
# Chrome-trace builder (satellite b)
# ---------------------------------------------------------------------------

def _ev(tid, state, ts, **extra):
    e = {"task_id": tid, "state": state, "ts": ts, "name": "f",
         "owner_pid": 7}
    e.update(extra)
    return e


def test_timeline_incomplete_tasks_render_monotone():
    from ray_tpu.observability.timeline import build_chrome_trace

    t0 = 1000.0
    events = [
        _ev(b"t1", "PENDING", t0),
        _ev(b"t1", "RUNNING", t0 + 1, worker_addr=["h", 1]),
        # a later event sets the ring horizon the open bar extends to
        _ev(b"t2", "RUNNING", t0 + 5, name="g", worker_addr=["h", 1]),
    ]
    a = build_chrome_trace(events)
    time.sleep(0.05)
    b = build_chrome_trace(events)
    assert a == b, "render must be a pure function of the event ring"

    bars = {e["args"]["task_id"]: e for e in a if e["cat"] == "task"}
    t1 = bars[b"t1".hex()]
    assert t1["args"]["state"] == "RUNNING"
    assert t1["args"]["incomplete"] is True
    assert t1["dur"] == pytest.approx(4 * 1e6)  # to horizon, not time.time()
    t2 = bars[b"t2".hex()]
    assert t2["dur"] == 0
    assert t2["args"]["incomplete"] is True


def test_timeline_clamps_negative_durations():
    from ray_tpu.observability.timeline import build_chrome_trace

    t0 = 2000.0
    events = [
        # skewed clocks: FINISHED stamped before RUNNING
        _ev(b"t1", "RUNNING", t0 + 1.0, worker_addr=["h", 1]),
        _ev(b"t1", "FINISHED", t0 + 0.5),
        _ev(b"s1", "SPAN", t0, name="sp", dur=-5.0),
    ]
    trace = build_chrome_trace(events)
    bar = [e for e in trace if e["cat"] == "task"][0]
    assert bar["dur"] == 0
    assert bar["args"]["state"] == "FINISHED"
    assert "incomplete" not in bar["args"]
    span = [e for e in trace if e["cat"] == "span"][0]
    assert span["dur"] == 0


def test_timeline_phase_segments():
    """All five lifecycle phases present -> four named submit segments,
    and the refined (worker-stamped) RUNNING supersedes the owner's
    push-time RUNNING for the execution bar."""
    from ray_tpu.observability.timeline import build_chrome_trace

    t0 = 3000.0
    ts = {p: t0 + i * 0.01 for i, p in enumerate(SCHED_PHASES)}
    events = [_ev(b"t1", p, ts[p]) for p in SCHED_PHASES]
    # owner's coarse push-time RUNNING, recorded *before* the refined one
    events.insert(2, _ev(b"t1", "RUNNING", ts["LEASE_GRANTED"] + 0.001,
                         worker_addr=["h", 1]))
    events[-1]["worker_addr"] = ["h", 1]
    events.append(_ev(b"t1", "FINISHED", t0 + 1.0))

    trace = build_chrome_trace(events)
    bar = [e for e in trace if e["cat"] == "task"][0]
    assert bar["ts"] == pytest.approx(ts["RUNNING"] * 1e6)  # refined wins

    segs = [e for e in trace if e["cat"] == "submit"]
    assert [s["args"]["phase"] for s in segs] == \
        [SCHED_SEGMENT_LABELS[p] for p in SCHED_PHASES[1:]]
    assert {s["name"] for s in segs} == \
        {f"f:{SCHED_SEGMENT_LABELS[p]}" for p in SCHED_PHASES[1:]}
    # segments tile the submit->exec window without gaps
    for (a, b) in zip(segs, segs[1:]):
        assert a["ts"] + a["dur"] == pytest.approx(b["ts"])
    assert segs[0]["ts"] == pytest.approx(ts["PENDING"] * 1e6)
    assert segs[-1]["ts"] + segs[-1]["dur"] == \
        pytest.approx(ts["RUNNING"] * 1e6)
    assert all(s["pid"] == "driver-7" for s in segs)

    # with only the legacy two events, a single exec_start segment remains
    legacy = build_chrome_trace([
        _ev(b"t9", "PENDING", t0),
        _ev(b"t9", "RUNNING", t0 + 0.2, worker_addr=["h", 1]),
        _ev(b"t9", "FINISHED", t0 + 0.4),
    ])
    legacy_segs = [e for e in legacy if e["cat"] == "submit"]
    assert len(legacy_segs) == 1
    assert legacy_segs[0]["dur"] == pytest.approx(0.2 * 1e6)


def test_observe_sched_phases_clamps_and_skips():
    """Unit: cross-host clock skew never produces a negative
    observation, and missing middle phases widen the next segment."""
    from ray_tpu.observability import profiling as prof

    recorded = []

    class _FakeHist:
        def observe(self, v, tags=None):
            recorded.append((tags["phase"], v))

    orig = prof._sched_metrics
    prof._sched_metrics = _FakeHist()
    try:
        prof.observe_sched_phases({
            "PENDING": 100.0,
            "LEASE_GRANTED": 100.010,
            # WORKER_STARTED missing (evicted) -> args_fetch widens
            "ARGS_READY": 100.030,
            "RUNNING": 100.025,  # skewed: earlier than ARGS_READY
        })
    finally:
        prof._sched_metrics = orig
    assert recorded == [
        ("lease_grant", pytest.approx(0.010)),
        ("args_fetch", pytest.approx(0.020)),
        ("exec_start", 0.0),  # clamped, not negative
    ]


# ---------------------------------------------------------------------------
# check_metrics histogram-suffix rule (satellite c)
# ---------------------------------------------------------------------------

def test_check_metrics_histogram_suffix_rule(tmp_path):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "check_metrics",
        os.path.join(_repo_root(), "scripts", "check_metrics.py"))
    cm = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(cm)

    bad = tmp_path / "bad.py"
    bad.write_text(
        "from ray_tpu.util.metrics import Histogram\n"
        "h = Histogram('serve_latency_ms', tag_keys=('route',))\n")
    problems = cm.check_paths(str(tmp_path))
    assert any("serve_latency_ms" in p and "_seconds" in p
               for p in problems), problems

    bad.write_text(
        "from ray_tpu.util.metrics import Histogram\n"
        "h = Histogram('sched_phase_seconds', tag_keys=('phase',))\n"
        "b = Histogram('object_store_spill_bytes')\n")
    assert cm.check_paths(str(tmp_path)) == []


# ---------------------------------------------------------------------------
# Cluster end-to-end
# ---------------------------------------------------------------------------

@ray_tpu.remote
class _Spinner:
    def ping(self):
        return "pong"

    def spin_marker_method(self, seconds):
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            pass
        return "spun"


def test_state_stack_covers_workers(ray_start_regular):
    """util.state.stack() returns live all-thread stacks for every
    worker on the node, and the actor selector narrows to one."""
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    a = _Spinner.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"

    rows = global_worker().gcs.call("list_workers", timeout=30)
    worker_ids = {r["worker_id"].hex() for r in rows
                  if r.get("mode") == "worker"}
    assert worker_ids

    out = state.stack()
    assert worker_ids <= set(out), (worker_ids, set(out))
    for whex in worker_ids:
        entry = out[whex]
        assert entry["pid"] > 0
        assert "--- thread MainThread" in entry["stacks"]
        names = {t["thread_name"] for t in entry["threads"]}
        assert "MainThread" in names

    narrowed = state.stack(actor_id=a._actor_id.hex())
    assert len(narrowed) == 1
    (whex,) = narrowed
    assert whex in worker_ids

    with pytest.raises(ValueError):
        state.stack(node_id="ab", worker_id="cd")


def test_state_profile_attributes_busy_actor(ray_start_regular):
    """util.state.profile(actor_id=..., duration=1) returns a non-empty
    collapsed-stack + speedscope payload attributing the busy method."""
    from ray_tpu.util import state

    a = _Spinner.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    ref = a.spin_marker_method.remote(3.0)

    out = state.profile(actor_id=a._actor_id.hex(), duration=1.0, hz=200)
    assert out["partial"] is False
    assert out["exit"] is None
    assert out["samples"] > 0
    assert out["pid"] > 0
    assert "spin_marker_method" in out["folded"]
    sco = out["speedscope"]
    assert sco["profiles"], sco
    assert any("spin_marker_method" in f["name"]
               for f in sco["shared"]["frames"])
    assert ray_tpu.get(ref, timeout=60) == "spun"

    with pytest.raises(ValueError):
        state.profile()  # needs exactly one selector


def test_sched_phases_in_timeline_and_metrics(ray_start_regular):
    """Executed tasks carry the full phase chain: segmented submit
    arrows in ray_tpu.timeline() and rtpu_sched_phase_seconds{phase}
    on the GCS /metrics exposition."""
    from ray_tpu._private.worker import global_worker

    @ray_tpu.remote
    def add(x, y):
        return x + y

    assert ray_tpu.get([add.remote(i, i) for i in range(5)],
                       timeout=60) == [2 * i for i in range(5)]

    want = set(SCHED_SEGMENT_LABELS.values())

    def _phases_rendered():
        segs = [e for e in ray_tpu.timeline()
                if e["cat"] == "submit" and e["name"].startswith("add:")]
        return want <= {s["args"]["phase"] for s in segs}

    assert _wait_for(_phases_rendered, timeout=30), \
        [e["name"] for e in ray_tpu.timeline() if e["cat"] == "submit"]

    w = global_worker()

    def _metric_exported():
        text = w.gcs.call("metrics_text", timeout=30)
        return ("rtpu_sched_phase_seconds_bucket" in text
                and 'phase="exec_start"' in text)

    assert _wait_for(_metric_exported, timeout=30)
    text = w.gcs.call("metrics_text", timeout=30)
    assert "# TYPE rtpu_sched_phase_seconds histogram" in text


def test_tpu_profile_noop_with_reason_on_cpu(ray_start_regular):
    """On CPU CI the device-trace bracket must refuse loudly-but-safely:
    a `skipped` reason, not an error (and not a hang)."""
    from ray_tpu.util import state

    a = _Spinner.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "pong"
    out = state.tpu_profile(actor_id=a._actor_id.hex(), duration=0.1)
    if "skipped" in out:  # CPU CI path
        assert "tpu" in out["skipped"]
    else:  # real TPU host
        assert out.get("artifact")


@pytest.fixture
def profiling_isolated():
    """Fresh per-test cluster for the death test; tears down the
    module-shared cluster first (init() refuses to double-init)."""
    if ray_tpu.is_initialized():
        ray_tpu.shutdown()
    info = ray_tpu.init(num_cpus=4, num_tpus=0,
                        object_store_memory=128 * 1024 * 1024)
    yield info
    ray_tpu.shutdown()


def test_profile_partial_when_worker_dies(profiling_isolated):
    """A target that dies mid-window yields the samples gathered so far,
    tagged with the raylet's exit classification — never a hang."""
    from ray_tpu.observability import WORKER_EXIT_TYPES
    from ray_tpu.util import state

    @ray_tpu.remote
    class _Doomed:
        def ping(self):
            return "ok"

        def busy_then_die(self, busy_s):
            deadline = time.monotonic() + busy_s
            while time.monotonic() < deadline:
                pass
            os._exit(3)

    a = _Doomed.remote()
    assert ray_tpu.get(a.ping.remote(), timeout=60) == "ok"
    a.busy_then_die.remote(1.2)

    out = state.profile(actor_id=a._actor_id.hex(), duration=6.0, hz=100)
    assert out["partial"] is True
    assert out["duration_s"] < 5.0  # stopped at death, not the full window
    assert out["exit"] is not None
    assert out["exit"]["exit_type"] in WORKER_EXIT_TYPES
    assert out["exit"]["exit_type"] == "USER_ERROR"  # os._exit(3)
    assert out["samples"] > 0
    assert "busy_then_die" in out["folded"]
