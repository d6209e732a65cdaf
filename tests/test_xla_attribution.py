"""Fleet-wide XLA program cost & roofline attribution
(observability/xla.py + chipspec.py, the TrackedJit capture/sample
hooks, the GCS program ring, and the dashboard surface).

Unit tier: the chip-spec lookup table (kind normalization, CPU tagging,
unknown-kind degradation), mesh.device_inventory over fake device
objects, cost/memory capture on the CPU backend against hand-computed
matmul FLOPs, the MFU/MBU/roofline derivation of a sampled wall, the
``xla_wall_sample_every=0`` guarantee (zero ``block_until_ready`` on the
hot path), the AOT surface (compiled()/eval_shape never inflate trace
counters; clear_cache re-arms both caches), and the regression
sentinel's once-per-episode state machine over fake compiled artifacts.

Cluster tier: synthetic program rows through the real
``report_xla_programs`` RPC drive the bounded ring, the latest-view
rollup (``util.state.xla_summary()``), malformed-row drop, a real tiny
LLM engine whose bucket programs all land with nonzero
FLOPs/HBM/MFU/MBU + verdict (CPU-tagged: plumbing, not performance),
the shape-drift recompile emitting exactly ONE typed PERF_REGRESSION
naming program and drifted dimension, ``GET /api/programs``, and the
``rtpu_xla_program_*`` metric exposition.
"""

import json
import os
import urllib.error
import urllib.request

import numpy as np
import pytest


# --------------------------------------------------------------- unit tier

class TestChipSpec:
    def test_kind_normalization(self):
        from ray_tpu.observability import chipspec

        assert chipspec.lookup("TPU v5 lite").spec == "v5e"
        assert chipspec.lookup("TPU v5e").spec == "v5e"
        assert chipspec.lookup("TPU v5p").spec == "v5p"
        # A bare "v5" names no generation: guessing v5p peaks for it
        # would fabricate every ratio derived from them.
        with pytest.raises(chipspec.UnknownChipError):
            chipspec.lookup("TPU v5")
        assert chipspec.lookup("TPU v4").spec == "v4"
        v5e = chipspec.lookup("TPU v5 lite")
        assert v5e.peak_flops == pytest.approx(197e12)
        assert v5e.peak_hbm_bytes_per_s == pytest.approx(819e9)
        assert v5e.measurement == "tpu" and v5e.known

    def test_cpu_is_tagged_plumbing_only(self):
        from ray_tpu.observability import chipspec

        cpu = chipspec.lookup("cpu")
        assert cpu.measurement == "cpu" and cpu.known
        # Tier-1 runs on the CPU backend: the local spec must resolve
        # to the nominal cpu row, never to unknown.
        assert chipspec.local_spec().measurement == "cpu"

    def test_unknown_kind_is_an_error_not_a_default(self):
        from ray_tpu.observability import chipspec

        with pytest.raises(chipspec.UnknownChipError, match="Gaudi 3"):
            chipspec.lookup("Gaudi 3")
        # "No kind to name" (no device, a mixed mesh) is not a guess:
        # it resolves to the peak-less UNKNOWN row.
        spec = chipspec.lookup("unknown")
        assert spec is chipspec.UNKNOWN and not spec.known
        assert spec.peak_flops is None
        assert spec.peak_hbm_bytes_per_s is None
        assert chipspec.lookup(None) is chipspec.UNKNOWN
        assert chipspec.lookup("") is chipspec.UNKNOWN


class _FakeDev:
    def __init__(self, platform, kind):
        self.platform = platform
        self.device_kind = kind


class TestDeviceInventory:
    def test_v5e_fleet(self):
        from ray_tpu.parallel.mesh import device_inventory

        inv = device_inventory([_FakeDev("tpu", "TPU v5 lite")] * 4)
        assert inv["devices"] == 4
        assert inv["platforms"] == ["tpu"]
        assert inv["device_kinds"] == ["TPU v5 lite"]
        assert inv["spec"] == "v5e" and inv["measurement"] == "tpu"
        assert inv["peak_flops"] == pytest.approx(197e12)
        assert inv["peak_hbm_bytes_per_s"] == pytest.approx(819e9)

    def test_cpu_backend(self):
        from ray_tpu.parallel.mesh import device_inventory

        inv = device_inventory()     # tier-1: the real CPU backend
        assert inv["devices"] >= 1
        assert inv["platforms"] == ["cpu"]
        assert inv["spec"] == "cpu" and inv["measurement"] == "cpu"

    def test_unknown_and_heterogeneous_degrade(self):
        from ray_tpu.parallel.mesh import device_inventory

        from ray_tpu.observability import chipspec

        with pytest.raises(chipspec.UnknownChipError):
            device_inventory([_FakeDev("xpu", "Gaudi 3")] * 2)
        # Mixed generations share no roofline: degrade, never average.
        mixed = device_inventory([_FakeDev("tpu", "TPU v4"),
                                  _FakeDev("tpu", "TPU v5e")])
        assert mixed["spec"] == "unknown"
        assert mixed["device_kinds"] == ["TPU v4", "TPU v5e"]
        assert mixed["peak_flops"] is None


# ------------------------------------------------------------ capture tier

@pytest.fixture
def registry():
    from ray_tpu.observability import xla

    xla.flush_captures()             # strand no straggler in the reg
    reg = xla.program_registry()
    reg.clear()
    yield reg
    xla.flush_captures()
    reg.clear()


def _flush():
    """Captures compile on a background worker: tests synchronize on
    the queue before asserting registry/GCS state."""
    from ray_tpu.observability import xla

    assert xla.flush_captures()


def _matmul_tracked(name, **kw):
    from ray_tpu.observability.jit import tracked_jit

    return tracked_jit(lambda a, b: a @ b, name=name, trace_budget=0,
                       **kw)


class TestCostCapture:
    def test_compile_captures_cost_and_memory(self, registry):
        import jax.numpy as jnp

        from ray_tpu.observability.jit import _arg_signature

        n = 64
        f = _matmul_tracked("xla_capture_matmul")
        x = jnp.ones((n, n), jnp.float32)
        np.asarray(f(x, x))
        _flush()
        sig = _arg_signature((x, x), {})
        row = registry.row("xla_capture_matmul", sig)
        assert row is not None
        # XLA's own count for an n x n x n matmul: 2n^3.
        assert row["flops"] == pytest.approx(2 * n ** 3)
        # Two f32 inputs + one output is the floor on traffic/footprint.
        assert row["bytes_accessed"] >= 3 * n * n * 4
        assert row["peak_hbm_bytes"] >= 3 * n * n * 4
        assert row["compile_seconds"] > 0
        assert row["spec"] == "cpu" and row["measurement"] == "cpu"
        # No wall sampled yet: no utilization claim.
        assert row["verdict"] == "unsampled"
        assert row["wall_s"] is None and row["mfu"] is None
        # The baseline is this function's first program.
        base = registry.baseline("xla_capture_matmul")
        assert base["flops"] == pytest.approx(2 * n ** 3)
        assert base["signature"] == sig

    def test_sampled_wall_derives_mfu_mbu_and_roofline(self, registry,
                                                       monkeypatch):
        import jax.numpy as jnp

        monkeypatch.setenv("RAY_TPU_xla_wall_sample_every", "1")
        n = 64
        f = _matmul_tracked("xla_sample_matmul")
        x = jnp.ones((n, n), jnp.float32)
        np.asarray(f(x, x))          # compiles (not sampled)
        _flush()                     # the capture row must exist first
        np.asarray(f(x, x))          # steady state: fenced + sampled
        rows = [r for r in registry.rows()
                if r["fn"] == "xla_sample_matmul"]
        assert len(rows) == 1
        row = rows[0]
        assert row["samples"] >= 1 and row["wall_s"] > 0
        # The derivation is exact arithmetic over the cpu spec
        # (100e9 FLOP/s, 100e9 B/s) — ratios prove plumbing on CPU.
        assert row["achieved_flops_per_s"] == pytest.approx(
            row["flops"] / row["wall_s"])
        assert row["mfu"] == pytest.approx(
            row["achieved_flops_per_s"] / 100e9)
        assert row["mbu"] == pytest.approx(
            row["achieved_bytes_per_s"] / 100e9)
        ideal = max(row["flops"] / 100e9, row["bytes_accessed"] / 100e9)
        assert row["lost_roofline_s_per_call"] == pytest.approx(
            max(row["wall_s"] - ideal, 0.0))
        assert row["lost_roofline_s_total"] == pytest.approx(
            row["lost_roofline_s_per_call"] * row["calls"])
        assert row["verdict"] in ("compute-bound", "memory-bound")
        # The sampled wall seeded the baseline for the wall sentinel.
        assert registry.baseline("xla_sample_matmul")["wall_s"] \
            == pytest.approx(row["wall_s"])

    def test_sampling_off_keeps_fence_off_hot_path(self, registry,
                                                   monkeypatch):
        import jax
        import jax.numpy as jnp

        monkeypatch.setenv("RAY_TPU_xla_wall_sample_every", "0")
        fences = {"n": 0}
        real = jax.block_until_ready

        def counting(tree):
            fences["n"] += 1
            return real(tree)

        monkeypatch.setattr(jax, "block_until_ready", counting)
        f = _matmul_tracked("xla_unfenced_matmul")
        x = jnp.ones((16, 16), jnp.float32)
        for _ in range(10):
            f(x, x)
        _flush()
        assert fences["n"] == 0
        rows = [r for r in registry.rows()
                if r["fn"] == "xla_unfenced_matmul"]
        # The compile capture still happened; walls never did.
        assert len(rows) == 1
        assert rows[0]["samples"] == 0 and rows[0]["wall_s"] is None

    def test_aot_surface_never_inflates_trace_counters(self, registry):
        import jax.numpy as jnp

        f = _matmul_tracked("xla_aot_matmul")
        x = jnp.ones((8, 8), jnp.float32)
        # eval_shape goes through the RAW function: no probe, no trace.
        shape = f.eval_shape(x, x)
        assert shape.shape == (8, 8)
        assert f.traces == 0
        np.asarray(f(x, x))
        _flush()
        assert f.traces == 1
        # The attribution hook already built (and cached) the AOT
        # artifact for this signature: compiled() hands back the SAME
        # object without re-lowering or inflating the counters.
        c1 = f.compiled(x, x)
        assert c1 is not None and f.traces == 1
        assert f.compiled(x, x) is c1
        assert [r["fn"] for r in registry.rows()] == ["xla_aot_matmul"]
        # clear_cache drops both caches: next call re-traces (and
        # re-counts), and compiled() re-lowers a fresh artifact.
        f.clear_cache()
        np.asarray(f(x, x))
        _flush()
        assert f.traces == 2
        assert f.compiled(x, x) is not c1
        # compiled() on a never-called wrapper lowers under the
        # suppression flag: speculative AOT queries stay invisible to
        # the user-facing trace counters.
        g = _matmul_tracked("xla_aot_precompiled")
        assert g.compiled(x, x) is not None
        assert g.traces == 0


# ----------------------------------------------------------- sentinel tier

class _FakeMem:
    def __init__(self, arg=1024, out=512, temp=256, alias=0):
        self.argument_size_in_bytes = arg
        self.output_size_in_bytes = out
        self.temp_size_in_bytes = temp
        self.alias_size_in_bytes = alias


class _FakeCompiled:
    """Just enough of a jax Compiled to drive record_compile."""

    def __init__(self, flops, bytes_accessed=1e5, mem=None):
        self._cost = {"flops": float(flops),
                      "bytes accessed": float(bytes_accessed),
                      "transcendentals": 0.0}
        self._mem = mem or _FakeMem()

    def cost_analysis(self):
        return [self._cost]          # the CPU-backend list shape

    def memory_analysis(self):
        return self._mem


@pytest.fixture
def sentinel(registry, monkeypatch):
    from ray_tpu.observability import xla

    fired = []
    monkeypatch.setattr(
        xla, "_emit_regression",
        lambda fn, row, dim, ratio, base, cur: fired.append(
            {"fn": fn, "dim": dim, "ratio": ratio, "base": base,
             "cur": cur}))
    return registry, fired


class TestRegressionSentinel:
    def test_recompile_drift_fires_once_per_episode(self, sentinel):
        reg, fired = sentinel
        reg.record_compile("drift_fn", "sigA", _FakeCompiled(1000), 0.1)
        assert fired == []           # the baseline itself never fires
        reg.record_compile("drift_fn", "sigB", _FakeCompiled(8000), 0.1)
        assert len(fired) == 1
        assert fired[0]["dim"] == "flops"
        assert fired[0]["ratio"] == pytest.approx(8.0)
        assert fired[0]["base"] == pytest.approx(1000.0)
        # Still drifted: the episode already fired, stay silent.
        reg.record_compile("drift_fn", "sigC", _FakeCompiled(16000), 0.1)
        assert len(fired) == 1
        # Back within the ratio: the dimension re-arms...
        reg.record_compile("drift_fn", "sigD", _FakeCompiled(1100), 0.1)
        assert len(fired) == 1
        # ...and a fresh drift is a NEW episode.
        reg.record_compile("drift_fn", "sigE", _FakeCompiled(9000), 0.1)
        assert len(fired) == 2

    def test_dimensions_fire_independently(self, sentinel):
        reg, fired = sentinel
        reg.record_compile("mem_fn", "sigA", _FakeCompiled(1000), 0.1)
        # Same flops, 10x the footprint: only peak_hbm_bytes drifts.
        reg.record_compile(
            "mem_fn", "sigB",
            _FakeCompiled(1000, mem=_FakeMem(arg=10240, out=5120,
                                             temp=2560)), 0.1)
        assert [f["dim"] for f in fired] == ["peak_hbm_bytes"]
        assert fired[0]["ratio"] == pytest.approx(10.0)

    def test_wall_drift_fires_once(self, sentinel):
        reg, fired = sentinel
        reg.record_compile("wall_fn", "sig", _FakeCompiled(1000), 0.1)
        reg.record_sample("wall_fn", "sig", 0.01)   # seeds the baseline
        assert fired == []
        for _ in range(6):                          # EWMA climbs past 1.5x
            reg.record_sample("wall_fn", "sig", 0.1)
        assert len(fired) == 1
        assert fired[0]["fn"] == "wall_fn"
        assert fired[0]["dim"] == "wall_s"

    def test_ratio_zero_disables(self, sentinel, monkeypatch):
        monkeypatch.setenv("RAY_TPU_xla_regression_ratio", "0")
        reg, fired = sentinel
        reg.record_compile("off_fn", "sigA", _FakeCompiled(1000), 0.1)
        reg.record_compile("off_fn", "sigB", _FakeCompiled(99000), 0.1)
        assert fired == []

    def test_sample_of_unknown_program_is_noop(self, registry):
        assert registry.record_sample("ghost", "sig", 0.5) is None


# ------------------------------------------------------------ cluster tier

@pytest.fixture(scope="module")
def xla_cluster():
    import ray_tpu

    # Small ring so the bound is observable; sample every call so the
    # engine's steady-state programs all derive utilization. Config
    # resolution is env-first, so the GCS and every TrackedJit built
    # after this point pick these up live.
    os.environ["RAY_TPU_xla_programs_buffer_size"] = "32"
    os.environ["RAY_TPU_xla_wall_sample_every"] = "1"
    info = ray_tpu.init(num_cpus=4, num_tpus=0,
                        object_store_memory=128 * 1024 * 1024,
                        include_dashboard=True,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_xla_programs_buffer_size", None)
    os.environ.pop("RAY_TPU_xla_wall_sample_every", None)


def _get(url: str):
    with urllib.request.urlopen(url, timeout=15) as resp:
        return resp.status, resp.read()


def _xrow(**kw):
    row = {"fn": "synth_fn", "signature": "(float32[8,8])",
           "flops": 1e6, "bytes_accessed": 3e5, "transcendentals": 0.0,
           "arg_bytes": 2e5, "out_bytes": 1e5, "temp_bytes": 0.0,
           "alias_bytes": 0.0, "peak_hbm_bytes": 3e5,
           "compile_seconds": 0.2, "calls": 10, "samples": 2,
           "wall_s": 0.01, "achieved_flops_per_s": 1e8,
           "achieved_bytes_per_s": 3e7, "mfu": 0.001, "mbu": 0.0003,
           "exposed_comm_fraction": 0.0, "verdict": "compute-bound",
           "lost_roofline_s_per_call": 0.005,
           "lost_roofline_s_total": 0.05, "spec": "cpu",
           "measurement": "cpu", "pid": 4242}
    row.update(kw)
    return row


def test_ring_list_and_summary(xla_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    for i in range(3):
        gcs.call("report_xla_programs", row=_xrow(
            fn="synth_a", signature=f"(float32[{8 << i},8])",
            flops=1e6 * (i + 1)))
    gcs.call("report_xla_programs", row=_xrow(
        fn="synth_b", verdict="memory-bound",
        node_id=b"\x5b\x7e\xc0\x14"))
    gcs.call("report_xla_programs", row=_xrow(
        fn="synth_hog", flops=1e9, calls=100,
        lost_roofline_s_total=9.0))

    rows = state.list_xla_programs(fn="synth_a")
    assert len(rows) == 3 and all(r["fn"] == "synth_a" for r in rows)
    assert rows[-1]["signature"] == "(float32[32,8])"   # newest-last
    assert len(state.list_xla_programs(fn="synth_a", limit=2)) == 2
    only = state.list_xla_programs(verdict="memory-bound")
    assert only and all(r["verdict"] == "memory-bound" for r in only)
    # Raw-bytes node ids land as hex — these rows feed JSON surfaces.
    assert only[-1]["node_id"] == "5b7ec014"

    summary = state.xla_summary()
    assert summary["programs"] >= 5
    assert summary["rows_recorded"] >= 5
    # Cumulative FLOPs rank: the hog's 1e9 x 100 calls dwarfs the rest.
    assert summary["top_by_flops"][0]["fn"] == "synth_hog"
    assert summary["top_by_headroom"][0]["fn"] == "synth_hog"
    assert summary["verdicts"]["compute-bound"] >= 4
    assert summary["verdicts"]["memory-bound"] >= 1
    # All-cpu measurements mark the ratios as plumbing proof.
    assert summary["measurements"]["cpu"] >= 5
    assert summary["total_flops"] >= 1e9 * 100
    assert summary["total_peak_hbm_bytes"] >= 5 * 3e5
    assert summary["lost_roofline_s_total"] >= 9.0


def test_ring_is_bounded(xla_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    before = state.xla_summary()["rows_recorded"]
    for i in range(100):
        gcs.call("report_xla_programs",
                 row=_xrow(fn="bulk", signature=f"(s{i})"))
    summary = state.xla_summary()
    assert summary["rows_recorded"] == before + 100
    assert summary["rows_in_buffer"] <= 32
    # The latest-view is bounded by the same knob as the ring.
    assert summary["programs"] <= 32


def test_malformed_row_dropped_not_fatal(xla_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    before = state.xla_summary()["rows_recorded"]
    assert gcs.call("report_xla_programs", row={"fn": "evil"})
    assert gcs.call("report_xla_programs",
                    row=_xrow(fn="evil2", flops="bogus"))
    assert state.xla_summary()["rows_recorded"] == before
    # The GCS is still alive and ingesting.
    gcs.call("report_xla_programs", row=_xrow(fn="after"))
    assert state.xla_summary()["rows_recorded"] == before + 1


def test_engine_bucket_programs_attributed(xla_cluster):
    """The acceptance run: a real (tiny) engine's programs all land in
    the fleet summary with nonzero FLOPs/HBM and — once sampled —
    MFU/MBU + a roofline verdict, every row CPU-tagged in tier-1."""
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request
    from ray_tpu.util import state

    config = LlamaConfig.tiny()
    params = init_params(config, jax.random.key(0))
    engine = LLMEngine(params, config, EngineConfig(
        num_slots=2, max_seq_len=32, prefill_buckets=(8,),
        kv_block_size=8))
    rng = np.random.RandomState(3)

    def _wave():
        for _ in range(3):
            engine.submit(Request(
                prompt=rng.randint(0, config.vocab_size, 5).tolist(),
                max_tokens=4))
        engine.drain()

    _wave()          # compiles the bucket programs (captures queued)
    _flush()         # every program row is in the registry now
    _wave()          # steady state: every call samples a wall

    for fn in ("llm_engine_tick", "llm_engine_insert"):
        rows = state.list_xla_programs(fn=fn)
        assert rows, f"no program rows for {fn}"
        for r in rows:
            assert r["flops"] > 0
            assert r["peak_hbm_bytes"] > 0
            assert r["measurement"] == "cpu"
        # sample_every=1: every steady-state call after the compile
        # sampled a wall, so the newest row carries utilization.
        last = rows[-1]
        assert last["samples"] > 0 and last["wall_s"] > 0
        assert last["mfu"] > 0 and last["mbu"] > 0
        assert last["verdict"] in ("compute-bound", "memory-bound",
                                   "comm-bound")


def test_shape_drift_emits_one_perf_regression(xla_cluster):
    """A recompile whose FLOPs drift past xla_regression_ratio emits
    exactly ONE typed PERF_REGRESSION naming the program and the
    drifted dimension — and only that dimension (the k=2 -> k=8 loop
    quadruples FLOPs while peak HBM grows just 1.33x, inside the
    ratio)."""
    import jax.numpy as jnp

    from ray_tpu.observability.jit import tracked_jit
    from ray_tpu.util import state

    def body(a, k):
        for _ in range(k):
            a = a @ a
        return a

    f = tracked_jit(body, name="drift_probe", static_argnums=(1,),
                    trace_budget=0)
    x = jnp.ones((64, 64), jnp.float32)
    np.asarray(f(x, 2))              # baseline program
    np.asarray(f(x, 8))              # recompile: 4x the FLOPs
    _flush()                         # captures land in compile order

    def _events():
        return [e for e in
                state.list_cluster_events(event_type="PERF_REGRESSION")
                if e.get("fn") == "drift_probe"]

    events = _events()
    assert len(events) == 1
    ev = events[0]
    assert ev["severity"] == "WARNING"
    assert ev["dimension"] == "flops"
    assert ev["ratio"] == pytest.approx(4.0)
    assert "drift_probe" in ev["message"]
    assert "flops" in ev["message"]
    assert ev["measurement"] == "cpu"
    # Still drifted on the next recompile: same episode, no new event.
    np.asarray(f(x, 16))
    _flush()
    assert len(_events()) == 1


def test_api_programs_contract(xla_cluster):
    from ray_tpu import _local_node
    from ray_tpu._private.worker import global_worker

    global_worker().gcs.call("report_xla_programs",
                             row=_xrow(fn="dash_fn"))
    base = _local_node.dashboard_url

    status, body = _get(base + "/api/programs")
    assert status == 200
    payload = json.loads(body)
    assert set(payload) == {"summary", "programs", "metrics"}
    assert payload["summary"]["programs"] >= 1
    assert payload["programs"]

    status, body = _get(base + "/api/programs?fn=dash_fn&limit=1")
    payload = json.loads(body)
    assert len(payload["programs"]) == 1
    assert payload["programs"][0]["fn"] == "dash_fn"

    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(base + "/api/programs?limit=bogus")
    assert ei.value.code == 400


def test_xla_metrics_exported(xla_cluster):
    import jax.numpy as jnp

    from ray_tpu._private.worker import global_worker
    from ray_tpu.observability.jit import tracked_jit
    from ray_tpu.util import metrics

    f = tracked_jit(lambda a, b: a @ b, name="xla_metric_probe",
                    trace_budget=0)
    x = jnp.ones((16, 16), jnp.float32)
    np.asarray(f(x, x))              # compile: flops/bytes gauges
    _flush()
    np.asarray(f(x, x))              # sample: mfu/mbu + wall histogram
    assert metrics.flush()
    text = global_worker().gcs.call("metrics_text")
    assert "rtpu_xla_program_flops" in text
    assert 'fn="xla_metric_probe"' in text
    assert "rtpu_xla_program_bytes_hbm" in text
    assert "rtpu_xla_program_mfu" in text
    assert "rtpu_xla_program_mbu" in text
    assert "rtpu_xla_program_wall_seconds_bucket" in text
