"""Per-request cost accounting & SLO attainment for the serving tier
(observability/accounting.py + the GCS accounting ring + the dashboard
surface).

Unit tier: the RequestMeter's block-seconds integration (monotone
across preempt/resume, idempotent finalize, migration absorb = one
ledger row), the bounded TenantLedger fold, SLO target parsing and the
SLOTracker's multi-window burn state machine under a fake clock.
Engine tier: real tiny-model engines — the reconciliation self-check
(meter token sums == rtpu_serve_tokens_total delta), row shape at
finish, the cancelled-in-queue path, and the instrumentation knob.
Cluster tier: synthetic cost rows through the real
report_serve_accounting RPC drive the bounded ring, the tenant rollup,
the SLO_BURN event, util.state.serve_accounting() (incl. the
trace-id-keyed row — the x-trace-id acceptance path), GET
/api/accounting, and the GCS-native SLO gauge exposition.
"""

import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest


# --------------------------------------------------------------- unit tier

def _meter(**kw):
    from ray_tpu.observability.accounting import RequestMeter

    t = {"now": 0.0}
    return RequestMeter(clock=lambda: t["now"], **kw), t


class TestRequestMeter:
    def test_block_seconds_integration(self):
        m, t = _meter(tenant="acme")
        m.blocks_acquired(4)
        t["now"] = 2.5
        row = m.finalize("length", tokens_out=8)
        assert row["block_seconds"] == pytest.approx(10.0)
        assert row["tenant"] == "acme"
        assert row["tokens_out"] == 8 and row["finished"]

    def test_preempt_resume_stays_monotone(self):
        m, t = _meter()
        m.blocks_acquired(2)          # t=0
        t["now"] = 1.0
        m.blocks_released(2)          # preempt: 2 blk x 1s
        t["now"] = 2.0
        m.blocks_acquired(2)          # resume: the gap is NOT billed
        t["now"] = 3.0
        row = m.finalize("length", tokens_out=4)
        assert row["block_seconds"] == pytest.approx(4.0)

    def test_double_release_never_subtracts(self):
        m, t = _meter()
        m.blocks_acquired(1)
        t["now"] = 1.0
        m.blocks_released(1)
        t["now"] = 2.0
        m.blocks_released(5)          # spurious: clamps at zero held
        assert m.blocks_held == 0
        t["now"] = 3.0
        row = m.finalize("length", tokens_out=1)
        assert row["block_seconds"] == pytest.approx(1.0)

    def test_finalize_is_idempotent(self):
        m, t = _meter()
        m.blocks_acquired(2)
        t["now"] = 1.0
        first = m.finalize("length", tokens_out=3, ttft_s=0.1)
        t["now"] = 50.0               # a second finalize must not re-bill
        again = m.finalize("cancelled", tokens_out=99)
        assert again["block_seconds"] == first["block_seconds"]
        assert again["tokens_out"] == 3
        assert again["finish_reason"] == "length"

    def test_unknown_chip_phase_rejected(self):
        m, _ = _meter()
        with pytest.raises(ValueError):
            m.note_chip("mystery", 0.1)

    def test_absorb_makes_one_row(self):
        # Disagg hand-off: the prefill side's snapshot folds into the
        # decode meter so the migrated request lands on ONE row, keyed
        # by the originating trace id.
        pre, tp = _meter(tenant="acme", trace_id="tr-1")
        pre.note_prefill(32, 8)
        pre.note_chip("prefill", 0.5)
        pre.blocks_acquired(4)
        tp["now"] = 1.0
        pre.ttft_s = 0.07             # first token sampled prefill-side
        snap = pre.finalize("prefill", tokens_out=1)

        dec, td = _meter(tenant="default", trace_id="tr-decode")
        dec.absorb(snap)
        dec.note_chip("decode", 0.25)
        td["now"] = 2.0
        row = dec.finalize("length", tokens_out=16, ttft_s=9.9)
        assert row["trace_id"] == "tr-1"
        assert row["tenant"] == "acme"
        assert row["migrations"] == 1
        assert row["prefill_tokens_computed"] == 32
        assert row["prefill_tokens_avoided"] == 8
        assert row["chip_seconds"]["prefill"] == pytest.approx(0.5)
        assert row["chip_seconds"]["decode"] == pytest.approx(0.25)
        assert row["chip_seconds_total"] == pytest.approx(0.75)
        assert row["block_seconds"] == pytest.approx(4.0)
        # The absorbed (prefill-side) TTFT wins; tokens are NOT
        # absorbed (the decode handle is seeded with them already).
        assert row["ttft_s"] == pytest.approx(0.07)
        assert row["tokens_out"] == 16

    def test_queue_wait_and_spec_ratio(self):
        m, _ = _meter()
        m.note_queue_wait(0.2)
        m.note_queue_wait(0.3)
        m.note_spec(9, 6)
        row = m.finalize("length", tokens_out=7)
        assert row["queue_wait_s"] == pytest.approx(0.5)
        assert row["spec_accept_ratio"] == pytest.approx(6 / 9)


class TestTenantLedger:
    def _row(self, tenant, chip=1.0, tokens=10):
        return {"tenant": tenant, "tokens_out": tokens,
                "block_seconds": 2.0, "chip_seconds_total": chip,
                "prefill_tokens_computed": 8,
                "prefill_tokens_avoided": 2, "queue_wait_s": 0.1,
                "trace_id": f"tr-{tenant}", "lane": "interactive"}

    def test_overflow_folds_into_other(self):
        from ray_tpu.observability.accounting import (OTHER_TENANT,
                                                      TenantLedger)

        led = TenantLedger(max_tenants=2)
        assert led.fold(self._row("a")) == "a"
        assert led.fold(self._row("b")) == "b"
        assert led.fold(self._row("c")) == OTHER_TENANT
        assert led.fold(self._row("d")) == OTHER_TENANT
        assert led.fold(self._row("a")) == "a"   # existing key still books
        snap = led.snapshot()
        assert set(snap) == {"a", "b", OTHER_TENANT}
        assert snap[OTHER_TENANT]["requests"] == 2
        assert snap["a"]["requests"] == 2
        assert snap["a"]["tokens"] == pytest.approx(20.0)

    def test_top_sorted_by_chip_seconds(self):
        from ray_tpu.observability.accounting import TenantLedger

        led = TenantLedger(max_tenants=8)
        led.fold(self._row("cheap", chip=0.1))
        led.fold(self._row("hungry", chip=5.0))
        led.fold(self._row("mid", chip=1.0))
        top = led.top(2)
        assert [t["tenant"] for t in top] == ["hungry", "mid"]
        assert top[0]["last_trace_id"] == "tr-hungry"

    def test_comma_in_tenant_is_cleaned(self):
        from ray_tpu.observability.accounting import TenantLedger

        led = TenantLedger(max_tenants=4)
        assert led.fold(self._row("a,b")) == "a_b"


class TestSLOTargets:
    def test_parse_lane_spec(self):
        from ray_tpu.observability.accounting import _parse_lane_targets

        got = _parse_lane_targets("interactive=500, *=2000")
        assert got == {"interactive": 0.5, "*": 2.0}
        assert _parse_lane_targets("250") == {"*": 0.25}
        assert _parse_lane_targets("bogus=x,batch=1000") == {"batch": 1.0}

    def test_config_defaults_resolve_both_lanes(self):
        from ray_tpu.observability.accounting import slo_targets

        got = slo_targets()
        assert got["interactive"] == (pytest.approx(0.5),
                                      pytest.approx(0.2))
        assert got["batch"] == (pytest.approx(2.0), pytest.approx(1.0))


def _tracker(**kw):
    from ray_tpu.observability.accounting import SLOTracker

    t = {"now": 0.0}
    kw.setdefault("targets", {"interactive": (0.1, 0.05)})
    kw.setdefault("objective", 0.99)
    kw.setdefault("fast_window_s", 60.0)
    kw.setdefault("slow_window_s", 3600.0)
    kw.setdefault("burn_threshold", 10.0)
    kw.setdefault("min_samples", 3)
    return SLOTracker(clock=lambda: t["now"], **kw), t


class TestSLOTracker:
    def test_good_traffic_never_fires(self):
        tr, t = _tracker()
        for i in range(20):
            t["now"] = float(i)
            assert tr.observe("interactive", 0.01, 0.001) is None
        assert not tr.burning("interactive")
        assert tr.attainment("interactive") == pytest.approx(1.0)
        assert tr.burn_rate("interactive") == pytest.approx(0.0)

    def test_fires_once_per_episode(self):
        tr, t = _tracker()
        flags = []
        for i in range(6):
            t["now"] = float(i)
            f = tr.observe("interactive", 10.0, 0.001)
            if f:
                flags.append(f)
        # min_samples=3 delays the first verdict; once burning, no
        # repeat flag until the episode clears.
        assert len(flags) == 1
        flag = flags[0]
        assert flag["lane"] == "interactive"
        assert flag["fast_burn"] >= 10.0
        assert flag["slow_burn"] >= 1.0
        assert flag["ttft_target_s"] == pytest.approx(0.1)
        assert tr.burning("interactive")

    def test_slow_window_gates_one_blip(self):
        # A long healthy history: the fast window can scream (3/3 bad)
        # while the slow window is still inside budget — no flag.
        tr, t = _tracker()
        for i in range(500):
            t["now"] = i * 5.0
            tr.observe("interactive", 0.01, 0.001)
        base = 500 * 5.0 + 120.0      # good samples age out of fast
        for j in range(3):
            t["now"] = base + j
            assert tr.observe("interactive", 10.0, 0.001) is None
        assert not tr.burning("interactive")

    def test_clears_and_refires(self):
        tr, t = _tracker()
        fired = [tr.observe("interactive", 10.0, 0.001,
                            now=float(i)) for i in range(4)]
        assert any(fired)
        # Bad samples age out of the fast window -> burn < threshold/2
        # clears the episode...
        t["now"] = 200.0
        assert tr.observe("interactive", 0.01, 0.001) is None
        assert not tr.burning("interactive")
        # ...and a fresh regression fires a NEW flag.
        flags = [tr.observe("interactive", 10.0, 0.001,
                            now=201.0 + i) for i in range(4)]
        assert any(flags)

    def test_snapshot_shape(self):
        tr, t = _tracker()
        t["now"] = 1.0
        tr.observe("interactive", 0.01, 0.001)
        snap = tr.snapshot()
        ent = snap["interactive"]
        assert ent["ttft_target_s"] == pytest.approx(0.1)
        assert ent["objective"] == pytest.approx(0.99)
        assert ent["burning"] is False
        assert ent["attainment_fast"] == pytest.approx(1.0)
        assert ent["burn_slow"] == pytest.approx(0.0)


# -------------------------------------------------------------- engine tier

_CACHE = {}


def _model():
    if "model" not in _CACHE:
        import jax

        from ray_tpu.models.llama import LlamaConfig, init_params

        config = LlamaConfig.tiny()
        _CACHE["model"] = (config, init_params(config, jax.random.key(0)))
    return _CACHE["model"]


def _paged_engine():
    """One shared engine; drained between tests to keep compile count
    flat."""
    if "engine" not in _CACHE:
        from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

        config, params = _model()
        _CACHE["engine"] = LLMEngine(params, config, EngineConfig(
            num_slots=2, max_seq_len=64, prefill_buckets=(8, 16),
            kv_block_size=8))
    return _CACHE["engine"]


def _prompts(n, lo=3, hi=8):
    config, _ = _model()
    rng = np.random.RandomState(7)
    return [rng.randint(0, config.vocab_size,
                        rng.randint(lo, hi)).tolist() for _ in range(n)]


class TestEngineAccounting:
    def test_reconciliation_and_row_shape(self):
        from ray_tpu.observability.accounting import TokenReconciler
        from ray_tpu.serve.llm.engine import Request

        engine = _paged_engine()
        with TokenReconciler() as rec:
            handles = [
                engine.submit(Request(prompt=p, max_tokens=3,
                                      tenant=ten))
                for p, ten in zip(_prompts(3), ("acme", "acme", "bob"))]
            engine.drain()
        # The self-check: windowed meter token sums equal the
        # rtpu_serve_tokens_total counter delta exactly.
        assert rec.holds(), rec.detail()
        assert rec.meter_sum == pytest.approx(9.0)

        rows = {r["tenant"]: r for r in rec._rows}
        assert set(rows) == {"acme", "bob"}
        for h in handles:
            assert h.meter is not None and h.meter.finished
            snap = h.meter.snapshot()
            assert snap["tokens_out"] == len(h.tokens) == 3
            assert snap["chip_seconds_total"] > 0
            assert snap["chip_seconds"]["prefill"] > 0
            assert snap["chip_seconds"]["decode"] > 0
            assert snap["block_seconds"] > 0
            assert snap["queue_wait_s"] is not None
            assert snap["prefill_tokens_computed"] > 0
            assert snap["finish_reason"] == "length"
            assert snap["model"].startswith("llama_")
            # All blocks were handed back at finish.
            assert h.meter.blocks_held == 0

    def test_cancelled_in_queue_row(self):
        from ray_tpu.observability.accounting import (register_row_hook,
                                                      unregister_row_hook)
        from ray_tpu.serve.llm.engine import Request

        engine = _paged_engine()
        rows = []
        register_row_hook(rows.append)
        try:
            # No step() between submits: everything is queued, so the
            # cancel is deterministically the queued-cancel path.
            handles = [engine.submit(Request(prompt=p, max_tokens=3,
                                             tenant="flaky"))
                       for p in _prompts(3)]
            assert engine.cancel(handles[-1])
            engine.drain()
        finally:
            unregister_row_hook(rows.append)
        cancelled = [r for r in rows if r["finish_reason"] == "cancelled"]
        assert len(cancelled) == 1
        row = cancelled[0]
        assert row["tokens_out"] == 0
        assert row["block_seconds"] == pytest.approx(0.0)
        # Never admitted: no first token, so the row is not an SLO
        # sample (the GCS skips ttft-less rows).
        assert row["ttft_s"] is None

    def test_knob_off_attaches_no_meter(self):
        from ray_tpu.serve.llm.engine import (EngineConfig, LLMEngine,
                                              Request)

        config, params = _model()
        os.environ["RAY_TPU_serve_accounting_instrumentation"] = "0"
        try:
            engine = LLMEngine(params, config, EngineConfig(
                num_slots=1, max_seq_len=32, prefill_buckets=(8,),
                kv_block_size=8))
            h = engine.submit(Request(prompt=[1, 2, 3], max_tokens=2))
            engine.drain()
        finally:
            os.environ.pop(
                "RAY_TPU_serve_accounting_instrumentation", None)
        assert h.finish_reason == "length"
        assert h.meter is None


# ------------------------------------------------------------ cluster tier

@pytest.fixture(scope="module")
def acct_cluster():
    import ray_tpu

    # Small ring so the bound is observable in-test; config resolution
    # is env-first, so the GCS picks these up live.
    os.environ["RAY_TPU_serve_accounting_buffer_size"] = "64"
    info = ray_tpu.init(num_cpus=4, num_tpus=0,
                        object_store_memory=128 * 1024 * 1024,
                        include_dashboard=True,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()
    os.environ.pop("RAY_TPU_serve_accounting_buffer_size", None)


def _get(url: str):
    with urllib.request.urlopen(url, timeout=15) as resp:
        return resp.status, resp.read()


def _row(**kw):
    row = {"tenant": "default", "model": "llama_d64_l2",
           "lane": "interactive", "trace_id": None, "request_id": 1,
           "queue_wait_s": 0.001, "prefill_tokens_computed": 8,
           "prefill_tokens_avoided": 0, "tokens_out": 16,
           "spec_proposed": 0, "spec_accepted": 0, "block_seconds": 0.5,
           "chip_seconds": {"prefill": 0.01, "decode": 0.04},
           "chip_seconds_total": 0.05, "migrations": 0, "ttft_s": 0.01,
           "tpot_s": 0.001, "e2e_s": 0.05, "finish_reason": "length",
           "finished": True}
    row.update(kw)
    return row


def test_ring_list_summary_and_trace_key(acct_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    for i in range(6):
        gcs.call("report_serve_accounting", row=_row(
            tenant="acme", trace_id=f"tr-acct-{i}", tokens_out=32,
            chip_seconds_total=0.5))
    gcs.call("report_serve_accounting", row=_row(
        tenant="bob", trace_id="tr-bob-0", chip_seconds_total=0.1,
        node_id=b"\x5b\x7e\xc0\x14"))

    rows = state.list_serve_accounting(tenant="acme")
    assert rows and all(r["tenant"] == "acme" for r in rows)
    assert rows[-1]["trace_id"] == "tr-acct-5"
    assert len(state.list_serve_accounting(tenant="acme", limit=2)) == 2
    only = state.list_serve_accounting(trace_id="tr-bob-0")
    assert len(only) == 1 and only[0]["tenant"] == "bob"
    # Raw-bytes node ids (worker.node_id) must land as hex — these rows
    # feed JSON surfaces (/api/accounting).
    assert only[0]["node_id"] == "5b7ec014"

    summary = state.serve_accounting()
    by_tenant = {t["tenant"]: t for t in summary["tenants"]}
    assert by_tenant["acme"]["requests"] >= 6
    assert by_tenant["acme"]["tokens"] >= 6 * 32
    # Top-N orders by chip-seconds: acme out-eats bob.
    assert summary["tenants"][0]["tenant"] == "acme"
    assert summary["rows_recorded"] >= 7
    assert "interactive" in summary["slo"]

    # The acceptance path: a request's cost keyed by its x-trace-id.
    keyed = state.serve_accounting(trace_id="tr-acct-3")
    assert keyed["request"] is not None
    assert keyed["request"]["tenant"] == "acme"
    assert keyed["request"]["tokens_out"] == 32
    assert state.serve_accounting(trace_id="tr-nope")["request"] is None


def test_ring_is_bounded(acct_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    before = state.serve_accounting()["rows_recorded"]
    for i in range(100):
        gcs.call("report_serve_accounting",
                 row=_row(tenant=f"bulk-{i % 4}", request_id=i))
    summary = state.serve_accounting()
    assert summary["rows_recorded"] == before + 100
    assert summary["rows_in_buffer"] <= 64


def test_malformed_row_dropped_not_fatal(acct_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    before = state.serve_accounting()["rows_recorded"]
    assert gcs.call("report_serve_accounting",
                    row={"tenant": "evil", "tokens_out": "bogus"})
    after = state.serve_accounting()
    assert after["rows_recorded"] == before
    # The GCS is still alive and ingesting.
    gcs.call("report_serve_accounting", row=_row())
    assert state.serve_accounting()["rows_recorded"] == before + 1


def test_slo_burn_event_fires(acct_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.util import state

    gcs = global_worker().gcs
    # An injected slow tenant on the batch lane: TTFT 10s against the
    # 2s default target. Defaults: objective .99, threshold 10x, min 3
    # samples -> the third all-bad sample trips both windows.
    for i in range(5):
        gcs.call("report_serve_accounting", row=_row(
            tenant="hog", lane="batch", trace_id=f"tr-hog-{i}",
            ttft_s=10.0, tpot_s=5.0))

    events = state.list_cluster_events(event_type="SLO_BURN")
    ev = next(e for e in events if e.get("lane") == "batch")
    assert ev["severity"] == "WARNING"
    assert ev["fast_burn"] >= 10.0
    assert ev["slow_burn"] >= 1.0
    assert ev["ttft_target_s"] == pytest.approx(2.0)
    assert "batch" in ev["message"]

    # Burning state is visible in the accounting summary...
    slo = state.serve_accounting()["slo"]["batch"]
    assert slo["burning"] is True
    assert slo["attainment_fast"] < 1.0

    # ...and one episode emits exactly one event.
    n = len([e for e in state.list_cluster_events(event_type="SLO_BURN")
             if e.get("lane") == "batch"])
    for i in range(3):
        gcs.call("report_serve_accounting", row=_row(
            tenant="hog", lane="batch", ttft_s=10.0, tpot_s=5.0))
    assert len([e for e in
                state.list_cluster_events(event_type="SLO_BURN")
                if e.get("lane") == "batch"]) == n


def test_api_accounting_and_events_contract(acct_cluster):
    from ray_tpu import _local_node
    from ray_tpu._private.worker import global_worker

    gcs = global_worker().gcs
    gcs.call("report_serve_accounting",
             row=_row(tenant="dash", trace_id="tr-dash-1"))
    base = _local_node.dashboard_url

    status, body = _get(base + "/api/accounting")
    assert status == 200
    payload = json.loads(body)
    assert set(payload) == {"summary", "requests", "metrics"}
    assert payload["summary"]["tenants"]
    assert payload["summary"]["slo"]
    assert payload["requests"]

    status, body = _get(base + "/api/accounting?tenant=dash&limit=1"
                             "&trace_id=tr-dash-1")
    payload = json.loads(body)
    assert len(payload["requests"]) == 1
    assert payload["requests"][0]["tenant"] == "dash"
    assert payload["summary"]["request"]["trace_id"] == "tr-dash-1"

    with pytest.raises(urllib.error.HTTPError) as ei:
        _get(base + "/api/accounting?limit=bogus")
    assert ei.value.code == 400

    # The burn event is visible on the events surface too.
    status, body = _get(base + "/api/events?type=SLO_BURN")
    assert status == 200
    events = json.loads(body)
    assert any(e.get("lane") == "batch" for e in events)


def test_accounting_metrics_exported(acct_cluster):
    from ray_tpu._private.worker import global_worker
    from ray_tpu.observability.accounting import fold_finished
    from ray_tpu.util import metrics

    # Fold a finished row in THIS process: tenant counters + cost
    # histograms land in the local registry and flush to the GCS.
    fold_finished(_row(tenant="m-acct", tokens_out=11,
                       block_seconds=1.5, chip_seconds_total=0.25,
                       trace_id="tr-metrics"))
    assert metrics.flush()
    text = global_worker().gcs.call("metrics_text")
    assert "rtpu_serve_tenant_tokens_total" in text
    assert 'tenant="m-acct"' in text
    assert "rtpu_serve_tenant_chip_seconds_total" in text
    assert "rtpu_serve_request_cost_chip_seconds" in text
    # GCS-native SLO gauges (the tracker lives in the GCS process).
    assert 'rtpu_serve_slo_attainment_ratio{lane="batch"}' in text
    assert 'rtpu_serve_slo_burn_rate{lane="batch",window="fast"}' in text
