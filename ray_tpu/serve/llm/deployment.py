"""LLMDeployment — the continuous-batching engine as a Serve replica.

Requests flow router -> replica -> engine: the replica actor hosts one
`LLMEngine` plus a single scheduler thread driving it; `__call__`
invocations (which Serve runs concurrently up to
``max_ongoing_requests``) just submit into the engine's queue and block
on their handle, so many in-flight HTTP/handle requests share the one
compiled decode program.
"""

from __future__ import annotations

import threading
from typing import Any, Dict, Optional, Sequence


class LLMServer:
    """Deployment callable: owns the engine and its scheduler thread.

    ``model_config`` / ``engine_config`` may be the dataclasses or plain
    kwargs dicts (dicts survive cloudpickle across replicas trivially).
    Weights: ``init_seed`` builds random params in-replica (tests,
    benchmarks); ``params_loader`` — a zero-arg callable returning the
    params pytree — is the production hook (checkpoint load happens in
    the replica process, never on the serialization path).

    ``quantize`` defaults to ``"int8"`` — weight-only int8 decode
    reads half the weight bytes a token (not measured on this chip: the
    benchmark's serving cells run bf16 and use int8 as their control);
    pass ``quantize="bf16"`` to opt out (e.g. for bit-parity against an
    offline bf16 reference). The legacy ``quantize_int8=True`` flag is
    honored as a synonym for ``quantize="int8"``.
    """

    def __init__(self, model_config: Any = None,
                 engine_config: Any = None,
                 init_seed: int = 0,
                 params_loader: Optional[Any] = None,
                 quantize: Optional[str] = None,
                 quantize_int8: bool = False,
                 speculative: Any = None):
        import jax

        from ray_tpu.models.llama import LlamaConfig
        from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

        if model_config is None:
            model_config = LlamaConfig.tiny()
        elif isinstance(model_config, dict):
            model_config = LlamaConfig(**model_config)
        if engine_config is None:
            engine_config = EngineConfig()
        elif isinstance(engine_config, dict):
            engine_config = EngineConfig(**engine_config)

        if quantize is None:
            quantize = "int8"           # serve default (see class doc)
        if quantize not in ("int8", "bf16"):
            raise ValueError(
                f"quantize must be 'int8' or 'bf16', got {quantize!r}")
        self.quantize = quantize
        model = model_config.serving()
        if quantize == "int8" and model.quantize_int8 is None:
            raise ValueError(
                f"{model.name} has no int8 weight-only path: pass "
                f"quantize='bf16'")

        if params_loader is not None:
            params = params_loader()
        else:
            params = model.init_params(model_config,
                                       jax.random.key(init_seed))
        if quantize == "int8":
            params = model.quantize_int8(params)

        # Speculative decoding (disagg/spec.py): ``speculative`` is
        # True (default draft geometry), a dict of draft kwargs
        # ({"draft_seed": .., "draft_config": {..}, "params_loader":
        # zero-arg callable}), or None to decode plainly. Weights load
        # in-replica like the target's.
        draft_params = draft_config = None
        if speculative:
            from ray_tpu.serve.llm.disagg.spec import (
                build_draft, draft_config_for,
            )

            spec = speculative if isinstance(speculative, dict) else {}
            dc = spec.get("draft_config")
            if isinstance(dc, dict):
                dc = LlamaConfig(**dc)
            draft_config = dc or draft_config_for(model_config)
            loader = spec.get("params_loader")
            if loader is not None:
                draft_params = loader()
            else:
                draft_params, draft_config = build_draft(
                    model_config, seed=int(spec.get("draft_seed", 0)),
                    draft_config=draft_config)

        self._engine = LLMEngine(params, model_config, engine_config,
                                 draft_params=draft_params,
                                 draft_config=draft_config)
        self._stop = threading.Event()
        self._thread = threading.Thread(
            target=self._engine.run, args=(self._stop,),
            name="llm-engine-scheduler", daemon=True)
        self._thread.start()

        # Cluster-wide prefix index: this replica's identity in the
        # GCS index (the router learns it from load()), plus the
        # publisher thread pushing hash-chain heads on a fixed period.
        # The publish IS the liveness signal — a dead replica ages out
        # of cache-aware routing at the index TTL.
        import uuid

        self._replica_id = uuid.uuid4().hex[:12]
        if getattr(self._engine, "_prefix", None) is not None:
            threading.Thread(
                target=self._publish_index_loop, daemon=True,
                name="llm-prefix-index-publish").start()

    def __call__(self, request: Dict[str, Any]) -> Dict[str, Any]:
        """request: {"prompt": [token ids], "max_tokens": int,
        "temperature": float, "stop": [token ids]} -> completed tokens
        plus latency detail. Blocks the calling Serve thread; the engine
        thread interleaves all concurrent requests."""
        from ray_tpu.observability import serve_metrics
        from ray_tpu.serve.llm.engine import Request
        from ray_tpu.util.tracing import span

        # Submit INSIDE the span: the engine captures the submitting
        # thread's trace context on the handle, so llm.request and its
        # phases parent under this llm.server_call hop.
        with span("llm.server_call",
                  attrs={"prompt_len": len(request["prompt"])}):
            handle = self._engine.submit(Request(
                prompt=list(request["prompt"]),
                max_tokens=int(request.get("max_tokens", 64)),
                temperature=float(request.get("temperature", 0.0)),
                stop=tuple(request.get("stop", ())),
                slo=str(request.get("slo", "interactive")),
                chunked_prefill=bool(
                    request.get("chunked_prefill", False)),
                tenant=str(request.get("tenant", "default"))))
            try:
                tokens = handle.result(timeout=float(
                    request.get("timeout_s", 300.0)))
            except TimeoutError:
                serve_metrics().request_timeouts.inc()
                raise
        return {
            "tokens": tokens,
            "num_tokens": len(tokens),
            "finish_reason": handle.finish_reason,
            "ttft_s": handle.ttft_s,
            "tpot_s": handle.tpot_s,
        }

    def _publish_index_loop(self) -> None:
        from ray_tpu._private.config import GlobalConfig
        from ray_tpu._private.worker import global_worker_or_none

        interval = float(
            GlobalConfig.serve_prefix_index_publish_interval_s)
        while not self._stop.wait(interval):
            w = global_worker_or_none()
            if w is None:
                continue        # no cluster: nothing to publish to
            try:
                eng = self._engine
                tiers: Dict[str, Any] = {
                    "block_size": eng.config.kv_block_size}
                if eng._tiers is not None:
                    ts = eng._tiers.stats()
                    tiers["host_blocks"] = ts["host"]["blocks"]
                    tiers["store_blocks"] = ts["store"]["blocks"]
                w.gcs.call("report_prefix_index", timeout=5,
                           replica=self._replica_id,
                           heads=eng.prefix_index_heads(),
                           tiers=tiers)
            except Exception:
                pass            # index is a hint; never crash a replica

    def export_prefix(self, tokens, max_blocks=None):
        """Donor side of a router-initiated peer pull: the longest
        HBM + tier chain covering ``tokens`` as per-block KVPrefix
        links. Hops to the scheduler thread — device state may only be
        read alongside the engine's donating programs there."""
        return self._engine.call_on_scheduler(
            lambda: self._engine.export_prefix(tokens,
                                               max_blocks=max_blocks),
            timeout_s=30.0)

    def import_prefix(self, prefixes) -> int:
        """Receiver side of a peer pull: park pulled links in the host
        tier; the pulling request's admission promotes them through
        the cost model. Thread-safe, no scheduler hop."""
        return self._engine.import_prefix(prefixes)

    def load(self) -> Dict[str, Any]:
        """Cheap load snapshot for the LLM router's queue-depth probe
        (serve/llm/router.py): engine queue + busy slots, no jit-stat
        scan, safe to call at probe frequency. ``index_id`` is how the
        router joins this replica's handle to its GCS prefix-index
        entry."""
        s = self._engine.stats()
        return {
            "queued": s["queued"],
            "active_slots": s["active_slots"],
            "free_slots": s["num_slots"] - s["active_slots"],
            "lanes": s["queued_by_lane"],
            "index_id": self._replica_id,
        }

    def stats(self) -> Dict[str, Any]:
        from ray_tpu.observability import jit_stats

        import jax

        from ray_tpu._private import compile_cache

        out = self._engine.stats()
        out["quantize"] = self.quantize
        # Where this replica actually runs: a caller that leased a chip
        # checks it got one (chip_smoke.py fails on anything but "tpu").
        import os

        dev = jax.devices()[0]
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
        out["device"] = {"platform": dev.platform, "kind": dev.device_kind,
                         "count": jax.device_count(), "pid": os.getpid(),
                         "peak_hbm_gib": round(peak / 2 ** 30, 3)}
        out["compile_cache"] = {"dir": compile_cache.cache_dir(),
                                **compile_cache.stats()}
        out["jit"] = {k: v for k, v in jit_stats().items()
                      if k.startswith("llm_engine_")}
        return out

    def check_health(self) -> None:
        if not self._thread.is_alive():
            raise RuntimeError("llm engine scheduler thread died")

    def __del__(self):
        try:
            self._stop.set()
        except Exception:
            pass


def build_llm_app(model_config: Any = None, engine_config: Any = None,
                  *, name: str = "llm", num_replicas: int = 1,
                  num_tpus: float, max_ongoing_requests: int = 32,
                  init_seed: int = 0, quantize: Optional[str] = None,
                  quantize_int8: bool = False,
                  params_loader: Optional[Any] = None):
    """Bind LLMServer as a Serve application: one engine per replica,
    `max_ongoing_requests` concurrent submitters feeding its slot pool.
    Pass configs as dicts (e.g. ``{"num_slots": 8}``) or dataclasses.
    ``quantize`` defaults to the int8 serve config; pass "bf16" to opt
    out. ``num_tpus`` is each replica's chip lease and has no default:
    a replica without a lease runs in a worker that can only see the
    CPU (pass ``num_tpus=0`` to ask for exactly that). For N replicas behind a queue-depth-aware router, use
    ``serve.llm.build_routed_llm_app`` instead."""
    from ray_tpu import serve

    if quantize is None and quantize_int8:
        quantize = "int8"
    dep = serve.deployment(
        LLMServer, name=name, num_replicas=num_replicas,
        num_tpus=num_tpus, max_ongoing_requests=max_ongoing_requests)
    return dep.bind(model_config=_plain(model_config),
                    engine_config=_plain(engine_config),
                    init_seed=init_seed, quantize=quantize,
                    params_loader=params_loader)


def _plain(cfg: Any):
    """Dataclass -> dict so the spec cloudpickles without importing jax
    dtypes driver-side; dicts/None pass through."""
    import dataclasses

    if cfg is None or isinstance(cfg, dict):
        return cfg
    if dataclasses.is_dataclass(cfg):
        return {f.name: getattr(cfg, f.name)
                for f in dataclasses.fields(cfg)}
    return cfg
