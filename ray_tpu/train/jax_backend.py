"""JaxBackend — the TPU-native Train backend.

This is the component BASELINE.json's north star names: the analogue of the
reference's TorchBackend/TorchConfig (`train/torch/config.py:146` — pick
process-group backend, broadcast rank-0 address, `dist.init_process_group`
at `:108`), re-designed for jax:

- on_start: rank 0 picks a coordinator port; every worker calls
  `jax.distributed.initialize(coordinator, num_processes, process_id)`.
  After that, `jax.devices()` on any worker sees the GLOBAL device set —
  on a TPU pod slice, collectives between them ride ICI, and the SPMD
  mesh spans the slice.
- Workers then build meshes via `ray_tpu.train.jax_utils` / collective
  `get_group_mesh` and run pjit'd steps; there is no DDP wrapper — data/
  model parallelism are sharding annotations, not engines.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

from ray_tpu.train.backend import Backend, BackendConfig


@dataclass
class JaxConfig(BackendConfig):
    # "tpu" on real hardware; "cpu" for the fake-mesh test tier
    # (the moral equivalent of the reference's _fake_gpus/gloo tiers).
    platform: Optional[str] = None
    # CPU tier only: per-process virtual device count
    # (jax.config jax_num_cpu_devices).
    num_cpu_devices: Optional[int] = None
    # Default mesh axes for workers that call `pod_train_loop` /
    # `run_pod_training` without an explicit mesh: data absorbs whatever
    # the fsdp/tensor factors leave over (parallel.make_mesh semantics).
    mesh_axes: Optional[dict] = None
    # "replicated" | "sharded" — ZeRO-style cross-replica sharding of the
    # optimizer update (parallel.zero) for loops driven via this config.
    weight_update: str = "replicated"
    # Chunked split-phase overlap of grad reduce-scatter / param allgather
    # with optimizer math (parallel.zero overlap schedule).  Only valid
    # with a pure data mesh; implies the explicit sharded update route.
    overlap: bool = False

    @property
    def backend_cls(self):
        return JaxBackend


def _setup_jax_distributed(coordinator: Optional[str], world_size: int,
                           rank: int, platform: Optional[str],
                           num_cpu_devices: Optional[int]) -> int:
    import jax

    from ray_tpu._private import compile_cache

    compile_cache.configure()
    if platform:
        jax.config.update("jax_platforms", platform)
    if num_cpu_devices and (platform == "cpu"):
        jax.config.update("jax_num_cpu_devices", num_cpu_devices)
    if world_size > 1:
        jax.distributed.initialize(
            coordinator_address=coordinator,
            num_processes=world_size,
            process_id=rank,
        )
    return jax.device_count()


def _shutdown_jax_distributed() -> None:
    import jax

    try:
        jax.distributed.shutdown()
    except Exception:
        pass


class JaxBackend(Backend):
    def on_start(self, worker_group, backend_config: JaxConfig) -> None:
        import ray_tpu

        world_size = worker_group.num_workers
        coordinator = None
        if world_size > 1:
            meta0 = worker_group.metadata()[0]
            port = worker_group.execute_single(0, _free_port_on_worker)
            coordinator = f"{meta0['ip']}:{port}"
        device_counts = ray_tpu.get([
            w.execute.remote(_setup_jax_distributed, coordinator, world_size,
                             rank, backend_config.platform,
                             backend_config.num_cpu_devices)
            for rank, w in enumerate(worker_group.workers)
        ], timeout=600)
        # All workers must agree on the global device count — a mismatch
        # means a partial gang (some host failed to join its slice).
        if len(set(device_counts)) != 1:
            raise RuntimeError(
                f"inconsistent global device count across workers: "
                f"{device_counts}")

    def on_shutdown(self, worker_group, backend_config: JaxConfig) -> None:
        try:
            worker_group.execute(_shutdown_jax_distributed)
        except Exception:
            pass


def _free_port_on_worker() -> int:
    import socket

    s = socket.socket()
    s.bind(("0.0.0.0", 0))
    port = s.getsockname()[1]
    s.close()
    return port


# ---------------------------------------------------------------------------
# Pod-scale sharded training loop.  One canonical path from "workers joined
# the gang" to "tokens/sec/chip": build the multi-host data×fsdp×tensor
# mesh, shard a Llama model over it, and run the pjit train step with the
# ZeRO weight-update knob.  `JaxTrainer(pod_train_loop, ...)` uses it as a
# train_loop_per_worker; the multichip dryrun calls `run_pod_training`
# directly so both exercise the identical code path.
# ---------------------------------------------------------------------------

# `summary["setup_process"]`: what this process's `run_pod_training`
# calls spent setting up, all of them together
_setup_process = {"calls": 0, "seconds": {}, "programs": {}}


def _add_setup(seconds, programs):
    """Adds one call's set-up phases and the rows it added to
    `jit_stats()` to the process's; returns a copy of those."""
    total = _setup_process
    total["calls"] += 1
    for phase, s in seconds.items():
        total["seconds"][phase] = total["seconds"].get(phase, 0.0) + s
    for name, row in programs.items():
        mine = total["programs"].setdefault(name, dict.fromkeys(row, 0))
        for k, v in row.items():
            mine[k] += v
    return {"calls": total["calls"], "seconds": dict(total["seconds"]),
            "programs": {k: dict(v) for k, v in total["programs"].items()}}


def run_pod_training(model_config=None, mesh_axes=None, steps: int = 4,
                     batch_size: Optional[int] = None, seq_len: int = 33,
                     weight_update: str = "replicated",
                     learning_rate: float = 1e-3, seed: int = 0,
                     overlap: bool = False, n_chunks: int = 4,
                     collective: str = "auto", report=None,
                     devices=None) -> dict:
    """Run `steps` sharded Llama train steps; returns throughput metrics.

    The returned dict carries ``tokens_per_sec`` / ``tokens_per_sec_per_chip``
    measured over the post-compile steps (step 0 is the compile+warmup step
    and is excluded). ``devices`` restricts the mesh to a subset of
    ``jax.devices()`` (default: all of them) — how one process compares a
    sharded run with the same steps on a one-device mesh. The summary's
    ``state_bytes_per_device`` says where parameters and optimizer state
    actually live after the last step.

    ``overlap=True`` routes the loop through the explicit chunked
    split-phase ZeRO step (`parallel.zero.build_zero_train_step` with
    ``overlap=True``): grad reduce-scatter and param allgather hops are
    pipelined chunk-by-chunk under the optimizer math instead of running
    as one exposed collective.  Requires a pure data mesh (the chunk
    schedule owns the whole flat parameter vector).

    When ``train_goodput_instrumentation`` is on (default), the loop
    runs under the per-step phase ledger (`observability.goodput`):
    each step is decomposed into h2d/compute/exposed-collective/
    weight-publish phases (``rtpu_train_step_phase_seconds{phase}`` +
    ``train.step`` spans), the warmup compile step is booked as
    ``recompiling`` lost time, and each step publishes a heartbeat row
    into the GCS step matrix (straggler + stall detection). The
    returned dict then carries ``goodput`` (the worker ledger
    snapshot) and ``phase_seconds`` (per-phase sums over the timed
    steps).

    ``setup_seconds`` in the summary says what the call spent before its
    first timed step: ``init_params`` (the parameters drawn from the
    seed), ``place`` (parameters and optimizer state put where they
    live), ``h2d`` (the batch) and ``compile_warmup`` (the first step:
    trace, lower, compile or load, run). ``setup_process`` says the
    same of ALL this process's calls so far, this one included: their
    number, the phases' sums, and what the calls added to
    ``jit_stats()``, by program (a later call finds the process's jit
    cache warm and costs a fraction of the first; a program traced
    again in a timed step is in these rows and in no phase).
    """
    import time
    from contextlib import contextmanager

    import jax
    import numpy as np
    import optax

    from ray_tpu._private import compile_cache
    from ray_tpu._private.config import GlobalConfig
    from ray_tpu.observability.goodput import (
        GoodputLedger, StepPhases, goodput_metrics, publish_train_done,
        set_active_ledger,
    )
    from ray_tpu.observability.jit import jit_stats, jit_stats_since

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel import (
        batch_sharding, build_train_step, build_zero_train_step,
        create_train_state, create_zero_state, llama_param_shardings,
        make_mesh, shard_params, sharded_flash_attention,
    )

    compile_cache.configure()          # before the step's first compile
    if model_config is None:
        model_config = LlamaConfig(
            vocab_size=512, dim=128, n_layers=4, n_heads=8, n_kv_heads=4,
            hidden_dim=256, max_seq_len=128)
    mesh = make_mesh(dict(mesh_axes) if mesh_axes else {"data": -1},
                     devices)
    n_devices = int(np.prod(mesh.devices.shape))

    if overlap:
        non_data = [ax for ax in mesh.axis_names
                    if ax != "data" and mesh.shape[ax] > 1]
        if non_data:
            raise ValueError(
                f"overlap=True needs a pure data mesh, got non-trivial "
                f"axes {non_data} — the chunked schedule shards the whole "
                "flat parameter vector over 'data'")
        weight_update = "sharded"

    setup_seconds, jit_before = {}, jit_stats()

    @contextmanager
    def setup(phase):
        t = time.perf_counter()
        yield
        setup_seconds[phase] = time.perf_counter() - t

    with setup("init_params"):
        params = init_params(model_config, jax.random.key(seed))
        jax.block_until_ready(params)
    shardings = llama_param_shardings(model_config, mesh)
    bsh = batch_sharding(mesh)
    optimizer = optax.adamw(learning_rate)
    params_shape = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)

    if overlap:
        step = build_zero_train_step(
            lambda p, b: loss_fn(p, b, model_config), optimizer, mesh,
            axis_name="data", collective=collective, overlap=True,
            n_chunks=n_chunks)
        with setup("place"):
            state = jax.block_until_ready(
                create_zero_state(params, optimizer, mesh))
    else:
        # A Mosaic kernel is not partitioned by GSPMD: on more than one
        # device the flash kernel runs under shard_map over batch/heads.
        attn = (sharded_flash_attention(mesh)
                if model_config.attn_impl == "flash" and n_devices > 1
                else None)
        step = build_train_step(
            lambda p, b: loss_fn(p, b, model_config, attn), optimizer,
            mesh, shardings, bsh, weight_update=weight_update,
            params_shape=params_shape)
        with setup("place"):
            state = jax.block_until_ready(create_train_state(
                shard_params(params, shardings), optimizer))

    # Batch must divide evenly over the data-like axes.
    data_shards = 1
    for ax in ("data", "fsdp"):
        if ax in mesh.axis_names:
            data_shards *= mesh.shape[ax]
    if batch_size is None:
        batch_size = max(8, n_devices)
    if batch_size % data_shards:
        batch_size = ((batch_size + data_shards - 1)
                      // data_shards) * data_shards
    instrument = bool(GlobalConfig.train_goodput_instrumentation)
    worker_label = f"train-{jax.process_index()}"
    ledger = GoodputLedger(worker=worker_label) if instrument else None
    if ledger is not None:
        set_active_ledger(ledger)

    rng = np.random.RandomState(seed)
    host_tokens = rng.randint(0, model_config.vocab_size,
                              (batch_size, seq_len)).astype("int32")
    with setup("h2d"):
        batch = {"tokens": jax.device_put(host_tokens, bsh)}
    if ledger is not None:
        # One-off input transfer: an h2d histogram sample + stalled
        # ledger time (a real input pipeline pays this per step).
        h2d_s = setup_seconds["h2d"]
        goodput_metrics().step_phase_seconds.observe(
            h2d_s, {"phase": "h2d"})
        ledger.book_phases({"h2d": h2d_s})
    tokens_per_step = batch_size * (seq_len - 1)  # next-token targets

    with setup("compile_warmup"):
        state, metrics = step(state, batch)  # compile + warmup
        jax.block_until_ready(metrics["loss"])
    if ledger is not None:
        # The compile+warmup step is wall time the pod spent not
        # training — exactly what a preemption/resume re-pays.
        ledger.lose("recompiling", setup_seconds["compile_warmup"])

    step_rows = []
    t0 = time.perf_counter()
    for i in range(steps):
        if ledger is not None:
            sp = StepPhases(step=i, worker=worker_label, ledger=ledger)
            with sp.phase("compute"):
                state, metrics = step(state, batch)
                # Phase attribution needs the step's device work fenced
                # inside its timed section (dispatch alone is ~free).
                jax.block_until_ready(metrics["loss"])
            if report is not None:
                with sp.phase("weight_publish"):
                    report({"loss": float(metrics["loss"]),
                            "step": int(metrics["step"])})
            step_rows.append(sp.finish())
        else:
            state, metrics = step(state, batch)
            if report is not None:
                report({"loss": float(metrics["loss"]),
                        "step": int(metrics["step"])})
    jax.block_until_ready(metrics["loss"])
    elapsed = time.perf_counter() - t0
    loss = float(metrics["loss"])
    # How many Mosaic kernels the compiled step holds (the tracked step
    # keeps its AOT artifact): 0 with attn_impl="flash" on a TPU would
    # mean attention quietly became XLA attention.
    compiled = step.compiled(state, batch) \
        if hasattr(step, "compiled") else None
    tokens_per_sec = tokens_per_step * steps / max(elapsed, 1e-9)
    extra = {}
    if ledger is not None:
        phase_seconds: dict = {}
        for row in step_rows:
            for phase, dur in row["phases"].items():
                phase_seconds[phase] = phase_seconds.get(phase, 0.0) + dur
        extra = {"goodput": ledger.snapshot(),
                 "phase_seconds": phase_seconds,
                 "step_walls": [row["wall_s"] for row in step_rows]}
        set_active_ledger(None)
        publish_train_done(worker_label)
    return {
        **extra,
        "step_tpu_custom_calls": (
            None if compiled is None
            else compiled.as_text().count("tpu_custom_call")),
        "state_bytes_per_device": {
            "params": _bytes_per_device(state.params),
            "opt_state": _bytes_per_device(state.opt_state)},
        "n_devices": n_devices,
        "mesh": {name: int(size) for name, size
                 in zip(mesh.axis_names, mesh.devices.shape)},
        "weight_update": weight_update,
        "overlap": overlap,
        "steps": steps,
        "batch_size": batch_size,
        "seq_len": seq_len,
        "loss": loss,
        "setup_seconds": setup_seconds,
        "setup_process": _add_setup(setup_seconds,
                                    jit_stats_since(jit_before)),
        "train_seconds": elapsed,
        "tokens_per_sec": tokens_per_sec,
        "tokens_per_sec_per_chip": tokens_per_sec / max(n_devices, 1),
    }


def _bytes_per_device(tree) -> dict:
    """{device id: bytes of `tree` resident there}, from each array's
    addressable shards (a replicated array counts once per holder)."""
    import jax

    out: dict = {}
    for leaf in jax.tree.leaves(tree):
        for shard in getattr(leaf, "addressable_shards", ()):
            out[shard.device.id] = (out.get(shard.device.id, 0)
                                    + shard.data.nbytes)
    return out


def pod_train_loop(config: Optional[dict] = None) -> None:
    """`train_loop_per_worker` for `JaxTrainer`: pod-scale sharded Llama
    training over the multi-host mesh, reporting throughput per step.

    Config keys (all optional): ``mesh_axes``, ``weight_update``,
    ``steps``, ``batch_size``, ``seq_len``, ``learning_rate``, ``seed``,
    ``model_config`` (a LlamaConfig).  Mesh/weight-update defaults come
    from the backend's `JaxConfig` when driven through `JaxTrainer`.
    """
    from ray_tpu import train

    config = dict(config or {})
    summary = run_pod_training(
        model_config=config.get("model_config"),
        mesh_axes=config.get("mesh_axes"),
        steps=int(config.get("steps", 4)),
        batch_size=config.get("batch_size"),
        seq_len=int(config.get("seq_len", 33)),
        weight_update=config.get("weight_update", "replicated"),
        learning_rate=float(config.get("learning_rate", 1e-3)),
        seed=int(config.get("seed", 0)),
        overlap=bool(config.get("overlap", False)),
        n_chunks=int(config.get("n_chunks", 4)),
        collective=config.get("collective", "auto"),
        report=None,
    )
    train.report(summary)
