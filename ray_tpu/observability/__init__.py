"""TPU-aware telemetry plane.

The two things that silently kill TPU performance are XLA recompiles
and idle device time (Podracer, arXiv:2104.06272, attributes its TPU
efficiency to exactly this per-step accounting). This package is the
shared instrumentation layer every hot path reports through:

- ``jit``: compile tracking for the ``jax.jit`` entry points we own —
  per-function trace/compile counters, compile wall-time histograms,
  ``jit_compile`` spans, and a recompile detector that warns once a
  function re-traces past its budget (:class:`TrackedJit`).
- ``device``: per-device HBM/count gauges sampled by the metrics
  flusher (``device.memory_stats()`` where the backend provides it).
- ``serve``: TTFT/TPOT/e2e/queue-wait histograms, queue-depth /
  active-slot / batch-utilization gauges, and token/request counters
  for the continuous-batching LLM engine.
- ``train``: step-duration / samples-per-sec / loss reporting for
  ``train`` sessions and RLlib learners.
- ``goodput``: the train-tier goodput & straggler plane — the
  :class:`StepPhases` per-step phase ledger
  (``rtpu_train_step_phase_seconds{phase}`` + ``train.step`` spans),
  the :class:`GoodputLedger` productive-vs-lost wall-clock accounting
  (``rtpu_train_goodput_ratio``,
  ``rtpu_train_lost_seconds_total{cause}``), the
  :class:`StragglerDetector` over the GCS cross-worker step matrix
  (``report/list_train_steps``), and the hooks the GCS stall watchdog
  builds TRAIN_STRAGGLER / TRAIN_STALL events from.
- ``rl``: the decoupled-RL (podracer) plane — env-step vs
  learner-sample throughput counters, weight version/staleness gauges
  for the versioned WeightStore channel, sample-queue depth and
  backpressure counters, inference-server batching factors.
- ``collective``: op/bytes counters and latency histograms for every
  ``util.collective`` op (``rtpu_collective_*{op,backend,dtype}``),
  plus ``collective:<op>`` timeline spans — the interconnect side of
  the idle-device question.
- ``data``: the Dataset executors' metric set — per-stage throughput
  counters finalized by ``DatasetStats`` plus live backpressure gauges
  (in-flight tasks, queued blocks) from the scheduler loops.
- ``object_store``: per-node object-store memory-pressure metrics
  (used/capacity/pinned/spilled gauges, spill/restore/eviction
  counters) sampled from ``NodeObjectStore.stats()`` at each flush.
- ``timeline``: the Chrome-trace builder shared by
  ``ray_tpu.timeline()`` and the dashboard's ``GET /api/timeline`` —
  including the segmented submit arrows of the scheduling-phase
  breakdown (PENDING → LEASE_GRANTED → WORKER_STARTED → ARGS_READY →
  RUNNING).
- ``profiling``: the live profiling plane — the wall-clock
  :class:`StackSampler` (bounded memory, per-thread attribution)
  behind ``util.state.profile()`` flamegraphs, the one-shot stack
  dumps behind ``util.state.stack()`` / ``GET /api/stacks``, the
  jax.profiler device-trace bracket behind ``util.state.tpu_profile()``
  and the ``rtpu_sched_phase_seconds{phase}`` scheduling-latency
  histogram.
- ``events``: the cluster event schema registry — typed,
  severity-tagged failure-forensics events (worker-exit taxonomy,
  actor death/restart, node membership, lease reclaim, OOM) recorded
  in the GCS ClusterEventLog and queried via
  ``ray_tpu.util.state.list_cluster_events`` / ``GET /api/events``.
- ``control``: the decision side of the loop — the
  ``rtpu_ctrl_decisions_total{controller,action}`` counter, the
  :func:`record_decision` fan-out (counter + timeline span + typed
  cluster event + GCS decision ring / ``GET /api/controller``), and
  the :class:`Hysteresis` hold-delay/cooldown gate shared by the serve
  autoscaler and the data backpressure tuner.

- ``xla`` / ``chipspec``: the fleet-wide XLA program cost & roofline
  attribution plane — on first compile every :class:`TrackedJit`
  program's ``cost_analysis()`` (FLOPs, HBM bytes accessed,
  transcendentals) and ``memory_analysis()`` (argument/output/temp/peak
  HBM bytes) land in the per-process :class:`ProgramRegistry`; every
  ``xla_wall_sample_every``-th steady-state call is fenced to sample an
  honest execution wall, which divided by the chip-spec peak table
  (``chipspec``: v4/v5e/v5p, CPU rows tagged ``measurement: cpu``)
  yields MFU/MBU and a compute-/memory-/comm-bound roofline verdict
  (the last folding the exposed-collective seconds the sampled call
  straddled). Rows publish over bounded GCS
  ``report/list_xla_programs`` RPCs, roll up via
  ``util.state.xla_summary()`` / ``GET /api/programs``, and export as
  ``rtpu_xla_program_{flops,bytes_hbm,mfu,mbu}`` gauges plus the
  exemplar-carrying ``rtpu_xla_program_wall_seconds`` histogram. The
  regression sentinel baselines each function's first program and emits
  one typed ``PERF_REGRESSION`` cluster event per drift episode when a
  re-compile's FLOPs/peak-HBM or a sampled wall moves past
  ``xla_regression_ratio``.

- ``accounting``: the per-request cost accounting & SLO attainment
  plane for the serving tier — the :class:`RequestMeter` attached to
  every engine request (prefill tokens computed vs avoided, decode
  tokens, speculative accept ratio, KV block-seconds, queue-wait and
  chip-seconds per phase, stamped ``{tenant, model, lane, trace_id}``),
  the :class:`TenantLedger` fold published to the GCS over bounded
  ``report/list_serve_accounting`` RPCs, and the :class:`SLOTracker`
  multi-window burn-rate evaluation of TTFT/TPOT attainment per lane
  that emits the typed ``SLO_BURN`` cluster event
  (``rtpu_serve_request_cost_*``, ``rtpu_serve_tenant_*_total{tenant}``,
  ``rtpu_serve_slo_attainment_ratio{lane}``, ``GET /api/accounting``).

Everything exports through the existing plane: metric objects are
``ray_tpu.util.metrics`` Counters/Gauges/Histograms (flushed to the GCS
``/metrics`` scrape endpoint with the ``rtpu_`` prefix), spans are
``ray_tpu.util.tracing`` events (rendered by ``ray_tpu.timeline()``).
"""

from ray_tpu.observability.accounting import (  # noqa: F401
    COST_PHASES,
    RequestMeter,
    SLOTracker,
    TenantLedger,
    TokenReconciler,
    accounting_enabled,
    accounting_metrics,
    fold_finished,
    publish_serve_row,
    slo_targets,
    tenant_ledger,
)
from ray_tpu.observability.chipspec import (  # noqa: F401
    ChipSpec,
    local_spec,
    lookup,
)
from ray_tpu.observability.jit import (  # noqa: F401
    RecompileWarning,
    TrackedJit,
    jit_stats,
    tracked_jit,
)
from ray_tpu.observability.xla import (  # noqa: F401
    ProgramRegistry,
    attribution_enabled,
    flush_captures,
    local_programs,
    program_registry,
    wall_sample_every,
    xla_metrics,
)
from ray_tpu.observability.device import (  # noqa: F401
    sample_device_metrics,
)
from ray_tpu.observability.control import (  # noqa: F401
    Hysteresis,
    control_metrics,
    record_decision,
)
from ray_tpu.observability.collective import (  # noqa: F401
    collective_metrics,
    observe_collective,
)
from ray_tpu.observability.data import data_metrics  # noqa: F401
from ray_tpu.observability.events import (  # noqa: F401
    EVENT_TYPES,
    SEVERITIES,
    WORKER_EXIT_TYPES,
    classify_worker_exit,
    make_event,
)
from ray_tpu.observability.goodput import (  # noqa: F401
    GOODPUT_CAUSES,
    TRAIN_PHASES,
    GoodputLedger,
    StepPhases,
    StragglerDetector,
    classify_phase,
    goodput_enabled,
    goodput_metrics,
    publish_train_done,
    publish_train_step,
    record_checkpoint,
    record_recompile,
)
from ray_tpu.observability.object_store import (  # noqa: F401
    object_store_metrics,
    register_store_sampler,
)
from ray_tpu.observability.profiling import (  # noqa: F401
    SCHED_PHASES,
    SCHED_SEGMENT_LABELS,
    StackSampler,
    capture_thread_stacks,
    collapse,
    format_thread_stacks,
    merge_counts,
    observe_sched_phases,
    render_speedscope,
    trace_span,
)
from ray_tpu.observability.rl import rl_metrics  # noqa: F401
from ray_tpu.observability.serve import serve_metrics  # noqa: F401
from ray_tpu.observability.timeline import build_chrome_trace  # noqa: F401
from ray_tpu.observability.train import (  # noqa: F401
    batch_num_samples,
    learner_metrics,
    train_metrics,
)

__all__ = [
    "RecompileWarning", "TrackedJit", "tracked_jit", "jit_stats",
    "sample_device_metrics", "serve_metrics", "rl_metrics",
    "train_metrics",
    "learner_metrics", "batch_num_samples", "build_chrome_trace",
    "data_metrics", "object_store_metrics", "register_store_sampler",
    "EVENT_TYPES", "SEVERITIES", "WORKER_EXIT_TYPES",
    "classify_worker_exit", "make_event",
    "Hysteresis", "control_metrics", "record_decision",
    "collective_metrics", "observe_collective",
    "SCHED_PHASES", "SCHED_SEGMENT_LABELS", "StackSampler",
    "capture_thread_stacks", "collapse", "format_thread_stacks",
    "merge_counts", "observe_sched_phases", "render_speedscope",
    "trace_span",
    "GOODPUT_CAUSES", "TRAIN_PHASES", "GoodputLedger", "StepPhases",
    "StragglerDetector", "classify_phase", "goodput_enabled",
    "goodput_metrics", "publish_train_done", "publish_train_step",
    "record_checkpoint", "record_recompile",
    "COST_PHASES", "RequestMeter", "SLOTracker", "TenantLedger",
    "TokenReconciler", "accounting_enabled", "accounting_metrics",
    "fold_finished", "publish_serve_row", "slo_targets", "tenant_ledger",
    "ChipSpec", "local_spec", "lookup",
    "ProgramRegistry", "attribution_enabled", "flush_captures",
    "local_programs", "program_registry", "wall_sample_every",
    "xla_metrics",
]
