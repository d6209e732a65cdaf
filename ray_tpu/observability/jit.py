"""JIT compile telemetry: trace/compile accounting for jitted programs.

XLA programs are shape-specialized, so a hot path that feeds a jitted
function changing shapes/dtypes retraces (and recompiles) silently —
the serving engine's ad-hoc ``_traces`` guard existed precisely to
catch that. :class:`TrackedJit` generalizes it onto a shared API:

    tick = tracked_jit(tick_fn, name="engine_tick", trace_budget=1,
                       donate_argnums=(1,))
    out = tick(params, state)           # drop-in for jax.jit(tick_fn)
    tick.traces                         # programs traced by THIS wrapper

Each new trace increments the ``jit_traces_total`` counter (tagged by
function name), observes the first-call wall time —
trace + lower + compile + first execute, the cost a user actually waits
for — into the ``jit_compile_seconds`` histogram, and records a
``jit_compile`` span so ``ray_tpu.timeline()`` shows compiles inline
with the run. That wall is also split by stage, from what
``_private/compile_cache`` heard of JAX's own compile events on the
calling thread during the call: ``jit_stats()[name]`` keeps
``trace_seconds``, ``lower_seconds``, ``backend_seconds`` (the compiler
on a persistent-cache miss, loading the executable on a hit) beside
``compile_seconds_total``, and what that total holds over the three is
the first dispatch and run. When an instance
re-traces past ``trace_budget`` it warns ONCE with
:class:`RecompileWarning` naming the function and the argument
signature that caused the re-trace.

Budgets are per-instance (a fresh engine legitimately re-traces its own
programs); the counters aggregate per function name across instances
and processes.

On top of the trace guard rides the XLA attribution plane
(observability/xla.py): each new program's ``cost_analysis()`` /
``memory_analysis()`` is captured through the :meth:`compiled` accessor
(one shared AOT artifact per signature, built on the plane's background
capture worker so the extra compile never lands on the caller), and
every ``xla_wall_sample_every``-th steady-state call
is fenced with ``block_until_ready`` to sample an honest execution wall
(0 disables sampling: the fence never runs on the hot path). An owner
that keeps its calls in flight and waits for them elsewhere (the serving
engine's decode tick) passes ``fence_samples=False``: the sampled call
is then only marked, and the owner hands in the wall it measured where
it waits (:meth:`TrackedJit.take_sample` / :meth:`TrackedJit.record_wall`).
"""

from __future__ import annotations

import re
import threading
import time
import warnings
from typing import Any, Callable, Dict, Optional

from ray_tpu._private import compile_cache

_lock = threading.Lock()
# name -> the counts of `jit_stats()`
_stats: Dict[str, Dict[str, float]] = {}


def _new_stats() -> Dict[str, float]:
    return {"traces": 0, "compiles": 0, "compile_seconds_total": 0.0,
            "trace_seconds": 0.0, "lower_seconds": 0.0,
            "backend_seconds": 0.0}

_metrics = None


class RecompileWarning(UserWarning):
    """A tracked jitted function re-traced beyond its trace budget."""


def _jit_metrics():
    """Lazy module-level metric singletons (one registry entry per
    process regardless of how many TrackedJit instances exist)."""
    global _metrics
    if _metrics is None:
        from ray_tpu.util.metrics import Counter, Histogram

        _metrics = {
            "traces": Counter(
                "jit_traces_total",
                description="XLA traces of tracked jitted functions.",
                tag_keys=("fn",)),
            "compile_seconds": Histogram(
                "jit_compile_seconds",
                description="First-call wall time of newly traced "
                            "programs (trace+compile+execute).",
                boundaries=(0.01, 0.05, 0.25, 1.0, 5.0, 15.0, 60.0,
                            300.0),
                tag_keys=("fn",)),
        }
    return _metrics


def _arg_signature(args, kwargs) -> str:
    """Compact human-readable shape/dtype signature for the warning."""
    def one(a: Any) -> str:
        shape = getattr(a, "shape", None)
        dtype = getattr(a, "dtype", None)
        if shape is not None:
            return f"{dtype}[{','.join(map(str, shape))}]"
        if isinstance(a, (dict, list, tuple)):
            return type(a).__name__
        return f"{type(a).__name__}:{a!r}"[:40]

    parts = [one(a) for a in args]
    parts += [f"{k}={one(v)}" for k, v in kwargs.items()]
    return "(" + ", ".join(parts) + ")"


class TrackedJit:
    """``jax.jit`` plus trace/compile telemetry and a recompile budget.

    The wrapped python callable only runs when jax traces a new
    program, so ``traces`` counts compiled programs exactly — the same
    mechanism as the engine's original ``_traces`` guard.
    """

    def __init__(self, fn: Callable, *, name: Optional[str] = None,
                 trace_budget: Optional[int] = None,
                 fence_samples: bool = True, **jit_kwargs):
        import jax

        self.name = name or getattr(fn, "__name__", "jitted")
        self.traces = 0
        self.calls = 0
        # False: a sampled call is marked, not fenced (`take_sample`)
        self._fence_samples = fence_samples
        self._sample_due = None
        if trace_budget is None:
            from ray_tpu._private.config import GlobalConfig

            trace_budget = GlobalConfig.jit_recompile_warn_budget
        self.trace_budget = trace_budget
        self._warned = False
        self._fn = fn
        self._jit_kwargs = dict(jit_kwargs)
        # AOT artifacts per argument signature, shared between the
        # attribution hook and compiled() callers — one lowered program
        # instead of a re-lower per consumer.
        self._compiled_cache: Dict[str, Any] = {}
        # While the attribution hook lowers through the jit wrapper the
        # probe still runs under tracing; this re-entrancy flag keeps
        # those internal traces out of the user-facing counters. Beside
        # it `at_trace`: the thread's compile totals when its last
        # counted trace began.
        self._suppress = threading.local()
        compile_cache.listen()
        from ray_tpu.observability import xla as _xla

        self._sample_every = _xla.wall_sample_every() \
            if _xla.attribution_enabled() else 0

        def probe(*args, **kwargs):
            # Runs only under tracing: count the new program here. The
            # mutation is the whole point — it fires once per trace, not
            # per call, which is exactly what a retrace counter wants.
            if not getattr(self._suppress, "on", False):
                self.traces += 1  # graftlint: disable=jit-global-mutation
                # the outermost trace is open and not yet counted: the
                # totals are those of before the call (nothing on the
                # hot path reads them); once a trace, as the count above
                at = compile_cache.thread_totals()
                self._suppress.at_trace = at  # graftlint: disable=jit-global-mutation
                with _lock:
                    st = _stats.setdefault(self.name, _new_stats())
                    st["traces"] += 1
            return fn(*args, **kwargs)

        # The trace names a program after the jitted callable
        # (`jit_llm_engine_tick(<fingerprint>)` on `XLA Modules`).
        probe.__name__ = probe.__qualname__ = re.sub(r"\W", "_", self.name)
        self._jitted = jax.jit(probe, **jit_kwargs)

    def __call__(self, *args, **kwargs):
        self.calls += 1
        sample = (self._sample_every > 0
                  and self.calls % self._sample_every == 0)
        exposed0 = _cumulative_exposed() if sample else 0.0
        before = self.traces
        t0 = time.perf_counter()
        out = self._jitted(*args, **kwargs)
        if self.traces > before:
            dt = time.perf_counter() - t0
            self._on_compile(dt, args, kwargs, self._stages())
        elif sample:
            due = (_arg_signature(args, kwargs), exposed0)
            if self._fence_samples:
                self._sample_wall(out, t0, due)
            else:
                self._sample_due = due
        return out

    def _stages(self):
        """(trace, lower, backend) seconds the calling thread spent
        since the probe ran in this call: the call's own stages. All
        zero for a call traced inside another program's trace, whose
        seconds hold it."""
        at = getattr(self._suppress, "at_trace", None)
        if at is None:                  # traced on another thread
            return 0.0, 0.0, 0.0
        self._suppress.at_trace = None
        return tuple(b - a for a, b in
                     zip(at, compile_cache.thread_totals()))

    def _on_compile(self, seconds: float, args, kwargs, stages) -> None:
        trace_s, lower_s, backend_s = stages
        with _lock:
            st = _stats[self.name]
            st["compiles"] += 1
            st["compile_seconds_total"] += seconds
            st["trace_seconds"] += trace_s
            st["lower_seconds"] += lower_s
            st["backend_seconds"] += backend_s
        try:
            m = _jit_metrics()
            tags = {"fn": self.name}
            m["traces"].inc(1.0, tags=tags)
            m["compile_seconds"].observe(seconds, tags=tags)
        except Exception:
            pass  # telemetry must never break the hot path
        try:
            # Compile wall time is lost training time: the goodput
            # ledger books it as "recompiling" when a train loop is
            # live in this process (no-op otherwise).
            from ray_tpu.observability.goodput import record_recompile

            record_recompile(seconds)
        except Exception:
            pass
        try:
            from ray_tpu.observability.profiling import trace_span
            from ray_tpu.util.tracing import record_span

            # An instant at the end of the compiling call: in a device
            # trace it stands beside the gap the compile caused.
            with trace_span("jit.compile", fn=self.name, seconds=seconds):
                pass
            record_span("jit_compile", time.time() - seconds, seconds,
                        attrs={"fn": self.name, "traces": self.traces})
        except Exception:
            pass
        try:
            # XLA attribution: capture this program's cost/memory
            # analysis into the per-process ProgramRegistry.
            from ray_tpu.observability import xla as _xla

            if _xla.attribution_enabled():
                _xla.on_tracked_compile(self, seconds, args, kwargs)
        except Exception:
            pass
        if (self.trace_budget and self.traces > self.trace_budget
                and not self._warned):
            self._warned = True
            warnings.warn(
                f"jitted function {self.name!r} traced {self.traces} "
                f"programs (budget {self.trace_budget}); last re-trace "
                f"caused by call {_arg_signature(args, kwargs)} — "
                f"check for varying shapes/dtypes/static args on the "
                f"hot path", RecompileWarning, stacklevel=4)

    def _sample_wall(self, out, t0: float, due) -> None:
        """Fence the sampled call and hand in its wall."""
        try:
            import jax

            from ray_tpu.observability.profiling import trace_span

            # The fence stands in a profiler trace under whatever span
            # holds the call.
            with trace_span("jit.wall_sample", fn=self.name):
                jax.block_until_ready(out)
            self._report_wall(due, time.perf_counter() - t0)
        except Exception:
            pass  # sampling must never break the hot path

    def take_sample(self):
        """With `fence_samples=False`: the mark of the call just made if
        it was a sampled one (for `record_wall`, once the owner has
        waited for that call's outputs), else None."""
        due, self._sample_due = self._sample_due, None
        return due

    def record_wall(self, due, wall_s: float) -> None:
        """Hand in the wall the owner measured for the call
        `take_sample` marked. `jit.wall_sample` is then an instant
        where the owner stood when it knew the wall."""
        try:
            from ray_tpu.observability.profiling import trace_span

            with trace_span("jit.wall_sample", fn=self.name):
                pass
            self._report_wall(due, wall_s)
        except Exception:
            pass  # sampling must never break the hot path

    def _report_wall(self, due, wall_s: float) -> None:
        """A sampled call's wall, plus the exposed collective seconds
        it straddled, to the attribution plane (`due`: the call's
        signature and the exposed seconds before it)."""
        from ray_tpu.observability import xla as _xla

        sig, exposed0 = due
        _xla.on_tracked_sample(
            self, sig, wall_s, max(_cumulative_exposed() - exposed0, 0.0))

    # -- AOT surface -------------------------------------------------

    def _abstract_args(self, args, kwargs):
        """Shape/dtype skeletons of a call: lowering through these never
        touches (possibly donated, possibly dead) device buffers."""
        import jax

        def one(a):
            shape = getattr(a, "shape", None)
            dtype = getattr(a, "dtype", None)
            if shape is not None and dtype is not None:
                return jax.ShapeDtypeStruct(shape, dtype)
            return a

        static_nums = self._jit_kwargs.get("static_argnums") or ()
        if isinstance(static_nums, int):
            static_nums = (static_nums,)
        static_names = self._jit_kwargs.get("static_argnames") or ()
        if isinstance(static_names, str):
            static_names = (static_names,)
        abs_args = tuple(
            a if i in static_nums else jax.tree_util.tree_map(one, a)
            for i, a in enumerate(args))
        abs_kwargs = {
            k: (v if k in static_names
                else jax.tree_util.tree_map(one, v))
            for k, v in kwargs.items()}
        return abs_args, abs_kwargs

    def compiled(self, *args, **kwargs):
        """AOT-compiled artifact for this call signature (lower +
        compile, cached per signature). The attribution hook and user
        code share the one artifact, so asking for ``cost_analysis()``
        never re-lowers a program the wrapper already built. Returns
        None when the backend cannot lower (telemetry callers treat
        that as "no analysis")."""
        key = _arg_signature(args, kwargs)
        cached = self._compiled_cache.get(key)
        if cached is not None:
            return cached
        try:
            abs_args, abs_kwargs = self._abstract_args(args, kwargs)
            self._suppress.on = True
            try:
                artifact = self._jitted.lower(
                    *abs_args, **abs_kwargs).compile()
            finally:
                self._suppress.on = False
            self._compiled_cache[key] = artifact
            return artifact
        except Exception:
            return None

    def lower(self, *args, **kwargs):
        return self._jitted.lower(*args, **kwargs)

    def unprobed(self):
        """`jax.jit` of the raw function under this wrapper's options:
        the same program under the function's own name, which no
        counter sees (lowering on shapes, serve/llm/programs.py)."""
        import jax

        return jax.jit(self._fn, **self._jit_kwargs)

    def eval_shape(self, *args, **kwargs):
        """Shape evaluation against the RAW function: never traces the
        probe, so speculative shape queries cannot inflate the
        trace/compile counters or mark a program as seen."""
        import jax

        return jax.eval_shape(self._fn, *args, **kwargs)

    def clear_cache(self) -> None:
        """Drop the jit trace cache AND the AOT artifact cache together
        — after this, the next call re-traces (and re-counts) like a
        fresh wrapper, and ``compiled()`` re-lowers."""
        self._compiled_cache.clear()
        try:
            self._jitted.clear_cache()
        except Exception:
            pass

    # jax.clear_caches()-era spelling; same semantics.
    clear_caches = clear_cache


def _cumulative_exposed() -> float:
    """Total exposed split-phase collective seconds this process has
    booked so far (observability/collective.py); 0.0 when the plane is
    unused. Deltas around a sampled call feed the comm-bound verdict."""
    try:
        from ray_tpu.observability.collective import (
            cumulative_exposed_seconds,
        )

        return cumulative_exposed_seconds()
    except Exception:
        return 0.0


def tracked_jit(fn: Optional[Callable] = None, *,
                name: Optional[str] = None,
                trace_budget: Optional[int] = None,
                fence_samples: bool = True,
                **jit_kwargs):
    """Drop-in ``jax.jit`` replacement with compile telemetry.

    Usable directly (``tracked_jit(fn, donate_argnums=...)``) or as a
    decorator (``@tracked_jit(name="step")``).
    """
    if fn is None:
        def deco(f):
            return TrackedJit(f, name=name, trace_budget=trace_budget,
                              fence_samples=fence_samples, **jit_kwargs)
        return deco
    return TrackedJit(fn, name=name, trace_budget=trace_budget,
                      fence_samples=fence_samples, **jit_kwargs)


def jit_stats() -> Dict[str, Dict[str, float]]:
    """Per-function aggregate {traces, compiles, compile_seconds_total,
    trace_seconds, lower_seconds, backend_seconds} for every tracked
    function in this process."""
    with _lock:
        return {k: dict(v) for k, v in _stats.items()}


def jit_stats_since(before: Dict[str, Dict[str, float]]
                    ) -> Dict[str, Dict[str, float]]:
    """What `jit_stats()` gained since the reading `before`: the rows of
    the programs traced since, as differences (what an owner's set-up
    compiled, whatever the process compiled under those names earlier)."""
    rows = {}
    for name, row in jit_stats().items():
        was = before.get(name, {})
        if row["traces"] > was.get("traces", 0):
            rows[name] = {k: v - was.get(k, 0) for k, v in row.items()}
    return rows
