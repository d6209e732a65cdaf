"""One place that decides where JAX's persistent compilation cache lives.

A cold process on the chip compiles every program it runs, and the cache
key includes the cache directory, so the directory must never move: no
temp name, pid or timestamp.

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing in code.
- unset: one fixed directory inside the checkout, ``<repo>/.jax_cache``
  (git-ignored).  `configure()` points the importing process at it;
  `child_env()` exports it to processes this one starts (raylet-spawned
  workers, the smoke's children), so they agree without importing JAX
  at boot.

Called before the first compile by the serving engine, the trainer and
`chip_smoke.py`.

The module is also the process's ONE listener for JAX's monitoring
events (`listen()`, which `configure()` calls), and so its set-up clock:
`stats()` gives this process's cache `hits` and `misses` and, since
start, the `seconds` of the three stages a first call waits for, each
from JAX's own enter / exit pair around it:

- `trace`: Python to jaxpr (`/jax/core/compile/jaxpr_trace_duration`);
- `lower`: jaxpr to an MLIR module (`.../jaxpr_to_mlir_module_duration`);
- `backend`: what JAX logs as "XLA compilation": the compiler on a cache
  miss, loading the executable on a hit
  (`.../backend_compile_duration`).

The stages nest (a jitted function calls jitted functions; an eager
operation on a constant runs a whole trace, lower and compile inside the
trace that met it), so a stage counts only when it is the OUTERMOST one
open on its thread: what stands inside it is part of its seconds.  The
three stages of one thread therefore never sum to more than the wall
they stood in.  Beside the process's totals each thread keeps its own
(`thread_totals()`: what `TrackedJit` lays to the program a call
traced, so that a compile on another thread is not laid to the caller).
Nothing fires unless something compiles.
"""

from __future__ import annotations

import os
import threading
from typing import Any, Dict, Tuple

ENV = "JAX_COMPILATION_CACHE_DIR"
_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"
_STAGES = {"/jax/core/compile/jaxpr_trace_duration": "trace",
           "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
           "/jax/core/compile/backend_compile_duration": "backend"}
SECONDS = ("trace", "lower", "backend")

_lock = threading.Lock()
# the process's totals (`stats()`), written under `_lock`
_totals: Dict[str, Any] = {"hits": 0, "misses": 0,
                           "seconds": dict.fromkeys(SECONDS, 0.0)}
# `.mine`: the calling thread's own counts (`_mine()`); gone with it
_local = threading.local()
_configured = False
_listening = False


def default_dir() -> str:
    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    return os.path.join(repo_root, ".jax_cache")


def cache_dir() -> str:
    return os.environ.get(ENV) or default_dir()


def child_env(env: Dict[str, str]) -> Dict[str, str]:
    """Give a child process's environment the cache directory (a child
    reads it at `import jax`; an inherited value is left alone)."""
    env.setdefault(ENV, default_dir())
    return env


def _mine() -> Dict[str, Any]:
    """The calling thread's own counts: stages `open` on it now, and
    what `thread_totals()` gives."""
    mine = getattr(_local, "mine", None)
    if mine is None:
        mine = _local.mine = {"open": 0, "trace": 0.0, "lower": 0.0,
                              "backend": 0.0}
    return mine


def _on_event(event: str, **_) -> None:
    if event in (_HITS, _MISSES):
        with _lock:
            _totals["hits" if event == _HITS else "misses"] += 1


def _on_enter(event: str, _value, **_) -> None:
    if event in _STAGES:
        _mine()["open"] += 1


def _on_duration(event: str, seconds: float, **_) -> None:
    name = _STAGES.get(event)
    if name is None:
        return
    mine = _mine()
    # an exit whose enter came before `listen()` finds nothing open
    mine["open"] = inside = max(mine["open"] - 1, 0)
    if not inside:              # else: in the outer stage's seconds
        mine[name] += seconds
        with _lock:
            _totals["seconds"][name] += seconds


def listen() -> None:
    """Register the listeners, once a process (`configure()` does; so
    does a `TrackedJit` whose owner never configured the cache)."""
    global _listening
    with _lock:
        if _listening:
            return
        _listening = True
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_scalar_listener(_on_enter)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


def configure() -> str:
    """Idempotent; call before this process's first compile."""
    global _configured
    import jax

    if not _configured:
        _configured = True
        if not os.environ.get(ENV):
            # Made here, once: processes that start together otherwise
            # race JAX's own lazy creation of the directory.
            os.makedirs(default_dir(), exist_ok=True)
            jax.config.update("jax_compilation_cache_dir", default_dir())
        listen()
    return cache_dir()


def thread_totals() -> Tuple[float, float, float]:
    """(trace, lower, backend) seconds of the CALLING thread since it
    started; the difference of two readings is what the thread spent
    between them."""
    mine = _mine()
    return mine["trace"], mine["lower"], mine["backend"]


def stats() -> Dict[str, Any]:
    """`hits`, `misses`; `seconds` by `SECONDS` name: the process's
    totals since start, as copies."""
    with _lock:
        return {k: dict(v) if isinstance(v, dict) else v
                for k, v in _totals.items()}
