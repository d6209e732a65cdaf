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
`chip_smoke.py`.  `stats()` counts this process's cache hits and misses
from JAX's own monitoring events.
"""

from __future__ import annotations

import os
from typing import Dict

ENV = "JAX_COMPILATION_CACHE_DIR"
_HITS = "/jax/compilation_cache/cache_hits"
_MISSES = "/jax/compilation_cache/cache_misses"

_counts = {"hits": 0, "misses": 0}
_configured = False


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


def _on_event(event: str, **_) -> None:
    if event == _HITS:
        _counts["hits"] += 1
    elif event == _MISSES:
        _counts["misses"] += 1


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
        jax.monitoring.register_event_listener(_on_event)
    return cache_dir()


def stats() -> Dict[str, int]:
    return dict(_counts)
