"""`_private/compile_cache`: one fixed place for JAX's persistent cache.

JAX's cache configuration is process-global, so every case runs in a child
process with the environment it is about."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_PROBE = """
import json, sys
import jax, jax.numpy as jnp
from ray_tpu._private import compile_cache
where = compile_cache.configure()
compile_cache.configure()           # idempotent
f = jax.jit(lambda x: jnp.tanh(x @ x.T).sum())
f(jnp.ones((64, 64))).block_until_ready()
print(json.dumps({"dir": where,
                  "config": jax.config.jax_compilation_cache_dir,
                  **compile_cache.stats()}))
"""


def _run(env_overrides):
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="true",
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="0",
               PYTHONPATH=REPO)
    env.update(env_overrides)
    proc = subprocess.run([sys.executable, "-c", _PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_env_dir_is_left_alone_and_second_run_hits(tmp_path):
    """With JAX_COMPILATION_CACHE_DIR set the cache lives there, nothing
    is set in code, and a second process finds the first one's work."""
    cache = str(tmp_path / "cache")
    first = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert first["dir"] == cache and first["config"] == cache
    assert first["misses"] >= 1 and first["hits"] == 0
    assert os.listdir(cache)
    second = _run({"JAX_COMPILATION_CACHE_DIR": cache})
    assert second["hits"] >= 1


def test_unset_env_uses_one_fixed_git_ignored_path():
    from ray_tpu._private import compile_cache

    fixed = os.path.join(REPO, ".jax_cache")
    assert compile_cache.default_dir() == fixed
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
    # The cache itself stays off here (as in the whole suite): this case
    # is about where it would live, not about filling the checkout.
    out = _run({"JAX_ENABLE_COMPILATION_CACHE": "false"})
    assert out["dir"] == fixed and out["config"] == fixed
    assert out["hits"] == out["misses"] == 0


@pytest.mark.parametrize("inherited", [None, "/somewhere/else"])
def test_child_env_exports_the_directory(inherited):
    from ray_tpu._private import compile_cache

    env = {} if inherited is None else {compile_cache.ENV: inherited}
    out = compile_cache.child_env(env)
    assert out[compile_cache.ENV] == (inherited
                                      or compile_cache.default_dir())
