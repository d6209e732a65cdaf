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
    # a hit is a load: the backend stage still takes its seconds
    assert second["seconds"]["backend"] > 0


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


# ------------------------------------------------ the set-up clock (PR 49)

_CLOCK_PROBE = """
import json, threading, time
import jax, jax.numpy as jnp
from ray_tpu._private import compile_cache
compile_cache.configure()
heard = []              # every exit JAX reports, nested ones too
jax.monitoring.register_event_duration_secs_listener(
    lambda event, secs, **_: heard.append((event, secs)))

@jax.jit
def inner(x):
    return jnp.tanh(x) + 1

@jax.jit
def mid(x):
    return inner(x) * 2

def outer(x):
    # `jnp` helpers are jitted too: each a trace INSIDE the outer trace
    return mid(x) + jnp.asarray(jnp.arange(4.0) * 3)

x = jnp.ones((4,)).block_until_ready()
s0, mine0 = compile_cache.stats(), compile_cache.thread_totals()
del heard[:]
t0 = time.perf_counter()
jax.jit(outer)(x).block_until_ready()
wall = time.perf_counter() - t0
s1, mine1 = compile_cache.stats(), compile_cache.thread_totals()
heard1 = list(heard)
jax.jit(outer)(x)                       # the same program again: nothing
s2 = compile_cache.stats()
th = threading.Thread(
    target=lambda: jax.jit(lambda y: y * 3 + 1)(x).block_until_ready())
th.start(); th.join()
s3, mine3 = compile_cache.stats(), compile_cache.thread_totals()
s3["seconds"]["trace"] += 1e6           # a copy: the next reading stands
s3["hits"] += 7
print(json.dumps({"wall": wall, "s0": s0, "s1": s1, "s2": s2, "s3": s3,
                  "mine": [mine0, mine1, mine3], "heard": heard1,
                  "s4": compile_cache.stats()}))
"""


@pytest.fixture(scope="module")
def clock():
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", JAX_ENABLE_COMPILATION_CACHE="false",
               PYTHONPATH=REPO)
    proc = subprocess.run([sys.executable, "-c", _CLOCK_PROBE], env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("case", ["outermost", "under_the_wall", "again",
                                  "by_thread", "json_and_copies"])
def test_stats_time_the_stages_of_a_compile(clock, case):
    """`stats()["seconds"]`: trace, lower, backend since the process
    started, from JAX's own events; a stage counts only when it is the
    outermost one open on its thread."""
    from ray_tpu._private import compile_cache as cc

    s0, s1, s2, s4 = (clock[k] for k in ("s0", "s1", "s2", "s4"))
    d = {n: s1["seconds"][n] - s0["seconds"][n] for n in s1["seconds"]}
    if case == "outermost":
        # three nested `jit`s and `jnp` helpers inside the trace: JAX
        # reports a trace for each, and ONE counts: the last to end,
        # which held the others
        for event, name in cc._STAGES.items():
            own = [secs for e, secs in clock["heard"] if e == event]
            assert d[name] == pytest.approx(own[-1])
            if name == "trace":
                assert len(own) > 3 and sum(own) > own[-1]
    elif case == "under_the_wall":
        assert all(v >= 0 for v in d.values())
        assert d["trace"] > 0 and d["backend"] > 0
        assert 0 < sum(d.values()) <= clock["wall"]
    elif case == "again":
        assert s2["seconds"] == s1["seconds"]
    elif case == "by_thread":
        mine0, mine1, mine3 = clock["mine"]
        # the main thread's own totals moved with its compile and stood
        # still while another thread compiled; the process's moved
        assert mine1[0] > mine0[0] and mine3 == mine1
        assert clock["s3"]["seconds"]["trace"] > s2["seconds"]["trace"] + 1e5
        assert s4["seconds"]["backend"] > s2["seconds"]["backend"]
    else:
        assert isinstance(s1["hits"], int) and isinstance(s1["misses"], int)
        assert set(s1) == {"hits", "misses", "seconds"}
        assert set(s1["seconds"]) == {"trace", "lower", "backend"}
        # what the probe did to the dict it was handed changed nothing
        assert s4["seconds"]["trace"] < 1e5 and s4["hits"] == s1["hits"]


def test_threads_that_compile_at_once_lose_no_count():
    """The process's totals are written under a lock: more threads than
    cores under a short switch interval lose nothing (a bare
    read-modify-write on the shared dict would)."""
    import threading

    from ray_tpu._private import compile_cache as cc

    trace, backend = (e for e, n in cc._STAGES.items()
                      if n in ("trace", "backend"))
    n_threads, n_events = 4 * (os.cpu_count() or 4), 2000
    before = cc.stats()
    start = threading.Event()

    def work():
        start.wait(10)
        for _ in range(n_events):
            cc._on_enter(trace, 0.0, fun_name="f")
            cc._on_enter(trace, 0.0, fun_name="g")      # nested: not counted
            cc._on_duration(trace, 5.0, fun_name="g")
            cc._on_duration(trace, 1.0, fun_name="f")
            cc._on_enter(backend, 0.0, fun_name="f")
            cc._on_event(cc._HITS)
            cc._on_duration(backend, 0.5, fun_name="f")

    threads = [threading.Thread(target=work) for _ in range(n_threads)]
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        for t in threads:
            t.start()
        start.set()
        for t in threads:
            t.join(timeout=120)
    finally:
        sys.setswitchinterval(old)
    assert not any(t.is_alive() for t in threads)
    after, n = cc.stats(), n_threads * n_events
    assert after["hits"] - before["hits"] == n
    for name, each in (("trace", 1.0), ("backend", 0.5)):
        assert after["seconds"][name] - before["seconds"][name] == \
            pytest.approx(n * each)
    assert after["seconds"]["lower"] == before["seconds"]["lower"]
