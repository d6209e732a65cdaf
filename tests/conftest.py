"""Test fixtures (reference model: `python/ray/tests/conftest.py`).

JAX runs on the CPU backend with 8 virtual devices — the moral equivalent of
the reference's `_fake_gpus` / gloo tiers (SURVEY §4): sharding/collective
code is exercised on a faked device mesh without TPU hardware. The
environment variables below reach every process the tests spawn; the
`jax.config` updates pin this process too, in case something imported jax
before conftest ran.
"""

import os
import sys

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")
os.environ["JAX_PLATFORMS"] = "cpu"
# The program keeps a persistent compilation cache (_private/compile_cache):
# off for the suite. Tests assert on compile behaviour — trace counts,
# critical paths dominated by a compile, routing under compile-time load —
# and a cache warmed by an earlier test would change what they see; XLA:CPU
# also warns at length on every hit. tests/test_compile_cache.py turns it
# back on, in child processes of its own.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax  # noqa: E402

try:
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
except Exception:
    pass

import pytest  # noqa: E402

# Rarer full collections: with per-module freeze discipline (below) gen2
# scans only objects created since the last module boundary, but the
# default threshold still fires a full pass every ~7k gen1 collections —
# observed burning whole 180s test budgets inside a single collection
# late in the suite. 10x the gen2 trigger; absolute heap growth stays
# bounded by the module-boundary collect.
import gc as _gc  # noqa: E402

_t0, _t1, _t2 = _gc.get_threshold()
_gc.set_threshold(_t0, _t1, _t2 * 10)

# ---------------------------------------------------------------------------
# Per-test timeout (reference enforces 180s via pytest.ini + pytest-timeout;
# that plugin isn't in this image, so use the same SIGALRM technique).
# A single hung test must never wedge the whole suite run.
# ---------------------------------------------------------------------------
TEST_TIMEOUT_S = int(os.environ.get("RAY_TPU_TEST_TIMEOUT", "180"))


class TestTimeoutError(BaseException):
    # BaseException so broad `except Exception` retry loops inside the
    # hung code can't swallow the one-shot alarm (pytest.Failed does the
    # same for the same reason).
    pass


def _install_alarm(phase, item):
    import faulthandler
    import signal

    mark = item.get_closest_marker("timeout")
    limit = int(mark.args[0]) if (mark and mark.args) else TEST_TIMEOUT_S

    def _on_alarm(signum, frame):
        # To a real file: pytest's capture plugin swallows stderr, and a
        # post-mortem needs the stack of the thing that hung.
        try:
            import gc

            with open("/tmp/ray_tpu_test_timeouts.log", "a") as f:
                f.write(f"\n=== {item.nodeid} {phase} "
                        f"exceeded {limit}s ===\n")
                # GC context: past wedges dumped with a collection in
                # progress; counts distinguish "pathological full GC"
                # from "blocked in runtime code".
                f.write(f"gc counts={gc.get_count()} "
                        f"thresholds={gc.get_threshold()} "
                        f"frozen={gc.get_freeze_count()}\n")
                # SIGUSR1 every cluster daemon: their faulthandler dumps
                # land in the session logs, giving the raylet/GCS/worker
                # side of the wedge (the driver stack alone showed only
                # "waiting for an object that never arrives").
                pids = []
                try:
                    for pid in os.listdir("/proc"):
                        if not pid.isdigit():
                            continue
                        try:
                            with open(f"/proc/{pid}/cmdline", "rb") as c:
                                cmd = c.read()
                        except OSError:
                            continue
                        if (b"ray_tpu._private" in cmd
                                or b"ray_tpu/_private" in cmd):
                            os.kill(int(pid), signal.SIGUSR1)
                            # Parked-coroutine stacks too — thread dumps
                            # can't see awaits (rpc.dump_event_loops).
                            os.kill(int(pid), signal.SIGUSR2)
                            pids.append(int(pid))
                except Exception:
                    pass
                f.write(f"signalled daemons (stacks in session logs): "
                        f"{pids}\n")
                # Driver-side loop state: submit-queue depth, drain flag,
                # and every parked coroutine's await stack — the piece
                # past wedge dumps were missing (all OS threads idle in
                # select() while a dispatcher coroutine awaited a lost
                # lease/reply forever).
                try:
                    from ray_tpu._private.rpc import dump_event_loops

                    dump_event_loops(file=f)
                except Exception as e:
                    f.write(f"loop dump failed: {e!r}\n")
                # Session dirs are DELETED at module teardown, taking the
                # dumps with them — preserve the newest sessions' logs
                # now (1.5s for the dumps to flush; the 5s re-fire
                # tolerates it).
                try:
                    import glob as _glob
                    import shutil
                    import time as _time

                    _time.sleep(1.5)
                    dest = (f"/tmp/ray_tpu_wedge_logs/"
                            f"{int(_time.time())}_{os.getpid()}")
                    for d in sorted(
                            _glob.glob("/tmp/ray_tpu/session_*/logs"),
                            key=os.path.getmtime)[-2:]:
                        shutil.copytree(
                            d, os.path.join(dest, os.path.basename(
                                os.path.dirname(d))),
                            dirs_exist_ok=True)
                    f.write(f"logs preserved at {dest}\n")
                except Exception as e:
                    f.write(f"log preservation failed: {e!r}\n")
                faulthandler.dump_traceback(file=f)
        except Exception:
            pass
        raise TestTimeoutError(
            f"{item.nodeid} {phase} exceeded {limit}s")

    old = signal.signal(signal.SIGALRM, _on_alarm)
    # Repeating timer, not a one-shot alarm: a single SIGALRM delivery
    # can be lost while the main thread sits in a non-interruptible
    # C call; the 5s re-fire keeps poking until the handler lands
    # (pytest-timeout's signal method has the same failure mode).
    signal.setitimer(signal.ITIMER_REAL, limit, 5.0)
    return old


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "timeout(seconds): per-test timeout override "
        "(default %ds)" % TEST_TIMEOUT_S)
    # Tier-1 runs `-m 'not slow'` (ROADMAP.md): benchmarks and other
    # long-haul tests opt out of the bounded tier with this marker.
    config.addinivalue_line(
        "markers", "slow: excluded from the bounded tier-1 run")
    # `--dist loadfile` starts the files in the order below, not by their
    # number of cases (xdist's `--loadscope-reorder`, on by default).
    config.option.loadscopereorder = False


# ---------------------------------------------------------------------------
# The order the files start in.  Under `--dist loadfile` xdist hands a worker
# the next FILE when it runs dry, by default the one with the most CASES
# left: a file of seven cases that takes 400 s (three learners on Pendulum;
# seven whole-program compiles for v5e) starts in the run's last third,
# beside every other file of few and long cases, and the run ends when it
# does (PR 46: 1,208 s and three learners over their 180 s, against 837 s
# with the same cases in files of 18 and 25).  Longest first is the schedule
# for a makespan: the files below start in the order of their seconds (junit,
# whole runs under six workers on eight cores), the rest after them as
# collected.  A file missing here costs the run's tail at most its own
# length; refresh the table from a run's junit file when the tail grows.
# ---------------------------------------------------------------------------
_FILE_SECONDS = {
    "test_kimi_linear.py": 249,
    "test_serve_llm.py": 235,
    "test_kimi_linear_engine.py": 229,
    "test_chip_compile_latent_cells.py": 214,
    "test_rllib_algos_continuous.py": 196,
    "test_rllib_offline.py": 180,
    "test_latent_moe.py": 159,
    "test_rllib_algos.py": 156,
    "test_chip_compile_kv_cells.py": 215,
    "test_sambay.py": 155,
    "test_jamba.py": 80,
    "test_podracer.py": 147,
    "test_paged_attention.py": 142,
    "test_conv_moe.py": 140,
    "test_blockdiff_moe.py": 135,
    "test_gdn_hybrid.py": 132,
    "test_models.py": 126,
    "test_window_moe.py": 124,
    "test_kda_step_kernel.py": 123,
    "test_chip_compile.py": 117,
    "test_serve_features.py": 116,
    "test_shortcut_moe.py": 112,
    "test_train.py": 109,
    "test_kv_tiering.py": 108,
    "test_llama_decode.py": 106,
    "test_graftlint.py": 106,
    "test_rllib.py": 93,
    "test_chip_smoke.py": 93,
    "test_rllib_rainbow.py": 85,
    "test_moe_pipeline.py": 76,
    "test_ring_attention.py": 68,
    "test_serve_llm_disagg.py": 65,
    "test_cluster.py": 64,
    "test_ulysses.py": 64,
    "test_rllib_multiagent.py": 61,
    "test_tune.py": 60,
    "test_overlap.py": 57,
    "test_nemotron_h.py": 62,
    "test_moe_held_picks.py": 60,
}
_START_ORDER = {name: at for at, name in enumerate(
    sorted(_FILE_SECONDS, key=_FILE_SECONDS.get, reverse=True))}


def pytest_collection_modifyitems(items):
    items.sort(key=lambda item: _START_ORDER.get(      # stable
        os.path.basename(item.nodeid.split("::", 1)[0]), len(_START_ORDER)))


def _clear_alarm(old):
    import signal

    signal.setitimer(signal.ITIMER_REAL, 0)
    signal.signal(signal.SIGALRM, old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_setup(item):
    old = _install_alarm("setup", item)
    try:
        yield
    finally:
        _clear_alarm(old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_call(item):
    old = _install_alarm("call", item)
    try:
        yield
    finally:
        _clear_alarm(old)


@pytest.hookimpl(hookwrapper=True)
def pytest_runtest_teardown(item, nextitem):
    old = _install_alarm("teardown", item)
    try:
        yield
    finally:
        _clear_alarm(old)


# ---------------------------------------------------------------------------
# The deviceless v5e compiles count themselves (tests/chip_programs.py): a
# program that compiles twice in a run is a minute spent twice. Each xdist
# worker hands its counts to the controller, and the run prints their sum.
# ---------------------------------------------------------------------------
def _v5e_compiles():
    counted = sys.modules.get("chip_programs")
    return {" ".join(key): n for key, n in counted.COMPILES.items()} \
        if counted else {}


def pytest_sessionfinish(session):
    handed = getattr(session.config, "workeroutput", None)
    if handed is not None:                  # an xdist worker
        handed["v5e_compiles"] = _v5e_compiles()


_WORKERS_V5E_COMPILES = []


@pytest.hookimpl(optionalhook=True)
def pytest_testnodedown(node, error):
    _WORKERS_V5E_COMPILES.append(
        getattr(node, "workeroutput", {}).get("v5e_compiles", {}))


def pytest_terminal_summary(terminalreporter):
    import collections

    total = collections.Counter()
    for counts in _WORKERS_V5E_COMPILES + [_v5e_compiles()]:
        total.update(counts)
    if total:
        terminalreporter.write_line(
            "v5e compiles: %d programs, %d compiles%s" % (
                len(total), sum(total.values()), "".join(
                    "\n  %d x %s" % (n, key)
                    for key, n in sorted(total.items()))))


@pytest.fixture(scope="module")
def shared_engine():
    """`shared_engine(key, build)`: the module's one `LLMEngine` under
    `key`, which names what decides its programs (the model's and the
    engine's configuration, the selectors' answers it was built under).
    `build()` makes it the first time a case asks: a tiny model's tick
    and inserts take the CPU compiler 10-30 s an engine, and a case that
    only serves through one and reads it needs no engine of its own.  It
    is handed out drained and a case leaves it drained; a case that must
    end with a broken or refused engine builds its own."""
    built = {}

    def get(key, build):
        if key not in built:
            built[key] = build()
        assert not built[key].has_work(), key
        return built[key]

    yield get
    built.clear()


@pytest.fixture(scope="module", autouse=True)
def _fresh_cluster_per_module():
    """Module isolation guarantee: if a previous module leaked its
    cluster connection (a test that init()'d without tearing down, or a
    teardown that died mid-way), the next module must NOT silently reuse
    it through init(ignore_reinit_error=True) — that was the root of the
    round-3 'suite hangs at serve streaming' cross-module leakage."""
    import ray_tpu

    if ray_tpu.is_initialized():
        try:
            ray_tpu.shutdown()
        except Exception:
            pass
    yield
    # Heap discipline at module boundaries. Without this, gen2 grows
    # across ~40 modules (pytest report caches, jax compilation caches —
    # ~2GB RSS by test ~280) and full collections take seconds EACH,
    # firing every ~70k allocations: late modules (observed: the serve
    # retry loops) burn their entire 180s budgets inside GC pauses.
    # collect() drains what's actually dead, then freeze() moves every
    # survivor out of the collector's working set so later collections
    # only scan objects created since — survivors were effectively
    # immortal anyway.
    import gc

    # unfreeze-collect-freeze: previously frozen entries that a later
    # module turned into cyclic garbage (evicted cache entries) get one
    # reclaim pass per module; survivors go back to the permanent
    # generation where per-test collections never rescan them.
    gc.unfreeze()
    gc.collect()
    gc.freeze()


@pytest.fixture(scope="module")
def ray_start_regular():
    """A real single-node cluster shared by a test module."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=8, num_tpus=0,
                        object_store_memory=256 * 1024 * 1024,
                        ignore_reinit_error=True)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_isolated():
    """A fresh single-node cluster per test (for failure-injection tests)."""
    import ray_tpu

    info = ray_tpu.init(num_cpus=4, num_tpus=0,
                        object_store_memory=128 * 1024 * 1024)
    yield info
    ray_tpu.shutdown()


@pytest.fixture
def ray_start_cluster():
    """Multi-raylet in-process cluster builder (reference: `Cluster`)."""
    from ray_tpu.cluster_utils import Cluster

    cluster = Cluster(initialize_head=False)
    yield cluster
    cluster.shutdown()
