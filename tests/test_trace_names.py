"""Names in the profiler's trace (PR 24): each tracked program lowers
under its own name, the engine step and the train loop write host spans
through `observability.profiling.trace_span`, and the model's operations
carry scope names that change nothing in the compiled program."""

import contextlib
import glob
import os
import re
import time

import pytest

STEP_CHILDREN = ["ctrl", "admit", "first_token_wait", "tick_dispatch",
                 "spill_land", "tick_wait", "emit", "gauges"]


# ------------------------------------------------------- program names

@pytest.mark.parametrize("name, module", [
    ("x", "jit_x"), ("llm_engine_tick", "jit_llm_engine_tick"),
    ("a b/c", "jit_a_b_c")])
def test_tracked_jit_lowers_under_its_own_name(name, module):
    import jax.numpy as jnp

    from ray_tpu.observability import tracked_jit

    f = tracked_jit(lambda x: x * 2, name=name)
    g = tracked_jit(lambda x: x + 1, name=name + "_other")
    x = jnp.ones((4,))
    assert f"module @{module} " in f.lower(x).as_text()
    assert f"module @{module}_other " in g.lower(x).as_text()
    f.clear_cache()
    before = f.traces
    f(x), f(x)
    assert f.traces == before + 1          # one trace per program


# ------------------------------------------------------------ host spans

def _host_events(trace_dir, prefixes):
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                         dict(e.stats)) for e in line.events
                        if e.name.startswith(prefixes)]
    return sorted(out, key=lambda e: (e[1], -e[2]))


@contextlib.contextmanager
def _profiled(trace_dir):
    import jax

    jax.profiler.start_trace(str(trace_dir))
    try:
        yield
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def engine_traces(tmp_path_factory):
    """One tiny paged engine, traced twice: two requests in a roomy pool
    (nothing evicted), then distinct prompts until the prefix cache has
    to give blocks back (evict + spill)."""
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine, Request

    config = LlamaConfig.tiny()
    engine = LLMEngine(init_params(config, jax.random.key(0)), config,
                       EngineConfig(num_slots=2, max_seq_len=64,
                                    prefill_buckets=(16,), kv_layout="paged",
                                    kv_block_size=8, num_kv_blocks=12,
                                    decode_block=1))

    def serve(base, n):
        hs = [engine.submit(Request(
            prompt=[(base + 7 * i + j) % 200 + 1 for j in range(16)],
            max_tokens=6)) for i in range(n)]
        engine.drain()
        assert all(h.finish_reason == "length" for h in hs)

    serve(0, 1)                                    # compiles, untraced
    out = {}
    for case, base, n in (("roomy", 50, 2), ("evicting", 100, 8)):
        d = tmp_path_factory.mktemp(case)
        ev0 = engine.stats()["prefix_cache"]["evictions"]
        with _profiled(d):
            serve(base, n)
        out[case] = (_host_events(str(d), ("llm_engine.",)),
                     engine.stats()["prefix_cache"]["evictions"] - ev0)
    return out


@pytest.mark.parametrize("case", ["roomy", "evicting"])
def test_engine_step_spans_nest_and_cover(engine_traces, case):
    events, evicted = engine_traces[case]
    steps = [e for e in events if e[0] == "llm_engine.step"]
    assert len(steps) >= 4
    covered = total = 0.0
    for _, s0, s1, _ in steps:
        inside = [e for e in events if e[1] >= s0 and e[2] <= s1
                  and e[0] != "llm_engine.step"]
        # direct children: those no other span of the step encloses
        kids = [e for e in inside if not any(
            o is not e and o[1] <= e[1] and e[2] <= o[2] for o in inside)]
        order = [e[0].split(".", 1)[1] for e in kids]
        assert order == [n for n in STEP_CHILDREN if n in order], order
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        if "tick_dispatch" in order:
            covered += sum(e[2] - e[1] for e in kids)
            total += s1 - s0
    assert total and 1.0 - covered / total < 0.05
    names = {e[0] for e in events}
    assert {"llm_engine.admit_one", "llm_engine.insert_dispatch"} <= names
    admits = [e for e in events if e[0] == "llm_engine.admit"]
    assert sum(int(e[3]["admitted"]) for e in admits) >= 2
    assert ("llm_engine.spill" in names) == (case == "evicting")
    assert ("llm_engine.spill_land" in names) == (case == "evicting")
    assert ("llm_engine.evict" in names) == (evicted > 0) == (case == "evicting")
    spills = [e for e in events if e[0] == "llm_engine.spill"]
    for sp in spills:
        assert int(sp[3]["evicted_blocks"]) > 0 and int(sp[3]["bytes"]) > 0
        assert any(e[0] == "llm_engine.evict" and e[1] <= sp[1]
                   and sp[2] <= e[2] for e in events)
    # every spilled block lands, in the step that spilled it: behind the
    # dispatched tick (a child of the step) or before a later admission
    # of the same step looks the tier up (under its admit_one)
    lands = [e for e in events if e[0] == "llm_engine.spill_land"]
    assert sum(int(e[3]["blocks"]) for e in lands) == sum(
        int(e[3]["evicted_blocks"]) for e in spills)
    assert sum(int(e[3]["bytes"]) for e in lands) == sum(
        int(e[3]["bytes"]) for e in spills)
    for ld in lands:
        assert int(ld[3]["blocks"]) > 0 and int(ld[3]["ready"]) in (0, 1)
        step = next(s for s in steps if s[1] <= ld[1] and ld[2] <= s[2])
        assert any(step[1] <= e[1] < ld[1] for e in spills)


@pytest.mark.parametrize("case", ["roomy", "evicting"])
def test_tick_dispatch_carries_its_live_rows(engine_traces, case):
    """`rows=` beside `live=`: the KV rows the tick about to go out has
    to read, here 16 prompt tokens + the 1..6 emitted of every live
    slot (the yardstick of a reader for the paged-attention kernel)."""
    ticks = [e[3] for e in engine_traces[case][0]
             if e[0] == "llm_engine.tick_dispatch"]
    assert ticks
    for args in ticks:
        live, rows = int(args["live"]), int(args["rows"])
        assert 1 <= live <= 2 and 17 * live <= rows <= 22 * live


@pytest.mark.parametrize("case, want", [
    ("landed", 3.0),            # landings of 2, 3 and 40 ms: the median
    ("no_admission", 0.0),      # a window of ticks alone still reports
    ("spill_cut", 0.0),         # the trace ended between spill and landing
    ("parent", None),           # a program that never writes the span
    ("no_spans", None)])
def test_spill_land_reader_reports_every_window(monkeypatch, case, want):
    """`benchmarks/layer_metrics/spill_land_ms.py`: every traced window
    of a program that lands its spills gives a number (the driver holds
    the change to that), and the parent's gives none."""
    import importlib.util

    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks")
    monkeypatch.syspath_prepend(bench)
    import program_spans as PS

    spec = importlib.util.spec_from_file_location(
        "lm_spill_land_ms", os.path.join(bench, "layer_metrics",
                                         "spill_land_ms.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod._program_lands()             # this tree writes mod.SPAN
    ms = 1_000_000
    spans = [("llm_engine.step", 0, 100 * ms, {}),
             ("llm_engine.tick_dispatch", 10 * ms, ms, {"live": "2"}),
             ("llm_engine.tick_wait", 60 * ms, 30 * ms, {})]
    if case in ("landed", "parent"):
        spans += [("llm_engine.admit_one", ms, 8 * ms, {}),
                  ("llm_engine.spill", 2 * ms, ms, {"evicted_blocks": "3"})]
    if case == "landed":
        spans += [(mod.SPAN, t * ms, d * ms, {"blocks": "3"})
                  for t, d in ((11, 2), (15, 3), (19, 40))]
    if case == "spill_cut":
        spans = [("llm_engine.admit_one", ms, 8 * ms, {}),
                 ("llm_engine.spill", 2 * ms, ms, {"evicted_blocks": "3"})]
    if case == "parent":
        monkeypatch.setattr(mod, "_program_lands", lambda: False)
    run = {"window": (0, 200 * ms), "trace": object(),
           "program": None if case == "no_spans" else PS.Program(
               sorted(spans, key=lambda s: (s[1], -s[2])), [])}
    got = mod.read(run)
    assert (got is None) if want is None else got == pytest.approx(want)


def test_step_phases_write_train_spans(tmp_path):
    from ray_tpu.observability.goodput import StepPhases

    with _profiled(tmp_path):
        sp = StepPhases(step=7, worker="t")
        with sp.phase("compute"):
            time.sleep(0.001)
        with sp.phase("weight_publish"):
            pass
        row = sp.finish(publish=False)
    assert row["phases"]["compute"] > 0
    ev = _host_events(str(tmp_path), ("train.",))
    assert [e[0] for e in ev] == ["train.step", "train.compute",
                                  "train.weight_publish"]
    step = ev[0]
    assert int(step[3]["step"]) == 7
    assert all(step[1] <= e[1] and e[2] <= step[2] for e in ev[1:])


def test_compile_stands_in_the_trace(tmp_path):
    import jax.numpy as jnp

    from ray_tpu.observability import tracked_jit

    f = tracked_jit(lambda x: x - 1, name="trace_names_compile")
    with _profiled(tmp_path):
        f(jnp.ones((3,)))
        f(jnp.ones((3,)))
    ev = _host_events(str(tmp_path), ("jit.compile",))
    assert len(ev) == 1 and ev[0][3]["fn"] == "trace_names_compile"
    assert float(ev[0][3]["seconds"]) > 0


def test_trace_span_off_is_cheap():
    """No profiler session: an entry is the annotation object and a flag
    test (about 1.4 us here; 5 us is generous for a shared runner)."""
    from ray_tpu.observability.profiling import trace_span

    n = 10_000
    best = float("inf")
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(n):
            with trace_span("llm_engine.tick_dispatch", live=3):
                pass
        best = min(best, (time.perf_counter() - t0) / n)
    assert best < 5e-6, best


# ---------------------------------------------------------------- scopes

def _strip_metadata(hlo_text):
    """Compiled text without what names carry: per-instruction metadata,
    the stack-frame tables and the module's name."""
    text = re.sub(r", metadata=\{[^}]*\}", "", hlo_text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|StackFrames)"
                  r"\n(.+\n)*", "\n", text)
    return re.sub(r"^HloModule \S+", "HloModule m", text)


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _compile_with_and_without_scopes(build, monkeypatch):
    import jax

    scoped = build().compile().as_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    return scoped, build().compile().as_text()


def _has_scope(op_names, scope):
    return any(scope in re.split(r"[/()]", n) for n in op_names)


def test_decode_step_scopes_are_metadata_only(monkeypatch):
    import jax
    import jax.numpy as jnp

    from ray_tpu.models.llama import (LlamaConfig, decode_step_paged,
                                      init_paged_kv_cache, init_params)

    c = LlamaConfig.tiny()
    params = jax.eval_shape(lambda: init_params(c, jax.random.key(0)))
    pools = jax.eval_shape(lambda: init_paged_kv_cache(c, 16, 4))
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731

    def build():
        return jax.jit(lambda p, kv, t, tok, pos: decode_step_paged(
            p, kv, t, tok, pos, c)).lower(params, pools, i32(2, 8), i32(2),
                                          i32(2))

    scoped, bare = _compile_with_and_without_scopes(build, monkeypatch)
    names = _op_names(scoped)
    for scope in ("layers", "kv_gather", "kv_write", "attn", "mlp",
                  "lm_head"):
        assert _has_scope(names, scope), scope
    assert not _has_scope(_op_names(bare), "kv_gather")
    assert _strip_metadata(scoped) == _strip_metadata(bare)


def test_engine_tick_carries_the_sample_scope():
    import jax

    from ray_tpu.models.llama import LlamaConfig, init_params
    from ray_tpu.serve.llm.engine import EngineConfig, LLMEngine

    c = LlamaConfig.tiny()
    e = LLMEngine(init_params(c, jax.random.key(0)), c, EngineConfig(
        num_slots=2, max_seq_len=32, prefill_buckets=(8,),
        kv_layout="paged", kv_block_size=8))
    lowered = e._jit_tick.lower(
        e.params, e._cache, e._tables.copy(), e._tok, e._pos,
        e._active.copy(), e._temp.copy(), e._key)
    assert "module @jit_llm_engine_tick " in lowered.as_text()
    names = _op_names(lowered.compile().as_text())
    for scope in ("sample", "layers", "kv_gather", "attn"):
        assert _has_scope(names, scope), scope


def test_train_step_scopes_are_metadata_only(monkeypatch):
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel.train_step import (build_train_step,
                                             create_train_state)

    c = LlamaConfig.tiny()
    mesh = Mesh(np.asarray(jax.devices()[:1]), ("data",))
    opt = optax.adamw(1e-3)
    state = create_train_state(init_params(c, jax.random.key(0)), opt)
    batch = {"tokens": jnp.zeros((2, 33), jnp.int32)}

    def build():
        step = build_train_step(partial(loss_fn, config=c), opt, mesh, None,
                                NamedSharding(mesh, P()))
        return step.lower(state, batch)

    assert "module @jit_train_step " in build().as_text()
    scoped, bare = _compile_with_and_without_scopes(build, monkeypatch)
    names = _op_names(scoped)
    for scope in ("layers", "attn", "mlp", "loss_head", "optimizer"):
        assert _has_scope(names, scope), scope
    # the backward pass keeps the scopes
    assert any("transpose(jvp(loss_head))" in n for n in names)
    assert any("transpose(jvp(layers))" in n and "/attn/" in n
               for n in names)
    assert not _has_scope(_op_names(bare), "optimizer")
    assert _strip_metadata(scoped) == _strip_metadata(bare)
