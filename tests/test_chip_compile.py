"""Deviceless v5e compiles of the main path's programs, at real widths.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2, step 3). These
tests hand the jitted functions `ShapeDtypeStruct`s placed on described
v5e devices, so the compiler refuses here — at no chip time — what it
would refuse on the machine: an API the installed JAX dropped, a kernel
that cannot be lowered or partitioned, more VMEM than a kernel may use, a
program that does not fit HBM. Nothing runs: a compile that passes says
nothing about results or speed.

Rules this file keeps (the guide explains each): the topology is described
only inside the module-scoped, non-autouse fixture below — never at
import, in a `skipif` or in `parametrize` — because only one process may
load the TPU library; every compile happens in the test's own process;
the persistent compilation cache is off around them (a deviceless
executable can be written to it but not read back); code that asks
`jax.default_backend()` is steered from the test (`monkeypatch`), not
through an option of the program; all of it lives in this one file.
"""

import functools
import math
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import (
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding,
)

GIB = 2 ** 30
V5E_HBM_GIB = 15.75     # what the v5e compiler itself reports as capacity


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to test with
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def ring_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("x",))


@pytest.fixture
def on_tpu(monkeypatch):
    """`ops.attention` picks kernel-vs-XLA and compiled-vs-interpret from
    the default backend, which is the CPU here: answer for the chip.
    `ops.paged_attention` (the decode tick's kernel) asks the same
    function, so the tick tests below compile what the CHIP runs."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def _placed(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def _without_metadata(text: str) -> str:
    """A compiled program's text less what only names things: the
    per-instruction metadata, the source tables and the kernels'
    serialized modules (their debug locations)."""
    import re

    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n(.+\n)*", "\n", text)
    return re.sub(r'"body":"[^"]+"', '"body":""', text)


# sha256 of `_without_metadata(compiled.as_text())` at PR 42's tree
# (efba6a6), which PR 43 left as it was: it changed how a long prompt's
# pieces are ADMITTED and no device program. A PR that means to change
# one of these programs pins its own text here and says so; one that
# does not (a scheduler change, a clean-up) has this to show it.
# PR 45 meant to change kanana's insert (`_History.attend` walks the
# history in tiles) and re-pinned it; the three others are PR 42's.
PROGRAM_TEXT_SHA256 = {
    ("chat-decode", "tick"):
        "48a91f54a548addd9d951f33258125cd66601f6eb5de512b9f23800388b2ae93",
    ("chat-decode", "insert"):
        "33e1fd0f5f8e39ac4168e9c0371c61ce1c57240d9308ea125ea1b63b1d527fc6",
    ("assistant-decode-moe", "tick"):
        "8f11c202dee0816c9bda3bb0a54e3745760458d31f1d8595b01ede0a6ca1dedb",
    ("assistant-decode-moe", "insert"):
        "7ea6d60d46bd647067feef60f4dff765f30aa43261cc52d3564b29c2b43221de",
}


def _is_the_pinned_text(cell, program, text):
    """Where a cell's tick or largest insert has a pinned hash, the
    compiled v5e text (as the chip runs it: `on_tpu`, no selector
    patched) is that text. The tests that compile these programs
    anyway call this, so the pin costs no compile of its own."""
    import hashlib

    want = PROGRAM_TEXT_SHA256.get((cell, program))
    if want is not None:
        assert hashlib.sha256(
            _without_metadata(text).encode()).hexdigest() == want, (
            cell, program)


def _hbm_gib(compiled) -> float:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes + m.temp_size_in_bytes) / GIB


# ------------------------------------------------------------ flash attention

# (batch, seq, kv heads): the bench shape, and the smoke's train shape with
# Llama-3's 8 KV heads widened to 32 the way models/llama.py::_layer does.
FLASH_SHAPES = {"B16_S1024_H32": (16, 1024, 32), "B2_S1024_kv8": (2, 1024, 8)}


def _flash(q, k, v):
    from ray_tpu.models.llama import _repeat_kv
    from ray_tpu.ops.attention import flash_attention

    rep = q.shape[2] // k.shape[2]
    return flash_attention(q, _repeat_kv(k, rep), _repeat_kv(v, rep),
                           causal=True)


@pytest.mark.parametrize("shape", sorted(FLASH_SHAPES))
@pytest.mark.parametrize("direction", ["forward", "backward"])
def test_flash_attention_compiles_for_v5e(one_chip, on_tpu, shape,
                                          direction):
    B, S, kv = FLASH_SHAPES[shape]
    q = jax.ShapeDtypeStruct((B, S, 32, 128), jnp.bfloat16,
                             sharding=one_chip)
    k = jax.ShapeDtypeStruct((B, S, kv, 128), jnp.bfloat16,
                             sharding=one_chip)
    fn = _flash if direction == "forward" else jax.grad(
        lambda q, k, v: _flash(q, k, v).astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    text = jax.jit(fn).lower(q, k, k).compile().as_text()
    # forward is one kernel; backward re-runs it and adds dk/dv and dq
    assert text.count("tpu_custom_call") >= (
        1 if direction == "forward" else 3)


# ------------------------------------------------------------ ring collectives

def _ring_fns(n):
    from ray_tpu.util.collective import pallas as rk

    kw = dict(n=n, impl="pallas")
    return {
        "allreduce": (lambda x: rk.ring_allreduce(x, "x", **kw), P("x")),
        "reduce_scatter": (
            lambda x: rk.ring_reduce_scatter(x, "x", **kw), P("x")),
        "allgather": (
            lambda x: rk.ring_allgather(x, "x", **kw), P(None, "x")),
        "permute": (
            lambda x: rk.wait_ring_permute(
                rk.start_ring_permute(x, "x", **kw)), P("x")),
        "quantized_allreduce": (
            lambda x: rk.quantized_ring_allreduce(x, "x", **kw), P("x")),
        "quantized_reduce_scatter": (
            lambda x: rk.wait_quantized_ring_reduce_scatter(
                rk.start_quantized_ring_reduce_scatter(x, "x", **kw)),
            P("x")),
    }


# hops a collective takes on a ring of 4 (each is one `ring_hop` kernel)
RING_HOPS = {"allreduce": 6, "reduce_scatter": 3, "allgather": 3,
             "permute": 1, "quantized_allreduce": 6,
             "quantized_reduce_scatter": 3}


@pytest.mark.parametrize("mib_per_shard", [1, 64])
@pytest.mark.parametrize("kind", sorted(RING_HOPS))
def test_ring_collective_compiles_for_v5e(ring_mesh, kind, mib_per_shard):
    """1 MiB and a gradient-sized 64 MiB per shard: the hop kernel keeps
    its operands in HBM, so no message size can exhaust VMEM."""
    n = 4
    fn, out_spec = _ring_fns(n)[kind]
    rows = mib_per_shard * 2 ** 20 // 4 // 128
    x = jax.ShapeDtypeStruct(
        (n * rows, 128), jnp.float32,
        sharding=NamedSharding(ring_mesh, P("x")))
    compiled = jax.jit(shard_map(
        fn, mesh=ring_mesh, in_specs=P("x"), out_specs=out_spec,
        check_vma=False)).lower(x).compile()
    assert compiled.as_text().count("tpu_custom_call") == RING_HOPS[kind]


def test_ring_hop_on_one_axis_of_a_multi_axis_mesh(topo):
    """ZeRO's ring runs over `data` inside data x tensor: neighbours are
    addressed by mesh coordinate, which only the real lowering checks."""
    from ray_tpu.util.collective import pallas as rk

    mesh = Mesh(np.asarray(topo.devices).reshape(2, 2), ("data", "tensor"))
    x = jax.ShapeDtypeStruct((2 * 2048, 128), jnp.bfloat16,
                             sharding=NamedSharding(mesh, P("data")))
    compiled = jax.jit(shard_map(
        lambda a: rk.ring_reduce_scatter(a, "data", n=2, impl="pallas"),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_vma=False)).lower(x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ring_attention_pallas_permute_compiles_for_v5e(topo):
    from ray_tpu.ops.ring_attention import ring_attention_global

    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("sp",))
    q = jax.ShapeDtypeStruct(
        (2, 4 * 1024, 8, 128), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "sp", None, None)))
    compiled = jax.jit(functools.partial(
        ring_attention_global, mesh=mesh, impl="pallas")
    ).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


# ------------------------------------------------ the smoke's whole programs

def _smoke_config(phase):
    import chip_smoke

    return chip_smoke._model_config(chip_smoke.chip_spec(0)[phase]["model"])


def _compiled_paged_tick(config, engine, one_chip):
    """`decode_step_paged` as the engine jits it (pools donated), at
    `engine`'s slots, row length, block size and pool."""
    from ray_tpu.models import llama

    B, bs = engine["num_slots"], engine["kv_block_size"]
    params = _placed(jax.eval_shape(
        lambda: llama.init_params(config, jax.random.key(0))), one_chip)
    pools = _placed(jax.eval_shape(lambda: llama.init_paged_kv_cache(
        config, engine["num_kv_blocks"], bs)), one_chip)
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    compiled = jax.jit(
        lambda p, pools, tables, tok, pos, active: llama.decode_step_paged(
            p, pools, tables, tok, pos, config, active),
        donate_argnums=(1,),
    ).lower(params, pools, ints((B, engine["max_seq_len"] // bs)),
            ints((B,)), ints((B,)),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
            ).compile()
    return compiled, params, pools


def test_paged_decode_program_fits_one_v5e(one_chip, on_tpu):
    """The engine's decode program at chip_smoke.py's serve widths, depth
    and pool: compiles with the paged-attention kernel in it, and
    weights + pool + the program's own temporaries (next to nothing:
    the donated pool is carried through the layer scan, written in
    place and read by the kernel where it lies) fit HBM."""
    import chip_smoke

    compiled, _, _ = _compiled_paged_tick(
        _smoke_config("serve"), chip_smoke.chip_spec(0)["serve"]["engine"],
        one_chip)
    assert 'custom_call_target="tpu_custom_call"' in compiled.as_text()
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 0.5


def test_benchmark_decode_tick_builds_no_repeated_kv(one_chip, on_tpu):
    """The tick of the benchmark's `chat-decode` cell (Mistral-7B-v0.3
    widths, 20 layers of bf16 weights, 32 slots x 2048, 1800 blocks of
    16): the paged-attention kernel reads the live K/V blocks out of
    the pool through the block table, so the compiled program holds the
    kernel and NO dense view of the padded rows -- neither the gathered
    `[32,2048,8,128]` / `[4096,16,8,128]` (0.13 GiB of temporaries until
    PR 31) nor anything the size of their `n_heads / n_kv_heads`-fold
    repeat (4.5-4.7 GiB until PR 25) -- and its temporaries show it."""
    import math
    import re

    from ray_tpu.models.llama import LlamaConfig

    config = LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=20, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, max_seq_len=2048, rope_theta=1e6, norm_eps=1e-5,
        param_dtype=jnp.bfloat16)
    B, S_pad = 32, 2048
    compiled, params, pools = _compiled_paged_tick(
        config, dict(num_slots=B, max_seq_len=S_pad, kv_block_size=16,
                     num_kv_blocks=1800), one_chip)
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text
    held = {x.shape for x in jax.tree.leaves((params, pools))}
    L, NB, bs, kvh, D = pools["k"].shape
    held.add((L, NB, bs * kvh, D))      # the kernel's view: a bitcast
    gathered = B * S_pad * config.n_kv_heads * config.head_dim
    shapes = {tuple(int(d) for d in dims.split(","))
              for dims in re.findall(r"\b[a-z]+\d+\[([\d,]+)\]", text)}
    assert pools["k"].shape in shapes                       # parsed
    # in particular no [32,2048,8,128] and no [4096,16,8,128]
    assert not {s for s in shapes
                if math.prod(s) >= gathered and s not in held}
    assert compiled.memory_analysis().temp_size_in_bytes < 0.01 * GIB


def _serving_cell(cell, one_chip):
    """The engine of one of the benchmark's serving cells as shapes on
    the described chip: what `LLMEngine`'s program functions read of
    `self` (`_model`, `model_config`, `config`), and beside it the
    configuration's file (`published`) and the model's parameters,
    pool and key."""
    import json
    import sys
    import types

    from ray_tpu.serve.llm.engine import EngineConfig

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    import importlib

    with open(os.path.join(root, "BENCHMARK.json")) as f:
        declared = json.load(f)
    name = next(w["config"] for w in declared["workloads"]
                if w["name"] == cell)
    with open(os.path.join(root, next(
            c["file"] for c in declared["configs"]
            if c["name"] == name))) as f:
        published = json.load(f)
    with open(os.path.join(bench, "workloads", cell + ".json")) as f:
        ec = EngineConfig(**json.load(f)["engine"])
    family = importlib.import_module("families." + published["family"])
    mc = family.model_config(published, max_seq_len=ec.max_seq_len,
                             compute_dtype="bfloat16",
                             param_dtype="bfloat16")
    model = mc.serving()
    # a model with a window kind of pool leaves (models/serving.py): the
    # ring's width and that pool's blocks, as `LLMEngine.__init__` has them
    ring, leaves, extra = None, (), {}
    if model.window_kind:
        window, leaves = model.window_kind(mc)
        ring = types.SimpleNamespace(ring=min(
            -(-(window + ec.prefill_buckets[-1]) // ec.kv_block_size),
            ec.max_blocks_per_slot))
        extra = {"window_blocks": ec.num_window_blocks}
    return types.SimpleNamespace(
        _model=model, model_config=mc, config=ec, published=published,
        _ring=ring, _window_leaves=leaves,
        params=_placed(jax.eval_shape(
            lambda: model.init_params(mc, jax.random.key(0))), one_chip),
        pools=_placed(jax.eval_shape(lambda: model.init_pool(
            mc, ec.pool_blocks, ec.kv_block_size, **extra)), one_chip),
        key=_placed(jax.eval_shape(lambda: jax.random.key(0)), one_chip))


def _by_kind(eng, full, window):
    """An argument the engine hands a kind for a model with a window
    kind of pool (`{"full": .., "window": ..}`), else the full kind's."""
    return full if eng._ring is None else {"full": full,
                                           "window": window(eng._ring.ring)}


def _slot_state(eng, one_chip):
    """The model's per-slot state as shapes on the chip, in a list (none
    for a model that keeps none)."""
    model = eng._model
    return [_placed(jax.eval_shape(lambda: model.init_slot_state(
        eng.model_config, eng.config.num_slots)), one_chip)] \
        if model.init_slot_state else []


def _compiled_insert(eng, one_chip):
    """`LLMEngine._insert_fn` at the cell's largest bucket, the slots'
    state donated beside the pools where the model keeps one."""
    from ray_tpu.serve.llm.engine import LLMEngine

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ec = eng.config
    B, Pb = ec.num_slots, ec.prefill_buckets[-1]
    state = _slot_state(eng, one_chip)
    ids = arg(jnp.int32, Pb // ec.kv_block_size)
    return jax.jit(
        functools.partial(LLMEngine._insert_fn, eng),
        donate_argnums=(1, 2, 3) + ((12,) if state else ())).lower(
        eng.params, eng.pools, arg(jnp.int32, B), arg(jnp.int32, B),
        _by_kind(eng, arg(jnp.int32, ec.max_blocks_per_slot),
                 lambda ring: arg(jnp.int32, ring)), arg(jnp.int32),
        arg(jnp.int32, Pb), arg(jnp.int32),
        _by_kind(eng, ids, lambda ring: ids), arg(jnp.int32),
        arg(jnp.float32), eng.key, *state).compile()


def _results(text):
    """(opcode, shapes of its result) of every instruction in a compiled
    program's text, fused computations' own instructions included."""
    import re

    out = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (.*?) ([a-z][a-z\-]*)\(",
                     line)
        if m:
            out.append((m.group(2), {
                tuple(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"\b[a-z]+\d+\[([\d,]*)\]",
                                       m.group(1))}))
    return out


def _grouped_products_are_the_kernel(text, n_moe_layers):
    """A compiled expert program with `ops.grouped_matmul` engaged: three
    kernel calls an expert layer and none of XLA's grouped matmul left
    (`ragged-dot` custom calls, `ragged_dot_tiling` in their config)."""
    assert text.count("grouped_matmul") >= 3 * n_moe_layers
    assert "ragged" not in _without_metadata(text)


def _compiled_cell_tick(eng, one_chip):
    """`LLMEngine._tick_fn` of a serving cell (`_serving_cell`), with
    the model's counters and per-slot state where it has them, donated
    as `_jit_tick` donates."""
    from ray_tpu.serve.llm.engine import LLMEngine

    ec, mc, model = eng.config, eng.model_config, eng._model

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    B = ec.num_slots
    extra = ([_placed(jax.eval_shape(lambda: model.init_counts(mc)),
                      one_chip)] if model.init_counts else []) \
        + _slot_state(eng, one_chip)
    return jax.jit(
        functools.partial(LLMEngine._tick_fn, eng),
        donate_argnums=(1, 3, 4) + ((9,) if model.init_slot_state else ())
    ).lower(
        eng.params, eng.pools,
        _by_kind(eng, arg(jnp.int32, B, ec.max_blocks_per_slot),
                 lambda ring: arg(jnp.int32, B, ring)),
        arg(jnp.int32, B), arg(jnp.int32, B), arg(jnp.bool_, B),
        arg(jnp.float32, B), eng.key, *extra).compile()


def test_benchmark_decode_tick_keeps_one_kv_pool(one_chip, on_tpu):
    """`LLMEngine._tick_fn` at the `chat-decode` cell's geometry, pools,
    tokens and positions donated as `_jit_tick` donates them: the stacked
    pools ride in the layer scan's carry and each layer writes its rows
    at its own index, so the donated pool is the only pool.  No
    instruction copies, slices or re-stacks a whole pool
    `[20,1800,16,8,128]` or one layer's `[1800,16,8,128]` (scanned in as
    `xs` and stacked out as `ys` the program had 2 `copy` and 2
    `dynamic-update-slice` of the first shape, a `copy-done` and the
    scan's slices of the second, and 2.56 GiB of temporaries: PERF.md
    F3).  The paged-attention kernel takes the whole stacked pools as
    they lie (its flat `[20,1800,128,128]` view is a bitcast) and the
    layer index as a scalar: its operand costs no copy either, and with
    the gathered view gone (0.126 GiB until PR 31) the temporaries are
    under a megabyte."""
    eng = _serving_cell("chat-decode", one_chip)
    compiled = _compiled_cell_tick(eng, one_chip)
    pool = eng.pools["k"].shape
    assert pool == (20, 1800, 16, 8, 128) == eng.pools["v"].shape
    text = compiled.as_text()
    assert 'custom_call_target="tpu_custom_call"' in text   # the kernel
    results = _results(text)
    made = {op for op, shapes in results if pool in shapes}
    assert "scatter" in made and "parameter" in made      # parsed
    flat = pool[:2] + (pool[2] * pool[3], pool[4])  # the kernel's view
    assert "bitcast" in {op for op, shapes in results if flat in shapes}
    moved = [(op, shapes) for op, shapes in results
             if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                       "dynamic-update-slice")
             and shapes & {pool, pool[1:], flat, flat[1:]}]
    assert not moved, moved
    m = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in eng.pools.values())
    assert m.alias_size_in_bytes >= pool_bytes            # in place
    assert m.temp_size_in_bytes < 0.13 * GIB


@pytest.mark.parametrize("cell", [
    "assistant-decode-moe", "agent-decode-hybrid", "chat-decode"])
def test_paged_attention_leaves_the_inserts_as_they_were(
        one_chip, on_tpu, monkeypatch, cell):
    """The kernel is the decode tick's (`_Paged.attend`, and since PR 36
    the latent models' `_PagedDecode`).  The inserts attend through
    `_History` and compile for v5e to the same text, metadata apart,
    whether the selector answers as on the chip or is taken away: the
    text the parent compiled (PERF.md section 6, PRs 31 and 36, have
    that comparison)."""
    from ray_tpu.ops import paged_attention

    def compiled():
        return _without_metadata(_compiled_insert(
            _serving_cell(cell, one_chip), one_chip).as_text())

    with_kernel = compiled()
    _is_the_pinned_text(cell, "insert", with_kernel)
    monkeypatch.setattr(paged_attention, "engages", lambda pool: False)
    assert compiled() == with_kernel
    assert "paged_attention" not in with_kernel


@pytest.mark.parametrize("cell, pool, gathered", [
    ("assistant-decode-moe", (8, 8192, 16, 640), (16384, 16, 640)),
    ("agent-decode-hybrid", (2, 32768, 16, 640), (65536, 16, 640))])
def test_latent_ticks_read_the_pool_through_the_block_table(
        one_chip, on_tpu, monkeypatch, cell, pool, gathered):
    """The two latent families' ticks at their cells' geometry (64 x
    4096 over 8192 blocks; 128 x 8192 over 32768): `paged_attention`
    answers "kernel", the tick holds one kernel call a latent layer, no
    instruction has the gathered view's shape (`pool[l, tables]`: 0.21
    and 0.84 GB a layer on the gather path) or the padded rows', and
    none copies, slices or re-stacks the whole pool (1.34 GB): the
    Python layer loop writes a row in place and hands the kernel the
    pool as it lies.  Against the same tick with the selector taken
    away (the parent's program) the temporaries fall from 0.33 to 0.02
    GiB and from 1.31 to 0.30; `test_*_cell_programs_fit_one_v5e` holds
    tick and insert to the chip's memory."""
    from ray_tpu.ops import paged_attention

    eng = _serving_cell(cell, one_chip)
    ec, latent = eng.config, eng.pools["latent"]
    assert latent.shape == pool
    assert eng._model.paged_attention(eng.pools) == "kernel"
    compiled = _compiled_cell_tick(eng, one_chip)
    text = compiled.as_text()
    _is_the_pinned_text(cell, "tick", text)
    assert text.count("paged_attention") >= pool[0]
    results = _results(text)
    padded = (ec.num_slots, ec.max_seq_len, pool[3])
    assert gathered == (ec.num_slots * ec.max_blocks_per_slot,) + pool[2:]
    assert not [op for op, shapes in results
                if shapes & {gathered, padded}]
    made = {op for op, shapes in results if pool in shapes}
    assert "scatter" in made and "parameter" in made        # parsed
    moved = [(op, shapes) for op, shapes in results
             if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                       "dynamic-update-slice", "gather")
             and shapes & {pool, pool[1:]}]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= math.prod(pool) * 2     # in place

    monkeypatch.setattr(paged_attention, "engages", lambda pool: False)
    assert eng._model.paged_attention(eng.pools) == "gather"
    parent = _compiled_cell_tick(eng, one_chip)
    assert any(gathered in shapes for _, shapes in _results(parent.as_text()))
    # the temporaries fall by most of one layer's gathered view (0.99
    # and 0.81 of it)
    assert m.temp_size_in_bytes + 0.75 * math.prod(gathered) * 2 \
        < parent.memory_analysis().temp_size_in_bytes


@pytest.mark.parametrize("program", ["chat-decode tick", "train step"])
def test_grouped_matmul_leaves_the_other_programs_as_they_were(
        topo, one_chip, on_tpu, monkeypatch, program):
    """The kernel is `dropless_moe`'s alone.  `chat-decode`'s tick and a
    train step (the dense decoder; flash, `remat="dots"`) compile for
    v5e to the same text, metadata apart, whether the selector answers
    as on the chip or is taken away, and hold no such call: the text
    the parent compiled (PERF.md section 6, PR 33, has that
    comparison)."""
    import optax

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.ops import grouped_matmul
    from ray_tpu.parallel import build_train_step, create_train_state

    def tick():
        eng = _serving_cell("chat-decode", one_chip)
        return _compiled_cell_tick(eng, one_chip).as_text()

    def train_step():
        config = LlamaConfig(vocab_size=2048, dim=512, n_layers=2,
                             n_heads=4, n_kv_heads=2, hidden_dim=1024,
                             max_seq_len=1024, attn_impl="flash",
                             remat="dots")
        mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
        everywhere = NamedSharding(mesh, P())
        optimizer = optax.adamw(1e-3)
        state = _placed(jax.eval_shape(
            lambda p: create_train_state(p, optimizer), jax.eval_shape(
                lambda: init_params(config, jax.random.key(0)))),
            everywhere)
        batch = {"tokens": jax.ShapeDtypeStruct(
            (2, 1025), jnp.int32, sharding=everywhere)}
        return build_train_step(
            lambda p, b: loss_fn(p, b, config), optimizer, mesh, None,
            everywhere).lower(state, batch).compile().as_text()

    compiled = tick if program == "chat-decode tick" else train_step
    with_kernel = _without_metadata(compiled())
    if program == "chat-decode tick":
        _is_the_pinned_text("chat-decode", "tick", with_kernel)
    monkeypatch.setattr(grouped_matmul, "engages",
                        lambda m, g, k, n, dtype: False)
    assert _without_metadata(compiled()) == with_kernel
    assert "grouped_matmul" not in with_kernel
    assert "ragged" not in with_kernel


@pytest.mark.parametrize("cell, rows", [
    ("chat-decode", (16, 32, 64, 128)),
    ("assistant-decode-moe", (16, 32, 64, 128, 256))])
def test_export_rows_compile_and_fit_beside_the_insert(one_chip, cell, rows):
    """The export gather at every row length a serving cell's engine can
    pick (`EngineConfig.export_rows`: a spill pads its victims to the
    smallest), and the cell's largest insert with the largest export row
    still alive beside it (a spill's row is pending while the admission's
    insert runs): all compile for v5e and fit its HBM."""
    from ray_tpu.serve.llm.engine import LLMEngine

    eng = _serving_cell(cell, one_chip)
    ec = eng.config
    assert ec.export_rows == rows and len(rows) <= 6
    block_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in eng.pools.values()) // ec.pool_blocks
    export = jax.jit(functools.partial(LLMEngine._export_fn, eng))
    for n in rows:
        m = export.lower(eng.pools, jax.ShapeDtypeStruct(
            (n,), jnp.int32, sharding=one_chip)).compile().memory_analysis()
        # the row's leaves and a few hundred bytes of tuple table
        assert 0 <= m.output_size_in_bytes - n * block_bytes < 4096
        assert m.alias_size_in_bytes == 0       # reads the pool, keeps it
    insert = _compiled_insert(eng, one_chip)
    assert _hbm_gib(insert) + rows[-1] * block_bytes / GIB < V5E_HBM_GIB


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_latent_moe_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick and its largest insert at the geometry of
    the benchmark's `assistant-decode-moe` cell (latent attention and
    dropless experts at kanana-2-30b-a3b's published widths, the depth,
    slots, row length, buckets and pool its files state): they compile
    for v5e, the grouped products are `ops.grouped_matmul`'s kernel
    calls (it engages at 384 rows over 128 experts and at the insert's
    12288) with no `ragged-dot` left, the pool is updated in place
    (unrolled layers: no second pool), and arguments + temporaries fit
    HBM.  These readings sized the configuration's depth and the
    cell's pool."""
    eng = _serving_cell("assistant-decode-moe", one_chip)
    mc, published, pools = eng.model_config, eng.published, eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["n_routed_experts"], published["vocab_size"]) \
        == (8, 2048, 128, 128256)
    assert eng._model.grouped_matmul(mc, eng.config.num_slots) == "kernel"

    compiled = (_compiled_cell_tick if program == "tick"
                else _compiled_insert)(eng, one_chip)
    _grouped_products_are_the_kernel(compiled.as_text(),
                                     mc.n_layers - mc.n_dense_layers)
    m = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in pools.values())
    assert m.alias_size_in_bytes >= pool_bytes          # in place
    assert m.temp_size_in_bytes < 1.5 * GIB
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 2.0


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_hybrid_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick and its largest insert at the geometry of
    the benchmark's `agent-decode-hybrid` cell (KDA state by slot beside
    the paged latent pool, 64 of 256 experts held, at
    Kimi-Linear-48B-A3B's published widths; the depth, slots, row
    length, buckets and pool its files state): they compile for v5e,
    the grouped products are `ops.grouped_matmul`'s kernel calls (it
    engages at 1024 rows of which a quarter are held and at the
    insert's 16384) with no `ragged-dot` left, the
    latent pool AND the slots' recurrent state are updated in place
    (donated, static layer index), and arguments + temporaries fit HBM.
    These readings sized the configuration's depth and the cell's
    slots and pool."""
    eng = _serving_cell("agent-decode-hybrid", one_chip)
    ec, mc, model, published = (eng.config, eng.model_config, eng._model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["num_experts"], published["vocab_size"],
            mc.n_experts, mc.n_kda_layers, mc.n_mla_layers) \
        == (8, 2304, 64, 40960, 256, 6, 2)
    state, = _slot_state(eng, one_chip)
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    compiled = (_compiled_cell_tick if program == "tick"
                else _compiled_insert)(eng, one_chip)
    _grouped_products_are_the_kernel(compiled.as_text(), mc.n_moe_layers)
    m = compiled.memory_analysis()
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", _hbm_gib(compiled), "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 0.5


def test_hybrid_tick_steps_live_states_where_they_lie(
        one_chip, on_tpu, monkeypatch):
    """The `agent-decode-hybrid` tick with `ops.kda.engages` answering
    as on the chip: one `kda_step` kernel call a KDA layer over the
    WHOLE donated stack `[6,128,32,128,128]` (1.61 GB), which no
    instruction copies, slices or re-stacks, and no instruction makes a
    layer's `[128,32,128,128]` (268 MB: the plain form, the same tick
    with the selector taken away, cuts one out of the stack, makes a new
    one, selects and writes it back, each a pass over all 128 slots).
    The insert is the same text either way (`kda_chunked` alone)."""
    from ray_tpu.ops import kda

    eng = _serving_cell("agent-decode-hybrid", one_chip)
    state, = _slot_state(eng, one_chip)
    stack = state["S"].shape
    assert stack == (6, 128, 32, 128, 128)
    assert kda.engages(*stack[-2:], state["S"].dtype)
    compiled = _compiled_cell_tick(eng, one_chip)
    text = compiled.as_text()
    assert text.count("kda_step") >= stack[0]
    results = _results(text)
    assert "parameter" in {op for op, shapes in results
                           if stack in shapes}                # parsed
    assert not [op for op, shapes in results if stack[1:] in shapes]
    moved = [(op, shapes) for op, shapes in results
             if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                       "dynamic-update-slice", "select", "fusion")
             and stack in shapes]
    assert not moved, moved
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes >= math.prod(stack) * 4      # in place
    insert = _without_metadata(_compiled_insert(eng, one_chip).as_text())
    assert "kda_step" not in insert

    monkeypatch.setattr(kda, "engages", lambda dk, dv, dtype: False)
    parent = _compiled_cell_tick(eng, one_chip)
    assert [op for op, shapes in _results(parent.as_text())
            if stack[1:] in shapes]
    # the temporaries fall by a layer's new state and more
    assert m.temp_size_in_bytes + math.prod(stack[1:]) * 4 \
        < parent.memory_analysis().temp_size_in_bytes
    assert _without_metadata(
        _compiled_insert(eng, one_chip).as_text()) == insert


@pytest.mark.parametrize("cell, dk, temp_gib", [
    ("agent-decode-hybrid", 128, 1.5), ("reason-decode-gdn-hybrid", 96, 0.5)])
def test_delta_rule_inserts_hold_no_chunk_by_chunk_by_channel_tensor(
        one_chip, on_tpu, cell, dk, temp_gib):
    """The largest insert of both delta-rule cells (Kimi at its 2048
    bucket, one decay a key channel; Olmo-Hybrid at 512, one a head):
    no float32 result, fused computations' own included, has both of
    `ops.kda.kda_chunked`'s chunk axes AND the channel axis `[.., 64,
    64, dk]` (2.1 GB a KDA layer at Kimi's bucket, which
    `_decayed_products` replaces by matrix products over 16-row
    sub-blocks: the diagonal blocks' `[.., 32, 16, 128]`, the k rows
    over the q rows, inside a reduction's fusion is what is left of it;
    the a-head arm never had one, its decays are `[.., 64, 64]`).
    Temporaries: 1.30 GiB against the 2.41 the `[C, C, dk]` form took
    for Kimi (the history's softmax holds them now), 0.45 for
    Olmo-Hybrid."""
    from ray_tpu.ops import kda

    eng = _serving_cell(cell, one_chip)
    C, b = kda.CHUNK, kda._SOLVE_BLOCK
    assert eng.config.prefill_buckets[-1] % C == 0
    compiled = _compiled_insert(eng, one_chip)
    shapes = set().union(*(
        shapes for _, shapes in _results(compiled.as_text())))
    assert any(s[-2:] == (C, C) for s in shapes)            # parsed
    if dk == 128:
        assert any(s[-3:] == (2 * b, b, dk) for s in shapes)
    assert not sorted(s for s in shapes if s[-3:] == (C, C, dk))
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gib * GIB


@pytest.mark.parametrize("cell, temp_gib", [
    ("assistant-decode-moe", 0.7), ("agent-decode-hybrid", 0.9),
    ("longform-decode-zero-moe", 1.1)])
def test_latent_inserts_hold_no_padded_score_tensor(
        one_chip, on_tpu, cell, temp_gib):
    """The largest insert of the three latent-attention cells (kanana
    and Kimi at their 2048 bucket over 4096 and 8192 padded rows,
    LongCat at 1024 over 5120): `latent_moe._History.attend` walks the
    history in tiles under a `while` a latent layer, so no result, fused
    computations' own included, has the heads beside `(Pb, S_pad)` (the
    plain form's `[1, H, Pb, S_pad]` float32 scores, 1.07 / 2.15 / 1.34
    GB a layer; `(Pb, S_pad)` alone is also kanana's `[2048, 4096]`
    attention output), and a tile's `[1, H, Pb, HISTORY_TILE]` are
    there.  Temporaries, deviceless, parent → PR 45: 0.917 → 0.430 GiB,
    1.297 → 0.594, 1.247 → 0.952 (LongCat's rest is the grouped
    products' 12,288 rows and the dense feed-forwards); the bounds lie
    between."""
    from ray_tpu.models.serving import HISTORY_TILE

    eng = _serving_cell(cell, one_chip)
    ec, H = eng.config, eng.model_config.n_heads
    Pb, S_pad = ec.prefill_buckets[-1], ec.max_seq_len
    compiled = _compiled_insert(eng, one_chip)
    text = compiled.as_text()
    shapes = set().union(*(shapes for _, shapes in _results(text)))
    assert any(s[-3:] == (H, Pb, HISTORY_TILE) for s in shapes)   # parsed
    assert not sorted(s for s in shapes if s[-3:] == (H, Pb, S_pad))
    assert text.count(" while(") >= eng.pools["latent"].shape[0]
    assert compiled.memory_analysis().temp_size_in_bytes < temp_gib * GIB


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_conv_moe_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick and its largest insert at the geometry of
    the benchmark's `compose-decode-conv-moe` cell (gated convolutions
    with a two-row tail by slot beside GQA heads of 64 in a K ‖ V paged
    pool, a whole bank of 32 experts, at LFM2-8B-A1B's published widths;
    the depth, slots, row length, buckets and pool its files state):
    they compile for v5e, `paged_attention` and `grouped_matmul` answer
    "kernel", the tick holds one paged-attention call an attention
    layer, tick and insert three `ops.grouped_matmul` calls an expert
    layer and no `ragged-dot`, the pool (2048 B a token a layer) AND
    the slots' tails are updated in place, and arguments + temporaries
    fit HBM."""
    eng = _serving_cell("compose-decode-conv-moe", one_chip)
    ec, mc, model, published = (eng.config, eng.model_config, eng._model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["num_experts"], published["vocab_size"],
            mc.n_conv_layers, mc.n_attn_layers, mc.n_moe_layers, mc.head_dim,
            ec.num_slots) == (14, 2048, 32, 65536, 11, 3, 12, 64, 256)
    assert model.paged_attention(pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    kv = pools["kv"]
    assert math.prod(kv.shape[3:]) * kv.dtype.itemsize == 2048
    state, = _slot_state(eng, one_chip)
    B, nb = ec.num_slots, ec.max_blocks_per_slot
    if program == "tick":
        compiled = _compiled_cell_tick(eng, one_chip)
        text = compiled.as_text()
        assert text.count("paged_attention") >= mc.n_attn_layers
        assert text.count('custom_call_target="tpu_custom_call"') \
            >= 3 * mc.n_moe_layers + mc.n_attn_layers
        # no padded [B, S_pad] view of the pool is built
        padded = (B, nb * ec.kv_block_size) + kv.shape[3:]
        assert not any(padded in shapes for _, shapes in _results(text))
    else:
        compiled = _compiled_insert(eng, one_chip)
    _grouped_products_are_the_kernel(compiled.as_text(), mc.n_moe_layers)
    m = compiled.memory_analysis()
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", _hbm_gib(compiled), "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 0.5


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_window_moe_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick and its largest insert at the geometry of
    the benchmark's `mixed-decode-window-moe` cell (window and full GQA
    layers over two kinds of paged pool, a table of 1152 blocks and a
    ring of 256 a slot, 128 experts beside a shared one and a head
    200,192 wide, at Trinity-Mini's published widths; the depth, slots,
    row length, buckets and both pools its files state): they compile
    for v5e, `paged_attention` and `grouped_matmul` answer "kernel", the
    tick holds one paged-attention call a layer (one of them the full
    form) and builds no padded view of either pool, tick and insert
    three `ops.grouped_matmul` calls an expert layer and no `ragged-dot`,
    both kinds of pool (2048 B a token a layer, a token's four KV heads
    side by side in one row) are updated in place and NOT copied to be
    re-tiled (as `[bs, 4, 128]` blocks each insert copied every pool in
    and out: 2.39 GiB of temporaries),
    the insert at 2048 over an 18,432-row history keeps its temporaries
    under 1.5 GiB (float32 scores over the whole history would be 4.5),
    and arguments + temporaries fit HBM."""
    eng = _serving_cell("mixed-decode-window-moe", one_chip)
    ec, mc, model, published = (eng.config, eng.model_config, eng._model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["num_experts"], published["vocab_size"],
            mc.n_window_layers, mc.n_full_layers, mc.n_moe_layers,
            mc.window, mc.n_kv_heads, ec.num_slots, ec.max_seq_len,
            eng._ring.ring) == (5, 2048, 128, 200192, 4, 1, 4, 2048, 4, 64,
                                18432, 256)
    assert model.paged_attention(pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    assert pools["k"].shape == (1, ec.pool_blocks, 16, 4 * 128)
    assert pools["v_w"].shape == (4, ec.num_window_blocks, 16, 4 * 128)
    B = ec.num_slots
    if program == "tick":
        compiled = _compiled_cell_tick(eng, one_chip)
        text = compiled.as_text()
        assert text.count("paged_attention") >= mc.n_layers
        assert text.count('custom_call_target="tpu_custom_call"') \
            >= 3 * mc.n_moe_layers + mc.n_layers
        row = pools["k"].shape[3:]
        padded = {(B, n * ec.kv_block_size) + row
                  for n in (ec.max_blocks_per_slot, eng._ring.ring)}
        assert not any(padded & shapes for _, shapes in _results(text))
    else:
        compiled = _compiled_insert(eng, one_chip)
    _grouped_products_are_the_kernel(compiled.as_text(), mc.n_moe_layers)
    m = compiled.memory_analysis()
    kept = sum(math.prod(x.shape) * x.dtype.itemsize for x in pools.values())
    print(program, "GiB", _hbm_gib(compiled), "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert m.temp_size_in_bytes < 1.5 * GIB
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 0.5


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_gdn_hybrid_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick (128 slots x 4096) and its largest insert
    (512) at the geometry of the benchmark's `reason-decode-gdn-hybrid`
    cell (gated delta-rule state by slot beside full attention of 30 K/V
    heads in two paged pools, at Olmo-Hybrid-7B's published widths; the
    depth, slots, row length, buckets and pool its files state): they
    compile for v5e; `paged_attention` answers "kernel" and the tick
    holds one call an attention layer over pools `[2, NB, 16, 30, 128]`
    that no instruction copies (the compiler lays a block of 30 heads
    head by head and the kernel takes that view: a bitcast); the
    delta-rule state steps through one `kda_step` kernel call a layer
    over the WHOLE donated stack `[6, 128, 15, 96, 384]`, two heads a
    row, which no instruction copies, slices or re-stacks and whose
    bytes in HBM are the mathematics' 2,211,840 a slot a layer (no
    padded lane: the stack's argument is exactly that many); pools and
    state are updated in place, and arguments + temporaries fit HBM."""
    eng = _serving_cell("reason-decode-gdn-hybrid", one_chip)
    ec, mc, model, published = (eng.config, eng.model_config, eng._model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["vocab_size"], mc.n_gdn_layers, mc.n_attn_layers,
            mc.n_kv_heads, mc.gdn_key_dim, mc.gdn_value_dim, mc.rope_theta,
            ec.num_slots, ec.max_seq_len, ec.prefill_buckets[-1]) \
        == (8, 3840, 100352, 6, 2, 30, 96, 192, None, 128, 4096, 512)
    assert model.paged_attention(pools) == "kernel"
    pool = pools["k"].shape
    assert pool == (2, ec.pool_blocks, 16, 30, 128) == pools["v"].shape
    state, = _slot_state(eng, one_chip)
    stack = state["S"].shape
    assert stack == (6, 128, 15, 96, 384)
    from ray_tpu.ops import kda

    assert kda.engages(*stack[-2:], state["S"].dtype)
    compiled = (_compiled_cell_tick if program == "tick"
                else _compiled_insert)(eng, one_chip)
    text = compiled.as_text()
    results = _results(text)
    m = compiled.memory_analysis()
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", _hbm_gib(compiled), "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 0.5
    # the state's bytes are the mathematics': what the program's
    # arguments weigh is the shapes' own product, no padded tile
    args = math.prod(stack) * 4 + sum(
        math.prod(x.shape) * x.dtype.itemsize
        for x in jax.tree.leaves((eng.params, pools, state["conv"])))
    assert math.prod(stack[2:]) * 4 == 2211840
    assert abs(m.argument_size_in_bytes - args) < 0.01 * GIB
    layouts = set(re.findall(
        r"f32\[6,128,15,96,384\]\{([^}]*)\}", text))
    assert layouts <= {"4,3,2,1,0:T(8,128)", "4,3,2,1,0"} \
        and "4,3,2,1,0:T(8,128)" in layouts, layouts        # whole tiles
    if program == "insert":
        plain = _without_metadata(text)
        assert "kda_step" not in plain and "paged_attention" not in plain
        assert m.temp_size_in_bytes < 1.5 * GIB
        return
    assert text.count("kda_step") >= stack[0]
    assert text.count("paged_attention") >= mc.n_attn_layers
    assert "parameter" in {op for op, shapes in results if stack in shapes}
    assert not [op for op, shapes in results if stack[1:] in shapes]
    flat = pool[:2] + (pool[2] * pool[3], pool[4])  # the kernel's view
    assert "bitcast" in {op for op, shapes in results if flat in shapes}
    moved = [(op, shapes) for op, shapes in results
             if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                       "dynamic-update-slice", "select", "fusion")
             and shapes & {stack, pool, pool[1:], flat, flat[1:]}]
    assert not moved, moved
    # no padded [B, S_pad] view of a pool is built
    padded = (ec.num_slots, ec.max_seq_len) + pool[3:]
    assert not any(padded in shapes for _, shapes in results)
    assert m.temp_size_in_bytes < 0.5 * GIB


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_shortcut_moe_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick and its largest insert at the geometry of
    the benchmark's `longform-decode-zero-moe` cell (two latent
    sublayers and two dense feed-forwards a layer, 16 of 512 routed
    experts held beside 256 zero-compute ones, at LongCat-Flash-Chat's
    published widths; the depth, slots, row length, buckets and pool its
    files state): they compile for v5e, both kernel paths answer
    "kernel" (the paged latent kernel at 64 query heads on a 640-wide
    row, one call a SUBLAYER; the grouped products at 6144 x 2048 over
    1536 rows of which the router's real, held picks are filled), the
    pool of 8 latent layers is updated in place, and arguments +
    temporaries fit HBM beside the 10.35 GB of weights.  These readings
    chose the top bucket, 1024 (the insert's temporaries at 512 / 1024 /
    2048: 0.92 / 1.25 / 1.84 GiB over 11.51 of arguments; the tick's
    0.02)."""
    eng = _serving_cell("longform-decode-zero-moe", one_chip)
    ec, mc, model, published = (eng.config, eng.model_config, eng._model,
                                eng.published)
    assert (published["num_layers"], published["hidden_size"],
            published["n_routed_experts"], published["zero_expert_num"],
            published["vocab_size"], mc.n_experts, mc.n_held_experts,
            mc.router_width) == (4, 6144, 16, 256, 16384, 512, 16, 768)
    assert eng.pools["latent"].shape == (8, 12288, 16, 640)
    assert model.paged_attention(eng.pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    compiled = (_compiled_cell_tick if program == "tick"
                else _compiled_insert)(eng, one_chip)
    text = compiled.as_text()
    _grouped_products_are_the_kernel(text, mc.n_layers)
    assert (text.count("paged_attention") >= 2 * mc.n_layers) \
        == (program == "tick")
    m = compiled.memory_analysis()
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in eng.pools.values())
    print(program, "GiB", _hbm_gib(compiled), "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= pool_bytes          # in place
    assert _hbm_gib(compiled) < V5E_HBM_GIB


def test_train_step_holds_flash_kernel_and_fits_one_v5e(topo, on_tpu):
    """One whole `build_train_step` program at chip_smoke.py's train
    widths, depth and batch, on a one-device mesh."""
    import optax

    import chip_smoke
    from ray_tpu.models.llama import init_params, loss_fn
    from ray_tpu.parallel import (
        batch_sharding, build_train_step, create_train_state,
        llama_param_shardings,
    )

    train = chip_smoke.chip_spec(0)["train"]
    config = _smoke_config("train")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    optimizer = optax.adamw(1e-3)
    params_shape = jax.eval_shape(
        lambda: init_params(config, jax.random.key(0)))
    step = build_train_step(
        lambda p, b: loss_fn(p, b, config), optimizer, mesh,
        llama_param_shardings(config, mesh), batch_sharding(mesh),
        params_shape=params_shape)
    state = _placed(jax.eval_shape(
        lambda p: create_train_state(p, optimizer), params_shape),
        NamedSharding(mesh, P()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (train["batch_size"], train["seq_len"]), jnp.int32,
        sharding=batch_sharding(mesh))}
    compiled = step.lower(state, batch).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
    assert _hbm_gib(compiled) < V5E_HBM_GIB - 0.5


def test_scopes_change_only_names_in_the_v5e_train_step(topo, on_tpu,
                                                         monkeypatch):
    """`jax.named_scope` in the model and the step builder (PR 24) must
    leave the chip's program alone.  With the flash kernels in it, the
    compiled text with and without the scopes differs in names only: the
    per-instruction metadata, the kernel calls' instruction names
    (`%closed_call.6` becomes `%attn.39`) and the debug locations inside
    each kernel's serialized module; the kernels themselves are equal."""
    import base64
    import contextlib
    import re

    import optax

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    from ray_tpu.models.llama import LlamaConfig, init_params, loss_fn
    from ray_tpu.parallel import build_train_step, create_train_state

    config = LlamaConfig(vocab_size=2048, dim=512, n_layers=2, n_heads=4,
                         n_kv_heads=2, hidden_dim=1024, max_seq_len=1024,
                         attn_impl="flash", remat="dots")
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    optimizer = optax.adamw(1e-3)
    params_shape = jax.eval_shape(
        lambda: init_params(config, jax.random.key(0)))
    state = _placed(jax.eval_shape(
        lambda p: create_train_state(p, optimizer), params_shape),
        NamedSharding(mesh, P()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, 1025), jnp.int32, sharding=NamedSharding(mesh, P()))}

    def compiled_text():
        step = build_train_step(lambda p, b: loss_fn(p, b, config),
                                optimizer, mesh, None,
                                NamedSharding(mesh, P()))
        return step.lower(state, batch).compile().as_text()

    scoped = compiled_text()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = compiled_text()
    assert "/optimizer/" in scoped and "/optimizer/" not in bare

    def kernels(text):
        out = []
        for body in re.findall(r'"body":"([^"]+)"', text):
            ctx = jax_mlir.make_ir_context()
            tpu.register_dialect(ctx)
            ctx.allow_unregistered_dialects = True   # `stable_mosaic`
            with ctx:
                out.append(ir.Module.parse(base64.b64decode(body))
                           .operation.get_asm(enable_debug_info=False))
        return out

    assert len(kernels(scoped)) >= 3 and kernels(scoped) == kernels(bare)

    def rest(text):
        return re.sub(r"%(attn|closed_call|rematted_computation|checkpoint)"
                      r"\.\d+", "%kernel", _without_metadata(text))

    assert rest(scoped) == rest(bare)


def test_carried_stacks_leave_the_benchmark_train_step_as_it_was(
        topo, on_tpu, monkeypatch):
    """`pretrain-1chip`'s `jit_train_step` (2 layers at Mistral-7B-v0.3
    widths, float32 state, flash, `remat="dots"`, 3 x 4097 tokens) runs
    `_trunk` with a cache that keeps no stack: the empty carry beside `x`
    and the empty leaves beside the weights must add nothing.  The trunk
    written the plain way below -- `x` alone carried, the weights alone
    scanned, as the program was before the pools moved into the carry --
    compiles for v5e to the same text, metadata stripped and instructions
    renumbered by first appearance."""
    import json
    import re
    import sys

    import optax
    from jax import lax

    from ray_tpu.models import llama
    from ray_tpu.parallel import (
        batch_sharding, build_train_step, create_train_state,
        llama_param_shardings,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    bench = os.path.join(root, "benchmarks")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from families import dense_decoder

    with open(os.path.join(
            bench, "configs", "mistral-7b-v0.3-train-1chip.json")) as f:
        published = json.load(f)
    with open(os.path.join(bench, "workloads", "pretrain-1chip.json")) as f:
        sequences = json.load(f)["job"]["batch_sequences"]
    config = dense_decoder.model_config(
        published, max_seq_len=4096,
        compute_dtype=published["precision"]["compute"],
        param_dtype=published["precision"]["parameters"],
        attn_impl=published["train"]["attn_impl"],
        remat=published["train"]["remat"])
    assert (config.n_layers, config.remat, sequences) == (2, "dots", 3)
    mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
    optimizer = optax.adamw(1e-3)
    params_shape = jax.eval_shape(
        lambda: llama.init_params(config, jax.random.key(0)))
    state = _placed(jax.eval_shape(
        lambda p: create_train_state(p, optimizer), params_shape),
        NamedSharding(mesh, P()))
    batch = {"tokens": jax.ShapeDtypeStruct(
        (sequences, 4097), jnp.int32, sharding=batch_sharding(mesh))}

    def compiled_text():
        step = build_train_step(
            lambda p, b: llama.loss_fn(p, b, config), optimizer, mesh,
            llama_param_shardings(config, mesh), batch_sharding(mesh),
            params_shape=params_shape)
        text = _without_metadata(step.lower(state, batch).compile().as_text())
        names = {}
        return re.sub(
            r"%[A-Za-z_][\w.\-]*",
            lambda m: names.setdefault(m.group(0), "%%i%d" % len(names)),
            text)

    def plain_trunk(c, params, tokens, rope, cache, scoring=False):
        assert scoring and cache.stacks == () == cache.leaves
        x = llama.embed_lookup(params["embed"].astype(c.dtype), tokens)

        def layer_fn(x, p):
            x, _, _, aux = llama._layer(c, p, x, rope, cache, (), ())
            return x, aux

        layer_fn = jax.checkpoint(
            layer_fn,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
        with jax.named_scope("layers"):
            return lax.scan(layer_fn, x, params["layers"])

    carried = compiled_text()
    monkeypatch.setattr(llama, "_trunk", plain_trunk)
    plain = compiled_text()
    assert carried.count("tpu_custom_call") >= 3
    assert carried == plain

