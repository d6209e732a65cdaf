"""Deviceless v5e compiles of the main path's programs, at real widths:
the kernels, the ring collectives, `chip_smoke.py`'s programs and the
train steps.  `chip_programs.py` has the rules these files keep, the
fixtures and the one compile a program; the benchmark's serving cells
are in `test_chip_compile_latent_cells.py` and
`test_chip_compile_kv_cells.py`.
"""

import functools
import math
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from chip_programs import (     # noqa: F401  (fixtures)
    GIB, V5E_HBM_GIB, cell_program, is_the_pinned_text, on_tpu, one_chip,
    placed, program, renumbered, results_of, serving_cell, topo,
)


@pytest.fixture(scope="module")
def ring_mesh(topo):
    return Mesh(np.asarray(topo.devices).reshape(4), ("x",))


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


@pytest.mark.parametrize("kind, keys", [("window", 2048 + 1024),
                                        ("full", 18432)])
def test_flash_prefill_compiles_at_the_1024_bucket(one_chip, on_tpu, kind,
                                                   keys):
    """`mixed-decode-window-moe` engages `ops.attention.flash_prefill`
    at two buckets: the 2048 bucket's two kernels compile inside the
    cell's largest insert (`test_chip_compile_kv_cells.py`), the 1024
    bucket's here, a window layer's piece over 3072 key rows and the
    full layer's over the slot's 18,432; the two small buckets stay
    under `prefill_engages`' sizes."""
    from ray_tpu.ops import attention

    assert attention._prefill_blocks(1024, keys) == (512, 1024)
    assert attention.prefill_engages(1024, 128, keys)
    assert not attention.prefill_engages(512, 128, 2048 + 512)

    def arg(dtype, *shape):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    text = jax.jit(functools.partial(
        attention.flash_prefill,
        window=2048 if kind == "window" else None)).lower(
            arg(jnp.bfloat16, 1024, 32, 128),
            arg(jnp.bfloat16, keys, 4, 128), arg(jnp.bfloat16, keys, 4, 128),
            arg(jnp.int32), arg(jnp.int32), arg(jnp.int32)
    ).compile().as_text()
    assert text.count("tpu_custom_call") >= 1


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


def _paged_tick(name, config, engine, one_chip):
    """`decode_step_paged` as the engine jits it (pools donated), at
    `engine`'s slots, row length, block size and pool: the program, and
    the shapes of its parameters and pools."""
    from ray_tpu.models import llama

    B, bs = engine["num_slots"], engine["kv_block_size"]
    params = placed(jax.eval_shape(
        lambda: llama.init_params(config, jax.random.key(0))), one_chip)
    pools = placed(jax.eval_shape(lambda: llama.init_paged_kv_cache(
        config, engine["num_kv_blocks"], bs)), one_chip)
    ints = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                             sharding=one_chip)
    compiled = program((name, "decode_step_paged"), lambda: jax.jit(
        lambda p, pools, tables, tok, pos, active: llama.decode_step_paged(
            p, pools, tables, tok, pos, config, active),
        donate_argnums=(1,),
    ).lower(params, pools, ints((B, engine["max_seq_len"] // bs)),
            ints((B,)), ints((B,)),
            jax.ShapeDtypeStruct((B,), jnp.bool_, sharding=one_chip)
            ).compile())
    return compiled, params, pools


def test_paged_decode_program_fits_one_v5e(one_chip):
    """The engine's decode program at chip_smoke.py's serve widths, depth
    and pool: compiles with the paged-attention kernel in it, and
    weights + pool + the program's own temporaries (next to nothing:
    the donated pool is carried through the layer scan, written in
    place and read by the kernel where it lies) fit HBM."""
    import chip_smoke

    compiled, _, _ = _paged_tick(
        "chip_smoke serve", _smoke_config("serve"),
        chip_smoke.chip_spec(0)["serve"]["engine"], one_chip)
    assert 'custom_call_target="tpu_custom_call"' in compiled.text
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


def test_benchmark_decode_tick_builds_no_repeated_kv(one_chip):
    """The tick of the benchmark's `chat-decode` cell (Mistral-7B-v0.3
    widths, 20 layers of bf16 weights, 32 slots x 2048, 1800 blocks of
    16): the paged-attention kernel reads the live K/V blocks out of
    the pool through the block table, so the compiled program holds the
    kernel and NO dense view of the padded rows -- neither the gathered
    `[32,2048,8,128]` / `[4096,16,8,128]` (0.13 GiB of temporaries until
    PR 31) nor anything the size of their `n_heads / n_kv_heads`-fold
    repeat (4.5-4.7 GiB until PR 25) -- and its temporaries show it."""
    from ray_tpu.models.llama import LlamaConfig

    config = LlamaConfig(
        vocab_size=32768, dim=4096, n_layers=20, n_heads=32, n_kv_heads=8,
        hidden_dim=14336, max_seq_len=2048, rope_theta=1e6, norm_eps=1e-5,
        param_dtype=jnp.bfloat16)
    B, S_pad = 32, 2048
    compiled, params, pools = _paged_tick(
        "chat-decode widths", config,
        dict(num_slots=B, max_seq_len=S_pad, kv_block_size=16,
             num_kv_blocks=1800), one_chip)
    text = compiled.text
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
    assert compiled.memory.temp_size_in_bytes < 0.01 * GIB


def test_benchmark_decode_tick_keeps_one_kv_pool(one_chip, on_tpu):
    """`Programs._tick_fn` at the `chat-decode` cell's geometry, pools,
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
    eng = serving_cell("chat-decode")
    compiled = cell_program(eng.name, "tick")
    pool = eng.pools["k"].shape
    assert pool == (20, 1800, 16, 8, 128) == eng.pools["v"].shape
    text = compiled.text
    assert 'custom_call_target="tpu_custom_call"' in text   # the kernel
    results = results_of(text)
    made = {op for op, shapes in results if pool in shapes}
    assert "scatter" in made and "parameter" in made      # parsed
    flat = pool[:2] + (pool[2] * pool[3], pool[4])  # the kernel's view
    assert "bitcast" in {op for op, shapes in results if flat in shapes}
    moved = [(op, shapes) for op, shapes in results
             if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                       "dynamic-update-slice")
             and shapes & {pool, pool[1:], flat, flat[1:]}]
    assert not moved, moved
    m = compiled.memory
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in eng.pools.values())
    assert m.alias_size_in_bytes >= pool_bytes            # in place
    assert m.temp_size_in_bytes < 0.13 * GIB


# ---------------------------------------------------------- the train steps

def _train_step(name, config, topo, batch_shape, *, sharded):
    """`build_train_step` of the dense decoder under adamw on a
    one-device mesh, compiled once under `name`; `sharded` as
    `pretrain-1chip` and the smoke build it (`llama_param_shardings`,
    `batch_sharding`), else everything replicated."""
    import optax

    from ray_tpu.models.llama import init_params, loss_fn
    from ray_tpu.parallel import (
        batch_sharding, build_train_step, create_train_state,
        llama_param_shardings,
    )

    def build():
        mesh = Mesh(np.asarray(topo.devices[:1]), ("data",))
        everywhere = NamedSharding(mesh, P())
        optimizer = optax.adamw(1e-3)
        params_shape = jax.eval_shape(
            lambda: init_params(config, jax.random.key(0)))
        if sharded:
            step = build_train_step(
                lambda p, b: loss_fn(p, b, config), optimizer, mesh,
                llama_param_shardings(config, mesh), batch_sharding(mesh),
                params_shape=params_shape)
        else:
            step = build_train_step(lambda p, b: loss_fn(p, b, config),
                                    optimizer, mesh, None, everywhere)
        state = placed(jax.eval_shape(
            lambda p: create_train_state(p, optimizer), params_shape),
            everywhere)
        batch = {"tokens": jax.ShapeDtypeStruct(
            batch_shape, jnp.int32,
            sharding=batch_sharding(mesh) if sharded else everywhere)}
        return step.lower(state, batch).compile()

    return program((name, "train step"), build)


def test_train_step_holds_flash_kernel_and_fits_one_v5e(topo):
    """One whole `build_train_step` program at chip_smoke.py's train
    widths, depth and batch, on a one-device mesh."""
    import chip_smoke

    train = chip_smoke.chip_spec(0)["train"]
    compiled = _train_step(
        "chip_smoke train", _smoke_config("train"), topo,
        (train["batch_size"], train["seq_len"]), sharded=True)
    assert compiled.text.count("tpu_custom_call") >= 3
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


def _small_train_step(topo):
    """Two small layers of the dense decoder (flash, `remat="dots"`),
    2 x 1025 tokens: the train step two accepted PRs were held to."""
    from ray_tpu.models.llama import LlamaConfig

    return _train_step("two small layers", LlamaConfig(
        vocab_size=2048, dim=512, n_layers=2, n_heads=4, n_kv_heads=2,
        hidden_dim=1024, max_seq_len=1024, attn_impl="flash",
        remat="dots"), topo, (2, 1025), sharded=False)


def _scope_names_apart(plain):
    """A train step's text (metadata stripped) less the kernel calls'
    instruction names, which come from the scope they are traced in."""
    return re.sub(r"%(attn|closed_call|rematted_computation|checkpoint)"
                  r"\.\d+", "%kernel", plain)


@pytest.mark.parametrize("program", ["chat-decode tick", "train step"])
def test_grouped_matmul_leaves_the_other_programs_as_they_were(
        topo, program):
    """The kernel is `dropless_moe`'s alone.  `chat-decode`'s tick and a
    train step (the dense decoder; flash, `remat="dots"`) compile for
    v5e to the pinned text and hold no such call.  Until PR 46 this
    compiled each a second time with `grouped_matmul.engages` taken
    away and compared the two; the pins are of the text both forms gave
    (PERF.md section 6, PR 33, has the parent's comparison), and trip on
    any change, not only one made through that selector."""
    if program == "chat-decode tick":
        plain = cell_program("chat-decode", "tick").plain     # pinned
    else:
        plain = _small_train_step(topo).plain
        is_the_pinned_text("two small layers", "train step, scope names "
                           "apart", _scope_names_apart(plain))
    assert "grouped_matmul" not in plain
    assert "ragged" not in plain


def test_scopes_change_only_names_in_the_v5e_train_step(topo):
    """`jax.named_scope` in the model and the step builder (PR 24) must
    leave the chip's program alone.  With the flash kernels in it, the
    compiled text with and without the scopes differed in names only:
    the per-instruction metadata, the kernel calls' instruction names
    (`%closed_call.6` becomes `%attn.39`) and the debug locations inside
    each kernel's serialized module; the kernels themselves were equal.
    Until PR 46 this compiled the step a second time with
    `jax.named_scope` taken away; what it compared is pinned now, and is
    the text the scopeless form gave: the text less those names, and the
    kernels' modules printed without debug locations."""
    import base64

    from jax._src.interpreters import mlir as jax_mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    scoped = _small_train_step(topo)
    assert "/optimizer/" in scoped.text

    kernels = []
    for body in re.findall(r'"body":"([^"]+)"', scoped.text):
        ctx = jax_mlir.make_ir_context()
        tpu.register_dialect(ctx)
        ctx.allow_unregistered_dialects = True   # `stable_mosaic`
        with ctx:
            kernels.append(ir.Module.parse(base64.b64decode(body))
                           .operation.get_asm(enable_debug_info=False))
    assert len(kernels) >= 3
    is_the_pinned_text("two small layers", "train step, its kernels",
                       "\n".join(kernels))
    is_the_pinned_text("two small layers", "train step, scope names apart",
                       _scope_names_apart(scoped.plain))


def test_carried_stacks_leave_the_benchmark_train_step_as_it_was(topo):
    """`pretrain-1chip`'s `jit_train_step` (2 layers at Mistral-7B-v0.3
    widths, float32 state, flash, `remat="dots"`, 3 x 4097 tokens) runs
    `_trunk` with a cache that keeps no stack: the empty carry beside `x`
    and the empty leaves beside the weights must add nothing.  Until PR
    46 the trunk was written the plain way here -- `x` alone carried,
    the weights alone scanned, as the program was before the pools moved
    into the carry -- and compiled beside `llama._trunk`'s; the pin is
    of the text both gave, metadata stripped and instructions renumbered
    by first appearance."""
    import json
    import os
    import sys

    from chip_programs import BENCH

    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    from families import dense_decoder

    with open(os.path.join(
            BENCH, "configs", "mistral-7b-v0.3-train-1chip.json")) as f:
        published = json.load(f)
    with open(os.path.join(BENCH, "workloads", "pretrain-1chip.json")) as f:
        sequences = json.load(f)["job"]["batch_sequences"]
    config = dense_decoder.model_config(
        published, max_seq_len=4096,
        compute_dtype=published["precision"]["compute"],
        param_dtype=published["precision"]["parameters"],
        attn_impl=published["train"]["attn_impl"],
        remat=published["train"]["remat"])
    assert (config.n_layers, config.remat, sequences) == (2, "dots", 3)
    carried = _train_step("pretrain-1chip", config, topo,
                          (sequences, 4097), sharded=True)
    assert carried.text.count("tpu_custom_call") >= 3
    is_the_pinned_text("pretrain-1chip", "train step, renumbered",
                       renumbered(carried.plain))
