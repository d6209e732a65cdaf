"""Deviceless v5e compiles of the benchmark's serving cells with latent
attention (`assistant-decode-moe`, `agent-decode-hybrid`,
`longform-decode-zero-moe`) and `chat-decode`'s insert: the decode tick
and the largest insert of each, as the chip runs them, at the geometry
its files state.  `chip_programs.py` has the rules these files keep, the
fixtures, the one compile a program (`cell_program`, which also holds
each text to its pin) and the cells as shapes (`serving_cell`); every
case that reads one of these cells' programs is in this file, the other
three cells are in `test_chip_compile_kv_cells.py`.
"""

import math

import pytest

import jax
import jax.numpy as jnp

from chip_programs import (     # noqa: F401  (fixtures)
    GIB, V5E_HBM_GIB, cell_program, delta_rule_insert_holds_no_channel_tensor,
    grouped_products_are_the_kernel, on_tpu, one_chip, results_of,
    serving_cell, tails_are_shifted_where_they_lie, topo,
)


@pytest.mark.parametrize("cell", [
    "assistant-decode-moe", "agent-decode-hybrid", "chat-decode"])
def test_paged_attention_leaves_the_inserts_as_they_were(one_chip, cell):
    """The kernel is the decode tick's (`_Paged.attend`, and since PR 36
    the latent models' `_PagedDecode`).  The inserts attend through
    `_History`: their v5e text is the pinned text and holds no call of
    the kernel.  Until PR 46 this compiled each insert a second time
    with `paged_attention.engages` taken away and compared the two; the
    pins are of the text both forms gave (PERF.md section 6, PRs 31 and
    36, have the parent's comparison), and trip on any change, not only
    one made through that selector."""
    insert = cell_program(cell, "insert")                   # pinned
    assert "paged_attention" not in insert.plain


@pytest.mark.parametrize("cell, pool, gathered", [
    ("assistant-decode-moe", (8, 8192, 16, 640), (16384, 16, 640)),
    ("agent-decode-hybrid", (2, 32768, 16, 640), (65536, 16, 640))])
def test_latent_ticks_read_the_pool_through_the_block_table(
        one_chip, on_tpu, cell, pool, gathered):
    """The two latent families' ticks at their cells' geometry (64 x
    4096 over 8192 blocks; 128 x 8192 over 32768): `paged_attention`
    answers "kernel", the tick holds one kernel call a latent layer, no
    instruction has the gathered view's shape (`pool[l, tables]`: 0.21
    and 0.84 GB a layer on the gather path) or the padded rows', and
    none copies, slices or re-stacks the whole pool (1.34 GB): the
    Python layer loop writes a row in place and hands the kernel the
    pool as it lies.  Against the same tick with the selector taken
    away (the gather path's program, compiled beside this one until PR
    46) the temporaries fall from 0.3321 to 0.0213 GiB and from
    1.2961 to 0.0244, by most of one layer's gathered view;
    `test_*_cell_programs_fit_one_v5e` holds tick and insert to the
    chip's memory."""
    eng = serving_cell(cell)
    ec, latent = eng.config, eng.pools["latent"]
    assert latent.shape == pool
    assert eng.model.paged_attention(eng.pools) == "kernel"
    compiled = cell_program(eng.name, "tick")
    text = compiled.text
    assert text.count("paged_attention") >= pool[0]
    results = results_of(text)
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
    m = compiled.memory
    assert m.alias_size_in_bytes >= math.prod(pool) * 2     # in place

    # the gather path's tick read 0.3321 and 1.2961 GiB of temporaries
    # (PR 46, at PR 45's tree): this one's lie under it by most of one
    # layer's gathered view (0.99 and 1.02 of it)
    gather_path_temp = {"assistant-decode-moe": 0.3321,
                        "agent-decode-hybrid": 1.2961}[cell] * GIB
    assert m.temp_size_in_bytes + 0.75 * math.prod(gathered) * 2 \
        < gather_path_temp


@pytest.mark.parametrize("cell, rows", [
    ("chat-decode", (16, 32, 64, 128)),
    ("assistant-decode-moe", (16, 32, 64, 128, 256))])
def test_export_rows_compile_and_fit_beside_the_insert(one_chip, cell, rows):
    """The export gather at every row length a serving cell's engine can
    pick (`EngineConfig.export_rows`: a spill pads its victims to the
    smallest), and the cell's largest insert, as the chip runs it, with
    the largest export row still alive beside it (a spill's row is
    pending while the admission's insert runs): all compile for v5e and
    fit its HBM."""
    eng = serving_cell(cell)
    ec = eng.config
    assert ec.export_rows == rows and len(rows) <= 6
    block_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                      for x in eng.pools.values()) // ec.pool_blocks
    export = jax.jit(eng.programs._export_fn)
    for n in rows:
        m = export.lower(eng.pools, jax.ShapeDtypeStruct(
            (n,), jnp.int32, sharding=one_chip)).compile().memory_analysis()
        # the row's leaves and a few hundred bytes of tuple table
        assert 0 <= m.output_size_in_bytes - n * block_bytes < 4096
        assert m.alias_size_in_bytes == 0       # reads the pool, keeps it
    insert = cell_program(cell, "insert")
    assert insert.hbm_gib + rows[-1] * block_bytes / GIB < V5E_HBM_GIB


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
    eng = serving_cell("assistant-decode-moe")
    mc, published, pools = eng.model_config, eng.published, eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["n_routed_experts"], published["vocab_size"]) \
        == (8, 2048, 128, 128256)
    assert eng.model.grouped_matmul(mc, eng.config.num_slots) == "kernel"

    compiled = cell_program(eng.name, program)
    grouped_products_are_the_kernel(compiled.text,
                                     mc.n_layers - mc.n_dense_layers)
    m = compiled.memory
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in pools.values())
    assert m.alias_size_in_bytes >= pool_bytes          # in place
    assert m.temp_size_in_bytes < 1.5 * GIB
    assert compiled.hbm_gib < V5E_HBM_GIB - 2.0


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
    eng = serving_cell("agent-decode-hybrid")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["num_experts"], published["vocab_size"],
            mc.n_experts, mc.n_kda_layers, mc.n_mla_layers) \
        == (8, 2304, 64, 40960, 256, 6, 2)
    state = eng.state["_slot_state"]
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    compiled = cell_program(eng.name, program)
    grouped_products_are_the_kernel(compiled.text, mc.n_moe_layers)
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


def test_hybrid_tick_steps_live_states_where_they_lie(one_chip, on_tpu):
    """The `agent-decode-hybrid` tick with `ops.kda.engages` answering
    as on the chip: one `kda_step` kernel call a KDA layer over the
    WHOLE donated stack `[6,128,32,128,128]` (1.61 GB), which no
    instruction copies, slices or re-stacks, and no instruction makes a
    layer's `[128,32,128,128]` (268 MB: the plain form, the same tick
    with the selector taken away, cuts one out of the stack, makes a new
    one, selects and writes it back, each a pass over all 128 slots;
    compiled beside this one until PR 46, it read 0.2973 GiB of
    temporaries against this one's 0.0244).  The insert is the pinned
    text, the same with the selector or without (`kda_chunked` alone)."""
    from ray_tpu.ops import kda

    eng = serving_cell("agent-decode-hybrid")
    state = eng.state["_slot_state"]
    stack = state["S"].shape
    assert stack == (6, 128, 32, 128, 128)
    assert kda.engages(*stack[-2:], state["S"].dtype)
    compiled = cell_program(eng.name, "tick")
    text = compiled.text
    assert text.count("kda_step") >= stack[0]
    results = results_of(text)
    assert "parameter" in {op for op, shapes in results
                           if stack in shapes}                # parsed
    assert not [op for op, shapes in results if stack[1:] in shapes]
    moved = [(op, shapes) for op, shapes in results
             if op in ("copy", "copy-start", "copy-done", "dynamic-slice",
                       "dynamic-update-slice", "select", "fusion")
             and stack in shapes]
    assert not moved, moved
    m = compiled.memory
    assert m.alias_size_in_bytes >= math.prod(stack) * 4      # in place
    # under the plain form's temporaries by a layer's new state and more
    assert m.temp_size_in_bytes + math.prod(stack[1:]) * 4 \
        < 0.2973 * GIB
    assert "kda_step" not in cell_program(eng.name, "insert").plain  # pinned


@pytest.mark.parametrize("cell, dk, dv, temp_gib", [("agent-decode-hybrid", 128, 128, 1.5)])
def test_delta_rule_inserts_hold_no_chunk_by_chunk_by_channel_tensor(
        one_chip, on_tpu, cell, dk, dv, temp_gib):
    delta_rule_insert_holds_no_channel_tensor(cell, dk, dv, temp_gib)


@pytest.mark.parametrize("cell, temp_gib", [
    ("assistant-decode-moe", 0.7), ("agent-decode-hybrid", 0.9),
    ("longform-decode-zero-moe", 0.55)])
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
    1.297 → 0.594, 1.247 → 0.952 (LongCat's rest was the grouped
    products' 12,288 rows and the dense feed-forwards; since PR 53 its
    expert layers walk 512 rows a pass, 0.952 → 0.486); the bounds lie
    between."""
    from ray_tpu.models.serving import HISTORY_TILE

    eng = serving_cell(cell)
    ec, H = eng.config, eng.model_config.n_heads
    Pb, S_pad = ec.prefill_buckets[-1], ec.max_seq_len
    compiled = cell_program(eng.name, "insert")
    text = compiled.text
    shapes = set().union(*(shapes for _, shapes in results_of(text)))
    assert any(s[-3:] == (H, Pb, HISTORY_TILE) for s in shapes)   # parsed
    assert not sorted(s for s in shapes if s[-3:] == (H, Pb, S_pad))
    assert text.count(" while(") >= eng.pools["latent"].shape[0]
    assert compiled.memory.temp_size_in_bytes < temp_gib * GIB


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
    a pass of 64 rows of the router's real, held picks, 512 in the
    1024 bucket, where they walked all 1536 and 12288 until PR 53), the
    pool of 8 latent layers is updated in place, and arguments +
    temporaries fit HBM beside the 10.35 GB of weights.  These readings
    chose the top bucket, 1024 (the insert's temporaries at 512 / 1024 /
    2048: 0.92 / 1.25 / 1.84 GiB over 11.51 of arguments; the tick's
    0.02)."""
    from ray_tpu.models.moe import compact_rows

    eng = serving_cell("longform-decode-zero-moe")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    assert (published["num_layers"], published["hidden_size"],
            published["n_routed_experts"], published["zero_expert_num"],
            published["vocab_size"], mc.n_experts, mc.n_held_experts,
            mc.router_width) == (4, 6144, 16, 256, 16384, 512, 16, 768)
    assert eng.pools["latent"].shape == (8, 12288, 16, 640)
    assert model.paged_attention(eng.pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    compiled = cell_program(eng.name, program)
    text = compiled.text
    grouped_products_are_the_kernel(text, mc.n_layers)
    assert (text.count("paged_attention") >= 2 * mc.n_layers) \
        == (program == "tick")
    m = compiled.memory
    pool_bytes = sum(math.prod(x.shape) * x.dtype.itemsize
                     for x in eng.pools.values())
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= pool_bytes          # in place
    assert compiled.hbm_gib < V5E_HBM_GIB
    # the expert layers walk the picks that have a group here (PR 53):
    # products over one pass's rows, no value with a row for every pick,
    # no copy of a bank into the loop, and the temporaries that leaves
    # (insert 0.952 → 0.486 GiB, tick 0.0200 → 0.0144)
    rows = (ec.num_slots if program == "tick"
            else ec.prefill_buckets[-1]) * mc.top_k
    M = compact_rows(rows, mc.n_held_experts, mc.router_width)
    assert (rows, M) == ((1536, 64) if program == "tick" else (12288, 512))
    results = results_of(text)
    shapes = set().union(*(found for _, found in results))
    assert (M, mc.expert_hidden_dim) in shapes and (M, mc.dim) in shapes
    # ((rows, dim) would be no evidence: a dense `w_down` is [12288, 6144])
    assert not {(rows, mc.expert_hidden_dim),
                (rows // mc.top_k, mc.top_k, mc.dim)} & shapes
    bank = {(mc.n_held_experts, mc.dim, mc.expert_hidden_dim),
            (mc.n_held_experts, mc.expert_hidden_dim, mc.dim)}
    assert not [found for op, found in results
                if op in ("copy", "copy-start") and found & bank]
    assert m.temp_size_in_bytes < (0.016 if program == "tick"
                                   else 0.55) * GIB


def test_convolution_tails_are_shifted_where_they_lie(one_chip, on_tpu):
    """`agent-decode-hybrid`'s q ‖ k ‖ v tails (the other five families'
    are in `test_chip_compile_kv_cells.py`): six unrolled KDA layers,
    one call of the step each (`chip_programs.
    tails_are_shifted_where_they_lie`)."""
    assert tails_are_shifted_where_they_lie(
        "agent-decode-hybrid", "conv", 6, 4, 3 * 4096) == 6
