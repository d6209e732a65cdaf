"""Deviceless v5e compiles of the benchmark's serving cells that keep K
and V heads in paged pools (`compose-decode-conv-moe`,
`mixed-decode-window-moe`, `reason-decode-gdn-hybrid`,
`think-decode-ssm-yoco`, `swarm-decode-ssd-moe`): the decode tick
and the largest insert of each, as the chip runs them, at the geometry
its files state.  `chip_programs.py` has the rules these files keep, the
fixtures, the one compile a program (`cell_program`, which also holds
each text to its pin) and the cells as shapes (`serving_cell`); every
case that reads one of these cells' programs is in this file, the other
cells are in `test_chip_compile_latent_cells.py`.
"""

import math
import re

import pytest

import jax

from chip_programs import (     # noqa: F401  (fixtures)
    GIB, V5E_HBM_GIB, cell_program, delta_rule_insert_holds_no_channel_tensor,
    grouped_products_are_the_kernel, on_tpu, one_chip, results_of,
    serving_cell, tails_are_shifted_where_they_lie, topo,
)


def insert_attends_through_the_kernel(compiled, calls, Pb):
    """A compiled insert whose pieces attend through
    `ops.attention.flash_prefill`: at least `calls` of the kernel's
    custom calls, each under the scope `attn`, and no float32 result,
    fused computations' own included, of a block of scores `[KV heads,
    heads a group, Pb, keys]` (the XLA loop's, 268 MB a step at the 2048
    bucket), nor of its running maximum, sum or accumulator."""
    kernels = [line for line in compiled.text.splitlines()
               if "custom-call(" in line and "flash_prefill" in line]
    assert len(kernels) >= calls
    assert all(re.search(r'op_name="[^"]*/attn/[^"]*flash_prefill', line)
               for line in kernels)
    f32 = {tuple(int(d) for d in dims.split(","))
           for dims in re.findall(r"f32\[([\d,]+)\]", compiled.text)}
    assert any(len(s) == 3 and Pb in s for s in f32)        # parsed
    assert not sorted(s for s in f32 if len(s) == 4 and s[-2] == Pb
                      and s[-1] != 128)


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
    eng = serving_cell("compose-decode-conv-moe")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
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
    state = eng.state["_slot_state"]
    B, nb = ec.num_slots, ec.max_blocks_per_slot
    if program == "tick":
        compiled = cell_program(eng.name, "tick")
        text = compiled.text
        assert text.count("paged_attention") >= mc.n_attn_layers
        assert text.count('custom_call_target="tpu_custom_call"') \
            >= 3 * mc.n_moe_layers + mc.n_attn_layers
        # no padded [B, S_pad] view of the pool is built
        padded = (B, nb * ec.kv_block_size) + kv.shape[3:]
        assert not any(padded in shapes for _, shapes in results_of(text))
    else:
        compiled = cell_program(eng.name, "insert")
    grouped_products_are_the_kernel(compiled.text, mc.n_moe_layers)
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


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
    the insert at 2048 over an 18,432-row history attends through five
    `flash_prefill` calls under `attn` and holds no block of float32
    scores, its temporaries under 0.35 GiB (0.31; 0.45 with the XLA
    loop's score blocks; float32 scores over the whole history would be
    4.5), and arguments + temporaries fit HBM."""
    eng = serving_cell("mixed-decode-window-moe")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["num_experts"], published["vocab_size"],
            mc.n_window_layers, mc.n_full_layers, mc.n_moe_layers,
            mc.window, mc.n_kv_heads, ec.num_slots, ec.max_seq_len,
            eng.programs.ring_blocks) \
        == (5, 2048, 128, 200192, 4, 1, 4, 2048, 4, 64, 18432, 256)
    assert model.paged_attention(pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    assert pools["k"].shape == (1, ec.pool_blocks, 16, 4 * 128)
    assert pools["v_w"].shape == (4, ec.num_window_blocks, 16, 4 * 128)
    B = ec.num_slots
    if program == "tick":
        compiled = cell_program(eng.name, "tick")
        text = compiled.text
        assert text.count("paged_attention") >= mc.n_layers
        assert text.count('custom_call_target="tpu_custom_call"') \
            >= 3 * mc.n_moe_layers + mc.n_layers
        row = pools["k"].shape[3:]
        padded = {(B, n * ec.kv_block_size) + row
                  for n in (ec.max_blocks_per_slot, eng.programs.ring_blocks)}
        assert not any(padded & shapes for _, shapes in results_of(text))
    else:
        compiled = cell_program(eng.name, "insert")
        assert model.insert_attention(
            mc, 0, ec.prefill_buckets[-1], ec.max_seq_len)[0] == "kernel"
        insert_attends_through_the_kernel(compiled, mc.n_layers,
                                          ec.prefill_buckets[-1])
    grouped_products_are_the_kernel(compiled.text, mc.n_moe_layers)
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize for x in pools.values())
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert m.temp_size_in_bytes < (1.5 if program == "tick" else 0.35) * GIB
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


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
    eng = serving_cell("reason-decode-gdn-hybrid")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
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
    state = eng.state["_slot_state"]
    stack = state["S"].shape
    assert stack == (6, 128, 15, 96, 384)
    from ray_tpu.ops import kda

    assert kda.engages(*stack[-2:], state["S"].dtype)
    compiled = cell_program(eng.name, program)
    text = compiled.text
    results = results_of(text)
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # both in place
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5
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
        plain = compiled.plain
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


@pytest.mark.parametrize("cell, dk, dv, temp_gib", [("reason-decode-gdn-hybrid", 96, 192, 0.5)])
def test_delta_rule_inserts_hold_no_chunk_by_chunk_by_channel_tensor(
        one_chip, on_tpu, cell, dk, dv, temp_gib):
    delta_rule_insert_holds_no_channel_tensor(cell, dk, dv, temp_gib)


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_sambay_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick (96 slots x 12288) and its largest
    insert (1024) at the published widths and the WHOLE depth: they
    compile for v5e; `paged_attention` answers "kernel" for both kinds
    of pool and the scan's state engages its kernels; the tick holds
    three paged-attention call sites (the window layers' inside the
    self pairs' scan, the full layer's, the cross layers' inside
    theirs) and two of the scan step (the self pairs', the middle
    pair's), the insert as many of the scan kernel and no paged
    attention (its window layers' 1024 queries over 1536 key rows stay
    under `ops.attention.prefill_engages`' sizes: the loop, no
    `flash_prefill`); neither builds a padded view of a pool; both kinds
    of pool AND the slots' state are updated in place and nothing is
    copied to be re-tiled (temporaries under 0.15 and 0.5 GiB beside
    3.1 + 2.5 GB of pools and 0.31 of state); and arguments +
    temporaries fit HBM with the 7.7 GB of weights."""
    from ray_tpu.ops import selective_scan

    eng = serving_cell("think-decode-ssm-yoco")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["vocab_size"], published["reduced"], mc.n_self_pairs,
            mc.n_cross_pairs, mc.n_ssm_layers, mc.window, mc.kv_width,
            ec.num_slots, ec.max_seq_len, eng.programs.ring_blocks) \
        == (32, 2560, 200064, {}, 8, 7, 9, 512, 1280, 96, 12288, 96)
    assert model.paged_attention(pools) == "kernel"
    assert pools["k"].shape == (1, ec.pool_blocks, 16, 1280)
    assert pools["v_w"].shape == (8, ec.num_window_blocks, 16, 1280)
    assert ec.pool_blocks * 16 >= 600_000
    state = eng.state["_slot_state"]
    assert state["h"].shape == (9, 96, 16, 40, 128)     # no padded lane
    assert selective_scan.engages(state["h"])
    compiled = cell_program(eng.name, program)
    text = compiled.text
    if program == "tick":
        assert compiled.plain.count("paged_attention") >= 3
        assert text.count("ssm_step") >= 2 and "ssm_scan" not in text
        row = pools["k"].shape[3:]
        padded = {(ec.num_slots, n * ec.kv_block_size) + row
                  for n in (ec.max_blocks_per_slot, eng.programs.ring_blocks)}
        assert not any(padded & shapes for _, shapes in results_of(text))
    else:
        assert text.count("ssm_scan") >= 2 and "ssm_step" not in text
        assert "paged_attention" not in compiled.plain
        assert model.insert_attention(
            mc, 0, ec.prefill_buckets[-1], ec.max_seq_len)[0] == "loop"
        assert "flash_prefill" not in compiled.plain
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # all in place
    assert m.temp_size_in_bytes < (0.15 if program == "tick" else 0.5) * GIB
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_ssd_moe_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick and its largest insert at the geometry of
    the benchmark's `swarm-decode-ssd-moe` cell (6 Mamba-2 layers whose
    state of 64 heads x 64 x 128 float32 the engine keeps by slot, 6
    layers of non-gated experts of width 1856 of which 32 of 128 are
    held, 2 attention layers of 2 K/V heads in a `full` pool; `MEMEM*E`
    twice, one scan of two repeats; 384 slots x 12288 rows, buckets to
    2048): every kernel engages, the grouped product at a width of HALF
    lane rows among them and the paged kernel in two parts of 192 slots
    (768 blocks of table a slot: all 384 do not fit scalar memory), the
    4.9 GB state stack and both pools are donated and updated in place
    (a second stack would show as +4.6 GiB of temporaries), no padded
    `[slots, max_seq_len, 256]` view of K or V is built, the weights of
    an expert lie at their published bytes (no padded lane), and the
    arguments and temporaries fit HBM."""
    from ray_tpu.ops import grouped_matmul, paged_attention, ssd

    eng = serving_cell("swarm-decode-ssd-moe")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["vocab_size"], sorted(published["reduced"]),
            mc.layout, mc.n_ssm_layers, mc.n_moe_layers, mc.n_attn_layers,
            mc.n_held_experts, mc.n_experts, mc.top_k, mc.expert_hidden_dim,
            ec.num_slots, ec.max_seq_len) \
        == (14, 2688, 32768,
            ["n_routed_experts", "num_hidden_layers", "vocab_size"],
            ("MEMEM*E", 2, ""), 6, 6, 2, 32, 128, 6, 1856, 384, 12288)
    assert model.paged_attention(pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    assert pools["k"].shape == (2, ec.pool_blocks, 16, 256)
    assert ec.pool_blocks * 16 >= 900_000
    assert paged_attention.slot_parts(ec.num_slots,
                                      ec.max_blocks_per_slot) == 2
    state = eng.state["_slot_state"]
    assert state["S"].shape == (6, 384, 32, 128, 128)   # no padded lane
    assert state["tail"].shape == (6, 384, 3 * 6144)    # taps on lanes
    assert ssd.engages(state["S"])
    experts = eng.params["blocks"][1]
    assert experts["w_up"].shape == experts["w_down"].shape \
        == (2, 32, 1856, 2688) and "w_gate" not in experts
    assert grouped_matmul.col_tile(2688, 1856) == 1856
    compiled = cell_program(eng.name, program)
    text = compiled.text
    # two grouped products an expert layer, in the scan's body once a
    # layer of the block
    assert text.count("grouped_matmul") >= 2 * 3
    assert "ragged" not in compiled.plain
    # a repeat's bank is read through the stack, never cut out of it
    # (a copy of 320 MB a product: 23 of a tick's 40 ms on the chip)
    bank = experts["w_up"].shape[1:]
    assert not any(bank in shapes for _, shapes in results_of(text))
    if program == "tick":
        assert compiled.plain.count("paged_attention") >= 2
        assert text.count("ssd_step") >= 3
        padded = (ec.num_slots, ec.max_seq_len) + pools["k"].shape[3:]
        assert not any(padded in shapes for _, shapes in results_of(text))
    else:
        assert "ssd_step" not in text
        assert "paged_attention" not in compiled.plain
        assert "flash_prefill" in compiled.plain
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(eng.params)) \
        == published["constants"]["total_params"]
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # all in place
    assert m.temp_size_in_bytes < (0.5 if program == "tick" else 2.0) * GIB
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_mamba_mqa_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's decode tick (512 slots x 8192) and its largest
    insert (2048) at the geometry of the benchmark's
    `tutor-decode-mamba-mqa` cell (26 Mamba-1 layers whose 16 x 5120
    float32 state the engine keeps by slot beside 2 attention layers of
    ONE K/V head in a `full` pool, at AI21-Jamba2-3B's published widths
    and WHOLE depth; the slots, row length, buckets, block and pool its
    files state): they compile for v5e; `paged_attention` answers
    "kernel" over pools `[2, NB, bs, 128]` in ONE call a layer (the
    table of a slot is narrow enough at the cell's block that all 512
    slots' scalars fit scalar memory) and the scan's state engages its
    kernels; 28 layers lower as three loops of Mamba layers and two
    attention layers (three call sites of the scan step, two of the
    paged kernel; the insert as many of the scan kernel and two
    `flash_prefill`s); the 4.4 GB state stack `[26, 512, 16, 40, 128]`,
    the tails and both pools are donated and updated in place (a second
    stack would show as +4.1 GiB of temporaries) and no padded
    `[slots, max_seq_len, 128]` view of K or V is built; the parameters
    count to the published 3.03 B; arguments + temporaries fit HBM:
    11.7 GiB, 74 % of the chip (12.1 while the tails lay `[26, 512, 3,
    5120]`, 3 rows in a tile of 4 and re-laid inside the tick: PR 60)."""
    from ray_tpu.ops import paged_attention, selective_scan

    eng = serving_cell("tutor-decode-mamba-mqa")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    pools = eng.pools
    bs = ec.kv_block_size
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["vocab_size"], published["reduced"], mc.mamba_runs,
            mc.n_ssm_layers, mc.n_attn_layers, mc.n_heads, mc.n_kv_heads,
            ec.num_slots, ec.max_seq_len, ec.prefill_buckets[-1]) \
        == (28, 2560, 65536, {}, [7, 13, 6], 26, 2, 20, 1, 512, 8192, 2048)
    assert model.paged_attention(pools) == "kernel"
    assert pools["k"].shape == pools["v"].shape \
        == (2, ec.pool_blocks, bs, 128)
    assert ec.pool_blocks * bs >= 1_400_000
    assert paged_attention.slot_parts(ec.num_slots,
                                      ec.max_blocks_per_slot) == 1
    assert not paged_attention.walks_groups(1, mc.n_heads, mc.n_kv_heads)
    state = eng.state["_slot_state"]
    assert state["h"].shape == (26, 512, 16, 40, 128)   # no padded lane
    assert state["tail"].shape == (26, 512, 3 * 5120)   # taps on lanes
    assert selective_scan.engages(state["h"])
    assert sum(math.prod(x.shape) for x in jax.tree.leaves(eng.params)) \
        == published["constants"]["total_params"] == 3_029_337_472
    compiled = cell_program(eng.name, program)
    text = compiled.text
    if program == "tick":
        assert compiled.plain.count("paged_attention") >= 2
        assert text.count("ssm_step") >= 3 and "ssm_scan" not in text
        padded = (ec.num_slots, ec.max_seq_len) + pools["k"].shape[3:]
        assert not any(padded in shapes for _, shapes in results_of(text))
    else:
        assert text.count("ssm_scan") >= 3 and "ssm_step" not in text
        assert "paged_attention" not in compiled.plain
        assert compiled.plain.count("flash_prefill") >= 2
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize
               for x in list(pools.values()) + list(state.values()))
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # all in place
    assert m.temp_size_in_bytes < (0.8 if program == "tick" else 0.5) * GIB
    assert 0.25 * V5E_HBM_GIB < compiled.hbm_gib < V5E_HBM_GIB - 0.5


@pytest.mark.parametrize("cell, leaf, layers, taps, channels, calls", [
    ("tutor-decode-mamba-mqa", "tail", 26, 4, 5120, 3),     # three loops
    ("think-decode-ssm-yoco", "tail", 9, 4, 5120, 2),
    ("swarm-decode-ssd-moe", "tail", 6, 4, 6144, 3),
    ("reason-decode-gdn-hybrid", "conv", 6, 4, 11520, 6),   # unrolled
    ("compose-decode-conv-moe", "tail", 11, 3, 2048, 11)])
def test_convolution_tails_are_shifted_where_they_lie(
        one_chip, on_tpu, cell, leaf, layers, taps, channels, calls):
    """A family a case (`agent-decode-hybrid`'s is beside its tick, in
    `test_chip_compile_latent_cells.py`): `chip_programs.
    tails_are_shifted_where_they_lie`."""
    assert tails_are_shifted_where_they_lie(
        cell, leaf, layers, taps, channels) == calls
