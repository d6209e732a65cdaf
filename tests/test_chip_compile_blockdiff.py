"""Deviceless v5e compiles of the benchmark's block-diffusion serving
cell (`solve-decode-blockdiff-moe`): the block form of the engine's tick
and its largest insert, as the chip runs them, at the geometry its files
state.  `chip_programs.py` has the rules this file keeps, the fixtures,
the one compile a program (`cell_program`, which also holds each text to
its pin) and the cell as shapes (`serving_cell`).  A file of its own so
that its two compiles (half a minute) go to one worker beside no other
cell's.
"""

import math

import pytest

from chip_programs import (     # noqa: F401  (fixtures)
    GIB, V5E_HBM_GIB, cell_program, grouped_products_are_the_kernel, on_tpu,
    one_chip, results_of, serving_cell, topo,
)


@pytest.mark.parametrize("program", ["tick", "insert"])
def test_blockdiff_moe_cell_programs_fit_one_v5e(one_chip, on_tpu, program):
    """The engine's block tick and its largest insert at the geometry of
    the benchmark's `solve-decode-blockdiff-moe` cell (12 layers of
    QK-normed 4-KV-head attention and 32 held of 128 softmax-routed
    experts at SDAR-30B-A3B's published widths, a head 151,936 wide, 256
    slots x 3072 rows, 20,480 blocks of 16): they compile for v5e,
    `paged_attention` and `grouped_matmul` answer "kernel"; the tick is
    ONE program that holds the paged kernel at 4 queries a sequence
    ONCE a layer, all 256 slots in one call: the call walks its 4 KV
    groups (`paged.walks_groups`) in chunks of 64 blocks
    (`paged.chunk_blocks`), so a slot's queries are 4 x 32 rows of 128
    lanes (`paged.query_bytes`: 32 KiB, 16 MiB of query and output a
    call, which `paged.slot_parts` leaves whole) and no query or output
    row is laid 512 lanes wide ([256, 128, 512] or a part's [64, 128,
    512]); it builds no padded view of the pool and holds NO [256, 4,
    151936] float32 logits: the head runs over the rows still masked,
    R = 384 of the 1,024 a pass
    (`programs._block_pass_rows`), so the widest result is [384, 151936];
    the insert at 2048 attends through `flash_prefill` under the
    block-causal mask in every layer but the last, whose attention
    nothing reads; both update the pool in place and arguments +
    temporaries fit HBM."""
    eng = serving_cell("solve-decode-blockdiff-moe")
    ec, mc, model, published = (eng.config, eng.model_config, eng.model,
                                eng.published)
    pools = eng.pools
    assert (published["num_hidden_layers"], published["hidden_size"],
            published["num_experts"], published["vocab_size"],
            mc.n_experts, mc.n_held_experts, mc.n_kv_heads, mc.block_length,
            ec.num_slots, ec.max_seq_len, ec.pool_blocks) \
        == (12, 2048, 32, 151936, 128, 32, 4, 4, 256, 3072, 20480)
    assert tuple(eng.programs.block) \
        == (4, 4, "low_confidence_dynamic", 0.9, 151669)
    assert model.paged_attention(pools) == "kernel"
    assert model.grouped_matmul(mc, ec.num_slots) == "kernel"
    assert pools["k"].shape == (12, 20480, 16, 4 * 128)
    B = ec.num_slots
    compiled = cell_program(eng.name, program)
    text = compiled.text

    def kernel_calls(name):     # the text's tables of file names hold any
        return [line for line in text.splitlines()
                if "custom-call(" in line and name in line]

    if program == "tick":
        from ray_tpu.ops import paged_attention as paged

        L, H, kvh, hd = mc.block_length, mc.n_heads, mc.n_kv_heads, \
            mc.head_dim
        assert paged.walks_groups(L, H, kvh)
        a_slot = paged.query_bytes(L, H, kvh, hd)
        assert a_slot == L * H * hd * 2
        assert paged.slot_parts(B, ec.max_seq_len // ec.kv_block_size,
                                paged.chunk_blocks(L, H, kvh),
                                query_bytes=a_slot) == 1
        assert len(kernel_calls("paged_attention")) == mc.n_layers
        found = set().union(*(shapes for _, shapes in results_of(text)))
        assert (B, kvh, L * H // kvh, hd) in found      # parsed
        padded = {(B, ec.max_seq_len) + pools["k"].shape[3:]}
        lane_placed = {(n, L * H, kvh * hd) for n in (B, B // 4)}
        assert not (padded | lane_placed) & found
        assert not kernel_calls("flash_prefill")
        from ray_tpu.serve.llm.programs import _block_pass_rows

        V = mc.vocab_size
        R = _block_pass_rows(B, L)
        assert R == 384
        wide = {shape for _, shapes in results_of(text) for shape in shapes
                if shape[-1:] == (V,)}
        assert (R, V) in wide
        assert not wide & {(B, L, V), (B * L, V)}
    else:
        # the insert yields no token, so nothing reads the last layer's
        # attention (its K/V rows alone are kept): the compiler drops it
        assert len(kernel_calls("flash_prefill")) == mc.n_layers - 1
        assert not kernel_calls("paged_attention")
    grouped_products_are_the_kernel(text, mc.n_layers)
    m = compiled.memory
    kept = sum(math.prod(x.shape) * x.dtype.itemsize for x in pools.values())
    print(program, "GiB", compiled.hbm_gib, "temp",
          m.temp_size_in_bytes / GIB, "args", m.argument_size_in_bytes / GIB)
    assert m.alias_size_in_bytes >= kept                # in place
    assert m.temp_size_in_bytes < (1.0 if program == "tick" else 0.6) * GIB
    assert compiled.hbm_gib < V5E_HBM_GIB - 0.5
