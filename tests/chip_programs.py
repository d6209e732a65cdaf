"""What the deviceless v5e compile tests (`test_chip_compile*.py`) share.

The TPU compiler is installed here and compiles for a chip that is
described, not attached (on-chip-measurement guide §2, step 3). The tests
hand the jitted functions `ShapeDtypeStruct`s placed on described v5e
devices, so the compiler refuses here — at no chip time — what it would
refuse on the machine: an API the installed JAX dropped, a kernel that
cannot be lowered or partitioned, more VMEM than a kernel may use, a
program that does not fit HBM. Nothing runs: a compile that passes says
nothing about results or speed.

Rules these files keep (the guide explains each): the topology is
described only inside the module-scoped, non-autouse fixture below —
never at import, in a `skipif` or in `parametrize` — so a process that
collects these files and runs none of their cases loads no TPU library;
several processes may each describe it (one xdist worker a file does);
every compile happens in the test's own process; the persistent
compilation cache is off around them (a deviceless executable can be
written to it but not read back); code that asks `jax.default_backend()`
is answered for the chip around the compile itself (`program`), not
through an option of the program.

A whole program compiles ONCE a process: `program` remembers, by a key
that names everything deciding the program, what the cases read of it
(its text, its memory analysis) and lets the executable go. The files
are cut so that every case reading a cell's tick or insert lives in one
file, and the session prints how often each key compiled over all
workers (`conftest.py`): a 2 there is a minute of the run spent twice.
"""

import collections
import functools
import hashlib
import importlib
import json
import os
import re
import sys
import types
import typing

import pytest

import jax
from jax.sharding import SingleDeviceSharding

GIB = 2 ** 30
V5E_HBM_GIB = 15.75     # what the v5e compiler itself reports as capacity

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")


@functools.cache
def _described():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    return topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        desc = _described()
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


@pytest.fixture
def on_tpu(monkeypatch):
    """`ops.attention` picks kernel-vs-XLA and compiled-vs-interpret from
    the default backend, which is the CPU here: answer for the chip, for
    a case that compiles a kernel itself or asks a selector
    (`model.paged_attention(pools)`, `kda.engages`).  `program` answers
    so around its own compiles and needs no fixture."""
    from ray_tpu.ops import attention

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)


def placed(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


def without_metadata(text: str) -> str:
    """A compiled program's text less what only names things: the
    per-instruction metadata, the source tables and the kernels'
    serialized modules (their debug locations)."""
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    text = re.sub(r"\n(FileNames|FunctionNames|FileLocations|"
                  r"StackFrames)\n(.+\n)*", "\n", text)
    return re.sub(r'"body":"[^"]+"', '"body":""', text)


def renumbered(text: str) -> str:
    """`text` with its instructions named by first appearance."""
    names = {}
    return re.sub(
        r"%[A-Za-z_][\w.\-]*",
        lambda m: names.setdefault(m.group(0), "%%i%d" % len(names)), text)


def hbm_gib(memory) -> float:
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            - memory.alias_size_in_bytes + memory.temp_size_in_bytes) / GIB


def results_of(text):
    """(opcode, shapes of its result) of every instruction in a compiled
    program's text, fused computations' own instructions included."""
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


# ---------------------------------------------------- one compile a program

COMPILES = collections.Counter()    # key -> compiles in this process
_PROGRAMS = {}


def program(key, build):
    """What the cases read of the v5e program `key`: `.text`, `.plain`
    (the text less its metadata), `.memory` (its `memory_analysis()`)
    and `.hbm_gib`.  `build()` lowers and compiles it the first time a
    case asks, with `ops.attention._on_tpu` answered for the chip around
    that compile and nowhere else: every program here is the one the
    chip runs (no case takes a selector away any more: the pins hold the
    texts that was done for), and `key` names what decides it."""
    from ray_tpu.ops import attention

    if key not in _PROGRAMS:
        asked, attention._on_tpu = attention._on_tpu, lambda: True
        try:
            compiled = build()
        finally:
            attention._on_tpu = asked
        COMPILES[key] += 1
        text, memory = compiled.as_text(), compiled.memory_analysis()
        _PROGRAMS[key] = types.SimpleNamespace(
            text=text, plain=without_metadata(text), memory=memory,
            hbm_gib=hbm_gib(memory))
    return _PROGRAMS[key]


# sha256 of a program's text less its metadata (`without_metadata`): every
# serving cell's tick and largest insert as the chip runs them, and the
# two train steps whose texts accepted PRs were shown to leave alone.
# A PR that means to change one of these programs pins its own text here
# and says so; one that does not (a scheduler change, a clean-up, a kernel
# for another model) has this to show it.  `chat-decode`'s two and
# `assistant-decode-moe`'s tick are PR 42's tree's (efba6a6), kanana's
# insert PR 45's (`_History.attend` walks the history in tiles); the rest
# were pinned by PR 46 at PR 45's tree, where each equal-text guard that a
# pin replaced was run one last time and held (CHANGES.md, PR 46);
# `think-decode-ssm-yoco`'s two are PR 47's, which brought the cell (and
# left every other text as it was: the engine's one new branch is on a
# shape); `mixed-decode-window-moe`'s insert is PR 48's (its pieces
# attend through `ops.attention.flash_prefill`; the other seventeen
# texts stood, `think-decode-ssm-yoco`'s insert among them: its window
# layers' 1536 key rows stay under `prefill_engages`' sizes);
# `swarm-decode-ssd-moe`'s two are PR 52's, which brought the cell (and
# left the other eighteen texts as they were: `dropless_moe` reads the
# expert's form off its parameters and the grouped kernel's second form
# is another static branch); `longform-decode-zero-moe`'s two are PR
# 53's: holding 16 of the router's 768 columns, its expert layers walk
# the picks that have a group here in passes of 64 (tick) and 512 (the
# 1024 bucket) rows (`models/moe.py::_held_picks`).  The other nineteen
# texts stood: `agent-decode-hybrid` and `swarm-decode-ssd-moe` hold a
# quarter of their routers, where `compact_rows` keeps the walk of all
# picks (the issue expected their four to change too; on the chip the
# compact walk lost there), and the whole-bank cells take the same
# static branch.  `solve-decode-blockdiff-moe`'s two are PR 55's, which
# brought the cell and the block form of the tick and the insert; the
# other twenty-one texts stood (`flash_prefill`'s block-causal mask and
# `slot_parts`' query budget are static branches, `softmax_top_k`'s
# renormalisation an argument).  `solve-decode-blockdiff-moe`'s tick is
# PR 56's: the head and the softmax over the rows still masked, 384 a
# pass (`engine._block_predict_rows`); the other twenty-two stood, that
# cell's insert among them: only a model with `ServingFns.block` runs the
# block tick.  `solve-decode-blockdiff-moe`'s tick is PR 58's: the paged
# kernel over its side-by-side pool walks the 4 KV groups, one call a
# layer in chunks of 64 blocks (`ops.paged_attention.walks_groups`: 32
# query rows a group; `chunk_blocks`); the
# other twenty-two stood, the three other cells with such a pool among
# them (`swarm` 16 rows a group, `mixed` 8, `think` 4: below the rule,
# they keep the whole-row form).  `tutor-decode-mamba-mqa`'s two are PR
# 59's, which brought the cell; the other twenty-three stood,
# `think-decode-ssm-yoco`'s two among them (`sambay.ssm_mixer` took a
# hook between `W_x` and `W_dt` whose default hands `[dt | B | C]` back
# as it came).  The tick AND the insert of the six cells whose model
# keeps a convolution's tail are PR 60's, which laid the tail
# `[L', B, (K-1) C]` (a program's argument changed shape, so both texts
# did) and gave the tick ONE step over it, `ops.short_conv.
# step_in_place`, a Pallas call a call site: `tutor-decode-mamba-mqa`
# (3 call sites, one a loop of Mamba layers; the 409 MB stack's two
# copies a tick gone), `think-decode-ssm-yoco` (2), `swarm-decode-ssd-moe`
# (3), `agent-decode-hybrid` (6, unrolled), `reason-decode-gdn-hybrid`
# (6), `compose-decode-conv-moe` (11); their inserts reshape one
# sequence's `[1, (K-1) C]` at `_Sequences.conv`'s edge and keep
# `short_conv`.  The other ten serving texts (`chat`, `assistant`,
# `mixed`, `longform`, `solve`: no tail in the model) and the three
# train texts stood.
PROGRAM_TEXT_SHA256 = {
    ("chat-decode", "tick"):
        "48a91f54a548addd9d951f33258125cd66601f6eb5de512b9f23800388b2ae93",
    ("chat-decode", "insert"):
        "33e1fd0f5f8e39ac4168e9c0371c61ce1c57240d9308ea125ea1b63b1d527fc6",
    ("assistant-decode-moe", "tick"):
        "8f11c202dee0816c9bda3bb0a54e3745760458d31f1d8595b01ede0a6ca1dedb",
    ("assistant-decode-moe", "insert"):
        "7ea6d60d46bd647067feef60f4dff765f30aa43261cc52d3564b29c2b43221de",
    ("agent-decode-hybrid", "tick"):
        "4a6c5f43af2db5c326cff16cf02c6451d58030885a79f1c519924105c0e11f80",
    ("agent-decode-hybrid", "insert"):
        "d7b820618fb515d45417e333ec51a5455ee250854ea2e1f4d38eb93cbce88e77",
    ("compose-decode-conv-moe", "tick"):
        "6542306df3583c652c2fcc31f9520cda66b422a6d4749850b087a45e8ab07843",
    ("compose-decode-conv-moe", "insert"):
        "b4f906823df9986bd12936b15c7079d5c9340044bda13bfc5c4bc74784111879",
    ("mixed-decode-window-moe", "tick"):
        "ab3d09efdab6c14edbb8e0cc678b75f5d1d1df6032fe7931641e9b39880fe1dd",
    ("mixed-decode-window-moe", "insert"):
        "dc7b87d48f9694f7cef7e9a84f1cf93771805234e2822c3e0e39972faa714ae6",
    ("reason-decode-gdn-hybrid", "tick"):
        "33132b67c38992b9d0401d1d6e722b2ec9ff0a3aee975c1abb4a2e25082fa429",
    ("reason-decode-gdn-hybrid", "insert"):
        "0a0e4a1834ebfeb0b980ac25f54340ea3e55c5f79a38b08b22286d20adc7d775",
    ("longform-decode-zero-moe", "tick"):
        "6d77af0f4785b5e86580c1d79abfcf881ee9ed1c86a99dae09123978d048b902",
    ("longform-decode-zero-moe", "insert"):
        "f2fffc5a59fcb63a9959f73e5139a961d1c09b180708d110378b7eff2cf0f76c",
    ("think-decode-ssm-yoco", "tick"):
        "f8b9e647a21ffe686e9d134794fdee242273b99d7cf5b9fb71528935984c942f",
    ("think-decode-ssm-yoco", "insert"):
        "51d5cd58703adf5a7628c6533cd9461eec2a0363f73316dddebe72679e3f2ed9",
    ("swarm-decode-ssd-moe", "tick"):
        "52ce9761db240de430b5d066204becea8eeb4486661638acf10cfeb9267a530d",
    ("swarm-decode-ssd-moe", "insert"):
        "6e41bf1f47650079a336151447011a7f96910ab872243f17fdfed517e6639b4f",
    ("solve-decode-blockdiff-moe", "tick"):
        "a787579becbeb61a966f2d3cb31927c6a496e18ccc6a6ec0efafa2d58b62483d",
    ("solve-decode-blockdiff-moe", "insert"):
        "07bb25384c7a15ab6eaed0fa8e18c697a73584806187d659fc03c0656337a63e",
    ("tutor-decode-mamba-mqa", "tick"):
        "e548add18e94e22b880c5822eb94d06582cb659424237654a27c9a8cb7b9a934",
    ("tutor-decode-mamba-mqa", "insert"):
        "1b1126de44e3fd33bd073ae096f51825856455e4c93fa71f64fec900514612c0",
    ("two small layers", "train step, scope names apart"):
        "1d700902d1c682aaec9e4b41afc84286eb99ba0c4758f7046d1be38b1848714c",
    ("two small layers", "train step, its kernels"):
        "cf8043a1be3c7d2314b71cb866520de6f8dbb0ddd6678f78b9a87daf49c49e31",
    ("pretrain-1chip", "train step, renumbered"):
        "51b06f2e0f3285376dbc998817fecf690d572e01726357a4a93a5765710e2b0d",
}


def text_sha256(text):
    return hashlib.sha256(text.encode()).hexdigest()


def is_the_pinned_text(name, which, plain):
    """`plain` (a program's text less its metadata) is the text pinned
    under `(name, which)`."""
    assert text_sha256(plain) == PROGRAM_TEXT_SHA256[name, which], (
        name, which)


# ------------------------------------------------ the benchmark's serving cells

class Cell(typing.NamedTuple):
    """One of the benchmark's serving cells on the described chip: the
    engine's own `Programs` for it (serve/llm/programs.py: built,
    nothing placed), the configuration's file, and as shapes on the
    chip the model's parameters and the programs' device values by
    attribute (`state["_cache"]` the pools, `state["_slot_state"]` the
    state by slot or None, ...)."""
    name: str
    programs: typing.Any
    published: dict
    one_chip: typing.Any
    params: typing.Any
    state: dict

    config = property(lambda self: self.programs.config)
    model_config = property(lambda self: self.programs.model_config)
    model = property(lambda self: self.programs.model)
    pools = property(lambda self: self.state["_cache"])


@functools.cache
def serving_cell(cell):
    from ray_tpu.serve.llm.engine import EngineConfig
    from ray_tpu.serve.llm.programs import programs_for

    one_chip = SingleDeviceSharding(_described().devices[0])
    if BENCH not in sys.path:
        sys.path.insert(0, BENCH)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        declared = json.load(f)
    name = next(w["config"] for w in declared["workloads"]
                if w["name"] == cell)
    with open(os.path.join(ROOT, next(
            c["file"] for c in declared["configs"]
            if c["name"] == name))) as f:
        published = json.load(f)
    with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
        ec = EngineConfig(**json.load(f)["engine"])
    family = importlib.import_module("families." + published["family"])
    mc = family.model_config(published, max_seq_len=ec.max_seq_len,
                             compute_dtype="bfloat16",
                             param_dtype="bfloat16")
    model = mc.serving()
    programs = programs_for(model, mc, ec)
    return Cell(
        cell, programs, published, one_chip,
        params=placed(jax.eval_shape(
            lambda: model.init_params(mc, jax.random.key(0))), one_chip),
        state=programs.shapes(one_chip)[0])


def cell_program(cell, which):
    """A serving cell's decode tick (`"tick"`) or its largest insert
    (`"insert"`) as the chip runs it — lowered by the engine's own
    `Programs`, donation and all — compiled once a process, and held to
    its pin."""
    eng = serving_cell(cell)
    bucket = {"tick": None,
              "insert": eng.programs.config.prefill_buckets[-1]}[which]
    compiled = program((cell, which), lambda: eng.programs.lower(
        eng.params, bucket, eng.one_chip).compile())
    # The pins are of modules lowered through a `functools.partial`, which
    # jax names `jit__unknown`; the engine's own lowering names a module
    # after its body (`jit__tick_fn`).  That name apart, letter for letter.
    is_the_pinned_text(cell, which, re.sub(
        r"^HloModule jit_\w+,", "HloModule jit__unknown,", compiled.plain,
        count=1))
    return compiled


def grouped_products_are_the_kernel(text, n_moe_layers):
    """A compiled expert program with `ops.grouped_matmul` engaged: three
    kernel calls an expert layer and none of XLA's grouped matmul left
    (`ragged-dot` custom calls, `ragged_dot_tiling` in their config)."""
    assert text.count("grouped_matmul") >= 3 * n_moe_layers
    assert "ragged" not in without_metadata(text)


def tails_are_shifted_where_they_lie(cell, leaf, layers, taps, channels):
    """`cell`'s tick on the chip and the convolution's tails (PR 60): the
    leaf `[L', B, (K-1) C]` of a configuration whose C is whole lane
    tiles, so `ops.short_conv.step_in_place` is the kernel, one call a
    call site; NOTHING else touches the stack (no `copy` or `copy-start`
    of it, which is how the `[L', B, K-1, C]` leaf was re-laid at both
    ends of a tick, no fusion that hands it on, no array of its shape
    in fast memory) and no `scatter` stands under a `conv` scope; the
    stack lies in HBM at its logical bytes (rows of whole (8, 128)
    tiles: no padded row, where 3 rows lay in a tile of 4)."""
    from ray_tpu.ops import short_conv

    eng = serving_cell(cell)
    stack = eng.state["_slot_state"][leaf]
    L, B, W = stack.shape
    assert (L, W) == (layers, (taps - 1) * channels)
    assert channels % 128 == 0 and B % 16 == 0
    assert B % short_conv._block_rows(B, W, stack.dtype.itemsize) == 0
    text = cell_program(cell, "tick").text
    shape = r"bf16\[%d,%d,%d\]" % stack.shape
    layouts = set(re.findall(shape + r"\{([^}]*)\}", text))
    assert layouts == {"2,1,0:T(8,128)(2,1)", "2,1,0"}, layouts
    made = collections.Counter(
        op for op, shapes in results_of(text) if stack.shape in shapes)
    calls = len(re.findall(
        r"= \([^=]*" + shape + r"[^=]*\) custom-call\([^\n]*short_conv_step",
        text))
    assert calls >= 1 and set(made) <= {
        "parameter", "get-tuple-element", "custom-call", "tuple", "while",
        "conditional", "call", "bitcast"}, made
    assert made["custom-call"] == calls
    under_conv = [line for line in text.splitlines()
                  if re.search(r'op_name="[^"]*/conv/', line)]
    assert under_conv and not [
        line for line in under_conv if re.search(r" scatter\(", line)]
    return calls


def delta_rule_insert_holds_no_channel_tensor(cell, dk, dv, temp_gib):
    """The largest insert of a delta-rule cell (Kimi at its 2048 bucket,
    one decay a key channel; Olmo-Hybrid at 512, one a head): no float32
    result, fused computations' own included, has both of
    `ops.kda.kda_chunked`'s chunk axes AND the channel axis `[.., 64,
    64, dk]` (2.1 GB a KDA layer at Kimi's bucket, which
    `_decayed_products` replaces by matrix products over 16-row
    sub-blocks: the diagonal blocks' `[.., 32, 16, 128]`, the k rows
    over the q rows, inside a reduction's fusion is what is left of it;
    the a-head arm never had one, its decays are `[.., 64, 64]`).
    Temporaries: 1.30 GiB against the 2.41 the `[C, C, dk]` form took
    for Kimi (the history's softmax holds them now), 0.45 for
    Olmo-Hybrid.  Nor does a row loop walk the solve's right-hand
    sides again: no `dynamic-update-slice`, a loop body's or a fusion's
    own, writes into a `[.., 16, dv + dk]` block (`_unit_lower_solve`
    wrote 60 rows a chunk that way until PR 50, each a pass over every
    tile of the block) nor into one side's `[.., 16, dv]` or `[.., 16,
    dk]`.  The two cells' cases live in two files, each beside the
    other readers of its cell's insert."""
    from ray_tpu.ops import kda

    C, b = kda.CHUNK, kda._SOLVE_BLOCK
    assert serving_cell(cell).programs.config.prefill_buckets[-1] % C == 0
    compiled = cell_program(cell, "insert")
    results = results_of(compiled.text)
    shapes = set().union(*(shapes for _, shapes in results))
    assert any(s[-2:] == (C, C) for s in shapes)            # parsed
    if dk == 128:
        assert any(s[-3:] == (2 * b, b, dk) for s in shapes)
    assert not sorted(s for s in shapes if s[-3:] == (C, C, dk))
    assert "dynamic-update-slice" in {op for op, _ in results}   # parsed
    assert not sorted(s for op, found in results for s in found
                      if op == "dynamic-update-slice"
                      and s[-2:-1] == (b,) and s[-1] in (dv + dk, dv, dk))
    assert compiled.memory.temp_size_in_bytes < temp_gib * GIB
