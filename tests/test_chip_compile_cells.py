"""The serving programs of the benchmark's cells at their published widths,
AOT-compiled for a described (not attached) TPU v5e: what fits the chip,
what is copied, which kernels are in (what the compiles are and are not:
``chip_compile_harness.py``).
"""

import re

import pytest
from chip_compile_harness import (  # noqa: F401 — fixtures
    compile_engine_program,
    no_compile_cache,
    pool_sized_ops,
    topo,
)


def _every_chip_run_compiles_it(program, bucket):
    """A bucket-1,024 prompt program out of tier-1 (``-m slow``): tens of
    seconds here, and the chip compiles it in every benchmark run of its
    cell. Each cell keeps its chunk and one prompt program in tier-1."""
    return pytest.param(program, bucket, marks=pytest.mark.slow)


def test_pool_sized_op_finder_sees_a_relayout():
    """The finder on two lines of the chat cell's chunk as PR 25 compiled it
    (the copies this layout removed) and on what may stay."""
    text = """
%fused_computation.1 (p: bf16[8,6144,16,1024]) -> bf16[6144,16,1024] {
  %inside = bf16[6144,16,1024]{2,1,0} dynamic-slice(%p), dynamic_slice_sizes={1,6144,16,1024}
}

ENTRY %main (a: bf16[8,6144,16,8,128]) -> bf16[6144,16,1024] {
  %a = bf16[8,6144,16,8,128]{4,3,2,1,0} parameter(0)
  %gte = bf16[8,6144,16,1024]{3,2,1,0} get-tuple-element(%t), index=3
  %fusion.9 = bf16[1,6144,16,8,128]{4,3,2,1,0} fusion(%a), kind=kLoop, calls=%fused_computation.1
  %reshape.556 = bf16[6144,16,1024]{2,1,0} reshape(%fusion.9)
  %fusion.183 = bf16[8,6144,16,1024]{3,2,1,0} fusion(%gte), kind=kCustom, calls=%fused_computation.7, metadata={op_name="jit(chunk)/while/body/closed_call/kv_write/scatter"}, backend_config={"aliasing_operands":{"lists":[{"indices":["0","3"]}]}}
  %small = bf16[32,4096]{1,0} copy(%x)
}
"""
    found = pool_sized_ops(text, 6144 * 16 * 1024)
    assert [op for op, _ in found] == ["fusion", "reshape"]


@pytest.mark.parametrize("tp", [1, 4], ids=["one_chip", "tp4"])
@pytest.mark.parametrize(
    "program,bucket", [("chunk", 4), ("prefill", 16), ("suffix", 16)]
)
def test_no_program_holds_a_pool_sized_copy(topo, program, bucket, tp):
    """No ``copy``, ``reshape``, ``dynamic-slice``, fusion or other
    materialized output of a serving program has a per-layer pool's element
    count or more, and the program's temps stay under one per-layer pool.
    The model is tiny and the pool is not, so only the pool is that large.
    (One chip stands in as a one-device mesh: the engine's own single-device
    path asks ``jax.devices()``, which is the CPU here.)"""
    compiled, cfg = compile_engine_program(
        topo, program, bucket, tp, num_pages=1024
    )
    # one device's share of one layer of the pool, in elements
    layer_pool = cfg.num_pages * cfg.page_size * cfg.model.kv_dim // tp
    assert pool_sized_ops(compiled.as_text(), layer_pool) == []
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < layer_pool * 2, temps  # bf16


@pytest.mark.parametrize(
    "program,bucket", [("chunk", 4), ("prefill", 16), ("suffix", 16)]
)
def test_no_patterned_program_holds_a_copy_of_either_pool(topo, program, bucket):
    """The same of a model with window layers, whose KV state is two pools
    (pages of the full-attention layers, rings of the window layers), and
    of its expert stacks: the rings go through the programs in their stored
    shape and back, and the grouped matmuls read a layer's experts out of
    the whole stack (models/moe.py:_grouped), so nothing the size of a
    layer of either pool, or of a layer's experts, is materialized."""
    from llm_d_fast_model_actuation_tpu.models import smallthinker

    model = smallthinker.SmallThinkerConfig(
        vocab_size=512, hidden_size=256, num_layers=8, num_heads=8,
        num_kv_heads=4, head_dim=128, intermediate_size=128, max_seq_len=4096,
        num_experts=8, experts_per_token=2, attention_impl="pallas",
        window_pattern=(0, 1024, 1024, 1024),
    )
    # pools too large for the compiler to move whole into fast memory, as
    # it does with arrays of a few MB
    compiled, cfg = compile_engine_program(
        topo, program, bucket, tp=1, model=model, num_pages=4096,
        max_batch=64, max_prefill_tokens=64, prefix_caching=False,
    )
    lay = cfg.kv_layout
    assert (lay.global_layers, lay.window_layers) == (2, 6)
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    layer_ring = cfg.max_batch * lay.ring_pages * cfg.page_size * model.kv_dim
    # the model is tiny (all its experts together are smaller than a layer
    # of the rings; the cell's real sizes are compiled below), the pools not
    all_experts = model.num_layers * (
        model.num_experts * model.hidden_size * model.intermediate_size
    )
    smaller = min(layer_ring, layer_pool)
    assert all_experts < smaller
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # attention and grouped matmuls
    assert pool_sized_ops(text, smaller) == []
    temps = compiled.memory_analysis().temp_size_in_bytes
    assert temps < smaller * 2, temps  # bf16


_CELL_PROGRAMS = {}


def _compile_cell_program(topo, name, program, bucket=None):
    """One serving program of a benchmark cell at its real sizes and engine
    options (``bucket`` None: the cell's ``--decode-chunk``), compiled for
    the described chip; returns ``(compiled, engine config, cell, model)``."""
    import dataclasses

    from fmabench import spec
    from llm_d_fast_model_actuation_tpu.engine import server
    from llm_d_fast_model_actuation_tpu.models import llama

    cell = spec.Cell(spec.benchmark(), name)
    model = dataclasses.replace(
        cell.family.part("program").build(cell.dims), attention_impl="pallas"
    )
    args = server.make_arg_parser().parse_args(
        ["--model", "tiny", *cell.engine_options(False)]
    )
    # two tests ask for the same program of a cell (the chunk programs of the
    # batch and the longmix cell): one compile serves both
    key = (name, program, bucket or args.decode_chunk)
    if key in _CELL_PROGRAMS:
        return (*_CELL_PROGRAMS[key], cell, model)
    compiled, cfg = _CELL_PROGRAMS[key] = compile_engine_program(
        topo, program, bucket or args.decode_chunk, tp=1, model=model,
        max_batch=args.max_batch, page_size=args.page_size,
        num_pages=args.num_pages, decode_chunk=args.decode_chunk,
        max_prefill_tokens=args.max_prefill_tokens,
        speculative_mtp=args.speculative_mtp,
        # the prefix cache refuses a model with per-slot state
        prefix_caching=llama.patterned(model) is None,
    )
    return compiled, cfg, cell, model


@pytest.mark.parametrize(
    "program,bucket",
    [("chunk", 8), ("prefill", 1024), _every_chip_run_compiles_it("suffix", 1024)],
)
def test_longmix_cell_programs_fit_the_chip(topo, program, bucket):
    """The programs of the cell ``smallthinker-21b.longmix`` at its real
    sizes and engine options, compiled for the described chip: kernels in
    (attention, and the three grouped expert matmuls), nothing the size of a
    layer of either pool or of a layer's experts copied, arguments + temps
    inside the chip's 16 GB beside the 11.7 GB of weights, pages and rings,
    and the expert layers' flops those of 6 experts a token, not of 64."""
    compiled, cfg, cell, _ = _compile_cell_program(
        topo, "smallthinker-21b.longmix", program, bucket
    )
    d = cell.dims
    lay = cfg.kv_layout
    assert lay.ring_pages * cfg.page_size == 4096 + 1024
    keys = cell.family.keys
    state = (
        2 * keys.param_count(d)
        + keys.kv_bytes(d, cfg.num_pages, cfg.page_size)
        + keys.ring_bytes(d, cfg.max_batch, 1024)
    )
    assert 11.6e9 < state < 11.8e9
    ma = compiled.memory_analysis()
    assert state <= ma.argument_size_in_bytes < state + 0.1e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_experts = d["num_experts"] * d["hidden_size"] * d["expert_size"]
    # ... but for the logits of a whole segment, which the prefill programs
    # of every family compute before they take the last (PERF.md section 5)
    logits = f"bf16[{bucket},{d['vocab_size']}]"
    assert [
        row for row in pool_sized_ops(text, layer_experts)
        if logits not in row[1]
    ] == []
    # XLA counts a loop's body once: one period of four layers, one step
    rows = cfg.max_batch if program == "chunk" else bucket
    per_expert = 3 * 2 * d["hidden_size"] * d["expert_size"]
    dense_experts = 4 * rows * d["num_experts"] * per_expert
    routed_experts = 4 * rows * d["experts_per_token"] * per_expert
    flops = compiled.cost_analysis()["flops"]
    assert routed_experts < flops < routed_experts + 0.5 * dense_experts


@pytest.mark.parametrize(
    "program,bucket",
    [("chunk", 8), ("prefill", 32), ("prefill", 64), ("prefill", 128),
     ("prefill", 256)],
)
def test_loopchat_cell_programs_fit_the_chip(topo, program, bucket):
    """The programs of the cell ``ouro-2.6b.loopchat`` at its real sizes and
    engine options, compiled for the described chip: the decode chunk and
    every prefill bucket its prompts (32-256 tokens) meet. The pool is 192
    cache layers deep (a layer for each of 4 passes x 48 layers) and the
    stack is a scan of passes over a scan of layers: the kernels are in,
    nothing the size of a cache layer of the pool is copied, and arguments +
    temps are 13.01 GB of the chip's 16: 12.21 GB of weights and pages, and
    0.81 GB that is two copies, once a program, of the ``wq`` and ``wk``
    stacks into another layout (the chat cell's ``wq`` copy, PERF.md section
    7; with 16 KV heads ``wk`` is as large as ``wq``). ISSUE 34 reckoned
    under 13 GB without them; nothing else is as large as a megabyte."""
    compiled, cfg, cell, model = _compile_cell_program(
        topo, "ouro-2.6b.loopchat", program, bucket
    )
    d = cell.dims
    assert cfg.kv_layout.global_layers == model.cache_layers == 192
    keys = cell.family.keys
    state = 2 * keys.param_count(d) + keys.kv_bytes(d, cfg.num_pages, cfg.page_size)
    assert state == 2 * 2_667_974_657 + 6_870_269_952
    ma = compiled.memory_analysis()
    assert state <= ma.argument_size_in_bytes < state + 0.1e9
    relayouts = 2 * 2 * d["num_layers"] * d["hidden_size"] * model.q_dim
    assert ma.temp_size_in_bytes < relayouts + 3e6
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13.1e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    # ... and the logits of a whole segment, which the prefill programs of
    # every family compute before they take the last (PERF.md section 5)
    logits = f"bf16[{bucket},{d['vocab_size']}]"
    assert [
        row for row in pool_sized_ops(text, layer_pool)
        if logits not in row[1]
        and not re.search(r"copy\(%params__layers____w[qk]__", row[1])
    ] == []
    if program == "chunk":
        tile = (2, 128, model.kv_dim)
        kernels_found = _kernel_args(text, "paged_decode_inline")
        assert kernels_found and all(k[-2:] == [tile, tile] for k in kernels_found)


def _solve_is_matmuls(text):
    """A delta-rule family's prompt program solves its chunks' triangular
    systems in float32 matmuls at the highest precision
    (``olmo_hybrid.unit_lower_solve``) and holds no ``triangular_solve``."""
    assert "InvertDiagBlocksLowerTriangular" not in text
    assert "triangular_solve" not in text
    dots = [
        line for line in text.splitlines()
        if "chunk_solve" in line and re.search(r" (convolution|dot)\(", line)
    ]
    assert dots and all(
        "operand_precision={highest,highest}" in line
        and re.search(r"= f32\[", line) for line in dots
    )


@pytest.mark.parametrize(
    "program,bucket",
    [("chunk", 8), ("prefill", 1024), _every_chip_run_compiles_it("suffix", 1024)],
)
def test_hybridmix_cell_programs_fit_the_chip(topo, program, bucket):
    """The programs of the cell ``olmo-hybrid-7b.hybridmix`` at its real
    sizes and engine options, compiled for the described chip: the pool is
    the 4 full-attention layers', the 12 linear layers' recurrent state
    stands beside it (stored with its minor axis of 192 laid out in 128-lane
    tiles, a third larger than reckoned), the kernels are in where the
    family runs them, nothing the size of a layer of the pool or of the
    whole state is copied (the state's layers are rewritten in place), and
    arguments + temps are under ISSUE 36's 14.5 GB."""
    compiled, cfg, cell, model = _compile_cell_program(
        topo, "olmo-hybrid-7b.hybridmix", program, bucket
    )
    d, lay, keys = cell.dims, cfg.kv_layout, cell.family.keys
    assert (lay.global_layers, lay.state_layers) == (4, 12) == (
        model.cache_layers, model.linear_layers)
    assert lay.table_width == 4096 // 16 + 1
    pages = keys.kv_bytes(d, cfg.num_pages, cfg.page_size)
    recurrent = keys.state_bytes(d, cfg.max_batch)
    assert pages == 4112 * 16 * 4 * 15_360 and recurrent == 16 * 12 * 2_280_960
    assert recurrent == lay.state_nbytes(cfg.max_batch, 2)
    state = 2 * keys.param_count(d) + pages + recurrent
    assert 12.6e9 < state < 12.8e9
    ma = compiled.memory_analysis()
    # the padded minor axis: 192 -> 256 lanes of float32
    padded = recurrent + 12 * 16 * 30 * 96 * 64 * 4
    assert state <= ma.argument_size_in_bytes < state + (padded - recurrent) + 0.05e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 14.5e9
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (program != "suffix")
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    whole_state = 12 * 16 * 30 * 96 * 192
    assert whole_state < layer_pool
    # what writes something the size of the state is a linear layer's update
    # of the state itself, in place: its output aliases the carried state
    lines = {line.strip()[:200]: line for line in text.splitlines()}
    sized = pool_sized_ops(text, whole_state)
    assert [row for row in sized if "aliasing" not in lines[row[1]]] == []
    assert all("f32[12,16,30,96,192]" in row[1] for row in sized)
    if program != "chunk":
        _solve_is_matmuls(text)


@pytest.mark.parametrize(
    "program,bucket",
    [("chunk", 8), _every_chip_run_compiles_it("prefill", 1024), ("suffix", 1024)],
)
def test_decodemix_cell_programs_fit_the_chip(topo, program, bucket):
    """The programs of the cell ``kimi-linear-48b.decodemix`` at its real
    sizes and engine options, compiled for the described chip: the pool is
    ONE array of the 2 latent layers' pages, stored 640 lanes wide, the 6 KDA
    layers' recurrent state stands beside it (128 x 128 a head: lane-aligned,
    stored as reckoned), the latent decode kernel is in the chunk program and
    reads one 128-token tile of 640 lanes a step and no V tile, nothing the
    size of a layer of the pool or of the whole state is copied (the state's
    layers are rewritten in place), no held expert stack is copied, and
    arguments + temps are under 10 GB."""
    compiled, cfg, cell, model = _compile_cell_program(
        topo, "kimi-linear-48b.decodemix", program, bucket
    )
    d, lay, keys = cell.dims, cfg.kv_layout, cell.family.keys
    assert (lay.global_layers, lay.state_layers) == (2, 6) == (
        model.cache_layers, model.kda_layers)
    assert (lay.latent_counted, lay.latent_width) == (576, 640)
    assert lay.table_width == 4096 // 16 + 1
    pages = keys.kv_bytes(d, cfg.num_pages, cfg.page_size)
    recurrent = keys.state_bytes(d, cfg.max_batch)
    assert pages == 16400 * 16 * 2 * 1_280 and recurrent == 64 * 6 * 2_170_880
    assert recurrent == lay.state_nbytes(cfg.max_batch, 2)
    state = 2 * keys.param_count(d) + pages + recurrent
    assert 9.04e9 < state < 9.06e9
    ma = compiled.memory_analysis()
    assert state <= ma.argument_size_in_bytes < state + 0.03e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 10e9
    text = compiled.as_text()
    # the grouped expert matmuls are XLA's ragged-dot custom calls in every
    # program; the one Pallas kernel of this family is the latent decode
    assert "tpu_custom_call" in text
    layer_pool = cfg.num_pages * cfg.page_size * lay.latent_width
    whole_state = 6 * 64 * 32 * 128 * 128
    layer_experts = 64 * 2304 * 1024
    assert layer_experts < layer_pool < whole_state
    # what writes something the size of a layer's held experts (the smallest
    # of the three) is the pool's own write or a KDA layer's update of the
    # state, in place: its output aliases the carried array
    lines = {line.strip()[:200]: line for line in text.splitlines()}
    sized = pool_sized_ops(text, layer_experts)
    loose = [row for row in sized if "aliasing" not in lines[row[1]]]
    if program == "chunk":
        assert loose == []
    else:
        # a prompt segment holds the pair decays of its sub-blocks, a sixth
        # of a GB a KDA layer at a time, and nothing else of that size
        assert all("f32[16,32,4,16,16,128]" in row[1] for row in loose)
    assert all(
        "f32[6,64,32,128,128]" in row[1] or "bf16[524800,640]" in row[1]
        or "f32[16,32,4,16,16,128]" in row[1] for row in sized)
    if program == "chunk":
        kernels_found = _kernel_args(text, "latent_decode_inline")
        # q [1, 32, 640], the new row [1, 1, 640], o [1, 32, 512], and ONE
        # double-buffered tile of 128 tokens of 640 lanes: no V tile
        assert kernels_found and all(
            k == [(1, 32, 640), (1, 1, 640), (1, 32, 512), (2, 128, 640)]
            for k in kernels_found)
    else:
        _solve_is_matmuls(text)


@pytest.mark.parametrize(
    "program,bucket",
    [("chunk", 8), _every_chip_run_compiles_it("prefill", 1024), ("suffix", 1024)],
)
def test_ssmchat_cell_programs_fit_the_chip(topo, program, bucket):
    """The programs of the cell ``nemotron-3-super-120b.ssmchat`` at its real
    sizes and engine options, compiled for the described chip: the pool is
    the ONE attention layer's pages of 2 KV heads, the 5 Mamba-2 layers'
    recurrent state stands beside it (128 state channels minor: lane-aligned,
    stored as reckoned, 2.72 GB at 128 slots), the paged decode and prefill
    kernels are in at 16 query rows a KV head, nothing the size of a layer of
    the state (the smallest of the three pools) is held as a temp (the
    state's layers are rewritten in place), no held expert stack is copied,
    and arguments + temps are under 13 GB."""
    compiled, cfg, cell, model = _compile_cell_program(
        topo, "nemotron-3-super-120b.ssmchat", program, bucket
    )
    d, lay, keys = cell.dims, cfg.kv_layout, cell.family.keys
    assert (lay.global_layers, lay.state_layers) == (1, 5) == (
        model.cache_layers, model.mamba_layers)
    assert lay.state_shape == (128, 64, 128) and lay.tail_shape == (3, 10240)
    assert lay.table_width == 4096 // 16 + 1
    pages = keys.kv_bytes(d, cfg.num_pages, cfg.page_size)
    recurrent = keys.state_bytes(d, cfg.max_batch)
    assert pages == 24592 * 16 * 1_024 and recurrent == 128 * 5 * 4_255_744
    # state bytes as laid out equal to as counted
    assert recurrent == lay.state_nbytes(cfg.max_batch, 2)
    state = 2 * keys.param_count(d) + pages + recurrent
    assert 12.41e9 < state < 12.43e9
    ma = compiled.memory_analysis()
    assert state <= ma.argument_size_in_bytes < state + 0.04e9
    assert ma.temp_size_in_bytes < 0.3e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13e9
    text = compiled.as_text()
    # the grouped expert matmuls are XLA's ragged-dot custom calls in the
    # prompt programs; the decode chunk's 128 rows compute every held expert
    # (models/moe.py:held_dense_ffn: batched matmuls that read the stacks as
    # stored) and hold none; the Pallas kernels are the attention layer's
    # (the suffix program's attention over the pages is XLA's)
    assert "tpu_custom_call" in text
    assert ("ragged-dot" in text) == (program != "chunk")
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    layer_state = 128 * 128 * 64 * 128
    layer_experts = 128 * 1024 * 2688
    assert layer_pool < layer_state < layer_experts
    # what writes something the size of a layer of the pages (the smallest)
    # is the pool's own write or a Mamba-2 layer's update of the state, in
    # place: its output aliases the carried array
    lines = {line.strip()[:200]: line for line in text.splitlines()}
    sized = pool_sized_ops(text, layer_pool)
    assert [row for row in sized if "aliasing" not in lines[row[1]]] == []
    assert all(
        "f32[5,128,128,64,128]" in row[1] or "bf16[1,24592,16,256]" in row[1]
        for row in sized)
    if program == "chunk":
        kernels_found = _kernel_args(text, "paged_decode_inline")
        tile = (2, 128, model.kv_dim)
        assert len(kernels_found) >= 1 and all(
            k[-2:] == [tile, tile] for k in kernels_found)
        # the state's step is TWO ops a layer, which the roofline metric's
        # reader picks by the whole state as their first operand
        assert len(set(re.findall(
            r"%(multiply_reduce_fusion[\w.]*) = f32\[128,128,64\]\S* fusion\(", text
        ))) == 5
        # ... and a layer's routed experts ONE, with the stacks as operands
        assert len(set(re.findall(
            r"%(fusion[\w.]*) = bf16\[128,1024\]\S* fusion\(%get-tuple-element", text
        ))) >= 5


@pytest.mark.parametrize(
    "program,bucket",
    [("chunk", 8), _every_chip_run_compiles_it("prefill", 1024)],
)
def test_mtpmix_cell_programs_fit_the_chip(topo, program, bucket):
    """The programs of the cell ``k-exaone-236b.mtpmix`` at its real sizes and
    engine options (``--speculative-mtp 1``), compiled for the described
    chip: three layers of pages (two full layers and the prediction module's
    block) beside six rings of 128 + 1,024 positions, the decode kernel in at
    TWO query positions a slot (16 query rows a KV head), no stack of weights
    copied into another layout (``wq`` and ``wk`` are stored as the decode
    step's few rows read them), no held expert stack sliced out (every period
    is traced by itself), arguments 12.57 GB and arguments + temps under
    13.65 GB."""
    compiled, cfg, cell, model = _compile_cell_program(
        topo, "k-exaone-236b.mtpmix", program, bucket
    )
    d, lay, keys = cell.dims, cfg.kv_layout, cell.family.keys
    assert cfg.speculative_mtp == 1 and cfg.model.serve_mtp
    assert (lay.global_layers, lay.window_layers, lay.window) == (3, 6, 128)
    assert lay.ring_pages * cfg.page_size == 128 + 1024
    assert lay.table_width == 4096 // 16 + 72
    pages = keys.kv_bytes(d, cfg.num_pages, cfg.page_size)
    rings = keys.ring_bytes(d, cfg.max_batch, 1024)
    assert (pages, rings) == (2_419_064_832, 1_358_954_496)
    state = 2 * keys.param_count(d) + pages + rings
    assert 12.56e9 < state < 12.58e9
    ma = compiled.memory_analysis()
    assert state <= ma.argument_size_in_bytes < state + 0.04e9
    assert ma.temp_size_in_bytes < 1.1e9
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 13.65e9
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    # no stack of weights in another layout: what the chip's compiler did to
    # ``wq`` and ``wk`` stored [in, out], 0.97 GB once a chunk
    assert not re.search(r"copy\(%?params__", text)
    # the held experts: grouped matmuls (the Pallas one at these widths) in
    # the prompt programs, every held expert on every row in the chunk
    # (by the op's name: a kernel's serialized body is base64 and may spell
    # anything)
    assert bool(re.search(r"%gmm[\w.\-]* = ", text)) == (program != "chunk")
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    layer_ring = cfg.max_batch * 1152 * model.kv_dim
    layer_experts = 8 * 6144 * 2048
    assert layer_ring < layer_experts < layer_pool
    lines = {line.strip()[:200]: line for line in text.splitlines()}
    sized = pool_sized_ops(text, layer_experts)
    # nothing the size of a layer's experts (or of a layer of the pages) is
    # written but the pools' own writes, in place; the chunk also stages a
    # layer's ``wq`` / ``wo`` (96 MB, the compiler's own prefetch: same shape
    # as one expert stack's layer, no copy of it)
    assert [
        row for row in sized
        if "aliasing" not in lines[row[1]] and "bf16[1,8192,6144]" not in row[1]
        and "bf16[8192,6144]" not in row[1]
    ] == []
    if program == "chunk":
        kernels_found = _kernel_args(text, "paged_decode_inline")
        tile = (2, 128, model.kv_dim)
        # one a layer of the two periods, and the module's
        assert len(kernels_found) == 9 and all(
            k[-2:] == [tile, tile] for k in kernels_found)
        # two query positions: 2 x 64 query rows a slot, two new rows
        assert all(k[0] == (1, 128, 128) and k[1] == (1, 2, 1024)
                   for k in kernels_found)
        # the routed experts' THREE ops a layer (gate and up over every held
        # expert on the chunk's 96 rows, the down matmul with the weighted
        # sum), which the roofline metric's reader picks by these results and
        # by the whole stack as their first operand: eight expert layers a
        # step, the module's among them
        shaped = [
            line for line in text.splitlines()
            if re.search(r"^\s*%fusion[\w.]* = bf16\[(8,96,2048|96,6144)\]", line)
            and "kind=kOutput" in line
        ]
        assert len(shaped) >= 3 * 8


def _kernel_args(text, name, space="vmem"):
    """For every Mosaic kernel called ``name`` in a compiled program's HLO
    text, the shapes of its VMEM operands in order (blocks in, blocks out,
    then scratch), or with ``space`` "smem" of its scalar operands
    (prefetched, then scratch), read from the kernel's own serialized
    module."""
    import base64

    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir

    ctx = mlir.make_ir_context()  # the compiler's own kernels are text
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True
    found = []
    for line in text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        body = re.search(r'"custom_call_config":\{"body":"([^"]+)"', line)
        with ctx:
            asm = ir.Module.parse(base64.b64decode(body.group(1))).operation.get_asm()
        if not asm.startswith(f"module @{name} "):
            continue
        args = asm[asm.index("^bb0(") : asm.index("\n", asm.index("^bb0("))]
        found.append([
            tuple(int(n) for n in shape.split("x"))
            for shape in re.findall(
                rf"memref<([0-9x]+)x[a-z0-9]+, #tpu.memory_space<{space}>>", args
            )
        ])
    return found


@pytest.mark.parametrize(
    "name",
    ["mistral-7b.chat", "mixtral-8x7b.batch", "smallthinker-21b.longmix"],
)
def test_accepted_cells_decode_walks_a_128_token_tile(topo, name):
    """The ``chunk`` program of each accepted cell at its real sizes and
    engine options, compiled for the described chip: every inline decode
    kernel in it (one of the scan's body; one a layer of a period of the
    patterned family) has K and V scratch of two 128-token tiles, eight
    16-token pages a step, which no caller chose (PERF.md section 6,
    PR 31), and still nothing the size of a layer of the pool is copied."""
    compiled, cfg, _, model = _compile_cell_program(topo, name, "chunk")
    assert cfg.page_size == 16
    text = compiled.as_text()
    tile = (2, 128, model.kv_dim)
    kernels_found = _kernel_args(text, "paged_decode_inline")
    assert kernels_found and all(k[-2:] == [tile, tile] for k in kernels_found)
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    # ... but for the chat chunk's copy of ``wq`` into another layout, once
    # a chunk and larger than a layer of that cell's pool (PERF.md section 7)
    assert [
        row for row in pool_sized_ops(text, layer_pool)
        if "copy(%params__layers____wq__" not in row[1]
    ] == []


#: cell -> (the decode kernel's name, its call sites in the ``chunk`` program:
#: one of a scan's body, one a layer of a traced period, and the module's)
_DECODE_KERNELS = {
    "mistral-7b.chat": ("paged_decode_inline", 1),
    "mixtral-8x7b.batch": ("paged_decode_inline", 1),
    "smallthinker-21b.longmix": ("paged_decode_inline", 4),
    "ouro-2.6b.loopchat": ("paged_decode_inline", 1),
    "olmo-hybrid-7b.hybridmix": ("paged_decode_inline", 1),
    "kimi-linear-48b.decodemix": ("latent_decode_inline", 2),
    "nemotron-3-super-120b.ssmchat": ("paged_decode_inline", 1),
    "k-exaone-236b.mtpmix": ("paged_decode_inline", 9),
}


@pytest.mark.parametrize("name", list(_DECODE_KERNELS))
def test_every_cell_decode_kernel_carries_the_walk_across_sequences(topo, name):
    """The ``chunk`` program of every cell at its real sizes and engine
    options, compiled for the described chip (the compiles the tests above
    made): the decode kernel lowers at every cell's shape — plain rows,
    rings, two query positions a slot, latent pages — with the ONE scalar
    the walk carries from a sequence to the next (the tiles walked so far,
    SMEM after the prefetched table, lengths and layer), and the program
    holds the kernels it held: the walk across sequences is the kernel's
    own, no caller's and no new program's (PERF.md section 6, PR 48)."""
    compiled, cfg, _, _ = _compile_cell_program(topo, name, "chunk")
    kernel, sites = _DECODE_KERNELS[name]
    scalars = _kernel_args(compiled.as_text(), kernel, space="smem")
    assert len(scalars) == sites
    for table, lens, layer, walked in scalars:
        assert table[0] == cfg.max_batch and lens == (cfg.max_batch,)
        assert layer == walked == (1,)


@pytest.mark.parametrize(
    "program,bucket",
    [("prefill", 1024), _every_chip_run_compiles_it("suffix", 1024),
     ("suffix", 16), ("chunk", 8)],
)
def test_batch_cell_prompt_rows_go_to_their_own_experts(topo, program, bucket):
    """The programs of the cell ``mixtral-8x7b.batch`` at its real sizes and
    engine options, compiled for the described chip. A segment of 1,024 rows
    runs its expert layers as Pallas grouped matmuls over the WHOLE expert
    stacks: no layer's experts are copied out for the custom call (what made
    PR 28's trial 41% slower), and the flops are those of 2 experts a token,
    not of 8. The decode chunk (64 rows) and a 16-row suffix segment stay the
    dense form, bound by the same weight reads either way: no grouped matmul
    in them (models/moe.py:takes_grouped; PERF.md section 6, PR 35)."""
    from llm_d_fast_model_actuation_tpu.models import moe

    compiled, cfg, _, model = _compile_cell_program(
        topo, "mixtral-8x7b.batch", program, bucket
    )
    text = compiled.as_text()
    rows = cfg.max_batch if program == "chunk" else bucket
    grouped = re.findall(r"%(gmm|ragged-dot)[\w.\-]* = ", text)
    if rows < moe.GROUPED_MIN_ROWS:
        assert grouped == []
        return
    assert grouped == ["gmm"] * 3  # a scan's body: one layer
    layer_experts = (
        model.num_experts * model.hidden_size * model.intermediate_size
    )
    # ... but for the logits of a whole segment, which the prefill programs
    # of every family compute before they take the last (PERF.md section 5)
    logits = f"bf16[{bucket},{model.vocab_size}]"
    assert [
        row for row in pool_sized_ops(text, layer_experts)
        if logits not in row[1]
    ] == []
    ma = compiled.memory_analysis()
    assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 9e9
    # XLA counts a loop's body once: one layer
    per_expert = 3 * 2 * model.hidden_size * model.intermediate_size
    dense = rows * model.num_experts * per_expert
    routed = rows * model.experts_per_token * per_expert
    flops = compiled.cost_analysis()["flops"]
    assert routed < flops < routed + 0.5 * dense


def test_longmix_suffix_program_scores_no_whole_table_row(topo):
    """The cell's ``suffix(128)`` program, compiled for the described chip:
    its XLA suffix attention (ops/attention.py:paged_suffix_attention) walks
    the table row in blocks of 1,024 columns, so no operation holds float32
    scores as wide as a full-attention layer's row (16,384 columns) or a
    ring (5,120), the score tiles of one block are there, and the walk is a
    loop the program did not have (the periods' scan and the query blocks'
    were its only ones)."""
    import re

    compiled, cfg, _, _ = _compile_cell_program(
        topo, "smallthinker-21b.longmix", "suffix", 128
    )
    lay = cfg.kv_layout
    widths = {lay.pages_per_seq * cfg.page_size, lay.ring_pages * cfg.page_size}
    assert widths == {16384, 5120}
    text = compiled.as_text()
    minor = {int(m) for m in re.findall(r"f32\[[\d,]*?(\d+)\]", text)}
    assert not widths & minor, sorted(widths & minor)
    assert re.search(r"f32\[1,128,4,7,1024\]", text)
    assert len(re.findall(r"\bwhile\(", text)) >= 3
