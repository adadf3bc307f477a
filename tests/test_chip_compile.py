"""AOT compiles of the Pallas kernels for a described (not attached) TPU v5e.

The chip's compiler is installed in the CPU sandbox and compiles for a
topology description, so what Mosaic would refuse on the chip (tiling,
layout, VMEM) is refused here, at real head shapes, at no chip time —
interpret mode hides all of it. Nothing runs: these tests say nothing about
results or times. Skipped where the topology cannot be described.
"""

import json
import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from llm_d_fast_model_actuation_tpu.ops import pallas as kernels

#: (heads, kv_heads, head_dim): TinyLlama, Llama-3-8B, Gemma-3-4B widths
HEAD_SHAPES = [(32, 4, 64), (32, 8, 128), (8, 4, 256)]
PAGE, NUM_PAGES, BATCH, PAGES_PER_SEQ = 16, 512, 8, 128


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e}")


@pytest.fixture(autouse=True)
def no_compile_cache():
    # an AOT compile for a described chip is written to the persistent
    # cache but cannot be read back without the chip (it warns and
    # recompiles), so keep the cache out of these tests
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args, **kw):
    text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _kernel_args(kind, h, kvh, d, sharding):
    def s(shape, dtype=jnp.bfloat16, sh=sharding):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sh)

    # the pool as stored (engine/kv_cache.py), two layers of it
    pages = s((2, NUM_PAGES, PAGE, kvh * d))
    table = s((BATCH, PAGES_PER_SEQ), jnp.int32)
    lens = s((BATCH,), jnp.int32)
    layer = s((), jnp.int32)
    if kind == "decode_inline":
        new = s((BATCH, kvh, d))
        return (s((BATCH, h, d)), pages, pages, new, new, table, lens, layer)
    if kind == "decode":
        return (s((BATCH, h, d)), pages, pages, table, lens, layer)
    if kind == "ragged":
        rows = s((256,), jnp.int32)
        return (s((256, h, d)), pages, pages, table, rows, rows, layer)
    assert kind == "prefill"
    kv = s((2, 512, kvh, d))
    return (s((2, 512, h, d)), kv, kv, s((2,), jnp.int32))


KERNELS = {
    "decode_inline": kernels.paged_decode_attention_inline_pallas,
    "decode": kernels.paged_decode_attention_pallas,
    "ragged": kernels.ragged_paged_attention_pallas,
    "prefill": kernels.causal_prefill_attention_pallas,
}


@pytest.mark.parametrize("shape", HEAD_SHAPES, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", list(KERNELS))
def test_kernel_compiles_for_v5e(topo, kind, shape):
    one = SingleDeviceSharding(topo.devices[0])
    _compile(KERNELS[kind], *_kernel_args(kind, *shape, one))


def _tp_mesh(topo, tp=4):
    import numpy as np

    from llm_d_fast_model_actuation_tpu.parallel.mesh import AXES

    return Mesh(np.array(topo.devices[:tp]).reshape(1, 1, 1, tp, 1), AXES)


@pytest.mark.parametrize("shape", [(32, 8, 128), (32, 8, 64)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", ["decode_inline", "ragged", "prefill"])
def test_sharded_kernel_compiles_for_v5e_2x2(topo, kind, shape):
    """The shard_map ports on a 4-device tp mesh of described chips — per
    shard 8 query heads over 2 KV heads (Llama-3-8B at tp=4) — with the
    operand specs the serving dispatcher uses (ops/attention.py)."""
    from llm_d_fast_model_actuation_tpu.ops import attention as attn
    from llm_d_fast_model_actuation_tpu.ops.pallas.decode import shard_over_tp

    mesh = _tp_mesh(topo)
    table, lens, layer = P(None, None), P(None), P()
    in_specs = {
        "decode_inline": (attn._HEADS3, attn.POOL_SPEC, attn.POOL_SPEC,
                          attn._HEADS3, attn._HEADS3, table, lens, layer),
        "ragged": (attn._HEADS3, attn.POOL_SPEC, attn.POOL_SPEC, table, lens,
                   lens, layer),
        "prefill": (attn._HEADS4, attn._HEADS4, attn._HEADS4, lens),
    }[kind]
    out_spec = attn._HEADS4 if kind == "prefill" else attn._HEADS3
    args = [
        jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=NamedSharding(mesh, spec)
        )
        for a, spec in zip(_kernel_args(kind, *shape, None), in_specs)
    ]
    _compile(shard_over_tp(mesh, KERNELS[kind], in_specs, out_spec), *args)


def _compile_engine_program(topo, program, bucket, tp, model=None, **engine):
    """AOT-compile one serving program of an engine (of a tiny model unless
    one is given) under ``pallas`` on a ``tp``-device mesh of described
    chips; returns ``(compiled, engine config)``."""
    from llm_d_fast_model_actuation_tpu.engine import EngineConfig, exec_pool
    from llm_d_fast_model_actuation_tpu.models import llama
    from llm_d_fast_model_actuation_tpu.ops import attention as attn

    model = model or llama.LlamaConfig(
        vocab_size=512, hidden_size=256, num_layers=3, num_heads=8,
        num_kv_heads=4, head_dim=128, intermediate_size=512,
        max_seq_len=256, attention_impl="pallas",
    )
    engine.setdefault("max_batch", 4)
    engine.setdefault("decode_chunk", 4)
    cfg = EngineConfig(model=model, attention_impl="pallas", **engine)
    attn.set_pallas_interpret(False)  # compile the kernels for the chip
    try:
        return exec_pool.compile_program(
            cfg, program, bucket, mesh=_tp_mesh(topo, tp)
        ), cfg
    finally:
        attn.set_pallas_interpret(True)


@pytest.mark.parametrize("program,bucket", [("chunk", 4), ("prefill", 16)])
def test_sharded_engine_program_compiles_for_v5e_2x2(topo, program, bucket):
    """A whole serving program of a tp=4 engine under ``pallas``: GSPMD
    cannot partition a Mosaic kernel, so any kernel the program reaches
    outside a shard_map fails here (and only here: interpret mode lowers
    to plain XLA ops, which partition fine)."""
    compiled, _ = _compile_engine_program(
        topo, program, bucket, tp=4, num_pages=64
    )
    assert "tpu_custom_call" in compiled.as_text()


# -- nothing pool-sized per layer --------------------------------------------
#
# The KV pool is stored as the kernels read it and the forward indexes it by
# layer (engine/kv_cache.py), so no serving program may slice a layer out of
# the pool, re-lay it out, or stack it back: on the chip each of those is a
# copy of pool size per layer per step (PERF.md section 6, PR 26). The
# compiled HLO decides, not the source.

#: instructions that move no bytes, whatever their shape
_FREE_OPS = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "while", "call",
    "conditional", "opt-barrier",
}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (\(?[a-z0-9]+\[.*?) ([a-z][a-z\-]*)\("
)


def _pool_sized_ops(text, min_elems):
    """(opcode, line) of every instruction of the compiled module, outside
    fused computations (what a fusion computes inside is never materialized),
    that writes ``min_elems`` elements or more — except the in-place cache
    write, a scatter whose output aliases its operand (named ``kv_write``,
    or, where an unrolled clone lost its name, a fusion that aliases an
    operand and whose computation is a scatter)."""
    fused = set(re.findall(r"fusion\(.*calls=%?([\w.\-]+)", text))
    found, skipping, scatters, inside = [], False, set(), None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            inside = head.group(1)
        elif " scatter(" in line:
            scatters.add(inside)
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            skipping = head.group(1) in fused
            continue
        m = _INSTRUCTION.match(line)
        if skipping or not m or m.group(2) in _FREE_OPS:
            continue
        elems = max(
            (
                math.prod(int(d) for d in dims.split(",") if d)
                for dims in re.findall(r"[a-z0-9]+\[([0-9,]*)\]", m.group(1))
            ),
            default=0,
        )
        calls = re.search(r"calls=%?([\w.\-]+)", line)
        in_place_write = "aliasing" in line and (
            "kv_write" in line or (calls and calls.group(1) in scatters)
        )
        if elems >= min_elems and not in_place_write:
            found.append((m.group(2), line.strip()[:200]))
    return found


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
    found = _pool_sized_ops(text, 6144 * 16 * 1024)
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
    compiled, cfg = _compile_engine_program(
        topo, program, bucket, tp, num_pages=1024
    )
    # one device's share of one layer of the pool, in elements
    layer_pool = cfg.num_pages * cfg.page_size * cfg.model.kv_dim // tp
    assert _pool_sized_ops(compiled.as_text(), layer_pool) == []
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
    compiled, cfg = _compile_engine_program(
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
    assert _pool_sized_ops(text, smaller) == []
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
    compiled, cfg = _CELL_PROGRAMS[key] = _compile_engine_program(
        topo, program, bucket or args.decode_chunk, tp=1, model=model,
        max_batch=args.max_batch, page_size=args.page_size,
        num_pages=args.num_pages, decode_chunk=args.decode_chunk,
        max_prefill_tokens=args.max_prefill_tokens,
        # the prefix cache refuses a model with per-slot state
        prefix_caching=llama.patterned(model) is None,
    )
    return compiled, cfg, cell, model


@pytest.mark.parametrize(
    "program,bucket", [("chunk", 8), ("prefill", 1024), ("suffix", 1024)]
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
        row for row in _pool_sized_ops(text, layer_experts)
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
        row for row in _pool_sized_ops(text, layer_pool)
        if logits not in row[1]
        and not re.search(r"copy\(%params__layers____w[qk]__", row[1])
    ] == []
    if program == "chunk":
        tile = (2, 128, model.kv_dim)
        kernels_found = _kernel_vmem_args(text, "paged_decode_inline")
        assert kernels_found and all(k[-2:] == [tile, tile] for k in kernels_found)


@pytest.mark.parametrize(
    "program,bucket", [("chunk", 8), ("prefill", 1024), ("suffix", 1024)]
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
    sized = _pool_sized_ops(text, whole_state)
    assert [row for row in sized if "aliasing" not in lines[row[1]]] == []
    assert all("f32[12,16,30,96,192]" in row[1] for row in sized)


@pytest.mark.parametrize(
    "program,bucket", [("chunk", 8), ("prefill", 1024), ("suffix", 1024)]
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
    sized = _pool_sized_ops(text, layer_experts)
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
        kernels_found = _kernel_vmem_args(text, "latent_decode_inline")
        # q [1, 32, 640], the new row [1, 1, 640], o [1, 32, 512], and ONE
        # double-buffered tile of 128 tokens of 640 lanes: no V tile
        assert kernels_found and all(
            k == [(1, 32, 640), (1, 1, 640), (1, 32, 512), (2, 128, 640)]
            for k in kernels_found)


def _kernel_vmem_args(text, name):
    """For every Mosaic kernel called ``name`` in a compiled program's HLO
    text, the shapes of its VMEM operands in order (blocks in, blocks out,
    then scratch), read from the kernel's own serialized module."""
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
                r"memref<([0-9x]+)x[a-z0-9]+, #tpu.memory_space<vmem>>", args
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
    kernels_found = _kernel_vmem_args(text, "paged_decode_inline")
    assert kernels_found and all(k[-2:] == [tile, tile] for k in kernels_found)
    layer_pool = cfg.num_pages * cfg.page_size * model.kv_dim
    # ... but for the chat chunk's copy of ``wq`` into another layout, once
    # a chunk and larger than a layer of that cell's pool (PERF.md section 7)
    assert [
        row for row in _pool_sized_ops(text, layer_pool)
        if "copy(%params__layers____wq__" not in row[1]
    ] == []


@pytest.mark.parametrize(
    "program,bucket",
    [("prefill", 1024), ("suffix", 1024), ("suffix", 16), ("chunk", 8)],
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
        row for row in _pool_sized_ops(text, layer_experts)
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


def test_lane_constraint_is_named_not_a_mosaic_crash(topo):
    """A per-device KV row narrower than the 128-lane tile is refused with
    the constraint spelled out (TinyLlama at tp=4: one 64-wide KV head)."""
    one = SingleDeviceSharding(topo.devices[0])
    with pytest.raises(ValueError, match="multiple of 128"):
        _compile(KERNELS["decode"], *_kernel_args("decode", 8, 1, 64, one))


# -- CPU rehearsal of chip_smoke.py ---------------------------------------------
#
# The smoke's phase functions at tiny size, with the device they must find
# injected here (``platform="cpu"``; Pallas in interpreter mode, the launcher
# on one mock chip): wrong paths, arguments and control flow are found without
# the chip (on-chip-measurement §2.1). What only the chip can refuse is above.


@pytest.fixture(scope="module")
def smoke():
    import importlib.util
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(repo, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolves the module by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_plan(smoke, tmp_path_factory):
    smoke.LOG_DIR = str(tmp_path_factory.mktemp("chip-smoke-logs"))
    return smoke.Plan(
        platform="cpu",
        model="tiny",
        second_model="tiny-gemma",
        engine_options="--num-pages 64 --max-batch 2 --page-size 8 "
        "--max-model-len 128 --sleep-release-devices always",
        launcher_args=("--mock-chips", "--mock-chip-count", "1"),
        head_shapes=((4, 2, 16),),
        page_size=8,
        context=64,
        interpret=True,
        prompt_lens=(5, 40),
        max_tokens=6,
        ready_timeout_s=240.0,
        tp_model="tiny",
        tp=2,
        tp_impls=("grouped", "reference"),
    )


def test_smoke_rehearsal_kernels(smoke, tiny_plan):
    rows = smoke.check_kernels(tiny_plan)
    assert [r["kernel"] for r in rows] == [
        "decode", "decode_inline", "ragged", "prefill",
    ]


@pytest.fixture()
def child_devices(monkeypatch):
    """How many virtual CPU devices the smoke's children see (this process
    keeps conftest's eight): the smoke holds each engine to its count."""

    def set_count(n: int) -> None:
        monkeypatch.setenv(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}"
        )

    return set_count


def test_smoke_rehearsal_server(smoke, tiny_plan, capsys, child_devices):
    child_devices(1)
    dev = smoke.phase_server(tiny_plan)
    assert dev["platform"] == "cpu" and dev["count"] == 1
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "server" and line["same_tokens_after_wake"]


def test_smoke_rehearsal_launcher_time_share(
    smoke, tiny_plan, capsys, child_devices
):
    child_devices(1)
    smoke.phase_launcher(tiny_plan)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["release_in_process"] and line["same_tokens_after_reacquire"]
    assert line["chip_mode"] == "naive-mock"


def test_smoke_rehearsal_sharded_phase(smoke, tiny_plan, capsys, child_devices):
    """The --chips 4 phase on virtual devices (tiny has two KV heads: tp=2):
    meshes, sharded init and the per-chip byte check (§2.2)."""
    child_devices(2)
    dev = smoke.phase_four_chips(tiny_plan)
    assert dev["count"] == 2
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "four_chips" and line["same_tokens"]
    assert all(c["ok"] for c in line["compared"])


def test_impl_comparison_accepts_only_demonstrated_near_ties(smoke):
    """Two implementations may part ways where the reference itself scores
    the other's token within the tolerance of its own, and nowhere else."""
    def run(tokens, lps=(-1.0, -1.0, -1.0)):
        return {"tokens": tokens, "logprobs": list(lps)}

    def never(i):
        raise AssertionError("nothing to score when the tokens agree")

    a = run([5, 6, 7])
    same = smoke.compare_impls(a, run([5, 6, 7]), never)
    assert same["ok"] and same["tokens_agreeing"] == 3
    # the reference gives a's token -1.02 where its own has -1.0: a tie
    at_tie = smoke.compare_impls(a, run([5, 9, 7]), lambda i: -1.02)
    assert at_tie["ok"] and at_tie["tokens_agreeing"] == 1
    assert at_tie["tie_gap"] == 0.02
    # ... and -2.0 is a clear loser: a real disagreement
    assert not smoke.compare_impls(a, run([8, 6, 7]), lambda i: -2.0)["ok"]
    drift = smoke.compare_impls(a, run([5, 6, 7], (-1.5, -1.0, -1.0)), never)
    assert not drift["ok"] and drift["max_logprob_diff"] == 0.5


def test_smoke_refuses_a_device_it_was_not_promised(smoke, tiny_plan):
    """No fallback: the same run held to ``tpu`` fails on this CPU."""
    import dataclasses

    with pytest.raises(RuntimeError, match="needs a tpu device"):
        smoke.check_kernels(dataclasses.replace(tiny_plan, platform="tpu"))


# -- the rules this bring-up made explicit --------------------------------------


def test_auto_attention_impl_is_a_rule_on_backend_and_shape(monkeypatch):
    from llm_d_fast_model_actuation_tpu.engine import engine as eng
    from llm_d_fast_model_actuation_tpu.engine.server import MODEL_CONFIGS
    from llm_d_fast_model_actuation_tpu.models import llama

    tinyllama = MODEL_CONFIGS["tinyllama-1.1b"]()  # 4 KV heads of 64
    assert eng.resolve_attention_impl("auto", tinyllama) == "grouped"  # cpu
    monkeypatch.setattr(eng.jax, "default_backend", lambda: "tpu")
    assert eng.resolve_attention_impl("auto", tinyllama) == "pallas"
    assert eng.resolve_attention_impl("auto", tinyllama, tp=2) == "pallas"
    # one 64-wide KV head per device does not fill the 128 lanes
    assert eng.resolve_attention_impl("auto", tinyllama, tp=4) == "grouped"
    assert eng.resolve_attention_impl("auto", llama.LlamaConfig.tiny()) == "grouped"
    assert eng.resolve_attention_impl("reference", tinyllama, tp=4) == "reference"


def test_compile_cache_has_one_rule(monkeypatch, tmp_path):
    from llm_d_fast_model_actuation_tpu.utils import compile_cache as cc

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    for var in (cc.ENV, "FMA_EXEC_SPILL_DIR", "JAX_PLATFORMS",
                "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"):
        monkeypatch.delenv(var, raising=False)
    was = (jax.config.jax_compilation_cache_dir,
           jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        # set from outside: that directory, and the spill follows it
        monkeypatch.setenv(cc.ENV, str(tmp_path / "x"))
        assert cc.arm() == str(tmp_path / "x")
        assert jax.config.jax_compilation_cache_dir == str(tmp_path / "x")
        assert os.environ["FMA_EXEC_SPILL_DIR"] == str(tmp_path / "x" / "exec-pool")
        assert cc.stats()["dir"] == str(tmp_path / "x")
        # unset, held to the CPU: nothing is armed
        monkeypatch.delenv(cc.ENV)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert cc.arm() == "" and cc.ENV not in os.environ
        # unset, a chip expected: one fixed path inside the checkout,
        # exported for children
        monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
        assert cc.arm() == os.path.join(repo, ".xla-cache") == os.environ[cc.ENV]
    finally:
        jax.config.update("jax_compilation_cache_dir", was[0])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", was[1])
        compilation_cache.reset_cache()
