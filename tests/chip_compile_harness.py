"""What the files that compile for a described (not attached) TPU v5e share:
``tests/test_chip_compile.py`` (the kernels) and
``tests/test_chip_compile_cells.py`` (whole serving programs).

The chip's compiler is installed in the CPU sandbox and compiles for a
topology description, so what Mosaic would refuse on the chip (tiling,
layout, VMEM) is refused here, at real head shapes, at no chip time —
interpret mode hides all of it. Nothing runs: these tests say nothing about
results or times. Skipped where the topology cannot be described.
"""

import math
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        if "libtpu multi-process lockfile" in str(e):
            # two files use this fixture and may run on two workers at once:
            # a skip here would be 23 or 31 passes fewer, in silence
            pytest.fail(
                "another process holds libtpu: run these files in one process, "
                "or as the driver does (README.md, Running)", pytrace=False
            )
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


def compile_kernel(fn, *args, **kw):
    text = jax.jit(lambda *a: fn(*a, **kw)).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def tp_mesh(topo, tp=4):
    import numpy as np

    from llm_d_fast_model_actuation_tpu.parallel.mesh import AXES

    return Mesh(np.array(topo.devices[:tp]).reshape(1, 1, 1, tp, 1), AXES)


def compile_engine_program(topo, program, bucket, tp, model=None, **engine):
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
            cfg, program, bucket, mesh=tp_mesh(topo, tp)
        ), cfg
    finally:
        attn.set_pallas_interpret(True)


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


def pool_sized_ops(text, min_elems):
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
