"""AOT compiles of the Pallas kernels for a described (not attached) TPU v5e,
alone and as the shard_map ports on a 2x2 mesh (``-k compiles``; what the
compiles are and are not: ``chip_compile_harness.py``). The whole serving
programs of the benchmark's cells are ``test_chip_compile_cells.py``, the CPU
rehearsal of ``chip_smoke.py`` is ``test_chip_smoke_rehearsal.py``: three
files, so that ``--dist loadfile`` gives them to three workers.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from chip_compile_harness import (  # noqa: F401 — fixtures
    compile_engine_program,
    compile_kernel,
    no_compile_cache,
    topo,
    tp_mesh,
)
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from llm_d_fast_model_actuation_tpu.ops import pallas as kernels

#: (heads, kv_heads, head_dim): TinyLlama, Llama-3-8B, Gemma-3-4B widths
HEAD_SHAPES = [(32, 4, 64), (32, 8, 128), (8, 4, 256)]
PAGE, NUM_PAGES, BATCH, PAGES_PER_SEQ = 16, 512, 8, 128


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
    compile_kernel(KERNELS[kind], *_kernel_args(kind, *shape, one))


@pytest.mark.parametrize(
    "shape,window",
    [((64, 8, 128), 0), ((64, 8, 128), 128), ((32, 8, 128), 128), ((8, 4, 256), 0)],
    ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else f"window{v}")
def test_decode_kernel_with_two_query_positions_compiles_for_v5e(topo, shape, window):
    """The inline decode kernel of a verify step (speculative decoding with
    the model's own prediction module): q [batch, 2, heads, head_dim], two new
    K and V rows a slot, 2 * group query rows a KV head (16 at K-EXAONE's 64 x
    8 x 128), one row iota that says which query a row belongs to, the second
    new row masked for the first query's rows; on full layers and on rings."""
    one = SingleDeviceSharding(topo.devices[0])
    q, pages, _, new, _, table, lens, layer = _kernel_args(
        "decode_inline", *shape, one)
    two = lambda a: jax.ShapeDtypeStruct(  # noqa: E731
        (a.shape[0], 2, *a.shape[1:]), a.dtype, sharding=one)
    text = compile_kernel(
        KERNELS["decode_inline"], two(q), pages, pages, two(new), two(new),
        table, lens, layer, **({"window": window} if window else {}))
    assert "paged_decode_inline" in text


@pytest.mark.parametrize("shape", [(32, 8, 128), (32, 8, 64)],
                         ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kind", ["decode_inline", "ragged", "prefill"])
def test_sharded_kernel_compiles_for_v5e_2x2(topo, kind, shape):
    """The shard_map ports on a 4-device tp mesh of described chips — per
    shard 8 query heads over 2 KV heads (Llama-3-8B at tp=4) — with the
    operand specs the serving dispatcher uses (ops/attention.py)."""
    from llm_d_fast_model_actuation_tpu.ops import attention as attn
    from llm_d_fast_model_actuation_tpu.ops.pallas.decode import shard_over_tp

    mesh = tp_mesh(topo)
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
    compile_kernel(shard_over_tp(mesh, KERNELS[kind], in_specs, out_spec), *args)


@pytest.mark.parametrize("program,bucket", [("chunk", 4), ("prefill", 16)])
def test_sharded_engine_program_compiles_for_v5e_2x2(topo, program, bucket):
    """A whole serving program of a tp=4 engine under ``pallas``: GSPMD
    cannot partition a Mosaic kernel, so any kernel the program reaches
    outside a shard_map fails here (and only here: interpret mode lowers
    to plain XLA ops, which partition fine)."""
    compiled, _ = compile_engine_program(
        topo, program, bucket, tp=4, num_pages=64
    )
    assert "tpu_custom_call" in compiled.as_text()


def test_lane_constraint_is_named_not_a_mosaic_crash(topo):
    """A per-device KV row narrower than the 128-lane tile is refused with
    the constraint spelled out (TinyLlama at tp=4: one 64-wide KV head)."""
    one = SingleDeviceSharding(topo.devices[0])
    with pytest.raises(ValueError, match="multiple of 128"):
        compile_kernel(KERNELS["decode"], *_kernel_args("decode", 8, 1, 64, one))


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
