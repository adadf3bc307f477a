"""Pallas kernels vs the pure-XLA reference implementations.

Runs the TPU kernels in interpreter mode on CPU (tests/conftest.py forces
the cpu platform) and checks numerical agreement with `ops/attention.py`
across GQA ratios, ragged sequence lengths, and partial last pages.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.ops import attention as attn
from llm_d_fast_model_actuation_tpu.ops.pallas import (
    causal_prefill_attention_pallas,
    paged_decode_attention_pallas,
)

def _rand(key, shape, dtype=jnp.float32):
    return jax.random.normal(key, shape, dtype=dtype)


def _pool(key, layers, num_pages, page_size, kv_heads, head_dim,
          dtype=jnp.float32):
    """A random K or V pool in the stored layout (PagePool.pool_shape)."""
    from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool

    return _rand(
        key,
        PagePool.pool_shape(layers, num_pages, page_size, kv_heads, head_dim),
        dtype,
    )


#: (batch, heads, kv_heads, head_dim, page_size, pages_per_seq, layers, layer):
#: the kernels take the whole pool and read ``layer`` of it
DECODE_CASES = [
    (2, 4, 2, 16, 8, 4, 1, 0),
    (3, 8, 8, 32, 16, 2, 3, 2),  # MHA (group=1)
    (1, 8, 2, 64, 8, 3, 2, 1),  # GQA 4x
    (2, 32, 8, 128, 16, 3, 2, 1),  # Llama-3-8B / Mistral-7B heads
    (2, 32, 4, 64, 16, 3, 3, 1),  # TinyLlama heads
]


@pytest.mark.parametrize(
    "batch,heads,kv_heads,head_dim,page_size,pages_per_seq,layers,layer",
    DECODE_CASES,
)
def test_paged_decode_matches_reference(
    batch, heads, kv_heads, head_dim, page_size, pages_per_seq, layers, layer
):
    key = jax.random.key(0)
    ks = jax.random.split(key, 4)
    num_pages = batch * pages_per_seq + 1  # page 0 unused by convention
    q = _rand(ks[0], (batch, heads, head_dim))
    k_pages = _pool(ks[1], layers, num_pages, page_size, kv_heads, head_dim)
    v_pages = _pool(ks[2], layers, num_pages, page_size, kv_heads, head_dim)
    page_table = jnp.asarray(
        np.arange(1, 1 + batch * pages_per_seq, dtype=np.int32).reshape(
            batch, pages_per_seq
        )
    )
    # ragged lengths incl. a partial last page and a single-token sequence
    max_len = pages_per_seq * page_size
    lens = [max_len, max_len - page_size // 2, 1][:batch]
    lens += [max_len // 2] * (batch - len(lens))
    seq_lens = jnp.asarray(lens, dtype=jnp.int32)

    want = attn.paged_decode_attention(
        q, k_pages, v_pages, page_table, seq_lens, layer
    )
    got = paged_decode_attention_pallas(
        q, k_pages, v_pages, page_table, seq_lens, layer, interpret=True
    )
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5)
    if layers > 1:  # another layer holds other pages: the index is read
        other = attn.paged_decode_attention(
            q, k_pages, v_pages, page_table, seq_lens, (layer + 1) % layers
        )
        assert not np.allclose(np.asarray(other), np.asarray(want), atol=1e-3)


@pytest.mark.parametrize(
    "batch,seq,heads,kv_heads,head_dim,block_q",
    [
        (2, 32, 4, 2, 16, 8),
        (1, 64, 8, 8, 32, 16),  # MHA
        (2, 64, 8, 2, 16, 64),  # single q block
    ],
)
def test_flash_prefill_matches_reference(batch, seq, heads, kv_heads, head_dim, block_q):
    key = jax.random.key(1)
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (batch, seq, heads, head_dim))
    k = _rand(ks[1], (batch, seq, kv_heads, head_dim))
    v = _rand(ks[2], (batch, seq, kv_heads, head_dim))
    seq_lens = jnp.asarray([seq, seq // 2][:batch], dtype=jnp.int32)

    want = attn.causal_prefill_attention(q, k, v, seq_lens)
    got = causal_prefill_attention_pallas(
        q, k, v, seq_lens, block_q=block_q, interpret=True
    )
    # rows past seq_len differ (reference normalizes garbage, kernel zeros);
    # only compare the valid prefix of each row
    for b in range(batch):
        n = int(seq_lens[b])
        np.testing.assert_allclose(
            np.asarray(got)[b, :n], np.asarray(want)[b, :n], atol=2e-5, rtol=2e-5
        )


def test_dispatcher_switches_impl():
    key = jax.random.key(2)
    ks = jax.random.split(key, 3)
    q = _rand(ks[0], (1, 32, 4, 2, 16)[:1] + (32, 4, 16))  # [1, 32, 4, 16]
    k = _rand(ks[1], (1, 32, 2, 16))
    v = _rand(ks[2], (1, 32, 2, 16))
    seq_lens = jnp.asarray([32], dtype=jnp.int32)

    ref = attn.causal_prefill_attention(q, k, v, seq_lens)
    attn.set_attention_impl("pallas")
    try:
        pal = attn.causal_prefill_attention(q, k, v, seq_lens)
    finally:
        attn.set_attention_impl("reference")
    np.testing.assert_allclose(np.asarray(pal), np.asarray(ref), atol=2e-5, rtol=2e-5)

    with pytest.raises(ValueError):
        attn.set_attention_impl("cuda")


def test_bf16_io_fp32_math():
    """Kernels keep softmax math in fp32 regardless of bf16 io."""
    key = jax.random.key(3)
    ks = jax.random.split(key, 4)
    batch, heads, kvh, d, ps, pps = 2, 4, 2, 32, 8, 2
    q = _rand(ks[0], (batch, heads, d), jnp.bfloat16)
    kp = _pool(ks[1], 2, batch * pps + 1, ps, kvh, d, jnp.bfloat16)
    vp = _pool(ks[2], 2, batch * pps + 1, ps, kvh, d, jnp.bfloat16)
    pt = jnp.asarray(
        np.arange(1, 1 + batch * pps, dtype=np.int32).reshape(batch, pps)
    )
    seq_lens = jnp.asarray([ps * pps, ps + 3], dtype=jnp.int32)
    want = attn.paged_decode_attention(q, kp, vp, pt, seq_lens, 1)
    got = paged_decode_attention_pallas(q, kp, vp, pt, seq_lens, 1, interpret=True)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(want, np.float32), atol=3e-2, rtol=3e-2
    )


@pytest.mark.parametrize(
    "layers,decode_chunk,new_tokens,second_prompt",
    # 9 = one token from prefill + 2 chunks of 4. With three layers the
    # random-weight model puts [9, 8, 7] on a near-tie that grouped's bf16
    # matmuls take the other way (before this layout too), so another prompt
    [(2, 8, 6, [9, 8, 7]), (3, 4, 9, [2, 7, 1, 8])],
    ids=["one_chunk", "three_layers_two_chunks"],
)
def test_engine_generates_identically_with_pallas_attention(
    layers, decode_chunk, new_tokens, second_prompt
):
    """Full engine generation with the Pallas kernels (interpret mode on CPU)
    must produce the same greedy tokens as the XLA reference path — every
    impl reads and writes the one stored pool layout, each layer its own."""
    import dataclasses

    from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
    from llm_d_fast_model_actuation_tpu.models import llama

    model = dataclasses.replace(llama.LlamaConfig.tiny(), num_layers=layers)
    prompts = [[1, 2, 3, 4, 5], second_prompt]
    outs = {}
    for impl in ("reference", "grouped", "pallas"):
        cfg = EngineConfig(
            model=model,
            max_batch=2,
            page_size=8,
            num_pages=32,
            max_seq_len=64,
            attention_impl=impl,
            decode_chunk=decode_chunk,
        )
        eng = InferenceEngine(cfg, seed=0)
        outs[impl] = eng.generate(prompts, max_new_tokens=new_tokens)
    attn.set_attention_impl("reference")
    assert outs["pallas"] == outs["reference"]
    assert outs["grouped"] == outs["reference"]


@pytest.mark.parametrize(
    "batch,heads,kv_heads,head_dim,page_size,pages_per_seq,layers,layer",
    DECODE_CASES,
)
def test_inline_decode_matches_scatter_then_attend(
    batch, heads, kv_heads, head_dim, page_size, pages_per_seq, layers, layer
):
    """The deferred-scatter serving path: attend(cache[<pos], inline new K/V)
    must equal scatter-into-cache-then-attend — for both the grouped-XLA
    math and the inline Pallas kernel (interpret mode on CPU)."""
    from llm_d_fast_model_actuation_tpu.ops.pallas import (
        paged_decode_attention_inline_pallas,
    )

    key = jax.random.key(11)
    ks = jax.random.split(key, 6)
    num_pages = batch * pages_per_seq + 1
    q = _rand(ks[0], (batch, heads, head_dim))
    k_pages = _pool(ks[1], layers, num_pages, page_size, kv_heads, head_dim)
    v_pages = _pool(ks[2], layers, num_pages, page_size, kv_heads, head_dim)
    k_new = _rand(ks[3], (batch, kv_heads, head_dim))
    v_new = _rand(ks[4], (batch, kv_heads, head_dim))
    pt = jnp.asarray(
        np.arange(1, 1 + batch * pages_per_seq, dtype=np.int32).reshape(
            batch, pages_per_seq
        )
    )
    # ragged positions incl. a page boundary and a partial last page
    pos_np = np.minimum(
        np.array([page_size * pages_per_seq - 1, page_size, 3][:batch]),
        page_size * pages_per_seq - 1,
    ).astype(np.int32)
    positions = jnp.asarray(pos_np)

    # golden: scatter k_new/v_new at `positions` first, then plain attention
    page_of = pos_np // page_size
    slot_of = pos_np % page_size
    phys = np.asarray(pt)[np.arange(batch), page_of]
    kp2 = k_pages.at[layer, phys, slot_of].set(k_new.reshape(batch, -1))
    vp2 = v_pages.at[layer, phys, slot_of].set(v_new.reshape(batch, -1))
    want = attn.paged_decode_attention(
        q, kp2, vp2, pt, jnp.asarray(pos_np + 1), layer, impl="reference"
    )

    got_grouped = attn.paged_decode_attention_inline(
        q, k_pages, v_pages, k_new, v_new, pt, positions, layer,
        impl="grouped",
    )
    np.testing.assert_allclose(
        np.asarray(got_grouped), np.asarray(want), atol=2e-5, rtol=2e-5
    )
    got_pallas = paged_decode_attention_inline_pallas(
        q, k_pages, v_pages, k_new, v_new, pt, positions, layer,
        interpret=True,
    )
    np.testing.assert_allclose(
        np.asarray(got_pallas), np.asarray(want), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("kind", ["decode", "decode_inline", "prefill"])
def test_kernels_over_a_tp_mesh_match_reference(devices8, kind):
    """The shard_map ports of the bucketed path's kernels (a tp engine's
    decode chunk and prefill reach them through the dispatcher with a
    mesh): each shard attends its own head slice; results equal the
    unsharded reference, and a non-dividing prefill length is padded."""
    from llm_d_fast_model_actuation_tpu.parallel.mesh import MeshPlan, make_mesh

    mesh = make_mesh(MeshPlan(tp=2), devices8[:2])
    batch, heads, kvh, d, ps, pps = 3, 8, 4, 32, 8, 3
    ks = jax.random.split(jax.random.key(5), 6)
    k_pages = _pool(ks[0], 2, batch * pps + 1, ps, kvh, d)
    v_pages = _pool(ks[1], 2, batch * pps + 1, ps, kvh, d)
    pt = jnp.asarray(
        np.arange(1, 1 + batch * pps, dtype=np.int32).reshape(batch, pps)
    )
    lens = jnp.asarray([ps * pps, ps, 3], jnp.int32)
    if kind == "decode":
        fn = attn.paged_decode_attention
        args = (_rand(ks[2], (batch, heads, d)), k_pages, v_pages, pt, lens, 1)
    elif kind == "decode_inline":
        fn = attn.paged_decode_attention_inline
        args = (
            _rand(ks[2], (batch, heads, d)), k_pages, v_pages,
            _rand(ks[3], (batch, kvh, d)), _rand(ks[4], (batch, kvh, d)),
            pt, lens - 1, 1,
        )
    else:
        fn = attn.causal_prefill_attention
        seq = 20  # not a multiple of the kernel block: padded, then sliced
        args = (
            _rand(ks[2], (2, seq, heads, d)), _rand(ks[3], (2, seq, kvh, d)),
            _rand(ks[4], (2, seq, kvh, d)), jnp.asarray([seq, 7], jnp.int32),
        )
    got = jax.jit(lambda *a: fn(*a, impl="pallas", mesh=mesh))(*args)
    want = fn(*args, impl="reference")
    got, want = np.array(got), np.array(want)
    if kind == "prefill":  # rows past seq_len are garbage by contract
        got[1, 7:] = want[1, 7:] = 0
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)
