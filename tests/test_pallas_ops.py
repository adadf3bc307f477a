"""Pallas kernels vs the pure-XLA reference implementations.

Runs the TPU kernels in interpreter mode on CPU (tests/conftest.py forces
the cpu platform) and checks numerical agreement with `ops/attention.py`
across GQA ratios, ragged sequence lengths, and partial last pages.
"""

import functools

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
    (2, 32, 2, 128, 16, 3, 1, 0),  # Nemotron-H heads: 16 query rows a KV head
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
        (1, 64, 32, 2, 128, 32),  # Nemotron-H heads: 16 query rows a KV head
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


# -- the serving tile of the inline decode kernel -------------------------------
#
# Every caller of the inline kernel gets a 128-token tile a step of the walk
# (ops/pallas/decode.py:decode_block_pages); nobody chooses it.


def _inline_scratch(fn, *args):
    """Scratch shapes of every ``paged_decode_inline`` kernel that
    ``fn(*args)`` traces to, loops and nested calls included."""
    from jax.extend import core as jex_core

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if (eqn.primitive.name == "pallas_call"
                    and eqn.params["name"] == "paged_decode_inline"):
                n = eqn.params["grid_mapping"].num_scratch_operands
                yield [tuple(v.aval.shape) for v in eqn.params["jaxpr"].invars[-n:]]
            for sub in jax.tree.leaves(
                list(eqn.params.values()),
                is_leaf=lambda x: isinstance(
                    x, (jex_core.Jaxpr, jex_core.ClosedJaxpr)
                ),
            ):
                sub = getattr(sub, "jaxpr", sub)  # a ClosedJaxpr's own
                if isinstance(sub, jex_core.Jaxpr):
                    yield from walk(sub)

    return list(walk(jax.make_jaxpr(fn)(*args).jaxpr))


@pytest.mark.parametrize(
    "page_size,block_pages", [(16, 8), (32, 4), (128, 1), (256, 1)]
)
def test_serving_path_walks_a_128_token_tile(page_size, block_pages):
    """What the dispatcher hands the kernel follows from the pool's page
    size alone: as many pages a step as fill 128 tokens, a page a step
    where a page is that long or longer."""
    from llm_d_fast_model_actuation_tpu.ops.pallas.decode import (
        decode_block_pages,
    )

    assert decode_block_pages(page_size) == block_pages
    heads, kvh, d, batch = 8, 2, 64, 2
    s = jax.ShapeDtypeStruct
    pool = s((2, 5, page_size, kvh * d), jnp.float32)
    new = s((batch, kvh, d), jnp.float32)
    tile = block_pages * page_size
    sems = (2, 2, block_pages) if block_pages > 1 else (2, 2)
    assert _inline_scratch(
        lambda *a: attn.paged_decode_attention_inline(*a, impl="pallas"),
        s((batch, heads, d), jnp.float32), pool, pool, new, new,
        s((batch, 4), jnp.int32), s((batch,), jnp.int32), s((), jnp.int32),
    ) == [[(2, tile, kvh * d), (2, tile, kvh * d), sems, (1,)]]


def _masked_decode(q, k, v, positions):
    """Float32 softmax of q [b, heads, d] over keys 0..positions[b] of
    k, v [b, ctx, kvh, d], written without pages."""
    b, heads, d = q.shape
    kk = jnp.repeat(k, heads // k.shape[2], axis=2)
    vv = jnp.repeat(v, heads // v.shape[2], axis=2)
    scores = jnp.einsum("bhd,bthd->bht", q * d**-0.5, kk)
    mask = jnp.arange(k.shape[1])[None, :] <= positions[:, None]
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    return jnp.einsum("bht,bthd->bhd", jax.nn.softmax(scores, axis=-1), vv)


@pytest.mark.parametrize(
    "contexts",
    [(0,), (1,), (15,), (16,), (127,), (128,), (129,), (1000,), (0, 1000, 130)],
    ids=lambda c: "ctx" + "_".join(map(str, c)),
)
def test_inline_decode_at_the_serving_tile_mistral_heads(contexts):
    """The inline kernel as the serving path calls it, at Mistral's and
    Mixtral's head layout (32 heads over 8 KV heads of 128, 16-token pages:
    eight pages a step), against the masked form: no cached position at
    all, contexts shorter than a tile, at its edges, of several tiles with
    spare pages in the last, and an empty slot beside a long sequence."""
    heads, kvh, d, page = 32, 8, 128, 16
    batch, longest = len(contexts), max(contexts)
    pages_per_seq = longest // page + 1
    ks = jax.random.split(jax.random.key(31), 3)
    q = _rand(ks[0], (batch, heads, d))
    k = _rand(ks[1], (batch, longest + 1, kvh, d))
    v = _rand(ks[2], (batch, longest + 1, kvh, d))
    positions = jnp.asarray(contexts, jnp.int32)
    # pages in a scattered order, page 0 left unused; a row holds garbage
    # past its sequence's context, which the kernel must never weigh in
    order = np.random.default_rng(7).permutation(batch * pages_per_seq) + 1
    table = jnp.asarray(order.reshape(batch, pages_per_seq), jnp.int32)

    def pool(x):
        rows = jnp.pad(
            x.reshape(batch, longest + 1, kvh * d),
            ((0, 0), (0, pages_per_seq * page - longest - 1), (0, 0)),
        ).reshape(batch * pages_per_seq, page, kvh * d)
        stored = jnp.zeros((2, batch * pages_per_seq + 1, page, kvh * d))
        return stored.at[1, table.reshape(-1)].set(rows)

    at = jnp.arange(batch)
    got = attn.paged_decode_attention_inline(
        q, pool(k), pool(v), k[at, positions], v[at, positions], table,
        positions, jnp.int32(1), impl="pallas",
    )
    np.testing.assert_allclose(
        got, _masked_decode(q, k, v, positions), atol=2e-5, rtol=2e-5
    )


@pytest.mark.parametrize("preset", ["tiny", "tiny_moe"])
def test_decode_step_logits_pallas_matches_grouped(preset):
    """``llama.decode_step`` of both accepted families under ``pallas``
    (the kernel at the serving tile, which nobody passes) against the XLA
    form by logits: contexts of none, a few, one tile and several tiles of
    cached positions in one batch."""
    import dataclasses

    from llm_d_fast_model_actuation_tpu.models import llama, moe
    from llm_d_fast_model_actuation_tpu.models.registry import init_params_for

    base = (
        llama.LlamaConfig.tiny() if preset == "tiny"
        else moe.MoeConfig.tiny_moe()
    )
    # float32 weights and cache: what is left between the two forms is the
    # order of summation, not bfloat16's rounding of the XLA form's operands
    base = dataclasses.replace(base, max_seq_len=512, dtype=jnp.float32)
    params = init_params_for(jax.random.key(2), base)
    page, pages_per_seq = 16, 20
    positions = jnp.asarray([0, 5, 128, 300], jnp.int32)
    batch = positions.shape[0]
    shape = (base.num_layers, batch * pages_per_seq + 1, page, base.kv_dim)
    ks = jax.random.split(jax.random.key(3), 2)
    cache = (_rand(ks[0], shape), _rand(ks[1], shape))
    table = jnp.arange(1, 1 + batch * pages_per_seq, dtype=jnp.int32).reshape(
        batch, pages_per_seq
    )
    args = (jnp.asarray([7, 11, 13, 17], jnp.int32), positions, cache, table)

    def step(impl):
        cfg = dataclasses.replace(base, attention_impl=impl)
        return lambda *a: llama.decode_step(params, cfg, *a)

    tile = (2, 128, base.kv_dim)
    assert _inline_scratch(step("pallas"), *args) == [[tile, tile, (2, 2, 8), (1,)]]
    np.testing.assert_allclose(
        step("pallas")(*args)[0], step("grouped")(*args)[0],
        atol=1e-4, rtol=1e-4,
    )


# -- the walk across sequences -----------------------------------------------------
#
# A live sequence's last tile step starts the first tile of the NEXT live
# sequence into the other buffer half (ops/pallas/decode.py:_decode_kernel):
# the batches below put every kind of boundary between two walks.

#: tiles a slot's context fills, slot by slot (0: an empty slot, or one at
#: position 0: nothing cached)
WALK_ORDERS = {
    "empty_first": (0, 0, 2, 1),
    "empty_last": (2, 1, 0, 0),
    "empty_between_two_live": (1, 0, 0, 2),
    "all_empty": (0, 0, 0, 0),
    "one_live_alone": (0, 3, 0, 0),
    "one_tile_after_nine": (9, 1, 0, 2),
    "nine_after_one_tile": (1, 9, 1, 0),
    "odd_then_even": (3, 2, 1, 4),
    "even_then_odd": (2, 3, 4, 1),
    "one_tile_each": (1, 1, 1, 1),
}
#: mode -> (window, queries, latent, block_pages, inline)
WALK_MODES = {
    "plain": (0, 1, 0, 8, True),  # the serving tile: 8 pages of 16
    "window": (75, 1, 0, 2, True),
    "queries2": (0, 2, 0, 2, True),
    "window_queries2": (75, 2, 0, 2, True),
    "latent": (0, 1, 32, 2, True),
    "block_pages1": (0, 1, 0, 1, True),
    "not_inline": (0, 1, 0, 1, False),
}
_WALK_PAGE = 16


@functools.partial(jax.jit, static_argnames=("window", "scale"))
def _masked_walk(q, k, v, positions, window, scale):
    """Float32 softmax, written without pages: query i of a slot, q [b, n,
    heads, d], sits at ``positions + i`` and sees the keys at and before it
    (the last ``window`` of them on a window layer) of k [b, ctx, kvh, d],
    v [b, ctx, kvh, dv]. A query that sees nothing gives zeros."""
    n, heads = q.shape[1:3]
    kk = jnp.repeat(k, heads // k.shape[2], axis=2)
    vv = jnp.repeat(v, heads // v.shape[2], axis=2)
    scores = jnp.einsum("bqhd,bthd->bqht", q * scale, kk)
    t = jnp.arange(k.shape[1])[None, None, :]
    qpos = (positions[:, None] + jnp.arange(n)[None, :])[:, :, None]
    mask = (t <= qpos) & ((t > qpos - window) if window else True)
    scores = jnp.where(mask[:, :, None, :], scores, -jnp.inf)
    out = jnp.einsum("bqht,bthd->bqhd", jax.nn.softmax(scores, axis=-1), vv)
    return jnp.where(mask.any(axis=-1)[..., None, None], out, 0.0)


def _walk_case(mode, tiles, dtype):
    """(kernel's output, masked float32 form, the XLA twin to call) for a
    batch whose slot i holds a context of ``tiles[i]`` tiles, the last one
    partly filled; every order of a mode has the same shapes, so one
    compile."""
    window, n, latent, block_pages, inline = WALK_MODES[mode]
    page, batch = _WALK_PAGE, len(tiles)
    tile = block_pages * page
    heads, kvh, d = (4, 1, 48) if latent else (4, 2, 32)
    # cached positions: whole tiles, a few short of them, one into the last
    short = (0, 5, tile - 1, 37 % tile)
    cached = np.array(
        [t * tile - short[i] if t else 0 for i, t in enumerate(tiles)], np.int32
    )
    # the same arrays under every mode: the longest walk at the serving tile
    longest = max(map(max, WALK_ORDERS.values())) * 128 + 2
    # a ring that wraps under the longer contexts; else the whole row
    width = (window + n) // page + 5 if window else longest // page + 1
    ks = jax.random.split(jax.random.key(41), 3)
    q = _rand(ks[0], (batch, n, heads, d))
    k = _rand(ks[1], (batch, longest, kvh, d))
    v = k[..., :latent] if latent else _rand(ks[2], (batch, longest, kvh, d))
    order = np.random.default_rng(9).permutation(batch * width) + 1
    table = order.reshape(batch, width).astype(np.int32)

    def pool(x):
        # position t of a slot at ring column (t // page) % width; what was
        # never written is large and finite: weighed in, it would show
        stored = np.full((2, batch * width + 1, page, kvh * d), 1e3, np.float32)
        x = np.asarray(x).reshape(batch, longest, kvh * d)
        for s in range(batch):
            t = np.arange(max(0, cached[s] - width * page), cached[s])
            stored[1, table[s, (t // page) % width], t % page] = x[s, t]
        return jnp.asarray(stored, dtype)

    positions = jnp.asarray(cached)
    at = (jnp.arange(batch)[:, None], positions[:, None] + jnp.arange(n)[None, :])
    q_in = (q if n > 1 else q[:, 0]).astype(dtype)
    new = lambda x: (x[at] if n > 1 else x[at][:, 0]).astype(dtype)  # noqa: E731
    table, layer = jnp.asarray(table), jnp.int32(1)
    scale = 0.11 if latent else d**-0.5
    if latent:
        args = (q_in, pool(k), new(k)[:, 0], table, positions, layer)
        kw = dict(latent=latent, scale=scale)
        got = attn.latent_decode_attention_inline(*args, **kw, impl="pallas")
        twin = functools.partial(
            attn.latent_decode_attention_inline, **kw, impl="grouped")
    elif inline:
        from llm_d_fast_model_actuation_tpu.ops.pallas import (
            paged_decode_attention_inline_pallas,
        )

        args = (q_in, pool(k), pool(v), new(k), new(v), table, positions, layer)
        got = paged_decode_attention_inline_pallas(
            *args, interpret=True, window=window, block_pages=block_pages
        )
        twin = functools.partial(
            attn.paged_decode_attention_inline, impl="grouped", window=window)
    else:
        # the cache holds the query's own position: lengths, not positions
        args = (q_in, pool(k), pool(v), table, positions, layer)
        got = paged_decode_attention_pallas(*args, interpret=True)
        twin = functools.partial(attn.paged_decode_attention, impl="reference")
    want = _masked_walk(
        q, k, v, positions - (0 if inline else 1), window=window, scale=scale
    )
    return got, (want if n > 1 else want[:, 0]), lambda: jax.jit(twin)(*args)


@pytest.mark.parametrize("order", list(WALK_ORDERS))
@pytest.mark.parametrize("mode", list(WALK_MODES))
def test_walk_runs_across_sequences(mode, order):
    """The first tile of the next live sequence is in flight under the last
    tile of the one before: empty slots first, last and between two live
    ones, none live, one alone, a short walk after a long one and the
    reverse, an odd and an even number of tiles before a boundary (both
    buffer halves are entered) — on plain rows, on rings that wrap, with two
    query positions a slot, on latent pages, a page a step, and in the kernel
    that reads the query's own position from the cache."""
    got, want, _ = _walk_case(mode, WALK_ORDERS[order], jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("mode", list(WALK_MODES))
def test_walk_over_a_bf16_pool_matches_the_xla_form(mode):
    """A bfloat16 pool under every mode: the kernel's float32 softmax state
    over tiles cast from the pool against the XLA form of the same attention
    (bfloat16 operands, float32 accumulation) and against the masked float32
    form, within bfloat16's rounding of the output
    (``test_bf16_io_fp32_math``'s tolerance)."""
    got, want, twin = _walk_case(mode, WALK_ORDERS["odd_then_even"], jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    for other in (twin(), want):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(other, np.float32),
            atol=3e-2, rtol=3e-2,
        )
