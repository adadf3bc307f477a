"""The suffix attention's walk over column blocks
(ops/attention.py:paged_suffix_attention): equal to the whole-row form it
replaced, kept here as the plain reference; the pages past its bound are
never read; a row of one block compiles what it always compiled."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from llm_d_fast_model_actuation_tpu.engine.engine import (
    EngineConfig, InferenceEngine,
)
from llm_d_fast_model_actuation_tpu.engine.kv_cache import KVLayout, PagePool
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.models import smallthinker as st
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for
from llm_d_fast_model_actuation_tpu.ops import attention

PAGE, KVH, G, HD = 4, 2, 3, 8
#: columns a step of the walk in these tests: two pages
BLOCK = 8
LAYER = jnp.int32(1)


def whole_row(q, k_pages, v_pages, page_table, start, layer, window=0):
    """The form before the walk: every page of the row gathered, every
    column scored, one softmax."""
    b, s, h, d = q.shape
    k = attention._gather_context(k_pages, layer, page_table, d)
    v = attention._gather_context(v_pages, layer, page_table, d)
    ctx, kvh = k.shape[1:3]
    g = h // kvh
    qg = (q.astype(jnp.float32) * (d**-0.5)).astype(q.dtype).reshape(
        b, s, kvh, g, d
    )
    qpos = start[:, None] + jnp.arange(s)[None, :]
    if window:
        kpos = attention._ring_positions(ctx, start + s - 1)[:, None, :]
    else:
        kpos = jnp.arange(ctx)[None, None, :]
    logits = jnp.einsum(
        "bsngd,bknd->bsngk", qg, k, preferred_element_type=jnp.float32
    )
    mask = kpos <= qpos[:, :, None]
    if window:
        mask = mask & (kpos >= 0) & (kpos > qpos[:, :, None] - window)
    logits = jnp.where(mask[:, :, None, None, :], logits, attention.NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "bsngk,bknd->bsngd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(b, s, h, d).astype(q.dtype)


def _rows(seed, starts, s, pages_per_seq, ring=False):
    """Pools of two layers whose layer 1 holds, for each row of the batch,
    positions 0 .. start + s - 1 of a random sequence (in a ring: at
    ``p % ring length``, later positions over earlier), the rest of the
    pool random too; the queries of each row's segment; the table."""
    b = len(starts)
    ks = jax.random.split(jax.random.key(seed), 3)
    num_pages = b * pages_per_seq + 1
    shape = (2, num_pages, PAGE, KVH * HD)
    kp, vp = jax.random.normal(ks[0], shape), jax.random.normal(ks[1], shape)
    table = jnp.arange(1, num_pages, dtype=jnp.int32).reshape(b, pages_per_seq)
    q = jax.random.normal(ks[2], (b, s, KVH * G, HD))
    if ring:
        # a ring is read by position: lay the sequence's positions in order
        width = pages_per_seq * PAGE
        flat = lambda x: x.reshape(2, num_pages * PAGE, KVH * HD)  # noqa: E731
        seq = jax.random.normal(jax.random.key(seed + 1), (2, b, 256, KVH * HD))
        kf, vf = flat(kp), flat(vp)
        for r, st in enumerate(starts):
            for p in range(st + s):
                slot = (1 + r * pages_per_seq) * PAGE + p % width
                kf = kf.at[1, slot].set(seq[0, r, p])
                vf = vf.at[1, slot].set(seq[1, r, p])
        kp, vp = kf.reshape(shape), vf.reshape(shape)
    return q, kp, vp, table, jnp.asarray(starts, jnp.int32)


def _close(got, want):
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=2e-5, rtol=2e-5
    )


#: (pages of the row, ``start`` of each row of the batch), segments of 4
PLAIN = [
    # one block: the single shot
    (2, (0,)), (2, (4,)),
    # two blocks
    (4, (0,)), (4, (8,)), (4, (3,)), (4, (11,)), (4, (0, 9)),
    # many
    (12, (0,)), (12, (8,)), (12, (16,)), (12, (3,)), (12, (13,)),
    (12, (44,)), (12, (0, 21)), (12, (17, 2)),
    # 52 columns: half of the seventh block is spare
    (13, (0,)), (13, (45,)), (13, (48,)), (13, (17, 2)),
]


@pytest.mark.parametrize("q_block", [0, 2], ids=["whole", "q_block2"])
@pytest.mark.parametrize(
    "pages,starts", PLAIN,
    ids=[f"ctx{p * PAGE}-start" + "_".join(map(str, s)) for p, s in PLAIN],
)
def test_walk_equals_whole_row_over_plain_rows(pages, starts, q_block):
    """Contexts of one, two and many blocks, and one whose last block is
    part spare; ``start`` at 0, at a block's edge, inside a block and at
    the row's end; a batch of two rows at different ``start``; query rows
    whole and in blocks."""
    q, kp, vp, table, start = _rows(1, starts, 4, pages)
    got = attention.paged_suffix_attention(
        q, kp, vp, table, start, LAYER, q_block=q_block, col_block=BLOCK
    )
    _close(got, whole_row(q, kp, vp, table, start, LAYER))


@pytest.mark.parametrize("q_block", [0, 4], ids=["whole", "q_block4"])
@pytest.mark.parametrize(
    "starts", [(0,), (5,), (16,), (24,), (25,), (60,), (3, 40), (24, 16)],
    ids=lambda s: "start" + "_".join(map(str, s)),
)
def test_walk_equals_whole_row_over_rings(starts, q_block):
    """A ring of 32 slots (four blocks) under a window of 24 and a segment
    of 8: not yet wrapped, exactly full (start 24), wrapped by one position
    and many times over, and a batch one row of which has wrapped."""
    s, pages, window = 8, 8, 24
    q, kp, vp, table, start = _rows(2, starts, s, pages, ring=True)
    got = attention.paged_suffix_attention(
        q, kp, vp, table, start, LAYER, window=window, q_block=q_block,
        col_block=BLOCK,
    )
    _close(got, whole_row(q, kp, vp, table, start, LAYER, window=window))


@pytest.mark.parametrize("col_block", [3, 4, 6, 64], ids=lambda c: f"block{c}")
def test_a_step_is_whole_pages(col_block):
    """A block that is no multiple of the page walks whole pages (one at
    least), and one longer than the row is the single shot."""
    q, kp, vp, table, start = _rows(3, (6, 11), 8, 5)
    got = attention.paged_suffix_attention(
        q, kp, vp, table, start, LAYER, col_block=col_block
    )
    _close(got, whole_row(q, kp, vp, table, start, LAYER))


def test_padded_suffix_rows_leave_the_real_rows_alone():
    """A segment of 5 real rows in a bucket of 8: the padded rows' slots
    were never written (whatever the pages held stays there), and the real
    rows read what a bucket of exactly 5 reads."""
    q, kp, vp, table, start = _rows(4, (BLOCK + 2,), 8, 6)
    got = attention.paged_suffix_attention(
        q, kp, vp, table, start, LAYER, col_block=BLOCK
    )
    want = whole_row(q[:, :5], kp, vp, table, start, LAYER)
    _close(got[:, :5], want)
    assert np.isfinite(np.asarray(got)).all()


def _poison(pools, table, row, first_col, layer=1):
    """NaN in every page of ``table[row]`` from column ``first_col`` on."""
    pages = np.asarray(table)[row, first_col // PAGE:]
    return tuple(p.at[layer, pages].set(jnp.nan) for p in pools)


@pytest.mark.parametrize("q_block", [0, 4], ids=["whole", "q_block4"])
@pytest.mark.parametrize(
    "start,window",
    [(0, 0), (5, 0), (8, 0), (19, 0), (30, 0), (0, 24), (9, 24), (16, 24)],
    ids=lambda v: str(v),
)
def test_columns_past_the_bound_are_not_read(start, window, q_block):
    """The pages of the table past the columns ``suffix_columns`` counts
    (the host's twin of the trip count) hold NaN: a walk that read one
    would give NaN, since a masked score still multiplies its V by 0."""
    s, pages = 8, 8 if window else 12
    q, kp, vp, table, st = _rows(5, (start,), s, pages, ring=bool(window))
    scored = attention.suffix_columns(
        start, s, pages * PAGE, PAGE, ring=bool(window), col_block=BLOCK
    )
    assert scored == -(-(start + s) // BLOCK) * BLOCK < pages * PAGE
    run = lambda kp, vp: attention.paged_suffix_attention(  # noqa: E731
        q, kp, vp, table, st, LAYER, window=window, q_block=q_block,
        col_block=BLOCK,
    )
    got = run(*_poison((kp, vp), table, 0, scored))
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_array_equal(np.asarray(got), np.asarray(run(kp, vp)))
    # and the bound is no larger than it has to be: the block before holds
    # a key the last query sees
    assert not np.isfinite(np.asarray(
        run(*_poison((kp, vp), table, 0, scored - BLOCK))
    )).all()


@pytest.mark.parametrize(
    "start,s,width,ring,want",
    [
        (0, 1024, 16384, False, 1024),
        (1024, 1024, 16384, False, 2048),
        (1000, 16, 16384, False, 1024),
        (1020, 16, 16384, False, 2048),
        (15360, 1024, 16384, False, 16384),
        (0, 1024, 4096, False, 1024),
        (0, 512, 1024, False, 1024),  # one block: the single shot
        (0, 64, 640, False, 640),
        (3072, 1024, 5120, True, 4096),
        (4096, 1024, 5120, True, 5120),  # exactly full
        (4097, 1024, 5120, True, 5120),  # wrapped
        (9000, 128, 5120, True, 5120),
        (2048, 1024, 5000, False, 3072),
        (4096, 512, 5008, False, 5008),  # the last block is part spare
    ],
)
def test_suffix_columns_by_hand(start, s, width, ring, want):
    assert attention.suffix_columns(start, s, width, 16, ring=ring) == want


def _hlo(fn, *args):
    text = jax.jit(fn).lower(*args).as_text()
    return text.replace(fn.__name__, "f")


@pytest.mark.parametrize("window", [0, 24], ids=["plain", "ring"])
@pytest.mark.parametrize("q_block", [0, 4], ids=["whole", "q_block4"])
def test_a_row_of_one_block_lowers_as_before(window, q_block):
    """A table row no longer than one block: no loop over columns, the
    program of the whole-row form (with ``q_block`` the one loop is the
    query blocks', as before)."""
    q, kp, vp, table, start = _rows(6, (3,), 8, 4, ring=bool(window))

    def walk(q, kp, vp, table, start):
        return attention.paged_suffix_attention(
            q, kp, vp, table, start, LAYER, window=window, q_block=q_block,
            col_block=4 * PAGE,
        )

    text = _hlo(walk, q, kp, vp, table, start)
    assert text.count("while") == (1 if q_block else 0)
    if not q_block:
        def plain(q, kp, vp, table, start):
            return whole_row(q, kp, vp, table, start, LAYER, window=window)

        assert text == _hlo(plain, q, kp, vp, table, start)
    # one block more of row and the columns are walked
    q, kp, vp, table, start = _rows(6, (3,), 8, 5, ring=bool(window))
    assert "while" in _hlo(walk, q, kp, vp, table, start)


# -- the walk inside a model's programs and the engine's count of it ----------


def _long_tiny(max_seq_len):
    return dataclasses.replace(
        st.SmallThinkerConfig.tiny_smallthinker(), max_seq_len=max_seq_len,
        attention_impl="grouped",
    )


def test_logits_of_walked_segments_match_the_reference():
    """The patterned tiny model over rows longer than one block (pages of
    16: 2,304 columns a plain row, a ring of 1,056 with a window of 24): a
    cold segment of 1,024, a suffix segment of 1,024 that wraps the ring
    and one of 100 in a bucket of 128, every position against the plain
    reference's logits, in float32."""
    cfg = dataclasses.replace(_long_tiny(2304), dtype=jnp.float32)
    page, segment = 16, 1024
    params = init_params_for(jax.random.key(5), cfg)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, size=2148)
    lay = KVLayout.plan(
        cfg.num_layers, *llama.window_layers(cfg), page, cfg.max_seq_len,
        segment,
    )
    assert lay.pages_per_seq * page == 2304 and lay.ring_pages * page == 1056
    pool = PagePool.create(
        lay.global_layers, lay.pages_per_seq + 1, page, cfg.num_kv_heads,
        cfg.head_dim, dtype=jnp.float32,
        ring_shape=lay.ring_shape(1, page, cfg.num_kv_heads, cfg.head_dim),
    )
    table = np.zeros((1, lay.table_width), np.int32)
    table[:, lay.pages_per_seq:] = lay.ring_columns(1)
    table[0, : lay.pages_per_seq] = 1 + np.arange(lay.pages_per_seq)
    row, toks = jnp.asarray(table), jnp.asarray(tokens, jnp.int32)
    logits, cache = llama.prefill(
        params, cfg, toks[None, :segment], jnp.asarray([segment]),
        pool.as_tuple(), row,
    )
    out = [logits[0]]
    for pos, n, bucket in ((1024, 1024, 1024), (2048, 100, 128)):
        seg = jnp.zeros((1, bucket), jnp.int32).at[0, :n].set(toks[pos : pos + n])
        logits, cache = llama.prefill_continue(
            params, cfg, seg, jnp.asarray([pos]), jnp.asarray([n]), cache, row
        )
        out.append(logits[0, :n])
    got = np.asarray(jnp.concatenate(out, axis=0), np.float64)
    want = np.asarray(st.reference_logits(params, cfg, toks), np.float64)
    err = np.sqrt(((got - want) ** 2).mean(-1) / (want**2).mean(-1))
    # float32 throughout (in bfloat16 the tiny model's routing turns a
    # rounding into another expert): the whole-row form reads 3.0e-6
    assert err.max() < 2e-5


def test_engine_counts_the_columns_of_a_prompt_of_three_segments():
    """``/v1/stats`` ``kv.suffix_cols_*``: a prompt of 2,500 tokens in
    segments of 1,024 (buckets 1,024, 1,024 and 512) over two
    full-attention layers with plain rows of 4,096 columns and six window
    layers with rings of 1,056 (window 24 + a segment, in pages of 16)."""
    eng = InferenceEngine(EngineConfig(
        model=_long_tiny(4096), max_batch=1, page_size=16, num_pages=200,
        max_prefill_tokens=1024, decode_chunk=4, prefix_caching=False,
    ), seed=3)
    lay = eng.kv_layout
    assert (lay.global_layers, lay.window_layers) == (2, 6)
    assert lay.pages_per_seq * 16 == 4096 and lay.ring_pages * 16 == 1056
    kv = eng.cache_stats()["kv"]
    assert (kv["suffix_cols_row"], kv["suffix_cols_scored"],
            kv["suffix_cols_skipped"]) == (0, 0, 0)
    prompt = list(map(int, np.random.default_rng(0).integers(0, 256, size=2500)))
    eng.generate([prompt], max_new_tokens=2)
    kv = eng.cache_stats()["kv"]
    # plain rows: blocks up to start + bucket = 1,024, 2,048 and 2,560 ->
    # 3,072; rings: not yet wrapped at 1,024, then the whole ring twice
    row = 3 * (2 * 4096 + 6 * 1056)
    scored = 2 * (1024 + 2048 + 3072) + 6 * (1024 + 1056 + 1056)
    assert (row, scored) == (43584, 31104)
    assert kv["suffix_cols_row"] == row
    assert kv["suffix_cols_scored"] == scored
    assert kv["suffix_cols_skipped"] == row - scored == 12480
    # a prompt of one cold segment goes through no suffix program
    eng.generate([prompt[:700]], max_new_tokens=2)
    assert eng.cache_stats()["kv"]["suffix_cols_row"] == row


def test_rows_of_one_block_skip_nothing():
    """The tiny models' rows (128 columns) are scored whole, and counted
    so."""
    eng = InferenceEngine(EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=4, num_pages=64,
        max_prefill_tokens=16, decode_chunk=4, prefix_caching=False,
    ), seed=0)
    eng.generate([list(range(3, 43))], max_new_tokens=2)
    kv = eng.cache_stats()["kv"]
    width = eng.cfg.pages_per_seq * 4
    assert kv["suffix_cols_row"] == 3 * eng.kv_layout.global_layers * width > 0
    assert kv["suffix_cols_scored"] == kv["suffix_cols_row"]
    assert kv["suffix_cols_skipped"] == 0


def test_walk_over_a_pool_sharded_by_kv_head_equals_one_device():
    """On a tp mesh the pool's lane-fused KV-head axis is sharded
    (``attention.POOL_SPEC``) and GSPMD partitions the XLA form in place:
    the walk's loop over column blocks gives what one device gives."""
    q, kp, vp, table, start = _rows(7, (13, 30), 8, 12)
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("tp",))
    pool = NamedSharding(mesh, attention.POOL_SPEC)
    rep = NamedSharding(mesh, P())
    walk = jax.jit(
        lambda q, kp, vp, table, start: attention.paged_suffix_attention(
            q, kp, vp, table, start, LAYER, q_block=4, col_block=BLOCK
        ),
        in_shardings=(NamedSharding(mesh, P(None, None, "tp", None)), pool,
                      pool, rep, rep),
    )
    _close(walk(q, kp, vp, table, start),
           whole_row(q, kp, vp, table, start, LAYER))
