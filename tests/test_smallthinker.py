"""The patterned family (models/smallthinker.py) at a tiny preset on the CPU:
two periods of (full + NoPE, 3 x window + RoPE), a window shorter than the
contexts so that rings wrap and a prompt spans several segments, small
pages, Pallas in interpret mode.

Tolerances. The served path computes in bfloat16 with float32 softmax,
norms, router and combine; the plain reference (``reference_logits``) is
float32 throughout. At every position the two logit rows differ by a share
of the reference row's own root-mean-square; ``LOGIT_ERR_TOL`` is the limit
on the MEDIAN of that share over the positions. It lies between two
readings taken here, at this preset, on two seeds of weights: the bfloat16
path reads 0.0115-0.0129 on both attention implementations, and the same
float32 reference with its matmul weights rounded to per-output-channel
int8 (the nearest precision below bfloat16) reads 0.039-0.044;
``test_int8_weights_fail_the_tolerance`` holds the second reading above the
limit with room, so a computation in a precision below the stated one
cannot pass. The median, because the error is not rounding alone: with
near-flat random logits a router near-tie falls the other way at a few
positions (an error of 0.1-0.3 there, and in what attends to them after),
which the mean would take for a fault and which a bound on single positions
(``LOGIT_ERR_WORST``: no position differs by more than the rows' own size)
only keeps from being one.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.engine import (
    SlotStateUnsupported,
)
from llm_d_fast_model_actuation_tpu.engine.kv_cache import KVLayout, PagePool
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama, moe
from llm_d_fast_model_actuation_tpu.models import smallthinker as st
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for
from llm_d_fast_model_actuation_tpu.ops import attention
from llm_d_fast_model_actuation_tpu.utils import tracing

LOGIT_ERR_TOL = 0.022
LOGIT_ERR_WORST = 1.0

PAGE, SEGMENT, SLOTS = 4, 16, 3


def _model(impl="grouped"):
    return dataclasses.replace(
        st.SmallThinkerConfig.tiny_smallthinker(), attention_impl=impl
    )


def _engine_cfg(**kw):
    kw.setdefault("prefix_caching", False)
    return EngineConfig(
        model=st.SmallThinkerConfig.tiny_smallthinker(), max_batch=SLOTS,
        page_size=PAGE, num_pages=128, max_prefill_tokens=SEGMENT,
        decode_chunk=4, **kw,
    )


def _row_errors(got, want):
    """Per position: RMS of the difference over RMS of the reference row."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).mean(-1) / (want**2).mean(-1))


def _served_logits(cfg, params, tokens, prompt_len, slot=1):
    """Logits at every position of ``tokens`` by the serving programs:
    a cold prefill of the first segment, chunked suffix segments for the
    rest of the prompt, then one decode step a token, through both caches."""
    lay = KVLayout.plan(
        cfg.num_layers, *llama.window_layers(cfg), PAGE, cfg.max_seq_len,
        SEGMENT,
    )
    assert lay.ring_pages * PAGE < len(tokens), "the ring has to wrap"
    pool = PagePool.create(
        lay.global_layers, 64, PAGE, cfg.num_kv_heads, cfg.head_dim,
        ring_shape=lay.ring_shape(SLOTS, PAGE, cfg.num_kv_heads, cfg.head_dim),
    )
    cache = pool.as_tuple()
    table = np.zeros((SLOTS, lay.table_width), np.int32)
    table[:, lay.pages_per_seq:] = lay.ring_columns(SLOTS)
    table[slot, : lay.pages_per_seq] = 1 + np.arange(lay.pages_per_seq)
    row = jnp.asarray(table[slot : slot + 1])
    toks = jnp.asarray(tokens, jnp.int32)
    out = []
    logits, cache = llama.prefill(
        params, cfg, toks[None, :SEGMENT], jnp.asarray([SEGMENT]), cache, row
    )
    out.append(logits[0])
    pos = SEGMENT
    # one compile for the prompt's four suffix segments, as the engine has
    suffix = jax.jit(
        lambda seg, pos, n, c: llama.prefill_continue(params, cfg, seg, pos, n, c, row)
    )
    while pos < prompt_len:
        n = min(SEGMENT, prompt_len - pos)
        seg = jnp.zeros((1, SEGMENT), jnp.int32).at[0, :n].set(toks[pos : pos + n])
        logits, cache = suffix(seg, jnp.asarray([pos]), jnp.asarray([n]), cache)
        out.append(logits[0, :n])
        pos += n
    full = jnp.asarray(table)
    active = jnp.arange(SLOTS) == slot
    step = jax.jit(
        lambda t, p, c: llama.decode_step(params, cfg, t, p, c, full, active)
    )
    for pos in range(prompt_len, len(tokens)):
        t = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(toks[pos])
        p = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(pos)
        logits, cache = step(t, p, cache)
        out.append(logits[slot : slot + 1])
    return jnp.concatenate(out, axis=0)


@pytest.fixture(scope="module")
def tiny():
    cfg = _model()
    params = init_params_for(jax.random.key(5), cfg)
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, size=100)
    return params, tokens, st.reference_logits(params, cfg, jnp.asarray(tokens))


@pytest.mark.parametrize("impl", ["grouped", "pallas"])
def test_logits_through_both_caches_match_the_reference(tiny, impl):
    """Prefill -> chunked suffix -> decode, every position: the ring of the
    window layers (40 positions) wraps under a 100-token sequence, the
    prompt (70) spans five segments, and the full-attention layers read
    their pages."""
    params, tokens, want = tiny
    got = _served_logits(_model(impl), params, tokens, prompt_len=70)
    assert got.shape == want.shape
    err = _row_errors(got, want)
    assert np.median(err) < LOGIT_ERR_TOL
    assert err.max() < LOGIT_ERR_WORST


def test_int8_weights_fail_the_tolerance(tiny):
    """The control of LOGIT_ERR_TOL: the float32 reference over weights
    rounded to per-output-channel int8 lies outside it."""
    params, tokens, want = tiny
    cfg = _model()

    def rounded(w):
        wf = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wf), axis=w.ndim - 2, keepdims=True) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    low = dict(params, lm_head=rounded(params["lm_head"]))
    low["layers"] = {
        k: rounded(v) if k in names else v for k, v in params["layers"].items()
    }
    got = st.reference_logits(low, cfg, jnp.asarray(tokens))
    assert np.median(_row_errors(got, want)) > 1.5 * LOGIT_ERR_TOL


# -- the window mask of each attention entry ---------------------------------------


def _masked_attention(q, k, v, q_pos, window):
    """The masked XLA form, float32: q [n, heads, d] at positions q_pos [n]
    over keys k, v [ctx, kvh, d] at positions 0..ctx-1; query i sees keys j
    with i - window < j <= i."""
    n, heads, d = q.shape
    kvh = k.shape[1]
    kk = jnp.repeat(k, heads // kvh, axis=1).astype(jnp.float32)
    vv = jnp.repeat(v, heads // kvh, axis=1).astype(jnp.float32)
    scores = jnp.einsum("nhd,thd->nht", q.astype(jnp.float32) * d**-0.5, kk)
    j = jnp.arange(k.shape[0])[None, :]
    mask = (j <= q_pos[:, None]) & (j > q_pos[:, None] - window)
    scores = jnp.where(mask[:, None, :], scores, -jnp.inf)
    return jnp.einsum("nht,thd->nhd", jax.nn.softmax(scores, axis=-1), vv)


WINDOW, HEADS, KVH, HD = 24, 4, 2, 16
RING_PAGES = 10  # 40 slots: the window plus one 16-token segment


def _ring_of(k_seq, upto, layers=2, layer=1):
    """A ring pool [layers, RING_PAGES, PAGE, fused] into which positions
    0..upto-1 of k_seq [ctx, kvh, d] were written in order."""
    ring = np.zeros((layers, RING_PAGES, PAGE, KVH * HD), np.float32)
    flat = ring.reshape(layers, RING_PAGES * PAGE, KVH * HD)
    for p in range(upto):
        flat[layer, p % (RING_PAGES * PAGE)] = np.asarray(k_seq[p]).reshape(-1)
    return jnp.asarray(ring)


def _qkv(ctx, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(ks[0], (ctx, HEADS, HD), jnp.float32)
    k = jax.random.normal(ks[1], (ctx, KVH, HD), jnp.float32)
    v = jax.random.normal(ks[2], (ctx, KVH, HD), jnp.float32)
    return q, k, v


def _decode_inline(impl, block, *args, **kw):
    """The decode entry as the forward calls it (``block`` None: the kernel
    walks the serving tile, which no caller chooses), or the kernel itself
    at ``block`` pages a step."""
    if block is None:
        return attention.paged_decode_attention_inline(*args, impl=impl, **kw)
    from llm_d_fast_model_actuation_tpu.ops.pallas import (
        paged_decode_attention_inline_pallas,
    )

    return paged_decode_attention_inline_pallas(
        *args, interpret=True, block_pages=block, **kw
    )


@pytest.mark.parametrize(
    "impl,block",
    [("grouped", None), ("pallas", 1), ("pallas", 4), ("pallas", None)],
    ids=["grouped", "pallas", "pallas_4_pages_a_step", "pallas_serving_tile"],
)
@pytest.mark.parametrize(
    "pos", [5, WINDOW - 1, WINDOW, 39, 40, 41, 97],
    ids=lambda p: f"pos{p}",
)
def test_decode_window_mask(impl, block, pos):
    """The decode entry (XLA form and the kernel, which starts its walk at
    the first page with a visible key: a page or four a step, and the
    serving tile of 128 tokens, which is longer than this ring) over a ring,
    at contexts below, at and past the window and past the ring's length."""
    q, k, v = _qkv(100)
    table = jnp.arange(RING_PAGES, dtype=jnp.int32)[None, :]
    got = _decode_inline(
        impl, block,
        q[pos][None], _ring_of(k, pos), _ring_of(v, pos), k[pos][None],
        v[pos][None], table, jnp.asarray([pos]), jnp.int32(1), window=WINDOW,
    )
    want = _masked_attention(
        q[pos][None], k[: pos + 1], v[: pos + 1], jnp.asarray([pos]), WINDOW
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [4, None], ids=["4_pages_a_step", "serving_tile"])
@pytest.mark.parametrize("pos", [0, 1, 15, 16, 17, 63, 64, 99], ids=lambda p: f"pos{p}")
def test_decode_kernel_several_pages_a_step_over_plain_pages(pos, block):
    """The full causal mask over a plain table row, four pages a step and
    the serving tile (32 of these 4-token pages): whole steps, a last step
    with spare pages, a context shorter than one step, and no cached
    position at all."""
    q, k, v = _qkv(100, seed=3)
    pages = 25
    pool = lambda x: jnp.zeros((2, pages * PAGE, KVH * HD)).at[1, :pos].set(  # noqa: E731
        x[:pos].reshape(pos, KVH * HD)).reshape(2, pages, PAGE, KVH * HD)
    table = jnp.arange(pages, dtype=jnp.int32)[None, :]
    got = _decode_inline(
        "pallas", block,
        q[pos][None], pool(k), pool(v), k[pos][None], v[pos][None], table,
        jnp.asarray([pos]), jnp.int32(1),
    )
    want = _masked_attention(
        q[pos][None], k[: pos + 1], v[: pos + 1], jnp.asarray([pos]), 10**6
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("impl", ["reference", "pallas"])
@pytest.mark.parametrize("seq", [16, WINDOW, 64], ids=lambda s: f"seq{s}")
def test_prefill_window_mask(impl, seq):
    """The cold-prefill entry (XLA form and the flash kernel, which skips
    the key blocks that have left the window) at a segment shorter than,
    equal to and longer than the window."""
    q, k, v = _qkv(64, seed=1)
    got = attention.causal_prefill_attention(
        q[None], k[None], v[None], jnp.asarray([seq]), impl=impl, window=WINDOW
    )[0, :seq]
    want = _masked_attention(q[:seq], k[:seq], v[:seq], jnp.arange(seq), WINDOW)
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("q_block", [0, 4])
@pytest.mark.parametrize("start", [0, 8, WINDOW, 50, 84], ids=lambda s: f"start{s}")
def test_suffix_window_mask(start, q_block):
    """The suffix entry over a ring into which the segment was just
    written, the segment starting below, at and past the window and past
    the ring's length; whole and in blocks of query rows."""
    q, k, v = _qkv(100, seed=2)
    end = start + SEGMENT
    table = jnp.arange(RING_PAGES, dtype=jnp.int32)[None, :]
    got = attention.paged_suffix_attention(
        q[start:end][None], _ring_of(k, end), _ring_of(v, end), table,
        jnp.asarray([start]), jnp.int32(1), window=WINDOW, q_block=q_block,
    )[0]
    want = _masked_attention(
        q[start:end], k[:end], v[:end], jnp.arange(start, end), WINDOW
    )
    np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# -- the routed expert layer -----------------------------------------------------------


def _dense_weighted_sum(cfg, lp, x, router_logits):
    """Every expert for every token, weighted by the top-k softmax, zero
    off the top k."""
    probs, idx = moe.route(cfg, router_logits)
    weights = jnp.sum(
        jax.nn.one_hot(idx, cfg.num_experts) * probs[..., None], axis=-2
    )
    g = jnp.einsum("nh,ehf->nef", x, lp["w_gate"])
    u = jnp.einsum("nh,ehf->nef", x, lp["w_up"])
    y = jnp.einsum("nef,efh->neh", jax.nn.relu(g) * u, lp["w_down"])
    return jnp.einsum("neh,ne->nh", y, weights)


def _routing(case, n, experts, k):
    base = jax.random.normal(jax.random.key(3), (n, experts)) * 0.01
    if case == "even":
        # token t prefers experts t, t+1, ..: every expert gets its share
        pref = (jnp.arange(experts)[None, :] - jnp.arange(n)[:, None]) % experts
        return base - pref.astype(jnp.float32)
    if case == "one_expert_takes_all":
        # every token's first choice is expert 2, the rest spread
        return base.at[:, 2].add(10.0)
    if case == "an_expert_with_none":
        return base.at[:, 5].add(-10.0)
    raise AssertionError(case)


@pytest.mark.parametrize(
    "case", ["even", "one_expert_takes_all", "an_expert_with_none"]
)
def test_routed_layer_is_the_dense_weighted_sum(case):
    """Dropless: whatever the routing, each of a token's k assignments is
    computed and nothing else is. float32, so the two sums differ by the
    order of additions alone."""
    cfg = dataclasses.replace(_model(), dtype=jnp.float32)
    n, E, k = 24, cfg.num_experts, cfg.experts_per_token
    ks = jax.random.split(jax.random.key(4), 4)
    h, f = cfg.hidden_size, cfg.intermediate_size
    lp = {
        "w_gate": jax.random.normal(ks[0], (E, h, f)) * h**-0.5,
        "w_up": jax.random.normal(ks[1], (E, h, f)) * h**-0.5,
        "w_down": jax.random.normal(ks[2], (E, f, h)) * f**-0.5,
    }
    x = jax.random.normal(ks[3], (n, h))
    logits = _routing(case, n, E, k)
    _, idx = moe.route(cfg, logits)
    counts = np.bincount(np.asarray(idx).reshape(-1), minlength=E)
    assert counts.sum() == n * k
    if case == "one_expert_takes_all":
        assert counts[2] == n
    if case == "an_expert_with_none":
        assert counts[5] == 0
    if case == "even":
        assert counts.min() == counts.max()
    got = moe.routed_ffn(cfg, lp, x, router_logits=logits)
    np.testing.assert_allclose(
        got, _dense_weighted_sum(cfg, lp, x, logits), atol=2e-5, rtol=2e-5
    )


def test_served_program_computes_k_experts_a_token():
    """The expert matmuls of the traced layer are three grouped ones over
    tokens x k rows (the TPU compiler lowers each to one kernel whose flops
    are the routed ones: tests/test_chip_compile_cells.py), and nothing of the
    size [tokens, experts, width] is computed."""
    cfg = _model()
    params = jax.eval_shape(lambda: init_params_for(jax.random.key(0), cfg))
    lp = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                      params["layers"])
    x = jax.ShapeDtypeStruct((SLOTS, cfg.hidden_size), cfg.dtype)
    jaxpr = jax.make_jaxpr(lambda lp, x: moe.routed_ffn(cfg, lp, x))(lp, x)
    eqns = list(jaxpr.jaxpr.eqns)
    grouped = [e for e in eqns if e.primitive.name == "ragged_dot_general"]
    rows = SLOTS * cfg.experts_per_token
    assert [e.invars[0].aval.shape[0] for e in grouped] == [rows] * 3
    dense = SLOTS * cfg.num_experts * cfg.intermediate_size
    assert all(
        v.aval.size < dense for e in eqns for v in e.outvars
        if hasattr(v.aval, "size")
    )


# -- the engine: both caches, sleep, refusals ------------------------------------------


def test_engine_serves_through_both_caches():
    eng = InferenceEngine(_engine_cfg(), seed=3)
    lay = eng.kv_layout
    assert (lay.global_layers, lay.window_layers, lay.window) == (2, 6, 24)
    assert lay.ring_pages * PAGE == 24 + SEGMENT  # window + one segment
    assert eng.pool.k_pages.shape[0] == 2 and eng.pool.k_ring.shape[:3] == (
        6, SLOTS, lay.ring_pages)
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, size=n))) for n in (50, 9, 37)]
    batched = eng.generate(prompts, max_new_tokens=20)
    stats = eng.cache_stats()
    # every position past a ring's 40 left it: (50+19-40) + (37+19-40)
    assert stats["kv"]["window_tokens_evicted"] == 29 + 16
    assert stats["kv"]["ring_bytes"] == eng.pool.ring_nbytes() > 0
    tokens = sum(len(p) + 19 for p in prompts)
    # a family that always routes: every token's experts ran grouped
    assert stats["moe"] == {
        "experts": 8, "experts_held": 8, "router_width": 8, "tokens": tokens,
        "routed_tokens": tokens, "assignments": tokens * 8 * 3}
    # the window layers never hold more than window + segment a sequence,
    # and the pages of the full-attention layers come back on retire
    assert stats["kv"]["global_pages_in_use"] == 0
    assert eng.generate(prompts[:1], max_new_tokens=20)[0] == batched[0]


def test_sleep_and_wake_in_mid_decode_carry_both_caches():
    """A level-1 sleep between two decode chunks and a wake resume token
    for token, and the spans' bytes are those of weights + pages + rings."""
    prompt = list(map(int, np.random.default_rng(2).integers(0, 256, size=45)))
    gold = InferenceEngine(_engine_cfg(), seed=3).generate(
        [prompt], max_new_tokens=24)[0]
    eng = InferenceEngine(_engine_cfg(), seed=3)
    mgr = attach_sleep(eng)
    eng.add_request(prompt, max_new_tokens=24)
    req = eng._waiting[0]
    while len(req.out_tokens) < 9:
        eng.step()
    assert not req.done and req.pos > eng.kv_layout.ring_pages * PAGE
    state_bytes = sum(
        x.nbytes for x in jax.tree.leaves((eng.params, eng.pool.as_tuple())))
    ring_bytes = eng.pool.ring_nbytes()
    tracing.enable()
    tracing.clear()
    try:
        mgr.sleep(1)
        assert eng.pool.k_ring is None and eng.pool.k_pages is None
        mgr.wake_up()
        spans = tracing.snapshot()
    finally:
        tracing.disable()
    for name in ("sleep.d2h", "wake.h2d"):
        moved = sum(s.attrs["bytes"] for s in spans if s.name == name)
        assert moved == state_bytes > ring_bytes > 0
    assert eng.pool.ring_nbytes() == ring_bytes
    while not req.done:
        eng.step()
    assert req.out_tokens == gold


def _refusals():
    def engine(**kw):
        return lambda: InferenceEngine(_engine_cfg(**kw), seed=0)

    def park():
        InferenceEngine(_engine_cfg(), seed=0).park_requests()

    def mixed():
        cfg = _model()
        llama.mixed_step(None, cfg, jnp.zeros((8,), jnp.int32), None, None, None, None)

    def zero_drain():
        from llm_d_fast_model_actuation_tpu.engine import server

        args = server.make_arg_parser().parse_args(
            ["--model", "tiny-smallthinker", "--zero-drain", "on"])
        svc = server.EngineService.__new__(server.EngineService)
        svc.args, svc._zero_drain = args, True
        svc._engine_cfg_for(_model(), -1, ())

    def prefix_on():
        from llm_d_fast_model_actuation_tpu.engine import server

        args = server.make_arg_parser().parse_args(
            ["--model", "tiny-smallthinker", "--prefix-caching", "on"])
        svc = server.EngineService.__new__(server.EngineService)
        svc.args, svc._zero_drain = args, False
        InferenceEngine(svc._engine_cfg_for(_model(), -1, ()), seed=0)

    return {
        "prefix_cache": engine(prefix_caching=True),
        "prefix_caching_flag_on": prefix_on,
        "packed_mixed_step": engine(packed_serving=True),
        "speculative_ngram": engine(speculative_ngram=4),
        "zero_drain_park": park,
        "zero_drain_flag": zero_drain,
        "mixed_step_program": mixed,
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_cannot_carry_a_ring_refuses_the_model_by_name(what):
    with pytest.raises((SlotStateUnsupported, NotImplementedError)) as err:
        _refusals()[what]()
    assert "SmallThinkerConfig" in str(err.value)


def test_prefix_caching_auto_is_off_for_window_layers_and_on_otherwise():
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(["--model", "tiny"])
    assert args.prefix_caching == "auto"
    svc = server.EngineService.__new__(server.EngineService)
    svc.args, svc._zero_drain = args, False
    assert not svc._engine_cfg_for(_model(), -1, ()).prefix_caching
    assert svc._engine_cfg_for(llama.LlamaConfig.tiny(), -1, ()).prefix_caching
