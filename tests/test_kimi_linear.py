"""The Kimi-Linear family (models/kimi_linear.py) at a tiny preset on the CPU:
two periods of (3 x KDA, the delta rule with a decay a channel; 1 x latent
attention over latent pages), a leading dense FFN, then a shared expert and 4
held of 16 sigmoid-routed experts; small pages, prompts that span several
prefill segments so that a segment resumes from its slot's recurrent state
and attends over its latent pages, Pallas in interpret mode.

Tolerances. (1) The program computed in float32 agrees with the plain
reference (``reference_logits``: float32, the recurrence token by token, the
plain form of latent attention, the experts densely) to ``F32_TOL`` of a
logit row's own root-mean-square at every position, through every
segmentation and through decode: the chunkwise form, the carried state, the
latent pages, the absorbed form and the share of the experts are the
reference's mathematics, to rounding (readings 2e-6 to 2e-5). (2) In bfloat16
the MEDIAN over positions and seeds of the same share is held under
``LOGIT_ERR_TOL``. Seeded random weights make this family amplify rounding
as the other delta-rule family does, and a router near-tie that falls the
other way swaps an expert whose share of a tiny layer is large: the bfloat16
path reads 0.09-0.34 a seed here (0.16 over the three) and the same float32
reference with its matmul weights rounded to per-output-channel int8 reads
0.22-0.39 (0.31); the limit lies between and
``test_int8_weights_fail_the_tolerance`` holds the second reading above it.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.engine import SlotStateUnsupported
from llm_d_fast_model_actuation_tpu.engine.kv_cache import KVLayout, PagePool
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import kimi_linear as kl
from llm_d_fast_model_actuation_tpu.models import llama, moe
from llm_d_fast_model_actuation_tpu.models import olmo_hybrid as gdn
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for
from llm_d_fast_model_actuation_tpu.utils import tracing

F32_TOL = 2e-4
LOGIT_ERR_TOL = 0.22
SEEDS = (5, 6, 7)

PAGE, SEGMENT, SLOTS = 4, 16, 3


def _model(impl="grouped", **kw):
    return dataclasses.replace(
        kl.KimiLinearConfig.tiny_kimi_linear(), attention_impl=impl, **kw
    )


def _engine_cfg(**kw):
    kw.setdefault("prefix_caching", False)
    return EngineConfig(
        model=kl.KimiLinearConfig.tiny_kimi_linear(), max_batch=SLOTS,
        page_size=PAGE, num_pages=128, max_prefill_tokens=SEGMENT,
        decode_chunk=4, **kw,
    )


def _row_errors(got, want):
    """Per position: RMS of the difference over RMS of the reference row."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).mean(-1) / (want**2).mean(-1))


def _layout(cfg, segment=SEGMENT):
    return KVLayout.plan(
        cfg.cache_layers, 0, 0, PAGE, cfg.max_seq_len, segment,
        llama.recurrent_state(cfg), llama.latent_cache(cfg),
    )


def _fresh_cache(cfg, poison=0.0):
    """(cache tuple, the full page table): slot 1 owns pages 1.., and every
    slot's state starts at ``poison`` (a first segment must not read it)."""
    lay = _layout(cfg)
    pool = PagePool.create(
        lay.global_layers, 80, PAGE, cfg.num_kv_heads, cfg.head_dim,
        dtype=cfg.dtype, state_shapes=lay.state_shapes(SLOTS),
        latent_width=lay.latent_width,
    )
    lat, state, tail = pool.as_tuple()
    table = np.zeros((SLOTS, lay.table_width), np.int32)
    table[:, lay.pages_per_seq:] = lay.static_columns(SLOTS)
    table[1, : lay.pages_per_seq] = 1 + np.arange(lay.pages_per_seq)
    return (lat, state + poison, tail + poison), table


def _served_logits(cfg, params, tokens, segments, slot=1, poison=7.0, spoil=None):
    """Logits at every position of ``tokens`` by the serving programs: the
    prompt in ``segments`` (the first through the cold program when it is
    the only one, else every one through the continue program as the
    engine's chunked prefill does), then one decode step a token, through
    latent pages and state. ``spoil(cache)`` -> cache is applied between the
    segments (a state or a page that is NOT carried)."""
    cache, table = _fresh_cache(cfg, poison)
    row = jnp.asarray(table[slot : slot + 1])
    toks = jnp.asarray(tokens, jnp.int32)
    out, pos = [], 0
    for n in segments:
        bucket = 16
        while bucket < n:
            bucket *= 2
        seg = jnp.full((1, bucket), 3, jnp.int32).at[0, :n].set(toks[pos : pos + n])
        if len(segments) == 1:
            logits, cache = _program(cfg, "prefill")(
                params, seg, jnp.asarray([n]), cache, row)
        else:
            logits, cache = _program(cfg, "suffix")(
                params, seg, jnp.asarray([pos]), jnp.asarray([n]), cache, row)
        if spoil is not None:
            cache = spoil(cache)
        out.append(logits[0, :n])
        pos += n
    full = jnp.asarray(table)
    active = jnp.arange(SLOTS) == slot
    step = _program(cfg, "decode")
    for pos in range(pos, len(tokens)):
        t = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(toks[pos])
        p = jnp.zeros((SLOTS,), jnp.int32).at[slot].set(pos)
        logits, cache = step(params, t, p, cache, full, active)
        out.append(logits[slot : slot + 1])
    return jnp.concatenate(out, axis=0), cache


_PROGRAMS = {}


def _program(cfg, which):
    """One jitted program a config and kind (the weights an argument, so that
    every seed and every test of a config shares the compile)."""
    if (cfg, which) not in _PROGRAMS:
        fn = {
            "prefill": lambda params, *a: llama.prefill(params, cfg, *a),
            "suffix": lambda params, *a: llama.prefill_continue(params, cfg, *a),
            "decode": lambda params, *a: llama.decode_step(params, cfg, *a),
            "reference": lambda params, tokens: kl.reference_logits(
                params, cfg, tokens),
        }[which]
        _PROGRAMS[cfg, which] = jax.jit(fn)
    return _PROGRAMS[cfg, which]


def _tokens(seed, n=60):
    return np.random.default_rng(seed).integers(0, 256, size=n)


@pytest.fixture(scope="module")
def tiny32():
    """The program in float32 and its reference logits for one sequence."""
    cfg = _model(dtype=jnp.float32)
    params = init_params_for(jax.random.key(5), cfg)
    tokens = _tokens(0)
    return cfg, params, tokens, _program(cfg, "reference")(params, jnp.asarray(tokens))


# -- the mathematics, in float32 ------------------------------------------------------

SEGMENTATIONS = {
    "whole": (40,),
    "two_segments": (16, 11),
    "three_segments": (16, 16, 13),
    "a_segment_shorter_than_the_tail": (16, 2, 16),
    "sixty_four_rows_one_chunk": (60,),
}


@pytest.mark.parametrize("how", sorted(SEGMENTATIONS))
def test_program_is_the_reference_through_segments_state_and_decode(tiny32, how):
    """Prefill whole or in segments that resume from the slot's state and
    attend over its latent pages, then decode in the absorbed form: every
    position's logits are the plain reference's, in float32 to rounding,
    whatever the slot held before."""
    cfg, params, tokens, ref = tiny32
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(cfg, params, tokens, SEGMENTATIONS[how])
    assert _row_errors(got, ref).max() < F32_TOL


def test_pallas_kernel_serves_the_latent_decode(tiny32):
    """The same through the Pallas latent decode kernel (interpreted): a
    page's row read once, as key whole and as value by its first lanes."""
    cfg, params, tokens, ref = tiny32
    cfg = dataclasses.replace(cfg, attention_impl="pallas")
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(cfg, params, tokens, (16, 16, 8))
    assert _row_errors(got, ref).max() < F32_TOL


SPOILED = {
    "state": lambda c: (c[0], c[1] * 0.0, c[2]),
    "conv_tail": lambda c: (c[0], c[1], c[2] * 0.0),
    "latent_page": lambda c: (c[0].at[:, 1:3].set(0.0), c[1], c[2]),
}


@pytest.mark.parametrize("what", sorted(SPOILED))
def test_what_is_not_carried_across_a_segment_comes_out_not_correct(tiny32, what):
    """A recurrent state, a convolution tail or a latent page lost between
    two segments: the positions after it are far from the reference."""
    cfg, params, tokens, ref = tiny32
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(
            cfg, params, tokens, (16, 16, 13), spoil=SPOILED[what])
    err = _row_errors(got, ref)
    assert err[:16].max() < F32_TOL and err[16:].max() > 100 * F32_TOL


@jax.jit
def _sequential(q, k, v, beta, g, S):
    """The recurrence a token at a time by ``recurrence_step``."""
    def token(S, t):
        q, k, v, beta, g = t
        o, S = gdn.recurrence_step(q, k, v, beta, jnp.exp(g), S)
        return S, o

    rows = jax.tree.map(lambda x: jnp.moveaxis(x, 1, 0), (q, k, v, beta, g))
    S, o = jax.lax.scan(token, S, rows)
    return jnp.moveaxis(o, 0, 1), S


DECAYS = {
    # log(alpha) a channel: moderate; alpha = 0 (a channel wiped) and 1 (kept
    # whole) on whole rows; a 64-token chunk of decays near 0 (exp(-G) would
    # overflow after three rows) and one near 1
    "mixed": lambda rng, s: -rng.exponential(0.3, s),
    "rows_of_none_and_total": lambda rng, s: _rows(-rng.exponential(0.3, s)),
    "a_chunk_near_zero": lambda rng, s: np.full(s, -30.0) * rng.uniform(0.5, 1, s),
    "a_chunk_near_one": lambda rng, s: -rng.uniform(0, 1e-5, s),
}


def _rows(g):
    g[:, ::7] = -40.0
    g[:, 1::5] = 0.0
    g[:, 3::11, :, ::2] = -200.0
    return g


@pytest.mark.parametrize("rows,chunk", [(64, 64), (128, 32)])
@pytest.mark.parametrize("decay", sorted(DECAYS))
def test_chunkwise_kda_is_token_by_token(decay, rows, chunk):
    """``kda_chunk_scan`` over several chunks from a non-zero state gives the
    outputs and the final state of the recurrence stepped a token at a time,
    with decays a channel from none to total: nothing overflows inside a
    chunk, and nothing is lost."""
    rng = np.random.default_rng(rows)
    b, H, d = 2, 3, 16
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k, v = unit(f(b, rows, H, d)), unit(f(b, rows, H, d)), f(b, rows, H, d)
    beta = jnp.asarray(rng.uniform(0, 1, (b, rows, H)), jnp.float32)
    g = jnp.asarray(DECAYS[decay](rng, (b, rows, H, d)), jnp.float32)
    S0 = f(b, H, d, d)
    want_o, want_S = _sequential(q, k, v, beta, g, S0)
    got_o, got_S = kl.kda_chunk_scan(q, k, v, beta, g, S0, chunk=chunk)
    assert np.isfinite(np.asarray(got_o)).all()
    np.testing.assert_allclose(got_o, want_o, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(got_S, want_S, atol=5e-4, rtol=5e-4)


def test_a_decay_a_head_is_the_step_it_was():
    """``recurrence_step`` with a scalar decay a head (Olmo-Hybrid's) and the
    same decay spread over the channels agree."""
    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    q, k, v, S = f(2, 3, 8), f(2, 3, 8), f(2, 3, 12), f(2, 3, 8, 12)
    beta = jnp.asarray(rng.uniform(0, 2, (2, 3)), jnp.float32)
    alpha = jnp.asarray(rng.uniform(0, 1, (2, 3)), jnp.float32)
    o1, S1 = gdn.recurrence_step(q, k, v, beta, alpha, S)
    o2, S2 = gdn.recurrence_step(
        q, k, v, beta, jnp.broadcast_to(alpha[..., None], q.shape), S)
    np.testing.assert_allclose(o1, o2, atol=1e-5)
    np.testing.assert_allclose(S1, S2, atol=1e-5)


@pytest.mark.parametrize("impl", ["grouped", "pallas"])
def test_absorbed_latent_decode_is_the_plain_form(impl):
    """One decode step over latent pages in the absorbed form (W_kvb folded
    into query and output, the page's row key and value at once) gives what
    the plain form gives for the same position over the same rows."""
    cfg = _model(impl, dtype=jnp.float32)
    params = init_params_for(jax.random.key(2), cfg)
    fp = {k: a[0] for k, a in params["mla"].items()}
    rng = np.random.default_rng(1)
    n = 23  # positions in the cache; the query is the 24th
    x = jnp.asarray(rng.standard_normal((1, n + 1, cfg.hidden_size)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        q_nope, q_pe, rows = kl._latent_rows(cfg, fp, x)
        assert rows.shape[-1] == 256 and not np.asarray(rows[..., 144:]).any()
        want = kl.mla_plain_attention(
            cfg, fp, q_nope, q_pe, rows, jnp.arange(n + 1)[None])[0, n]
        pool = jnp.zeros((1, 16, PAGE, 256), jnp.float32)
        table = jnp.asarray([[3, 1, 7, 2, 9, 4, 5, 6]], jnp.int32)
        pool = llama._scatter_prefill(
            pool, 0, rows[:, :n], table, jnp.arange(n)[None],
            jnp.ones((1, n), bool), PAGE)
        got = kl.mla_absorbed_decode(
            cfg, fp, q_nope[:, n], q_pe[:, n], rows[:, n], pool, table,
            jnp.asarray([n]), 0)[0]
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-4)


# -- the share of the experts ---------------------------------------------------------


def _expert_layer(seed=0):
    """An uncut config (16 of 16 held), its ``experts`` parameters of one
    layer, and rows to route."""
    cfg = _model(dtype=jnp.float32, num_experts=16)
    params = init_params_for(jax.random.key(seed), cfg)
    ep = {k: a[2] for k, a in params["experts"].items()}
    x = jnp.asarray(
        np.random.default_rng(seed).standard_normal((2, 9, cfg.hidden_size)),
        jnp.float32)
    return cfg, ep, x


def _routed(cfg, ep, x):
    logits = jnp.einsum("...h,he->...e", x, ep["router"])
    return moe.routed_ffn(cfg, ep, x, logits)


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE share test: the routed parts that the four shares of 4 experts
    give, with what every chip computes alike (the shared expert) counted
    once, add up to the uncut layer as the plain reference computes it."""
    cfg, ep, x = _expert_layer()
    with jax.default_matmul_precision("highest"):
        w, idx = moe.route(cfg, x @ ep["router"], ep["router_bias"])
        weights = jnp.sum(jax.nn.one_hot(idx, 16) * w[..., None], axis=-2)
        g = jnp.einsum("...h,ehf->...ef", x, ep["w_gate"])
        u = jnp.einsum("...h,ehf->...ef", x, ep["w_up"])
        y = jnp.einsum("...ef,efh->...eh", jax.nn.silu(g) * u, ep["w_down"])
        uncut = jnp.einsum("...eh,...e->...h", y, weights)
        assert float(jnp.abs(uncut).max()) > 0.1
        np.testing.assert_allclose(_routed(cfg, ep, x), uncut, atol=2e-5)
        parts = []
        for share in range(4):
            held = dataclasses.replace(
                cfg, num_experts=4, router_width=16, share_index=share)
            mine = {
                k: a[4 * share : 4 * share + 4] if k in moe.EXPERT_STACKS else a
                for k, a in ep.items()
            }
            parts.append(_routed(held, mine, x))
            # a share alone is not the layer
            assert float(jnp.abs(parts[-1] - uncut).max()) > 0.05
        np.testing.assert_allclose(sum(parts), uncut, atol=2e-5)


def test_sigmoid_router_renormalises_over_the_kept_and_the_bias_only_selects():
    cfg, ep, x = _expert_layer(1)
    logits = x @ ep["router"]
    w, idx = moe.route(cfg, logits, ep["router_bias"])
    assert idx.shape == w.shape == (2, 9, 3)
    np.testing.assert_allclose(w.sum(-1), 2.446, rtol=1e-5)
    scores = jax.nn.sigmoid(logits)
    kept = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(w, 2.446 * kept / kept.sum(-1, keepdims=True), rtol=1e-5)
    # a large bias on one expert selects it everywhere and weighs it as before
    bias = ep["router_bias"].at[5].set(10.0)
    w2, idx2 = moe.route(cfg, logits, bias)
    assert (idx2 == 5).any(-1).all()
    _, plain = moe.route(cfg, logits)
    assert not np.array_equal(np.asarray(plain), np.asarray(idx2))
    with pytest.raises(ValueError, match="outside a router"):
        dataclasses.replace(cfg, num_experts=4, router_width=16, share_index=4)


def test_all_held_is_the_routed_layer_it_was():
    """SmallThinker takes the one path with every expert held: softmax over
    the kept logits, no row left out."""
    from llm_d_fast_model_actuation_tpu.models.smallthinker import SmallThinkerConfig

    cfg = SmallThinkerConfig.tiny_smallthinker()
    assert cfg.router_outputs == cfg.num_experts == 8 and cfg.share_index == 0
    logits = jnp.asarray(np.random.default_rng(0).standard_normal((5, 8)), jnp.float32)
    w, idx = moe.route(cfg, logits)
    top, top_idx = jax.lax.top_k(logits, 3)
    assert np.array_equal(np.asarray(idx), np.asarray(top_idx))
    np.testing.assert_allclose(w, jax.nn.softmax(top, -1), rtol=1e-6)


# -- bfloat16 against the reference, and the control ---------------------------------


def _int8(w):
    """``w`` rounded to per-output-channel int8 and back (models/quant.py)."""
    from llm_d_fast_model_actuation_tpu.models.quant import quantize_weight

    low = quantize_weight(w)
    return (low["q"].astype(jnp.float32) * low["s"]).astype(w.dtype)


@pytest.fixture(scope="module")
def readings():
    """Per seed: (bfloat16 program, int8-weight reference) row errors."""
    cfg = _model()
    rows = []
    for seed in SEEDS:
        params = init_params_for(jax.random.key(seed), cfg)
        tokens = _tokens(seed)
        ref = _program(cfg, "reference")(params, jnp.asarray(tokens))
        got, _ = _served_logits(cfg, params, tokens, (16, 16, 13))
        low = jax.tree.map(
            lambda a: _int8(a) if a.ndim >= 3 and a.shape[-2] > 8 else a, params)
        low["lm_head"] = _int8(params["lm_head"])
        ctl = _program(cfg, "reference")(low, jnp.asarray(tokens))
        rows.append((_row_errors(got, ref), _row_errors(ctl, ref)))
    return rows


def test_bfloat16_program_is_within_the_tolerance(readings):
    assert np.median(np.concatenate([p for p, _ in readings])) < LOGIT_ERR_TOL


def test_int8_weights_fail_the_tolerance(readings):
    assert np.median(np.concatenate([c for _, c in readings])) > 1.15 * LOGIT_ERR_TOL


# -- the layout ------------------------------------------------------------------------


def test_layout_has_latent_pages_and_the_state_beside_them():
    cfg = _model()
    lay = _layout(cfg)
    assert (lay.global_layers, lay.window_layers, lay.state_layers) == (2, 0, 6)
    assert (lay.latent_counted, lay.latent_width) == (144, 256) == cfg.latent_cache
    assert lay.state_shape == (4, 16, 16) and lay.tail_shape == (3, 192)
    assert lay.table_width == lay.pages_per_seq + 1
    pool = PagePool.create(
        lay.global_layers, 8, PAGE, cfg.num_kv_heads, cfg.head_dim,
        state_shapes=lay.state_shapes(SLOTS), latent_width=lay.latent_width)
    assert pool.kinds == ("latent", "state") and len(pool.as_tuple()) == 3
    assert pool.v_pages is None and pool.k_pages.shape == (2, 8, PAGE, 256)
    assert pool.nbytes() == pool.k_pages.nbytes + pool.state_nbytes()
    assert pool.k_pages.nbytes == PagePool.estimate_nbytes(
        2, 8, PAGE, 4, 16, latent_width=256) == 8 * PagePool.page_nbytes(
        2, PAGE, 4, 16, latent_width=256)
    kv = pool.as_tuple()
    pool.drop()
    assert pool.k_pages is None and pool.kinds == ("latent", "state")
    pool.replace(kv)
    assert pool.k_pages is kv[0] and pool.state is kv[1] and pool.conv_tail is kv[2]
    # the published sizes: 512 + 64 counted, 640 stored; no K-and-V model has them
    big = kl.KimiLinearConfig.kimi_linear_48b_a3b()
    assert big.latent_cache == (576, 640) and llama.latent_cache(llama.LlamaConfig.tiny()) is None
    plain = KVLayout.plan(2, 0, 0, PAGE, 64, 16)
    assert (plain.latent_width, plain.latent_counted) == (0, 0)


def test_config_counts_its_parameters_and_its_kinds():
    cfg = _model()
    params = init_params_for(jax.random.key(0), cfg)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    assert (cfg.kda_layers, cfg.cache_layers, cfg.expert_layers, cfg.periods) == (6, 2, 7, 2)
    assert params["kda"]["w_qkv"].shape == (6, 64, 192)
    assert params["mla"]["w_kva"].shape == (2, 64, 144)
    assert params["experts"]["w_gate"].shape == (7, 4, 64, 32)
    assert params["experts"]["router"].shape == (7, 64, 16)
    assert params["dense"]["w_gate"].shape == (1, 64, 96)
    assert params["kda"]["a_log"].dtype == params["experts"]["router_bias"].dtype == jnp.float32
    assert float(jnp.abs(params["experts"]["router_bias"]).max()) > 0
    big = kl.KimiLinearConfig.kimi_linear_48b_a3b()
    assert big.num_params() == 3_772_368_832
    assert big.recurrent_state == (6, (32, 128, 128), (3, 12288))
    assert big.q_dim == 6144 and big.router_outputs == 256 and big.num_experts == 64
    for bad in (dict(layer_kinds=("kda", "full")), dict(num_layers=6),
                dict(quantization="int8"), dict(loop_steps=2),
                dict(first_dense=5), dict(kv_lora_rank=96)):
        with pytest.raises(ValueError):
            _model(**bad)


# -- the engine: latent pages and state, recycled slots, sleep, refusals --------------


def test_engine_serves_through_latent_pages_and_state_and_recycles_slots():
    eng = InferenceEngine(_engine_cfg(), seed=3)
    lay = eng.kv_layout
    assert (lay.global_layers, lay.state_layers, lay.latent_width) == (2, 6, 256)
    assert eng.pool.k_pages.shape == (2, 128, PAGE, 256) and eng.pool.v_pages is None
    assert eng.pool.state.shape == (6, SLOTS, 4, 16, 16)
    assert eng._page_table[:, -1].tolist() == [0, 1, 2]
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, size=n))) for n in (50, 9, 37, 20, 33)]
    batched = eng.generate(prompts, max_new_tokens=20)
    stats = eng.cache_stats()
    tokens = sum(len(p) + 19 for p in prompts)
    assert stats["state"] == {
        "layers": 6, "bytes_per_slot": 6 * (4 * 16 * 16 * 4 + 3 * 192 * 2),
        "bytes": eng.pool.state_nbytes(), "token_updates": tokens * 6,
        "first_segments": 5, "resumed_segments": 3 + 2 + 1 + 2,
    }
    assert stats["kv"]["latent_layers"] == 2
    assert stats["kv"]["latent_bytes_per_token"] == 2 * 144 * 2
    assert stats["kv"]["latent_bytes_per_token_laid_out"] == 2 * 256 * 2
    assert stats["kv"]["bytes_per_token"] == 2 * 256 * 2
    assert stats["kv"]["global_pages_in_use"] == 0
    assert stats["moe"] == {
        "experts": 4, "experts_held": 4, "router_width": 16, "tokens": tokens,
        "routed_tokens": tokens, "assignments": tokens * 7 * 3,
    }
    # a K-and-V model reports no latent layer, and every expert held
    plain = InferenceEngine(EngineConfig(
        model=moe.MoeConfig.tiny_moe(), max_batch=2, page_size=PAGE,
        num_pages=32), seed=0).cache_stats()
    assert plain["kv"]["latent_layers"] == plain["kv"]["latent_bytes_per_token"] == 0
    assert (plain["moe"]["experts_held"], plain["moe"]["router_width"]) == (4, 4)
    # five requests through three slots: the last two were served from
    # recycled slots beside other sequences, and each reads what the engine
    # serves it alone, from a slot that held another sequence's state
    for i in (3, 4):
        assert eng.generate([prompts[i]], max_new_tokens=20)[0] == batched[i]


def test_sleep_and_wake_in_mid_decode_carry_latent_pages_and_state():
    """A level-1 sleep between two decode chunks and a wake resume token for
    token; the latent pages and the state come back bit for bit, and the
    spans' bytes are those of weights + latent pages + state."""
    prompt = list(map(int, np.random.default_rng(2).integers(0, 256, size=45)))
    eng = InferenceEngine(_engine_cfg(), seed=3)
    gold = eng.generate([prompt], max_new_tokens=24)[0]
    mgr = attach_sleep(eng)
    eng.add_request(prompt, max_new_tokens=24)
    req = eng._waiting[0]
    while len(req.out_tokens) < 9:
        eng.step()
    assert not req.done
    state_bytes = sum(
        x.nbytes for x in jax.tree.leaves((eng.params, eng.pool.as_tuple())))
    before = [np.asarray(a) for a in eng.pool.as_tuple()]
    assert np.abs(before[0]).max() > 0 and len(before) == 3
    tracing.enable()
    tracing.clear()
    try:
        mgr.sleep(1)
        assert eng.pool.state is None and eng.pool.k_pages is None
        mgr.wake_up()
        spans = tracing.snapshot()
    finally:
        tracing.disable()
    for name in ("sleep.d2h", "wake.h2d"):
        moved = sum(s.attrs["bytes"] for s in spans if s.name == name)
        assert moved == state_bytes > eng.pool.nbytes() > 0
    for was, now in zip(before, eng.pool.as_tuple()):
        assert np.array_equal(was, np.asarray(now))
    while not req.done:
        eng.step()
    assert req.out_tokens == gold


def _refusals():
    def engine(**kw):
        return lambda: InferenceEngine(_engine_cfg(**kw), seed=0)

    def park():
        InferenceEngine(_engine_cfg(), seed=0).park_requests()

    def mixed():
        llama.mixed_step(None, _model(), jnp.zeros((8,), jnp.int32), None, None, None, None)

    def service(*flags, zero_drain=False):
        from llm_d_fast_model_actuation_tpu.engine import server

        args = server.make_arg_parser().parse_args(
            ["--model", "tiny-kimi-linear", *flags])
        svc = server.EngineService.__new__(server.EngineService)
        svc.args, svc._zero_drain = args, zero_drain
        return svc

    def zero_drain():
        service("--zero-drain", "on", zero_drain=True)._engine_cfg_for(_model(), -1, ())

    def prefix_on():
        svc = service("--prefix-caching", "on")
        InferenceEngine(svc._engine_cfg_for(_model(), -1, ()), seed=0)

    def migrate():
        from llm_d_fast_model_actuation_tpu.engine.engine import refuse_slot_state

        refuse_slot_state(_model(), "a live migration")

    def quantized():
        _model(quantization="int8")

    return {
        "prefix_cache": engine(prefix_caching=True),
        "prefix_caching_flag_on": prefix_on,
        "packed_mixed_step": engine(packed_serving=True),
        "speculative_ngram": engine(speculative_ngram=4),
        "zero_drain_park": park,
        "zero_drain_flag": zero_drain,
        "mixed_step_program": mixed,
        "live_migration": migrate,
        "weight_quantization": quantized,
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_knows_k_and_v_pages_alone_refuses_the_family_by_name(what):
    with pytest.raises((SlotStateUnsupported, NotImplementedError, ValueError)) as err:
        _refusals()[what]()
    assert "KimiLinearConfig" in str(err.value)
    if what != "weight_quantization":
        assert "latent-attention layers" in str(err.value)
        assert "recurrent state" in str(err.value)


def test_one_refusal_names_latent_pages_rings_and_recurrent_state_alike():
    from llm_d_fast_model_actuation_tpu.engine.engine import refuse_slot_state
    from llm_d_fast_model_actuation_tpu.models.smallthinker import SmallThinkerConfig

    refuse_slot_state(llama.LlamaConfig.tiny(), "anything")
    with pytest.raises(SlotStateUnsupported, match="per-sequence rings"):
        refuse_slot_state(SmallThinkerConfig.tiny_smallthinker(), "a path")
    with pytest.raises(SlotStateUnsupported, match="6 linear-attention layers"):
        refuse_slot_state(gdn.OlmoHybridConfig.tiny_olmo_hybrid(), "a path")
    with pytest.raises(
        SlotStateUnsupported,
        match="2 latent-attention layers whose pages are one array of 144 values",
    ):
        refuse_slot_state(_model(), "a path")


def test_prefix_caching_auto_is_off_and_the_names_resolve():
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(["--model", "tiny-kimi-linear"])
    assert args.prefix_caching == "auto"
    svc = server.EngineService.__new__(server.EngineService)
    svc.args, svc._zero_drain = args, False
    assert not svc._engine_cfg_for(_model(), -1, ()).prefix_caching
    assert server.MODEL_CONFIGS["tiny-kimi-linear"]() == kl.KimiLinearConfig.tiny_kimi_linear()
    assert server.MODEL_CONFIGS["kimi-linear-48b-a3b"]().num_layers == 8


def test_cost_oracle_counts_latent_pages_and_state():
    """``_kv_pool_nbytes`` (a cold build's predicted bytes) is the one array
    of latent pages plus the per-slot state, as the pool builds them."""
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(
        ["--model", "tiny-kimi-linear", "--max-batch", str(SLOTS),
         "--page-size", str(PAGE), "--num-pages", "128"])
    svc = server.EngineService.__new__(server.EngineService)
    svc.args = args
    eng = InferenceEngine(_engine_cfg(), seed=0)
    assert svc._kv_pool_nbytes(_model()) == eng.pool.nbytes()
    assert eng.pool.nbytes() == 2 * 128 * PAGE * 256 * 2 + eng.pool.state_nbytes()


def test_auto_attention_impl_goes_by_the_stored_row():
    """The kernels read a latent page as one head as wide as its stored row:
    640 lanes at the published sizes, whatever ``head_dim`` says."""
    from llm_d_fast_model_actuation_tpu.engine.engine import kernel_kv_shape

    assert kernel_kv_shape(kl.KimiLinearConfig.kimi_linear_48b_a3b()) == (1, 640)
    assert kernel_kv_shape(_model()) == (1, 256)
    assert kernel_kv_shape(llama.LlamaConfig.tiny(), 2) == (1, 16)
