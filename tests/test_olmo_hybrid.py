"""The hybrid family (models/olmo_hybrid.py) at a tiny preset on the CPU: two
periods of (3 x gated-delta-rule linear attention, 1 x full attention +
NoPE), head sizes that are no multiple of anything, small pages, prompts
that span several prefill segments so that a segment resumes from its slot's
recurrent state, Pallas in interpret mode.

Tolerances. Two comparisons with the plain reference (``reference_logits``:
float32, the recurrence token by token). (1) The program computed in
float32 agrees with it to ``F32_TOL`` of a logit row's own root-mean-square
at every position, through every segmentation and through decode: the
chunkwise form, the carried state, the convolution tail and the slot
addressing are the reference's mathematics, to rounding (readings 4e-6 to
4e-5). (2) In bfloat16 the MEDIAN over positions and seeds of the same share
is held under ``LOGIT_ERR_TOL``. Seeded random weights make this family
amplify rounding: a stack of linear layers carries a perturbation of its
input about 1.5x a layer (eight of them read 0.18 where eight full-attention
layers read 0.014), so the bfloat16 path reads 0.07-0.10 here and the same
float32 reference with its matmul weights rounded to per-output-channel int8
reads 0.18-0.24; the limit lies between and
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
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.models import olmo_hybrid as oh
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for
from llm_d_fast_model_actuation_tpu.utils import tracing

F32_TOL = 2e-4
LOGIT_ERR_TOL = 0.14
SEEDS = (5, 6, 7)

PAGE, SEGMENT, SLOTS = 4, 16, 3


def _model(impl="grouped", **kw):
    return dataclasses.replace(
        oh.OlmoHybridConfig.tiny_olmo_hybrid(), attention_impl=impl, **kw
    )


def _engine_cfg(**kw):
    kw.setdefault("prefix_caching", False)
    return EngineConfig(
        model=oh.OlmoHybridConfig.tiny_olmo_hybrid(), max_batch=SLOTS,
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
        llama.recurrent_state(cfg),
    )


def _fresh_cache(cfg, poison=0.0):
    """(cache tuple, the full page table): slot 1 owns pages 1.., and every
    slot's state starts at ``poison`` (a first segment must not read it)."""
    lay = _layout(cfg)
    pool = PagePool.create(
        lay.global_layers, 80, PAGE, cfg.num_kv_heads, cfg.head_dim,
        dtype=cfg.dtype, state_shapes=lay.state_shapes(SLOTS),
    )
    kp, vp, state, tail = pool.as_tuple()
    table = np.zeros((SLOTS, lay.table_width), np.int32)
    table[:, lay.pages_per_seq:] = lay.static_columns(SLOTS)
    table[1, : lay.pages_per_seq] = 1 + np.arange(lay.pages_per_seq)
    return (kp, vp, state + poison, tail + poison), table


def _served_logits(cfg, params, tokens, segments, slot=1, poison=7.0):
    """Logits at every position of ``tokens`` by the serving programs: the
    prompt in ``segments`` (their lengths; the first through the cold
    program when it is the only one, else every one through the continue
    program as the engine's chunked prefill does), then one decode step a
    token, through pages and state. Returns (logits, the cache after)."""
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
            "reference": lambda params, tokens: oh.reference_logits(
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
    """Prefill whole or in segments that resume from the slot's state, then
    decode through pages and state: every position's logits are the
    token-by-token reference's, in float32 to rounding, whatever the slot
    held before."""
    cfg, params, tokens, ref = tiny32
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(cfg, params, tokens, SEGMENTATIONS[how])
    assert _row_errors(got, ref).max() < F32_TOL


def test_pallas_attention_serves_the_full_layers_too(tiny32):
    """The same through the Pallas prefill and paged-decode kernels
    (interpreted): the full layers' pool index is their ordinal."""
    cfg, params, tokens, ref = tiny32
    cfg = dataclasses.replace(cfg, attention_impl="pallas")
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(cfg, params, tokens, (16, 16, 8))
    assert _row_errors(got, ref).max() < F32_TOL


def _sequential(q, k, v, beta, g, S):
    """The recurrence a token at a time by ``recurrence_step``."""
    out = []
    for t in range(q.shape[1]):
        o, S = oh.recurrence_step(
            q[:, t], k[:, t], v[:, t], beta[:, t], jnp.exp(g[:, t]), S)
        out.append(o)
    return jnp.stack(out, axis=1), S


@pytest.mark.parametrize("rows,chunk", [(16, 64), (64, 64), (256, 64), (128, 32)])
def test_chunkwise_is_token_by_token(rows, chunk):
    """``chunk_scan`` over several chunks from a non-zero state gives the
    outputs and the final state of the recurrence stepped a token at a
    time, with decays from none to total and beta over (0, 2)."""
    rng = np.random.default_rng(rows)
    b, H, dk, dv = 2, 3, 12, 24
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q, k, v = unit(f(b, rows, H, dk)), unit(f(b, rows, H, dk)), f(b, rows, H, dv)
    beta = jnp.asarray(rng.uniform(0, 2, (b, rows, H)), jnp.float32)
    g = -jnp.asarray(rng.exponential(0.3, (b, rows, H)), jnp.float32)
    g = g.at[:, ::7].set(-40.0).at[:, 1::5].set(0.0)
    S0 = f(b, H, dk, dv)
    want_o, want_S = _sequential(q, k, v, beta, g, S0)
    got_o, got_S = oh.chunk_scan(q, k, v, beta, g, S0, chunk=chunk)
    np.testing.assert_allclose(got_o, want_o, atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(got_S, want_S, atol=2e-4, rtol=2e-4)


#: the two delta-rule families' published head shapes: heads, d_k, d_v, the
#: ceiling of beta, and whether the decay is one number a head or a channel
SOLVE_SHAPES = {
    "olmo": (30, 96, 192, 2.0, False),
    "kimi": (32, 128, 128, 1.0, True),
}


def _chunk_system(family, C, keys):
    """One chunk's ``A`` and ``rhs`` as the two scans build them, in float64
    from float64 draws: [H, C, C] and [H, C, d_v + d_k]."""
    H, dk, dv, beta_max, per_channel = SOLVE_SHAPES[family]
    rng = np.random.default_rng(C)
    k, v = rng.standard_normal((H, C, dk)), rng.standard_normal((H, C, dv))
    beta = rng.uniform(0, beta_max, (H, C))
    g = -rng.exponential(0.3, (H, C, dk if per_channel else 1))
    g[:, ::7], g[:, 1::5] = -40.0, 0.0
    if keys == "collinear":
        k = np.broadcast_to(k[:, :1], k.shape)
        beta, g = np.full_like(beta, beta_max), np.zeros_like(g)
    k = k / np.linalg.norm(k, axis=-1, keepdims=True)
    if keys == "padded":
        beta[:, -C // 4:], g[:, -C // 4:] = 0.0, 0.0
    G = np.cumsum(g, axis=1)  # [H, C, dk or 1], never above zero
    lower = np.tril(np.ones((C, C), bool))[..., None]
    A = np.stack([
        np.sum(
            kh[:, None] * kh[None] * np.exp(
                np.where(lower, Gh[:, None] - Gh[None], -np.inf)),
            axis=-1,
        )
        for kh, Gh in zip(k, G)
    ]) * beta[..., None] * np.tril(np.ones((C, C)), -1)
    rhs = np.concatenate(
        [beta[..., None] * v, beta[..., None] * np.exp(G) * k], axis=-1)
    return A, rhs


@pytest.mark.parametrize("keys", ["random", "collinear", "padded"])
@pytest.mark.parametrize("C", [16, 32, 64])
@pytest.mark.parametrize("family", sorted(SOLVE_SHAPES))
def test_unit_lower_solve_is_no_worse_than_xlas(family, C, keys):
    """``unit_lower_solve`` on a chunk's system at the published head shapes,
    one, two and four diagonal blocks: against a float64 solve of the same
    float32 inputs its largest error is at most twice that of XLA's
    ``triangular_solve``, the call it replaced; a padded row (beta 0, g 0)
    has a zero row of ``A`` and comes out as its row of ``rhs``, bit for bit."""
    A, rhs = (jnp.asarray(x, jnp.float32) for x in _chunk_system(family, C, keys))
    want = np.linalg.solve(
        np.eye(C) + np.asarray(A, np.float64), np.asarray(rhs, np.float64))
    got = np.asarray(jax.jit(oh.unit_lower_solve)(A, rhs))
    xla = np.asarray(jax.lax.linalg.triangular_solve(
        A, rhs, left_side=True, lower=True, unit_diagonal=True))
    assert np.isfinite(got).all()
    err, xla_err = np.abs(got - want).max(), np.abs(xla - want).max()
    assert err <= 2 * xla_err, (err, xla_err)
    if keys == "padded":
        assert not np.asarray(A)[:, -C // 4:].any()
        assert np.array_equal(got[:, -C // 4:], np.asarray(rhs)[:, -C // 4:])


def test_a_padded_bucket_row_changes_neither_state_nor_tail(tiny32):
    """A segment of 10 tokens in a bucket of 16: the state and the tail it
    leaves are those of the 10 tokens, bit for bit whatever the padding
    holds, and what the reference's recurrence reaches after 10 tokens."""
    cfg, params, tokens, _ = tiny32
    ends = []
    for pad in (0, 201):
        cache, table = _fresh_cache(cfg)
        seg = jnp.full((1, 16), pad, jnp.int32).at[0, :10].set(
            jnp.asarray(tokens[:10], jnp.int32))
        with jax.default_matmul_precision("highest"):
            _, cache = _program(cfg, "prefill")(
                params, seg, jnp.asarray([10]), cache, jnp.asarray(table[1:2]))
        ends.append((np.asarray(cache[2]), np.asarray(cache[3])))
    assert np.array_equal(ends[0][0], ends[1][0])
    assert np.array_equal(ends[0][1], ends[1][1])
    state, tail = ends[0]
    assert np.abs(state[:, 1]).max() > 0 and not state[:, 0].any()
    assert not state[:, 2].any() and not tail[:, 2].any()
    # the same prompt unpadded, by 10 single decode steps from zero
    cache, table = _fresh_cache(cfg)
    full, active = jnp.asarray(table), jnp.arange(SLOTS) == 1
    for pos in range(10):
        t = jnp.zeros((SLOTS,), jnp.int32).at[1].set(int(tokens[pos]))
        p = jnp.zeros((SLOTS,), jnp.int32).at[1].set(pos)
        with jax.default_matmul_precision("highest"):
            _, cache = _program(cfg, "decode")(params, t, p, cache, full, active)
    np.testing.assert_allclose(cache[2], state, atol=1e-4)
    np.testing.assert_allclose(cache[3], tail, atol=1e-4)


def test_an_inactive_decode_row_leaves_its_slot_alone(tiny32):
    cfg, params, _, _ = tiny32
    cache, table = _fresh_cache(cfg, poison=2.0)
    active = jnp.asarray([False, True, False])
    _, after = _program(cfg, "decode")(
        params, jnp.asarray([7, 8, 9]), jnp.asarray([3, 0, 5]), cache,
        jnp.asarray(table), active,
    )
    for before, now in zip(cache[2:], after[2:]):
        assert np.array_equal(before[:, 0], now[:, 0])
        assert np.array_equal(before[:, 2], now[:, 2])
        assert not np.array_equal(before[:, 1], now[:, 1])


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
            lambda a: _int8(a) if a.ndim == 3 and a.shape[1] > 8 else a, params)
        low["lm_head"] = _int8(params["lm_head"])
        ctl = _program(cfg, "reference")(low, jnp.asarray(tokens))
        rows.append((_row_errors(got, ref), _row_errors(ctl, ref)))
    return rows


def test_bfloat16_program_is_within_the_tolerance(readings):
    assert np.median(np.concatenate([p for p, _ in readings])) < LOGIT_ERR_TOL


def test_int8_weights_fail_the_tolerance(readings):
    assert np.median(np.concatenate([c for _, c in readings])) > 1.15 * LOGIT_ERR_TOL


# -- the layout ------------------------------------------------------------------------


def test_layout_puts_the_state_beside_the_pages():
    cfg = _model()
    lay = _layout(cfg)
    assert (lay.global_layers, lay.window_layers, lay.state_layers) == (2, 0, 6)
    assert lay.state_shape == (4, 12, 24) and lay.tail_shape == (3, 192)
    assert lay.table_width == lay.pages_per_seq + 1
    assert lay.static_columns(SLOTS).tolist() == [[0], [1], [2]]
    assert lay.state_shapes(SLOTS) == ((6, SLOTS, 4, 12, 24), (6, SLOTS, 3, 192))
    assert lay.state_nbytes(SLOTS, 2) == 6 * SLOTS * (4 * 12 * 24 * 4 + 3 * 192 * 2)
    pool = PagePool.create(
        lay.global_layers, 8, PAGE, cfg.num_kv_heads, cfg.head_dim,
        state_shapes=lay.state_shapes(SLOTS))
    assert pool.kinds == ("state",) and len(pool.as_tuple()) == 4
    assert pool.state.dtype == jnp.float32 and pool.conv_tail.dtype == jnp.bfloat16
    assert pool.state_nbytes() == lay.state_nbytes(SLOTS, 2)
    assert pool.nbytes() == 2 * pool.k_pages.nbytes + pool.state_nbytes()
    kv = pool.as_tuple()
    pool.drop()
    assert pool.state is None and pool.kinds == ("state",)
    pool.replace(kv)
    assert pool.state is kv[2] and pool.conv_tail is kv[3]
    # a model without linear layers has neither the column nor the arrays
    plain = KVLayout.plan(2, 0, 0, PAGE, 64, 16, llama.recurrent_state(llama.LlamaConfig.tiny()))
    assert plain.table_width == plain.pages_per_seq and plain.state_shapes(3) is None
    assert plain.state_nbytes(3, 2) == 0


def test_config_counts_its_parameters_and_its_kinds():
    cfg = _model()
    params = init_params_for(jax.random.key(0), cfg)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    assert (cfg.linear_layers, cfg.cache_layers, cfg.periods) == (6, 2, 2)
    assert params["linear"]["w_qkv"].shape == (6, 64, 192)
    assert params["full"]["wq"].shape == (2, 64, 64)
    assert params["layers"]["w_gate"].shape == (8, 64, 96)
    assert params["linear"]["a_log"].dtype == jnp.float32
    big = oh.OlmoHybridConfig.olmo_hybrid_7b()
    assert big.conv_dim == 11520 and big.recurrent_state == (
        24, (30, 96, 192), (3, 11520))
    assert dataclasses.replace(big, num_layers=16).num_params() == 4_100_788_944
    for bad in (dict(layer_kinds=("linear", "window")), dict(num_layers=6),
                dict(quantization="int8"), dict(loop_steps=2)):
        with pytest.raises(ValueError):
            _model(**bad)


# -- the engine: pages and state, recycled slots, sleep, refusals ---------------------


def test_engine_serves_through_pages_and_state_and_recycles_slots():
    eng = InferenceEngine(_engine_cfg(), seed=3)
    lay = eng.kv_layout
    assert (lay.global_layers, lay.state_layers) == (2, 6)
    assert eng.pool.k_pages.shape[0] == 2
    assert eng.pool.state.shape == (6, SLOTS, 4, 12, 24)
    assert eng._page_table[:, -1].tolist() == [0, 1, 2]
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, size=n))) for n in (50, 9, 37, 20, 33)]
    batched = eng.generate(prompts, max_new_tokens=20)
    stats = eng.cache_stats()
    tokens = sum(len(p) + 19 for p in prompts)
    assert stats["state"] == {
        "layers": 6, "bytes_per_slot": 6 * (4 * 12 * 24 * 4 + 3 * 192 * 2),
        "bytes": eng.pool.state_nbytes(), "token_updates": tokens * 6,
        # prompts of 50, 37, 20 and 33 tokens run past one 16-token segment
        "first_segments": 5, "resumed_segments": 3 + 2 + 1 + 2,
    }
    assert stats["stack"]["layer_passes"] % 8 == 0
    assert stats["kv"]["global_layers"] == 2
    assert stats["kv"]["bytes_per_token"] == 2 * 2 * 4 * 16 * 2
    assert stats["kv"]["global_pages_in_use"] == 0
    # five requests through three slots: a recycled slot serves what a
    # fresh engine serves
    for prompt, served in zip(prompts, batched):
        fresh = InferenceEngine(_engine_cfg(), seed=3)
        assert fresh.generate([prompt], max_new_tokens=20)[0] == served


def test_sleep_and_wake_in_mid_decode_carry_the_state():
    """A level-1 sleep between two decode chunks and a wake resume token
    for token, and the spans' bytes are those of weights + pages + state."""
    prompt = list(map(int, np.random.default_rng(2).integers(0, 256, size=45)))
    gold = InferenceEngine(_engine_cfg(), seed=3).generate(
        [prompt], max_new_tokens=24)[0]
    eng = InferenceEngine(_engine_cfg(), seed=3)
    mgr = attach_sleep(eng)
    eng.add_request(prompt, max_new_tokens=24)
    req = eng._waiting[0]
    while len(req.out_tokens) < 9:
        eng.step()
    assert not req.done
    state_bytes = sum(
        x.nbytes for x in jax.tree.leaves((eng.params, eng.pool.as_tuple())))
    recurrent = eng.pool.state_nbytes()
    before = np.asarray(eng.pool.state)
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
        assert moved == state_bytes > recurrent > 0
    assert np.array_equal(np.asarray(eng.pool.state), before)
    assert eng.pool.state_nbytes() == recurrent
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
            ["--model", "tiny-olmo-hybrid", *flags])
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

    return {
        "prefix_cache": engine(prefix_caching=True),
        "prefix_caching_flag_on": prefix_on,
        "packed_mixed_step": engine(packed_serving=True),
        "speculative_ngram": engine(speculative_ngram=4),
        "zero_drain_park": park,
        "zero_drain_flag": zero_drain,
        "mixed_step_program": mixed,
        "live_migration": migrate,
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_what_knows_pages_alone_refuses_the_model_and_names_its_state(what):
    with pytest.raises((SlotStateUnsupported, NotImplementedError)) as err:
        _refusals()[what]()
    assert "OlmoHybridConfig" in str(err.value)
    assert "recurrent state" in str(err.value)


def test_one_refusal_names_rings_and_recurrent_state_alike():
    from llm_d_fast_model_actuation_tpu.engine.engine import refuse_slot_state
    from llm_d_fast_model_actuation_tpu.models.smallthinker import SmallThinkerConfig

    refuse_slot_state(llama.LlamaConfig.tiny(), "anything")
    with pytest.raises(SlotStateUnsupported, match="per-sequence rings"):
        refuse_slot_state(SmallThinkerConfig.tiny_smallthinker(), "a path")
    with pytest.raises(SlotStateUnsupported, match="6 linear-attention layers"):
        refuse_slot_state(_model(), "a path")


def test_prefix_caching_auto_is_off_for_recurrent_state():
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(["--model", "tiny-olmo-hybrid"])
    assert args.prefix_caching == "auto"
    svc = server.EngineService.__new__(server.EngineService)
    svc.args, svc._zero_drain = args, False
    assert not svc._engine_cfg_for(_model(), -1, ()).prefix_caching
    assert server.MODEL_CONFIGS["tiny-olmo-hybrid"]() == oh.OlmoHybridConfig.tiny_olmo_hybrid()
    assert server.MODEL_CONFIGS["olmo-hybrid-7b"]().num_layers == 32


def test_cost_oracle_counts_the_recurrent_state():
    """``_kv_pool_nbytes`` (a cold build's predicted bytes) is the pages of
    the full-attention layers plus the per-slot state, as the pool builds
    them."""
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(
        ["--model", "tiny-olmo-hybrid", "--max-batch", str(SLOTS),
         "--page-size", str(PAGE), "--num-pages", "128"])
    svc = server.EngineService.__new__(server.EngineService)
    svc.args = args
    eng = InferenceEngine(_engine_cfg(), seed=0)
    assert svc._kv_pool_nbytes(_model()) == eng.pool.nbytes()
    assert svc._kv_pool_nbytes(_model()) > 2 * eng.pool.k_pages.nbytes
    plain = llama.LlamaConfig.tiny()
    assert svc._kv_pool_nbytes(plain) == PagePool.estimate_nbytes(
        plain.cache_layers, 128, PAGE, plain.num_kv_heads, plain.head_dim)
