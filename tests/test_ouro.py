"""The looped family (``loop_steps`` on ``LlamaConfig``; Ouro) at the tiny
preset on the CPU: 3 layers applied 3 times a token over 9 cache layers,
multi-head attention (4 heads, 4 KV heads), sandwich norms, small pages,
Pallas in interpret mode. The plain reference is ``models/ouro.py``.

Tolerances. The served path computes in bfloat16 with float32 softmax and
norms; the reference is float32 throughout. At every position the two logit
rows differ by a share of the reference row's own root-mean-square
(``_row_errors``); ``LOGIT_ERR_TOL`` limits the MEDIAN of that share over the
positions. It lies between two readings taken here, at this preset, on two
seeds of weights (5, which the fixture uses, and 6): the bfloat16 path reads
a median of 0.0094 and 0.0120-0.0122 on the three attention implementations
(nine layer passes a token, but each adds what a sandwich norm has brought
back to unit size, so the rounding does not grow with the passes); the same
float32 reference with its matmul weights rounded to per-output-channel int8
(the nearest precision below bfloat16) reads 0.0315 and 0.0241. The limit
of 0.017 has 1.4x of room on both sides, and
``test_int8_weights_fail_the_tolerance`` holds the control outside it. Single
positions do not separate the two (the program's worst reads 0.022-0.039,
the control's 0.048-0.089), so ``LOGIT_ERR_WORST`` only keeps a position
from being off by a tenth of its row. The four breakages of the looped stack
read far outside: one pass fewer a median of 0.65-0.67, a cache layer ``l``
in place of ``u * L + l`` 0.77-0.79 over the positions that read the cache
(the cold prefill, which attends to the keys it has just computed, gives the
sound program's logits bit for bit), the final norm left out between passes 0.83-0.90.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool
from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama, moe, ouro
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for

LOGIT_ERR_TOL = 0.017
LOGIT_ERR_WORST = 0.1

PAGE, SEGMENT, SLOTS, PAGES = 4, 16, 3, 64
PROMPT, LENGTH = 40, 56  # a prefill, two suffix segments (16 + 8), 16 decode steps


def _model(impl="grouped", **kw):
    return dataclasses.replace(
        llama.LlamaConfig.tiny_ouro(), attention_impl=impl, **kw
    )


def _engine_cfg(**kw):
    return EngineConfig(
        model=llama.LlamaConfig.tiny_ouro(), max_batch=SLOTS, page_size=PAGE,
        num_pages=PAGES, max_prefill_tokens=SEGMENT, decode_chunk=4, **kw,
    )


def _row_errors(got, want):
    """Per position: RMS of the difference over RMS of the reference row."""
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return np.sqrt(((got - want) ** 2).mean(-1) / (want**2).mean(-1))


def _served_logits(cfg, params, tokens, prompt_len=PROMPT, slot=1):
    """Logits at every position of ``tokens`` by the serving programs: a cold
    prefill of the first segment, suffix segments for the rest of the prompt,
    then one decode step a token, through the paged cache."""
    pool = PagePool.create(
        cfg.cache_layers, PAGES, PAGE, cfg.num_kv_heads, cfg.head_dim
    )
    cache = pool.as_tuple()
    pps = cfg.max_seq_len // PAGE
    table = np.zeros((SLOTS, pps), np.int32)
    table[slot] = 1 + np.arange(pps)
    row = jnp.asarray(table[slot : slot + 1])
    toks = jnp.asarray(tokens, jnp.int32)
    logits, cache = llama.prefill(
        params, cfg, toks[None, :SEGMENT], jnp.asarray([SEGMENT]), cache, row
    )
    out = [logits[0]]
    pos = SEGMENT
    # one compile for the prompt's suffix segments, as the engine has (traced
    # in this call: a test may have patched what it traces)
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
    tokens = np.random.default_rng(1).integers(0, cfg.vocab_size, size=LENGTH)
    return params, tokens, ouro.reference_logits(params, cfg, jnp.asarray(tokens))


def _holds(got, want, rows=slice(None)):
    err = _row_errors(got, want)[rows]
    return bool(np.median(err) < LOGIT_ERR_TOL and err.max() < LOGIT_ERR_WORST)


@pytest.mark.parametrize("impl", ["reference", "grouped", "pallas"])
def test_logits_through_the_cache_match_the_reference(tiny, impl):
    """Prefill -> chunked suffix -> decode, every position, each pass of each
    layer through its own cache layer."""
    params, tokens, want = tiny
    got = _served_logits(_model(impl), params, tokens)
    assert got.shape == want.shape
    assert _holds(got, want), np.median(_row_errors(got, want))


def test_int8_weights_fail_the_tolerance(tiny):
    """The control of LOGIT_ERR_TOL: the float32 reference over weights
    rounded to per-output-channel int8 lies outside it."""
    params, tokens, want = tiny

    def rounded(w):
        wf = w.astype(jnp.float32)
        scale = jnp.max(jnp.abs(wf), axis=w.ndim - 2, keepdims=True) / 127.0
        return (jnp.round(wf / scale) * scale).astype(w.dtype)

    names = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")
    low = dict(params, lm_head=rounded(params["lm_head"]))
    low["layers"] = {
        k: rounded(v) if k in names else v for k, v in params["layers"].items()
    }
    got = ouro.reference_logits(low, _model(), jnp.asarray(tokens))
    assert np.median(_row_errors(got, want)) > 1.4 * LOGIT_ERR_TOL


def test_one_pass_fewer_fails_the_tolerance(tiny):
    """The program at ``loop_steps - 1`` passes over the same weights."""
    params, tokens, want = tiny
    got = _served_logits(_model(loop_steps=2), params, tokens)
    assert not _holds(got, want)
    # and the reference's own control agrees with that program
    two = ouro.reference_logits(params, _model(loop_steps=2), jnp.asarray(tokens))
    assert _holds(got, two)


def _broken_scan(cache_layer_of_pass, norm_between):
    """``llama._scan_layers`` for a looped config with one of two faults."""

    def scan_layers(cfg, layer_fn, carry, params):
        L = cfg.num_layers
        ys = []
        for u in range(cfg.loop_steps):
            layers = cache_layer_of_pass(u, L) + jnp.arange(L, dtype=jnp.int32)
            carry, y = jax.lax.scan(layer_fn, carry, (params["layers"], layers))
            ys.append(y)
            if norm_between or u == cfg.loop_steps - 1:
                carry = (llama._norm(cfg, carry[0], params["final_norm"]), *carry[1:])
        return carry, jax.tree.map(lambda *y: jnp.concatenate(y), *ys)

    return scan_layers


@pytest.mark.parametrize("impl", ["reference", "grouped"])
def test_a_cache_layer_of_l_fails_the_tolerance(tiny, impl, monkeypatch):
    """Pass u writing cache layer ``l`` (every pass over the first L cache
    layers) in place of ``u * L + l``: the cold prefill attends to the keys
    it has just computed and cannot tell, every position that reads the
    cache can."""
    params, tokens, want = tiny
    sound = _broken_scan(lambda u, L: u * L, norm_between=True)
    monkeypatch.setattr(llama, "_scan_layers", sound)
    whole = _served_logits(_model(impl), params, tokens)
    assert _holds(whole, want)
    monkeypatch.setattr(
        llama, "_scan_layers", _broken_scan(lambda u, L: 0, norm_between=True)
    )
    got = _served_logits(_model(impl), params, tokens)
    np.testing.assert_array_equal(got[:SEGMENT], whole[:SEGMENT])
    assert not _holds(got, want, slice(SEGMENT, None))


def test_no_final_norm_between_passes_fails_the_tolerance(tiny, monkeypatch):
    params, tokens, want = tiny
    monkeypatch.setattr(
        llama, "_scan_layers", _broken_scan(lambda u, L: u * L, norm_between=False)
    )
    assert not _holds(_served_logits(_model(), params, tokens), want)


def test_train_forward_is_the_looped_stack(tiny):
    from llm_d_fast_model_actuation_tpu.models.train import forward_train

    params, tokens, want = tiny
    got = forward_train(
        params, _model(), jnp.asarray(tokens)[None], jnp.asarray([LENGTH]),
        remat=False,
    )[0]
    assert _holds(got, want)


# -- the engine: every KV size from cache_layers, the features the family takes ----


def test_every_kv_size_counts_cache_layers():
    from llm_d_fast_model_actuation_tpu.engine import server

    cfg = llama.LlamaConfig.tiny_ouro()
    assert (cfg.num_layers, cfg.loop_steps, cfg.cache_layers) == (3, 3, 9)
    big = llama.LlamaConfig.ouro_2_6b()
    assert (big.num_layers, big.loop_steps, big.cache_layers) == (48, 4, 192)
    assert big.num_params() == 2_667_974_657
    # a 16-token page over 192 cache layers, K and V in bfloat16
    assert PagePool.page_nbytes(
        big.cache_layers, 16, big.num_kv_heads, big.head_dim) == 25_165_824
    eng = InferenceEngine(_engine_cfg(), seed=3)
    token = 9 * 2 * cfg.kv_dim * 2  # nine layers of K and V in bfloat16
    assert eng.kv_layout.global_layers == 9
    assert eng.pool.k_pages.shape == (9, PAGES, PAGE, cfg.kv_dim)
    assert eng.pool.nbytes() == PAGES * PAGE * token
    stats = eng.cache_stats()
    assert stats["kv"]["bytes_per_token"] == token
    assert stats["stack"] == {
        "num_layers": 3, "loop_steps": 3, "cache_layers": 9, "layer_passes": 0}
    # the AOT warm-up's avals are the live tree's and the live pool's
    from llm_d_fast_model_actuation_tpu.engine import exec_pool

    params, kv = exec_pool._abstract_state(eng.cfg)
    assert kv[0].shape == eng.pool.k_pages.shape
    assert jax.tree.structure(params) == jax.tree.structure(eng.params)
    # the cost oracle's pool bytes and the park's page bytes
    args = server.make_arg_parser().parse_args(
        ["--model", "tiny-ouro", "--num-pages", str(PAGES), "--page-size", str(PAGE)])
    svc = server.EngineService.__new__(server.EngineService)
    svc.args = args
    assert svc._kv_pool_nbytes(server.MODEL_CONFIGS["tiny-ouro"]()) == eng.pool.nbytes()
    # the parameter tree holds the exit gate, and it is counted
    gate = eng.params["early_exit_gate"]
    assert gate["w"].shape == (cfg.hidden_size, 1) and gate["b"].shape == (1,)
    assert sum(x.size for x in jax.tree.leaves(eng.params)) == cfg.num_params()


def test_layer_passes_counts_every_pass_of_every_dispatched_forward():
    from llm_d_fast_model_actuation_tpu.utils import tracing

    eng = InferenceEngine(_engine_cfg(prefix_caching=False), seed=3)
    before = tracing.phase_stats()["phase_n"]
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, size=n))) for n in (9, 40)]
    eng.generate(prompts, max_new_tokens=9)
    after = tracing.phase_stats()["phase_n"]
    chunks = after["chunk_dispatch"] - before["chunk_dispatch"]
    # 9 tokens fit one segment, 40 take three; the scheduler counts a phase a
    # request, the counter a forward
    forwards = 1 + 3 + eng.cfg.decode_chunk * chunks
    assert chunks >= 2
    assert eng.cache_stats()["stack"]["layer_passes"] == 9 * forwards


def test_prefix_cache_hit_gives_the_same_tokens():
    shared = list(range(1, 1 + 6 * PAGE))
    cold = InferenceEngine(_engine_cfg(prefix_caching=False), seed=3)
    warm = InferenceEngine(_engine_cfg(prefix_caching=True), seed=3)
    for tail in ([41, 42, 43], [51, 52]):
        want = cold.generate([shared + tail], max_new_tokens=12)[0]
        assert warm.generate([shared + tail], max_new_tokens=12)[0] == want
    assert warm.prefix_cache.hits == 1
    assert warm.prefix_cache.hit_tokens == 6 * PAGE


def test_sleep_and_wake_in_mid_decode_resume_token_for_token():
    """A level-1 sleep between two decode chunks: weights (the exit gate
    among them) and nine cache layers of pages move out and back."""
    from llm_d_fast_model_actuation_tpu.utils import tracing

    prompt = list(map(int, np.random.default_rng(2).integers(0, 256, size=30)))
    gold = InferenceEngine(_engine_cfg(), seed=3).generate(
        [prompt], max_new_tokens=20)[0]
    eng = InferenceEngine(_engine_cfg(), seed=3)
    mgr = attach_sleep(eng)
    eng.add_request(prompt, max_new_tokens=20)
    req = eng._waiting[0]
    while len(req.out_tokens) < 9:
        eng.step()
    assert not req.done
    state_bytes = sum(
        x.nbytes for x in jax.tree.leaves((eng.params, eng.pool.as_tuple())))
    tracing.enable()
    tracing.clear()
    try:
        mgr.sleep(1)
        assert eng.pool.k_pages is None
        mgr.wake_up()
        spans = tracing.snapshot()
    finally:
        tracing.disable()
    for name in ("sleep.d2h", "wake.h2d"):
        assert sum(s.attrs["bytes"] for s in spans if s.name == name) == state_bytes
    assert eng.pool.k_pages.shape[0] == 9
    while not req.done:
        eng.step()
    assert req.out_tokens == gold


@pytest.mark.parametrize(
    "feature", ["packed_serving", "speculative_ngram", "zero_drain_park"]
)
def test_nothing_refuses_the_family(feature):
    """Its state is one kind of page, so what carries Mistral carries it."""
    rng = np.random.default_rng(4)
    prompt = list(map(int, rng.integers(0, 256, size=21)))
    gold = InferenceEngine(_engine_cfg(), seed=3).generate(
        [prompt], max_new_tokens=10)[0]
    if feature == "zero_drain_park":
        eng = InferenceEngine(_engine_cfg(), seed=3)
        eng.add_request(prompt, max_new_tokens=10)
        req = eng._waiting[0]
        while len(req.out_tokens) < 4:
            eng.step()
        bundle, _ = eng.park_requests()
        assert bundle.k_host.shape[0] == 9
        eng.rebuild_kv_pool()
        eng.resume_parked(bundle)
        while eng.has_work():
            eng.step()
        assert req.out_tokens == gold
        return
    kw = {"packed_serving": True} if feature == "packed_serving" else {
        "speculative_ngram": 3}
    eng = InferenceEngine(_engine_cfg(**kw), seed=3)
    assert len(eng.generate([prompt], max_new_tokens=10)[0]) == 10


def test_an_exit_threshold_below_one_is_refused_by_name():
    with pytest.raises(ValueError, match="early_exit_threshold 0.9"):
        _model(early_exit_threshold=0.9)
    with pytest.raises(ValueError, match="loop_steps"):
        moe.MoeConfig.tiny_moe().__class__(loop_steps=2)
    assert _model(early_exit_threshold=1.0).loop_steps == 3


# -- loop_steps 1: what every other family traces is what it traced -------------------


def _scan_layers_before(layer_fn, x, params, cache):
    """``llama._scan_layers`` as it stood before the looped stack (PR 31),
    with the final norm its callers applied after it."""
    k_pages, v_pages = cache
    layers = jnp.arange(k_pages.shape[0], dtype=jnp.int32)
    (x, k_pages, v_pages), _ = jax.lax.scan(
        layer_fn, (x, k_pages, v_pages), (params["layers"], layers)
    )
    return x, (k_pages, v_pages)


def _before(cfg, layer_fn, carry, params):
    if len(carry) == 1:
        # the inline decode step carried the hidden state alone and stacked
        # each layer's new K and V
        def layer(x, scanned):
            (x,), ys = layer_fn((x,), scanned)
            return x, ys

        layers = jnp.arange(cfg.num_layers, dtype=jnp.int32)
        x, ys = jax.lax.scan(layer, carry[0], (params["layers"], layers))
        return (llama._norm(cfg, x, params["final_norm"]),), ys
    x, cache = _scan_layers_before(layer_fn, carry[0], params, carry[1:])
    return (llama._norm(cfg, x, params["final_norm"]), *cache), None


FAMILIES = {
    "llama": llama.LlamaConfig.tiny,
    "gemma": llama.LlamaConfig.tiny_gemma,
    "moe": moe.MoeConfig.tiny_moe,
}


@pytest.mark.parametrize("impl", ["reference", "grouped"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_loop_steps_one_is_bit_identical_to_the_scan_before(family, impl, monkeypatch):
    cfg = dataclasses.replace(FAMILIES[family](), attention_impl=impl)
    assert cfg.loop_steps == 1 and cfg.cache_layers == cfg.num_layers
    params = init_params_for(jax.random.key(2), cfg)
    assert "early_exit_gate" not in params
    tokens = np.random.default_rng(3).integers(0, cfg.vocab_size, size=LENGTH)
    now = _served_logits(cfg, params, tokens)
    monkeypatch.setattr(llama, "_scan_layers", _before)
    before = _served_logits(cfg, params, tokens)
    np.testing.assert_array_equal(np.asarray(now), np.asarray(before))
