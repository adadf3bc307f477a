"""The Nemotron-H family (models/nemotron_h.py) at a tiny preset on the CPU:
the published first 11 layers' pattern ``MEMEMEM*EME`` (5 Mamba-2, 5 expert
layers in a latent space with 4 held of 16 experts, 1 attention layer of 4
query heads over 2 KV heads without positional encoding), head and state
sizes that are no lane multiple, small pages, prompts that span several
prefill segments so that a segment resumes from its slot's recurrent state
and attends over its pages, Pallas in interpret mode.

Tolerances. (1) The program computed in float32 agrees with the plain
reference (``reference_logits``: float32, the recurrence token by token, the
experts densely) to ``F32_TOL`` of a logit row's own root-mean-square at every
position, through every segmentation and through decode (the issue's 1e-4:
readings 1e-6 to 1e-5). (2) In bfloat16 the MEDIAN over positions and seeds of
the same share is held under ``LOGIT_ERR_TOL``: the bfloat16 path reads
0.015-0.019 a seed here, and the same float32 reference with its matmul
weights rounded to per-output-channel int8 reads 0.039-0.057; the limit lies
between and ``test_int8_weights_fail_the_tolerance`` holds the second
reading above it.
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
from llm_d_fast_model_actuation_tpu.models import llama, moe
from llm_d_fast_model_actuation_tpu.models import nemotron_h as nh
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for
from llm_d_fast_model_actuation_tpu.utils import tracing

F32_TOL = 1e-4
LOGIT_ERR_TOL = 0.024
SEEDS = (5, 6, 7)

PAGE, SEGMENT, SLOTS = 4, 16, 3


def _model(impl="grouped", **kw):
    return dataclasses.replace(
        nh.NemotronHConfig.tiny_nemotron_h(), attention_impl=impl, **kw
    )


def _engine_cfg(**kw):
    kw.setdefault("prefix_caching", False)
    return EngineConfig(
        model=nh.NemotronHConfig.tiny_nemotron_h(), max_batch=SLOTS,
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
    )
    kp, vp, state, tail = pool.as_tuple()
    table = np.zeros((SLOTS, lay.table_width), np.int32)
    table[:, lay.pages_per_seq:] = lay.static_columns(SLOTS)
    table[1, : lay.pages_per_seq] = 1 + np.arange(lay.pages_per_seq)
    return (kp, vp, state + poison, tail + poison), table


def _served_logits(cfg, params, tokens, segments, slot=1, poison=7.0, spoil=None):
    """Logits at every position of ``tokens`` by the serving programs: the
    prompt in ``segments`` (the first through the cold program when it is
    the only one, else every one through the continue program as the
    engine's chunked prefill does), then one decode step a token, through
    pages and state. ``spoil(cache)`` -> cache is applied between the
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
            "reference": lambda params, tokens: nh.reference_logits(
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
    "sixty_four_rows_four_chunks": (60,),
}


@pytest.mark.parametrize("how", sorted(SEGMENTATIONS))
def test_program_is_the_reference_through_segments_state_and_decode(tiny32, how):
    """Prefill whole or in segments that resume from the slot's state and
    attend over its pages, then decode: every position's logits are the plain
    reference's, in float32 to rounding, whatever the slot held before."""
    cfg, params, tokens, ref = tiny32
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(cfg, params, tokens, SEGMENTATIONS[how])
    assert _row_errors(got, ref).max() < F32_TOL


def test_pallas_kernels_serve_two_query_heads_a_kv_head(tiny32):
    """The same through the Pallas prefill and paged decode kernels
    (interpreted): 4 query heads over 2 KV heads, no positional encoding."""
    cfg, params, tokens, ref = tiny32
    cfg = dataclasses.replace(cfg, attention_impl="pallas")
    with jax.default_matmul_precision("highest"):
        # a cold prompt through the prefill kernel, then decode steps
        got, _ = _served_logits(cfg, params, tokens, (40,))
    assert _row_errors(got, ref).max() < F32_TOL


SPOILED = {
    "state": lambda c: (c[0], c[1], c[2] * 0.0, c[3]),
    "conv_tail": lambda c: (c[0], c[1], c[2], c[3] * 0.0),
    "k_page": lambda c: (c[0].at[:, 1:3].set(0.0), c[1], c[2], c[3]),
}


@pytest.mark.parametrize("what", sorted(SPOILED))
def test_what_is_not_carried_across_a_segment_comes_out_not_correct(tiny32, what):
    """A recurrent state, a convolution tail or a page of K lost between two
    segments: the positions after it are far from the reference."""
    cfg, params, tokens, ref = tiny32
    with jax.default_matmul_precision("highest"):
        got, _ = _served_logits(
            cfg, params, tokens, (16, 16, 13), spoil=SPOILED[what])
    err = _row_errors(got, ref)
    assert err[:16].max() < F32_TOL and err[16:].max() > 100 * F32_TOL


@jax.jit
def _sequential(x, dt, g, B, C, h):
    """The recurrence a token at a time, as the module docstring writes it
    (``ssd_step`` is tested against the same)."""
    H, G = x.shape[2], B.shape[2]

    def token(h, t):
        x, dt, g, B, C = t
        B, C = (jnp.repeat(a, H // G, axis=1) for a in (B, C))  # [b, H, N]
        h = jnp.exp(g)[..., None, None] * h + (
            (dt[..., None] * x)[..., None] * B[:, :, None, :]
        )
        return h, jnp.einsum("bhpn,bhn->bhp", h, C)

    rows = jax.tree.map(lambda a: jnp.moveaxis(a, 1, 0), (x, dt, g, B, C))
    h, y = jax.lax.scan(token, h, rows)
    return jnp.moveaxis(y, 0, 1), h


STEPS = {
    # dt a head: log-uniform over the published init's range; every row near
    # time_step_floor (the state barely moves: decay 1 - 1e-4 A); every row
    # at time_step_max under the largest A (16: a chunk of 128 rows decays
    # by exp(-205)); rows of dt = 0 (padding) among others
    "init_range": lambda rng, s: np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), s)),
    "near_the_floor": lambda rng, s: np.full(s, 1e-4) * rng.uniform(1, 1.5, s),
    "near_the_max": lambda rng, s: np.full(s, 0.1) * rng.uniform(0.9, 1, s),
    "padded_rows_among_others": lambda rng, s: _padded(rng.uniform(0.01, 0.1, s)),
}


def _padded(dt):
    dt[:, ::3] = 0.0
    return dt


@pytest.mark.parametrize("rows,chunk", [(128, 128), (96, 32)])
@pytest.mark.parametrize("steps", sorted(STEPS))
def test_chunkwise_ssd_is_token_by_token(steps, rows, chunk):
    """``ssd_chunk_scan`` over one chunk or several from a non-zero state
    gives the outputs and the final state of the recurrence stepped a token
    at a time, and so does ``ssd_step``, with dt near ``time_step_floor`` and
    near ``time_step_max`` across a chunk: nothing under- or overflows."""
    rng = np.random.default_rng(rows)
    b, H, P, G, N = 2, 6, 12, 2, 24
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, B, C, h0 = f(b, rows, H, P), f(b, rows, G, N), f(b, rows, G, N), f(b, H, P, N)
    dt = jnp.asarray(STEPS[steps](rng, (b, rows, H)), jnp.float32)
    g = -jnp.asarray(np.linspace(1.0, 16.0, H), jnp.float32) * dt
    want_y, want_h = _sequential(x, dt, g, B, C, h0)
    got_y, got_h = nh.ssd_chunk_scan(x, dt, g, B, C, h0, chunk)
    assert np.isfinite(np.asarray(got_y)).all()
    np.testing.assert_allclose(got_y, want_y, atol=5e-4, rtol=5e-4)
    np.testing.assert_allclose(got_h, want_h, atol=5e-4, rtol=5e-4)
    h, ys = h0, []
    for t in range(4):
        y, h = nh.ssd_step(x[:, t], dt[:, t], g[:, t], B[:, t], C[:, t], h)
        ys.append(y)
    np.testing.assert_allclose(jnp.stack(ys, 1), want_y[:, :4], atol=1e-4, rtol=1e-4)
    if steps == "padded_rows_among_others":
        # a row with dt = 0 leaves the state as it was
        _, h1 = nh.ssd_step(x[:, 0], dt[:, 0], g[:, 0], B[:, 0], C[:, 0], h0)
        assert np.array_equal(np.asarray(h1), np.asarray(h0))


# -- the share of the experts ---------------------------------------------------------


def _expert_layer(seed=0):
    """An uncut config (16 of 16 held), its parameters, and rows to route."""
    cfg = _model(dtype=jnp.float32, num_experts=16)
    params = init_params_for(jax.random.key(seed), cfg)
    u = jnp.asarray(
        np.random.default_rng(seed).standard_normal((2, 9, cfg.hidden_size)),
        jnp.float32)
    return cfg, params, u


def test_the_four_shares_and_the_shared_expert_once_are_the_uncut_layer():
    """THE share test: what the four shares of 4 experts give for a layer,
    each its routed part through ``W_up`` plus the shared expert, add up to
    the uncut layer as the plain reference's mathematics computes it once
    the shared expert (which every chip computes alike) is counted ONCE."""
    cfg, params, u = _expert_layer()
    e = 2
    ep = {k: a[e] for k, a in params["experts"].items()}
    relu2 = lambda a: jnp.square(jax.nn.relu(a))  # noqa: E731
    with jax.default_matmul_precision("highest"):
        w, idx = moe.route(cfg, u @ ep["router"], ep["router_bias"])
        weights = jnp.sum(jax.nn.one_hot(idx, 16) * w[..., None], axis=-2)
        v = u @ ep["latent_down"]
        a = relu2(jnp.einsum("...l,elf->...ef", v, ep["w_up"]))
        y = jnp.einsum("...ef,efl->...el", a, ep["w_down"])
        routed = jnp.einsum("...el,...e->...l", y, weights) @ ep["latent_up"]
        shared = relu2(u @ ep["s_up"]) @ ep["s_down"]
        uncut = routed + shared
        assert float(jnp.abs(routed).max()) > 0.1
        np.testing.assert_allclose(nh._experts(cfg, params, e, u), uncut, atol=2e-5)
        parts = []
        for share in range(4):
            held = dataclasses.replace(
                cfg, num_experts=4, router_width=16, share_index=share)
            mine = {**params, "experts": {
                k: a[:, 4 * share : 4 * share + 4]
                if k in moe.expert_stacks(cfg) else a
                for k, a in params["experts"].items()
            }}
            parts.append(nh._experts(held, mine, e, u))
            # a share alone is not the layer
            assert float(jnp.abs(parts[-1] - uncut).max()) > 0.05
        np.testing.assert_allclose(sum(parts) - 3 * shared, uncut, atol=2e-5)
        # the decode batch's form, every held expert on every row, is the
        # same layer: uncut, and share by share
        small = dict(dense_max_rows=18)
        assert moe.takes_grouped(cfg, 18, None) and not moe.takes_grouped(
            dataclasses.replace(cfg, **small), 18, None)
        np.testing.assert_allclose(
            nh._experts(dataclasses.replace(cfg, **small), params, e, u), uncut,
            atol=2e-5)
        held = dataclasses.replace(
            cfg, num_experts=4, router_width=16, share_index=2, **small)
        mine = {**params, "experts": {
            k: a[:, 8:12] if k in moe.expert_stacks(cfg) else a
            for k, a in params["experts"].items()
        }}
        np.testing.assert_allclose(
            nh._experts(held, mine, e, u), parts[2], atol=2e-5)


def test_router_reads_the_input_and_the_experts_the_latent_space():
    """The router's 22-of-512 rule at tiny size: sigmoid scores over the whole
    width, weights renormalised over the kept and scaled by 5; the experts
    are two matrices in the latent width."""
    cfg, params, u = _expert_layer(1)
    ep = {k: a[0] for k, a in params["experts"].items()}
    w, idx = moe.route(cfg, u @ ep["router"], ep["router_bias"])
    assert idx.shape == w.shape == (2, 9, 3)
    np.testing.assert_allclose(w.sum(-1), 5.0, rtol=1e-5)
    assert moe.expert_stacks(cfg) == ("w_up", "w_down") and not cfg.expert_gated
    assert ep["w_up"].shape == (16, 24, 40) and ep["w_down"].shape == (16, 40, 24)
    assert cfg.expert_input_size == cfg.latent_size == 24
    assert "w_gate" not in params["experts"]
    assert moe.stored_expert_stack(cfg, params) is params["experts"]["w_down"]


def _gated_outputs():
    """The routed layer of the three gated families on fixed rows."""
    from llm_d_fast_model_actuation_tpu.models import kimi_linear as kl
    from llm_d_fast_model_actuation_tpu.models.smallthinker import SmallThinkerConfig

    out = {}
    for name, cfg in (
        ("mixtral", dataclasses.replace(moe.MoeConfig.tiny_moe(), dtype=jnp.float32)),
        ("smallthinker", dataclasses.replace(
            SmallThinkerConfig.tiny_smallthinker(), dtype=jnp.float32)),
        ("kimi", dataclasses.replace(
            kl.KimiLinearConfig.tiny_kimi_linear(), dtype=jnp.float32)),
    ):
        h, f, E, W = (
            cfg.hidden_size, cfg.intermediate_size, cfg.num_experts,
            cfg.router_outputs,
        )
        rng = np.random.default_rng(3)
        g = lambda *s: jnp.asarray(rng.standard_normal(s) * s[-2] ** -0.5, jnp.float32)  # noqa: E731
        lp = {
            "router": g(h, W), "w_gate": g(E, h, f), "w_up": g(E, h, f),
            "w_down": g(E, f, h),
        }
        x = jnp.asarray(rng.standard_normal((7, h)), jnp.float32)
        out[name] = (cfg, lp, x)
    return out


@pytest.mark.parametrize("family", ["kimi", "mixtral", "smallthinker"])
def test_gated_expert_families_outputs_are_unchanged(family):
    """``routed_ffn``'s one path with the gate: act(x w_gate) * (x w_up) into
    w_down for each token's kept experts that are held, weighted, as written
    out here expert by expert."""
    cfg, lp, x = _gated_outputs()[family]
    assert cfg.expert_gated and moe.expert_stacks(cfg) == moe.EXPERT_STACKS
    with jax.default_matmul_precision("highest"):
        got = moe.routed_ffn(cfg, lp, x)
        w, idx = moe.route(cfg, x @ lp["router"])
        weights = jnp.sum(
            jax.nn.one_hot(idx, cfg.router_outputs) * w[..., None], axis=-2)
        lo = cfg.share_index * cfg.num_experts
        act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[cfg.expert_activation]
        want = sum(
            weights[:, lo + e, None]
            * ((act(x @ lp["w_gate"][e]) * (x @ lp["w_up"][e])) @ lp["w_down"][e])
            for e in range(cfg.num_experts)
        )
    np.testing.assert_allclose(got, want, atol=2e-5)


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


def test_layout_has_one_layer_of_pages_and_the_state_beside_them():
    cfg = _model()
    lay = _layout(cfg)
    assert (lay.global_layers, lay.window_layers, lay.state_layers) == (1, 0, 5)
    assert (lay.latent_counted, lay.latent_width) == (0, 0)
    assert lay.state_shape == (8, 12, 24) and lay.tail_shape == (3, 96 + 2 * 48)
    assert lay.table_width == lay.pages_per_seq + 1
    pool = PagePool.create(
        lay.global_layers, 8, PAGE, cfg.num_kv_heads, cfg.head_dim,
        state_shapes=lay.state_shapes(SLOTS))
    assert pool.kinds == ("state",) and len(pool.as_tuple()) == 4
    assert pool.k_pages.shape == pool.v_pages.shape == (1, 8, PAGE, 32)
    assert pool.state.shape == (5, SLOTS, 8, 12, 24) and pool.state.dtype == jnp.float32
    assert pool.conv_tail.shape == (5, SLOTS, 3, 192)
    # the published sizes: the 128-wide state axis is the minor one
    big = nh.NemotronHConfig.nemotron_3_super_120b_a12b()
    assert big.recurrent_state == (5, (128, 64, 128), (3, 10240))
    assert big.cache_layers == 1 and big.kv_dim == 256
    from llm_d_fast_model_actuation_tpu.engine.kv_cache import recurrent_nbytes

    assert recurrent_nbytes(big.recurrent_state, 1, 2) == 5 * 4_255_744 == 21_278_720


def test_config_counts_its_parameters_and_its_kinds():
    cfg = _model()
    params = init_params_for(jax.random.key(0), cfg)
    assert sum(x.size for x in jax.tree.leaves(params)) == cfg.num_params()
    assert (cfg.mamba_layers, cfg.expert_layers, cfg.cache_layers) == (5, 5, 1)
    assert cfg.layer_kind_counts == {"ssm": 5, "moe": 5, "attn": 1}
    assert params["mamba"]["w_in"].shape == (5, 64, 96 + 192 + 8)
    assert params["mamba"]["conv_bias"].shape == (5, 192)
    assert params["experts"]["w_up"].shape == (5, 4, 24, 40)
    assert params["experts"]["router"].shape == (5, 64, 16)
    assert params["attn"]["wk"].shape == (1, 64, 32)
    assert params["layers"]["norm"].shape == (11, 64)
    for name in ("a_log", "dt_bias", "d"):
        assert params["mamba"][name].dtype == jnp.float32
    assert float(jnp.abs(params["mamba"]["conv_bias"]).max()) > 0
    dt = jax.nn.softplus(params["mamba"]["dt_bias"][0])
    np.testing.assert_allclose([dt.min(), dt.max()], [1e-3, 1e-1], rtol=1e-4)
    big = nh.NemotronHConfig.nemotron_3_super_120b_a12b()
    assert big.num_params() == 4_648_163_712
    assert big.layer_kinds == tuple("MEMEMEM*EME") and big.in_proj_size == 18_560
    assert big.router_outputs == 512 and big.num_experts == 128
    for bad in (dict(layer_kinds=tuple("MEMEMEM-EME")), dict(num_layers=12),
                dict(quantization="int8"), dict(loop_steps=2),
                dict(ssm_groups=3), dict(share_index=4)):
        with pytest.raises(ValueError):
            _model(**bad)
    # the config names its forward; which state a family keeps does not
    assert llama.patterned(cfg) is nh
    assert llama.patterned(llama.LlamaConfig.tiny()) is None


# -- the engine: pages and state, recycled slots, sleep, refusals ---------------------


def test_engine_serves_through_pages_and_state_and_recycles_slots():
    eng = InferenceEngine(_engine_cfg(), seed=3)
    lay = eng.kv_layout
    assert (lay.global_layers, lay.state_layers, lay.latent_width) == (1, 5, 0)
    assert eng.pool.k_pages.shape == (1, 128, PAGE, 32)
    assert eng.pool.state.shape == (5, SLOTS, 8, 12, 24)
    assert eng._page_table[:, -1].tolist() == [0, 1, 2]
    rng = np.random.default_rng(0)
    prompts = [list(map(int, rng.integers(0, 256, size=n))) for n in (50, 9, 37, 20, 33)]
    batched = eng.generate(prompts, max_new_tokens=20)
    stats = eng.cache_stats()
    tokens = sum(len(p) + 19 for p in prompts)
    assert stats["state"] == {
        "layers": 5, "bytes_per_slot": 5 * (8 * 12 * 24 * 4 + 3 * 192 * 2),
        "bytes": eng.pool.state_nbytes(), "token_updates": tokens * 5,
        "first_segments": 5, "resumed_segments": 3 + 2 + 1 + 2,
    }
    assert stats["stack"]["layer_kinds"] == {"ssm": 5, "moe": 5, "attn": 1}
    assert stats["stack"]["cache_layers"] == 1
    assert stats["kv"]["bytes_per_token"] == 2 * 32 * 2
    assert stats["kv"]["global_pages_in_use"] == 0
    # a decode batch of 3 rows takes the dense form over the held experts
    # (dense_max_rows 4); every prompt token went through a grouped matmul
    assert stats["moe"] == {
        "experts": 4, "experts_held": 4, "router_width": 16, "latent_size": 24,
        "tokens": tokens, "routed_tokens": sum(len(p) for p in prompts),
        "assignments": tokens * 5 * 3,
    }
    # (a family of mixer + FFN blocks reports neither new key: the accepted
    # families' tests compare their `moe` and `stack` blocks whole)
    # five requests through three slots: the last two were served from
    # recycled slots beside other sequences, and each reads what the engine
    # serves it alone, from a slot that held another sequence's state
    for i in (3, 4):
        assert eng.generate([prompts[i]], max_new_tokens=20)[0] == batched[i]


def test_sleep_and_wake_in_mid_decode_carry_pages_and_state():
    """A level-1 sleep between two decode chunks and a wake resume token for
    token; the pages and the state come back bit for bit, and the spans'
    bytes are those of weights + pages + state."""
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
    assert len(before) == 4 and all(np.abs(a).max() > 0 for a in before)
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
            ["--model", "tiny-nemotron-h", *flags])
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
    assert "NemotronHConfig" in str(err.value)
    if what not in ("weight_quantization", "mixed_step_program"):
        assert "5 state-space layers whose recurrent state" in str(err.value)


def test_prefix_caching_auto_is_off_and_the_names_resolve():
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(["--model", "tiny-nemotron-h"])
    assert args.prefix_caching == "auto"
    svc = server.EngineService.__new__(server.EngineService)
    svc.args, svc._zero_drain = args, False
    assert not svc._engine_cfg_for(_model(), -1, ()).prefix_caching
    assert server.MODEL_CONFIGS["tiny-nemotron-h"]() == nh.NemotronHConfig.tiny_nemotron_h()
    assert server.MODEL_CONFIGS["nemotron-3-super-120b-a12b"]().num_layers == 11


def test_cost_oracle_counts_pages_and_state():
    """``_kv_pool_nbytes`` (a cold build's predicted bytes) is one layer of K
    and V pages plus the per-slot state, as the pool builds them."""
    from llm_d_fast_model_actuation_tpu.engine import server

    args = server.make_arg_parser().parse_args(
        ["--model", "tiny-nemotron-h", "--max-batch", str(SLOTS),
         "--page-size", str(PAGE), "--num-pages", "128"])
    svc = server.EngineService.__new__(server.EngineService)
    svc.args = args
    eng = InferenceEngine(_engine_cfg(), seed=0)
    assert svc._kv_pool_nbytes(_model()) == eng.pool.nbytes()
    assert eng.pool.nbytes() == 2 * 128 * PAGE * 32 * 2 + eng.pool.state_nbytes()
