"""K-EXAONE (``models/exaone_moe.py``): periods of window and full layers over
rings and pages with norms on the sub-layers' outputs, a leading dense layer,
a share of sigmoid-routed experts beside a shared expert, and a
multi-token-prediction module that drafts inside the decode chunk
(``--speculative-mtp 1``).

On the CPU, at a tiny size, seeded weights, float32:

  (a) prefill, chunked suffix segments and decode through pages and rings
      against ``reference_logits``' one full forward, the main path alone
      (one position a slot) and through the two-position verify step;
  (b) the module's draft logits, from the prompt programs' segment and from
      the decode step, against the reference's module forward;
  (c) at a vocabulary of 12, where chance accepts some drafts, the served
      tokens with the option on are those with it off;
  (d) the shares' routed parts plus the shared expert once add up to the
      uncut layer;
  (e) a level-1 sleep and wake in mid-request resumes token for token;
  (f) what cannot carry the family's state, or the drafts, refuses by name;
and the two-position attention itself, kernel and XLA twin, against the
one-position form applied twice.
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
from llm_d_fast_model_actuation_tpu.models import exaone_moe as xm
from llm_d_fast_model_actuation_tpu.models import llama, moe
from llm_d_fast_model_actuation_tpu.models.registry import init_params_for
from llm_d_fast_model_actuation_tpu.ops import attention

PAGE, SEGMENT, SLOTS = 4, 16, 3
#: float32 through another order of additions
TOL = 5e-5


def _model(impl="grouped", mtp=True, **kw):
    return dataclasses.replace(
        xm.ExaoneMoeConfig.tiny_exaone_moe(), dtype=jnp.float32,
        attention_impl=impl, serve_mtp=mtp, **kw,
    )


def _engine_cfg(vocab=256, mtp=1, **kw):
    kw.setdefault("prefix_caching", False)
    kw.setdefault("max_batch", 4)
    model = dataclasses.replace(
        xm.ExaoneMoeConfig.tiny_exaone_moe(vocab), dtype=jnp.float32
    )
    return EngineConfig(
        model=model, page_size=PAGE, num_pages=400,
        max_prefill_tokens=2 * SEGMENT, decode_chunk=4, speculative_mtp=mtp,
        **kw,
    )


@pytest.fixture(scope="module")
def tiny():
    cfg = _model()
    params = init_params_for(jax.random.key(5), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(1).integers(0, cfg.vocab_size, size=100)
    )
    logits, drafts = xm.reference_logits(params, cfg, tokens, mtp=True)
    return params, tokens, logits, drafts


def _state(cfg):
    """An empty sequence state for SLOTS slots and slot 1's table row."""
    lay = KVLayout.plan(
        cfg.cache_layers, *llama.window_layers(cfg), PAGE, cfg.max_seq_len,
        SEGMENT,
    )
    pool = PagePool.create(
        lay.global_layers, 1 + lay.pages_per_seq, PAGE, cfg.num_kv_heads,
        cfg.head_dim, dtype=cfg.dtype,
        ring_shape=lay.ring_shape(SLOTS, PAGE, cfg.num_kv_heads, cfg.head_dim),
    )
    table = np.zeros((SLOTS, lay.table_width), np.int32)
    table[:, lay.pages_per_seq:] = lay.ring_columns(SLOTS)
    table[1, : lay.pages_per_seq] = 1 + np.arange(lay.pages_per_seq)
    return lay, pool.as_tuple(), jnp.asarray(table)


def _prompt(cfg, params, tokens, n, cache, table):
    """The prompt's first ``n`` tokens through the prompt programs' parts, a
    cold segment and then suffix segments, the module after each -> (logits
    [n, vocab], the module's logits after each segment's last position, the
    cache)."""
    row = table[1:2]
    out, drafts, pos = [], [], 0
    while pos < n:
        m = min(SEGMENT, n - pos)
        seg = jnp.zeros((1, SEGMENT), jnp.int32).at[0, :m].set(tokens[pos:pos + m])
        nxt = jnp.zeros((1, SEGMENT), jnp.int32).at[0, :m].set(
            tokens[pos + 1:pos + m + 1])
        lens, start = jnp.asarray([m]), jnp.asarray([pos])
        if pos == 0:
            x, cache = xm.prefill(params, cfg, seg, lens, cache, row, hidden=True)
        else:
            x, cache = xm.prefill_continue(
                params, cfg, seg, start, lens, cache, row, hidden=True)
        out.append(llama.lm_logits(cfg, params, x)[0, :m])
        if cfg.serve_mtp:
            d, cache = xm.draft_segment(
                params, cfg, x, nxt, start, lens, cache, row, cold=pos == 0)
            drafts.append(d[0])
        pos += m
    return jnp.concatenate(out), drafts, cache


@pytest.mark.parametrize("impl", ["grouped", "pallas"])
def test_main_path_through_pages_and_rings_is_the_reference(tiny, impl):
    """(a) with the option off: a 70-token prompt in five segments, then a
    decode step a token to position 100; the rings (24 + 16 positions) wrap
    and the two full layers read their pages. The module's weights are held
    and nothing of it runs: its K and V have no layer of the pages."""
    params, tokens, want, _ = tiny
    cfg = _model(impl, mtp=False)
    lay, cache, table = _state(cfg)
    assert (lay.global_layers, lay.window_layers) == (2, 6)
    assert lay.ring_pages * PAGE == 24 + SEGMENT < len(tokens)
    got, _, cache = _prompt(cfg, params, tokens, 70, cache, table)
    assert float(jnp.abs(got - want[:70]).max()) < TOL
    active = jnp.arange(SLOTS) == 1
    step = jax.jit(lambda t, p, c: llama.decode_step(
        params, cfg, t, p, c, table, active))
    for pos in range(70, 100):
        t = jnp.zeros((SLOTS,), jnp.int32).at[1].set(tokens[pos])
        logits, cache = step(t, jnp.zeros((SLOTS,), jnp.int32).at[1].set(pos), cache)
        assert float(jnp.abs(logits[1] - want[pos]).max()) < TOL, pos
    with pytest.raises(ValueError, match="not served"):
        xm.draft_step(params, cfg, None, None, None, cache, table, None, None)


@pytest.mark.parametrize("impl", ["grouped", "pallas"])
def test_verify_and_draft_steps_are_the_reference(tiny, impl):
    """(a) with the option on, and (b): the prompt programs' parts write the
    module's pages and give its draft after each segment; then verify steps
    of two positions a slot, first with the second position rejected (its K
    and V are not written, the step moves by one and the next overwrites
    nothing it needs), then accepted (the step moves by two); every logit of
    both positions and every draft against the reference's one forward."""
    params, tokens, want, want_drafts = tiny
    cfg = _model(impl)
    lay, cache, table = _state(cfg)
    assert lay.global_layers == 3  # the module's K and V: a layer of the pages
    got, drafts, cache = _prompt(cfg, params, tokens, 64, cache, table)
    assert float(jnp.abs(got - want[:64]).max()) < TOL
    for i, d in enumerate(drafts):
        # after the segment's last position p: from h_p and token p + 1
        assert float(jnp.abs(d - want_drafts[SEGMENT * (i + 1) - 1]).max()) < TOL
    slot = jnp.arange(SLOTS) == 1

    @jax.jit
    def step(pos, accept, cache):
        at = jnp.zeros((SLOTS,), jnp.int32).at[1].set(pos)
        toks = jnp.zeros((SLOTS, 2), jnp.int32).at[1].set(
            jax.lax.dynamic_slice_in_dim(tokens, pos, 2))
        nxt = jnp.zeros((SLOTS, 2), jnp.int32).at[1].set(
            jax.lax.dynamic_slice_in_dim(tokens, pos + 1, 2))
        write = jnp.stack([slot, slot & accept], axis=1)
        logits, hidden, cache = xm.verify_step(
            params, cfg, toks, at, cache, table, write)
        draft, cache = xm.draft_step(
            params, cfg, hidden, nxt, at, cache, table, write,
            jnp.where(accept, 1, 0) * jnp.ones((SLOTS,), jnp.int32))
        return logits[1], draft[1], cache

    pos = 64
    while pos < 97:
        accept = pos >= 76
        logits, draft, cache = step(pos, accept, cache)
        assert float(jnp.abs(logits - want[pos:pos + 2]).max()) < TOL, pos
        assert float(jnp.abs(draft - want_drafts[pos + accept]).max()) < TOL, pos
        pos += 1 + accept


@pytest.mark.parametrize(
    "window,n", [(0, 2), (6, 2), (6, 3)], ids=["full-2", "window6-2", "window6-3"])
def test_several_positions_a_slot_are_one_position_several_times(window, n):
    """``paged_decode_attention_inline`` with n query positions a slot, the
    kernel (n * group query rows a KV head, the pages walked once) and its
    XLA twin, against the one-position form with each new row written
    before the next: at position 0, inside the first tile, and past a ring's
    wrap (48 slots, position 70)."""
    attention.set_pallas_interpret(True)
    b, h, kvh, d, ps, pps, L = 3, 8, 2, 16, 4, 12, 2
    ks = jax.random.split(jax.random.key(0), 5)
    pages = 1 + b * pps
    kp = jax.random.normal(ks[0], (L, pages, ps, kvh * d), jnp.float32)
    vp = jax.random.normal(ks[1], (L, pages, ps, kvh * d), jnp.float32)
    q = jax.random.normal(ks[2], (b, n, h, d), jnp.float32)
    kn = jax.random.normal(ks[3], (b, n, kvh, d), jnp.float32)
    vn = jax.random.normal(ks[4], (b, n, kvh, d), jnp.float32)
    table = jnp.arange(1, pages, dtype=jnp.int32).reshape(b, pps)
    pos = jnp.array([0, 7, 70 if window else 45 - n], jnp.int32)
    one, k2, v2 = [], kp, vp
    for i in range(n):
        one.append(attention.paged_decode_attention_inline(
            q[:, i], k2, v2, kn[:, i], vn[:, i], table, pos + i, 1,
            impl="grouped", window=window))
        at = (pos + i) % (pps * ps) if window else pos + i
        phys = table[jnp.arange(b), at // ps]
        k2 = k2.at[1, phys, at % ps].set(kn[:, i].reshape(b, -1))
        v2 = v2.at[1, phys, at % ps].set(vn[:, i].reshape(b, -1))
    want = jnp.stack(one, axis=1)
    for impl in ("grouped", "pallas"):
        got = attention.paged_decode_attention_inline(
            q, kp, vp, kn, vn, table, pos, 1, impl=impl, window=window)
        assert got.shape == want.shape
        assert float(jnp.abs(got - want).max()) < 1e-5, impl


def test_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """(d) guide section 4: the four shares' routed parts (4 of 16 experts
    each) plus the shared expert, which every chip computes alike, counted
    ONCE, are the uncut layer, in the grouped form and in the dense one
    over the held experts."""
    cfg = _model()
    f32 = jnp.float32
    h, f, W = cfg.hidden_size, cfg.intermediate_size, cfg.router_outputs
    ks = jax.random.split(jax.random.key(7), 9)
    dense = lambda k, shape, fan: jax.random.normal(k, shape, f32) * fan**-0.5  # noqa: E731
    whole = {
        "router": dense(ks[0], (h, W), h),
        "router_bias": 0.01 * jax.random.normal(ks[1], (W,), f32),
        "w_gate": dense(ks[2], (W, h, f), h), "w_up": dense(ks[3], (W, h, f), h),
        "w_down": dense(ks[4], (W, f, h), f),
    }
    shared = {"s_gate": dense(ks[5], (h, f), h), "s_up": dense(ks[6], (h, f), h),
              "s_down": dense(ks[7], (f, h), f)}
    x = jax.random.normal(ks[8], (24, h), f32)
    logits = x @ whole["router"]
    uncut_cfg = dataclasses.replace(cfg, num_experts=W, router_width=0)
    with jax.default_matmul_precision("highest"):
        uncut = moe.routed_ffn(uncut_cfg, whole, x, logits)
        parts = {"grouped": 0.0, "dense": 0.0}
        E = cfg.num_experts
        for share in range(W // E):
            c = dataclasses.replace(cfg, share_index=share)
            lp = {k: v[share * E:(share + 1) * E] if k.startswith("w_") else v
                  for k, v in whole.items()}
            parts["grouped"] += moe.routed_ffn(c, lp, x, logits)
            parts["dense"] += moe.held_dense_ffn(c, lp, x, logits)
        once = llama._mlp(cfg, x, shared["s_gate"], shared["s_up"], shared["s_down"])
    for form, routed in parts.items():
        assert float(jnp.abs((routed + once) - (uncut + once)).max()) < TOL, form
    # and the layer as the model computes it for share 1: that share's part
    params = {"experts": {
        **{k: v[None] for k, v in shared.items()},
        "router": whole["router"][None], "router_bias": whole["router_bias"][None],
        **{k: whole[k][None, E:2 * E] for k in moe.EXPERT_STACKS}}}
    one = dataclasses.replace(cfg, share_index=1, dense_max_rows=0)
    from llm_d_fast_model_actuation_tpu.models import kimi_linear

    with jax.default_matmul_precision("highest"):
        got = kimi_linear._ffn(one, params, 1, x)  # layer 1: expert layer 0
        lp = {k: v[E:2 * E] if k.startswith("w_") else v for k, v in whole.items()}
        want = once + moe.routed_ffn(
            dataclasses.replace(cfg, share_index=1), lp, x, logits)
    assert float(jnp.abs(got - want).max()) < TOL


# -- the engine: the chunk that drafts ------------------------------------------------


def _serve(cfg, requests, seed=4):
    """Serve ``requests`` to the end -> (the engine, the finished requests in
    order, and for each the indices of its output that were ACCEPTED drafts,
    read off the drained chunks: how many of a step's two tokens count)."""
    eng = InferenceEngine(cfg, seed=seed)
    seconds, emit = {}, eng._emit_chunk

    def spy(toks, lps, avs, ais, skeys, running, defer, *drafted):
        at = {r.seq_id: len(r.out_tokens) for r in running.values()}
        reqs = dict(running)
        out = emit(toks, lps, avs, ais, skeys, running, defer, *drafted)
        for slot, r in reqs.items() if drafted else ():
            i = at[r.seq_id]
            for n in drafted[0][:, slot]:
                if n == 2:
                    seconds.setdefault(r.seq_id, []).append(i + 1)
                i += int(n)
        return out

    eng._emit_chunk = spy
    ids = [eng.add_request(prompt, **kw) for prompt, kw in requests]
    done = {}
    while eng.has_work():
        for r in eng.step():
            done[r.seq_id] = r
    return eng, [done[i] for i in ids], [seconds.get(i, []) for i in ids]


def _requests(vocab):
    rng = np.random.default_rng(0)
    prompt = lambda n: rng.integers(0, vocab, n).tolist()  # noqa: E731
    greedy = lambda n, out: (prompt(n), dict(max_new_tokens=out, ignore_eos=True))  # noqa: E731
    return [
        greedy(10, 24), greedy(50, 18), greedy(70, 32), greedy(33, 1),
        greedy(20, 2), greedy(90, 12), greedy(12, 12), greedy(26, 12),
        (prompt(17), dict(max_new_tokens=12, temperature=0.8, seed=7,
                          ignore_eos=True)),
        (prompt(28), dict(max_new_tokens=12, presence_penalty=0.5,
                          ignore_eos=True)),
    ]


@pytest.fixture(scope="module")
def served():
    """Ten requests of unequal lengths through four slots at a vocabulary
    of 12, with the option off and on: greedy ones, one that samples, one
    with a penalty, budgets of 1 and 2."""
    return {mtp: _serve(_engine_cfg(12, mtp), _requests(12)) for mtp in (0, 1)}


def test_tokens_with_the_option_on_are_those_with_it_off(served):
    """(c) token for token, for every request, whether its drafts were
    accepted or not; some were and some were not; a slot that samples or
    carries a penalty took every step's first token through the sampler and
    never accepted."""
    (_, off, _), (eng, on, seconds) = served[0], served[1]
    for a, b in zip(off, on):
        assert a.out_tokens == b.out_tokens
        assert a.finish_reason == b.finish_reason == "length"
        assert np.allclose(a.out_logprobs, b.out_logprobs, atol=1e-4)
    stats = eng.cache_stats()["mtp"]
    assert stats["on"] == 1 and stats["accepted"] > 0
    assert stats["drafted"] - stats["accepted"] > 0
    assert stats["emitted"] == stats["drafted"] + stats["accepted"]
    assert stats["accepted"] == sum(map(len, seconds))
    assert seconds[-2] == seconds[-1] == []  # the sampler's and the penalty's
    # every token but a request's first came out of a verify step
    assert stats["emitted"] == sum(len(r.out_tokens) - 1 for r in on)
    assert stats["steps"] * 4 >= stats["drafted"]  # four slots at most a step
    assert served[0][0].cache_stats()["mtp"] == {
        "on": 0, "steps": 0, "drafted": 0, "accepted": 0, "emitted": 0}
    assert [len(r.out_tokens) for r in on] == [24, 18, 32, 1, 2, 12, 12, 12, 12, 12]


def test_eos_inside_a_run_ends_it_there(served):
    """(c) a step whose ACCEPTED second token is eos ends the slot there, on
    the device (the slot's budget) and on the host (the run): a request is
    picked whose accepted draft is the first occurrence of its value, and
    everything is served again with that value as eos, option off and on;
    the other requests ignore it."""
    _, on, seconds = served[1]
    which, j = next(
        (i, j) for i, js in enumerate(seconds) for j in js
        if on[i].out_tokens[j] not in on[i].out_tokens[:j]
    )
    eos = on[which].out_tokens[j]
    requests = _requests(12)
    requests[which] = (requests[which][0], dict(
        max_new_tokens=requests[which][1]["max_new_tokens"]))
    (_, off, _), (eng, again, _) = (
        _serve(_engine_cfg(12, mtp, eos_token_id=eos), requests) for mtp in (0, 1)
    )
    assert [r.out_tokens for r in again] == [r.out_tokens for r in off]
    assert again[which].finish_reason == "stop"
    assert again[which].out_tokens == on[which].out_tokens[:j + 1]
    # nothing past the eos was emitted or counted, on the device or the host
    assert eng.cache_stats()["mtp"]["emitted"] == sum(
        len(r.out_tokens) - 1 for r in again)


def test_sleep_and_wake_in_mid_request_resume_token_for_token():
    """(e) a level-1 sleep between two chunks and a wake: the slot's draft is
    carried where its last token is (the host's mirror), the module's pages
    with the others."""
    prompt = list(map(int, np.random.default_rng(2).integers(0, 12, size=45)))
    _, (gold,), _ = _serve(
        _engine_cfg(12, 1), [(prompt, dict(max_new_tokens=40))], seed=3)
    eng = InferenceEngine(_engine_cfg(12, 1), seed=3)
    mgr = attach_sleep(eng)
    eng.add_request(prompt, max_new_tokens=40)
    req = eng._waiting[0]
    while len(req.out_tokens) < 9:
        eng.step()
    assert not req.done and eng._drafts[req.slot] == eng._dev["draft"][req.slot]
    mgr.sleep(1)
    assert eng.pool.k_pages is None and eng._dev is None
    mgr.wake_up()
    while not req.done:
        eng.step()
    assert req.out_tokens == gold.out_tokens
    assert eng.cache_stats()["mtp"]["accepted"] > 0


def _refusals():
    def engine(**kw):
        return lambda: InferenceEngine(_engine_cfg(**kw), seed=0)

    def park():
        InferenceEngine(_engine_cfg(), seed=0).park_requests()

    def no_module():
        EngineConfig(model=llama.LlamaConfig.tiny(), speculative_mtp=1)

    def packed_flag():
        from llm_d_fast_model_actuation_tpu.engine import server

        server.validate_parsed_args(server.make_arg_parser().parse_args(
            ["--model", "tiny-exaone-moe", "--speculative-mtp", "1",
             "--packed-serving", "on"]))

    def gang():
        from llm_d_fast_model_actuation_tpu.engine.multihost import LockstepLeader

        LockstepLeader(InferenceEngine(_engine_cfg(), seed=0))

    return {
        "prefix_cache": (engine(prefix_caching=True), "ExaoneMoeConfig"),
        "packed_mixed_step": (engine(mtp=0, packed_serving=True), "ExaoneMoeConfig"),
        "speculative_ngram": (engine(speculative_ngram=4), "ExaoneMoeConfig"),
        "zero_drain_park": (park, "ExaoneMoeConfig"),
        "mtp_with_packed_serving": (
            lambda: _engine_cfg(packed_serving=True), "--packed-serving"),
        "mtp_with_packed_serving_flag": (packed_flag, "--packed-serving"),
        "mtp_without_a_module": (no_module, "LlamaConfig has no"),
        "mtp_on_a_gang": (gang, "multi-host gangs"),
        "mtp_of_two": (lambda: _engine_cfg(mtp=2), "0 or 1"),
    }


@pytest.mark.parametrize("what", sorted(_refusals()))
def test_refusals_by_name(what):
    """(f)"""
    run, names = _refusals()[what]
    with pytest.raises((SlotStateUnsupported, ValueError)) as err:
        run()
    assert names in str(err.value)


def test_the_option_is_the_models_config_and_off_by_default():
    """The engine's option makes the model serve its module (its K and V a
    layer of the pages, in every size that follows from the model); with it
    off the chunk takes no draft and the layout has the main stack's layers
    alone."""
    off, on = _engine_cfg(mtp=0), _engine_cfg(mtp=1)
    assert not off.model.serve_mtp and on.model.serve_mtp
    assert (off.kv_layout.global_layers, on.kv_layout.global_layers) == (2, 3)
    assert off.model.num_params() == on.model.num_params()
    from llm_d_fast_model_actuation_tpu.engine import exec_pool, server

    assert len(exec_pool.abstract_args(on, "chunk", 4)) == 1 + len(
        exec_pool.abstract_args(off, "chunk", 4))
    assert exec_pool.exec_signature(on) != exec_pool.exec_signature(off)
    assert server.make_arg_parser().parse_args(["--model", "tiny"]).speculative_mtp == 0
    model = server.MODEL_CONFIGS["k-exaone-236b-a23b"]()
    assert model.num_params() == 4_394_720_512 and model.nextn_layers == 1
    assert llama.window_layers(model) == (6, 128)
