"""Engine: continuous batching, page accounting, sleep/wake."""

import time

import jax
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import (
    EngineConfig,
    InferenceEngine,
    PageAllocator,
)
from llm_d_fast_model_actuation_tpu.engine.kv_cache import OutOfPages
from llm_d_fast_model_actuation_tpu.engine.sleep import SleepLevel, attach_sleep
from llm_d_fast_model_actuation_tpu.models import llama


@pytest.fixture(scope="module")
def engine():
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=4,
        page_size=8,
        num_pages=64,
        max_seq_len=64,
    )
    return InferenceEngine(cfg, seed=0)


def test_allocator():
    a = PageAllocator(8)
    assert a.available == 7  # page 0 reserved
    pages = a.alloc(3)
    assert len(set(pages)) == 3 and 0 not in pages
    a.free(pages)
    assert a.available == 7
    with pytest.raises(OutOfPages):
        a.alloc(8)
    assert PageAllocator.pages_needed(17, 8) == 3


def test_single_generate(engine):
    out = engine.generate([[1, 2, 3, 4, 5]], max_new_tokens=6)
    assert len(out) == 1 and len(out[0]) == 6
    assert all(0 <= t < engine.cfg.model.vocab_size for t in out[0])
    # engine fully drained: all pages returned
    assert engine.allocator.available == engine.cfg.num_pages - 1


def test_greedy_deterministic(engine):
    a = engine.generate([[7, 8, 9]], max_new_tokens=5)[0]
    b = engine.generate([[7, 8, 9]], max_new_tokens=5)[0]
    assert a == b


def test_batch_matches_single(engine):
    """Continuous batching must not change greedy results."""
    prompts = [[1, 2, 3], [10, 20, 30, 40], [100, 101]]
    batched = engine.generate(prompts, max_new_tokens=4)
    singles = [engine.generate([p], max_new_tokens=4)[0] for p in prompts]
    assert batched == singles


def test_oversubscription_queues(engine):
    """More requests than slots: all complete eventually."""
    prompts = [[i + 1, i + 2] for i in range(9)]  # 9 requests, 4 slots
    outs = engine.generate(prompts, max_new_tokens=3)
    assert len(outs) == 9
    assert all(len(o) == 3 for o in outs)
    assert engine.allocator.available == engine.cfg.num_pages - 1


def test_request_validation(engine):
    with pytest.raises(ValueError):
        engine.add_request([], 4)
    with pytest.raises(ValueError):
        engine.add_request([1] * 60, 10)  # exceeds max_seq_len=64


def test_sleep_wake_preserves_generation():
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
    )
    eng = InferenceEngine(cfg, seed=0)
    before = eng.generate([[4, 5, 6]], max_new_tokens=4)[0]

    mgr = attach_sleep(eng)
    assert not mgr.is_sleeping
    info = mgr.sleep(1)
    assert mgr.is_sleeping and info["is_sleeping"]
    assert info["level"] == SleepLevel.L1_HOST_OFFLOAD
    assert info["bytes_offloaded"] > 0
    assert eng.params is None  # HBM actually released

    mgr.wake_up()
    assert not mgr.is_sleeping
    after = eng.generate([[4, 5, 6]], max_new_tokens=4)[0]
    assert before == after


def test_sleep_wake_midstream_resumes():
    """Level-1 sleep in the middle of a generation, wake, and the sequence
    continues bit-exact (KV pages survived the round trip)."""
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
    )
    eng = InferenceEngine(cfg, seed=0)
    gold = eng.generate([[9, 8, 7]], max_new_tokens=24)[0]

    eng2 = InferenceEngine(cfg, seed=0)
    eng2.add_request([9, 8, 7], max_new_tokens=24)
    for _ in range(2):  # prefill + a few decode chunks; still mid-generation
        eng2.step()
    assert eng2.has_work(), "request must still be in flight before sleep"
    mgr = attach_sleep(eng2)
    mgr.sleep(1)
    mgr.wake_up()
    outs = []
    while eng2.has_work():
        outs.extend(eng2.step())
    assert outs[0].out_tokens == gold


def test_level2_discard_and_reinit():
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8, num_pages=16
    )
    eng = InferenceEngine(cfg, seed=0)
    mgr = attach_sleep(eng)
    mgr.sleep(2)
    assert mgr.is_sleeping and mgr.stats.bytes_offloaded == 0
    with pytest.raises(ValueError):
        mgr.wake_up()  # level-2 needs reinit

    def reinit():
        params = llama.init_params(jax.random.key(0), cfg.model)
        from llm_d_fast_model_actuation_tpu.engine.kv_cache import PagePool

        pool = PagePool.create(
            cfg.model.num_layers,
            cfg.num_pages,
            cfg.page_size,
            cfg.model.num_kv_heads,
            cfg.model.head_dim,
            dtype=cfg.model.dtype,
        )
        return {"params": params, "kv": pool.as_tuple()}

    mgr.wake_up(reinit=reinit)
    out = eng.generate([[1, 2]], max_new_tokens=3)[0]
    assert len(out) == 3


def test_abort_waiting_and_inflight():
    """abort(seq_id) (client disconnect): waiting requests drop before
    admission; in-flight ones retire and their pages return to the pool."""
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(),
        max_batch=1,  # slot pressure: second request stays waiting
        page_size=8,
        num_pages=32,
        max_seq_len=64,
    )
    eng = InferenceEngine(cfg, seed=0)
    a = eng.add_request([1, 2, 3], max_new_tokens=30)
    b = eng.add_request([4, 5, 6], max_new_tokens=30)
    eng.step()  # admits a (prefill + chunk); b waits
    assert eng._waiting and eng._waiting[0].seq_id == b

    assert eng.abort(b, "client gone") is True
    assert not eng._waiting

    assert eng.abort(a, "client gone") is True
    assert all(s is None for s in eng._slots)
    assert eng.allocator.available == cfg.num_pages - 1, "pages all returned"
    assert eng.abort(999) is False
    assert not eng.has_work()


def test_service_abort_frees_slot():
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    svc = EngineService(
        parse_engine_options(
            "--model tiny --num-pages 32 --max-batch 2 --page-size 8 "
            "--max-model-len 64 --sleep-release-devices never"
        )
    )
    try:
        fut = svc.submit(list(range(1, 9)), 40, 0.0)
        svc.abort(fut)
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            if fut.done() and not svc.engine.has_work():
                break
            time.sleep(0.05)
        assert fut.cancelled() or fut.done()
        assert not svc.engine.has_work(), "aborted request must not keep decoding"
        assert (
            svc.engine.allocator.available == svc.engine.cfg.num_pages - 1
        )
        # the engine still serves new work afterwards
        out = svc.submit([1, 2, 3], 4, 0.0).result(timeout=60)
        assert len(out.out_tokens) == 4
    finally:
        svc.shutdown()


# ---------------------------------------------------- streaming stop holdback


def test_allocator_version_counts_mutations():
    a = PageAllocator(8)
    v0 = a.version
    pages = a.alloc(2)
    assert a.version > v0
    v1 = a.version
    a.free([])  # no-op: nothing moved
    assert a.version == v1
    a.free(pages)
    assert a.version > v1


def _stream_req(engine, stop_seqs, max_new_tokens=16):
    from llm_d_fast_model_actuation_tpu.engine.engine import Request

    seen = []
    req = Request(
        seq_id=0,
        prompt=[1],
        max_new_tokens=max_new_tokens,
        stop_seqs=tuple(tuple(s) for s in stop_seqs),
        on_tokens=lambda r, run: seen.append((run, r.done)),
    )
    return req, seen


def test_stream_holds_back_stop_prefix_until_disambiguated(engine):
    """A token that could start a multi-token stop sequence is not streamed
    until the next token rules the match out — then both flush."""
    req, seen = _stream_req(engine, [(5, 6)])
    engine._emit(req, 1)
    assert seen == [([1], False)]
    engine._emit(req, 5)  # possible start of (5, 6): held back
    assert seen == [([1], False)]
    engine._emit(req, 7)  # disambiguated: 5 then 7 stream in one delivery
    assert seen == [([1], False), ([5, 7], False)]
    assert req.out_tokens == [1, 5, 7] and not req.done


def test_stream_never_emits_stripped_stop_content(engine):
    req, seen = _stream_req(engine, [(5, 6)])
    for t in (1, 5, 6):
        engine._emit(req, t)
    assert req.done and req.finish_reason == "stop"
    assert req.out_tokens == [1]
    # the held-back 5 and the matching 6 were stripped, never streamed
    assert seen == [([1], False)]


def test_stream_flushes_survivors_on_other_stop_match(engine):
    """A held-back prefix of stop A that survives because stop B matched
    instead is flushed, carrying the done flag on the final token only."""
    req, seen = _stream_req(engine, [(5, 6), (7,)])
    engine._emit(req, 5)  # held: possible start of (5, 6)
    assert seen == []
    engine._emit(req, 7)  # stop (7,) matches; 5 survives into the output
    assert req.done and req.out_tokens == [5]
    assert seen == [([5], True)]


def test_stream_holdback_overlapping_prefix(engine):
    req, seen = _stream_req(engine, [(5, 5, 6)])
    for t in (5, 5, 5, 6):
        engine._emit(req, t)
    assert req.done and req.finish_reason == "stop"
    assert req.out_tokens == [5]
    assert [run for run, _ in seen] == [[5]]


def test_stream_flushes_held_tokens_on_eos_and_length(engine):
    eos = engine.cfg.eos_token_id
    req, seen = _stream_req(engine, [(5, 6)])
    for t in (1, 5, eos):
        engine._emit(req, t)
    assert req.done and req.out_tokens == [1, 5, eos]
    # the held 5 flushes with the eos, `done` on that one delivery
    assert seen == [([1], False), ([5, eos], True)]

    req, seen = _stream_req(engine, [(5, 6)], max_new_tokens=2)
    engine._emit(req, 1)
    engine._emit(req, 5)  # budget exhausted: held 5 flushes with done
    assert req.done and req.finish_reason == "length"
    assert seen == [([1], False), ([5], True)]


# ------------------------------------------------------- per-request seeds


def _drain(engine):
    done = []
    while engine.has_work():
        done.extend(engine.step())
    return done


def test_seeded_request_independent_of_batch_position():
    """OpenAI/vLLM `seed`: a seeded sampled request's output depends only
    on (seed, model, prompt, knobs) — not on which slot it lands in, who
    it shares the batch with, or the engine's own RNG seed (which also
    drives unseeded requests' streams)."""
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=4, page_size=8,
        num_pages=64, max_seq_len=64, eos_token_id=-1,
    )
    # one set of weights for every engine below: the engine seed must
    # only affect RNG streams, and the MODEL must be fixed to compare
    params = llama.init_params(jax.random.key(0), cfg.model)
    prompt = [5, 6, 7]

    # run 1: the seeded request alone
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request(prompt, max_new_tokens=8, temperature=0.9, seed=123)
    (alone,) = _drain(eng)

    # run 2: same seeded request surrounded by unseeded neighbors that
    # admit FIRST (different slot) — on a different ENGINE seed too
    eng = InferenceEngine(cfg, params=params, seed=7)
    eng.add_request([9, 9], max_new_tokens=12, temperature=0.8)
    eng.add_request([8, 8, 8], max_new_tokens=3, temperature=0.7)
    eng.add_request(prompt, max_new_tokens=8, temperature=0.9, seed=123)
    done = _drain(eng)
    crowded = next(r for r in done if r.seed == 123)

    assert crowded.out_tokens == alone.out_tokens

    # a different seed gives a different draw (overwhelmingly likely
    # for 8 tokens over a 256 vocab at temp 0.9)
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request(prompt, max_new_tokens=8, temperature=0.9, seed=124)
    (other,) = _drain(eng)
    assert other.out_tokens != alone.out_tokens


def test_unseeded_requests_still_vary_and_greedy_unaffected():
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8,
        num_pages=64, max_seq_len=64, eos_token_id=-1,
    )
    eng = InferenceEngine(cfg, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=8, temperature=0.9)
    eng.add_request([5, 6, 7], max_new_tokens=8, temperature=0.9)
    a, b = _drain(eng)
    # two unseeded identical requests draw from distinct streams
    assert a.out_tokens != b.out_tokens

    # greedy output is seed-independent
    eng = InferenceEngine(cfg, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=5, temperature=0.0, seed=1)
    eng.add_request([5, 6, 7], max_new_tokens=5, temperature=0.0, seed=2)
    a, b = _drain(eng)
    assert a.out_tokens == b.out_tokens


def test_ignore_eos_decodes_full_budget():
    """vLLM `ignore_eos`: the request decodes its whole budget even when
    the model emits eos — both the host finish check AND the device-side
    budget zeroing must stand down for that slot."""
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8,
        num_pages=64, max_seq_len=64, eos_token_id=-1,
    )
    params = llama.init_params(jax.random.key(0), cfg.model)
    # find the greedy stream, then make its SECOND token the eos id so a
    # normal request stops early and an ignore_eos one continues
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=8)
    done = []
    while eng.has_work():
        done.extend(eng.step())
    stream = done[0].out_tokens
    eos = stream[1]

    import dataclasses
    cfg2 = dataclasses.replace(cfg, eos_token_id=eos)
    eng = InferenceEngine(cfg2, params=params, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=8)
    eng.add_request([5, 6, 7], max_new_tokens=8, ignore_eos=True)
    done = []
    while eng.has_work():
        done.extend(eng.step())
    normal = next(r for r in done if not r.ignore_eos)
    ignored = next(r for r in done if r.ignore_eos)
    assert normal.finish_reason == "stop"
    assert len(normal.out_tokens) < 8
    assert len(ignored.out_tokens) == 8
    assert ignored.finish_reason == "length"
    assert eos in ignored.out_tokens  # the eos token itself is kept


def test_logit_bias_forces_and_bans_tokens():
    """OpenAI logit_bias: +100 pins greedy decoding to a token; -100
    effectively bans one (shifting greedy to the next-best)."""
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8,
        num_pages=64, max_seq_len=64, eos_token_id=-1,
    )
    params = llama.init_params(jax.random.key(0), cfg.model)
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=4)
    (plain,) = _drain(eng)

    # +100 on an arbitrary token: greedy emits it everywhere
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=4, logit_bias={17: 100.0})
    (forced,) = _drain(eng)
    assert forced.out_tokens == [17, 17, 17, 17]
    # the reported logprob reflects the BIASED distribution
    assert forced.out_logprobs[0] > -1e-3

    # -100 on the plain run's first token: it disappears from the output
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request(
        [5, 6, 7], max_new_tokens=4, logit_bias={plain.out_tokens[0]: -100.0}
    )
    (banned,) = _drain(eng)
    assert plain.out_tokens[0] not in banned.out_tokens

    # an unbiased neighbor in the same batch is unaffected
    eng = InferenceEngine(cfg, params=params, seed=0)
    eng.add_request([5, 6, 7], max_new_tokens=4, logit_bias={17: 100.0})
    eng.add_request([5, 6, 7], max_new_tokens=4)
    done = _drain(eng)
    neighbor = next(r for r in done if not r.logit_bias)
    assert neighbor.out_tokens == plain.out_tokens

    import pytest as _p
    with _p.raises(ValueError, match="outside vocab"):
        eng.add_request([1], max_new_tokens=1, logit_bias={9999: 1.0})
    with _p.raises(ValueError, match="outside"):
        eng.add_request([1], max_new_tokens=1, logit_bias={1: 200.0})


def test_decode_chunk_length_invariant():
    """Chunk length is a pure scheduling knob: T=32 must produce the same
    greedy tokens as T=4 (the bench serves chunk 32 on TPU)."""
    from llm_d_fast_model_actuation_tpu.models import llama

    prompt = [5, 6, 7, 8, 9]

    def run(chunk):
        cfg = EngineConfig(
            model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8,
            num_pages=32, max_seq_len=64, decode_chunk=chunk,
        )
        eng = InferenceEngine(cfg, seed=0)
        return eng.generate([prompt], max_new_tokens=40)[0]

    assert run(4) == run(32)


def _pipeline_pair(**cfg_kw):
    """Two engines differing only in pipeline_decode."""
    from llm_d_fast_model_actuation_tpu.models import llama

    def mk(pipeline):
        cfg = EngineConfig(
            model=llama.LlamaConfig.tiny(), max_batch=4, page_size=8,
            num_pages=64, max_seq_len=64, decode_chunk=4,
            pipeline_decode=pipeline, **cfg_kw,
        )
        return InferenceEngine(cfg, seed=0)

    return mk(False), mk(True)


def test_pipeline_decode_matches_sequential():
    """pipeline_decode is a pure scheduling change: identical outputs for
    a multi-request batch, including SEEDED requests admitted while a
    chunk is genuinely in flight (the drain must not rewind a key that
    prefill wrote after the chunk's dispatch)."""
    seq, pipe = _pipeline_pair()
    prompts = [[5, 6, 7], [9, 8], [1, 2, 3, 4], [11]]

    def run(eng):
        for p in prompts[:2]:
            eng.add_request(p, max_new_tokens=20)
        done = []
        done.extend(eng.step())  # prefill (+ pipelined: dispatch, no drain)
        done.extend(eng.step())
        if eng.cfg.pipeline_decode:
            assert eng._inflight is not None  # admission really interleaves
        # second wave admitted mid-run: seeded sampling, so outputs are
        # batch-composition-independent and must match across modes
        for p in prompts[2:]:
            eng.add_request(p, max_new_tokens=9, temperature=0.8, seed=7)
        while eng.has_work():
            done.extend(eng.step())
        return sorted(tuple(r.out_tokens) for r in done)

    assert run(seq) == run(pipe)


def test_pipeline_decode_stop_sequences_and_sleep():
    """Host-side finishes (stop sequences) defer retire safely, and a
    sleep mid-stream drains the in-flight chunk (no lost tokens)."""
    from llm_d_fast_model_actuation_tpu.engine.sleep import attach_sleep

    seq, pipe = _pipeline_pair()
    gold = seq.generate([[3, 1, 4]], max_new_tokens=30)[0]
    assert len(gold) == 30
    # stop on a sequence that actually occurs in the greedy output
    stop = tuple(gold[4:6])

    def run_with_stop(eng):
        eng.add_request([3, 1, 4], max_new_tokens=30, stop_seqs=(stop,))
        done = []
        while eng.has_work():
            done.extend(eng.step())
        return done[0].out_tokens, done[0].finish_reason

    assert run_with_stop(seq) == run_with_stop(pipe)

    # sleep with a chunk dispatched-but-unread: drain preserves tokens
    mgr = attach_sleep(pipe)
    pipe.add_request([3, 1, 4], max_new_tokens=12)
    pipe.step()  # dispatches (pipeline: no drain yet)
    mgr.sleep(1)
    assert pipe._inflight is None
    mgr.wake_up()
    done = []
    while pipe.has_work():
        done.extend(pipe.step())
    assert done and done[0].out_tokens == gold[:12]


def test_pipeline_decode_no_wasted_tail_dispatch():
    """End-of-batch tail: when every running request can finish inside the
    in-flight chunk, no speculative chunk k+1 is dispatched (it would be
    fully frozen — pure wasted device work). Pins the dispatch count AND
    output identity with the sequential engine."""
    seq, pipe = _pipeline_pair()  # decode_chunk=4
    dispatches = []
    orig = pipe._dispatch_chunk

    def counting_dispatch(running):
        dispatches.append(sorted(running))
        return orig(running)

    pipe._dispatch_chunk = counting_dispatch
    prompt = [5, 6, 7]
    # 5 tokens total: 1 from prefill + 4 decoded = exactly one T=4 chunk;
    # the old code dispatched a second, fully-frozen chunk at the tail
    gold = seq.generate([prompt], max_new_tokens=5)[0]
    out = pipe.generate([prompt], max_new_tokens=5)[0]
    assert out == gold
    assert len(dispatches) == 1, dispatches

    # longer run: budget 9 -> prefill + chunk(4) + chunk(4) and nothing
    # after the second chunk's drain
    dispatches.clear()
    out = pipe.generate([prompt], max_new_tokens=9)[0]
    assert out == seq.generate([prompt], max_new_tokens=9)[0]
    assert len(dispatches) == 2, dispatches


def test_pipeline_decode_abort_mid_flight():
    """Aborting while a chunk is in flight defers the retire; pages are
    not recycled until the chunk drains, and the allocator balances."""
    _, pipe = _pipeline_pair()
    free0 = pipe.allocator.available
    sid = pipe.add_request([5, 6, 7], max_new_tokens=40)
    pipe.step()  # prefill + dispatch
    assert pipe._inflight is not None
    assert pipe.abort(sid)
    assert pipe._pending_retire  # deferred, not freed mid-flight
    while pipe.has_work():
        pipe.step()
    assert pipe._pending_retire == []
    # every page returned (prefix cache may hold some as cache-only)
    if pipe.prefix_cache is not None:
        pipe.allocator.free(pipe.prefix_cache.clear())
    assert pipe.allocator.available == free0


def test_drain_tail_chunk_matches_single():
    """drain_tail='chunk' runs the full chunk program for the tail with
    surplus steps frozen in-program — outputs identical to T=1 tails,
    for mixed budgets (tails of different lengths per slot)."""
    from llm_d_fast_model_actuation_tpu.models import llama

    def run(tail):
        cfg = EngineConfig(
            model=llama.LlamaConfig.tiny(), max_batch=3, page_size=8,
            num_pages=48, max_seq_len=64, decode_chunk=8, drain_tail=tail,
        )
        eng = InferenceEngine(cfg, seed=0)
        # budgets 5/11/14: every request ends inside a tail, at different
        # offsets; one sampled+seeded to cover RNG-stream identity
        eng.add_request([5, 6, 7], max_new_tokens=5)
        eng.add_request([9, 8], max_new_tokens=11, temperature=0.9, seed=3)
        eng.add_request([1, 2, 3], max_new_tokens=14)
        done = []
        while eng.has_work():
            done.extend(eng.step())
        return sorted(tuple(r.out_tokens) for r in done)

    assert run("single") == run("chunk")


# ------------------------------------------- a drained chunk, run by run

EOS = 7


@pytest.fixture(scope="module")
def emit_engine():
    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=4, page_size=8,
        num_pages=64, max_seq_len=64, eos_token_id=EOS,
    )
    return InferenceEngine(cfg, seed=0)


def _holdback(out, stop_seqs):
    """Longest suffix of `out` that is a proper prefix of a stop sequence."""
    return max(
        (
            k
            for seq in stop_seqs
            for k in range(1, min(len(seq) - 1, len(out)) + 1)
            if out[-k:] == list(seq[:k])
        ),
        default=0,
    )


def _per_token_reference(specs, chunks, aborts):
    """What a drained chunk means, written out token by token: walk the
    chunk step by step and slot by slot; a token is appended, counted, may
    complete a stop sequence (strip it, finish), may be eos, may meet a
    stop asked for at an earlier token, may use up the budget; whatever the
    stop-sequence hold-back releases is then streamed, and the text-stop
    watch (here: "the k-th streamed token") asks for a stop as it sees
    it. Returns a state per request and the order they finished in."""
    # the state of one request: `streamed` is the tokens its hook was given
    state = [
        dict(
            out=[], lps=[], tops=[], done=False, reason="", pos=s["pos"],
            streamed=[], stop_requested=False, counts={}, aborted=False,
        )
        for s in specs
    ]
    order = []
    for ci, (toks, lps, ais, avs) in enumerate(chunks):
        for slot in aborts.get(ci, ()):
            state[slot]["done"] = state[slot]["aborted"] = True
        at_chunk_start = [len(st["streamed"]) for st in state]
        for t in range(len(toks)):
            for slot, (s, st) in enumerate(zip(specs, state)):
                if st["done"]:
                    continue
                tok = int(toks[t][slot])
                st["pos"] += 1
                st["out"].append(tok)
                st["lps"].append(float(lps[t][slot]))
                st["tops"].append(
                    [
                        (int(i), float(v))
                        for i, v in zip(ais[t][slot], avs[t][slot])
                    ]
                    if s.get("want_top")
                    else []
                )
                st["counts"][tok] = st["counts"].get(tok, 0) + 1
                for seq in s.get("stop_seqs", ()):
                    if st["out"][-len(seq):] == list(seq):
                        for key in ("out", "lps", "tops"):
                            del st[key][-len(seq):]
                        st["done"], st["reason"] = True, "stop"
                        break
                if not st["done"]:
                    if st["stop_requested"] or (
                        tok == EOS and not s.get("ignore_eos")
                    ):
                        st["done"], st["reason"] = True, "stop"
                    elif len(st["out"]) >= s["max_new"]:
                        st["done"], st["reason"] = True, "length"
                safe = len(st["out"])
                if not st["done"]:
                    safe -= _holdback(st["out"], s.get("stop_seqs", ()))
                for tok in st["out"][len(st["streamed"]):safe]:
                    st["streamed"].append(tok)
                    if len(st["streamed"]) == s.get("watch_stops_at"):
                        st["stop_requested"] = True
                if st["done"]:
                    order.append(slot)
                    # a stop sequence that strips all that was held back
                    # over a chunk's end leaves nothing to stream with
                    # the finish
                    st["finish_streamed"] = safe > at_chunk_start[slot]
    return state, order


def _chunk(rows, topk):
    """[T][slots] token rows -> (toks, lps, ais, avs) as a chunk's fetch
    gives them; logprobs and alternatives are made from the token."""
    toks = np.array(rows, dtype=np.int32)
    lps = -(toks.astype(np.float32) + 0.5) / 8
    ais = (toks[:, :, None] + np.arange(topk, dtype=np.int32)) % 97
    avs = lps[:, :, None] - np.arange(topk, dtype=np.float32)
    return toks, lps, ais, avs


EMIT_CHUNK_CASES = {
    # slot 0 has 5 tokens of budget and stops at the chunk's fifth step;
    # slot 1 takes all eight
    "budget_ends_mid_chunk": dict(
        specs=[dict(max_new=5), dict(max_new=30)],
        chunks=[[[20 + t, 40 + t] for t in range(8)]],
    ),
    "eos_mid_chunk": dict(
        specs=[dict(max_new=30), dict(max_new=30)],
        chunks=[[[20, 40], [21, 41], [EOS, 42], [23, 43], [24, EOS],
                 [25, 45], [26, 46], [27, 47]]],
    ),
    "eos_mid_chunk_ignored": dict(
        specs=[dict(max_new=30, ignore_eos=True), dict(max_new=6, ignore_eos=True)],
        chunks=[[[20, 40], [21, 41], [EOS, 42], [23, 43], [24, EOS],
                 [25, EOS], [26, 46], [27, 47]]],
    ),
    # (5, 6) completes at step 4: 5 was held back, both are stripped, the
    # rest of the chunk is not the request's
    "stop_sequence_mid_chunk": dict(
        specs=[dict(max_new=30, stop_seqs=[(5, 6)]), dict(max_new=30)],
        chunks=[[[20, 40], [5, 41], [22, 42], [5, 43], [6, 44],
                 [25, 45], [26, 46], [27, 47]]],
    ),
    # the first chunk ends on 5, 5 (held back over the boundary); the
    # second begins with 6: (5, 5, 6) is stripped from what chunk one gave
    "stop_sequence_straddles_two_chunks": dict(
        specs=[dict(max_new=30, stop_seqs=[(5, 5, 6), (9, 9)]), dict(max_new=30)],
        chunks=[
            [[20, 40], [9, 41], [22, 42], [23, 43], [5, 44], [24, 45],
             [5, 46], [5, 47]],
            [[6, 50], [30, 51], [31, 52], [32, 53], [33, 54], [34, 55],
             [35, 56], [36, 57]],
        ],
    ),
    # the watch asks for the stop at the third streamed token: the request
    # ends at the fourth ("stop"), which it keeps
    "stop_requested_by_watch_at_token_3_of_8": dict(
        specs=[dict(max_new=30, watch_stops_at=3), dict(max_new=30)],
        chunks=[[[20 + t, 40 + t] for t in range(8)]],
    ),
    # the watch sees tokens as the hold-back releases them: 5 is held at
    # step 3, released with 23 at step 4 as the third streamed token
    "watch_behind_a_stop_sequence_holdback": dict(
        specs=[dict(max_new=30, watch_stops_at=3, stop_seqs=[(5, 6)])],
        chunks=[[[20], [21], [5], [23], [24], [25], [26], [27]]],
    ),
    # asked for at the chunk's last token: the next chunk's first ends it
    "watch_at_the_chunk_s_last_token": dict(
        specs=[dict(max_new=30, watch_stops_at=8)],
        chunks=[[[20 + t] for t in range(8)], [[30 + t] for t in range(8)]],
    ),
    "want_top_logprobs": dict(
        specs=[dict(max_new=6, want_top=True), dict(max_new=30),
               dict(max_new=30, want_top=True, stop_seqs=[(43, 44)])],
        chunks=[[[20 + t, 30 + t, 40 + t] for t in range(8)]],
    ),
    # slot 1 is aborted after the dispatch: its column is frozen repeats
    "aborted_between_dispatch_and_drain": dict(
        specs=[dict(max_new=30), dict(max_new=30), dict(max_new=3)],
        chunks=[[[20 + t, 40 + t, 60 + t] for t in range(8)],
                [[30 + t, 47, 67] for t in range(8)]],
        aborts={1: [1]},
    ),
    # slots 2, 0, 1 finish at steps 2, 4, 4: retires wait, in that order
    "defer_retire": dict(
        specs=[dict(max_new=4), dict(max_new=30), dict(max_new=2),
               dict(max_new=30)],
        chunks=[[[20, 40, 60, 80], [21, 41, 61, 81], [22, 42, 62, 82],
                 [23, EOS, 63, 83], [24, 44, 64, 84], [25, 45, 65, 85],
                 [26, 46, 66, 86], [27, 47, 67, 87]]],
        defer_retire=True,
    ),
    # the hook fails on its second call: nothing of that run counts as
    # delivered, and the next delivery brings it again with the new run
    "hook_raises_on_its_second_delivery": dict(
        specs=[dict(max_new=30)],
        chunks=[[[20 + t] for t in range(8)], [[30 + t] for t in range(8)],
                [[40 + t] for t in range(8)]],
        hook_raises_on=2,
    ),
}


@pytest.mark.parametrize("name", sorted(EMIT_CHUNK_CASES))
def test_emit_chunk_matches_per_token_semantics(emit_engine, name):
    """`_emit_chunk` emits a drained chunk one run a request; what every
    request ends up with is what the walk token by token gives."""
    from llm_d_fast_model_actuation_tpu.engine.engine import Request

    eng = emit_engine
    case = EMIT_CHUNK_CASES[name]
    specs = [dict(pos=10 + 3 * i, **s) for i, s in enumerate(case["specs"])]
    topk = eng.cfg.logprobs_topk
    chunks = [_chunk(rows, topk) for rows in case["chunks"]]
    aborts = case.get("aborts", {})
    want, want_order = _per_token_reference(specs, chunks, aborts)

    deliveries = [[] for _ in specs]  # (run, done) in the order delivered
    calls = [0]

    def hook(req, run):
        calls[0] += 1
        if calls[0] == case.get("hook_raises_on"):
            raise RuntimeError("client went away")
        deliveries[req.seq_id].append((list(run), req.done))

    reqs = []
    for slot, s in enumerate(specs):
        seen = []
        req = Request(
            seq_id=slot, prompt=[1], max_new_tokens=s["max_new"],
            stop_seqs=tuple(s.get("stop_seqs", ())),
            ignore_eos=bool(s.get("ignore_eos")),
            want_top_logprobs=bool(s.get("want_top")),
            on_tokens=hook,
        )
        if "watch_stops_at" in s:
            def watch(tok, seen=seen, at=s["watch_stops_at"]):
                seen.append(tok)
                return len(seen) == at

            req.stop_watch = watch
        req.slot, req.pos = slot, s["pos"]
        eng._slots[slot] = req
        eng._token_counts[slot] = 0
        reqs.append(req)
    finished = []
    raised = 0
    try:
        for ci, (toks, lps, ais, avs) in enumerate(chunks):
            for slot in aborts.get(ci, ()):
                assert eng.abort(slot)
            # what the dispatch saw as running: aborted slots among them
            live = {
                s: r for s, r in enumerate(reqs)
                if not r.done or s in aborts.get(ci, ())
            }
            try:
                finished += eng._emit_chunk(
                    toks, lps, avs, ais, None, live,
                    case.get("defer_retire", False),
                )
            except RuntimeError:
                raised += 1
        for slot, st in enumerate(want):
            if not st["done"]:
                # a live slot's mirrors are what the next upload sends
                assert eng._positions[slot] == st["pos"]
                assert eng._last_tokens[slot] == st["out"][-1]
                assert eng._budgets[slot] == (
                    specs[slot]["max_new"] - len(st["out"])
                )
                assert {
                    int(t): int(eng._token_counts[slot, t])
                    for t in np.nonzero(eng._token_counts[slot])[0]
                } == st["counts"]
        if case.get("defer_retire"):
            assert [r.seq_id for r in eng._pending_retire] == want_order
            assert all(eng._slots[s] is reqs[s] for s in want_order)
            assert all(eng._budgets[s] == 0 for s in want_order) and eng._dirty
        assert raised == (1 if "hook_raises_on" in case else 0)
        assert [r.seq_id for r in finished] == want_order
        for req, st, got in zip(reqs, want, deliveries):
            assert req.out_tokens == st["out"]
            assert req.out_logprobs == st["lps"]
            assert req.out_top_logprobs == st["tops"]
            assert req.pos == st["pos"]
            if st["aborted"]:
                assert req.done and req.error == "aborted"
                continue
            assert (req.done, req.finish_reason) == (st["done"], st["reason"])
            assert req.stop_requested == st["stop_requested"]
            # every streamed token once and in order, one delivery a chunk
            # at most, `done` on the delivery that holds the last token
            assert [t for run, _ in got for t in run] == st["streamed"]
            assert req.streamed == len(st["streamed"])
            assert len(got) <= len(chunks)
            assert [d for _, d in got] == [False] * (len(got) - 1) + (
                [st["done"] and st["finish_streamed"]] if got else []
            )
    finally:
        eng._pending_retire = []
        for req in reqs:
            if req.slot >= 0:
                eng._retire(req)
        eng._dirty = True
