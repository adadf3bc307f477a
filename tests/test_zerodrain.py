"""Zero-drain actuation: preempt, page out, and resume live requests
(--zero-drain; engine/parked.py, docs/perf.md "Zero-drain actuation").

The contract under test:
  * a preempted-then-resumed greedy stream is BIT-EQUAL to an
    uninterrupted one — across mid-decode, packed chunked prefill,
    penalties/bias/stop, seeded sampling, and shared prefix pages;
  * a swap under live load aborts NOTHING (cause="swap" stays zero) and
    the displaced futures resolve after the swap-back;
  * the failure paths are transactional: a ``kvsave.d2h`` fault falls
    back to today's abort path (engine untouched), a ``kvrestore.h2d``
    fault rolls back to a CLEAN abort with the existing ``state_loss``
    cause and the engine keeps serving;
  * ``--zero-drain off`` (the default) is inert byte-for-byte;
  * a park that would not fit the pool budget is rejected up front;
  * the cost oracle's byte predictions stay EXACT on preempting and
    resuming swaps (the parked-KV satellite).
"""

import json
import time

import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine.engine import (
    EngineConfig,
    InferenceEngine,
)
from llm_d_fast_model_actuation_tpu.engine.server import (
    EngineService,
    parse_engine_options,
)
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.utils import faults

pytestmark = pytest.mark.zerodrain


# ------------------------------------------------------------ engine level


def _tiny_cfg(**kw):
    base = dict(
        model=llama.LlamaConfig.tiny(),
        max_batch=2,
        page_size=8,
        num_pages=32,
        max_seq_len=64,
        decode_chunk=2,
    )
    base.update(kw)
    return EngineConfig(**base)


def _drain(eng, results):
    while eng.has_work():
        for r in eng.step():
            results[r.seq_id] = r


def _interrupt_cycle(eng, steps: int):
    """Step `steps` times, then park -> rebuild pool -> resume — the
    engine-level skeleton of what a swap-away-and-back does."""
    results = {}
    for _ in range(steps):
        if not eng.has_work():
            break
        for r in eng.step():
            results[r.seq_id] = r
    bundle, finished = eng.park_requests()
    for r in finished:
        results[r.seq_id] = r
    assert eng.kv_detached and not eng.has_work()
    eng.rebuild_kv_pool()
    eng.resume_parked(bundle)
    _drain(eng, results)
    return results


def test_park_resume_mid_decode_bit_exact():
    gold = InferenceEngine(_tiny_cfg(), seed=0).generate(
        [[1, 2, 3, 4, 5]], max_new_tokens=12
    )[0]
    eng = InferenceEngine(_tiny_cfg(), seed=0)
    sid = eng.add_request([1, 2, 3, 4, 5], max_new_tokens=12)
    results = _interrupt_cycle(eng, steps=2)
    assert results[sid].out_tokens == gold


def test_park_wire_resume_round_trip_keeps_the_pool_bytes():
    """The stored pool layout (kv_cache.PagePool: [L, P, page, kvh * hd])
    through a park -> migration wire -> resume on a FRESH engine: the
    bundle holds the live pages' bytes in that layout, the wire document
    carries and returns them bit-exact, the importer's pool holds them at
    the re-mapped page ids, and the stream continues as uninterrupted."""
    import dataclasses

    from llm_d_fast_model_actuation_tpu.engine import parked
    from llm_d_fast_model_actuation_tpu.engine.engine import Request

    cfg = _tiny_cfg(
        model=dataclasses.replace(llama.LlamaConfig.tiny(), num_layers=3)
    )
    m = cfg.model
    gold = InferenceEngine(cfg, seed=0).generate(
        [[1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11]], max_new_tokens=12
    )[0]
    src = InferenceEngine(cfg, seed=0)
    sid = src.add_request([1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11], max_new_tokens=12)
    results = {}
    for _ in range(2):
        for r in src.step():
            results[r.seq_id] = r
    k_before = np.asarray(src.pool.k_pages)
    v_before = np.asarray(src.pool.v_pages)
    bundle, _ = src.park_requests()
    ids = bundle.page_ids
    assert len(ids) == 2  # 13-14 tokens at page_size 8
    assert bundle.k_host.shape == (
        m.num_layers, len(ids), cfg.page_size, m.num_kv_heads * m.head_dim
    )
    np.testing.assert_array_equal(bundle.k_host, k_before[:, ids])
    np.testing.assert_array_equal(bundle.v_host, v_before[:, ids])
    assert np.abs(bundle.k_host.astype(np.float32)).sum() > 0

    doc = parked.encode_wire(bundle, identity={}, chunk_bytes=1)  # 1 page/chunk
    assert doc["version"] == parked.WIRE_VERSION == 3
    assert doc["kv"]["shape"] == list(bundle.k_host.shape)
    got, _ = parked.decode_wire(json.loads(json.dumps(doc)), Request)
    np.testing.assert_array_equal(got.k_host, bundle.k_host)
    np.testing.assert_array_equal(got.v_host, bundle.v_host)
    stale = dict(doc, version=1)
    with pytest.raises(ValueError, match="wire version"):
        parked.decode_wire(stale, Request)

    dst = InferenceEngine(cfg, seed=0)
    dst.resume_parked(got)
    (req,) = [r for r in dst._slots if r is not None]
    new_ids = req.pages[: len(ids)]
    np.testing.assert_array_equal(
        np.asarray(dst.pool.k_pages)[:, new_ids], bundle.k_host
    )
    np.testing.assert_array_equal(
        np.asarray(dst.pool.v_pages)[:, new_ids], bundle.v_host
    )
    _drain(dst, results)
    (out,) = [r for r in results.values()]
    assert out.out_tokens == gold and sid == out.seq_id


def test_park_resume_penalties_bias_stop_seeded():
    """The full sampling-state surface: repetition penalties (the saved
    counts row — NOT recomputable once a stop strip happened), logit
    bias, stop sequences, and a seeded temperature>0 stream (the saved
    RNG key). Resumed == uninterrupted, including the finish reason."""
    kw = dict(
        max_new_tokens=14,
        temperature=0.8,
        seed=1234,
        top_p=0.9,
        presence_penalty=0.7,
        frequency_penalty=0.4,
        logit_bias={7: 4.0, 11: -6.0},
        stop_seqs=((9, 9, 9),),
    )
    eng_g = InferenceEngine(_tiny_cfg(), seed=0)
    gid = eng_g.add_request([3, 1, 4, 1, 5], **kw)
    gold = {}
    _drain(eng_g, gold)

    eng = InferenceEngine(_tiny_cfg(), seed=0)
    sid = eng.add_request([3, 1, 4, 1, 5], **kw)
    results = _interrupt_cycle(eng, steps=2)
    assert results[sid].out_tokens == gold[gid].out_tokens
    assert results[sid].out_logprobs == gold[gid].out_logprobs
    assert results[sid].finish_reason == gold[gid].finish_reason


def test_park_resume_packed_mid_prefill():
    """Packed serving: a request parked MID chunked prefill is demoted
    back to the queue (no KV carried — prefill is a pure function of the
    prompt and consumes no key split before its final segment) and the
    re-run reproduces the uninterrupted output exactly."""
    kw = dict(packed_serving=True, max_prefill_tokens=4, max_batch=2)
    prompt = list(range(1, 17))  # 16 tokens -> 4 packed segments
    gold = InferenceEngine(_tiny_cfg(**kw), seed=0).generate(
        [prompt], max_new_tokens=6
    )[0]
    eng = InferenceEngine(_tiny_cfg(**kw), seed=0)
    sid = eng.add_request(prompt, max_new_tokens=6)
    results = {}
    for r in eng.step():
        results[r.seq_id] = r
    req = next(r for r in eng._slots if r is not None)
    assert req.prefilling, "expected a mid-prefill park"
    bundle, _ = eng.park_requests()
    assert not bundle.live and len(bundle.waiting) == 1
    assert bundle.kv_nbytes == 0
    eng.rebuild_kv_pool()
    eng.resume_parked(bundle)
    _drain(eng, results)
    assert results[sid].out_tokens == gold


def test_park_resume_shared_prefix_pages():
    """Two live requests sharing prefix-cache pages: the park gathers
    each shared page once, the resume maps old->new preserving the
    sharing (refcounted through the prefix cache), and both streams
    resume bit-exact."""
    shared = list(range(1, 10))  # > one full page of shared prefix
    p1, p2 = shared + [21], shared + [22]
    eng_g = InferenceEngine(_tiny_cfg(), seed=0)
    gold = eng_g.generate([p1, p2], max_new_tokens=10)
    eng = InferenceEngine(_tiny_cfg(), seed=0)
    s1 = eng.add_request(p1, max_new_tokens=10)
    s2 = eng.add_request(p2, max_new_tokens=10)
    results = _interrupt_cycle(eng, steps=3)
    assert results[s1].out_tokens == gold[0]
    assert results[s2].out_tokens == gold[1]


def test_park_gather_failure_leaves_engine_serving():
    """kvsave.d2h failing mid page-out must leave the engine untouched
    (the gather runs before any detach): the request keeps decoding to
    its normal completion."""
    eng = InferenceEngine(_tiny_cfg(), seed=0)
    gold = InferenceEngine(_tiny_cfg(), seed=0).generate(
        [[5, 6, 7]], max_new_tokens=8
    )[0]
    sid = eng.add_request([5, 6, 7], max_new_tokens=8)
    results = {}
    for r in eng.step():
        results[r.seq_id] = r
    faults.arm("kvsave.d2h", mode="fail", count=1)
    try:
        with pytest.raises(faults.FaultError):
            eng.park_requests()
    finally:
        faults.reset()
    assert not eng.kv_detached and eng.has_work()
    _drain(eng, results)
    assert results[sid].out_tokens == gold


# ----------------------------------------------------------- service level


BASE_OPTS = (
    "--model tiny --num-pages 32 --page-size 16 --max-batch 2 "
    "--max-model-len 64 --swap-bucket-mib 1 --decode-chunk 2 "
)


@pytest.fixture
def zd_service():
    svc = EngineService(parse_engine_options(BASE_OPTS + "--zero-drain on"))
    yield svc
    faults.reset()
    svc.shutdown()


def _slow_stream(seen, delay=0.03):
    def cb(req, run):
        seen.extend(run)
        time.sleep(delay * len(run))

    return cb


def _live_request(svc, prompt=(1, 2, 3, 4), max_tokens=24, min_tokens=3):
    """Submit a throttled greedy request and wait until it is mid-decode
    (the throttle keeps it live while the admin verb takes the lock)."""
    seen: list = []
    fut = svc.submit(
        list(prompt), max_tokens, 0.0, on_tokens=_slow_stream(seen)
    )
    deadline = time.time() + 60
    while len(seen) < min_tokens and time.time() < deadline:
        time.sleep(0.005)
    assert len(seen) >= min_tokens, "request never started decoding"
    return fut


def test_flag_validation():
    parse_engine_options("--model tiny --zero-drain on")
    parse_engine_options("--model tiny --zero-drain off")
    with pytest.raises(ValueError, match="multi-host gangs"):
        parse_engine_options(
            "--model tiny --zero-drain on --num-processes 2 "
            "--process-id 0 --coordinator-address 127.0.0.1:9999"
        )


def test_swap_preempts_and_resumes_bit_exact(zd_service):
    svc = zd_service
    gold = svc.submit([1, 2, 3, 4], 24, 0.0).result(timeout=120).out_tokens

    fut = _live_request(svc)
    out = svc.swap("tiny-gemma")
    zd = out["zero_drain"]
    assert zd["parked"] >= 1 and zd["kv_pageout_bytes"] > 0
    assert not fut.done(), "preempted stream must stay open, not abort"
    # no swap-caused aborts anywhere
    st = svc.stats()
    assert "swap" not in st["aborted"]
    assert st["zero_drain"]["preempted"] >= 1
    assert st["zero_drain"]["parked_kv_bytes"] == zd["kv_pageout_bytes"]
    # the other model serves while the victim's stream is parked
    assert len(svc.submit([9, 8, 7], 4, 0.0).result(120).out_tokens) == 4

    back = svc.swap("tiny")
    assert back["zero_drain"]["resumed"] >= 1
    assert back["zero_drain"]["kv_pagein_bytes"] > 0
    res = fut.result(timeout=120)
    assert res.out_tokens == gold, "resumed stream must be bit-exact"
    st = svc.stats()
    assert st["zero_drain"]["resumed"] >= 1
    assert st["zero_drain"]["parked_kv_bytes"] == 0
    # flight recorder: the actuation records carry preempt/resume counts
    recs = svc.actuations_view(kind="swap")["records"]
    assert any(
        (r.get("extra") or {}).get("preempted", 0) >= 1 for r in recs
    )
    assert any(
        (r.get("extra") or {}).get("resumed", 0) >= 1 for r in recs
    )
    # metrics exposition: both new families present with samples
    from prometheus_client import generate_latest

    text = generate_latest().decode()
    assert 'fma_engine_preempted_requests_total{' in text
    assert 'outcome="resumed"' in text
    assert 'fma_engine_kv_pageout_bytes_total{dir="d2h"}' in text
    assert 'fma_engine_kv_pageout_bytes_total{dir="h2d"}' in text


def test_preempting_swap_predicted_bytes_exact(zd_service):
    """Cost-oracle satellite: with parked KV counted, predicted bytes ==
    actual bytes on BOTH the preempting swap and the resuming swap-back
    (page_size 16 and a short request keep the live page count stable
    between pricing and quiesce)."""
    svc = zd_service
    # prewarm: pool both models so both directions are pool hits
    svc.swap("tiny-gemma")
    svc.swap("tiny")

    fut = _live_request(svc, prompt=(1, 2, 3, 4), max_tokens=8)
    out = svc.swap("tiny-gemma")
    rec = out["costs"]
    assert out["zero_drain"]["parked"] >= 1
    assert rec["predicted_bytes"] == rec["actual_bytes"], rec
    assert rec["bytes_error_ratio"] == 0.0

    back = svc.swap("tiny")
    rec2 = back["costs"]
    assert back["zero_drain"]["resumed"] >= 1
    assert rec2["predicted_bytes"] == rec2["actual_bytes"], rec2
    fut.result(timeout=120)
    # the stats summary scores them byte-exact too
    summary = svc.stats()["costs"]["prediction"]
    assert summary["byte_exact_frac"] == 1.0, summary


def test_sleep_wake_park_resume_bit_exact(zd_service):
    svc = zd_service
    gold = svc.submit([1, 2, 3, 4], 24, 0.0).result(timeout=120).out_tokens
    fut = _live_request(svc)
    pred = svc.price_sleep()
    out = svc.sleep(1)
    assert svc._runtime.parked is not None
    assert pred["predicted_kv_pageout_bytes"] > 0
    # weights-only offload: the slept bytes exclude the (mostly empty)
    # KV pool the full-pool path would have parked
    assert out["bytes_offloaded"] < svc.price_wake()["predicted_bytes"] + 1
    svc.wake_up()
    assert svc._runtime.parked is None
    res = fut.result(timeout=120)
    assert res.out_tokens == gold
    st = svc.stats()
    assert st["zero_drain"]["resumed"] >= 1
    # the sleep and wake records priced the parked KV byte-exact
    for kind in ("sleep", "wake"):
        recs = svc.actuations_view(kind=kind)["records"]
        assert recs and recs[-1]["predicted_bytes"] == recs[-1][
            "actual_bytes"
        ], recs[-1]


def test_kvrestore_fault_rolls_back_to_clean_state_loss(zd_service):
    """The acceptance drill: a kvrestore.h2d failure mid resume ends in
    a SERVED engine — the preempted request aborts with the existing
    state_loss cause, nothing wedges, and new traffic flows."""
    svc = zd_service
    fut = _live_request(svc)
    svc.sleep(1)
    assert svc._runtime.parked is not None
    faults.arm("kvrestore.h2d", mode="fail", count=1)
    svc.wake_up()
    with pytest.raises(RuntimeError, match="zero-drain KV restore"):
        fut.result(timeout=60)
    st = svc.stats()
    assert st["aborted"].get("state_loss") == 1
    assert st["zero_drain"]["aborted"] == 1
    # the documented balance always closes (runbook invariant)
    zd = st["zero_drain"]
    assert zd["preempted"] == zd["resumed"] + zd["aborted"], zd
    assert svc.failure is None, "engine must stay healthy"
    assert "state_loss" in (svc.degraded or "")
    # the rolled-back restore moved none of the predicted park-in
    # bytes: the wake record must be UNPRICED, never a false byte miss
    recs = svc.actuations_view(kind="wake")["records"]
    assert recs and recs[-1]["predicted_bytes"] is None, recs[-1]
    # still serving, and a fresh actuation cycle works end to end
    assert len(svc.submit([5, 6, 7], 4, 0.0).result(120).out_tokens) == 4
    svc.sleep(1)
    svc.wake_up()
    assert len(svc.submit([5, 6, 7], 4, 0.0).result(120).out_tokens) == 4


def test_kvsave_fault_falls_back_to_abort_path(zd_service):
    """A park that fails mid page-out must not half-preempt: the swap
    falls back to today's abort path (cause="swap") and still commits."""
    svc = zd_service
    fut = _live_request(svc)
    faults.arm("kvsave.d2h", mode="fail", count=1)
    out = svc.swap("tiny-gemma")
    assert out["swapped"]
    assert out["zero_drain"]["parked"] == 0
    assert "fallback" in out["zero_drain"]
    with pytest.raises(RuntimeError, match="aborted by model swap"):
        fut.result(timeout=60)
    assert svc.stats()["aborted"].get("swap", 0) >= 1
    # a fallback swap's offload moved the full pool the prediction's
    # peek excluded: the record must be UNPRICED (oracle blameless)
    recs = svc.actuations_view(kind="swap")["records"]
    assert recs and recs[-1]["predicted_bytes"] is None, recs[-1]


def test_l2_escalation_aborts_parked_state_loss(zd_service):
    """An L1->L2 escalation drops the host state a parked bundle would
    resume against: the parked requests abort cleanly (state_loss)."""
    svc = zd_service
    fut = _live_request(svc)
    svc.sleep(1)
    assert svc._runtime.parked is not None
    svc.sleep(2)  # escalation
    assert svc._runtime.parked is None
    with pytest.raises(RuntimeError, match="level-2 sleep"):
        fut.result(timeout=60)
    assert svc.stats()["aborted"].get("state_loss", 0) >= 1
    svc.wake_up()  # L2 wake reinitializes; engine serves again
    assert len(svc.submit([5, 6, 7], 4, 0.0).result(120).out_tokens) == 4


def test_pool_budget_admission_rejects_park():
    """A park whose bytes cannot fit --model-pool-mib would be evicted
    (and aborted) the instant it was pooled: admission rejects it up
    front and the swap takes the abort path instead."""
    svc = EngineService(
        parse_engine_options(
            BASE_OPTS + "--zero-drain on --model-pool-mib 0"
        )
    )
    try:
        fut = _live_request(svc)
        out = svc.swap("tiny-gemma")
        assert out["swapped"]
        assert out["zero_drain"]["parked"] == 0
        assert "park rejected" in out["zero_drain"]["fallback"]
        with pytest.raises(RuntimeError):
            fut.result(timeout=60)
        assert svc.stats()["aborted"].get("swap", 0) >= 1
    finally:
        svc.shutdown()


def test_zero_drain_off_is_inert():
    """The default keeps today's abort path byte-for-byte: live work
    aborts with cause="swap", the response carries NO zero_drain block,
    and /v1/stats reports the feature disabled with zero counters."""
    svc = EngineService(parse_engine_options(BASE_OPTS))
    try:
        fut = _live_request(svc)
        out = svc.swap("tiny-gemma")
        assert out["swapped"]
        assert "zero_drain" not in out
        with pytest.raises(RuntimeError, match="aborted by model swap"):
            fut.result(timeout=60)
        st = svc.stats()
        assert st["aborted"].get("swap", 0) >= 1
        assert st["zero_drain"] == {
            "enabled": False,
            "preempted": 0,
            "resumed": 0,
            "aborted": 0,
            "migrated": 0,
            "parked_kv_bytes": 0,
        }
    finally:
        svc.shutdown()


def test_parked_model_eviction_aborts_bundle():
    """Budget pressure evicting a parked model's pool entry must resolve
    its parked futures (state_loss), never leave them hanging."""
    svc = EngineService(
        parse_engine_options(BASE_OPTS + "--zero-drain on")
    )
    try:
        fut = _live_request(svc)
        svc.swap("tiny-gemma")
        assert not fut.done()
        # find the pooled parked runtime and force-evict it
        entry = svc.model_pool.take_match("tiny")
        assert entry is not None and entry.runtime.parked is not None
        svc._free_pooled([entry], "test eviction")
        with pytest.raises(RuntimeError, match="evicted"):
            fut.result(timeout=60)
        st = svc.stats()
        assert st["aborted"].get("state_loss", 0) >= 1
        assert st["zero_drain"]["aborted"] >= 1
    finally:
        svc.shutdown()
