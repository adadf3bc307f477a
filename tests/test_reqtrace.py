"""Request-lifecycle tracing (PR 19): the request-span ring is isolated
from the actuation ring, --trace-requests 0 is inert on the hot path,
tail-keep retains violated/aborted lifecycles at sampling 0.0, migrated
streams keep ONE trace_id across the instance boundary, and a
migrated-then-client-dropped stream resolves to exactly one client
abort on EACH instance (the cross-instance balance invariant).
"""

import json
import threading
import time

import jax
import pytest
from prometheus_client import REGISTRY

from llm_d_fast_model_actuation_tpu.engine.server import (
    EngineService,
    _lifecycle_usage,
    parse_engine_options,
)
from llm_d_fast_model_actuation_tpu.models import checkpoint, llama
from llm_d_fast_model_actuation_tpu.utils import tracing

pytestmark = pytest.mark.reqtrace


@pytest.fixture(autouse=True)
def _clean_tracing():
    """Tracing state is process-global: every test starts enabled, empty
    (both rings), unsampled — and leaves it that way."""
    tracing.enable()
    tracing.clear()
    tracing.clear_requests()
    tracing.configure_request_sampling(0.0)
    yield
    tracing.enable()
    tracing.clear()
    tracing.clear_requests()
    tracing.configure_request_sampling(0.0)


def _counter(name, labels):
    return REGISTRY.get_sample_value(name, labels) or 0.0


# -- ring isolation + sampling (no engine) ------------------------------------


def test_request_spans_never_evict_actuation_spans(monkeypatch):
    """The dedicated request ring: decode traffic can never push swap
    forensics out of the actuation ring, however hard it floods."""
    buf = tracing.TraceBuffer(capacity=4)
    rbuf = tracing.TraceBuffer(capacity=4)
    monkeypatch.setattr(tracing, "_BUFFER", buf)
    monkeypatch.setattr(tracing, "_REQ_BUFFER", rbuf)
    with tracing.span("engine.swap"):
        pass
    for _ in range(50):
        tr = tracing.RequestTrace(sampled=True)
        tr.add("request.queue", 0.0, 1.0)
        tr.finish(0.0, 2.0, keep=True)
    assert len(rbuf) == 4  # bounded, newest kept
    assert [s.name for s in buf.snapshot()] == ["engine.swap"]
    assert all(s.name.startswith("request.") for s in rbuf.snapshot())
    # and the actuation-ring views stay actuation-only
    assert [s.name for s in tracing.snapshot()] == ["engine.swap"]


def test_sampling_draw_clamps_and_short_circuits(monkeypatch):
    tracing.configure_request_sampling(1.0)
    assert tracing.sample_request() is True  # random() < 1.0 always
    # out-of-range / junk input clamps, never raises
    tracing.configure_request_sampling(2.0)
    assert tracing.request_sampling() == 1.0
    tracing.configure_request_sampling(-3)
    assert tracing.request_sampling() == 0.0
    tracing.configure_request_sampling("nope")
    assert tracing.request_sampling() == 0.0
    # frac 0 short-circuits BEFORE the RNG draw (the inert hot path)
    def boom():
        raise AssertionError("sample_request drew RNG at frac 0")

    monkeypatch.setattr(tracing.random, "random", boom)
    assert tracing.sample_request() is False
    # disabled tracing wins over any fraction
    tracing.configure_request_sampling(1.0)
    tracing.disable()
    monkeypatch.undo()
    assert tracing.sample_request() is False


def test_unsampled_finish_drops_and_double_finish_is_idempotent():
    tr = tracing.RequestTrace(sampled=False)
    tr.add("request.queue", 0.0, 1.0)
    tid = tr.finish(0.0, 2.0, keep=False)
    assert tid and tracing.request_buffer_len() == 0
    kept = tracing.RequestTrace(sampled=True)
    kept.finish(0.0, 1.0, keep=True)
    n = tracing.request_buffer_len()
    kept.finish(0.0, 1.0, keep=True)
    assert tracing.request_buffer_len() == n


def test_export_http_unions_both_rings():
    with tracing.span("engine.swap"):
        pass
    tr = tracing.RequestTrace(sampled=True)
    tr.add("request.queue", 1.0, 2.0)
    tr.finish(1.0, 3.0, keep=True)
    status, body, _ = tracing.export_http("chrome")
    assert status == 200
    names = {e["name"] for e in json.loads(body)["traceEvents"]}
    assert {"engine.swap", "request.lifecycle", "request.queue"} <= names
    # trace_id filter scopes across rings too
    status, body, _ = tracing.export_http("chrome", trace_id=tr.trace_id)
    names = {e["name"] for e in json.loads(body)["traceEvents"]}
    assert names == {"request.lifecycle", "request.queue"}


def test_reset_after_fork_resets_request_ring_and_sampling(monkeypatch):
    monkeypatch.setenv(tracing.REQ_BUFFER_ENV_VAR, "8")
    try:
        tracing.configure_request_sampling(0.5)
        tracing.RequestTrace(sampled=True).finish(0.0, 1.0, keep=True)
        tracing.reset_after_fork()
        assert tracing.request_buffer_len() == 0
        assert tracing.request_sampling() == 0.0
        for _ in range(20):
            tracing.RequestTrace(sampled=True).finish(
                0.0, 1.0, keep=True
            )
        assert tracing.request_buffer_len() == 8  # env capacity applied
    finally:
        monkeypatch.delenv(tracing.REQ_BUFFER_ENV_VAR)
        tracing.reset_after_fork()


# -- engine-backed lifecycle traces -------------------------------------------


@pytest.fixture(scope="module")
def ckpt(tmp_path_factory):
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(7), cfg)
    d = str(tmp_path_factory.mktemp("reqtrace-ckpt"))
    checkpoint.save_params(d, cfg, params)
    return d


def _service(ckpt_dir: str, extra: str = "") -> EngineService:
    return EngineService(
        parse_engine_options(
            f"--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
            f"--max-model-len 64 --swap-bucket-mib 1 --zero-drain on "
            f"--checkpoint-dir {ckpt_dir} {extra}"
        )
    )


def _wire(src: EngineService, dst: EngineService) -> None:
    """In-process transport seams for both claim verbs."""
    src._claim_fetch = lambda dest, cid, have, wait_s: dst.claim_view(
        cid, wait_s=wait_s, have=have
    )
    src._claim_abort = lambda dest, cid: dst.abort_claim(cid)


def _live_stream(svc: EngineService, prompt, max_tokens=8, **kw):
    """A stream provably mid-decode at export time (test_migrate's
    idiom): the inline on_tokens sleep throttles the batch."""
    toks: list = []
    started = threading.Event()

    def slow(req, run):
        toks.extend(run)
        started.set()
        time.sleep(0.05 * len(run))

    fut = svc.submit(
        list(prompt), max_tokens, kw.pop("temperature", 0.0),
        on_tokens=slow, **kw,
    )
    assert started.wait(timeout=60), "stream never produced a token"
    return fut, toks


def test_trace_requests_zero_records_nothing_for_met_requests(ckpt):
    """The default is byte-inert: no collector is created at submit, no
    spans land in either ring, usage carries no trace_id."""
    svc = _service(ckpt)
    try:
        assert tracing.request_sampling() == 0.0
        req = svc.submit([1, 2, 3], 4, 0.0).result(timeout=120)
        assert getattr(req, "trace_id", "") == ""
        assert tracing.request_buffer_len() == 0
        u = _lifecycle_usage(req)
        assert "trace_id" not in u and "queue_wait_s" in u
        assert svc.stats()["slo_exemplars"] == []
    finally:
        svc.shutdown()


def test_client_traceparent_forces_a_trace_at_zero_sampling(ckpt):
    """A caller-sent traceparent is an explicit ask: the lifecycle is
    traced and retained even with head sampling off, parented on the
    remote span."""
    svc = _service(ckpt)
    try:
        remote_trace, remote_span = "ab" * 16, "cd" * 8
        ctx = tracing.SpanContext(remote_trace, remote_span)
        req = svc.submit(
            [1, 2, 3], 4, 0.0, trace_ctx=ctx
        ).result(timeout=120)
        assert req.trace_id == remote_trace
        assert _lifecycle_usage(req)["trace_id"] == remote_trace
        spans = tracing.request_snapshot(remote_trace)
        by_name = {s.name: s for s in spans}
        assert {
            "request.lifecycle", "request.queue", "request.prefill",
            "request.decode",
        } <= set(by_name)
        root = by_name["request.lifecycle"]
        assert root.parent_id == remote_span
        assert root.attrs["outcome"] == "finished"
        for name in ("request.queue", "request.prefill", "request.decode"):
            assert by_name[name].parent_id == root.span_id
        # legs tile the lifecycle window (no per-step span flood:
        # exactly ONE decode span regardless of token count)
        assert sum(
            1 for s in spans if s.name == "request.decode"
        ) == 1
        assert by_name["request.decode"].attrs["tokens"] == len(
            req.out_tokens
        )
        # the actuation ring saw none of this
        assert tracing.snapshot(trace_id=remote_trace) == []
    finally:
        svc.shutdown()


def test_tail_keep_retains_violated_trace_at_zero_sampling(ckpt):
    """A forced TTFT violation at --trace-requests 0: the trace is
    synthesized at completion from the Request's timestamps, retained,
    and surfaced as an slo_exemplar with a leg breakdown that sums to
    the request's server-side wall time."""
    svc = _service(ckpt, extra="--slo-ttft-ms 0.001")
    try:
        req = svc.submit([1, 2, 3], 4, 0.0).result(timeout=120)
        assert req.trace_id  # tail-keep overruled the 0.0 head draw
        spans = tracing.request_snapshot(req.trace_id)
        by_name = {s.name: s for s in spans}
        assert {"request.lifecycle", "request.queue", "request.prefill",
                "request.decode"} <= set(by_name)
        assert by_name["request.prefill"].attrs.get("synthesized") is True
        root = by_name["request.lifecycle"]
        assert root.attrs["violated"] is True
        ex = svc.stats()["slo_exemplars"]
        assert ex and ex[-1]["trace_id"] == req.trace_id
        assert ex[-1]["violated"] == ["ttft"]
        legs = ex[-1]["legs"]
        assert set(legs) == {
            "queue", "prefill", "decode", "preempt", "migrate"
        }
        wall = root.end_s - root.start_s
        assert abs(sum(legs.values()) - wall) <= 0.1 * wall + 1e-3
    finally:
        svc.shutdown()


def test_migrated_stream_spans_share_origin_trace_id(ckpt):
    """One Perfetto timeline for a stream that lived on two engines:
    the trace context rides the parked bundle, so the destination's
    resume/decode spans and the source's migrate span carry the SAME
    trace_id."""
    src, dst = _service(ckpt), _service(ckpt)
    _wire(src, dst)
    try:
        trace_id = "ab" * 16
        ctx = tracing.SpanContext(trace_id, "cd" * 8)
        fut, toks = _live_stream(src, [1, 2, 3], trace_ctx=ctx)
        doc = src.export_parked("tiny")
        ack = dst.import_parked(doc)
        rel = src.release_parked(
            doc["fence"]["token"], dest="local", claims=ack["claims"]
        )
        assert rel["ok"] and rel["migrated"] == 1
        req = fut.result(timeout=120)
        assert req.out_tokens and toks == req.out_tokens

        spans = tracing.request_snapshot(trace_id)
        assert {s.trace_id for s in spans} == {trace_id}
        names = [s.name for s in spans]
        # source half: preempt at export, migrate over the handoff
        assert "request.preempt" in names and "request.migrate" in names
        # destination half: the resume span joined the same trace
        resume = next(s for s in spans if s.name == "request.resume")
        assert resume.attrs.get("migrated") is True
        # two lifecycle roots — source (outcome=migrated, no decode
        # span of its own) and destination (finished)
        roots = [s for s in spans if s.name == "request.lifecycle"]
        assert {r.attrs.get("outcome") for r in roots} == {
            "migrated", "finished"
        }
        mig_span = next(s for s in spans if s.name == "request.migrate")
        assert mig_span.attrs["outcome"] == "migrated"
    finally:
        src.shutdown()
        dst.shutdown()


# -- cross-instance abort balance (the satellite-2 invariant) -----------------


def _balance(svc: EngineService) -> None:
    zd = svc.stats()["zero_drain"]
    assert (
        zd["preempted"] == zd["resumed"] + zd["aborted"] + zd["migrated"]
    ), zd


def _wait_counter(name, labels, floor, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if _counter(name, labels) >= floor:
            return
        time.sleep(0.05)
    raise AssertionError(
        f"{name}{labels} never reached {floor} "
        f"(at {_counter(name, labels)})"
    )


def test_client_drop_before_release_counts_one_abort_per_side(
    ckpt, fresh_programs
):
    """Client vanishes while the bundle is in flight: the source books
    exactly one reason=client abort + one outcome=aborted (never
    state_loss), and the destination — told via DELETE claim — books
    exactly its own single client abort. (The source's count is read
    before the destination's asynchronous abort lands in the counter they
    share here: engines that compile their own programs keep that order.)"""
    src, dst = _service(ckpt), _service(ckpt)
    _wire(src, dst)
    aborts = "fma_engine_aborted_requests_total"
    lab_client = {"model": "tiny", "reason": "client"}
    lab_loss = {"model": "tiny", "reason": "state_loss"}
    try:
        fut, _ = _live_stream(src, [1, 2, 3], max_tokens=48)
        doc = src.export_parked("tiny")
        ack = dst.import_parked(doc)
        src_client0 = _counter(aborts, lab_client)
        src_loss0 = _counter(aborts, lab_loss)
        pre_aborted0 = _counter(
            "fma_engine_preempted_requests_total",
            {"model": "tiny", "outcome": "aborted"},
        )
        assert fut.cancel()  # the client dropped mid-handoff
        rel = src.release_parked(
            doc["fence"]["token"], dest="http://dst", claims=ack["claims"]
        )
        assert rel["migrated"] == 0 and rel["proxied"] == 0
        # source: exactly one client abort, one aborted outcome, no loss
        assert _counter(aborts, lab_client) - src_client0 == 1
        assert _counter(aborts, lab_loss) - src_loss0 == 0
        assert (
            _counter(
                "fma_engine_preempted_requests_total",
                {"model": "tiny", "outcome": "aborted"},
            )
            - pre_aborted0
            == 1
        )
        _balance(src)
        # destination: the async claim abort lands as ITS single client
        # abort (src and dst share the process-global counter here, so
        # the combined delta settling at exactly 2 pins both sides)
        _wait_counter(aborts, lab_client, src_client0 + 2)
        time.sleep(0.3)  # no late double-count on either side
        assert _counter(aborts, lab_client) - src_client0 == 2
        assert _counter(aborts, lab_loss) - src_loss0 == 0
        s = src.stats()["zero_drain"]
        assert s["migrated"] == 0 and s["aborted"] == 1
    finally:
        src.shutdown()
        dst.shutdown()


def test_client_drop_after_release_counts_one_abort_per_side(ckpt):
    """Client vanishes AFTER the handoff committed: the watcher exits
    silently, _drain_aborts books the source's single client abort from
    the proxy registry, and the destination claim-abort books its own —
    the stream's outcome stays the one 'migrated' booked at release."""
    src, dst = _service(ckpt), _service(ckpt)
    _wire(src, dst)
    aborts = "fma_engine_aborted_requests_total"
    lab_client = {"model": "tiny", "reason": "client"}
    lab_loss = {"model": "tiny", "reason": "state_loss"}
    try:
        fut, _ = _live_stream(src, [1, 2, 3], max_tokens=48)
        doc = src.export_parked("tiny")
        ack = dst.import_parked(doc)
        client0 = _counter(aborts, lab_client)
        loss0 = _counter(aborts, lab_loss)
        mig0 = _counter(
            "fma_engine_preempted_requests_total",
            {"model": "tiny", "outcome": "migrated"},
        )
        rel = src.release_parked(
            doc["fence"]["token"], dest="http://dst", claims=ack["claims"]
        )
        assert rel["migrated"] == 1 and rel["proxied"] == 1
        assert (
            _counter(
                "fma_engine_preempted_requests_total",
                {"model": "tiny", "outcome": "migrated"},
            )
            - mig0
            == 1
        )
        src.abort(fut)  # the client hangs up on the proxied stream
        # one client abort on the source (from the proxy registry), one
        # on the destination (claim abort -> its own abort choke point)
        _wait_counter(aborts, lab_client, client0 + 2)
        time.sleep(0.3)
        assert _counter(aborts, lab_client) - client0 == 2
        assert _counter(aborts, lab_loss) - loss0 == 0
        assert fut.done()  # cancelled by _drain_aborts
        _balance(src)
        s = src.stats()["zero_drain"]
        assert s["migrated"] == 1 and s["aborted"] == 0
    finally:
        src.shutdown()
        dst.shutdown()


# -- launcher exemplar surfaces ----------------------------------------------


def test_fleet_rollup_lifts_exemplars_and_rest_serves_them(
    monkeypatch, tmp_path
):
    """The launcher's fleet block tags each child's slo_exemplars with
    its instance id, and GET /v2/vllm/exemplars serves the list without
    the full instances payload."""
    import asyncio

    from aiohttp.test_utils import TestClient, TestServer

    from llm_d_fast_model_actuation_tpu.launcher.chiptranslator import (
        ChipTranslator,
    )
    from llm_d_fast_model_actuation_tpu.launcher.instance import (
        InstanceConfig,
    )
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        EngineProcessManager,
    )
    from llm_d_fast_model_actuation_tpu.launcher.rest import build_app

    def fake_kickoff(config, log_path):
        with open(log_path, "ab", buffering=0) as f:
            f.write(b"fake engine\n")
        time.sleep(300)

    manager = EngineProcessManager(
        ChipTranslator.create(
            mock_chips=True, mock_chip_count=4, mock_topology="2x2"
        ),
        log_dir=str(tmp_path),
        kickoff=fake_kickoff,
        enforce_chip_exclusivity=False,
    )
    try:
        for iid in ("i-a", "i-b"):
            manager.create_instance(
                InstanceConfig(options="--model tiny", chip_ids=None),
                instance_id=iid,
            )
        ex = {
            "trace_id": "ab" * 16,
            "model": "tiny",
            "violated": ["ttft"],
            "ttft_s": 3.5,
            "legs": {
                "queue": 3.4, "prefill": 0.1, "decode": 1.0,
                "preempt": 0.0, "migrate": 0.0,
            },
        }
        canned = {
            "i-a": {
                "model": "tiny",
                "slo": {"ttft_ms": 500, "tpot_ms": 0,
                        "met": 1, "violated": 1},
                "slo_exemplars": [ex],
            },
            "i-b": {"model": "tiny", "slo_exemplars": []},
        }
        monkeypatch.setattr(
            manager, "_poll_instance_stats",
            lambda iid, timeout: canned[iid],
        )
        fleet = manager.fleet_rollup()
        assert fleet["slo_exemplars"] == [{"instance": "i-a", **ex}]

        async def scenario():
            app = build_app(manager)
            server = TestServer(app)
            client = TestClient(server)
            await client.start_server()
            try:
                r = await client.get("/v2/vllm/exemplars")
                assert r.status == 200
                body = await r.json()
                assert body["slo_exemplars"] == [
                    {"instance": "i-a", **ex}
                ]
                assert body["slo_requests_violated"] == 1
                assert "per_instance" not in body
            finally:
                await client.close()

        asyncio.run(scenario())
    finally:
        manager.stop_all_instances(timeout=2)
