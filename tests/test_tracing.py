"""Actuation tracing (utils/tracing.py): span model, W3C propagation,
bounded ring buffer, Chrome/Perfetto + tree export, the engine's
/v1/traces + /v1/profile surfaces, and the launcher RPC latency metric.
"""

import json
import threading

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_fast_model_actuation_tpu.utils import tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    """Tracing state is process-global: every test starts enabled+empty
    and leaves it that way."""
    tracing.enable()
    tracing.clear()
    yield
    tracing.enable()
    tracing.clear()


# -- span model ---------------------------------------------------------------


@pytest.mark.tracing
def test_span_nesting_parents_and_attrs():
    with tracing.span("outer", kind="root") as outer:
        with tracing.span("inner", bytes=123) as inner:
            assert inner.trace_id == outer.trace_id
            # inner is the current context while open
            assert tracing.current_context().span_id == inner.span_id
        # inner closed: context pops back to outer
        assert tracing.current_context().span_id == outer.span_id
    assert tracing.current_context() is None

    spans = {s.name: s for s in tracing.snapshot()}
    assert spans["inner"].parent_id == spans["outer"].span_id
    assert spans["outer"].parent_id == ""
    assert spans["inner"].attrs["bytes"] == 123
    assert spans["inner"].duration_s >= 0.0
    assert spans["outer"].end_s >= spans["outer"].start_s


@pytest.mark.tracing
def test_span_exception_stamps_error_and_resets_context():
    with pytest.raises(RuntimeError):
        with tracing.span("boom"):
            raise RuntimeError("kaput")
    assert tracing.current_context() is None
    (sp,) = tracing.snapshot()
    assert sp.name == "boom" and "kaput" in sp.attrs["error"]


@pytest.mark.tracing
def test_explicit_parent_for_worker_threads():
    """ContextVars do not cross thread starts: workers must receive the
    parent explicitly — the pattern every instrumented thread pool uses."""
    with tracing.span("root") as root:
        ctx = root.context()

        def worker():
            # ambient context is empty on a fresh thread
            assert tracing.current_context() is None
            with tracing.span("child", parent=ctx):
                pass

        t = threading.Thread(target=worker)
        t.start()
        t.join()
    spans = {s.name: s for s in tracing.snapshot()}
    assert spans["child"].parent_id == spans["root"].span_id
    assert spans["child"].trace_id == spans["root"].trace_id


@pytest.mark.tracing
def test_overlapping_handles_with_activate_false():
    """Pipelined bucket spans: several open at once on one thread, none of
    them becoming the ambient context (no misparenting)."""
    with tracing.span("loop") as root:
        ctx = root.context()
        a = tracing.begin("bucket", parent=ctx, activate=False, bucket=0)
        b = tracing.begin("bucket", parent=ctx, activate=False, bucket=1)
        assert tracing.current_context().span_id == root.span_id
        b.end()
        a.end()
        a.end()  # idempotent
    buckets = [s for s in tracing.snapshot() if s.name == "bucket"]
    assert len(buckets) == 2
    assert {s.parent_id for s in buckets} == {root.span_id}


# -- ring buffer bound --------------------------------------------------------


@pytest.mark.tracing
def test_ring_buffer_is_bounded(monkeypatch):
    buf = tracing.TraceBuffer(capacity=8)
    monkeypatch.setattr(tracing, "_BUFFER", buf)
    for i in range(100):
        with tracing.span(f"s{i}"):
            pass
    assert len(buf) == 8
    # the ring keeps the NEWEST spans
    assert [s.name for s in buf.snapshot()] == [f"s{i}" for i in range(92, 100)]


@pytest.mark.tracing
def test_buffer_capacity_env(monkeypatch):
    monkeypatch.setenv(tracing.BUFFER_ENV_VAR, "16")
    monkeypatch.setenv(tracing.ENV_VAR, "")
    tracing.reset_after_fork()
    try:
        for i in range(50):
            with tracing.span("x"):
                pass
        assert tracing.buffer_len() == 16
    finally:
        monkeypatch.delenv(tracing.BUFFER_ENV_VAR)
        tracing.reset_after_fork()


# -- disabled path ------------------------------------------------------------


@pytest.mark.tracing
def test_disabled_tracing_is_the_noop_singleton():
    """The swap hot loop's contract: when disabled, begin() hands back ONE
    shared object (no per-chunk allocations) and nothing is recorded."""
    tracing.disable()
    assert not tracing.enabled()
    sp = tracing.begin("hot", bytes=1)
    assert sp is tracing.NOOP_SPAN
    assert tracing.begin("hot2") is sp  # same singleton every call
    with tracing.span("ctx") as c:
        assert c is tracing.NOOP_SPAN
    sp.set(x=1).end()
    assert sp.traceparent() is None
    assert tracing.buffer_len() == 0
    assert tracing.current_traceparent() is None


# -- W3C traceparent ----------------------------------------------------------


@pytest.mark.tracing
def test_traceparent_roundtrip_and_rejects():
    with tracing.span("root") as root:
        tp = tracing.current_traceparent()
        assert tp == f"00-{root.trace_id}-{root.span_id}-01"
    ctx = tracing.parse_traceparent(tp)
    assert ctx.trace_id == root.trace_id and ctx.span_id == root.span_id
    for bad in (
        None,
        "",
        "junk",
        "00-short-abcdabcdabcdabcd-01",
        "00-" + "0" * 32 + "-" + "1" * 16 + "-01",  # all-zero trace id
        "00-" + "a" * 32 + "-" + "0" * 16 + "-01",  # all-zero span id
        "00-" + "g" * 32 + "-" + "1" * 16 + "-01",  # non-hex
        "00-" + "a" * 32 + "-" + "1" * 16,  # missing flags
    ):
        assert tracing.parse_traceparent(bad) is None, bad


@pytest.mark.tracing
def test_env_context_and_use_context(monkeypatch):
    monkeypatch.setenv(
        tracing.TRACEPARENT_ENV, "00-" + "ab" * 16 + "-" + "cd" * 8 + "-01"
    )
    ctx = tracing.env_context()
    assert ctx.trace_id == "ab" * 16 and ctx.span_id == "cd" * 8
    assert tracing.current_context() is None
    with tracing.use_context(ctx):
        assert tracing.current_context() is ctx
        with tracing.span("adopted"):
            pass
    assert tracing.current_context() is None
    (sp,) = tracing.snapshot()
    assert sp.trace_id == ctx.trace_id and sp.parent_id == ctx.span_id
    # use_context(None) is a no-op, not a clear
    with tracing.use_context(None):
        assert tracing.current_context() is None


# -- export -------------------------------------------------------------------


@pytest.mark.tracing
def test_chrome_export_shape_and_reimport():
    with tracing.span("parent", model="tiny"):
        with tracing.span("child", bytes=42):
            pass
    spans = tracing.snapshot()
    payload = tracing.export_chrome(spans)
    assert payload["displayTimeUnit"] == "ms"
    evs = payload["traceEvents"]
    assert len(evs) == 2
    for e in evs:
        assert {"name", "cat", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["ph"] == "X" and e["ts"] > 0 and e["dur"] >= 0
        assert e["args"]["trace_id"] and e["args"]["span_id"]
    json.dumps(payload)  # serializable as-is

    back = tracing.spans_from_chrome(json.loads(json.dumps(payload)))
    by_name = {s.name: s for s in back}
    orig = {s.name: s for s in spans}
    assert by_name["child"].parent_id == orig["child"].parent_id
    assert by_name["child"].trace_id == orig["child"].trace_id
    assert abs(by_name["child"].duration_s - orig["child"].duration_s) < 1e-3
    assert by_name["child"].attrs["bytes"] == 42


@pytest.mark.tracing
def test_tree_render_indents_children():
    with tracing.span("root"):
        with tracing.span("mid"):
            with tracing.span("leaf", bytes=7):
                pass
    out = tracing.render_tree(tracing.snapshot())
    lines = out.splitlines()
    assert lines[0].startswith("trace ")
    root_i = next(i for i, l in enumerate(lines) if "root" in l)
    mid_i = next(i for i, l in enumerate(lines) if "mid" in l)
    leaf_i = next(i for i, l in enumerate(lines) if "leaf" in l)
    indent = lambda s: len(s) - len(s.lstrip())  # noqa: E731
    assert indent(lines[root_i]) < indent(lines[mid_i]) < indent(lines[leaf_i])
    assert "bytes=7" in lines[leaf_i]


@pytest.mark.tracing
def test_export_http_clear_scoped_to_trace_id():
    """clear=1 composed with trace_id drains ONLY the exported trace —
    a concurrent actuation's spans must never be dropped unexported."""
    import json as _json

    with tracing.span("trace_a") as a:
        pass
    with tracing.span("trace_b"):
        pass
    status, body, ctype = tracing.export_http(
        "chrome", trace_id=a.trace_id, clear=True
    )
    assert status == 200 and ctype == "application/json"
    exported = [e["name"] for e in _json.loads(body)["traceEvents"]]
    assert exported == ["trace_a"]
    remaining = [s.name for s in tracing.snapshot()]
    assert remaining == ["trace_b"]
    # bare clear drains everything; bad format is a 400
    tracing.export_http("chrome", clear=True)
    assert tracing.buffer_len() == 0
    assert tracing.export_http("bogus")[0] == 400


@pytest.mark.tracing
def test_orphan_spans_are_roots_not_dropped():
    with tracing.span("kept"):
        pass
    (kept,) = tracing.snapshot()
    orphan = tracing.Span(
        trace_id=kept.trace_id,
        span_id="f" * 16,
        parent_id="e" * 16,  # parent not in the set (evicted)
        name="orphan",
        start_s=kept.start_s,
        end_s=kept.end_s,
    )
    roots, children = tracing.build_tree([kept, orphan])
    assert {r.name for r in roots} == {"kept", "orphan"}
    assert "orphan" in tracing.render_tree([kept, orphan])


# -- engine service: swap trace + HTTP surfaces -------------------------------


@pytest.fixture(scope="module")
def swap_service():
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    svc = EngineService(
        parse_engine_options(
            "--model tiny --num-pages 16 --page-size 8 --max-batch 2 "
            "--max-model-len 32 --swap-bucket-mib 1 --model-pool-mib 256"
        )
    )
    yield svc
    svc.shutdown()


def _run_client(app, scenario):
    import asyncio

    async def runner():
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            await scenario(client)
        finally:
            await client.close()

    asyncio.run(runner())


@pytest.mark.tracing
def test_swap_records_device_transfer_spans(swap_service):
    """A pool-hit hot-swap yields engine.swap -> swap.transfer ->
    swap.d2h / swap.h2d bucket spans with byte attrs, all one trace."""
    svc = swap_service
    with tracing.span("test.root") as root:
        svc.swap("tiny-gemma")  # cold: tiny parks in the pool
        tracing.clear()  # keep only the pool-hit swap's tree
        svc.swap("tiny")  # pool hit: chunked two-direction transfer
    spans = tracing.snapshot(trace_id=root.trace_id)
    by_id = {s.span_id: s for s in spans}
    names = {s.name for s in spans}
    assert {"engine.swap", "swap.transfer", "swap.d2h", "swap.h2d"} <= names

    swap_sp = next(s for s in spans if s.name == "engine.swap")
    assert swap_sp.attrs["pool_hit"] is True
    xfer = next(s for s in spans if s.name == "swap.transfer")
    assert by_id[xfer.parent_id].name == "engine.swap"
    for s in spans:
        if s.name in ("swap.d2h", "swap.h2d"):
            assert by_id[s.parent_id] is xfer
            assert s.attrs["bytes"] > 0
    # single coherent trace
    assert {s.trace_id for s in spans} == {root.trace_id}


@pytest.mark.tracing
def test_traced_swap_exports_chrome_json_with_transfer_children(swap_service):
    """The artifact an operator loads in Perfetto: a pool-hit swap's tree
    exported as Chrome trace-event JSON, every event complete ("X") and of
    one trace, swap.d2h / swap.h2d under swap.transfer with their bytes."""
    svc = swap_service
    with tracing.span("test.root") as root:
        svc.swap("tiny-gemma")
        svc.swap("tiny")  # pool hit: chunked two-direction transfer
    status, body, _ = tracing.export_http("chrome", trace_id=root.trace_id)
    assert status == 200
    evs = json.loads(body)["traceEvents"]
    assert evs
    for e in evs:
        assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        assert e["ph"] == "X" and e["args"]["trace_id"] == root.trace_id
    by_span = {e["args"]["span_id"]: e for e in evs}
    moved = [e for e in evs if e["name"] in ("swap.d2h", "swap.h2d")]
    assert {e["name"] for e in moved} == {"swap.d2h", "swap.h2d"}
    for e in moved:
        assert by_span[e["args"]["parent_id"]]["name"] == "swap.transfer"
        assert e["args"]["bytes"] > 0


@pytest.mark.tracing
def test_disabled_tracing_records_nothing_on_swap(swap_service):
    svc = swap_service
    tracing.disable()
    svc.swap("tiny-gemma")
    svc.swap("tiny")
    assert tracing.buffer_len() == 0


@pytest.mark.tracing
def test_traces_endpoint_and_traceparent_hop(swap_service):
    """POST /v1/swap with a W3C traceparent: the engine-side tree joins
    the remote trace, and GET /v1/traces exports it as valid Chrome
    trace-event JSON (chrome + tree formats, clear=1 drains)."""
    from llm_d_fast_model_actuation_tpu.engine.server import build_app

    remote_trace = "ab" * 16
    remote_span = "cd" * 8
    header = {"traceparent": f"00-{remote_trace}-{remote_span}-01"}

    async def scenario(client):
        r = await client.post(
            "/v1/swap", json={"model": "tiny-gemma"}, headers=header
        )
        assert r.status == 200, await r.text()

        r = await client.get("/v1/traces")
        assert r.status == 200
        payload = await r.json()
        evs = payload["traceEvents"]
        assert evs
        for e in evs:
            assert {"name", "ph", "ts", "dur", "pid", "tid", "args"} <= set(e)
        swap_evs = [e for e in evs if e["name"] == "engine.swap"]
        assert swap_evs, sorted({e["name"] for e in evs})
        # the hop: engine.swap is a child of the REMOTE span, same trace
        assert swap_evs[-1]["args"]["trace_id"] == remote_trace
        assert swap_evs[-1]["args"]["parent_id"] == remote_span

        r = await client.get("/v1/traces", params={"format": "tree"})
        assert r.status == 200
        assert "engine.swap" in await r.text()

        r = await client.get("/v1/traces", params={"format": "bogus"})
        assert r.status == 400

        r = await client.get("/v1/traces", params={"clear": "1"})
        assert r.status == 200
        r = await client.get("/v1/traces")
        assert (await r.json())["traceEvents"] == []

        # restore the pool-state for sibling tests
        r = await client.post("/v1/swap", json={"model": "tiny"})
        assert r.status == 200

    _run_client(build_app(swap_service), scenario)


@pytest.mark.tracing
def test_profile_endpoints_gate_one_capture(swap_service, tmp_path):
    """POST /v1/profile starts a jax.profiler capture; a second POST is
    409 (one concurrent capture); DELETE stops it; DELETE with none is
    409 — the on-demand deep-profiling runbook (docs/tracing.md)."""
    from llm_d_fast_model_actuation_tpu.engine.server import build_app

    log_dir = str(tmp_path / "prof")

    async def scenario(client):
        r = await client.get("/v1/profile")
        assert (await r.json())["profiling"] is False

        r = await client.post("/v1/profile", json={"log_dir": log_dir})
        assert r.status == 200, await r.text()
        body = await r.json()
        assert body["profiling"] is True and body["log_dir"] == log_dir

        r = await client.post("/v1/profile", json={"log_dir": log_dir})
        assert r.status == 409

        r = await client.get("/v1/profile")
        assert (await r.json())["profiling"] is True

        r = await client.delete("/v1/profile")
        assert r.status == 200, await r.text()
        assert (await r.json()) == {"profiling": False, "log_dir": log_dir}

        r = await client.delete("/v1/profile")
        assert r.status == 409

    _run_client(build_app(swap_service), scenario)
    import os

    assert os.path.isdir(log_dir)  # the capture directory was created


# -- launcher RPC: metric + traceparent injection -----------------------------


@pytest.mark.tracing
def test_launcher_rpc_metric_and_traceparent_header(tmp_path):
    """_engine_request observes fma_launcher_rpc_seconds{verb,outcome} per
    attempt and injects the current traceparent so the engine side joins
    the launcher's trace."""
    from llm_d_fast_model_actuation_tpu.launcher import manager as manager_mod
    from llm_d_fast_model_actuation_tpu.launcher.chiptranslator import (
        ChipTranslator,
    )
    from llm_d_fast_model_actuation_tpu.launcher.instance import InstanceConfig
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        LAUNCHER_RPC_SECONDS,
        EngineProcessManager,
        SwapFailed,
    )

    def fake_kickoff(config, log_path):
        import time as _t

        _t.sleep(3600)

    translator = ChipTranslator.create(mock_chips=True, mock_chip_count=2)
    m = EngineProcessManager(
        translator, log_dir=str(tmp_path), kickoff=fake_kickoff
    )

    def sample(outcome):
        v = LAUNCHER_RPC_SECONDS.labels(
            verb="GET /v1/swap", outcome=outcome
        )._sum.get()
        return v

    seen_headers = {}

    class _Resp:
        def __enter__(self):
            return self

        def __exit__(self, *a):
            return False

        def read(self):
            return json.dumps({"ok": True}).encode()

    def fake_urlopen(req, timeout=None):
        seen_headers.update(req.headers)
        return _Resp()

    orig = manager_mod.urllib.request.urlopen
    manager_mod.urllib.request.urlopen = fake_urlopen
    try:
        m.create_instance(InstanceConfig(options="--model tiny"), "m1")
        ok_before = sample("ok")
        with tracing.span("test.rpc") as root:
            out = m._engine_request(
                "m1", "GET", "/v1/swap", None, 5, SwapFailed
            )
        assert out == {"ok": True}
        assert sample("ok") > ok_before
        # the header crossed (urllib capitalizes)
        ctx = tracing.parse_traceparent(seen_headers.get("Traceparent"))
        assert ctx is not None and ctx.trace_id == root.trace_id
        # and the RPC span is a child of the caller's span
        rpc = next(
            s for s in tracing.snapshot() if s.name == "launcher.rpc"
        )
        assert rpc.parent_id == root.span_id
        assert rpc.attrs["outcome"] == "ok"

        # failure outcome labels: HTTP error -> http_<code>
        import urllib.error

        def failing_urlopen(req, timeout=None):
            raise urllib.error.HTTPError(
                req.full_url, 503, "busy", {}, None
            )

        manager_mod.urllib.request.urlopen = failing_urlopen
        err_before = sample("http_503")
        with pytest.raises(SwapFailed):
            m._engine_request("m1", "GET", "/v1/swap", None, 5, SwapFailed)
        assert sample("http_503") > err_before

        # the family is exposed in the launcher's prometheus exposition
        from prometheus_client import generate_latest

        assert b"fma_launcher_rpc_seconds" in generate_latest()
    finally:
        manager_mod.urllib.request.urlopen = orig
        m.stop_all_instances(timeout=2)
