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


# -- start-up stages, program compiles, slow scheduler entries -----------------


@pytest.fixture
def fresh_startup():
    """The start-up table is process-global and frozen by the first app
    that listened in this process: each of these tests starts with a new
    one and leaves a new one."""
    from conftest import stop_listening_to_compiles

    from llm_d_fast_model_actuation_tpu.utils import compile_cache

    def clean():
        tracing.reset_startup()
        compile_cache._by_program.clear()
        compile_cache._thread.__dict__.clear()

    clean()
    listening = compile_cache._listening
    yield tracing
    clean()
    if not listening:
        stop_listening_to_compiles()


def _start_an_engine(sleep=lambda s: None):
    """What run_server, EngineService and InferenceEngine do, in order."""
    tracing.server_imported()
    tracing.startup_begin(False, model="m")
    for name in tracing.STAGES[2:-1]:
        with tracing.stage(name) as st:
            st.set(bytes=7)
            sleep(name)
    tracing.stage("start.listen").__enter__()
    tracing.startup_ready()


@pytest.mark.tracing
def test_stages_fill_the_table_and_close_it(fresh_startup):
    import time

    _start_an_engine(lambda name: time.sleep(0.002))
    block = tracing.startup_stats()
    assert set(block["stage_s"]) == {
        "import", "entry", "backend", "resolve", "weights", "pool",
        "programs", "sleeper", "listen",
    }
    for name in ("backend", "resolve", "weights", "pool", "programs", "sleeper"):
        assert block["stage_s"][name] >= 0.002
    assert block["stage_s"]["import"] > 0
    assert sum(block["stage_s"].values()) + block["other_s"] == pytest.approx(
        block["ready_s"]
    )
    assert 0 <= block["other_s"] < block["ready_s"]
    assert block["process_start_source"] in ("proc", "import")
    assert block["backend_made_in"] == "backend"
    assert block["backend_s"] == block["stage_s"]["backend"]
    # engine.start, back-dated to the process's start, and its nine children
    spans = tracing.snapshot()
    (start,) = [s for s in spans if s.name == "engine.start"]
    assert start.duration_s == pytest.approx(block["ready_s"], abs=1e-3)
    assert start.attrs["process_start_unix"] == pytest.approx(
        block["process_start_unix"], abs=1e-3
    )
    kids = [s for s in spans if s.parent_id == start.span_id]
    assert sorted(s.name for s in kids) == sorted(tracing.STAGES)
    assert {s.attrs.get("bytes") for s in kids if s.name == "start.pool"} == {7}


@pytest.mark.tracing
def test_the_table_is_frozen_once_the_listener_accepts(fresh_startup):
    _start_an_engine()
    frozen = tracing.startup_stats()
    n = tracing.buffer_len()
    # a later swap builds through the same code: nothing is rewritten
    with tracing.stage("start.weights") as st:
        assert not st.timing
        st.set(bytes=1)
    tracing.startup_begin(True)
    tracing.startup_ready()
    assert tracing.startup_stats() == frozen
    assert tracing.buffer_len() == n
    # until the listener accepts, ready_s is the seconds so far
    tracing.reset_startup()
    a = tracing.startup_stats()["ready_s"]
    assert tracing.startup_stats()["ready_s"] > a


@pytest.mark.tracing
def test_backend_made_by_the_entry_point_is_read_from_start_entry(fresh_startup):
    tracing.server_imported()
    tracing.startup_begin(True)
    block = tracing.startup_stats()
    assert block["backend_made_in"] == "entry"
    assert block["backend_s"] == block["stage_s"]["entry"] > 0


@pytest.mark.tracing
def test_tracing_off_fills_the_table_and_records_no_span(fresh_startup):
    import jax
    import jax.numpy as jnp

    from llm_d_fast_model_actuation_tpu.utils import compile_cache

    compile_cache.listen()
    tracing.disable()

    def work(name):
        if name == "start.weights":
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()

    _start_an_engine(work)
    block = tracing.startup_stats()
    assert block["stage_s"]["weights"] > 0 and block["ready_s"] > 0
    cc = compile_cache.stats()
    assert cc["by_program"]["jit(<lambda>)"]["n"] == 1
    assert cc["trace_s"] > 0 and cc["lower_s"] > 0 and cc["backend_s"] > 0
    assert tracing.buffer_len() == 0
    # and with it on, the same work leaves its spans under the stage
    tracing.enable()
    tracing.reset_startup()
    _start_an_engine(
        lambda name: name == "start.weights"
        and jax.jit(lambda x: x * 3 - 1)(jnp.ones(3)).block_until_ready()
    )
    spans = tracing.snapshot()
    (weights,) = [s for s in spans if s.name == "start.weights"]
    under = [s for s in spans if s.parent_id == weights.span_id]
    assert {"program.trace", "program.lower", "program.compile"} <= {
        s.name for s in under
    }
    assert all(s.attrs["program"].startswith("jit(") for s in under)
    (compiled,) = [s for s in under if s.attrs["program"] == "jit(<lambda>)"
                   and s.name == "program.compile"]
    assert compiled.attrs["cache_hit"] is False
    assert weights.start_s <= compiled.start_s <= compiled.end_s <= weights.end_s + 1e-3


TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"


@pytest.mark.tracing
def test_a_nested_traced_function_is_counted_once(fresh_startup):
    """jax's events end innermost first; a thread's seconds are the union
    of its intervals, each second under the innermost event's kind and the
    outermost program's name."""
    from llm_d_fast_model_actuation_tpu.utils import compile_cache as cc

    # chunk traces 0..10; inside it `_where` 1..2 and `inner` 3..7, inside
    # which `multiply` 4..5; and a constant folded eagerly, 8..9.5: traced
    # 8..8.5, lowered 8.5..9, compiled 9..9.5
    for event, start, end, name in (
        (TRACE, 1.0, 2.0, "_where"),
        (TRACE, 4.0, 5.0, "multiply"),
        (TRACE, 3.0, 7.0, "inner"),
        (TRACE, 8.0, 8.5, "iota"),
        (LOWER, 8.5, 9.0, "jit(iota)"),
        (COMPILE, 9.0, 9.5, "jit(iota)"),
        (TRACE, 0.0, 10.0, "chunk"),
        (LOWER, 10.0, 12.0, "jit(chunk)"),
        (COMPILE, 12.0, 12.25, "jit(chunk)"),
    ):
        cc._on_time_span(event, start, end, fun_name=name)
    block = cc.stats()
    assert set(block["by_program"]) == {"jit(chunk)", "jit(iota)"}
    assert block["by_program"]["jit(chunk)"] == {
        "n": 1, "seconds": 0.25, "trace_s": 9.0, "lower_s": 2.5,
    }
    assert block["by_program"]["jit(iota)"] == {
        "n": 1, "seconds": 0.5, "trace_s": 0.0, "lower_s": 0.0,
    }
    # 12.25 s of one thread, each counted once
    assert block["trace_s"] + block["lower_s"] + block["backend_s"] == 12.25
    assert block["backend_s"] == sum(
        p["seconds"] for p in block["by_program"].values()
    )
    # the real thing: every jnp function a program calls is traced inside it
    import jax
    import jax.numpy as jnp

    cc.listen()
    before = set(cc.stats()["by_program"])

    @jax.jit
    def helper(x):
        return jnp.where(x > 0, x, 0) * 2

    def outer_program(x):
        return helper(x) + jnp.clip(x, 0, 1)

    jax.jit(outer_program)(jnp.ones(4)).block_until_ready()
    after = cc.stats()["by_program"]
    new = set(after) - before
    assert "jit(outer_program)" in new
    assert not {"jit(helper)", "jit(_where)", "jit(clip)"} & new
    assert after["jit(outer_program)"]["trace_s"] > 0


@pytest.mark.tracing
@pytest.mark.parametrize("fun_name", ["chunk", "jit_chunk", "jit(chunk)"])
def test_the_three_names_jax_gives_a_program_fold_to_one_key(fun_name):
    from llm_d_fast_model_actuation_tpu.utils import compile_cache as cc

    assert cc.program_name(fun_name) == "jit(chunk)"
    assert cc.program_name("pmap_step") == "pmap(step)"
    assert cc.program_name("<lambda>") == "jit(<lambda>)"


@pytest.mark.tracing
@pytest.mark.parametrize("name,seconds,slow", [
    ("sched.intake", 0.06, True),       # PR 37's stalled run: 2.7 s here
    ("sched.admit", 0.019, False),      # the longest steady entry
    ("sched.chunk_fetch", 0.3, False),  # a chunk is 86-300 ms
    ("sched.chunk_fetch", 1.2, True),
    ("sched.prefill_fetch", 1.2, True),
    ("sched.wait", 30.0, False),        # idle is not a stall
])
def test_a_slow_phase_entry_counts_and_a_fast_one_does_not(
    name, seconds, slow, fresh_startup, caplog
):
    import logging

    tracing.reset_after_fork()
    tracing.startup_ready()     # serving: a slow entry is also logged
    tracing.describe_slow_entries_with(
        lambda overlapped: f"live sequences 3, in flight: {overlapped}"
    )
    try:
        with caplog.at_level(logging.WARNING, logger=tracing.logger.name):
            with tracing.phase(name) as p:
                p._t0 -= seconds
        block = tracing.phase_stats()
        key = name.partition(".")[2]
        if slow:
            assert block["slow_entries"] == 1
            assert block["slow_s"] == pytest.approx(seconds, abs=0.01)
            assert block["slow_by_phase"] == {key: block["slow_s"]}
            assert name in caplog.text and "live sequences 3" in caplog.text
        else:
            assert block["slow_entries"] == 0 and block["slow_s"] == 0.0
            assert block["slow_by_phase"] == {} and not caplog.text
        assert block["phase_n"][key] == 1
    finally:
        tracing.describe_slow_entries_with(None)
        tracing.reset_after_fork()
