"""The scheduler loop's own phases (docs/tracing.md "Scheduler phases"):
``sched.*`` counters always, spans and profiler annotations only while a
capture runs, a ``/v1/profile`` that starts without the Python tracer, a
compile line by program, and the program names the benchmark keys on."""

import threading
import time
from unittest import mock

import jax
import jax.numpy as jnp
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_fast_model_actuation_tpu.utils import compile_cache, tracing

SLOTS = 2
PROMPT = [3, 1, 4, 1, 5, 9, 2, 6]


@pytest.fixture(autouse=True)
def _clean_tracing():
    tracing.enable()
    tracing.clear()
    tracing.clear_requests()
    yield
    tracing.capture_stopped()
    tracing.clear()
    tracing.clear_requests()


def _stop_leaked_loops():
    """The phase table is per process and belongs to ONE scheduler thread:
    a service that an earlier failing test of this worker left running
    would add its own waits to it."""
    for t in threading.enumerate():
        target = getattr(t, "_target", None)
        if t.name == "engine-loop" and t.is_alive() and target is not None:
            target.__self__.shutdown()


def _service(extra: str = ""):
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    return EngineService(
        parse_engine_options(
            f"--model tiny --num-pages 64 --page-size 8 --max-batch {SLOTS} "
            "--max-model-len 64 --decode-chunk 4 " + extra
        )
    )


@pytest.fixture(scope="module")
def service():
    _stop_leaked_loops()
    svc = _service()
    yield svc
    svc.shutdown()


def _serve(svc, n: int, max_tokens: int = 24):
    """n greedy requests at once; returns when all have finished."""
    futs = [svc.submit(PROMPT, max_tokens, 0.0) for _ in range(n)]
    for f in futs:
        assert f.result(timeout=120).out_tokens


def _sched(svc):
    return svc.stats()["scheduler"]


def test_every_phase_counts_and_the_sum_is_the_loops_wall_time(service):
    _serve(service, 1)  # the programs compile outside the timed stretch
    time.sleep(0.12)
    before, t0 = _sched(service), time.monotonic()
    _serve(service, 3 * SLOTS)
    time.sleep(max(0.0, 1.5 - (time.monotonic() - t0)))
    after, wall = _sched(service), time.monotonic() - t0
    names = {p.partition(".")[2] for p in tracing.PHASES}
    assert set(after["phase_s"]) == set(after["phase_n"]) == names
    for name in names:
        assert after["phase_n"][name] > before["phase_n"][name], name
        assert after["phase_s"][name] >= before["phase_s"][name], name
    spent = {k: after["phase_s"][k] - before["phase_s"][k] for k in names}
    # nothing of the loop lies outside a phase but the lock and a few
    # branches; a phase open at either reading is at most one 50 ms wait
    assert abs(sum(spent.values()) - wall) <= 0.10 * wall, (spent, wall)
    host_only = after["host_only_s"] - before["host_only_s"]
    not_waiting = sum(
        s for k, s in spent.items()
        if "sched." + k not in tracing.WAITING_PHASES
    )
    # the default loop has no chunk in flight during any phase: host-only
    # is every phase but the fetches and the wait, less what a step's
    # second and later prompts spend under the one before them
    assert 0 < host_only <= not_waiting + 1e-6
    assert "prefills_overlapped" in after
    assert spent["wait"] > 0 and spent["chunk_fetch"] > 0
    assert host_only < sum(spent.values()) - spent["wait"]


def _engine(slots: int = 4):
    from llm_d_fast_model_actuation_tpu.engine import (
        EngineConfig,
        InferenceEngine,
    )
    from llm_d_fast_model_actuation_tpu.models import llama

    return InferenceEngine(
        EngineConfig(
            model=llama.LlamaConfig.tiny(), max_batch=slots, page_size=8,
            num_pages=64, max_seq_len=64, decode_chunk=4,
        ),
        seed=0,
    )


def _phase_rows(names):
    return {
        n: (
            tracing._PHASES["sched." + n].seconds,
            tracing._PHASES["sched." + n].host_only_s,
            tracing._PHASES["sched." + n].count,
        )
        for n in names
    }


def test_host_only_keeps_a_steps_first_prompt_and_leaves_out_the_overlapped():
    """`sched.admit` and `sched.prefill_dispatch` of a step's first prompt
    run before an idle chip and are `host_only_s`; those of its second and
    third run under the prompt before them and are not; nor is a first
    token's `sched.emit` with the next prompt dispatched."""
    eng = _engine()
    eng.generate([PROMPT], max_new_tokens=2)  # compiled
    names = ("admit", "prefill_dispatch", "emit")
    me = threading.current_thread().name
    # one waiting: the step is the serial one, every second host-only
    before, n0 = _phase_rows(names), tracing.phase_stats()["prefills_overlapped"]
    eng.add_request(PROMPT, 2)
    eng.step()
    after = _phase_rows(names)
    for n in names:
        seconds, host_only, count = (a - b for a, b in zip(after[n], before[n]))
        assert count >= 1 and seconds > 0
        assert host_only == pytest.approx(seconds, abs=1e-9), n
    assert tracing.phase_stats()["prefills_overlapped"] == n0
    while eng.has_work():
        eng.step()
    # three waiting, read through a capture's spans
    with mock.patch("jax.profiler.TraceAnnotation"):
        tracing.capture_started()
        before = _phase_rows(names)
        for i in range(3):
            eng.add_request(PROMPT[i:], 2)
        eng.step()
        after = _phase_rows(names)
        tracing.capture_stopped()
    assert tracing.phase_stats()["prefills_overlapped"] == n0 + 2
    spans = sorted(
        (
            s for s in tracing.snapshot()
            if s.name.startswith("sched.") and s.thread == me
        ),
        key=lambda s: s.start_s,
    )
    order = [s.name.partition(".")[2] for s in spans]
    assert order == [
        "admit", "prefill_dispatch",
        "admit", "prefill_dispatch", "prefill_fetch", "emit",
        "admit", "prefill_dispatch", "prefill_fetch", "emit",
        "prefill_fetch", "emit",
        "upload", "chunk_dispatch", "chunk_fetch", "emit",
    ]
    # phases do not nest: each ends before the next begins
    for a, b in zip(spans, spans[1:]):
        assert a.end_s <= b.start_s, (a.name, b.name)
    dispatches = [s for s in spans if s.name == "sched.prefill_dispatch"]
    assert [s.attrs["overlapped"] for s in dispatches] == [0, 1, 1]
    for n in ("admit", "prefill_dispatch"):
        seconds, host_only, count = (a - b for a, b in zip(after[n], before[n]))
        first = next(s for s in spans if s.name == "sched." + n)
        assert count == 3
        # the span encloses the phase's own two clock reads
        assert 0 < host_only <= first.duration_s
        assert host_only < seconds
    seconds, host_only, count = (
        a - b for a, b in zip(after["emit"], before["emit"])
    )
    assert count == 4 and 0 < host_only < seconds


def test_admit_blocked_counts_steps_with_more_waiting_than_slots(service):
    before = _sched(service)["admit_blocked"]
    _serve(service, SLOTS)
    assert _sched(service)["admit_blocked"] == before
    _serve(service, 3 * SLOTS)
    assert _sched(service)["admit_blocked"] > before


def test_pipelined_dispatch_beside_a_chunk_in_flight_is_not_host_only(service):
    # the module's idle service only adds waits and intakes to the table
    svc = _service("--pipeline-decode on")
    try:
        _serve(svc, 1)
        before = _sched(svc)
        _serve(svc, SLOTS, max_tokens=40)
        after = _sched(svc)
    finally:
        svc.shutdown()
    spent = {
        k: after["phase_s"][k] - before["phase_s"][k]
        for k in after["phase_s"]
    }
    not_waiting = sum(
        s for k, s in spent.items()
        if "sched." + k not in tracing.WAITING_PHASES
    )
    host_only = after["host_only_s"] - before["host_only_s"]
    assert spent["chunk_dispatch"] > 0
    # chunk k+1 is dispatched, and chunk k emitted, while another runs
    assert host_only < not_waiting


def test_no_capture_no_sched_span_and_no_annotation(service):
    with mock.patch("jax.profiler.TraceAnnotation") as ann:
        _serve(service, SLOTS + 1)
        time.sleep(0.12)
    assert ann.call_count == 0
    for span in tracing.snapshot() + tracing.request_snapshot():
        assert not span.name.startswith("sched."), span.name


def test_capture_flag_leaves_one_span_and_one_annotation_per_phase(service):
    with mock.patch("jax.profiler.TraceAnnotation") as ann:
        tracing.capture_started()
        _serve(service, SLOTS + 1)
        time.sleep(0.12)
        tracing.capture_stopped()
        time.sleep(0.15)  # a wait that began inside the capture ends
        annotated = [c.args[0] for c in ann.call_args_list]
        entered = ann.return_value.__enter__.call_count
        exited = ann.return_value.__exit__.call_count
    assert annotated[0] == "fma.clock"
    assert ann.call_args_list[0].kwargs["wall_us"] == pytest.approx(
        time.time() * 1e6, abs=60e6
    )
    assert entered == exited == len(annotated)
    spans = [s for s in tracing.snapshot() if s.name.startswith("sched.")]
    assert not any(
        s.name.startswith("sched.") for s in tracing.request_snapshot()
    )
    for name in tracing.PHASES:
        n_spans = sum(1 for s in spans if s.name == name)
        assert n_spans == annotated.count(name) >= 1, name
    assert set(annotated) == set(tracing.PHASES) | {"fma.clock"}
    by_name = {s.name: s for s in spans}
    assert by_name["sched.upload"].attrs["why"] in ("dirty", "no_dev")
    assert by_name["sched.upload"].attrs["bytes"] > 0
    assert by_name["sched.chunk_dispatch"].attrs["T"] in (1, 4)
    assert by_name["sched.prefill_dispatch"].attrs["program"] == "prefill"
    assert "admitted" in by_name["sched.admit"].attrs
    # and the table kept counting all the while
    assert _sched(service)["phase_n"]["emit"] > 0


def test_emit_counts_one_delivery_a_request_a_chunk(service):
    """`emit_deliveries` counts calls of the streaming hook, `emit_tokens`
    what they carried: the first token alone, then one run a drained
    chunk; a chunk's `sched.emit` span says how many it made."""
    runs = []
    before = _sched(service)
    with mock.patch("jax.profiler.TraceAnnotation"):
        tracing.capture_started()
        futs = [
            service.submit(
                PROMPT, 21, 0.0, on_tokens=lambda r, run: runs.append(len(run))
            ),
            service.submit(PROMPT, 21, 0.0),  # nobody listens: no delivery
        ]
        for f in futs:
            assert len(f.result(timeout=120).out_tokens) == 21
        tracing.capture_stopped()
    after = _sched(service)
    # 1 + 20 tokens in five chunks of --decode-chunk 4
    assert runs == [1, 4, 4, 4, 4, 4]
    assert after["emit_deliveries"] - before["emit_deliveries"] == 6
    assert after["emit_tokens"] - before["emit_tokens"] == 21
    chunk_emits = [
        s.attrs for s in tracing.snapshot()
        if s.name == "sched.emit" and "deliveries" in s.attrs
    ]
    assert sum(a["deliveries"] for a in chunk_emits) == 5
    assert sum(a["tokens"] for a in chunk_emits) == 40


@pytest.mark.parametrize(
    "name, cell, at_open, at_close, want",
    [
        ("sched_emit_s.batch", "mixtral-8x7b.batch",
         {"phase_s": {"emit": 0.5, "upload": 1.0}},
         {"phase_s": {"emit": 1.75, "upload": 9.0}}, 1.25),
        ("sched_emit_s.longmix", "smallthinker-21b.longmix",
         {"phase_s": {"emit": 0.5, "upload": 1.0}},
         {"phase_s": {"emit": 1.75, "upload": 9.0}}, 1.25),
        ("prefills_overlapped.batch", "mixtral-8x7b.batch",
         {"prefills_overlapped": 40, "admit_blocked": 1},
         {"prefills_overlapped": 425, "admit_blocked": 9}, 385),
    ],
)
def test_scheduler_metric_reads_its_counter_in_the_one_cell_it_names(
    name, cell, at_open, at_close, want
):
    """The benchmark's `sched_emit_s.*` and `prefills_overlapped.batch` are
    data files that read this module's `scheduler` block, close less open,
    in the one cell they name."""
    from fmabench import readers, spec

    bench = spec.benchmark()
    rows = {m["name"]: m for m in spec.Cell(bench, cell).per_layer()}
    assert rows[name]["layer"] == "scheduler"
    assert rows[name]["moves"] == "out_tokens_per_s"
    assert rows[name]["workloads"] == [cell]
    ev = readers.Evidence()
    ev.stats_open = {"scheduler": at_open}
    ev.stats_close = {"scheduler": at_close}
    assert readers.read_metric(rows[name]["reader"], ev) == want
    # a program with no such counter (the parent of the PR that brought
    # it) gives nothing, not a zero
    ev.stats_open = ev.stats_close = {"scheduler": {"admit_blocked": 3}}
    assert readers.read_metric(rows[name]["reader"], ev) is None
    for w in bench["workloads"]:
        if w["name"] != cell:
            assert name not in {
                m["name"] for m in spec.Cell(bench, w["name"]).per_layer()
            }


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


def test_profile_starts_without_the_python_tracer_and_marks_the_clock(
    service, tmp_path
):
    from llm_d_fast_model_actuation_tpu.engine.server import build_app

    log_dir = str(tmp_path / "prof")
    with mock.patch("jax.profiler.start_trace") as start, \
            mock.patch("jax.profiler.stop_trace") as stop, \
            mock.patch("jax.profiler.TraceAnnotation") as ann:
        service.start_profile(log_dir)
        assert start.call_args.args == (log_dir,)
        assert start.call_args.kwargs["profiler_options"].python_tracer_level == 0
        assert tracing.capturing()
        assert [c.args[0] for c in ann.call_args_list] == ["fma.clock"]
        assert "wall_us" in ann.call_args_list[0].kwargs
        # a stop that raises leaves the capture marked running for a
        # retry, but the phases stop annotating at once
        stop.side_effect = RuntimeError("export failed")
        with pytest.raises(RuntimeError):
            service.stop_profile()
        assert not tracing.capturing()
        assert service.profile_status()["profiling"] is True
        stop.side_effect = None
        assert service.stop_profile() == {"profiling": False, "log_dir": log_dir}
        capture = [s for s in tracing.snapshot() if s.name == "profile.capture"]
        assert len(capture) == 1 and capture[0].attrs["log_dir"] == log_dir

        async def scenario(client):
            r = await client.post(
                "/v1/profile", json={"log_dir": log_dir, "python_tracer": "yes"}
            )
            assert r.status == 400
            r = await client.post(
                "/v1/profile", json={"log_dir": log_dir, "python_tracer": True}
            )
            assert r.status == 200, await r.text()
            assert (await r.json())["python_tracer"] is True
            assert start.call_args.kwargs["profiler_options"].python_tracer_level > 0
            r = await client.delete("/v1/profile")
            assert r.status == 200

        _run_client(build_app(service), scenario)
    assert not tracing.capturing()


def test_compile_cache_counts_by_program_and_logs_once_serving(
    monkeypatch, caplog
):
    compile_cache.listen()

    def fma_sched_test_program(x):
        return x * 2 + 1

    jax.jit(fma_sched_test_program)(jnp.ones((3,))).block_until_ready()
    row = compile_cache.stats()["by_program"]["jit(fma_sched_test_program)"]
    assert row["n"] == 1 and row["seconds"] > 0
    monkeypatch.setattr(compile_cache, "_serving", False)
    compile_cache.serving()
    with caplog.at_level("INFO", logger=compile_cache.logger.name):
        jax.jit(fma_sched_test_program)(jnp.ones((5,))).block_until_ready()
    assert "compiled jit(fma_sched_test_program) in" in caplog.text
    assert compile_cache.stats()["by_program"][
        "jit(fma_sched_test_program)"]["n"] == 2


@pytest.mark.parametrize("program,bucket,module", [
    ("chunk", 4, "jit_chunk"),
    ("prefill", 16, "jit__prefill"),
    ("suffix", 16, "jit__suffix_prefill"),
])
def test_serving_programs_keep_the_names_the_benchmark_keys_on(
    program, bucket, module
):
    """``fmabench/metrics/decode_step_dev_ms.*`` match ``^jit_chunk`` in
    the profiler's XLA Modules line and PERF.md's breakdowns name the two
    prefill programs: a rename has to fail here, not empty a metric."""
    from llm_d_fast_model_actuation_tpu.engine import exec_pool
    from llm_d_fast_model_actuation_tpu.engine.engine import EngineConfig
    from llm_d_fast_model_actuation_tpu.models import llama

    cfg = EngineConfig(
        model=llama.LlamaConfig.tiny(), max_batch=2, page_size=8,
        num_pages=16, max_seq_len=32,
    )
    ps = exec_pool._program_set(cfg)
    fn = ps.chunk(bucket) if program == "chunk" else getattr(ps, program)
    lowered = fn.lower(*exec_pool.abstract_args(cfg, program, bucket))
    assert f"module @{module} " in lowered.as_text()
