"""The ten metrics of PR 38: eight ``setup_*`` numbers that read the
program's own account of its start (``/v1/stats`` ``startup`` and the
extended ``compile_cache`` block) and two that read the scheduler's slow
entries. Their files resolve through ``spec`` in exactly their cells, read
the number out of a document of the shape the program gives (one case
takes it from a real engine on the CPU), and find nothing (not a zero) on a
program that has no such block."""

import pytest

from fmabench import readers, spec

BENCH = spec.benchmark()
ALL_CELLS = [w["name"] for w in BENCH["workloads"]]
#: not `ouro-2.6b.loopchat` nor `olmo-hybrid-7b.hybridmix`: each has an
#: accepted test that pins its cell's set of metrics (PERF.md section 7)
CELLS = ["mixtral-8x7b.batch", "mistral-7b.chat", "smallthinker-21b.longmix"]
TOKEN_CELLS = [c for c in CELLS if c != "mistral-7b.chat"]

#: metric -> (its cells, layer, what it moves, the value the evidence holds)
METRICS = {
    "setup_ready_s": (CELLS, "launcher", "setup_s", 14.5),
    "setup_import_s": (CELLS, "launcher", "setup_s", 3.25),
    "setup_backend_s": (CELLS, "device", "setup_s", 7.5),
    "setup_weights_s": (CELLS, "transfer", "setup_s", 1.75),
    "setup_pool_s": (CELLS, "cache", "setup_s", 0.5),
    "setup_trace_s": (CELLS, "compile", "setup_s", 6.0),
    "setup_lower_s": (CELLS, "compile", "setup_s", 5.0),
    "setup_load_s": (CELLS, "compile", "setup_s", 3.5),
    "sched_slow_s": (TOKEN_CELLS, "scheduler", "out_tokens_per_s", 2.75),
    "sched_slow_s.chat": (["mistral-7b.chat"], "scheduler", "tpot_p95_ms", 2.75),
}


def stats_document(slow_s, ladder_s=0.0):
    """``GET /v1/stats`` as the program gives it (the blocks read here)."""
    return {
        "startup": {
            "process_start_unix": 1790000000.0, "process_start_source": "proc",
            "ready_s": 14.5, "backend_made_in": "entry", "backend_s": 7.5,
            "stage_s": {"import": 3.25, "entry": 7.5, "backend": 0.001,
                        "resolve": 1.0, "weights": 1.75, "pool": 0.5,
                        "programs": 0.001, "sleeper": 0.001, "listen": 0.002},
            "other_s": 0.495,
        },
        "compile_cache": {
            "dir": "/x", "requests": 21, "hits": 21, "writes": 0,
            "trace_s": 1.0 + ladder_s, "lower_s": 0.5 + ladder_s / 2,
            "backend_s": 0.25 + ladder_s / 4, "retrieval_s": 0.2,
            "by_program": {"jit(chunk)": {"n": 2, "seconds": 0.37,
                                          "trace_s": 0.9, "lower_s": 0.4}},
        },
        "scheduler": {"phase_s": {"intake": 0.1}, "host_only_s": 4.0,
                      "slow_entries": 12, "slow_s": slow_s,
                      "slow_by_phase": {"intake": slow_s}},
    }


def evidence():
    ev = readers.Evidence()
    # the start is over at /health; the ladder's compiles come before the
    # window opens; the stall is the window's
    ev.stats_open = stats_document(slow_s=18.0, ladder_s=0.0)
    ev.stats_open["compile_cache"].update(trace_s=6.0, lower_s=5.0, backend_s=3.5)
    ev.stats_close = stats_document(slow_s=20.75)
    ev.wall_open, ev.wall_close = 1000.0, 1050.0
    return ev


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_resolves_in_exactly_its_cells(name):
    cells, layer, moves, _ = METRICS[name]
    for cell_name in ALL_CELLS:
        rows = {m["name"]: m for m in spec.Cell(BENCH, cell_name).per_layer()}
        assert (name in rows) == (cell_name in cells)
        if name not in rows:
            continue
        row = rows[name]
        assert row["workloads"] == cells      # named, so a later cell is not bound
        assert (row["layer"], row["moves"]) == (layer, moves)
        assert (row["unit"], row["better"]) == ("s", "lower")
        assert row["source"] == "program_counter"
        assert row["reader"] == spec.metric_file(name)
        assert row["reader"]["kind"] == "stats"
        assert row["reader"]["at"] == ("delta" if name.startswith("sched_") else "open")
    # appended: the accepted entries stand before them, in their order
    names = [m["name"] for m in BENCH["per_layer"]]
    assert names.index(name) >= len(names) - len(METRICS)


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_the_programs_own_number(name):
    value = METRICS[name][3]
    assert readers.read_metric(spec.metric_file(name), evidence()) == value


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_the_block_gives_nothing_and_does_not_raise(name):
    """The parent commit: no ``startup`` block, a ``compile_cache`` block
    without the totals, a ``scheduler`` block without the slow entries."""
    ev = readers.Evidence()
    for doc in (ev.stats_open, ev.stats_close):
        doc.update({
            "compile_cache": {"requests": 21, "hits": 21, "by_program": {
                "jit(chunk)": {"n": 2, "seconds": 0.37}}},
            "scheduler": {"phase_s": {"intake": 0.1}, "host_only_s": 4.0},
        })
    ev.wall_open, ev.wall_close = 1000.0, 1050.0
    assert readers.read_metric(spec.metric_file(name), ev) is None
    assert readers.read_metric(spec.metric_file(name), readers.Evidence()) is None


@pytest.fixture(scope="module")
def real_stats():
    """``EngineService.stats()`` of a real engine on the CPU, at its start
    and after it served a request (which compiles: slow entries, traces)."""
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService, parse_engine_options,
    )
    from llm_d_fast_model_actuation_tpu.utils import compile_cache, tracing

    tracing.reset_startup()
    listening = compile_cache._listening
    compile_cache.listen()
    svc = EngineService(parse_engine_options(
        "--model tiny --num-pages 16 --page-size 8 --max-batch 2 "
        "--max-model-len 32"
    ))
    try:
        tracing.startup_ready()
        first = svc.stats()
        svc.submit([1, 2, 3], 4, 0.0).result(timeout=120)
        yield first, svc.stats()
    finally:
        svc.shutdown()
        tracing.reset_startup()
        if not listening:
            from conftest import stop_listening_to_compiles

            stop_listening_to_compiles()


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_reads_a_real_engines_stats(name, real_stats):
    ev = readers.Evidence()
    ev.stats_open, ev.stats_close = real_stats
    value = readers.read_metric(spec.metric_file(name), ev)
    assert isinstance(value, float) and value >= 0.0
    if name in ("setup_ready_s", "setup_weights_s", "setup_pool_s"):
        assert value > 0.0
    if name.startswith("sched_"):
        # the first request compiled its programs inside a dispatch
        assert value > 0.05


def test_a_real_engines_blocks_close(real_stats):
    first, later = real_stats
    start = first["startup"]
    assert later["startup"] == start        # frozen
    assert sum(start["stage_s"].values()) + start["other_s"] == pytest.approx(
        start["ready_s"])
    assert start["backend_s"] == start["stage_s"][start["backend_made_in"]]
    cc = later["compile_cache"]
    assert cc["backend_s"] == pytest.approx(
        sum(p["seconds"] for p in cc["by_program"].values()), abs=1e-6)
    assert cc["trace_s"] == pytest.approx(
        sum(p["trace_s"] for p in cc["by_program"].values()), abs=1e-6)
    for name in ("jit(chunk)", "jit(_prefill)"):
        row = cc["by_program"][name]
        assert row["n"] >= 1 and row["trace_s"] > 0 and row["lower_s"] > 0
    sched = later["scheduler"]
    assert sched["slow_s"] == pytest.approx(sum(sched["slow_by_phase"].values()))
    assert sched["slow_entries"] >= 1
