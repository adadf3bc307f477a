"""The five scheduler metrics of PR 25: their files resolve through
``spec``, read the program's ``/v1/stats`` ``scheduler`` block and its
``request.queue`` spans, and find nothing (not a zero) on a program that has
no such block. And an idle gap under a ``sched.*`` span is named by it, not
by the ``request.*`` span some request is always inside."""

import pytest

from fmabench import readers, spec, xplane

#: metric -> (cell, what it moves, the value the evidence below holds)
METRICS = {
    "sched_host_s.batch": ("mixtral-8x7b.batch", "out_tokens_per_s", 7.5),
    "sched_host_s.chat": ("mistral-7b.chat", "tpot_p95_ms", 7.5),
    "sched_upload_s.batch": ("mixtral-8x7b.batch", "out_tokens_per_s", 3.25),
    "queue_wait_s.chat": ("mistral-7b.chat", "tpot_p95_ms", 0.75),
    "admit_blocked_steps.chat": ("mistral-7b.chat", "tpot_p95_ms", 0.0),
}


def evidence():
    ev = readers.Evidence()
    ev.stats_open = {"scheduler": {
        "phase_s": {"upload": 1.0, "chunk_fetch": 20.0, "wait": 2.0},
        "phase_n": {"upload": 40},
        "host_only_s": 4.0, "admit_blocked": 3,
    }}
    ev.stats_close = {"scheduler": {
        "phase_s": {"upload": 4.25, "chunk_fetch": 55.0, "wait": 2.5},
        "phase_n": {"upload": 370},
        "host_only_s": 11.5, "admit_blocked": 3,
    }}
    ev.wall_open, ev.wall_close = 1000.0, 1050.0
    ev.spans = [
        {"name": "request.queue", "ph": "X", "ts": 1001e6, "dur": 0.25e6},
        {"name": "request.queue", "ph": "X", "ts": 1049e6, "dur": 0.5e6},
        # admitted before the window opened: not this window's wait
        {"name": "request.queue", "ph": "X", "ts": 999e6, "dur": 9e6},
        {"name": "request.decode", "ph": "X", "ts": 1001e6, "dur": 30e6},
    ]
    return ev


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_resolves_and_reads_the_programs_own_count(name):
    cell_name, moves, value = METRICS[name]
    cell = spec.Cell(spec.benchmark(), cell_name)
    rows = {m["name"]: m for m in cell.per_layer()}
    assert rows[name]["moves"] == moves
    assert rows[name]["layer"] == "scheduler"
    assert rows[name]["source"] == "program_counter"
    assert rows[name]["workloads"] == [cell_name]
    assert rows[name]["reader"] == spec.metric_file(name)
    assert readers.read_metric(rows[name]["reader"], evidence()) == value
    # the other cell does not report it
    other = [w["name"] for w in spec.benchmark()["workloads"]
             if w["name"] != cell_name]
    for o in other:
        assert name not in {m["name"] for m in spec.Cell(
            spec.benchmark(), o).per_layer()}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_a_program_without_the_block_gives_nothing_and_does_not_raise(name):
    ev = readers.Evidence()
    ev.stats_open = {"compile_cache": {"requests": 1}}
    ev.stats_close = {"compile_cache": {"requests": 1}}
    ev.wall_open, ev.wall_close = 1000.0, 1050.0
    assert readers.read_metric(spec.metric_file(name), ev) is None


def test_a_gap_under_a_scheduler_phase_is_named_by_it():
    # one device, busy 0-10 ms and 40-50 ms: a 30 ms hole between
    ops = {"/device:TPU:0": [("%fusion.1 = f32[8]{0} fusion()", 0.000, 0.010),
                             ("%fusion.2 = f32[8]{0} fusion()", 0.040, 0.050)]}
    host = [("np.asarray(jax.Array)", 0.0102, 0.0398),
            ("PjitFunction(chunk)", 0.0400, 0.0410)]
    summary = xplane.TraceSummary(ops, host)
    spans = [
        # some request is always decoding: covers the hole, and is long
        ("request.decode", -1.0, 2.0),
        ("request.lifecycle", -2.0, 3.0),
        # the scheduler thread's own phases
        ("sched.chunk_dispatch", 0.0005, 0.0099),
        ("sched.chunk_fetch", 0.0100, 0.0400),
        ("sched.emit", 0.0400, 0.0440),
    ]
    [(name, seconds)] = summary.idle_gaps(1, spans)
    assert name == "span:sched.chunk_fetch|host:np.asarray(jax.Array)"
    assert seconds == pytest.approx(0.030)
    # without the phases the same hole reads as PR 24's traces read it
    [(name, _)] = summary.idle_gaps(1, spans[:2])
    assert name == "span:request.decode|host:np.asarray(jax.Array)"
    # a phase span laid 5 ms off (the start call's length) covers less than
    # the whole hole and loses to the request span: PERF.md section 7
    late = [s if s[0] != "sched.chunk_fetch" else (s[0], 0.015, 0.045)
            for s in spans]
    [(name, _)] = summary.idle_gaps(1, late)
    assert name.startswith("span:request.decode|")
