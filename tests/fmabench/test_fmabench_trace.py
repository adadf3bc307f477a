"""The trace -> metrics reduction on a small trace with known numbers
(``fmabench/testdata/synthetic.xplane.pb``, laid out like a TPU trace; see
``make_xplane.py`` beside it), and the readers on hand-made evidence."""

import os
import re

import pytest

from fmabench import readers, roofline, spec, xplane

TRACE = os.path.join(spec.HERE, "testdata", "synthetic.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    s = xplane.summarize(TRACE)
    assert s is not None
    return s


def test_busy_and_window(summary):
    assert summary.window_s == pytest.approx(0.100)
    assert summary.busy_s == pytest.approx(0.064)


def test_ops_and_modules_by_regex(summary):
    total, n = summary.matching(re.compile("paged_decode"))
    assert (n, total) == (4, pytest.approx(0.008))
    total, n = summary.matching(re.compile("^jit_chunk"), "modules")
    assert (n, total) == (2, pytest.approx(0.024))
    # anchored: a program with "chunk" further inside its name is not counted
    assert summary.matching(re.compile("^chunk"), "modules") == (0.0, 0)
    assert summary.matching(re.compile("no_such_op")) == (0.0, 0)


def test_top_ops_and_idle_gaps(summary):
    top = summary.top_ops(2)
    assert [name for name, _ in top] == ["fusion.9", "fusion.7"]
    assert top[0][1] == pytest.approx(0.040)
    gaps = summary.idle_gaps(2)
    assert gaps[0][0] == "host:device_get"
    assert gaps[0][1] == pytest.approx(0.030)
    assert gaps[1][1] == pytest.approx(0.006)
    named = summary.idle_gaps(1, spans=[("request.decode", 0.0, 0.06)])
    assert named[0][0] == "span:request.decode|host:device_get"


def evidence(summary):
    ev = readers.Evidence()
    ev.trace = summary
    ev.peaks = spec.peaks("TPU v5 lite")
    ev.engine_option = lambda flag, default=None: {"--decode-chunk": "2"}.get(
        flag, default
    )
    return ev


def test_trace_readers(summary):
    ev = evidence(summary)
    idle = readers.read_metric({"kind": "trace_op", "stat": "idle_pct"}, ev)
    assert idle == pytest.approx(36.0)
    step = readers.read_metric(
        spec.metric_file("decode_step_dev_ms.batch"), ev)
    assert step == pytest.approx(6.0)  # 24 ms over 2 calls x 2 steps
    none = readers.read_metric(
        {"kind": "trace_op", "regex": "absent", "stat": "sum_ms"}, ev)
    assert none is None


def test_roofline_reader_and_bytes_function(summary):
    ev = evidence(summary)
    ev.shapes = {"num_kv_heads": 8, "head_dim": 128, "num_heads": 32,
                 "live_kv_tokens": 40000.0, "live_seqs": 50.0}
    need = roofline.paged_decode_step(ev.shapes)
    assert need["bytes"] == 40000 * 8 * 128 * 4 + 50 * 32 * 128 * 4
    pct = readers.read_metric(
        {"kind": "roofline", "regex": "paged_decode",
         "function": "paged_decode_step"}, ev)
    # 164.7 MB at 819 GB/s is 0.2011 ms; the kernel takes 2 ms a call
    assert pct == pytest.approx(100 * (need["bytes"] / 819e9) / 0.002)
    assert 0 < pct < 100
    ev.shapes = {}
    assert readers.read_metric(
        {"kind": "roofline", "regex": "paged_decode",
         "function": "paged_decode_step"}, ev) is None


def test_a_trace_without_a_device_plane_gives_nothing(tmp_path):
    assert xplane.find_xplane(str(tmp_path)) is None


def test_counter_span_and_client_readers():
    ev = readers.Evidence()
    ev.stats_open = {"compile_cache": {"requests": 12, "hits": 9}}
    ev.stats_close = {"compile_cache": {"requests": 13, "hits": 9}}
    ev.wall_open, ev.wall_close = 100.0, 150.0
    gib = 1 << 30
    ev.spans = [
        {"name": "sleep.d2h", "ph": "X", "ts": 101e6, "dur": 2e6, "args": {"bytes": gib}},
        {"name": "sleep.d2h", "ph": "X", "ts": 120e6, "dur": 2e6, "args": {"bytes": gib}},
        {"name": "sleep.d2h", "ph": "X", "ts": 90e6, "dur": 9e6, "args": {"bytes": gib}},
    ]
    ev.series = {"ttft_ms": [float(i) for i in range(1, 101)]}
    ev.prom = {"fma_engine_decode_slot_occupancy": [0.5, 1.0]}
    ev.engine_option = lambda flag, default=None: "64"
    read = readers.read_metric
    assert read({"kind": "stats", "path": "compile_cache.hits",
                 "over": "compile_cache.requests", "at": "ratio_open"}, ev) == 75.0
    assert read({"kind": "stats", "path": "compile_cache.requests",
                 "at": "delta"}, ev) == 1.0
    assert read({"kind": "stats", "path": "no.such", "at": "close"}, ev) is None
    assert read({"kind": "span", "name": "sleep.d2h", "stat": "sum_s"}, ev) == 4.0
    assert read({"kind": "span", "name": "sleep.d2h", "stat": "count"}, ev) == 2.0
    assert read({"kind": "span", "name": "wake.h2d", "stat": "sum_s"}, ev) is None
    assert read({"kind": "client", "series": "ttft_ms", "stat": "p95"}, ev) == 95.0
    assert read({"kind": "client", "series": "absent", "stat": "mean"}, ev) is None
    assert read({"kind": "prom", "name": "fma_engine_decode_slot_occupancy",
                 "scale_by_option": "--max-batch"}, ev) == 48.0
    with pytest.raises(ValueError):
        read({"kind": "no_such_kind"}, ev)


def test_a_roofline_function_is_found_as_a_file(summary):
    ev = evidence(summary)
    ev.shapes = {"hidden_size": 4096, "vocab_size": 32000, "live_seqs": 50.0}
    r = {"kind": "roofline", "regex": "paged_decode", "function": "lm_head_step"}
    with pytest.raises(FileNotFoundError):      # not under fmabench/rooflines/
        readers.read_metric(r, ev)
    ev.data_dir = "fmabench/testdata/examples"
    need_bytes = (4096 * 32000 + 50 * (4096 + 32000)) * 2
    assert readers.read_metric(r, ev) == pytest.approx(
        100 * (need_bytes / 819e9) / 0.002)
    # a function of roofline.py still resolves there first, as before
    ev.shapes.update(num_kv_heads=8, head_dim=128, num_heads=32, live_kv_tokens=4e4)
    assert readers.read_metric({**r, "function": "paged_decode_step"}, ev) > 0
