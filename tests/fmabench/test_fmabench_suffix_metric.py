"""The per-layer metric of PR 43, ``suffix_cols_skipped.longmix``: its file
resolves through ``spec`` in the longmix cell alone, reads the program's
``/v1/stats`` ``kv.suffix_cols_skipped`` over the window, and finds nothing
(not a zero) on a program that has no such counter, as the parent has not."""

import pytest

from fmabench import readers, spec

NAME = "suffix_cols_skipped.longmix"
CELL = "smallthinker-21b.longmix"


def _evidence(open_kv, close_kv):
    ev = readers.Evidence()
    ev.stats_open, ev.stats_close = {"kv": open_kv}, {"kv": close_kv}
    ev.wall_open, ev.wall_close = 1000.0, 1050.0
    return ev


def test_metric_resolves_in_the_longmix_cell_alone():
    bench = spec.benchmark()
    rows = {m["name"]: m for m in spec.Cell(bench, CELL).per_layer()}
    row = rows[NAME]
    assert (row["unit"], row["better"]) == ("columns", "higher")
    assert row["source"] == "program_counter"
    assert row["layer"] == "model step"
    assert row["moves"] == "out_tokens_per_s"
    assert row["workloads"] == [CELL]
    assert row["reader"] == spec.metric_file(NAME)
    # appended: the 77 accepted entries stand before it (a later PR's
    # entries come after, so no "last of the list" here)
    assert [m["name"] for m in bench["per_layer"]].index(NAME) >= 77
    for w in bench["workloads"]:
        if w["name"] != CELL:
            assert NAME not in {
                m["name"] for m in spec.Cell(bench, w["name"]).per_layer()
            }


def test_reader_takes_the_windows_share_of_the_programs_count():
    ev = _evidence(
        {"suffix_cols_row": 500000, "suffix_cols_scored": 300000,
         "suffix_cols_skipped": 200000},
        {"suffix_cols_row": 9500000, "suffix_cols_scored": 4300000,
         "suffix_cols_skipped": 5200000},
    )
    assert readers.read_metric(spec.metric_file(NAME), ev) == 5000000


@pytest.mark.parametrize(
    "open_kv,close_kv",
    [
        # the parent's kv block: no such counter
        ({"window_tokens_evicted": 1200}, {"window_tokens_evicted": 91200}),
        ({}, {}),
        # a program that gained it inside the window: no delta to take
        ({"window_tokens_evicted": 1}, {"suffix_cols_skipped": 7}),
    ],
    ids=["parent", "empty", "half"],
)
def test_a_program_without_the_counter_gives_nothing_and_does_not_raise(
    open_kv, close_kv
):
    ev = _evidence(open_kv, close_kv)
    assert readers.read_metric(spec.metric_file(NAME), ev) is None
    ev.stats_open = ev.stats_close = {"compile_cache": {"requests": 1}}
    assert readers.read_metric(spec.metric_file(NAME), ev) is None
