"""BENCHMARK.json and the data files behind it: everything resolves by
name, and every name, unit and limit keeps to the benchmark's contract."""

import json
import os

import pytest

from fmabench import harness, readers, spec

BENCH = spec.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {
        "command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer",
    }
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check with all 24 cells fits the driver's allowance
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(spec.BENCHMARK_JSON) <= 64 * 1024
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
    assert BENCH["paths"] == ["fmabench", "tests/fmabench"]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    c = spec.Cell(BENCH, cell)
    assert c.chips in (1, 4)
    assert c.traffic["kind"] in harness.DRIVERS
    assert len(c.workload["why"]) <= 200 and "\n" not in c.workload["why"]
    names = {m["name"] for m in c.end_to_end()}
    assert "setup_s" in names and len(names) >= 2
    assert c.per_layer(), "every cell reports a per-layer metric"
    for m in c.per_layer():
        assert m["reader"]["kind"] in readers.KINDS
        assert m["moves"] in names
    assert c.engine_options(True) == c.engine_options(False) + [
        "--trace-requests", "1.0"
    ]


@pytest.mark.parametrize("cell", ["mixtral-8x7b.batch", "mistral-7b.chat"])
def test_the_first_cells_name_their_departure_from_the_engines_default(cell):
    """Both serve at --decode-chunk 8 where the engine's default on TPU is
    32 (PERF.md section 4): the cell's one line says so."""
    c = spec.Cell(BENCH, cell)
    assert c.engine_option("--decode-chunk") == "8"
    assert "--decode-chunk 8" in c.workload["why"]


def test_a_quarter_of_the_cells_at_most_ask_for_four_chips():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_configuration_file(cfg):
    assert cfg["file"].startswith("fmabench/")
    doc = spec.load_json(os.path.join(spec.ROOT, cfg["file"]))
    assert doc["source"] == cfg["source"]
    assert doc["reduced"] == cfg["reduced"]
    for key in cfg["reduced"]:
        assert key not in spec.WIDTH_KEYS and not key.endswith(("_dim", "_rank"))
        assert doc[key] != doc["published"][key]
    # published widths of Mistral-7B-v0.1 / Mixtral-8x7B-v0.1
    assert (doc["hidden_size"], doc["intermediate_size"]) == (4096, 14336)
    assert (doc["num_attention_heads"], doc["num_key_value_heads"]) == (32, 8)
    assert doc["vocab_size"] == 32000
    dims = spec.model_dims(doc)
    assert dims["head_dim"] == 128
    limits = doc["check"]["limits"]
    assert limits and set(limits) <= set(harness.GAP_NUMBERS)
    assert all(0 < v < 1 for v in limits.values())
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_names_units_and_sources(metric):
    assert spec.NAME_RE.match(metric["name"])
    assert spec.UNIT_RE.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in (
        "device_trace", "program_span", "program_counter", "host_clock"
    )
    for cell in metric.get("workloads", []):
        assert cell in CELLS
    if "bound" in metric:  # end to end
        assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert 0.01 <= metric["bound"] <= 0.1
        assert metric["source"] in ("host_clock", "device_trace")
    else:
        assert set(metric) <= {
            "name", "unit", "better", "source", "layer", "moves", "workloads"
        }
        assert metric["moves"] in {m["name"] for m in BENCH["end_to_end"]}
        assert spec.metric_file(metric["name"])["kind"] in readers.KINDS


def test_names_are_unique_and_well_formed():
    for rows in (BENCH["configs"], BENCH["workloads"], METRICS):
        names = [r["name"] for r in rows]
        assert len(set(names)) == len(names)
        assert all(spec.NAME_RE.match(n) for n in names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert spec.NAME_RE.match(w["traffic"])


def test_peaks_table_knows_the_v5e_and_refuses_the_unknown():
    peaks = spec.peaks("TPU v5 lite")
    assert peaks["bf16_flops_per_s"] == 197e12
    assert peaks["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v99")


def test_parameter_counts_match_the_configuration_files():
    d = spec.model_dims(spec.config_file("mistral-7b-v0.1-d8"))
    assert spec.param_count(d) == 8 * 218_112_000 + 262_148_096
    m = spec.model_dims(spec.config_file("mixtral-8x7b-v0.1-d2"))
    assert round(spec.param_count(m) * 2 / 1e9, 2) == 6.33


# -- the README's worked examples: a cell, a configuration, a traffic mix and a
#    metric added as files, with no file of the benchmark edited ---------------

EXAMPLES = os.path.join(spec.HERE, "testdata", "examples")


def test_readme_examples_resolve_without_editing_a_file():
    bench = json.loads(json.dumps(BENCH))
    added = spec.load_json(os.path.join(EXAMPLES, "benchmark_additions.json"))
    bench["data_dir"] = "fmabench/testdata/examples"
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] = bench[key] + added.get(key, [])
    cell = spec.Cell(bench, added["workloads"][0]["name"])
    assert cell.traffic["kind"] == "open"
    layer = {m["name"]: m for m in cell.per_layer()}
    new = added["per_layer"][0]["name"]
    assert layer[new]["reader"]["kind"] == "span"
    # a new configuration is its file and one entry
    d4 = spec.Cell(bench, added["workloads"][1]["name"])
    assert spec.model_dims(d4.config)["num_layers"] == 4
    assert d4.config["reduced"] == added["configs"][0]["reduced"]
    # the old cells are untouched by the addition
    for name in CELLS:
        assert new not in {m["name"] for m in spec.Cell(bench, name).per_layer()}
