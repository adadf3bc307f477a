"""BENCHMARK.json and the data files behind it: everything resolves by
name, and every name, unit and limit keeps to the benchmark's contract."""

import glob
import json
import os
import subprocess
import sys

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
    """What holds for every configuration, whatever its family; a
    configuration's own widths are asserted by a test file it brings."""
    assert cfg["file"].startswith("fmabench/")
    doc = spec.load_json(os.path.join(spec.ROOT, cfg["file"]))
    assert doc["source"] == cfg["source"]
    assert doc["reduced"] == cfg["reduced"]
    keys = spec.family_of(doc).keys
    for key in cfg["reduced"]:
        assert key in keys.reducible
        assert not key.endswith(("_dim", "_rank"))
        assert doc[key] != doc["published"][key]
    dims = spec.model_dims(doc)
    assert dims["vocab_size"] > 0 and dims["max_context"] > 0
    limits = doc["check"]["limits"]
    assert limits and set(limits) <= set(harness.GAP_NUMBERS)
    assert all(0 < v < 1 for v in limits.values())
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("name,family", [
    ("mistral-7b-v0.1-d8", "mistral"), ("mixtral-8x7b-v0.1-d2", "mixtral"),
])
def test_the_first_two_configurations_keep_their_published_widths(name, family):
    """Mistral-7B-v0.1 / Mixtral-8x7B-v0.1; neither file has a ``family``
    key, and each resolves by the rule it was accepted under."""
    doc = spec.config_file(name)
    assert "family" not in doc and spec.family_name(doc) == family
    assert (doc["hidden_size"], doc["intermediate_size"]) == (4096, 14336)
    assert (doc["num_attention_heads"], doc["num_key_value_heads"]) == (32, 8)
    assert doc["vocab_size"] == 32000
    dims = spec.model_dims(doc)
    assert dims["head_dim"] == 128
    assert ("num_experts" in dims) == (family == "mixtral")
    # depth alone may be cut: the vocabulary and the experts are widths
    assert spec.Family(family).keys.reducible == ("num_hidden_layers",)


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
    chat = spec.Cell(BENCH, "mistral-7b.chat")
    count = chat.family.keys.param_count(chat.dims)
    assert count == 8 * 218_112_000 + 262_148_096
    batch = spec.Cell(BENCH, "mixtral-8x7b.batch")
    count = batch.family.keys.param_count(batch.dims)
    assert round(count * 2 / 1e9, 2) == 6.33
    # KV pool of the cells' engine options, as PERF.md section 4 states them
    assert round(chat.family.keys.kv_bytes(chat.dims, 6144, 16) / 1e9, 2) == 3.22
    assert round(batch.family.keys.kv_bytes(batch.dims, 12288, 16) / 1e9, 2) == 1.61


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
    # a roofline metric whose function is a file of its own
    roof = layer[added["per_layer"][1]["name"]]["reader"]
    assert roof["kind"] == "roofline"
    fn = spec.roofline_function(roof["function"], bench["data_dir"])
    assert fn({"live_seqs": 2, **cell.dims})["flops"] == 2.0 * 2 * 4096 * 32000
    assert fn(dict(cell.dims)) is None
    # a new configuration is its file and one entry
    d4 = spec.Cell(bench, added["workloads"][1]["name"])
    assert spec.model_dims(d4.config)["num_layers"] == 4
    assert d4.config["reduced"] == added["configs"][0]["reduced"]
    # the old cells are untouched by the addition
    for name in CELLS:
        assert new not in {m["name"] for m in spec.Cell(bench, name).per_layer()}


# -- a model family is a directory found by name: keys, program, reference ----

TESTDATA = "fmabench/testdata"
PROOF = "geglu-sandwich"   # under testdata/ only: test_fmabench_family.py


def tiny_config(name):
    return spec.load_json(
        os.path.join(spec.ROOT, TESTDATA, "configs", name + ".json"))


def with_config(tmp_path, doc, data_dir=TESTDATA):
    """A benchmark of one cell whose configuration file is ``doc``."""
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": data_dir,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-chat",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_a_family_is_found_under_data_dir_before_fmabench(tmp_path):
    assert spec.Family(PROOF, TESTDATA).dir == os.path.join(
        spec.HERE, "testdata", "families", PROOF)
    with pytest.raises(KeyError, match=PROOF):
        spec.Family(PROOF)          # fmabench/families/ has no such family
    # a directory of the same name under data_dir wins, whole
    own = tmp_path / "families" / "mistral"
    own.mkdir(parents=True)
    (own / "keys.py").write_text("carried = ('only_here',)\n")
    assert spec.Family("mistral", str(tmp_path)).keys.carried == ("only_here",)
    assert "model_type" in spec.Family("mistral").keys.carried
    with pytest.raises(FileNotFoundError):
        spec.Family("mistral", str(tmp_path)).part("reference")


def test_a_family_takes_its_trunk_from_the_directory_beside_its_own(tmp_path):
    """Mixtral builds on Mistral's files: a rehearsal that brings its own
    copy of the two gets its own trunk in every part, so that the parent,
    the engine child and the reference child cannot part ways over it."""
    import shutil

    for name in ("mistral", "mixtral"):
        shutil.copytree(os.path.join(spec.HERE, "families", name),
                        tmp_path / "families" / name)
    with open(tmp_path / "families" / "mistral" / "keys.py", "a") as f:
        f.write("\ncarried = carried + ('only_in_this_trunk',)\n")
    own = spec.Family("mixtral", str(tmp_path))
    assert "only_in_this_trunk" in own.keys.carried
    assert "only_in_this_trunk" not in spec.Family("mixtral").keys.carried
    for part in ("keys", "program", "reference"):
        with open(os.path.join(own.dir, part + ".py"), encoding="utf-8") as f:
            text = f.read()
        assert f'sibling_part(__file__, "mistral", "{part}")' in text
        assert "Family(" not in text
    # a copy of the one without the other is refused by the missing file
    shutil.rmtree(tmp_path / "families" / "mistral")
    spec._MODULES.clear()
    with pytest.raises(FileNotFoundError, match="mistral"):
        spec.Family("mixtral", str(tmp_path)).keys


FAMILY_DIRS = sorted(
    glob.glob(os.path.join(spec.HERE, "families", "*"))
    + glob.glob(os.path.join(spec.ROOT, TESTDATA, "families", "*")))


@pytest.mark.parametrize("family_dir", FAMILY_DIRS, ids=os.path.basename)
def test_loading_a_familys_keys_leaves_jax_out_of_the_parent(family_dir):
    """``keys.py`` is standard library only: the parent of a run loads it,
    and a parent that has touched JAX holds the chip its children need."""
    code = (
        "import sys; from fmabench import spec; "
        f"keys = spec.load_py({os.path.join(family_dir, 'keys.py')!r}); "
        "assert callable(keys.dims) and keys.reducible; "
        "bad = [m for m in ('jax', 'jaxlib', 'numpy') if m in sys.modules]; "
        "assert not bad, bad"
    )
    proc = subprocess.run([sys.executable, "-c", code], cwd=spec.ROOT,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe", "tiny-geglu"])
def test_a_key_nobody_reads_is_an_error_that_names_it(tmp_path, name):
    doc = tiny_config(name)
    spec.Cell(with_config(tmp_path, doc), "w")          # as it is: accepted
    doc["sliding_window_layout"] = [1, 1, 1, 0]
    with pytest.raises(ValueError, match=r"\['sliding_window_layout'\]"):
        spec.Cell(with_config(tmp_path, doc), "w")


def test_a_stated_value_the_family_would_serve_as_another_is_refused(tmp_path):
    doc = tiny_config("tiny-dense")
    for key, value in (("hidden_act", "gelu"), ("tie_word_embeddings", True),
                       ("sliding_window", 128), ("max_position_embeddings", 64)):
        with pytest.raises(ValueError):
            spec.Cell(with_config(tmp_path, {**doc, key: value}), "w")
    spec.Cell(with_config(tmp_path, {**doc, "sliding_window": 256}), "w")


SHARING_KEYS = """
carried = ()
reducible = ("num_hidden_layers", "experts_here", "vocab_size")
def dims(config):
    return {"vocab_size": config["vocab_size"], "experts": config["experts_here"],
            "layers": config["num_hidden_layers"],
            "max_context": config["assumed"]["max_context"]}
"""


def test_expert_share_and_vocabulary_slice_in_reduced(tmp_path):
    """The usual cut (model-configs section 4) can be written down for a
    family that lists it, and is refused for one that does not."""
    fam = tmp_path / "families" / "sharing"
    fam.mkdir(parents=True)
    (fam / "keys.py").write_text(SHARING_KEYS)
    doc = {"family": "sharing", "vocab_size": 16000, "experts_here": 8,
           "num_hidden_layers": 4, "assumed": {"max_context": 64},
           "published": {"vocab_size": 128000, "experts_here": 64,
                         "num_hidden_layers": 32},
           "reduced": ["num_hidden_layers", "experts_here", "vocab_size"]}
    bench = with_config(tmp_path, doc, data_dir=str(tmp_path))
    (tmp_path / "traffic").mkdir()
    (tmp_path / "traffic" / "tiny-chat.json").write_text("{}")
    assert spec.Cell(bench, "w").dims["experts"] == 8
    # a key its dims() never looks at is refused for this family too
    with pytest.raises(ValueError, match="never_read"):
        spec.Cell(with_config(tmp_path, {**doc, "never_read": 1},
                              data_dir=str(tmp_path)), "w")
    # Mistral lists depth alone: its vocabulary is a width
    dense = tiny_config("tiny-dense")
    spec.Cell(with_config(tmp_path, {**dense, "reduced": ["num_hidden_layers"]}), "w")
    for key in ("vocab_size", "num_attention_heads", "head_dim"):
        with pytest.raises(ValueError, match="may not stand in `reduced`"):
            spec.Cell(with_config(tmp_path, {**dense, "reduced": [key]}), "w")
    moe = tiny_config("tiny-moe")
    with pytest.raises(ValueError, match="num_local_experts"):
        spec.Cell(with_config(tmp_path, {**moe, "reduced": ["num_local_experts"]}), "w")


def test_the_reference_job_carries_the_family():
    bench = spec.benchmark(os.path.join(spec.ROOT, TESTDATA, "benchmark.json"))
    for name, family in (("tiny.chat", "mistral"), ("tiny.batch", "mixtral"),
                         ("tiny.geglu", PROOF)):
        cell = spec.Cell(bench, name)
        job = harness.reference_job(cell, 7, [], "cpu")
        assert job["family"] == family and job["data_dir"] == TESTDATA
        assert job["dims"] == cell.dims and job["seed"] == 7
        json.dumps(job)     # goes to the child as a file
    from fmabench import client

    argv = client.server_argv(spec.Cell(bench, "tiny.geglu"), "c.json", 1, 7,
                              False, "m.json", "cpu")
    assert argv[argv.index("--data-dir") + 1] == TESTDATA
    real = client.server_argv(spec.Cell(BENCH, CELLS[0]), "c.json", 1, 7,
                              False, "m.json", "tpu")
    assert "--data-dir" not in real
