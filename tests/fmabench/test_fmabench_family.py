"""The family that is files under ``fmabench/testdata/`` and nothing else
(``families/geglu-sandwich/``, ``configs/tiny-geglu.json``, the cell
``tiny.geglu``): the proof that the harness takes an architecture as files,
and the pattern of the test file a ``model_config`` PR brings with its
configuration. The program runs the architecture already; neither family
under ``fmabench/families/`` can say it, and no file of the harness names it.
The comparisons are ``test_fmabench_correct.py``'s own, handed this
family's configuration.
"""

import glob
import json
import os

import pytest

import test_fmabench_correct as base
from fmabench import spec
from test_fmabench_spec import PROOF, TESTDATA, tiny_config, with_config

#: the family's keys at the size the in-process comparisons of
#: test_fmabench_correct.py use
SIZE = {
    "family": PROOF, "hidden_act": "gelu_pytorch_tanh", "qk_norm": True,
    "sandwich_norms": True, "hidden_size": 256, "ffn_hidden_size": 512,
    "num_attention_heads": 8, "num_key_value_heads": 4, "head_dim": 32,
    "num_hidden_layers": 2, "vocab_size": 1024, "rope_theta": 10000.0,
    "rms_norm_eps": 1e-5, "assumed": {"max_context": 256},
}
#: the mean gap pooled over base.SEEDS reads 0.00013 for the program and
#: 0.0010 for the int8 control at this size; the limit lies between
POOLED_MEAN_LIMIT = 0.0004


def test_the_proof_family_resolves_with_keys_of_its_own(tmp_path):
    doc = tiny_config("tiny-geglu")
    assert doc["family"] == PROOF and "intermediate_size" not in doc
    cell = spec.Cell(spec.benchmark(os.path.join(spec.ROOT, TESTDATA, "benchmark.json")),
                     "tiny.geglu")
    assert cell.family.name == PROOF and cell.dims["ffn_size"] == 96
    assert "intermediate_size" not in cell.dims
    assert cell.family.keys.param_count(cell.dims) == (
        2 * (64 * 64 + 2 * 64 * 32 + 64 * 64 + 3 * 64 * 96 + 4 * 64 + 32)
        + 2 * 256 * 64 + 64)
    # without its family key the file is read as one of the first two, whose
    # keys these are not: refused by name, before any child starts
    del doc["family"]
    with pytest.raises(ValueError, match="ffn_hidden_size.*qk_norm.*sandwich_norms"):
        spec.Cell(with_config(tmp_path, doc), "w")


def test_the_proof_family_is_named_in_no_file_of_the_harness():
    for path in glob.glob(os.path.join(spec.HERE, "*.py")) + glob.glob(
            os.path.join(spec.HERE, "families", "*", "*.py")):
        with open(path, encoding="utf-8") as f:
            text = f.read()
        assert PROOF not in text and "geglu" not in text.lower(), path


def test_reference_weights_are_the_programs_weights():
    base.reference_weights_are_the_programs(SIZE)


def test_program_agrees_with_the_reference():
    base.program_agrees_with_the_reference(SIZE, POOLED_MEAN_LIMIT)


def test_int8_control_comes_out_not_correct():
    base.int8_control_comes_out_not_correct(SIZE, POOLED_MEAN_LIMIT)


def test_rehearsal_run_is_correct_and_names_the_cpu():
    base.rehearsal_is_correct_and_names_the_cpu("tiny.geglu")


def test_broken_timed_path_comes_out_not_correct():
    base.broken_timed_path_comes_out_not_correct("tiny.geglu")


def test_a_file_without_its_family_key_is_refused_and_nothing_is_served(tmp_path):
    bench = spec.benchmark(os.path.join(spec.ROOT, TESTDATA, "benchmark.json"))
    row = next(c for c in bench["configs"] if c["name"] == "tiny-geglu")
    doc = tiny_config("tiny-geglu")
    del doc["family"]
    row["file"] = str(tmp_path / "tiny-geglu.json")
    with open(row["file"], "w", encoding="utf-8") as f:
        json.dump(doc, f)
    with open(tmp_path / "benchmark.json", "w", encoding="utf-8") as f:
        json.dump(bench, f)
    proc = base.rehearsal("tiny.geglu", benchmark=str(tmp_path / "benchmark.json"))
    assert proc.returncode != 0 and '"correct"' not in proc.stdout
    assert "server_ready" not in proc.stdout
    for key in ("ffn_hidden_size", "qk_norm", "sandwich_norms"):
        assert repr(key) in proc.stderr
