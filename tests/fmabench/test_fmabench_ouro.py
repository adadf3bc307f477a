"""The family ``ouro`` (``fmabench/families/ouro/``), its configuration
``ouro-2.6b`` and its cell ``ouro-2.6b.loopchat``, after the pattern of
``test_fmabench_smallthinker.py``: the keys it refuses by name, its sizes,
its reference against the program at a tiny size (in process, and as a
whole CPU rehearsal), and the metric files of its cell. The rehearsal's own
files are under ``fmabench/testdata/ouro/``.
"""

import json
import os
import subprocess
import sys

import pytest

import test_fmabench_correct as base
from fmabench import readers, spec

CELL, CONFIG = "ouro-2.6b.loopchat", "ouro-2.6b"
DATA_DIR = "fmabench/testdata/ouro"
TINY_BENCH = os.path.join(spec.ROOT, DATA_DIR, "benchmark.json")
#: the mean gap pooled over base.SEEDS at the tiny size: the program reads
#: 0.00030 (0.00026-0.00031 a seed; 1.6-2.3% of its tokens have any gap) and
#: the int8 control 0.0032 (0.0018-0.0041; 4-10%); the limit lies between,
#: 3.3x from each
POOLED_MEAN_LIMIT = 0.001


def tiny():
    return spec.load_json(os.path.join(
        spec.ROOT, DATA_DIR, "configs", "tiny-ouro.json"))


def cell():
    return spec.Cell(spec.benchmark(), CELL)


def with_doc(tmp_path, doc):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": DATA_DIR,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-loopchat",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_the_configuration_is_the_catalog_row_uncut():
    doc = spec.config_file(CONFIG)
    assert doc["family"] == "ouro" and doc["reduced"] == []
    row = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == [] and row["source"] == doc["source"]
    assert doc["source"].endswith("ByteDance/Ouro-2.6B/blob/main/config.json")
    assert (doc["num_hidden_layers"], doc["total_ut_steps"]) == (48, 4)
    assert doc["early_exit_threshold"] == 1 and len(doc["layer_types"]) == 48
    for what in ("norms", "norm_weights", "attention_bias", "cache", "exit_gate"):
        assert doc["assumed"][what]
    d = cell().dims
    assert (d["num_layers"], d["loop_steps"], d["cache_layers"]) == (48, 4, 192)
    assert (d["hidden_size"], d["num_heads"], d["num_kv_heads"], d["head_dim"]) == (
        2048, 16, 16, 128)
    assert (d["intermediate_size"], d["vocab_size"], d["max_context"]) == (
        5632, 49152, 4096)
    assert d["rope_theta"] == 1e6 and d["rms_eps"] == 1e-6
    keys = cell().family.keys
    assert keys.reducible == ("num_hidden_layers",)
    assert keys.param_count(d) == 48 * 51_388_416 + 2 * 49_152 * 2_048 + 2_048 + 2_049
    assert keys.param_count(d) == 2_667_974_657
    # a 16-token page over 192 cache layers is 25,165,824 B; the cell's 273
    assert keys.kv_bytes(d, 1, 16) == 25_165_824
    assert keys.kv_bytes(d, 273, 16) == 6_870_269_952


REFUSED = {
    "adaptive_exit": ({"early_exit_threshold": 0.9}, "early_exit_threshold 0.9"),
    "a_window_layer": ({"layer_types": ["full_attention", "sliding_attention",
                                        "full_attention"]}, "layer_types"),
    "fewer_layer_types_than_layers": ({"layer_types": ["full_attention"]},
                                      "layer_types"),
    "sliding_window_on": ({"use_sliding_window": True}, "use_sliding_window"),
    "rope_scaling": ({"rope_scaling": {"type": "yarn", "factor": 4.0}},
                     "rope_scaling"),
    "tied_embeddings": ({"tie_word_embeddings": True}, "untied"),
    "another_activation": ({"hidden_act": "gelu"}, "hidden_act"),
    "no_pass": ({"total_ut_steps": 0}, "total_ut_steps"),
    "context_past_the_published": ({"assumed": {"max_context": 512}},
                                   "max_position_embeddings"),
    "a_key_nobody_reads": ({"ut_share_cache": True}, "ut_share_cache"),
    "a_width_in_reduced": ({"reduced": ["intermediate_size"]}, "intermediate_size"),
    "the_passes_in_reduced": ({"reduced": ["total_ut_steps"]}, "total_ut_steps"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_keys_refused_by_name(what, tmp_path):
    change, named = REFUSED[what]
    with pytest.raises(ValueError, match=named):
        spec.Cell(with_doc(tmp_path, {**tiny(), **change}), "w")


def test_a_missing_key_is_refused_by_name(tmp_path):
    doc = tiny()
    del doc["total_ut_steps"]
    with pytest.raises(ValueError, match="total_ut_steps"):
        spec.Cell(with_doc(tmp_path, doc), "w")


def test_program_config_is_the_looped_trunk():
    model = cell().family.part("program").build(cell().dims)
    assert type(model).__name__ == "LlamaConfig"
    assert (model.num_layers, model.loop_steps, model.cache_layers) == (48, 4, 192)
    assert model.post_norms and not model.attn_bias and model.norm_offset == 0.0
    assert not model.tie_embeddings and model.early_exit_threshold == 1.0
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (16, 16, 128)
    assert model.max_seq_len == 4096 and model.rope_theta == 1e6
    assert model.num_params() == 2_667_974_657
    from llm_d_fast_model_actuation_tpu.engine import server

    assert server.MODEL_CONFIGS["ouro-2.6b"]() == model


def test_reference_weights_are_the_programs_weights(monkeypatch):
    """Every leaf the program holds, the exit gate among them."""
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    base.reference_weights_are_the_programs(tiny())


def test_program_agrees_with_the_reference_and_the_control_does_not(monkeypatch):
    """In process: prefill, then decode through nine cache layers, against
    the family's reference; and the int8 control outside the same limit."""
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    monkeypatch.setattr(base, "served", served)
    config = tiny()
    base.program_agrees_with_the_reference(config, POOLED_MEAN_LIMIT)
    base.int8_control_comes_out_not_correct(config, POOLED_MEAN_LIMIT)


def served(config, seed):
    """``base.served`` at this family's vocabulary, with segments short
    enough that the longer prompts take a suffix segment."""
    import random

    from fmabench import serve
    from llm_d_fast_model_actuation_tpu.engine.engine import (
        EngineConfig, InferenceEngine,
    )

    eng = InferenceEngine(
        EngineConfig(model=serve.build_model_config(config, DATA_DIR), max_batch=4,
                     page_size=4, num_pages=256, decode_chunk=8,
                     max_prefill_tokens=32),
        seed=seed,
    )
    assert eng.pool.k_pages.shape[0] == 9
    rng = random.Random(seed)
    prompts = [[rng.randrange(1, 256) for _ in range(n)]
               for n in (24, 40, 9, 60, 33, 17, 50, 28)]
    outs = eng.generate(prompts, max_new_tokens=32)
    return [{"prompt": p, "tokens": list(o)} for p, o in zip(prompts, outs)]


def test_one_pass_fewer_in_the_programs_place_comes_out_not_correct(monkeypatch):
    """The reading ISSUE 34 asks for by hand on the chip, at the tiny size:
    the reference at ``loop_steps - 1`` passes put where the program stands
    reads gaps far outside the limit the program holds."""
    import functools

    import jax
    import numpy as np

    from fmabench import reference

    config = tiny()
    dims = spec.model_dims(config, DATA_DIR)
    fam = spec.family_of(config, DATA_DIR).part("reference")
    reqs = served(config, 1)
    weights = jax.jit(functools.partial(fam.init_weights, d=dims))(np.uint32(1))
    fewer = {**dims, "loop_steps": dims["loop_steps"] - 1}
    gaps = []
    for req in reqs[:3]:
        ids = jax.numpy.asarray(req["prompt"] + req["tokens"][:-1])
        rows = jax.numpy.arange(len(req["prompt"]) - 1, len(ids))
        with jax.default_matmul_precision("highest"):
            ref = fam.forward_logits(dims, weights, ids, len(ids), rows)
            low = fam.forward_logits(fewer, weights, ids, len(ids), rows)
        put_first = jax.numpy.argmax(low, axis=-1)
        gaps.append(np.asarray(
            ref.max(-1) - jax.numpy.take_along_axis(ref, put_first[:, None], -1)[:, 0]
        ))
    assert reference.reduce_gaps(gaps)["gap_mean"] > 50 * POOLED_MEAN_LIMIT


def test_rehearsal_run_is_correct_and_reads_the_cells_counters():
    """A whole traced run on the CPU: ``correct``, no compile inside the
    window, ``layer_passes`` is nine a forward, and no device metric is
    reported from a CPU."""
    proc = base.rehearsal("tiny.loopchat", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "fmabench", "--rehearse", "--benchmark", TINY_BENCH,
         "--workload", "tiny.loopchat", "--seed", "7", "--seconds", "3",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["window_compiles.loopchat"] == 0
    assert metrics["layer_passes.loopchat"] > 0
    assert metrics["layer_passes.loopchat"] % 9 == 0
    assert metrics["sched_host_s.loopchat"] > 0
    for device_metric in ("decode_step_dev_ms.loopchat", "prefill_dev_ms.loopchat",
                          "paged_decode_hbm_pct.loopchat", "device_idle_pct.loopchat"):
        assert device_metric not in metrics


def test_broken_timed_path_comes_out_not_correct():
    proc = base.rehearsal("tiny.loopchat", "--serve-module",
                          "tests.fmabench.broken_serve", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["gap_max"]["value"] > line["checks"]["gap_max"]["limit"]


# -- the cell's metric files ---------------------------------------------------------

METRICS = {
    "window_compiles.loopchat": ("compile", "program_counter"),
    "decode_step_dev_ms.loopchat": ("model step", "device_trace"),
    "prefill_dev_ms.loopchat": ("model step", "device_trace"),
    "device_idle_pct.loopchat": ("device", "device_trace"),
    "sched_host_s.loopchat": ("scheduler", "program_counter"),
    "paged_decode_hbm_pct.loopchat": ("kernels", "device_trace"),
    "layer_passes.loopchat": ("model step", "program_counter"),
}


@pytest.mark.parametrize("name", sorted(METRICS))
def test_metric_resolves_in_the_new_cell_alone(name):
    layer, source = METRICS[name]
    rows = {m["name"]: m for m in cell().per_layer()}
    assert rows[name]["layer"] == layer and rows[name]["source"] == source
    assert rows[name]["moves"] == "out_tokens_per_s"
    assert rows[name]["workloads"] == [CELL]
    assert rows[name]["reader"] == spec.metric_file(name)
    assert rows[name]["reader"]["kind"] in readers.KINDS
    for other in spec.benchmark()["workloads"]:
        if other["name"] != CELL:
            assert name not in {
                m["name"] for m in spec.Cell(spec.benchmark(), other["name"]).per_layer()
            }
    # on a program without the counter (the parent), or a run without a
    # trace, the reader finds nothing and does not raise
    assert readers.read_metric(rows[name]["reader"], readers.Evidence()) is None


def test_the_cell_reports_what_the_issue_names():
    c = cell()
    assert [m["name"] for m in c.end_to_end()] == ["out_tokens_per_s", "setup_s"]
    assert sorted(m["name"] for m in c.per_layer()) == sorted(
        [*METRICS, "setup_cache_hit_pct"])
    assert c.chips == 1 and c.traffic["kind"] == "closed"
    assert c.traffic["clients"] == 8 and c.traffic["pool"] == 16
    assert c.traffic["prompt_len"] == {
        "dist": "lognormal", "median": 128, "sigma": 0.6, "min": 32, "max": 256}
    assert c.traffic["output_len"] == {
        "dist": "lognormal", "median": 160, "sigma": 0.5, "min": 64, "max": 288}
    assert c.engine_options(False) == (
        "--max-batch 8 --page-size 16 --num-pages 273 "
        "--max-prefill-tokens 1024 --decode-chunk 8").split()
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    assert longest == 544 <= c.dims["max_context"]
    # the worst case is the pool less its reserved page: nothing is preempted
    assert 8 * longest == (273 - 1) * 16
    # every prompt is one segment, and the ladder reaches every bucket
    assert c.traffic["prompt_len"]["max"] <= 1024
    assert c.traffic["warmup"]["prompt_lens"] == [32, 64, 128, 256]


def test_layer_passes_reader_reads_the_programs_counter():
    ev = readers.Evidence()
    ev.stats_open = {"stack": {"layer_passes": 192 * 100}}
    ev.stats_close = {"stack": {"layer_passes": 192 * (100 + 8 * 150 + 60)}}
    reader = spec.metric_file("layer_passes.loopchat")
    assert readers.read_metric(reader, ev) == 192 * (8 * 150 + 60)


def test_decode_roofline_counts_sixteen_kv_heads_and_one_call():
    fn = readers.roofline_mod.paged_decode_step
    d = cell().dims
    assert fn(d) is None
    got = fn({**d, "live_seqs": 8, "live_kv_tokens": 1800})
    # K and V of 16 heads of 128 in bfloat16 a token of ONE cache layer
    assert got["bytes"] == 1800 * 16 * 128 * 2 * 2 + 8 * 16 * 128 * 2 * 2
    assert got["flops"] == 2.0 * 2.0 * 1800 * 16 * 128
