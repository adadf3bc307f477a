"""The family ``smallthinker`` (``fmabench/families/smallthinker/``), its
configuration ``smallthinker-21b-a3b-d8`` and its cell
``smallthinker-21b.longmix``, after the pattern of
``test_fmabench_family.py``: the keys it refuses by name, its sizes, its
reference against the program at a tiny size (in process, and as a whole
CPU rehearsal through both kinds of KV cache), its roofline functions and
the metric files of its cell. The rehearsal's own files are under
``fmabench/testdata/smallthinker/``.
"""

import json
import os

import numpy as np
import pytest

import test_fmabench_correct as base
from fmabench import readers, spec

CELL, CONFIG = "smallthinker-21b.longmix", "smallthinker-21b-a3b-d8"
DATA_DIR = "fmabench/testdata/smallthinker"
TINY_BENCH = os.path.join(spec.ROOT, DATA_DIR, "benchmark.json")
#: the mean gap pooled over base.SEEDS at the tiny size: the program reads
#: 0.0024 and the int8 control 0.0109 (the program's is router near-ties
#: that fall the other way in bfloat16, 8 experts of which 3 are kept: 2-5%
#: of its tokens have any gap); the limit lies between, 2x from each
POOLED_MEAN_LIMIT = 0.005


def tiny():
    return spec.load_json(os.path.join(
        spec.ROOT, DATA_DIR, "configs", "tiny-smallthinker.json"))


def cell():
    return spec.Cell(spec.benchmark(), CELL)


def with_doc(tmp_path, doc):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": DATA_DIR,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-longmix",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    doc = spec.config_file(CONFIG)
    assert doc["family"] == "smallthinker" and doc["reduced"] == ["num_hidden_layers"]
    assert doc["published"] == {"num_hidden_layers": 52}
    row = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == doc["reduced"] and row["source"] == doc["source"]
    d = cell().dims
    assert (d["hidden_size"], d["num_heads"], d["num_kv_heads"], d["head_dim"]) == (
        2560, 28, 4, 128)
    assert (d["num_experts"], d["experts_per_token"], d["expert_size"]) == (64, 6, 768)
    assert (d["vocab_size"], d["window"], d["max_context"]) == (151936, 4096, 16384)
    assert d["rope_theta"] == 1.5e6 and d["rms_eps"] == 1e-6
    # the layouts stay whole in the file; the depth held is served
    assert len(doc["sliding_window_layout"]) == 52 and d["num_layers"] == 8
    assert d["window_layout"] == d["rope_layout"] == [0, 1, 1, 1, 0, 1, 1, 1]
    assert d["period"] == 4
    keys = cell().family.keys
    assert keys.param_count(d) == 3_966_937_600
    assert keys.param_count({**d, "num_layers": 52}) > 21e9
    # pages of the 2 full-attention layers; rings of the 6 window layers
    assert keys.kv_bytes(d, 26656, 16) == 26656 * 16 * 2 * 2048
    assert keys.ring_bytes(d, 32, 1024) == 32 * 5120 * 6 * 2048


REFUSED = {
    "sigmoid_router": ({"moe_primary_router_apply_softmax": False},
                       "moe_primary_router_apply_softmax"),
    "unnormalised_top_k": ({"norm_topk_prob": False}, "norm_topk_prob"),
    "tied_embeddings": ({"tie_word_embeddings": True}, "untied"),
    "rope_scaling": ({"rope_scaling": {"type": "yarn", "factor": 4.0}},
                     "rope_scaling"),
    "a_layout_shorter_than_the_depth": ({"rope_layout": [0, 1, 1]}, "rope_layout"),
    "context_past_the_published": ({"assumed": {"max_context": 512}},
                                   "max_position_embeddings"),
    "a_key_nobody_reads": ({"moe_secondary_experts": 4}, "moe_secondary_experts"),
    "a_width_in_reduced": ({"reduced": ["moe_ffn_hidden_size"]},
                           "moe_ffn_hidden_size"),
    "experts_in_reduced": ({"reduced": ["moe_num_primary_experts"]},
                           "moe_num_primary_experts"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_keys_refused_by_name(what, tmp_path):
    change, named = REFUSED[what]
    with pytest.raises(ValueError, match=named):
        spec.Cell(with_doc(tmp_path, {**tiny(), **change}), "w")


def test_a_missing_key_is_refused_by_name(tmp_path):
    doc = tiny()
    del doc["sliding_window_size"]
    with pytest.raises(ValueError, match="sliding_window_size"):
        spec.Cell(with_doc(tmp_path, doc), "w")


def test_program_config_carries_the_pattern():
    model = cell().family.part("program").build(cell().dims)
    assert type(model).__name__ == "SmallThinkerConfig"
    assert model.window_pattern == (0, 4096, 4096, 4096)
    assert model.rope_pattern == (False, True, True, True)
    assert model.routed_experts and model.expert_activation == "relu"
    assert (model.num_layers, model.num_experts, model.experts_per_token,
            model.intermediate_size) == (8, 64, 6, 768)
    assert model.num_params() == 3_966_937_600


def test_reference_weights_are_the_programs_weights(monkeypatch):
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    base.reference_weights_are_the_programs(tiny())


def test_program_agrees_with_the_reference_and_the_control_does_not(monkeypatch):
    """In process: prefill, then decode through pages and rings, against
    the family's reference; and the int8 control outside the same limit."""
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    monkeypatch.setattr(base, "served", served)
    config = tiny()
    base.program_agrees_with_the_reference(config, POOLED_MEAN_LIMIT)
    base.int8_control_comes_out_not_correct(config, POOLED_MEAN_LIMIT)


def served(config, seed):
    """``base.served`` with the options a model with window layers needs
    (no prefix cache) and segments short enough that the prompts span
    several and the rings wrap."""
    import random

    from fmabench import serve
    from llm_d_fast_model_actuation_tpu.engine.engine import (
        EngineConfig, InferenceEngine,
    )

    eng = InferenceEngine(
        EngineConfig(model=serve.build_model_config(config, DATA_DIR), max_batch=4,
                     page_size=4, num_pages=256, decode_chunk=8,
                     max_prefill_tokens=16, prefix_caching=False),
        seed=seed,
    )
    assert eng.kv_layout.ring_pages * 4 == 40
    rng = random.Random(seed)
    prompts = [[rng.randrange(1, 256) for _ in range(n)]
               for n in (24, 40, 9, 60, 33, 17, 50, 28)]
    outs = eng.generate(prompts, max_new_tokens=32)
    assert eng.window_tokens_evicted > 0
    return [{"prompt": p, "tokens": list(o)} for p, o in zip(prompts, outs)]


def test_rehearsal_run_is_correct_and_reads_the_cells_counters():
    """A whole traced run on the CPU through both caches: ``correct``, no
    compile inside the window, positions left the rings, and no device
    metric is reported from a CPU."""
    proc = base.rehearsal("tiny.longmix", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    import subprocess
    import sys

    proc = subprocess.run(
        [sys.executable, "-m", "fmabench", "--rehearse", "--benchmark", TINY_BENCH,
         "--workload", "tiny.longmix", "--seed", "7", "--seconds", "3",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["window_compiles.longmix"] == 0
    assert metrics["kv_window_evicted_tokens.longmix"] > 0
    assert metrics["sched_host_s.longmix"] > 0
    for device_metric in ("decode_step_dev_ms.longmix", "paged_decode_hbm_pct.longmix",
                          "moe_decode_hbm_pct.longmix", "device_idle_pct.longmix"):
        assert device_metric not in metrics


def test_broken_timed_path_comes_out_not_correct():
    proc = base.rehearsal("tiny.longmix", "--serve-module",
                          "tests.fmabench.broken_serve", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["gap_max"]["value"] > line["checks"]["gap_max"]["limit"]


# -- the cell's metric files and roofline functions ------------------------------------

METRICS = {
    "window_compiles.longmix": ("compile", "program_counter"),
    "decode_step_dev_ms.longmix": ("model step", "device_trace"),
    "prefill_dev_ms.longmix": ("model step", "device_trace"),
    "device_idle_pct.longmix": ("device", "device_trace"),
    "sched_host_s.longmix": ("scheduler", "program_counter"),
    "kv_window_evicted_tokens.longmix": ("cache", "program_counter"),
    "paged_decode_hbm_pct.longmix": ("kernels", "device_trace"),
    "moe_decode_hbm_pct.longmix": ("kernels", "device_trace"),
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
    # on a program without the counter, or a run without a trace, the reader
    # finds nothing and does not raise
    assert readers.read_metric(rows[name]["reader"], readers.Evidence()) is None


def test_the_cell_reports_what_the_issue_names():
    c = cell()
    assert [m["name"] for m in c.end_to_end()] == ["out_tokens_per_s", "setup_s"]
    assert c.chips == 1 and c.traffic["kind"] == "closed"
    # ISSUE 28 named a pool of 64 shapes; 16 steadied the cell (PERF.md §2)
    assert c.traffic["clients"] == 32 and c.traffic["pool"] == 16
    assert c.engine_options(False) == (
        "--max-batch 32 --page-size 16 --num-pages 26656 "
        "--max-prefill-tokens 1024 --decode-chunk 8").split()
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    assert longest == 13312 <= c.dims["max_context"]
    assert 32 * longest <= 26656 * 16  # nothing is preempted
    # the ladder reaches every prefill and suffix bucket up to the segment
    lens = c.traffic["warmup"]["prompt_lens"]
    assert {n - 1024 for n in lens if n > 1024} == {16, 32, 64, 128, 256, 512, 1024}
    assert {512, 1024} <= set(lens)


def test_evicted_tokens_reader_reads_the_programs_counter():
    ev = readers.Evidence()
    ev.stats_open = {"kv": {"window_tokens_evicted": 1200}}
    ev.stats_close = {"kv": {"window_tokens_evicted": 91200}}
    reader = spec.metric_file("kv_window_evicted_tokens.longmix")
    assert readers.read_metric(reader, ev) == 90000


def test_windowed_decode_roofline_counts_the_least_the_contexts_allow():
    fn = spec.roofline_function("paged_decode_step_windowed")
    d = cell().dims
    assert fn(d) is None and fn({**d, "live_seqs": 0, "live_kv_tokens": 0}) is None
    row = 4 * 128 * 2 * 2  # bytes of K and V a token a layer
    qo = lambda n: n * 28 * 128 * 2 * 2  # noqa: E731
    # every context inside the window: both kinds read everything
    got = fn({**d, "live_seqs": 2, "live_kv_tokens": 3000})
    assert got["bytes"] == 3000 * row + qo(2)
    # 32 sequences, 176,000 tokens: 10 could be 16,384 long and one 12,160,
    # the rest empty: 11 windows is the least the window layers read
    got = fn({**d, "live_seqs": 32, "live_kv_tokens": 176000})
    least = (2 * 176000 + 6 * 11 * 4096) / 8
    assert got["bytes"] == least * row + qo(32)
    # never more than the exact count for any split of the sum
    ctx = np.random.default_rng(0).integers(1, 13312, size=32)
    exact = (2 * ctx.sum() + 6 * np.minimum(ctx, 4096).sum()) / 8
    got = fn({**d, "live_seqs": 32, "live_kv_tokens": int(ctx.sum())})
    assert got["bytes"] - qo(32) <= exact * row


def test_moe_decode_roofline_counts_the_experts_a_step_touches():
    fn = spec.roofline_function("moe_decode_step")
    d = cell().dims
    assert fn(d) is None
    got = fn({**d, "live_seqs": 32})
    touched = 64 * (1 - (58 / 64) ** 32)
    assert 60 < touched < 62
    assert got["bytes"] == pytest.approx(
        touched * 2560 * 768 * 2 + 32 * 6 * (2560 + 768) * 2)
    assert got["flops"] == 2.0 * 192 * 2560 * 768
    # one live sequence touches its six experts
    assert fn({**d, "live_seqs": 1})["bytes"] == pytest.approx(
        6 * 2560 * 768 * 2 + 6 * (2560 + 768) * 2)
