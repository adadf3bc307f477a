"""The family ``olmo_hybrid`` (``fmabench/families/olmo_hybrid/``), its
configuration ``olmo-hybrid-7b-d16`` and its cell
``olmo-hybrid-7b.hybridmix``, after the pattern of
``test_fmabench_ouro.py``: the keys it refuses by name, its sizes, its
reference (the recurrence token by token) against the program at a tiny size
(in process, and as a whole CPU rehearsal), and the metric files of its cell.
The rehearsal's own files are under ``fmabench/testdata/olmo_hybrid/``.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

import test_fmabench_correct as base
from fmabench import readers, spec

CELL, CONFIG = "olmo-hybrid-7b.hybridmix", "olmo-hybrid-7b-d16"
DATA_DIR = "fmabench/testdata/olmo_hybrid"
TINY_BENCH = os.path.join(spec.ROOT, DATA_DIR, "benchmark.json")
#: the mean gap pooled over base.SEEDS at the tiny size: seeded random
#: weights make a stack of linear layers amplify rounding (tests/
#: test_olmo_hybrid.py), so the program reads 0.037 (0.025-0.045 a seed;
#: 12-21% of its tokens have a gap) and the int8 control 0.146 (0.124-0.164;
#: 36-42%); the limit is about their geometric mean
POOLED_MEAN_LIMIT = 0.07


def tiny():
    return spec.load_json(os.path.join(
        spec.ROOT, DATA_DIR, "configs", "tiny-olmo-hybrid.json"))


def cell():
    return spec.Cell(spec.benchmark(), CELL)


def with_doc(tmp_path, doc):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": DATA_DIR,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-hybridmix",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_the_configuration_is_the_catalog_row_cut_in_depth_alone():
    doc = spec.config_file(CONFIG)
    assert doc["family"] == "olmo_hybrid" and doc["reduced"] == ["num_hidden_layers"]
    row = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == ["num_hidden_layers"] and row["source"] == doc["source"]
    assert doc["source"].endswith("allenai/Olmo-Hybrid-7B/blob/main/config.json")
    assert doc["published"] == {"num_hidden_layers": 32}
    # the published pattern stays whole in the file; the first 16 are served
    assert doc["num_hidden_layers"] == 16 and len(doc["layer_types"]) == 32
    assert doc["layer_types"] == 8 * (3 * ["linear_attention"] + ["full_attention"])
    assert doc["rope_parameters"] == {"rope_theta": None}
    for what in ("block", "qk_norm", "positions", "linear_layers", "state",
                 "decay_init", "max_context", "head_dim"):
        assert doc["assumed"][what]
    d = cell().dims
    assert (d["num_layers"], d["linear_layers"], d["full_layers"]) == (16, 12, 4)
    assert d["layer_kinds"] == ["linear", "linear", "linear", "full"]
    assert (d["hidden_size"], d["num_heads"], d["num_kv_heads"], d["head_dim"]) == (
        3840, 30, 30, 128)
    assert (d["linear_heads"], d["linear_key_dim"], d["linear_value_dim"]) == (
        30, 96, 192)
    assert (d["conv_kernel"], d["conv_dim"], d["allow_neg_eigval"]) == (4, 11520, True)
    assert (d["intermediate_size"], d["vocab_size"], d["max_context"]) == (
        11008, 100352, 4096)
    keys = cell().family.keys
    assert keys.reducible == ("num_hidden_layers",)
    assert keys.param_count(d) == (
        16 * 126_819_840 + 12 * 88_750_332 + 4 * 58_990_080
        + 2 * 385_351_680 + 3_840) == 4_100_788_944
    # K and V 15,360 B a token a full layer; S and the tail 2,280,960 B a slot
    assert keys.kv_bytes(d, 1, 16) == 16 * 4 * 15_360
    assert keys.kv_bytes(d, 4112, 16) == 4_042_260_480
    assert keys.state_bytes_per_slot_layer(d) == 2_211_840 + 69_120 == 2_280_960
    assert keys.state_bytes(d, 16) == 437_944_320


REFUSED = {
    "a_window_layer": ({"layer_types": 2 * ["linear_attention", "linear_attention",
                                            "sliding_attention", "full_attention"]},
                       "sliding_attention"),
    "another_period": ({"layer_types": 4 * ["linear_attention", "full_attention"]},
                       "whole periods"),
    "fewer_layer_types_than_layers": (
        {"layer_types": ["linear_attention", "linear_attention",
                         "linear_attention", "full_attention"]}, "whole periods"),
    "a_depth_that_cuts_a_period": ({"num_hidden_layers": 6}, "whole periods"),
    "key_and_value_heads_differ": ({"linear_num_value_heads": 8},
                                   "linear_num_key_heads"),
    "a_rope_theta": ({"rope_parameters": {"rope_theta": 500000.0}}, "rope_theta"),
    "attention_bias": ({"attention_bias": True}, "attention_bias"),
    "tied_embeddings": ({"tie_word_embeddings": True}, "untied"),
    "another_activation": ({"hidden_act": "gelu"}, "hidden_act"),
    "a_convolution_of_one_tap": ({"linear_conv_kernel_dim": 1},
                                 "linear_conv_kernel_dim"),
    "another_head_dim": ({"assumed": {"max_context": 256, "head_dim": 32}},
                         "head_dim"),
    "context_past_the_published": ({"assumed": {"max_context": 1024, "head_dim": 16}},
                                   "max_position_embeddings"),
    "a_key_nobody_reads": ({"linear_use_gate": True}, "linear_use_gate"),
    "a_width_in_reduced": ({"reduced": ["linear_key_head_dim"]}, "linear_key_head_dim"),
    "the_heads_in_reduced": ({"reduced": ["linear_num_key_heads"]},
                             "linear_num_key_heads"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_keys_refused_by_name(what, tmp_path):
    change, named = REFUSED[what]
    with pytest.raises(ValueError, match=named):
        spec.Cell(with_doc(tmp_path, {**tiny(), **change}), "w")


def test_a_missing_key_is_refused_by_name(tmp_path):
    doc = tiny()
    del doc["linear_conv_kernel_dim"]
    with pytest.raises(ValueError, match="linear_conv_kernel_dim"):
        spec.Cell(with_doc(tmp_path, doc), "w")


def test_program_config_is_the_hybrid_family():
    model = cell().family.part("program").build(cell().dims)
    assert type(model).__name__ == "OlmoHybridConfig"
    assert (model.num_layers, model.linear_layers, model.cache_layers) == (16, 12, 4)
    assert model.layer_kinds == ("linear", "linear", "linear", "full")
    assert model.recurrent_state == (12, (30, 96, 192), (3, 11520))
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (30, 30, 128)
    assert model.max_seq_len == 4096 and model.rms_eps == 1e-6
    assert not model.tie_embeddings and model.allow_neg_eigval
    assert model.num_params() == 4_100_788_944
    import dataclasses

    from llm_d_fast_model_actuation_tpu.engine import server

    assert dataclasses.replace(
        server.MODEL_CONFIGS["olmo-hybrid-7b"](), num_layers=16) == model


def test_reference_weights_are_the_programs_weights(monkeypatch):
    """Every leaf the program holds, the decay's two float32 vectors among
    them."""
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    base.reference_weights_are_the_programs(tiny())


def served(config, seed):
    """``base.served`` at this family's vocabulary, with segments short
    enough that most prompts resume from carried state, and more requests
    than slots so that slots are recycled."""
    import random

    from fmabench import serve
    from llm_d_fast_model_actuation_tpu.engine.engine import (
        EngineConfig, InferenceEngine,
    )

    eng = InferenceEngine(
        EngineConfig(model=serve.build_model_config(config, DATA_DIR), max_batch=3,
                     page_size=4, num_pages=256, decode_chunk=8,
                     max_prefill_tokens=32, prefix_caching=False),
        seed=seed,
    )
    assert eng.pool.k_pages.shape[0] == 2 and eng.pool.state.shape[:2] == (6, 3)
    rng = random.Random(seed)
    prompts = [[rng.randrange(1, 256) for _ in range(n)]
               for n in (24, 40, 9, 60, 33, 17, 50, 28)]
    outs = eng.generate(prompts, max_new_tokens=32)
    assert eng.state_resumed_segments == 4
    return [{"prompt": p, "tokens": list(o)} for p, o in zip(prompts, outs)]


def test_program_agrees_with_the_reference_and_the_control_does_not(monkeypatch):
    """In process: prefill in segments that resume from the slot's state,
    then decode through pages and state, against the family's reference (one
    forward, the recurrence token by token); and the int8 control outside
    the same limit."""
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    monkeypatch.setattr(base, "served", served)
    rows = base.readings(tiny())
    for prog, _ in rows:
        assert prog["finite"] and prog["compared_tokens"] == 8 * 32
    prog = np.mean([p["gap_mean"] for p, _ in rows])
    ctrl = np.mean([c["gap_mean"] for _, c in rows])
    assert ctrl > POOLED_MEAN_LIMIT > prog
    assert np.mean([c["nonzero_share"] for _, c in rows]) > 1.3 * np.mean(
        [p["nonzero_share"] for p, _ in rows])


def test_a_state_that_is_not_carried_comes_out_not_correct(monkeypatch):
    """The reference with its recurrence RESET every 32 positions (what a
    program that dropped the state between segments would serve) put where
    the program stands reads gaps far outside the limit the program holds."""
    import functools

    import jax

    from fmabench import reference

    config = tiny()
    dims = spec.model_dims(config, DATA_DIR)
    fam = spec.family_of(config, DATA_DIR).part("reference")
    reqs = served(config, 1)
    weights = jax.jit(functools.partial(fam.init_weights, d=dims))(np.uint32(1))
    gaps = []
    for req in reqs[:4]:
        ids = jax.numpy.asarray(req["prompt"] + req["tokens"][:-1])
        rows = jax.numpy.arange(len(req["prompt"]) - 1, len(ids))
        with jax.default_matmul_precision("highest"):
            ref = fam.forward_logits(dims, weights, ids, len(ids), rows)
            # the last 32 positions alone: every state starts from zero there
            cut = max(0, len(ids) - 32)
            low = fam.forward_logits(
                dims, weights, ids[cut:], len(ids) - cut,
                jax.numpy.maximum(rows - cut, 0))
        put_first = jax.numpy.argmax(low, axis=-1)
        keep = np.asarray(rows - cut >= 0)
        gaps.append(np.asarray(
            ref.max(-1) - jax.numpy.take_along_axis(ref, put_first[:, None], -1)[:, 0]
        )[keep])
    assert reference.reduce_gaps(gaps)["gap_mean"] > 5 * POOLED_MEAN_LIMIT


def test_rehearsal_run_is_correct_and_reads_the_cells_counters():
    """A whole traced run on the CPU: ``correct``, no request failed, no
    compile inside the window, the state's counters read, and no device
    metric reported from a CPU."""
    proc = base.rehearsal("tiny.hybridmix", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "fmabench", "--rehearse", "--benchmark", TINY_BENCH,
         "--workload", "tiny.hybridmix", "--seed", "2147500007", "--seconds", "3",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["window_compiles.hybridmix"] == 0
    assert metrics["gdn_token_updates.hybridmix"] > 0
    assert metrics["gdn_token_updates.hybridmix"] % 6 == 0
    assert metrics["gdn_resumed_segments.hybridmix"] > 0
    assert metrics["sched_host_s.hybridmix"] > 0
    for device_metric in ("decode_step_dev_ms.hybridmix", "prefill_dev_ms.hybridmix",
                          "paged_decode_hbm_pct.hybridmix", "gdn_decode_hbm_pct.hybridmix",
                          "device_idle_pct.hybridmix"):
        assert device_metric not in metrics


def test_broken_timed_path_comes_out_not_correct():
    proc = base.rehearsal("tiny.hybridmix", "--serve-module",
                          "tests.fmabench.broken_serve", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["gap_max"]["value"] > line["checks"]["gap_max"]["limit"]


# -- the cell's metric files ---------------------------------------------------------

METRICS = {
    "window_compiles.hybridmix": ("compile", "program_counter"),
    "decode_step_dev_ms.hybridmix": ("model step", "device_trace"),
    "prefill_dev_ms.hybridmix": ("model step", "device_trace"),
    "device_idle_pct.hybridmix": ("device", "device_trace"),
    "sched_host_s.hybridmix": ("scheduler", "program_counter"),
    "paged_decode_hbm_pct.hybridmix": ("kernels", "device_trace"),
    "gdn_decode_hbm_pct.hybridmix": ("kernels", "device_trace"),
    "gdn_token_updates.hybridmix": ("model step", "program_counter"),
    "gdn_resumed_segments.hybridmix": ("cache", "program_counter"),
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
    assert c.traffic["clients"] == 16 and c.traffic["pool"] == 16
    assert c.traffic["prompt_len"] == {
        "dist": "lognormal", "median": 1536, "sigma": 0.6, "min": 256, "max": 3072}
    assert c.traffic["output_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 128, "max": 1024}
    assert c.engine_options(False) == (
        "--max-batch 16 --page-size 16 --num-pages 4112 "
        "--max-prefill-tokens 1024 --decode-chunk 8").split()
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    assert longest == 4096 == c.dims["max_context"]
    # the worst case fits the pool less its reserved page: nothing is preempted
    assert 16 * longest <= (4112 - 1) * 16
    # the ladder reaches the three cold buckets and every suffix bucket
    assert c.traffic["warmup"]["prompt_lens"] == [
        256, 512, 1024, 1040, 1056, 1088, 1152, 1280, 1536, 2048]
    # three prompts in four of the pool run past one segment
    from fmabench import traffic

    lens = traffic.stratified_lengths(c.traffic["prompt_len"], 16)
    assert sum(n > 1024 for n in lens) == 12 and (min(lens), max(lens)) == (502, 3072)


def test_state_readers_read_the_programs_counters():
    ev = readers.Evidence()
    ev.stats_open = {"state": {"token_updates": 12 * 1000, "resumed_segments": 3}}
    ev.stats_close = {"state": {"token_updates": 12 * 151_000, "resumed_segments": 80}}
    assert readers.read_metric(
        spec.metric_file("gdn_token_updates.hybridmix"), ev) == 12 * 150_000
    assert readers.read_metric(
        spec.metric_file("gdn_resumed_segments.hybridmix"), ev) == 77


def test_state_roofline_counts_one_read_and_one_write_of_the_live_slots():
    fn = spec.roofline_function("gdn_decode_step")
    d = cell().dims
    assert fn(d) is None and fn({"live_seqs": 4}) is None
    got = fn({**d, "live_seqs": 14.5})
    # a layer's need, spread over the two ops a layer's step is today
    assert 2 * got["bytes"] == 14.5 * 2 * 2_280_960
    assert 2 * got["flops"] == 14.5 * 8.0 * 30 * 96 * 192
    # the bytes decide: far below the ridge
    assert got["flops"] / 197e12 < got["bytes"] / 819e9


def test_state_roofline_reader_sums_a_layers_two_ops():
    """The metric's regex picks the decode step's two state ops by their
    printed shapes (names as a v5e capture of this cell gave them) and none
    of the prefill programs'; the share is the layer's bytes over both ops'
    summed time."""
    import re

    state = "f32[12,16,30,96,192]{4,3,2,1,0:T(8,128)}"
    read = ("%fusion.675 = (f32[16,30,192]{2,1,0:T(8,128)S(1)}, f32[16,30,192]"
            "{2,1,0:T(8,128)S(1)}) fusion(" + state + " %fusion.674, s32[] %select_n.521)")
    update = "%fusion.660 = " + state + " fusion(" + state + " %get-tuple-element.2476)"
    others = [
        "%bitcast_dynamic-update-slice_fusion.8 = " + state + " fusion(" + state + " %p)",
        "%fusion.12 = f32[1,30,96,192]{3,2,1,0} fusion(" + state + " %p, s32[] %i)",
        "%while.72 = (s32[], bf16[16,3840], " + state + ") while(%tuple.4)",
        "%fusion.639 = bf16[16,1,11520]{2,0,1} fusion(bf16[16,3840] %x)",
    ]
    reader = spec.metric_file("gdn_decode_hbm_pct.hybridmix")
    rx = re.compile(reader["regex"])
    assert rx.search(read) and rx.search(update)
    assert not any(rx.search(name) for name in others)

    class Trace:
        def matching(self, regex, line="ops"):
            # one layer-step: a read pass of 65 us and an update of 145 us
            return 65e-6 + 145e-6, 2

    ev = readers.Evidence()
    ev.trace, ev.shapes = Trace(), {**cell().dims, "live_seqs": 16}
    ev.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = readers.read_metric(reader, ev)
    assert share == pytest.approx(100 * (16 * 2 * 2_280_960 / 819e9) / 210e-6)


def test_decode_roofline_counts_thirty_kv_heads_and_one_call():
    fn = readers.roofline_mod.paged_decode_step
    got = fn({**cell().dims, "live_seqs": 16, "live_kv_tokens": 32000})
    assert got["bytes"] == 32000 * 15_360 + 16 * 30 * 128 * 2 * 2
