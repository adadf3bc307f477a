"""The family ``exaone_moe`` (``fmabench/families/exaone_moe/``), its
configuration ``k-exaone-236b-a23b-d8-e8`` and its cell
``k-exaone-236b.mtpmix``, after the pattern of
``test_fmabench_nemotron_h.py``: the keys it refuses by name, its sizes to the
unit (the uncut 236.5 B among them), its reference (both attention kinds, the
share of the experts) against the program at a tiny size with the prediction
module drafting (in process, and as a whole CPU rehearsal), and the metric
files of its cell. The rehearsal's own files are under
``fmabench/testdata/exaone_moe/``.
"""

import json
import os
import re
import subprocess
import sys

import numpy as np
import pytest

import test_fmabench_correct as base
from fmabench import readers, spec

CELL, CONFIG = "k-exaone-236b.mtpmix", "k-exaone-236b-a23b-d8-e8"
DATA_DIR = "fmabench/testdata/exaone_moe"
TINY_BENCH = os.path.join(spec.ROOT, DATA_DIR, "benchmark.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ["num_hidden_layers", "num_experts", "vocab_size"]
#: the gap's 95th percentile pooled over two of base.SEEDS at the tiny size:
#: the program reads 0.053 and 0.000 (0.027 over the two; 0.002 on the third)
#: and the int8 control 0.094 and 0.069 (0.081; 0.132); the limit lies between.
#: (The mean gap separates by 1.3x only here, 0.0127 against 0.0170: a router
#: near-tie that falls the other way swaps an expert whose share of a tiny
#: layer is large, in both, and the control does not round the held experts.)
POOLED_P95_LIMIT = 0.042


def tiny():
    return spec.load_json(os.path.join(
        spec.ROOT, DATA_DIR, "configs", "tiny-exaone-moe.json"))


def cell():
    return spec.Cell(spec.benchmark(), CELL)


def with_doc(tmp_path, doc):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": DATA_DIR,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-mtpmix",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary():
    doc = spec.config_file(CONFIG)
    assert doc["family"] == "exaone_moe" and doc["reduced"] == CUT
    row = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == CUT and row["source"] == doc["source"]
    assert doc["source"].endswith("LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json")
    assert doc["published"] == {
        "num_hidden_layers": 48, "num_experts": 128, "vocab_size": 153600}
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        8, 8, 19200)
    # the published lists stay whole in the file; their first 8 are served
    assert len(doc["layer_types"]) == len(doc["sliding_windows"]) == 48
    assert doc["layer_types"][:4] == ["sliding_attention"] * 3 + ["full_attention"]
    assert doc["mlp_layer_types"][:2] == ["dense", "sparse"]
    for what in ("block", "qk_norm", "global_nope", "ffn", "selection_bias",
                 "mtp", "max_context", "dense_max_rows"):
        assert doc["assumed"][what]
    assert "TAKEN, not stated by the source" in doc["assumed"]["mtp"]
    assert "modeling_exaone4.py" in doc["assumed"]["block"]
    assert doc["assumed"]["share_index"] == 0
    assert "4,394,720,512" in doc["deployment"] and "v5e-64" in doc["deployment"]
    assert "4 pipeline stages of 16" in doc["deployment"]
    assert set(doc["check"]["limits"]) <= {"gap_max", "gap_mean", "gap_p95"}
    assert "PR 47" in doc["check"]["why"]
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows if r["name"] == "K-EXAONE-236B-A23B")
        assert pub["source_url"] == doc["source"]
        # no key of the row differs or is left out but the three in `reduced`
        for key, value in pub["config"].items():
            assert (doc[key] == value) == (key not in CUT), key
            if key in CUT:
                assert doc["published"][key] == value


def test_the_sizes_are_the_issues_table_to_the_unit():
    d, keys = cell().dims, cell().family.keys
    assert list(keys.reducible) == CUT
    assert (d["num_layers"], d["expert_layers"], d["nextn_layers"]) == (8, 7, 1)
    assert d["layer_windows"] == [1, 1, 1, 0, 1, 1, 1, 0]
    # one entry a call of the decode kernel a step: the module's block last
    assert d["window_layout"] == [1, 1, 1, 0, 1, 1, 1, 0, 0]
    assert (d["experts_held"], d["router_width"], d["experts_per_token"]) == (8, 128, 8)
    assert (d["vocab_size"], d["max_context"], d["hidden_size"]) == (19200, 4096, 6144)
    assert (d["window"], d["rope_theta"], d["routed_scaling"]) == (128, 1e6, 2.5)
    norms = 2 * 128 + 2 * 6144
    assert keys.attention_params(d) - norms == 113_246_208
    assert keys.expert_params(d) == 37_748_736  # a routed or the shared expert
    assert 6144 * 128 == 786_432  # the router
    assert 3 * 6144 * 18432 == 339_738_624  # the dense FFN
    assert keys.expert_layer_ffn_params(d) == 9 * 37_748_736 + 786_432 + 128
    assert keys.module_params(d) == (
        keys.attention_params(d) + keys.expert_layer_ffn_params(d)
        + 12_288 * 6144 + 3 * 6144) == 529_299_840
    ends = 2 * 19200 * 6144 + 6144
    assert keys.param_count(d) == (
        452_997_376 + 7 * 453_783_936 + ends + 529_299_840) == 4_394_720_512
    # uncut, by the same formulas: the model's published 236 B
    whole = {**d, "num_layers": 48, "expert_layers": 47, "experts_held": 128,
             "vocab_size": 153600}
    main = lambda d: keys.param_count(d) - keys.module_params(d)  # noqa: E731
    assert 236.4e9 < main(whole) < 236.6e9
    # a token activates 8 experts of each expert layer and every other matrix
    assert 23e9 < main({**whole, "experts_held": 8}) < 24e9
    # K and V 4,096 B a token a layer: two full layers and the module's
    assert keys.kv_bytes(d, 1, 16) == 3 * 16 * 4_096
    assert keys.kv_bytes(d, 12304, 16) == 2_419_064_832
    # a ring of 128 + 1,024 positions a slot a window layer: 28.3 MB a slot
    assert keys.ring_bytes(d, 1, 1024) == 6 * 1152 * 4_096 == 28_311_552
    assert keys.ring_bytes(d, 48, 1024) == 1_358_954_496
    state = 2 * keys.param_count(d) + 2_419_064_832 + 1_358_954_496
    assert 12.56e9 < state < 12.58e9



REFUSED = {
    "more_than_one_expert_group": ({"n_group": 8}, "n_group"),
    "a_group_limited_top_k": ({"topk_group": 4}, "topk_group"),
    "softmax_scores": ({"scoring_func": "softmax"}, "sigmoid"),
    "scores_not_renormalised": ({"norm_topk_prob": False}, "norm_topk_prob"),
    "two_leading_dense_layers": ({"first_k_dense_replace": 2}, "first_k_dense_replace"),
    "tied_embeddings": ({"tie_word_embeddings": True}, "untied"),
    "another_activation": ({"hidden_act": "gelu"}, "hidden_act"),
    "a_scaled_rotary": (
        {"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn", "factor": 4}},
        "rope_parameters"),
    "no_prediction_module": ({"num_nextn_predict_layers": 0}, "num_nextn_predict_layers"),
    "a_windowed_prediction_module": (
        {"mtp_layer_types": ["sliding_attention"], "mtp_sliding_windows": [24]},
        "mtp_layer_types"),
    "a_layer_kind_nobody_serves": (
        {"layer_types": ["linear_attention"] + tiny()["layer_types"][1:]},
        "not a kind served"),
    "windows_that_disagree_with_the_kinds": (
        {"sliding_windows": [0] + tiny()["sliding_windows"][1:]}, "disagree"),
    "a_pattern_that_disagrees_with_the_kinds": (
        {"sliding_window_pattern": "LGLG"}, "disagree"),
    "a_depth_of_no_whole_periods": ({"num_hidden_layers": 6}, "whole periods"),
    "a_sparse_first_layer": (
        {"mlp_layer_types": ["sparse"] * 48}, "mlp_layer_types"),
    "a_share_outside_the_router": (
        {"assumed": {"max_context": 256, "share_index": 4, "dense_max_rows": 4}},
        "outside the router"),
    "context_past_the_published": (
        {"assumed": {"max_context": 1024, "share_index": 0, "dense_max_rows": 4}},
        "max_position_embeddings"),
    "a_key_nobody_reads": ({"attention_output_gate": True}, "attention_output_gate"),
    "a_width_in_reduced": ({"reduced": ["moe_intermediate_size"]},
                           "moe_intermediate_size"),
    "the_window_in_reduced": ({"reduced": ["sliding_window"]}, "sliding_window"),
    "the_experts_per_token_in_reduced": ({"reduced": ["num_experts_per_tok"]},
                                         "num_experts_per_tok"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_keys_refused_by_name(what, tmp_path):
    change, named = REFUSED[what]
    with pytest.raises(ValueError, match=named):
        spec.Cell(with_doc(tmp_path, {**tiny(), **change}), "w")


@pytest.mark.parametrize(
    "key", ["layer_types", "mlp_layer_types", "mtp_layer_types", "sliding_windows",
            "mtp_sliding_windows", "rope_parameters", "num_shared_experts"])
def test_a_missing_key_is_refused_by_name(key, tmp_path):
    doc = tiny()
    del doc[key]
    with pytest.raises(ValueError, match=key):
        spec.Cell(with_doc(tmp_path, doc), "w")


def test_program_config_is_the_exaone_moe_family():
    model = cell().family.part("program").build(cell().dims)
    assert type(model).__name__ == "ExaoneMoeConfig"
    assert (model.num_layers, model.expert_layers, model.nextn_layers) == (8, 7, 1)
    assert model.window_pattern == (128, 128, 128, 0)
    assert model.rope_pattern == (True, True, True, False)
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (64, 8, 128)
    assert (model.num_experts, model.router_outputs, model.share_index) == (8, 128, 0)
    assert (model.experts_per_token, model.routed_scaling) == (8, 2.5)
    assert (model.intermediate_size, model.dense_intermediate_size) == (2048, 18432)
    assert model.dense_max_rows == 240 == cell().config["assumed"]["dense_max_rows"]
    assert model.max_seq_len == 4096 and model.rms_eps == 1e-5 and model.qk_norm
    assert not model.tie_embeddings and model.vocab_size == 19200
    assert model.num_params() == 4_394_720_512 == cell().family.keys.param_count(
        cell().dims)
    # the main stack's layers keep K and V; the engine's option adds the module's
    assert not model.serve_mtp and model.cache_layers == 8
    from llm_d_fast_model_actuation_tpu.engine import server

    assert server.MODEL_CONFIGS["k-exaone-236b-a23b"]() == model
    args = server.make_arg_parser().parse_args(
        ["--model", "tiny", *cell().engine_options(False)])
    assert args.speculative_mtp == 1 and args.max_batch == 48
    # the rehearsal's file holds share 1 of four
    small = spec.family_of(tiny(), DATA_DIR)
    built = small.part("program").build(small.dims(tiny()))
    assert (built.num_experts, built.router_outputs, built.share_index) == (4, 16, 1)
    assert built.window_pattern == (24, 24, 24, 0) and built.dense_max_rows == 4


def test_reference_weights_are_the_programs_weights():
    """Every leaf of the main stack that the program holds: the reference
    draws its stacks one entry shorter (the prediction module's block is the
    program's last, and the comparison never computes it) and reads the
    program's numbers, since an element of a draw is a function of the key
    and of its own row-major index alone. The held experts' three stacks are
    no leaves of the reference: it keeps what draws them and draws one
    expert's matrices where they are used, bit for bit."""
    import functools

    import jax

    from fmabench import serve
    from llm_d_fast_model_actuation_tpu.models.registry import init_params_placed

    dims = spec.model_dims(tiny(), DATA_DIR)
    fam = spec.family_of(tiny(), DATA_DIR).part("reference")
    h, f = dims["hidden_size"], dims["expert_size"]
    matrices = jax.jit(lambda p, e: (
        fam.expert_matrix(dims, p["w_gate_key"], 3, e, h, f),  # layer static
        fam.expert_matrix(dims, p["w_up_key"], 3, e, h, f),
        fam.expert_matrix(dims, p["w_down_key"], 3, e, f, h)))
    L, ne = dims["num_layers"], dims["expert_layers"]
    for seed in (0, 3_000_000_019, 2**32 + 5):
        mine = jax.jit(functools.partial(fam.init_weights, d=dims))(
            np.uint32(seed % 2**32))
        theirs = init_params_placed(
            jax.random.key(seed), serve.build_model_config(tiny(), DATA_DIR))
        assert theirs["layers"]["wq"].shape[0] == L + 1 == ne + 2
        theirs.pop("mtp")
        stacks = {k: theirs["experts"].pop(k) for k in ("w_gate", "w_up", "w_down")}
        keys = {k: mine["experts"].pop(k) for k in fam.EXPERT_KEYS}
        theirs = dict(jax.tree_util.tree_leaves_with_path(theirs))
        mine = jax.tree_util.tree_leaves_with_path(mine)
        assert len(mine) == len(theirs)     # every other leaf the program has
        for path, leaf in mine:
            # the program's stack less the module's entry, its last
            held = np.asarray(theirs[path], np.float32)[: leaf.shape[0]]
            assert held.shape == leaf.shape, jax.tree_util.keystr(path)
            assert np.array_equal(np.asarray(leaf, np.float32), held), (
                seed, jax.tree_util.keystr(path))
        for e in range(dims["experts_held"]):
            gate, up, down = matrices(keys, e)
            assert np.array_equal(gate, np.asarray(stacks["w_gate"][3, e], np.float32))
            assert np.array_equal(up, np.asarray(stacks["w_up"][3, e], np.float32))
            assert np.array_equal(down, np.asarray(stacks["w_down"][3, e], np.float32))


_SERVED = {}


def served(seed):
    """What the program serves under the tiny configuration with the seed's
    weights and the module drafting: eight prompts through three slots (slots
    are recycled), in segments short enough that four prompts go through the
    suffix program and the rings wrap. ONE engine for every seed, its weights
    put anew (the programs take them as an argument, so they compile once)."""
    import random

    import jax

    from fmabench import serve
    from llm_d_fast_model_actuation_tpu.engine.engine import (
        EngineConfig, InferenceEngine,
    )
    from llm_d_fast_model_actuation_tpu.models.registry import init_params_placed

    if "engine" not in _SERVED:
        eng = _SERVED["engine"] = InferenceEngine(
            EngineConfig(model=serve.build_model_config(tiny(), DATA_DIR),
                         max_batch=3, page_size=4, num_pages=256, decode_chunk=8,
                         max_prefill_tokens=32, prefix_caching=False,
                         speculative_mtp=1),
            seed=0,
        )
        assert eng.pool.k_pages.shape == (3, 256, 4, 32)  # 2 full + the module
        assert eng.pool.k_ring.shape == (6, 3, 14, 4, 32)  # 24 + 32 positions
    if seed not in _SERVED:
        eng = _SERVED["engine"]
        eng.params = init_params_placed(jax.random.key(seed), eng.cfg.model)
        before = eng.cache_stats()
        rng = random.Random(seed)
        prompts = [[rng.randrange(1, 256) for _ in range(n)]
                   for n in (24, 40, 9, 60, 33, 17, 50, 28)]
        outs = eng.generate(prompts, max_new_tokens=32)
        after = eng.cache_stats()
        assert after["mtp"]["drafted"] - before["mtp"]["drafted"] >= 8 * 31 - 8
        assert after["kv"]["window_tokens_evicted"] > before["kv"]["window_tokens_evicted"]
        _SERVED[seed] = [
            {"prompt": p, "tokens": list(o)} for p, o in zip(prompts, outs)]
    return _SERVED[seed]


def _weights(fam, dims, seed):
    import functools

    import jax

    return jax.jit(functools.partial(fam.init_weights, d=dims))(np.uint32(seed))


def _gaps(gap_fn, weights, low, reqs):
    """``reference.compare``'s loop with the gap function given, so that one
    compile serves every seed."""
    from fmabench import reference

    gaps = []
    for req in reqs:
        prompt, tokens = req["prompt"], req["tokens"]
        ids = prompt + tokens[:-1]
        S = -(-len(ids) // reference.SEQ_BUCKET) * reference.SEQ_BUCKET
        R = reference.ROW_BUCKET
        rows = reference._pad(list(range(len(prompt) - 1, len(ids))), R)
        gaps.append(np.asarray(gap_fn(
            weights, low, reference._pad(ids, S), np.int32(len(ids)), rows,
            reference._pad(tokens, R)))[: len(tokens)])
    return reference.reduce_gaps(gaps)


def test_program_agrees_with_the_reference_and_the_control_does_not():
    """In process: prefill in segments through rings and pages, then verify
    steps of two positions a slot with the module drafting, share 1 of the
    experts, against the family's reference (one forward of the main stack);
    and the int8 control outside the same limit."""
    import jax

    from fmabench import reference

    dims = spec.model_dims(tiny(), DATA_DIR)
    fam = spec.family_of(tiny(), DATA_DIR).part("reference")
    program = reference.make_gap_fn(fam, dims, False)
    control = reference.make_gap_fn(fam, dims, True)
    round_low = jax.jit(lambda w: reference.to_int8(w, list(fam.MATMUL_WEIGHTS)))
    rows = []
    for seed in base.SEEDS[:2]:
        weights = _weights(fam, dims, seed)
        rows.append((_gaps(program, weights, None, served(seed)),
                     _gaps(control, weights, round_low(weights), served(seed))))
    for prog, _ in rows:
        assert prog["finite"] and prog["compared_tokens"] == 8 * 32
    prog = np.mean([p["gap_p95"] for p, _ in rows])
    ctrl = np.mean([c["gap_p95"] for _, c in rows])
    assert ctrl > POOLED_P95_LIMIT > prog
    assert np.mean([c["gap_mean"] for _, c in rows]) > 1.2 * np.mean(
        [p["gap_mean"] for p, _ in rows])
    assert np.mean([c["nonzero_share"] for _, c in rows]) > 1.3 * np.mean(
        [p["nonzero_share"] for p, _ in rows])


PAD = 96  # the longest served request here is 60 + 31 positions


def _gaps_of(low_dims, last=0):
    """Gaps, under the family's reference, of the tokens that the same
    reference puts first when it is given ``low_dims`` and, with ``last``,
    the last ``last`` positions alone; on four served requests. Every
    request is padded to one length, so each forward compiles once."""
    import jax
    import jax.numpy as jnp

    from fmabench import reference

    dims = spec.model_dims(tiny(), DATA_DIR)
    fam = spec.family_of(tiny(), DATA_DIR).part("reference")
    weights = _weights(fam, dims, 1)

    def forward(d):
        def run(w, ids, length, rows):
            with jax.default_matmul_precision("highest"):
                return fam.forward_logits(d, w, ids, length, rows)
        return jax.jit(run)

    ref_fn, low_fn = forward(dims), forward({**dims, **low_dims})
    gaps = []
    for req in served(1)[:4]:
        ids = req["prompt"] + req["tokens"][:-1]
        n = len(ids)
        rows = jnp.arange(len(req["prompt"]) - 1, n)
        ref = ref_fn(weights, reference._pad(ids, PAD), n, rows)
        cut = n - last if last else 0
        low = low_fn(weights, reference._pad(ids[cut:], last or PAD), n - cut,
                     jnp.maximum(rows - cut, 0))
        put_first = jnp.argmax(low, axis=-1)
        gaps.append(np.asarray(
            ref.max(-1) - jnp.take_along_axis(ref, put_first[:, None], -1)[:, 0]
        )[np.asarray(rows - cut >= 0)])
    return reference.reduce_gaps(gaps)


def test_pages_that_are_not_carried_come_out_not_correct():
    """The reference over the last 16 positions alone (what a program whose
    full layers lost their pages and whose rings lost all but 16 positions
    would serve) put where the program stands reads gaps far outside the
    limit the program holds."""
    assert _gaps_of({}, last=16)["gap_p95"] > 5 * POOLED_P95_LIMIT


def test_another_share_of_the_experts_comes_out_not_correct():
    assert _gaps_of({"share_index": 0})["gap_p95"] > 5 * POOLED_P95_LIMIT


def test_rotary_on_the_full_layers_comes_out_not_correct():
    """A program that rotated the full layers' q and k too (the reference
    given every layer as a window layer of a window wider than the context)
    is far outside the limit."""
    wrong = {"layer_windows": [1] * 8, "window": 4096}
    assert _gaps_of(wrong)["gap_p95"] > 5 * POOLED_P95_LIMIT


def test_rehearsal_run_is_correct_and_reads_the_cells_counters():
    """A whole traced run on the CPU with the module drafting: ``correct``,
    no request failed, no compile inside the window, the drafts', the rings'
    and the experts' counters read, and no device metric reported from a
    CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "fmabench", "--rehearse", "--benchmark", TINY_BENCH,
         "--workload", "tiny.mtpmix", "--seed", "2147500007", "--seconds", "3",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["window_compiles.mtpmix"] == 0
    # a draft a live slot a step; at a vocabulary of 256 few are accepted
    assert metrics["mtp_drafted_tokens.mtpmix"] > 50
    assert 0 <= metrics["mtp_accepted_tokens.mtpmix"] < 0.1 * metrics[
        "mtp_drafted_tokens.mtpmix"]
    assert metrics["kv_window_evicted_tokens.mtpmix"] > 0
    # prompt rows go through the grouped matmuls, a verify step's 6 rows too
    # (assumed.dense_max_rows 4)
    assert metrics["moe_routed_tokens.mtpmix"] > 0
    assert metrics["sched_host_s.mtpmix"] > 0
    for device_metric in ("decode_step_dev_ms.mtpmix", "prefill_dev_ms.mtpmix",
                          "paged_decode_hbm_pct.mtpmix", "device_idle_pct.mtpmix",
                          "moe_share_decode_hbm_pct.mtpmix"):
        assert device_metric not in metrics


# -- the cell's metric files ---------------------------------------------------------

METRICS = {
    "window_compiles.mtpmix": ("compile", "program_counter"),
    "decode_step_dev_ms.mtpmix": ("model step", "device_trace"),
    "prefill_dev_ms.mtpmix": ("model step", "device_trace"),
    "device_idle_pct.mtpmix": ("device", "device_trace"),
    "sched_host_s.mtpmix": ("scheduler", "program_counter"),
    "mtp_drafted_tokens.mtpmix": ("model step", "program_counter"),
    "mtp_accepted_tokens.mtpmix": ("model step", "program_counter"),
    "kv_window_evicted_tokens.mtpmix": ("cache", "program_counter"),
    "moe_routed_tokens.mtpmix": ("model step", "program_counter"),
    "paged_decode_hbm_pct.mtpmix": ("kernels", "device_trace"),
    "moe_share_decode_hbm_pct.mtpmix": ("kernels", "device_trace"),
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


def test_the_new_entries_are_present_and_the_cell_reports_what_the_issue_names():
    # present, and after what the benchmark had before them (seven
    # configurations, seven cells); NOT that they are last: a later PR's
    # entries come after these and must not turn this test
    bench = spec.benchmark()
    assert [c["name"] for c in bench["configs"]].index(CONFIG) >= 7
    assert [w["name"] for w in bench["workloads"]].index(CELL) >= 7
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(METRICS)))
    assert names[first : first + len(METRICS)] == list(METRICS)
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert CELL in tokens["workloads"]
    c = cell()
    assert [m["name"] for m in c.end_to_end()] == ["out_tokens_per_s", "setup_s"]
    reported = {m["name"] for m in c.per_layer()}
    assert set(METRICS) <= reported
    # every other metric the cell reports is one without a list, of set-up
    assert all(
        "workloads" not in m and m["moves"] == "setup_s"
        for m in c.per_layer() if m["name"] not in METRICS
    )
    assert c.chips == 1 and c.traffic["kind"] == "closed"
    assert c.traffic["clients"] == 48 and c.traffic["pool"] == 16
    assert c.traffic["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 256, "max": 3072}
    assert c.traffic["output_len"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 192, "max": 1024}
    assert c.engine_options(False) == (
        "--max-batch 48 --page-size 16 --num-pages 12304 "
        "--max-prefill-tokens 1024 --decode-chunk 8 --speculative-mtp 1").split()
    assert c.traffic["check"] == {"sample": 4}
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    assert longest == 4096 == c.dims["max_context"]
    # the worst case fits the pool less its reserved page: nothing is preempted
    assert 48 * longest == 12288 * 16 <= (12304 - 1) * 16
    from fmabench import traffic
    from llm_d_fast_model_actuation_tpu.engine.engine import prefill_bucket

    lens = traffic.stratified_lengths(c.traffic["prompt_len"], 16)
    assert sum(n > 1024 for n in lens) == 8 and (min(lens), max(lens)) == (256, 3072)
    # the ladder reaches every cold bucket and every suffix bucket the pool
    # can meet (a prompt past 1,024 tokens goes through the suffix program in
    # 1,024-token segments and a rest)
    ladder = c.traffic["warmup"]["prompt_lens"]
    cold = {prefill_bucket(n, 4096) for n in ladder if n <= 1024}
    rest = {prefill_bucket(n % 1024 or 1024, 4096) for n in ladder if n > 1024}
    assert {prefill_bucket(n, 4096) for n in lens if n <= 1024} <= cold
    assert {prefill_bucket(n % 1024 or 1024, 4096) for n in lens if n > 1024} <= rest
    # ids come from the slice of the vocabulary this chip holds
    ids = traffic.Prompts(c.dims["vocab_size"], 2**31 + 7, "window").draw(4096)
    assert 1 <= min(ids) and max(ids) < 19200


def test_counter_readers_read_the_programs_counters():
    ev = readers.Evidence()
    ev.stats_open = {"mtp": {"drafted": 1000, "accepted": 2},
                     "kv": {"window_tokens_evicted": 50_000},
                     "moe": {"routed_tokens": 1000}}
    ev.stats_close = {"mtp": {"drafted": 118_000, "accepted": 9},
                      "kv": {"window_tokens_evicted": 170_000},
                      "moe": {"routed_tokens": 151_000}}
    read = lambda name: readers.read_metric(spec.metric_file(name), ev)  # noqa: E731
    assert read("mtp_drafted_tokens.mtpmix") == 117_000
    assert read("mtp_accepted_tokens.mtpmix") == 7
    assert read("kv_window_evicted_tokens.mtpmix") == 120_000
    assert read("moe_routed_tokens.mtpmix") == 150_000


def test_paged_roofline_counts_a_steps_nine_calls_and_a_slots_cache_once():
    """The mean over a verify step's nine calls of the decode kernel: the
    two full layers and the module's block read whole contexts, the six
    window layers at least min(c, 128) a sequence; K and V are counted ONCE
    a slot, whatever the step's query positions."""
    reader = spec.metric_file("paged_decode_hbm_pct.mtpmix")
    fn = spec.roofline_function(reader["function"])
    d = cell().dims
    assert fn(d) is None
    got = fn({**d, "live_seqs": 48, "live_kv_tokens": 48 * 1800})
    tokens = 48 * 1800
    full = tokens // 4096  # sequences at the longest context, the least windowed
    least = full * 128 + min(tokens - full * 4096, 128)
    per_call = (3 * tokens + 6 * least) / 9
    assert got["bytes"] == per_call * 8 * 128 * 2 * 2 + 48 * 64 * 128 * 2 * 2
    assert re.search(reader["regex"], "paged_decode_inline")


def test_moe_share_regex_picks_the_decode_steps_three_ops_a_layer_alone():
    """The metric's regex picks the decode step's routed-expert ops by their
    printed shapes (names as the chunk program compiled for a v5e has them:
    tests/test_chip_compile_cells.py holds that) and none of the prompt
    programs', the shared expert's or the dense layer's."""
    reader = spec.metric_file("moe_share_decode_hbm_pct.mtpmix")
    pick = re.compile(reader["regex"])
    stack = "bf16[8,8,6144,2048]{3,2,1,0:T(8,128)(2,1)}"
    down = "bf16[8,8,2048,6144]{3,2,1,0:T(8,128)(2,1)}"
    picked = [
        "%fusion.1500 = bf16[8,96,2048]{2,1,0:T(8,128)(2,1)S(1)} fusion(" + stack
        + " %get-tuple-element.2643, bf16[96,6144]{1,0} %reshape.3202)",
        "%fusion.1502 = bf16[96,6144]{1,0:T(8,128)(2,1)S(1)} fusion(" + down
        + " %get-tuple-element.2641, f32[96,8]{1,0} %copy.968)",
    ]
    others = [
        "%fusion.77 = bf16[96,2048]{1,0} fusion(bf16[8,6144,2048]{2,1,0} %s_gate)",
        "%fusion.78 = bf16[96,18432]{1,0} fusion(bf16[1,6144,18432]{2,1,0} %w_gate)",
        "%gmm.2 = bf16[8192,6144]{1,0} custom-call(bf16[8192,2048]{1,0} %x)",
        "%fusion.9 = bf16[8,6144,2048]{2,1,0} fusion(" + stack + " %param_0.4529)",
        "%fusion.3 = bf16[48,6144]{1,0} fusion(" + down + " %gte, f32[48,8]{1,0} %w)",
    ]
    assert all(pick.search(op) for op in picked)
    assert not any(pick.search(op) for op in others)
    fn = spec.roofline_function(reader["function"])
    d = cell().dims
    got = fn({**d, "live_seqs": 48})
    touched = 8 * (1 - (120 / 128) ** 48)
    assert 7.6 < touched < 7.7
    rows = 48 * 8 * 8 / 128
    assert got["bytes"] == touched * 6144 * 2048 * 2 + rows * (6144 + 2048) * 2
