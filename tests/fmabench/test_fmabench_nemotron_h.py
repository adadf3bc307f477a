"""The family ``nemotron_h`` (``fmabench/families/nemotron_h/``), its
configuration ``nemotron-3-super-120b-a12b-d11-e128`` and its cell
``nemotron-3-super-120b.ssmchat``, after the pattern of
``test_fmabench_kimi_linear.py``: the keys it refuses by name, its sizes to
the unit (the uncut 120.67 B among them), its reference (the state-space
recurrence token by token, the share of the latent experts) against the
program at a tiny size (in process, and as a whole CPU rehearsal), and the
metric files of its cell. The rehearsal's own files are under
``fmabench/testdata/nemotron_h/``.
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

CELL, CONFIG = "nemotron-3-super-120b.ssmchat", "nemotron-3-super-120b-a12b-d11-e128"
DATA_DIR = "fmabench/testdata/nemotron_h"
TINY_BENCH = os.path.join(spec.ROOT, DATA_DIR, "benchmark.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
CUT = ["num_hidden_layers", "n_routed_experts", "vocab_size"]
#: the mean gap pooled over two of base.SEEDS at the tiny size: the program
#: reads 0.040-0.041 a seed and the int8 control 0.080-0.122 (0.101 over the
#: two; a router near-tie that falls the other way swaps an expert whose
#: share of a tiny layer is large, in both); the limit is their geometric mean
POOLED_MEAN_LIMIT = 0.064


def tiny():
    return spec.load_json(os.path.join(
        spec.ROOT, DATA_DIR, "configs", "tiny-nemotron-h.json"))


def cell():
    return spec.Cell(spec.benchmark(), CELL)


def with_doc(tmp_path, doc):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": DATA_DIR,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-ssmchat",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary():
    doc = spec.config_file(CONFIG)
    assert doc["family"] == "nemotron_h" and doc["reduced"] == CUT
    row = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == CUT and row["source"] == doc["source"]
    assert doc["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    assert doc["published"] == {
        "num_hidden_layers": 88, "n_routed_experts": 512, "vocab_size": 131072}
    assert (doc["num_hidden_layers"], doc["n_routed_experts"], doc["vocab_size"]) == (
        11, 128, 32768)
    # the published pattern stays whole in the file; its first 11 are served
    pattern = doc["hybrid_override_pattern"]
    assert len(pattern) == 88 and pattern[:11] == "MEMEMEM*EME"
    assert (pattern.count("M"), pattern.count("E"), pattern.count("*")) == (40, 40, 8)
    stars = [i for i, c in enumerate(pattern) if c == "*"]
    assert [b - a for a, b in zip([-1] + stars, stars + [88])] == [
        8, 9, 9, 11, 11, 11, 11, 9, 10]
    for what in ("block", "mamba_layers", "attention_layers", "expert_layers",
                 "selection_bias", "state", "decay_init", "mtp", "max_context"):
        assert doc["assumed"][what]
    assert "NOT served" in doc["assumed"]["mtp"]
    assert doc["assumed"]["share_index"] == 0
    assert "4,648,163,712" in doc["deployment"] and "v5e-32" in doc["deployment"]
    assert "120,668,707,840" in doc["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows
                   if r["name"] == "NVIDIA-Nemotron-3-Super-120B-A12B-BF16")
        assert pub["source_url"] == doc["source"]
        # no key of the row differs or is left out but the three in `reduced`
        for key, value in pub["config"].items():
            assert (doc[key] == value) == (key not in CUT), key
            if key in CUT:
                assert doc["published"][key] == value


def test_the_sizes_are_the_issues_table_to_the_unit():
    d, keys = cell().dims, cell().family.keys
    assert list(keys.reducible) == CUT
    assert (d["num_layers"], d["ssm_layers"], d["expert_layers"], d["attn_layers"]) == (
        11, 5, 5, 1)
    assert d["layer_kinds"] == "MEMEMEM*EME"
    assert (d["experts_held"], d["router_width"], d["experts_per_token"]) == (128, 512, 22)
    assert (d["vocab_size"], d["max_context"], d["hidden_size"]) == (32768, 4096, 4096)
    assert (d["in_proj_size"], d["conv_dim"], d["inner_size"]) == (18_560, 10_240, 8_192)
    norm = 4096
    assert 4096 * 18_560 == 76_021_760
    assert keys.mamba_layer_params(d) + norm == 109_640_064
    assert keys.attention_layer_params(d) + norm == 35_655_680
    assert keys.expert_params(d) == 5_505_024
    shared, latent, router = 2 * 4096 * 5376, 2 * 4096 * 1024, 4096 * 512 + 512
    assert (shared, latent, router) == (44_040_192, 8_388_608, 2_097_664)
    assert 128 * keys.expert_params(d) == 704_643_072
    assert keys.expert_layer_params(d) + norm == 759_173_632
    assert keys.expert_layer_params({**d, "experts_held": 512}) + norm == 2_873_102_848
    ends = 2 * 32768 * 4096 + 4096
    assert ends == 268_439_552
    assert keys.param_count(d) == (
        5 * 109_640_064 + 5 * 759_173_632 + 35_655_680 + ends) == 4_648_163_712
    # uncut, by the same formulas: the model's published 120B
    pattern = spec.config_file(CONFIG)["hybrid_override_pattern"]
    whole = {**d, "num_layers": 88, "layer_kinds": pattern, "ssm_layers": 40,
             "expert_layers": 40, "attn_layers": 8, "experts_held": 512,
             "vocab_size": 131072}
    assert keys.param_count(whole) == 120_668_707_840
    # a token activates 22 experts of each expert layer and every other matrix
    active = keys.param_count({**whole, "experts_held": 22})
    assert 12.6e9 < active < 12.8e9
    # K and V 1,024 B a token; h and the tail 4,255,744 B a slot a layer
    assert keys.kv_bytes(d, 1, 16) == 16 * 1_024
    assert keys.kv_bytes(d, 24592, 16) == 402_915_328
    assert keys.state_bytes_per_slot_layer(d) == 4_194_304 + 61_440 == 4_255_744
    assert keys.state_bytes(d, 1) == 21_278_720
    assert keys.state_bytes(d, 128) == 2_723_676_160


def _pattern(s):
    return {"hybrid_override_pattern": s}


REFUSED = {
    "a_dense_layer_in_the_pattern": (_pattern("MEMEMEM-EMEMEMEM*EME"), "M.*E.*\\*"),
    "a_pattern_shorter_than_the_depth": (_pattern("MEMEMEM*"), "names 8 layers"),
    "more_than_one_expert_group": ({"n_group": 8}, "n_group"),
    "a_group_limited_top_k": ({"topk_group": 4}, "topk_group"),
    "heads_that_are_not_the_expansion": ({"mamba_num_heads": 6}, "expand x hidden_size"),
    "heads_that_are_no_whole_groups": ({"n_groups": 3}, "n_groups"),
    "a_sliding_window": ({"sliding_window": 4096}, "sliding_window"),
    "an_attention_bias": ({"attention_bias": True}, "attention_bias"),
    "an_mlp_bias": ({"mlp_bias": True}, "mlp_bias"),
    "a_projection_bias": ({"mamba_proj_bias": True}, "mamba_proj_bias"),
    "a_bias_everywhere": ({"use_bias": True}, "use_bias"),
    "no_convolution_bias": ({"use_conv_bias": False}, "use_conv_bias"),
    "tied_embeddings": ({"tie_word_embeddings": True}, "untied"),
    "another_expert_activation": ({"mlp_hidden_act": "silu"}, "mlp_hidden_act"),
    "another_mamba_activation": ({"mamba_hidden_act": "swish"}, "mamba_hidden_act"),
    "scores_not_renormalised": ({"norm_topk_prob": False}, "norm_topk_prob"),
    "two_epsilons": ({"layer_norm_epsilon": 1e-6}, "layer_norm_epsilon"),
    "a_convolution_of_one_tap": ({"conv_kernel": 1}, "conv_kernel"),
    "a_share_outside_the_router": (
        {"assumed": {"max_context": 256, "share_index": 4}}, "outside the router"),
    "context_past_the_published": (
        {"assumed": {"max_context": 1024, "share_index": 0}}, "max_position_embeddings"),
    "a_key_nobody_reads": ({"mamba_use_gate": True}, "mamba_use_gate"),
    "a_width_in_reduced": ({"reduced": ["moe_intermediate_size"]},
                           "moe_intermediate_size"),
    "the_latent_width_in_reduced": ({"reduced": ["moe_latent_size"]}, "moe_latent_size"),
    "the_experts_per_token_in_reduced": ({"reduced": ["num_experts_per_tok"]},
                                         "num_experts_per_tok"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_keys_refused_by_name(what, tmp_path):
    change, named = REFUSED[what]
    with pytest.raises(ValueError, match=named):
        spec.Cell(with_doc(tmp_path, {**tiny(), **change}), "w")


@pytest.mark.parametrize("key", ["hybrid_override_pattern", "moe_latent_size", "n_groups"])
def test_a_missing_key_is_refused_by_name(key, tmp_path):
    doc = tiny()
    del doc[key]
    with pytest.raises(ValueError, match=key):
        spec.Cell(with_doc(tmp_path, doc), "w")


def test_program_config_is_the_nemotron_h_family():
    model = cell().family.part("program").build(cell().dims)
    assert type(model).__name__ == "NemotronHConfig"
    assert (model.num_layers, model.mamba_layers, model.expert_layers,
            model.cache_layers) == (11, 5, 5, 1)
    assert model.layer_kinds == tuple("MEMEMEM*EME")
    assert model.recurrent_state == (5, (128, 64, 128), (3, 10240))
    assert (model.num_heads, model.num_kv_heads, model.head_dim) == (32, 2, 128)
    assert (model.num_experts, model.router_outputs, model.share_index) == (128, 512, 0)
    assert (model.experts_per_token, model.routed_scaling) == (22, 5.0)
    assert (model.latent_size, model.intermediate_size) == (1024, 2688)
    assert model.dense_max_rows == 240 == cell().config["assumed"]["dense_max_rows"]
    assert model.max_seq_len == 4096 and model.rms_eps == 1e-5
    assert not model.tie_embeddings and model.vocab_size == 32768
    assert model.num_params() == 4_648_163_712
    from llm_d_fast_model_actuation_tpu.engine import server

    assert server.MODEL_CONFIGS["nemotron-3-super-120b-a12b"]() == model
    # the rehearsal's file holds share 1 of four
    small = spec.family_of(tiny(), DATA_DIR)
    built = small.part("program").build(small.dims(tiny()))
    assert (built.num_experts, built.router_outputs, built.share_index) == (4, 16, 1)
    assert built.layer_kinds == tuple("MEMEMEM*EME") and built.dense_max_rows == 4


def test_reference_weights_are_the_programs_weights():
    """Every leaf the program holds: A_log, dt_bias and D in float32, the
    convolution's bias and the selection bias among them. The held experts'
    two stacks are no leaves of the reference (two copies of them would not
    fit the chip beside each other: the int8 control): it keeps what draws
    them and draws one expert's matrices where they are used, the program's
    own numbers bit for bit."""
    import functools

    import jax

    from fmabench import serve
    from llm_d_fast_model_actuation_tpu.models.registry import init_params_placed

    dims = spec.model_dims(tiny(), DATA_DIR)
    fam = spec.family_of(tiny(), DATA_DIR).part("reference")
    matrices = jax.jit(
        lambda p, e: fam.expert_matrices(dims, p, 3, e)  # layer static, e traced
    )
    for seed in (0, 3_000_000_019, 2**32 + 5):
        mine = jax.jit(functools.partial(fam.init_weights, d=dims))(
            np.uint32(seed % 2**32))
        theirs = init_params_placed(
            jax.random.key(seed), serve.build_model_config(tiny(), DATA_DIR))
        stacks = {k: theirs["experts"].pop(k) for k in ("w_up", "w_down")}
        keys = {k: mine["experts"].pop(k) for k in fam.EXPERT_KEYS}
        theirs = dict(jax.tree_util.tree_leaves_with_path(theirs))
        mine = jax.tree_util.tree_leaves_with_path(mine)
        assert len(mine) == len(theirs)     # every other leaf the program has
        for path, leaf in mine:
            assert np.array_equal(
                np.asarray(leaf, np.float32), np.asarray(theirs[path], np.float32)
            ), (seed, jax.tree_util.keystr(path))
        for e in range(dims["experts_held"]):
            up, down = matrices(keys, e)
            assert np.array_equal(up, np.asarray(stacks["w_up"][3, e], np.float32))
            assert np.array_equal(down, np.asarray(stacks["w_down"][3, e], np.float32))
    # a run of a larger draw, wherever it starts
    whole = jax.random.normal(jax.random.key(9), (7, 5, 11), np.float32).reshape(-1)
    assert np.array_equal(fam.normal_rows(jax.random.key(9), 123, 200), whole[123:323])


_SERVED = {}


def served(seed):
    """What the program serves under the tiny configuration with the seed's
    weights: eight prompts through three slots (slots are recycled), in
    segments short enough that four prompts resume from carried state and
    attend over their pages. ONE engine for every seed, its weights put anew
    (the programs take them as an argument, so they compile once)."""
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
                         max_prefill_tokens=32, prefix_caching=False),
            seed=0,
        )
        assert eng.pool.k_pages.shape == (1, 256, 4, 32)
        assert eng.pool.state.shape == (5, 3, 8, 12, 24)
    if seed not in _SERVED:
        eng = _SERVED["engine"]
        eng.params = init_params_placed(jax.random.key(seed), eng.cfg.model)
        before = eng.state_resumed_segments
        rng = random.Random(seed)
        prompts = [[rng.randrange(1, 256) for _ in range(n)]
                   for n in (24, 40, 9, 60, 33, 17, 50, 28)]
        outs = eng.generate(prompts, max_new_tokens=32)
        assert eng.state_resumed_segments - before == 4
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
    """In process: prefill in segments that resume from the slot's state and
    attend over its pages, then decode, share 1 of the experts, against the
    family's reference (one forward, the recurrence token by token); and the
    int8 control outside the same limit."""
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
    prog = np.mean([p["gap_mean"] for p, _ in rows])
    ctrl = np.mean([c["gap_mean"] for _, c in rows])
    assert ctrl > POOLED_MEAN_LIMIT > prog
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


def test_a_state_or_a_page_that_is_not_carried_comes_out_not_correct():
    """The reference over the last 32 positions alone (what a program that
    dropped the recurrent state AND the pages between segments would serve)
    put where the program stands reads gaps far outside the limit the program
    holds."""
    assert _gaps_of({}, last=32)["gap_mean"] > 5 * POOLED_MEAN_LIMIT


def test_another_share_of_the_experts_comes_out_not_correct():
    """The reference given share 0 of the experts where the file says share
    1 (a program that held the wrong experts, or dropped none) is far
    outside the limit."""
    assert _gaps_of({"share_index": 0})["gap_mean"] > 5 * POOLED_MEAN_LIMIT


def test_rehearsal_run_is_correct_and_reads_the_cells_counters():
    """A whole traced run on the CPU: ``correct``, no request failed, no
    compile inside the window, the state's and the experts' counters read,
    and no device metric reported from a CPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-m", "fmabench", "--rehearse", "--benchmark", TINY_BENCH,
         "--workload", "tiny.ssmchat", "--seed", "2147500007", "--seconds", "3",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["window_compiles.ssmchat"] == 0
    assert metrics["ssm_token_updates.ssmchat"] > 0
    # prompt rows go through the grouped matmuls, a decode batch of 3 rows
    # through the dense form over the held experts (assumed.dense_max_rows 4)
    assert 0 < 5 * metrics["moe_routed_tokens.ssmchat"] < metrics["ssm_token_updates.ssmchat"]
    assert metrics["state_resumed_segments.ssmchat"] > 0
    assert metrics["sched_host_s.ssmchat"] > 0
    for device_metric in ("decode_step_dev_ms.ssmchat", "prefill_dev_ms.ssmchat",
                          "paged_decode_hbm_pct.ssmchat", "ssm_decode_hbm_pct.ssmchat",
                          "latent_moe_decode_hbm_pct.ssmchat", "device_idle_pct.ssmchat"):
        assert device_metric not in metrics


def test_broken_timed_path_comes_out_not_correct():
    proc = base.rehearsal("tiny.ssmchat", "--serve-module",
                          "tests.fmabench.broken_serve", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0 < line["attempted"]
    # an untraced run: the end-to-end metrics and nothing else
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert line["checks"]["gap_max"]["value"] > line["checks"]["gap_max"]["limit"]


# -- the cell's metric files ---------------------------------------------------------

METRICS = {
    "window_compiles.ssmchat": ("compile", "program_counter"),
    "decode_step_dev_ms.ssmchat": ("model step", "device_trace"),
    "prefill_dev_ms.ssmchat": ("model step", "device_trace"),
    "device_idle_pct.ssmchat": ("device", "device_trace"),
    "sched_host_s.ssmchat": ("scheduler", "program_counter"),
    "paged_decode_hbm_pct.ssmchat": ("kernels", "device_trace"),
    "ssm_decode_hbm_pct.ssmchat": ("kernels", "device_trace"),
    "latent_moe_decode_hbm_pct.ssmchat": ("kernels", "device_trace"),
    "ssm_token_updates.ssmchat": ("model step", "program_counter"),
    "state_resumed_segments.ssmchat": ("cache", "program_counter"),
    "moe_routed_tokens.ssmchat": ("model step", "program_counter"),
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


def test_the_new_entries_are_appended_and_the_cell_reports_what_the_issue_names():
    # appended: after everything the benchmark had (six configurations, six
    # cells, 66 per-layer metrics), in one run; a later PR's entries come
    # after these and do not turn this test
    bench = spec.benchmark()
    assert [c["name"] for c in bench["configs"]].index(CONFIG) == 6
    assert [w["name"] for w in bench["workloads"]].index(CELL) == 6
    names = [m["name"] for m in bench["per_layer"]]
    first = names.index(next(iter(METRICS)))
    assert first == 66 and names[first : first + len(METRICS)] == list(METRICS)
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert tokens["workloads"].index(CELL) == 5
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
    assert c.traffic["clients"] == 128 and c.traffic["pool"] == 16
    assert c.traffic["prompt_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.9, "min": 64, "max": 2048}
    assert c.traffic["output_len"] == {
        "dist": "lognormal", "median": 384, "sigma": 0.6, "min": 96, "max": 1024}
    assert c.engine_options(False) == (
        "--max-batch 128 --page-size 16 --num-pages 24592 "
        "--max-prefill-tokens 1024 --decode-chunk 8").split()
    assert c.traffic["check"] == {"sample": 4}
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    assert longest == 3072 <= c.dims["max_context"] == 4096
    # the worst case fits the pool less its reserved page: nothing is preempted
    assert 128 * longest == 24576 * 16 <= (24592 - 1) * 16
    from fmabench import traffic
    from llm_d_fast_model_actuation_tpu.engine.engine import prefill_bucket

    lens = traffic.stratified_lengths(c.traffic["prompt_len"], 16)
    assert sum(n > 1024 for n in lens) == 2 and (min(lens), max(lens)) == (72, 2048)
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
    assert 1 <= min(ids) and max(ids) < 32768


def test_counter_readers_read_the_programs_counters():
    ev = readers.Evidence()
    ev.stats_open = {"state": {"token_updates": 5 * 1000, "resumed_segments": 3},
                     "moe": {"routed_tokens": 1000}}
    ev.stats_close = {"state": {"token_updates": 5 * 151_000, "resumed_segments": 80},
                      "moe": {"routed_tokens": 151_000}}
    read = lambda name: readers.read_metric(spec.metric_file(name), ev)  # noqa: E731
    assert read("ssm_token_updates.ssmchat") == 5 * 150_000
    assert read("state_resumed_segments.ssmchat") == 77
    assert read("moe_routed_tokens.ssmchat") == 150_000


def test_state_roofline_counts_one_read_and_one_write_of_the_live_slots():
    fn = spec.roofline_function("ssm_decode_step")
    d = cell().dims
    assert fn(d) is None and fn({"live_seqs": 4}) is None
    got = fn({**d, "live_seqs": 120.5})
    # a layer's need, spread over the two ops a layer's step is today
    assert 2 * got["bytes"] == 120.5 * 2 * 4_255_744
    assert 2 * got["flops"] == 120.5 * 6.0 * 128 * 64 * 128
    assert got["flops"] / 197e12 < got["bytes"] / 819e9


def test_latent_roofline_counts_the_held_experts_in_the_latent_width():
    fn = spec.roofline_function("latent_moe_decode_step")
    d = cell().dims
    assert fn(d) is None and fn({"live_seqs": 4}) is None
    got = fn({**d, "live_seqs": 120})
    touched = 128 * (1 - (490 / 512) ** 120)
    assert 127.2 < touched < 127.4
    rows = 120 * 22 * 128 / 512
    # a layer's need, both matrices, in the one op a layer's experts are today
    assert got["bytes"] == pytest.approx(
        2 * (touched * 1024 * 2688 * 2 + rows * (1024 + 2688) * 2))
    assert got["flops"] == 2 * 2.0 * rows * 1024 * 2688
    assert got["flops"] / 197e12 < got["bytes"] / 819e9
    # the share function of the gated family, handed this cell's sizes, takes
    # the residual stream's width for the experts' input: four times the
    # bytes a matrix
    wide = spec.roofline_function("moe_share_decode_step")({**d, "live_seqs": 120})
    assert wide["bytes"] > 3.9 * got["bytes"] / 2
    # the paged decode kernel's: 2 KV heads of 128 a token, K and V
    paged = readers.roofline_mod.paged_decode_step(
        {**d, "live_seqs": 120, "live_kv_tokens": 100_000})
    assert paged["bytes"] == 100_000 * 1_024 + 120 * 32 * 128 * 2 * 2


def test_roofline_regexes_pick_the_decode_steps_ops_alone():
    """The metrics' regexes pick the decode step's ops by their printed
    shapes (names as the chunk program compiled for a v5e has them) and none
    of the prefill programs'; the state's share is the layer's bytes over
    both ops' summed time."""
    state = "f32[5,128,128,64,128]{4,3,2,1,0:T(8,128)}"
    read = ("%multiply_reduce_fusion.30 = f32[128,128,64]{2,1,0:T(8,128)S(1)} "
            "fusion(" + state + " %get-tuple-element.2735, f32[128,128,128] %bitcast.1057)")
    update = ("%select_dynamic-update-slice_fusion.10 = " + state + " fusion("
              + state + " %get-tuple-element.2735, f32[128,128,128] %bitcast.1062)")
    plain_update = "%fusion.660 = " + state + " fusion(" + state + " %gte.2476)"
    others = [
        # the prefill programs' stores of one slot
        "%bitcast_dynamic-update-slice_fusion.4 = " + state + " fusion(" + state + " %cache_2_.1)",
        "%add_dynamic-update-slice_fusion.1 = " + state + " fusion(" + state + " %p)",
        "%fusion.12 = f32[1,128,64,128]{3,2,1,0} fusion(" + state + " %p, s32[] %i)",
        "%while.17 = (s32[], bf16[128,4096], " + state + ") while(%tuple.4)",
        "%fusion.639 = bf16[128,1,10240]{2,0,1} fusion(bf16[128,4096] %x)",
    ]
    reader = spec.metric_file("ssm_decode_hbm_pct.ssmchat")
    rx = re.compile(reader["regex"])
    assert rx.search(read) and rx.search(update) and rx.search(plain_update)
    assert not any(rx.search(name) for name in others)
    moe = re.compile(spec.metric_file("latent_moe_decode_hbm_pct.ssmchat")["regex"])
    assert moe.search(
        "%fusion.731 = bf16[128,1024]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[5,128,2688,1024]"
        "{3,2,1,0:T(8,128)(2,1)} %get-tuple-element.2710, bf16[5,128,1024,2688]{3,2,1,0} %gte)")
    # the latent projection beside it, and the prompt segments' grouped matmuls
    assert not moe.search(
        "%fusion.730 = bf16[128,1024]{1,0:T(8,128)(2,1)S(1)} fusion(bf16[5,4096,1024]{2,1,0} %p)")
    assert not moe.search("%ragged-dot-none.7 = bf16[22528,1024]{1,0} custom-call(")
    paged = re.compile(spec.metric_file("paged_decode_hbm_pct.ssmchat")["regex"])
    assert paged.search("%paged_decode_inline = bf16[128,32,128] custom-call(")

    class Trace:
        def matching(self, regex, line="ops"):
            # one layer-step: a read pass of 0.9 ms and an update of 1.6 ms
            return 0.9e-3 + 1.6e-3, 2

    ev = readers.Evidence()
    ev.trace, ev.shapes = Trace(), {**cell().dims, "live_seqs": 120}
    ev.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = readers.read_metric(reader, ev)
    assert share == pytest.approx(100 * (120 * 2 * 4_255_744 / 819e9) / 2.5e-3)
