"""The family ``kimi_linear`` (``fmabench/families/kimi_linear/``), its
configuration ``kimi-linear-48b-a3b-d8-e64`` and its cell
``kimi-linear-48b.decodemix``, after the pattern of
``test_fmabench_olmo_hybrid.py``: the keys it refuses by name, its sizes to
the unit, its reference (the recurrence token by token, latent attention in
its plain form, the share of the experts) against the program at a tiny size
(in process, and as a whole CPU rehearsal), and the metric files of its cell.
The rehearsal's own files are under ``fmabench/testdata/kimi_linear/``.
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

CELL, CONFIG = "kimi-linear-48b.decodemix", "kimi-linear-48b-a3b-d8-e64"
DATA_DIR = "fmabench/testdata/kimi_linear"
TINY_BENCH = os.path.join(spec.ROOT, DATA_DIR, "benchmark.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
#: the mean gap pooled over two of base.SEEDS at the tiny size: the program
#: reads 0.075-0.093 a seed (0.079 over the two) and the int8 control
#: 0.155-0.200 (0.178; a
#: router near-tie that falls the other way swaps an expert whose share of a
#: tiny layer is large, in both); the limit is about their geometric mean
POOLED_MEAN_LIMIT = 0.12


def tiny():
    return spec.load_json(os.path.join(
        spec.ROOT, DATA_DIR, "configs", "tiny-kimi-linear.json"))


def cell():
    return spec.Cell(spec.benchmark(), CELL)


def with_doc(tmp_path, doc):
    path = tmp_path / "config.json"
    path.write_text(json.dumps(doc))
    return {
        "data_dir": DATA_DIR,
        "configs": [{"name": "c", "file": str(path)}],
        "workloads": [{"name": "w", "config": "c", "traffic": "tiny-decodemix",
                       "chips": 1}],
        "end_to_end": [], "per_layer": [],
    }


def test_the_configuration_is_the_catalog_row_cut_in_depth_experts_and_vocabulary():
    doc = spec.config_file(CONFIG)
    cut = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert doc["family"] == "kimi_linear" and doc["reduced"] == cut
    row = next(c for c in spec.benchmark()["configs"] if c["name"] == CONFIG)
    assert row["reduced"] == cut and row["source"] == doc["source"]
    assert doc["source"].endswith(
        "moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json")
    assert doc["published"] == {
        "num_hidden_layers": 27, "num_experts": 256, "vocab_size": 163840}
    assert (doc["num_hidden_layers"], doc["num_experts"], doc["vocab_size"]) == (
        8, 64, 40960)
    # the two published layer lists stay whole in the file; the first 8 are served
    lin = doc["linear_attn_config"]
    assert lin["full_attn_layers"] == [4, 8, 12, 16, 20, 24, 27]
    assert len(lin["kda_layers"]) == 20
    for what in ("block", "kda_layers", "mla_layers", "ffn", "selection_bias",
                 "state", "decay_init", "max_context"):
        assert doc["assumed"][what]
    assert doc["assumed"]["share_index"] == 0
    assert "3,772,368,832" in doc["deployment"] and "v5e-16" in doc["deployment"]
    if os.path.exists(CATALOG):
        with open(CATALOG, encoding="utf-8") as f:
            rows = [json.loads(line) for line in f]
        pub = next(r for r in rows if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
        assert pub["source_url"] == doc["source"]
        # no key of the row differs or is left out but the three in `reduced`
        for key, value in pub["config"].items():
            assert (doc[key] == value) == (key not in cut), key
            if key in cut:
                assert doc["published"][key] == value


def test_the_sizes_are_the_issues_table_to_the_unit():
    d, keys = cell().dims, cell().family.keys
    assert keys.reducible == ("num_hidden_layers", "num_experts", "vocab_size")
    assert (d["num_layers"], d["kda_layers"], d["mla_layers"], d["expert_layers"]) == (
        8, 6, 2, 7)
    assert d["layer_kinds"] == ["kda", "kda", "kda", "mla"]
    assert (d["experts_held"], d["router_width"], d["experts_per_token"]) == (64, 256, 8)
    assert (d["vocab_size"], d["max_context"], d["hidden_size"]) == (40960, 4096, 2304)
    assert (d["latent_dim"], d["latent_stored"], d["kv_lora_rank"]) == (576, 640, 512)
    assert keys.kda_mixer_params(d) == 39_514_272
    assert keys.mla_mixer_params(d) == 29_114_880
    assert keys.expert_params(d) == 7_077_888
    router, dense, norms = 2304 * 256 + 256, 3 * 2304 * 9216, 2 * 2304
    assert (router, dense, norms) == (590_080, 63_700_992, 4_608)
    layer1 = keys.kda_mixer_params(d) + dense + norms
    ffn = keys.expert_layer_ffn_params(d)
    assert ffn == 65 * 7_077_888 + router
    kda_layer = keys.kda_mixer_params(d) + ffn + norms
    mla_layer = keys.mla_mixer_params(d) + ffn + norms
    assert (layer1, kda_layer, mla_layer) == (103_219_872, 500_171_680, 489_772_288)
    ends = 2 * 40960 * 2304 + 2304
    assert ends == 188_745_984
    assert keys.param_count(d) == layer1 + 5 * kda_layer + 2 * mla_layer + ends
    assert keys.param_count(d) == 3_772_368_832
    # a latent layer 1,152 B a token as counted, 1,280 as laid out; S and the
    # tails 2,170,880 B a slot a KDA layer
    assert keys.latent_bytes_counted(d, 1, 16) == 16 * 2 * 1_152
    assert keys.latent_bytes_counted(d, 16400, 16) == 604_569_600
    assert keys.kv_bytes(d, 16400, 16) == 16400 * 16 * 2 * 1_280 == 671_744_000
    assert keys.state_bytes_per_slot_layer(d) == 2_097_152 + 73_728 == 2_170_880
    assert keys.state_bytes(d, 64) == 6 * 64 * 2_170_880 == 833_617_920


REFUSED = {
    "a_query_lora": ({"q_lora_rank": 1536}, "q_lora_rank"),
    "more_than_one_expert_group": ({"num_expert_group": 8}, "num_expert_group"),
    "a_group_limited_top_k": ({"topk_group": 4}, "topk_group"),
    "rotary_on_the_latent_key": ({"mla_use_nope": False}, "mla_use_nope"),
    "two_leading_dense_layers": ({"first_k_dense_replace": 2}, "first_k_dense_replace"),
    "experts_every_other_layer": ({"moe_layer_freq": 2}, "moe_layer_freq"),
    "a_softmax_router": ({"moe_router_activation_func": "softmax"}, "sigmoid"),
    "scores_not_renormalised": ({"moe_renormalize": False}, "moe_renormalize"),
    "no_grouped_gate": ({"use_grouped_topk": False}, "use_grouped_topk"),
    "a_rope_scaling": ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
    "a_next_token_head": ({"num_nextn_predict_layers": 1}, "num_nextn_predict_layers"),
    "tied_embeddings": ({"tie_word_embeddings": True}, "untied"),
    "another_activation": ({"hidden_act": "gelu"}, "hidden_act"),
    "another_period": (
        {"linear_attn_config": {"full_attn_layers": [2, 4, 6, 8], "head_dim": 16,
                                "kda_layers": [1, 3, 5, 7], "num_heads": 4,
                                "short_conv_kernel_size": 4}}, "whole periods"),
    "a_layer_of_neither_kind": (
        {"linear_attn_config": {"full_attn_layers": [4, 8], "head_dim": 16,
                                "kda_layers": [1, 2, 3, 5, 6], "num_heads": 4,
                                "short_conv_kernel_size": 4}}, "whole periods"),
    "a_depth_that_cuts_a_period": ({"num_hidden_layers": 6}, "whole periods"),
    "a_convolution_of_one_tap": (
        {"linear_attn_config": {"full_attn_layers": [4, 8], "head_dim": 16,
                                "kda_layers": [1, 2, 3, 5, 6, 7], "num_heads": 4,
                                "short_conv_kernel_size": 1}}, "short_conv_kernel_size"),
    "a_share_outside_the_router": (
        {"assumed": {"max_context": 256, "share_index": 4}}, "outside the router"),
    "a_latent_rank_that_is_no_lane_multiple": ({"kv_lora_rank": 96}, "kv_lora_rank"),
    "context_past_the_published": (
        {"assumed": {"max_context": 1024, "share_index": 0}}, "model_max_length"),
    "a_key_nobody_reads": ({"kda_use_gate": True}, "kda_use_gate"),
    "a_width_in_reduced": ({"reduced": ["moe_intermediate_size"]},
                           "moe_intermediate_size"),
    "the_rank_in_reduced": ({"reduced": ["kv_lora_rank"]}, "kv_lora_rank"),
    "the_experts_per_token_in_reduced": ({"reduced": ["num_experts_per_token"]},
                                         "num_experts_per_token"),
}


@pytest.mark.parametrize("what", sorted(REFUSED))
def test_keys_refused_by_name(what, tmp_path):
    change, named = REFUSED[what]
    with pytest.raises(ValueError, match=named):
        spec.Cell(with_doc(tmp_path, {**tiny(), **change}), "w")


@pytest.mark.parametrize("key", ["kv_lora_rank", "linear_attn_config", "q_lora_rank"])
def test_a_missing_key_is_refused_by_name(key, tmp_path):
    doc = tiny()
    del doc[key]
    with pytest.raises(ValueError, match=key):
        spec.Cell(with_doc(tmp_path, doc), "w")


def test_program_config_is_the_kimi_linear_family():
    model = cell().family.part("program").build(cell().dims)
    assert type(model).__name__ == "KimiLinearConfig"
    assert (model.num_layers, model.kda_layers, model.cache_layers) == (8, 6, 2)
    assert model.layer_kinds == ("kda", "kda", "kda", "mla")
    assert model.recurrent_state == (6, (32, 128, 128), (3, 12288))
    assert model.latent_cache == (576, 640)
    assert (model.num_experts, model.router_outputs, model.share_index) == (64, 256, 0)
    assert (model.experts_per_token, model.routed_scaling) == (8, 2.446)
    assert model.max_seq_len == 4096 and model.rms_eps == 1e-5
    assert not model.tie_embeddings and model.vocab_size == 40960
    assert model.num_params() == 3_772_368_832
    from llm_d_fast_model_actuation_tpu.engine import server

    assert server.MODEL_CONFIGS["kimi-linear-48b-a3b"]() == model
    # the rehearsal's file holds share 1 of four
    small = spec.family_of(tiny(), DATA_DIR)
    built = small.part("program").build(small.dims(tiny()))
    assert (built.num_experts, built.router_outputs, built.share_index) == (4, 16, 1)


def test_reference_weights_are_the_programs_weights(monkeypatch):
    """Every leaf the program holds, the decay's two float32 vectors and the
    selection bias among them."""
    monkeypatch.setattr(base, "DATA_DIR", DATA_DIR)
    base.reference_weights_are_the_programs(tiny())


_SERVED = {}


def served(seed):
    """What the program serves under the tiny configuration with the seed's
    weights: eight prompts through three slots (slots are recycled), in
    segments short enough that four prompts resume from carried state and
    attend over latent pages. ONE engine for every seed, its weights put
    anew (the programs take them as an argument, so they compile once)."""
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
        assert eng.pool.k_pages.shape == (2, 256, 4, 256) and eng.pool.v_pages is None
        assert eng.pool.state.shape[:2] == (6, 3)
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
    attend over its latent pages, then decode in the absorbed form, share 1
    of the experts, against the family's reference (one forward, the
    recurrence token by token, the plain form); and the int8 control outside
    the same limit."""
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
    dropped the recurrent state AND the latent pages between segments would
    serve) put where the program stands reads gaps far outside the limit the
    program holds."""
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
         "--workload", "tiny.decodemix", "--seed", "2147500007", "--seconds", "3",
         "--trace", "1"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    metrics = {k: v["value"] for k, v in line["metrics"].items()}
    assert metrics["window_compiles.decodemix"] == 0
    assert metrics["kda_token_updates.decodemix"] > 0
    assert metrics["kda_token_updates.decodemix"] == 6 * metrics["moe_routed_tokens.decodemix"]
    assert metrics["state_resumed_segments.decodemix"] > 0
    assert metrics["sched_host_s.decodemix"] > 0
    for device_metric in ("decode_step_dev_ms.decodemix", "prefill_dev_ms.decodemix",
                          "latent_decode_hbm_pct.decodemix", "kda_decode_hbm_pct.decodemix",
                          "moe_share_decode_hbm_pct.decodemix", "device_idle_pct.decodemix"):
        assert device_metric not in metrics


def test_broken_timed_path_comes_out_not_correct():
    proc = base.rehearsal("tiny.decodemix", "--serve-module",
                          "tests.fmabench.broken_serve", benchmark=TINY_BENCH)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    assert line["correct"] is False and line["failed"] == 0 < line["attempted"]
    # an untraced run: the end-to-end metrics and nothing else
    assert set(line["metrics"]) == {"out_tokens_per_s", "setup_s"}
    assert line["checks"]["gap_max"]["value"] > line["checks"]["gap_max"]["limit"]


# -- the cell's metric files ---------------------------------------------------------

METRICS = {
    "window_compiles.decodemix": ("compile", "program_counter"),
    "decode_step_dev_ms.decodemix": ("model step", "device_trace"),
    "prefill_dev_ms.decodemix": ("model step", "device_trace"),
    "device_idle_pct.decodemix": ("device", "device_trace"),
    "sched_host_s.decodemix": ("scheduler", "program_counter"),
    "latent_decode_hbm_pct.decodemix": ("kernels", "device_trace"),
    "kda_decode_hbm_pct.decodemix": ("kernels", "device_trace"),
    "moe_share_decode_hbm_pct.decodemix": ("kernels", "device_trace"),
    "kda_token_updates.decodemix": ("model step", "program_counter"),
    "state_resumed_segments.decodemix": ("cache", "program_counter"),
    "moe_routed_tokens.decodemix": ("model step", "program_counter"),
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
    bench = spec.benchmark()
    assert bench["configs"][-1]["name"] == CONFIG
    assert bench["workloads"][-1]["name"] == CELL
    assert [m["name"] for m in bench["per_layer"][-len(METRICS):]] == list(METRICS)
    tokens = next(m for m in bench["end_to_end"] if m["name"] == "out_tokens_per_s")
    assert tokens["workloads"][-1] == CELL
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
    assert c.traffic["clients"] == 64 and c.traffic["pool"] == 16
    assert c.traffic["prompt_len"] == {
        "dist": "lognormal", "median": 1024, "sigma": 0.8, "min": 256, "max": 3072}
    assert c.traffic["output_len"] == {
        "dist": "lognormal", "median": 512, "sigma": 0.5, "min": 192, "max": 1024}
    assert c.engine_options(False) == (
        "--max-batch 64 --page-size 16 --num-pages 16400 "
        "--max-prefill-tokens 1024 --decode-chunk 8").split()
    assert c.traffic["check"] == {"sample": 4}
    longest = c.traffic["prompt_len"]["max"] + c.traffic["output_len"]["max"]
    assert longest == 4096 == c.dims["max_context"]
    # the worst case fits the pool less its reserved page: nothing is preempted
    assert 64 * longest == 16384 * 16 <= (16400 - 1) * 16
    # the ladder reaches the three cold buckets and every suffix bucket
    assert c.traffic["warmup"]["prompt_lens"] == [
        256, 512, 1024, 1040, 1056, 1088, 1152, 1280, 1536, 2048]
    from fmabench import traffic

    lens = traffic.stratified_lengths(c.traffic["prompt_len"], 16)
    assert sum(n > 1024 for n in lens) == 8 and (min(lens), max(lens)) == (256, 3072)
    # ids come from the slice of the vocabulary this chip holds
    ids = traffic.Prompts(c.dims["vocab_size"], 2**31 + 7, "window").draw(4096)
    assert 1 <= min(ids) and max(ids) < 40960


def test_counter_readers_read_the_programs_counters():
    ev = readers.Evidence()
    ev.stats_open = {"state": {"token_updates": 6 * 1000, "resumed_segments": 3},
                     "moe": {"routed_tokens": 1000}}
    ev.stats_close = {"state": {"token_updates": 6 * 151_000, "resumed_segments": 80},
                      "moe": {"routed_tokens": 151_000}}
    read = lambda name: readers.read_metric(spec.metric_file(name), ev)  # noqa: E731
    assert read("kda_token_updates.decodemix") == 6 * 150_000
    assert read("state_resumed_segments.decodemix") == 77
    assert read("moe_routed_tokens.decodemix") == 150_000


def test_state_roofline_counts_one_read_and_one_write_of_the_live_slots():
    fn = spec.roofline_function("kda_decode_step")
    d = cell().dims
    assert fn(d) is None and fn({"live_seqs": 4}) is None
    got = fn({**d, "live_seqs": 60.5})
    # a layer's need, spread over the two ops a layer's step is today
    assert 2 * got["bytes"] == 60.5 * 2 * 2_170_880
    assert 2 * got["flops"] == 60.5 * 8.0 * 32 * 128 * 128
    assert got["flops"] / 197e12 < got["bytes"] / 819e9


def test_latent_roofline_counts_a_row_once_and_as_counted():
    fn = spec.roofline_function("latent_decode_step")
    d = cell().dims
    assert fn(d) is None and fn({"live_seqs": 4, "live_kv_tokens": 9}) is None
    got = fn({**d, "live_seqs": 60, "live_kv_tokens": 108_000})
    # 1,152 B a token ONCE (not the 1,280 stored, not key and value apart),
    # 32 absorbed query rows of 576 in and 32 outputs of 512 out
    assert got["bytes"] == 108_000 * 1_152 + 60 * 32 * (576 + 512) * 2
    assert got["flops"] == 2.0 * 108_000 * 32 * (576 + 512)
    assert got["flops"] / 197e12 < got["bytes"] / 819e9


def test_share_roofline_counts_the_held_experts_and_their_rows_alone():
    fn = spec.roofline_function("moe_share_decode_step")
    d = cell().dims
    assert fn(d) is None and fn({"live_seqs": 4}) is None
    got = fn({**d, "live_seqs": 60})
    touched = 64 * (1 - (248 / 256) ** 60)
    assert 54.4 < touched < 54.6
    rows = 60 * 8 * 64 / 256
    assert got["bytes"] == pytest.approx(
        touched * 2304 * 1024 * 2 + rows * (2304 + 1024) * 2)
    assert got["flops"] == 2.0 * rows * 2304 * 1024
    # the accepted function, handed this cell's sizes, would count every
    # expert of the router's width as read here: over four times the bytes
    whole = spec.roofline_function("moe_decode_step")(
        {**d, "num_experts": 256, "live_seqs": 60})
    assert whole["bytes"] > 3.9 * got["bytes"]


def test_roofline_regexes_pick_the_decode_steps_ops_alone():
    """The metrics' regexes pick the decode step's ops by their printed
    shapes (names as the chunk program compiled for a v5e has them) and none
    of the prefill programs'; the state's share is the layer's bytes over
    both ops' summed time."""
    state = "f32[6,64,32,128,128]{4,3,2,1,0:T(8,128)}"
    read = ("%fusion.1464 = (f32[64,32,128]{2,1,0:T(8,128)S(1)}, f32[64,32,128]"
            "{2,1,0:T(8,128)S(1)}) fusion(" + state + " %get-tuple-element.4875, "
            "f32[64,32,128] %mul.6139)")
    update = ("%select_dynamic-update-slice_fusion.12 = " + state + " fusion("
              + state + " %get-tuple-element.4875, f32[64,32,128] %x)")
    plain_update = "%fusion.660 = " + state + " fusion(" + state + " %gte.2476)"
    others = [
        "%bitcast_dynamic-update-slice_fusion.7 = " + state + " fusion(" + state + " %p)",
        "%fusion.12 = f32[1,32,128,128]{3,2,1,0} fusion(" + state + " %p, s32[] %i)",
        "%while.24 = (s32[], bf16[64,2304], " + state + ") while(%tuple.4)",
        "%fusion.639 = bf16[64,1,12288]{2,0,1} fusion(bf16[64,2304] %x)",
    ]
    reader = spec.metric_file("kda_decode_hbm_pct.decodemix")
    rx = re.compile(reader["regex"])
    assert rx.search(read) and rx.search(update) and rx.search(plain_update)
    assert not any(rx.search(name) for name in others)
    moe = re.compile(spec.metric_file("moe_share_decode_hbm_pct.decodemix")["regex"])
    assert moe.search("%ragged-dot-none.1 = bf16[512,1024]{1,0:T(8,128)(2,1)} custom-call(")
    assert not moe.search("%ragged-dot-none.7 = bf16[8192,1024]{1,0} custom-call(")
    lat = re.compile(spec.metric_file("latent_decode_hbm_pct.decodemix")["regex"])
    assert lat.search("%latent_decode_inline.3 = bf16[64,32,512] custom-call(")
    assert not lat.search("%paged_decode_inline = bf16[16,30,128] custom-call(")
    # the op that consumes the kernel's result names it among its operands
    assert not lat.search(
        "%fusion.1380 = bf16[64,4096]{1,0} fusion(bf16[64,32,512] %latent_decode_inline.23)")

    class Trace:
        def matching(self, regex, line="ops"):
            # one layer-step: a read pass of 150 us and an update of 350 us
            return 150e-6 + 350e-6, 2

    ev = readers.Evidence()
    ev.trace, ev.shapes = Trace(), {**cell().dims, "live_seqs": 60}
    ev.peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    share = readers.read_metric(reader, ev)
    assert share == pytest.approx(100 * (60 * 2 * 2_170_880 / 819e9) / 500e-6)
