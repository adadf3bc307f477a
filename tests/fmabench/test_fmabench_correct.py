"""How ``correct`` is decided, at a size a test run can hold (CPU).

* each family's plain reference makes the program's weights from the seed
  alone and agrees with what the program serves (prefill, then decode
  through the paged cache) at tiny configurations of the two families under
  ``fmabench/families/`` (``test_fmabench_family.py`` holds the same of the
  family that exists under ``testdata/`` only, through the helpers here);
* the control — the reference with int8 weights put in the program's
  place — reads wider gaps than the program does, and fails limits set the
  way PERF.md sets the cells' (above the sound runs' largest, below the
  control's smallest) at this size;
* a whole rehearsal run comes out ``correct``; the same run with every
  token altered where it is produced comes out not correct.
"""

import functools
import json
import os
import random
import subprocess
import sys

import jax
import numpy as np
import pytest

from fmabench import harness, reference, serve, spec

TESTDATA = os.path.join(spec.HERE, "testdata")
SIZES = {
    "dense": {
        "hidden_size": 256, "intermediate_size": 512, "num_attention_heads": 8,
        "num_key_value_heads": 4, "num_hidden_layers": 2, "vocab_size": 1024,
        "rope_theta": 10000.0, "rms_norm_eps": 1e-5,
        "assumed": {"max_context": 256},
    },
}
SIZES["moe"] = {**SIZES["dense"], "num_local_experts": 4, "num_experts_per_tok": 2}
KINDS = sorted(SIZES)
#: a rehearsal's own files are looked in first: a family that exists under
#: testdata/ only (test_fmabench_family.py) is found there
DATA_DIR = "fmabench/testdata"
SEEDS = (1, 2, 3)
#: limits for THIS size on the mean gap pooled over the seeds, set between
#: the two readings the way the cells' limits are: the program reads
#: 0.00024 (dense) / 0.0043 (moe: routing near-ties that fall the other way
#: in bfloat16), the int8 control 0.0012 / 0.0129
POOLED_MEAN_LIMIT = {"dense": 0.0006, "moe": 0.0085}


def served(config, seed):
    """What the program serves for a few seeded prompts (in-process engine:
    the same prefill and decode-chunk programs the server drives)."""
    from llm_d_fast_model_actuation_tpu.engine.engine import (
        EngineConfig, InferenceEngine,
    )

    eng = InferenceEngine(
        EngineConfig(model=serve.build_model_config(config, DATA_DIR), max_batch=4,
                     page_size=16, num_pages=128, decode_chunk=8),
        seed=seed,
    )
    rng = random.Random(seed)
    prompts = [[rng.randrange(1, 1024) for _ in range(n)]
               for n in (24, 40, 9, 60, 33, 17, 50, 28)]
    outs = eng.generate(prompts, max_new_tokens=32)
    return [{"prompt": p, "tokens": list(o)} for p, o in zip(prompts, outs)]


def family_reference(config):
    return spec.family_of(config, DATA_DIR).part("reference")


_READINGS = {}


def readings(config):
    """(program, int8 control) on each of SEEDS, once per configuration."""
    key = json.dumps(config, sort_keys=True)
    if key not in _READINGS:
        dims = spec.model_dims(config, DATA_DIR)
        fam = family_reference(config)
        rows = []
        for seed in SEEDS:
            reqs = served(config, seed)
            rows.append((
                reference.compare(fam, dims, seed, reqs),
                reference.compare(fam, dims, seed, reqs, control="int8"),
            ))
        _READINGS[key] = rows
    return _READINGS[key]


def reference_weights_are_the_programs(config):
    from llm_d_fast_model_actuation_tpu.models.registry import init_params_placed

    dims = spec.model_dims(config, DATA_DIR)
    fam = family_reference(config)
    for seed in (0, 3_000_000_019, 2**32 + 5):
        mine = jax.jit(functools.partial(fam.init_weights, d=dims))(
            np.uint32(seed % 2**32)
        )
        theirs = init_params_placed(
            jax.random.key(seed), serve.build_model_config(config, DATA_DIR)
        )
        theirs = dict(jax.tree_util.tree_leaves_with_path(theirs))
        mine = jax.tree_util.tree_leaves_with_path(mine)
        assert len(mine) == len(theirs)     # every leaf the program has
        for path, leaf in mine:
            assert np.array_equal(
                np.asarray(leaf, np.float32), np.asarray(theirs[path], np.float32)
            ), (seed, jax.tree_util.keystr(path))


def program_agrees_with_the_reference(config, limit):
    for prog, _ in readings(config):
        assert prog["finite"] and prog["compared_tokens"] == 8 * 32
        # most served tokens ARE the reference's best
        assert prog["nonzero_share"] <= 0.08
    pooled = np.mean([p["gap_mean"] for p, _ in readings(config)])
    assert pooled <= limit


def int8_control_comes_out_not_correct(config, limit):
    rows = readings(config)
    prog = np.mean([p["gap_mean"] for p, _ in rows])
    ctrl = np.mean([c["gap_mean"] for _, c in rows])
    assert ctrl > limit > prog
    assert np.mean([c["nonzero_share"] for _, c in rows]) > 1.5 * np.mean(
        [p["nonzero_share"] for p, _ in rows]
    )


@pytest.mark.parametrize("kind", KINDS)
def test_reference_weights_are_the_programs_weights(kind):
    reference_weights_are_the_programs(SIZES[kind])


@pytest.mark.parametrize("kind", KINDS)
def test_program_agrees_with_the_reference(kind):
    program_agrees_with_the_reference(SIZES[kind], POOLED_MEAN_LIMIT[kind])


@pytest.mark.parametrize("kind", KINDS)
def test_int8_control_comes_out_not_correct(kind):
    int8_control_comes_out_not_correct(SIZES[kind], POOLED_MEAN_LIMIT[kind])


def test_decide_holds_each_number_to_its_own_limit():
    ref = {"finite": True, "gap_max": 0.02, "gap_mean": 0.0003, "gap_p95": 0.0}
    lim = {"gap_max": 0.05, "gap_mean": 0.001, "gap_p95": 0.002}
    ok, checks = harness.decide(ref, lim, 6)
    assert ok and list(checks)[-3:] == ["gap_max", "gap_mean", "gap_p95"]
    ok, checks = harness.decide(ref, {**lim, "gap_mean": 0.0002}, 6)
    assert not ok and checks["gap_max"]["holds"] and not checks["gap_mean"]["holds"]
    ref["gap_p95"] = 0.01
    assert not harness.decide(ref, lim, 6)[0]
    # a configuration compares the numbers it gives limits for
    ok, checks = harness.decide(ref, {"gap_max": 0.05}, 6)
    assert ok and "gap_p95" not in checks
    ref["gap_p95"] = 0.0
    assert not harness.decide(ref, lim, 0)[0]
    assert not harness.decide({"finite": False}, lim, 6)[0]
    for bad in ({}, {"gap_p90": 0.1}, {"gap_max": 0.05, "typo": 1}):
        with pytest.raises(KeyError):
            harness.decide(ref, bad, 6)


def test_reduce_gaps_gives_known_numbers():
    g1 = np.zeros(100)
    g1[:4] = [0.5, 0.2, 0.04, 0.01]
    g2 = np.zeros(100)
    red = reference.reduce_gaps([g1, g2])
    assert red["compared_tokens"] == 200 and red["finite"]
    assert red["gap_max"] == 0.5 and red["gap_mean"] == pytest.approx(0.75 / 200)
    assert red["gap_p95"] == 0.0 and red["nonzero_share"] == 0.02
    assert red["big_share"] == 0.01
    assert [r["big"] for r in red["per_request"]] == [2, 0]
    assert red["per_request"][0]["gap_p95"] == pytest.approx(0.0)
    g2[:12] = 1.0   # 8% of the positions wrong: the pooled 95th percentile sees it
    assert reference.reduce_gaps([g1, g2])["gap_p95"] > 0.5
    assert not reference.reduce_gaps([g1, np.array([np.nan])])["finite"]


def finished(due, ended, tokens=2):
    from fmabench import client

    r = client.Record({"prompt": [1, 2], "max_tokens": tokens}, due)
    if ended is not None:
        r.first, r.last, r.ended = ended - 0.5, ended, ended
        r.tokens = [7] * tokens
    else:
        r.error = "cancelled at window close"
    return r


def test_a_request_that_had_the_whole_window_and_did_not_end_is_failed():
    win = harness.Window()
    win.t_open, win.t_close = 100.0, 150.0
    done = [finished(99.0, 120.0), finished(110.0, 149.0), finished(90.0, 99.5)]
    hung = finished(100.0, None)       # due at the open, never ended
    young = finished(130.0, None)      # still decoding at the close: not counted
    ok = harness._reduce(win, done, [hung, young], 50.0)
    assert len(ok) == 2 and hung in win.records and young not in win.records
    assert win.notes["unfinished_at_close"] == 2
    assert win.notes["overdue_at_close"] == 1
    assert sum(not r.ok for r in win.records) == 1
    # ... and as the worst latency, the whole window
    assert win.e2e["tpot_p95_ms"] == 50_000.0
    assert max(win.series["ttft_ms"]) == 50_000.0


def test_token_meter_counts_the_window_by_tenth():
    from fmabench import client

    m = client.TokenMeter()
    m.open, m.close = 100.0, 150.0
    for t, n in ((99.9, 5), (100.0, 1), (104.99, 2), (105.0, 3),
                 (149.999, 4), (150.0, 9)):
        m.add(t, n)
    assert m.count == 10 and sum(m.by_tenth) == m.count
    assert m.by_tenth == [3, 3, 0, 0, 0, 0, 0, 0, 0, 4]


def test_sample_is_seeded_and_holds_the_longest():
    from fmabench import client

    recs = []
    for i in range(20):
        r = client.Record({"prompt": [1] * (10 + i), "max_tokens": 2}, 0.0)
        r.tokens = [5, 6]
        recs.append(r)
    recs[3].error = "failed"
    a = harness.sample_for_check(recs, 7, 6)
    assert a == harness.sample_for_check(recs, 7, 6)
    assert len(a) == 6 and a[0] is recs[19] and recs[3] not in a
    assert a != harness.sample_for_check(recs, 8, 6)
    assert harness.sample_for_check([], 7, 6) == []


def rehearsal(cell, *extra, benchmark=os.path.join(TESTDATA, "benchmark.json")):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, "-m", "fmabench", "--rehearse", "--benchmark",
         benchmark, "--workload", cell,
         "--seed", str(2**31 + 12345), "--seconds", "2", "--trace", "0", *extra],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )


def rehearse(cell, *extra):
    """One whole run on the CPU: the harness's look for a chip is skipped
    (--rehearse), everything else is the run the driver makes."""
    proc = rehearsal(cell, *extra)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc.stderr


def rehearsal_is_correct_and_names_the_cpu(cell):
    line, err = rehearse(cell)
    assert list(line) == [
        "correct", "attempted", "failed", "metrics", "device", "checks"
    ]
    assert line["correct"] is True and line["failed"] == 0 < line["attempted"]
    assert line["device"]["platform"] == "cpu"
    assert set(line["metrics"]) == {"tpot_p95_ms", "setup_s"}
    assert "busy_s" not in line["device"]  # no device metric from a CPU run
    assert "check gap_mean:" in err.splitlines()[-1]


def test_rehearsal_run_is_correct_and_names_the_cpu():
    rehearsal_is_correct_and_names_the_cpu("tiny.chat")


def broken_timed_path_comes_out_not_correct(cell):
    line, err = rehearse(cell, "--serve-module", "tests.fmabench.broken_serve")
    assert line["correct"] is False and line["failed"] == 0
    assert line["checks"]["gap_max"]["value"] > line["checks"]["gap_max"]["limit"]
    assert "FAILS" in err


def test_broken_timed_path_comes_out_not_correct():
    broken_timed_path_comes_out_not_correct("tiny.chat")


def test_measured_path_never_falls_back_to_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=spec.ROOT)
    proc = subprocess.run(
        [sys.executable, "-m", "fmabench", "--benchmark",
         os.path.join(TESTDATA, "benchmark.json"), "--workload", "tiny.chat",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=spec.ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode != 0
    assert '"correct"' not in proc.stdout
