"""The suite's own harness (tests/conftest.py): one set of compiled programs
per configuration per test process, and a time limit round every test."""

import dataclasses
import pathlib
import signal
import time

import conftest
import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from llm_d_fast_model_actuation_tpu.engine import (
    EngineConfig,
    InferenceEngine,
    engine,
    exec_pool,
)
from llm_d_fast_model_actuation_tpu.models import llama
from llm_d_fast_model_actuation_tpu.parallel.mesh import AXES
from llm_d_fast_model_actuation_tpu.utils import tracing

PROGRAMS = ("prefill", "prefill_plp", "suffix", "suffix_plp", "verify")


def _cfg(**kw):
    kw.setdefault("model", llama.LlamaConfig.tiny())
    return EngineConfig(
        max_batch=2, page_size=8, num_pages=32, max_seq_len=64, **kw
    )


def test_engines_of_one_configuration_share_their_programs():
    """The second engine dispatches the first one's jitted objects, so jax's
    own cache serves it: serving the same shapes compiles nothing more, and
    gives the same tokens."""
    a, b = InferenceEngine(_cfg(), seed=0), InferenceEngine(_cfg(), seed=0)
    assert a.programs is b.programs
    for name in PROGRAMS:
        assert getattr(a.programs, name) is getattr(b.programs, name)
    assert a.programs.chunk(4) is b.programs.chunk(4)
    prompt = [[1, 2, 3, 4, 5]]
    first = a.generate(prompt, max_new_tokens=6)
    compiled = a.programs.prefill._cache_size()
    assert compiled >= 1
    assert b.generate(prompt, max_new_tokens=6) == first
    assert b.programs.prefill._cache_size() == compiled


def _one_device_mesh():
    return Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1, 1, 1), AXES)


@pytest.mark.parametrize(
    "other",
    [
        lambda: (_cfg(model=dataclasses.replace(
            llama.LlamaConfig.tiny(), rope_theta=5e5)), None),
        lambda: (_cfg(logprobs_topk=3), None),
        lambda: (_cfg(eos_token_id=7), None),
        lambda: (_cfg(), _one_device_mesh()),
    ],
    ids=["model", "logprobs_topk", "eos_token_id", "mesh"],
)
def test_engines_that_differ_in_a_constructor_argument_do_not(other):
    """The memo's key is every argument of ``ProgramSet``: a program traced
    for one model, top-k, eos token or mesh never serves another."""
    cfg, mesh = other()
    a = InferenceEngine(_cfg(), seed=0)
    b = InferenceEngine(cfg, seed=0, mesh=mesh)
    assert a.programs is not b.programs
    for name in PROGRAMS:
        assert getattr(a.programs, name) is not getattr(b.programs, name)
    assert b.programs.mesh is mesh
    assert (b.programs.alt_k, b.programs.eos) == (
        cfg.logprobs_topk, cfg.eos_token_id
    )


def test_fresh_programs_gives_every_engine_its_own(fresh_programs):
    a, b = InferenceEngine(_cfg(), seed=0), InferenceEngine(_cfg(), seed=0)
    assert type(a.programs) is conftest.ProgramSet
    assert a.programs is not b.programs
    assert a.programs.prefill is not b.programs.prefill


def test_the_warm_up_driver_builds_its_own_programs():
    """As in the product (engine/exec_pool.py:_program_set): what the driver
    compiles for an incoming model is never a live engine's set."""
    eng = InferenceEngine(_cfg(), seed=0)
    own = exec_pool._program_set(eng.cfg)
    assert type(own) is conftest.ProgramSet
    assert own is not eng.programs
    assert own is not exec_pool._program_set(eng.cfg)


def test_the_benchmarks_own_tests_are_exempt_by_path(request):
    """``tests/fmabench/`` may not be edited to name the fixture, so the
    conftest tells its files by where they are."""
    root = pathlib.Path(conftest.REPO_ROOT)
    assert conftest.is_the_benchmarks(root / "tests/fmabench/test_fmabench_spec.py")
    assert not conftest.is_the_benchmarks(root / "tests/test_fmabench_spec.py")
    assert not conftest.is_the_benchmarks(request.path)
    assert engine.ProgramSet is not conftest.ProgramSet


def test_a_test_may_leave_tracing_off():
    assert tracing.enabled()
    tracing.disable()


def test_and_the_next_test_finds_it_on_again():
    """In file order (the driver runs ``-p no:randomly``): a switch of the
    process does not outlive the test that flipped it."""
    assert tracing.enabled()


def _run_limited(item, body):
    """``body`` under conftest's limit, as the hooks run a test's phases."""
    limited = conftest._time_limited(item)
    next(limited)
    try:
        body()
    finally:
        with pytest.raises(StopIteration):
            next(limited)


def test_a_test_past_its_limit_fails_by_name(monkeypatch, request, capfd):
    monkeypatch.setattr(conftest, "TEST_TIME_LIMIT_S", 0.2)
    started = time.monotonic()
    with pytest.raises(pytest.fail.Exception) as failed:
        _run_limited(request.node, lambda: time.sleep(30))
    assert time.monotonic() - started < 10
    assert request.node.nodeid in str(failed.value)
    assert "ran past its 0.2 s" in str(failed.value)
    # every thread's stack, for whoever reads the failure
    assert "test_a_test_past_its_limit_fails_by_name" in capfd.readouterr().err


def test_a_test_inside_its_limit_leaves_the_alarm_as_it_was(
    monkeypatch, request
):
    """This test runs under the suite's own limit, which the nested one
    must hand back: the handler, and what was left of the time."""
    handler = signal.getsignal(signal.SIGALRM)
    left, _ = signal.getitimer(signal.ITIMER_REAL)
    assert callable(handler) and 0 < left <= conftest.TEST_TIME_LIMIT_S
    monkeypatch.setattr(conftest, "TEST_TIME_LIMIT_S", 0.2)
    _run_limited(request.node, lambda: None)
    assert signal.getsignal(signal.SIGALRM) is handler
    assert 0 < signal.getitimer(signal.ITIMER_REAL)[0] <= left
    time.sleep(0.3)  # the nested alarm is gone with its limit
