"""CPU rehearsal of ``chip_smoke.py`` (``-k smoke``).

The smoke's phase functions at tiny size, with the device they must find
injected here (``platform="cpu"``; Pallas in interpreter mode, the launcher
on one mock chip): wrong paths, arguments and control flow are found without
the chip (on-chip-measurement §2.1). What only the chip can refuse is in
``test_chip_compile.py`` and ``test_chip_compile_cells.py``.
"""

import json
import os

import pytest


@pytest.fixture(scope="module")
def smoke():
    import importlib.util
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(repo, "chip_smoke.py")
    )
    mod = importlib.util.module_from_spec(spec)
    sys.modules["chip_smoke"] = mod  # dataclasses resolves the module by name
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_plan(smoke, tmp_path_factory):
    smoke.LOG_DIR = str(tmp_path_factory.mktemp("chip-smoke-logs"))
    return smoke.Plan(
        platform="cpu",
        model="tiny",
        second_model="tiny-gemma",
        engine_options="--num-pages 64 --max-batch 2 --page-size 8 "
        "--max-model-len 128 --sleep-release-devices always",
        launcher_args=("--mock-chips", "--mock-chip-count", "1"),
        head_shapes=((4, 2, 16),),
        page_size=8,
        context=64,
        interpret=True,
        prompt_lens=(5, 40),
        max_tokens=6,
        ready_timeout_s=240.0,
        tp_model="tiny",
        tp=2,
        tp_impls=("grouped", "reference"),
    )


def test_smoke_rehearsal_kernels(smoke, tiny_plan):
    rows = smoke.check_kernels(tiny_plan)
    assert [r["kernel"] for r in rows] == [
        "decode", "decode_inline", "ragged", "prefill",
    ]


@pytest.fixture()
def child_devices(monkeypatch):
    """How many virtual CPU devices the smoke's children see (this process
    keeps conftest's eight): the smoke holds each engine to its count."""

    def set_count(n: int) -> None:
        monkeypatch.setenv(
            "XLA_FLAGS", f"--xla_force_host_platform_device_count={n}"
        )

    return set_count


def test_smoke_rehearsal_server(smoke, tiny_plan, capsys, child_devices):
    child_devices(1)
    dev = smoke.phase_server(tiny_plan)
    assert dev["platform"] == "cpu" and dev["count"] == 1
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "server" and line["same_tokens_after_wake"]


def test_smoke_rehearsal_launcher_time_share(
    smoke, tiny_plan, capsys, child_devices
):
    child_devices(1)
    smoke.phase_launcher(tiny_plan)
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["release_in_process"] and line["same_tokens_after_reacquire"]
    assert line["chip_mode"] == "naive-mock"


def test_smoke_rehearsal_sharded_phase(smoke, tiny_plan, capsys, child_devices):
    """The --chips 4 phase on virtual devices (tiny has two KV heads: tp=2):
    meshes, sharded init and the per-chip byte check (§2.2)."""
    child_devices(2)
    dev = smoke.phase_four_chips(tiny_plan)
    assert dev["count"] == 2
    line = json.loads(capsys.readouterr().out.splitlines()[-1])
    assert line["phase"] == "four_chips" and line["same_tokens"]
    assert all(c["ok"] for c in line["compared"])


def test_impl_comparison_accepts_only_demonstrated_near_ties(smoke):
    """Two implementations may part ways where the reference itself scores
    the other's token within the tolerance of its own, and nowhere else."""
    def run(tokens, lps=(-1.0, -1.0, -1.0)):
        return {"tokens": tokens, "logprobs": list(lps)}

    def never(i):
        raise AssertionError("nothing to score when the tokens agree")

    a = run([5, 6, 7])
    same = smoke.compare_impls(a, run([5, 6, 7]), never)
    assert same["ok"] and same["tokens_agreeing"] == 3
    # the reference gives a's token -1.02 where its own has -1.0: a tie
    at_tie = smoke.compare_impls(a, run([5, 9, 7]), lambda i: -1.02)
    assert at_tie["ok"] and at_tie["tokens_agreeing"] == 1
    assert at_tie["tie_gap"] == 0.02
    # ... and -2.0 is a clear loser: a real disagreement
    assert not smoke.compare_impls(a, run([8, 6, 7]), lambda i: -2.0)["ok"]
    drift = smoke.compare_impls(a, run([5, 6, 7], (-1.5, -1.0, -1.0)), never)
    assert not drift["ok"] and drift["max_logprob_diff"] == 0.5


def test_smoke_refuses_a_device_it_was_not_promised(smoke, tiny_plan):
    """No fallback: the same run held to ``tpu`` fails on this CPU."""
    import dataclasses

    with pytest.raises(RuntimeError, match="needs a tpu device"):
        smoke.check_kernels(dataclasses.replace(tiny_plan, platform="tpu"))


# -- the rules this bring-up made explicit --------------------------------------
