"""Fleet e2e: launcher subprocess -> engine child -> SLO/goodput surfaces.

The live-path counterpart of the faked rollup test in
test_observability.py: a real launcher process forks a real engine child
serving two sibling tiny variants; traffic + one hot-swap under load run
through the public REST surfaces, then all three observability legs are
read back — the engine's /v1/stats and /metrics, and the launcher's
GET /v2/vllm/instances ``fleet`` block and fma_launcher_fleet_* gauges.

Marked ``slow`` (on top of ``e2e``): the timeout-bound tier-1 sweep skips
it; CI's e2e job covers the path.
"""

import os
import subprocess
import sys

import numpy as np
import pytest
import requests

from conftest import cpu_subprocess_env, free_port, wait_http

pytestmark = [pytest.mark.e2e, pytest.mark.fleet, pytest.mark.slow]


def _make_variants(tmp_path, n=2):
    import jax

    from llm_d_fast_model_actuation_tpu.models import checkpoint, llama

    cfg = llama.LlamaConfig.tiny()
    base = llama.init_params(jax.random.key(3), cfg)
    rng = np.random.default_rng(9)
    dirs = []
    for i in range(n):
        params = dict(base)
        if i:
            fn = np.asarray(base["final_norm"])
            params["final_norm"] = fn + rng.standard_normal(
                fn.shape
            ).astype(np.float32)
        d = str(tmp_path / f"variant-{i}")
        checkpoint.save_params(d, cfg, params)
        dirs.append(d)
    return dirs


def test_fleet_block_and_slo_surfaces_end_to_end(tmp_path):
    variants = _make_variants(tmp_path, n=2)
    lport, eport = free_port(), free_port()
    log_dir = str(tmp_path / "logs")
    os.makedirs(log_dir, exist_ok=True)
    env = cpu_subprocess_env()
    with open(os.path.join(log_dir, "launcher-stdout.log"), "wb") as out:
        proc = subprocess.Popen(
            [
                sys.executable, "-m",
                "llm_d_fast_model_actuation_tpu.launcher.main",
                "--mock-chips", "--mock-chip-count", "4",
                "--mock-topology", "2x2",
                "--host", "127.0.0.1", "--port", str(lport),
                "--log-dir", log_dir,
            ],
            env=env, stdout=out, stderr=subprocess.STDOUT,
        )
    lbase = f"http://127.0.0.1:{lport}"
    ebase = f"http://127.0.0.1:{eport}"
    try:
        wait_http(lbase + "/health", timeout=240)
        options = (
            f"--model tiny --checkpoint-dir {variants[0]} --port {eport} "
            f"--num-pages 32 --page-size 8 --max-batch 2 "
            f"--max-model-len 64 --swap-bucket-mib 1 --model-pool-mib 256 "
            f"--slo-ttft-ms 60000 --slo-tpot-ms 60000"
        )
        r = requests.put(
            lbase + "/v2/vllm/instances/fleet-e2e",
            json={
                "options": options,
                "env_vars": {"JAX_PLATFORMS": "cpu"},
            },
            timeout=30,
        )
        assert r.status_code == 201, r.text
        wait_http(ebase + "/health", timeout=300)

        def complete(n=4):
            r = requests.post(
                ebase + "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": n,
                      "ignore_eos": True},
                timeout=120,
            )
            assert r.status_code == 200, r.text
            return r.json()

        for _ in range(3):
            body = complete()
        usage = body["usage"]
        assert usage["queue_wait_s"] is not None
        assert usage["time_to_first_token_s"] >= usage["queue_wait_s"]

        # hot-swap to the sibling under the launcher, then serve again
        r = requests.post(
            lbase + "/v2/vllm/instances/fleet-e2e/swap",
            json={"model": "tiny", "checkpoint_dir": variants[1]},
            timeout=180,
        )
        assert r.status_code == 200, r.text
        complete()

        # engine leg: stats row + the new exposition families
        st = requests.get(ebase + "/v1/stats", timeout=10).json()
        assert st["finished_requests"] >= 4
        assert st["slo"]["met"] >= 4 and st["slo"]["violated"] == 0
        assert st["goodput_tokens"] > 0
        assert st["actuations"].get("swap", 0) >= 1
        text = requests.get(ebase + "/metrics", timeout=10).text
        for fam in (
            "fma_engine_queue_wait_seconds_bucket",
            "fma_engine_slo_requests_total",
            "fma_engine_goodput_tokens_total",
            "fma_engine_request_arrival_rate",
        ):
            assert fam in text, fam

        # launcher leg: the aggregated fleet block on the instances read
        body = requests.get(lbase + "/v2/vllm/instances", timeout=30).json()
        fleet = body["fleet"]
        assert fleet["instances_total"] == 1
        assert fleet["instances_reporting"] == 1
        assert fleet["slo_requests_met"] >= 4
        assert 0.0 <= fleet["slo_attainment"] <= 1.0
        assert fleet["goodput_tokens"] == st["goodput_tokens"]
        assert fleet["per_instance"]["fleet-e2e"]["reporting"] is True
        # ...and its gauge mirror on the launcher's own /metrics
        ltext = requests.get(lbase + "/metrics", timeout=30).text
        assert "fma_launcher_fleet_slo_attainment" in ltext
        assert (
            'fma_launcher_fleet_instances{state="reporting"} 1.0' in ltext
        )

        requests.delete(lbase + "/v2/vllm/instances", timeout=60)
    finally:
        proc.terminate()
        try:
            proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            proc.kill()
