"""POST /v1/swap end to end on the engine service: two registered models
time-sharing one chip, pool hit on swap-back with zero checkpoint re-reads,
and bit-exact generations for whichever model is resident."""

import asyncio

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_fast_model_actuation_tpu.engine.server import (
    EngineService,
    build_app,
    parse_engine_options,
)


@pytest.fixture
def service():
    args = parse_engine_options(
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64 --swap-bucket-mib 1"
    )
    svc = EngineService(args)
    yield svc
    svc.shutdown()


def run_async(coro):
    return asyncio.run(coro)


async def _client(service, fn):
    app = build_app(service)
    client = TestClient(TestServer(app))
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def test_swap_roundtrip_pool_hit_and_bit_exact(service):
    async def scenario(client):
        # gold generation on the initial model
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 4}
        )
        assert r.status == 200
        gold = (await r.json())["choices"][0]["token_ids"]
        assert service.builds_total == 1

        # swap to a second registered model: cold build (pool miss)
        r = await client.post("/v1/swap", json={"model": "tiny-gemma"})
        assert r.status == 200
        body = await r.json()
        assert body["swapped"] and not body["pool_hit"]
        assert body["previous_model"] == "tiny" and body["model"] == "tiny-gemma"
        assert service.builds_total == 2

        # the second model serves (different weights, different output)
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 4}
        )
        assert r.status == 200
        other = (await r.json())["choices"][0]["token_ids"]
        assert other != gold

        # /v1/models follows the swap
        r = await client.get("/v1/models")
        assert (await r.json())["data"][0]["id"] == "tiny-gemma"

        # swap back: pool hit, ZERO checkpoint re-reads (no new build),
        # and the generation is bit-exact with the pre-swap gold
        r = await client.post("/v1/swap", json={"model": "tiny"})
        assert r.status == 200
        body = await r.json()
        assert body["pool_hit"] and body["builds_total"] == 2
        assert service.builds_total == 2
        assert body["pool"]["hits"] == 1
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 4}
        )
        assert r.status == 200
        assert (await r.json())["choices"][0]["token_ids"] == gold

        # swap metrics are exported
        r = await client.get("/metrics")
        text = await r.text()
        assert "fma_engine_swap_seconds" in text
        assert "fma_engine_model_pool_bytes" in text
        assert 'fma_engine_swaps_total{model="tiny",source="pool"}' in text

    run_async(_client(service, scenario))


def test_swap_validation_errors(service):
    async def scenario(client):
        r = await client.post("/v1/swap", json={"model": "bogus-model"})
        assert r.status == 400
        r = await client.post("/v1/swap", json={})
        assert r.status == 400
        r = await client.post("/v1/swap", data=b"junk")
        assert r.status == 400
        r = await client.post("/v1/swap", json={"model": "hf:"})
        assert r.status == 400
        # no-op swap to the current model
        r = await client.post("/v1/swap", json={"model": "tiny"})
        assert r.status == 200
        assert (await r.json())["swapped"] is False
        # swapping while asleep is refused (wake first)
        r = await client.post("/sleep", params={"level": "1"})
        assert r.status == 200
        r = await client.post("/v1/swap", json={"model": "tiny-gemma"})
        assert r.status == 400
        r = await client.post("/wake_up")
        assert r.status == 200

    run_async(_client(service, scenario))


def test_swap_aborts_inflight_requests(service):
    """A request decoding on the outgoing model fails with a clear error;
    fresh requests after the swap serve the incoming model."""
    import time as _time

    orig_step = service.engine.step

    def slow_step():
        # generation must comfortably outlast the 0.4 s trigger below even
        # on a loaded box (~7 steps for 40 tokens at decode_chunk=8)
        _time.sleep(0.2)
        return orig_step()

    service.engine.step = slow_step

    async def scenario(client):
        task = asyncio.create_task(
            client.post(
                "/v1/completions", json={"prompt": [5, 6], "max_tokens": 40}
            )
        )
        await asyncio.sleep(0.4)  # let it admit + start decoding
        r = await client.post("/v1/swap", json={"model": "tiny-gemma"})
        assert r.status == 200
        resp = await asyncio.wait_for(task, timeout=30)
        assert resp.status >= 500  # aborted, not silently wrong-model
        r = await client.post(
            "/v1/completions", json={"prompt": [5, 6], "max_tokens": 3}
        )
        assert r.status == 200

    run_async(_client(service, scenario))


def test_swap_pool_eviction_budget():
    """With a zero pool budget every swap-out is evicted immediately and a
    swap-back is a cold build (builds_total grows)."""
    args = parse_engine_options(
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64 --model-pool-mib 0"
    )
    svc = EngineService(args)
    try:
        svc.swap("tiny-gemma")
        assert svc.builds_total == 2
        assert len(svc.model_pool) == 0 and svc.model_pool.evictions == 1
        out = svc.swap("tiny")
        assert not out["pool_hit"]
        assert svc.builds_total == 3  # cold re-build, nothing pooled
    finally:
        svc.shutdown()


def test_release_sleep_drains_pool():
    """A device-releasing sleep destroys the client that owns the pooled
    models' host state: the pool must be invalidated first, and a later
    swap-in must cold-build instead of streaming from dead buffers."""
    args = parse_engine_options(
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64"
    )
    svc = EngineService(args)
    try:
        svc.swap("tiny-gemma")  # pools "tiny"
        assert len(svc.model_pool) == 1
        svc.release_on_sleep = True  # the TPU default, forced on CPU
        svc.sleep(1)
        assert svc.sleeper._released
        assert len(svc.model_pool) == 0 and svc.model_pool.evictions == 1
        svc.wake_up()
        out = svc.swap("tiny")  # survives: cold build, not a dead-pool hit
        assert not out["pool_hit"] and svc.builds_total == 3
        fut = svc.submit([1, 2, 3], 2, 0.0)
        assert len(fut.result(timeout=60).out_tokens) == 2
    finally:
        svc.shutdown()


def test_swap_preserves_prefix_cache_registration():
    """An idle engine's prefix cache survives the round trip: pages move
    bit-exact, so a swap-back serves the cached prefix without re-prefill."""
    args = parse_engine_options(
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64"
    )
    svc = EngineService(args)
    try:
        fut = svc.submit([7] * 16, 2, 0.0)
        fut.result(timeout=60)
        assert svc.engine.prefix_cache is not None
        hit0 = svc.engine.prefix_cache.hit_tokens
        old_engine = svc.engine
        svc.swap("tiny-gemma")
        svc.swap("tiny")
        assert svc.engine is old_engine  # the pooled runtime came back
        fut = svc.submit([7] * 16, 2, 0.0)
        req = fut.result(timeout=60)
        assert req.cached_tokens > 0  # served from the surviving cache
        assert svc.engine.prefix_cache.hit_tokens > hit0
    finally:
        svc.shutdown()
