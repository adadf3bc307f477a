"""EngineService (the engine HTTP server's core) — failure and sleep edges."""

import asyncio
import time

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
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 --max-model-len 64"
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


def test_parse_engine_options_errors():
    with pytest.raises(ValueError):
        parse_engine_options("--model bogus")
    with pytest.raises(ValueError):
        parse_engine_options("--model tiny --what")
    with pytest.raises(ValueError):
        parse_engine_options("--model tiny --tensor-parallel-size 0")


def test_completion_roundtrip(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 4}
        )
        assert r.status == 200
        body = await r.json()
        assert len(body["choices"][0]["token_ids"]) == 4
        assert body["usage"]["prompt_tokens"] == 3

        # string prompts tokenize
        r = await client.post(
            "/v1/completions", json={"prompt": "hi", "max_tokens": 2}
        )
        assert r.status == 200

        # bad bodies are 400s
        r = await client.post("/v1/completions", data=b"junk")
        assert r.status == 400
        r = await client.post("/v1/completions", json={"prompt": []})
        assert r.status == 400
        r = await client.post(
            "/v1/completions", json={"prompt": [1] * 63, "max_tokens": 10}
        )
        assert r.status == 400  # exceeds max_model_len

    run_async(_client(service, scenario))


@pytest.mark.parametrize(
    "path,body",
    [
        ("/v1/completions", {"prompt": [1, 2, 3]}),
        ("/v1/chat/completions", {"messages": [{"role": "user", "content": "hi"}]}),
    ],
    ids=["completions", "chat"],
)
def test_request_model_names_the_served_model_or_is_a_400(service, path, body):
    """A body's ``model`` is the served model (by name, or as the pool keys
    it, ``model@checkpoint_dir``) or left out; another name is a 400 that
    says what is served, never a silent answer from the wrong weights."""

    async def scenario(client):
        for model in (None, "tiny"):
            named = {"model": model} if model else {}
            r = await client.post(path, json={**body, **named, "max_tokens": 2})
            assert r.status == 200, await r.text()
            assert (await r.json())["model"] == "tiny"
        r = await client.post(path, json={**body, "model": "tiny-gemma", "max_tokens": 2})
        assert r.status == 400
        text = await r.text()
        assert "tiny-gemma" in text and "serving 'tiny'" in text

    run_async(_client(service, scenario))
    service.args.checkpoint_dir = "/ckpts/a"
    service.check_request_model("tiny@/ckpts/a")
    with pytest.raises(ValueError, match="serving 'tiny@/ckpts/a'"):
        service.check_request_model("tiny@/ckpts/b")


def test_level2_wake_aborts_inflight(service):
    # slow each engine step down so the generation is reliably in flight
    orig_step = service.engine.step

    def slow_step():
        # generation must comfortably outlast the 0.4 s trigger below even
        # on a loaded box (~7 steps for 40 tokens at decode_chunk=8); at
        # 0.05 s/step the request could finish before the sleep landed
        time.sleep(0.2)
        return orig_step()

    service.engine.step = slow_step

    async def scenario(client):
        # a long generation in flight
        task = asyncio.create_task(
            client.post(
                "/v1/completions", json={"prompt": [5, 6], "max_tokens": 40}
            )
        )
        await asyncio.sleep(0.4)  # let it admit + start decoding
        r = await client.post("/sleep", params={"level": "2"})
        assert r.status == 200 and (await r.json())["level"] == 2
        r = await client.post("/wake_up")
        assert r.status == 200
        resp = await asyncio.wait_for(task, timeout=30)
        # the in-flight request must NOT succeed with garbage: 500 family
        assert resp.status >= 500

        # fresh requests after wake work
        r = await client.post(
            "/v1/completions", json={"prompt": [5, 6], "max_tokens": 3}
        )
        assert r.status == 200

    run_async(_client(service, scenario))


def test_sleep_escalation(service):
    service.sleep(1)
    assert service.sleeper.stats.bytes_offloaded > 0
    info = service.sleep(2)  # escalate: host copy dropped
    assert info["level"] == 2 and info["bytes_offloaded"] == 0
    service.wake_up()
    assert not service.sleeper.is_sleeping


def test_engine_loop_failure_fails_health_and_requests(service):
    async def scenario(client):
        def boom():
            raise RuntimeError("injected device failure")

        service.engine.step = boom
        task = asyncio.create_task(
            client.post("/v1/completions", json={"prompt": [1], "max_tokens": 2})
        )
        resp = await asyncio.wait_for(task, timeout=10)
        assert resp.status == 500

        r = await client.get("/health")
        assert r.status == 503
        body = await r.json()
        assert "injected device failure" in body["error"]

    run_async(_client(service, scenario))


async def _read_sse(resp):
    """Collect SSE data events until [DONE]; returns the decoded JSON list."""
    events = []
    async for line in resp.content:
        line = line.decode().strip()
        if not line.startswith("data: "):
            continue
        payload = line[len("data: "):]
        if payload == "[DONE]":
            return events, True
        import json

        events.append(json.loads(payload))
    return events, False


def test_streaming_completion_delivers_every_token(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 5, "stream": True},
        )
        assert r.status == 200
        assert r.headers["Content-Type"].startswith("text/event-stream")
        events, done = await _read_sse(r)
        assert done
        # token chunks carry choices; the final usage chunk (OpenAI
        # include_usage shape: empty choices) closes the stream
        tok_events = [e for e in events if e.get("choices")]
        toks = [t for e in tok_events for t in e["choices"][0]["token_ids"]]
        assert len(toks) == 5
        tails = [e for e in events if not e.get("choices")]
        assert len(tails) == 1 and events[-1] is tails[0]
        u = tails[0]["usage"]
        assert u["completion_tokens"] == 5
        assert "queue_wait_s" in u and "decode_tpot_s" in u

        # the streamed tokens match a non-streamed run of the same prompt
        r2 = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 5}
        )
        body = await r2.json()
        assert body["choices"][0]["token_ids"] == toks

    run_async(_client(service, scenario))


def test_streamed_events_carry_one_run_a_chunk():
    """A streamed completion gets one event for its first token and one
    for every decode chunk after, each carrying that chunk's ids; the ids
    in order are the non-streamed output; `done` only on the last; the
    scheduler counts one hook delivery an event."""
    svc = EngineService(parse_engine_options(
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64 --decode-chunk 4"
    ))

    async def scenario(client):
        before = (await (await client.get("/v1/stats")).json())["scheduler"]
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 13, "stream": True,
                  "ignore_eos": True},
        )
        assert r.status == 200
        events, done = await _read_sse(r)
        assert done
        choices = [e["choices"][0] for e in events if e.get("choices")]
        runs = [c["token_ids"] for c in choices]
        # the first token is its own event (ttft is what it was), then
        # twelve tokens in three chunks of four
        assert [len(run) for run in runs] == [1, 4, 4, 4]
        # the writer saw `done` on the last run and on no earlier one: the
        # usage event (written only after a `done`) follows all four
        assert [bool(e.get("choices")) for e in events] == [True] * 4 + [False]
        assert events[-1]["usage"]["completion_tokens"] == 13
        # the text an event holds back (bytes that are no character yet)
        # comes with a later one: in all, the decode of all the ids
        assert "".join(c["text"] for c in choices) == svc_tok.decode(
            [t for run in runs for t in run]
        )
        after = (await (await client.get("/v1/stats")).json())["scheduler"]
        assert after["emit_deliveries"] - before["emit_deliveries"] == 4
        assert after["emit_tokens"] - before["emit_tokens"] == 13

        r2 = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 13, "ignore_eos": True},
        )
        body = await r2.json()
        assert body["choices"][0]["token_ids"] == [t for run in runs for t in run]
        # a request nobody streams makes no delivery
        last = (await (await client.get("/v1/stats")).json())["scheduler"]
        assert last["emit_deliveries"] == after["emit_deliveries"]

    from llm_d_fast_model_actuation_tpu.engine.tokenizer import ByteTokenizer

    svc_tok = ByteTokenizer()
    try:
        run_async(_client(svc, scenario))
    finally:
        svc.shutdown()


def test_mailbox_loses_nothing_and_wakes_the_loop_less_than_once_an_item():
    """Threads post runs for their own queues while the loop drains: every
    queue gets all of its items, in order, and the loop was woken fewer
    times than items were posted (a burst shares one wake-up)."""
    import sys
    import threading

    from llm_d_fast_model_actuation_tpu.engine.server import _Mailbox

    posters, items = 12, 400

    async def scenario():
        loop = asyncio.get_running_loop()
        box = _Mailbox()
        wakes = [0]
        drain = box._drain

        def counting_drain():
            wakes[0] += 1
            drain()

        box._drain = counting_drain
        queues = [asyncio.Queue() for _ in range(posters)]

        def post_all(q):
            for i in range(items):
                box.post(loop, q, i)

        threads = [
            threading.Thread(target=post_all, args=(q,)) for q in queues
        ]
        for t in threads:
            t.start()
        got = [
            [await asyncio.wait_for(q.get(), timeout=30) for _ in range(items)]
            for q in queues
        ]
        for t in threads:
            t.join(timeout=30)
            assert not t.is_alive()
        assert got == [list(range(items))] * posters
        assert all(q.empty() for q in queues) and not box._items
        assert 1 <= wakes[0] < posters * items

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        run_async(scenario())
    finally:
        sys.setswitchinterval(old)


def test_streaming_submit_error_is_sse_error_event(service):
    async def scenario(client):
        # request larger than max_model_len fails at admission, after SSE
        # headers are committed: must surface as an error event, not a hang
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1] * 63, "max_tokens": 10, "stream": True},
        )
        assert r.status == 400  # rejected before streaming starts

        # an engine-loop failure mid-stream surfaces as an SSE error event
        def boom():
            raise RuntimeError("injected stream failure")

        service.engine.step = boom
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2], "max_tokens": 4, "stream": True},
        )
        assert r.status == 200
        events, done = await _read_sse(r)
        assert done
        assert any("error" in e for e in events)

    run_async(_client(service, scenario))


def test_chat_completions_roundtrip_and_stream(service):
    async def scenario(client):
        msgs = [
            {"role": "system", "content": "be terse"},
            {"role": "user", "content": "hi"},
        ]
        r = await client.post(
            "/v1/chat/completions", json={"messages": msgs, "max_tokens": 4}
        )
        assert r.status == 200
        body = await r.json()
        assert body["object"] == "chat.completion"
        msg = body["choices"][0]["message"]
        assert msg["role"] == "assistant" and len(msg["token_ids"]) == 4

        # streamed chat: first delta carries the role, deltas concatenate
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": msgs, "max_tokens": 4, "stream": True},
        )
        assert r.status == 200
        events, done = await _read_sse(r)
        assert done
        tok_events = [e for e in events if e.get("choices")]
        assert len(tok_events) == 4
        assert tok_events[0]["choices"][0]["delta"]["role"] == "assistant"
        streamed = "".join(
            e["choices"][0]["delta"]["content"] for e in tok_events
        )
        assert streamed == msg["content"]
        # the final usage chunk mirrors the non-streamed usage block
        u = events[-1]["usage"]
        assert not events[-1]["choices"]
        assert u["completion_tokens"] == 4 and "queue_wait_s" in u

        # malformed messages are 400s
        r = await client.post("/v1/chat/completions", json={"messages": []})
        assert r.status == 400
        r = await client.post(
            "/v1/chat/completions", json={"messages": [{"role": "user"}]}
        )
        assert r.status == 400

    run_async(_client(service, scenario))


def test_resolve_distributed_flags_and_env(monkeypatch):
    from llm_d_fast_model_actuation_tpu.engine.server import resolve_distributed

    # single-process default
    args = parse_engine_options("--model tiny")
    assert resolve_distributed(args) is None

    # CLI flags
    args = parse_engine_options(
        "--model tiny --num-processes 2 --process-id 1 "
        "--coordinator-address 10.0.0.1:8476"
    )
    assert resolve_distributed(args) == {
        "coordinator_address": "10.0.0.1:8476",
        "num_processes": 2,
        "process_id": 1,
    }

    # gang env (what the slice-gang coordinator ships)
    monkeypatch.setenv("FMA_NUM_PROCESSES", "4")
    monkeypatch.setenv("FMA_PROCESS_ID", "3")
    monkeypatch.setenv("FMA_COORDINATOR_ADDRESS", "10.0.0.2:8476")
    args = parse_engine_options("--model tiny")
    assert resolve_distributed(args) == {
        "coordinator_address": "10.0.0.2:8476",
        "num_processes": 4,
        "process_id": 3,
    }

    # CLI beats env
    args = parse_engine_options(
        "--model tiny --num-processes 2 --process-id 0 "
        "--coordinator-address 10.0.0.3:1"
    )
    assert resolve_distributed(args)["num_processes"] == 2

    # incomplete coordination config is an error
    monkeypatch.delenv("FMA_PROCESS_ID")
    monkeypatch.delenv("FMA_COORDINATOR_ADDRESS")
    args = parse_engine_options("--model tiny --num-processes 2")
    with pytest.raises(ValueError):
        resolve_distributed(args)


def test_engine_serving_metrics_are_exercised(service):
    from prometheus_client import REGISTRY

    async def scenario(client):
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 4}
        )
        assert r.status == 200

    run_async(_client(service, scenario))

    def val(name, **labels):
        return REGISTRY.get_sample_value(name, {"model": "tiny", **labels})

    assert val("fma_engine_prompt_tokens_total") >= 3
    assert val("fma_engine_generation_tokens_total") >= 4
    assert val("fma_engine_time_to_first_token_seconds_count") >= 1
    assert val("fma_engine_request_seconds_count") >= 1
    assert val("fma_engine_kv_cache_usage_ratio") is not None


def test_sampling_top_p_stop_and_logprobs():
    import jax
    import jax.numpy as jnp
    import numpy as np

    from llm_d_fast_model_actuation_tpu.engine.sampling import sample

    logits = jnp.log(
        jnp.asarray([[0.6, 0.3, 0.05, 0.03, 0.02]], dtype=jnp.float32)
    )
    # greedy: temperature 0 picks argmax and reports its true logprob
    tok, lp = sample(
        logits, jax.random.key(0), jnp.zeros((1,)), top_p=jnp.ones((1,))
    )
    assert int(tok[0]) == 0
    assert np.isclose(float(lp[0]), float(jnp.log(0.6)), atol=1e-5)

    # top_p=0.5: only token 0 survives nucleus truncation, at any temp
    for seed in range(5):
        tok, _ = sample(
            logits,
            jax.random.key(seed),
            jnp.ones((1,)),
            top_p=jnp.asarray([0.5]),
        )
        assert int(tok[0]) == 0
    # top_p=0.95 at high temp can pick beyond token 0
    seen = {
        int(
            sample(
                logits,
                jax.random.key(s),
                jnp.full((1,), 5.0),
                top_p=jnp.asarray([0.95]),
            )[0][0]
        )
        for s in range(30)
    }
    assert len(seen) > 1


def test_stop_sequences_and_logprobs_over_http(service):
    async def scenario(client):
        # learn what the model emits greedily
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 6, "logprobs": True},
        )
        body = await r.json()
        toks = body["choices"][0]["token_ids"]
        lps = body["choices"][0]["logprobs"]["token_logprobs"]
        assert len(lps) == len(toks) == 6
        assert all(lp <= 0.0 for lp in lps)

        # stop on the first emitted token: it is stripped (OpenAI
        # semantics) so the output is empty with finish_reason length/stop
        r = await client.post(
            "/v1/completions",
            json={
                "prompt": [1, 2, 3],
                "max_tokens": 6,
                "stop": [[toks[0]]],
            },
        )
        body = await r.json()
        assert body["choices"][0]["token_ids"] == []

        # a stop sequence that never occurs leaves the output untouched
        absent = (toks[0] + 1) % 256 or 1
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 6, "stop": [[absent]]},
        )
        body = await r.json()
        assert body["choices"][0]["token_ids"] == toks

        # top_p validation
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 2, "top_p": 1.5},
        )
        assert r.status == 400
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 2, "top_p": 0.9,
                  "temperature": 0.8},
        )
        assert r.status == 200

    run_async(_client(service, scenario))


def test_n_parallel_completions(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 3, "n": 2},
        )
        assert r.status == 200
        body = await r.json()
        assert len(body["choices"]) == 2
        assert [c["index"] for c in body["choices"]] == [0, 1]
        # greedy: both samples identical; usage sums completions
        assert body["choices"][0]["token_ids"] == body["choices"][1]["token_ids"]
        assert body["usage"]["completion_tokens"] == 6

        r = await client.post(
            "/v1/completions",
            json={"prompt": [1], "max_tokens": 2, "n": 99},
        )
        assert r.status == 400
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1], "max_tokens": 2, "n": "x"},
        )
        assert r.status == 400

    run_async(_client(service, scenario))


def test_n_edge_cases(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions", json={"prompt": [1], "max_tokens": 2, "n": 0}
        )
        assert r.status == 400
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1], "max_tokens": 2, "n": 2, "stream": True},
        )
        assert r.status == 400

    run_async(_client(service, scenario))


def test_chat_n_parallel(service):
    async def scenario(client):
        msgs = [{"role": "user", "content": "hi"}]
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": msgs, "max_tokens": 3, "n": 2},
        )
        assert r.status == 200
        body = await r.json()
        assert len(body["choices"]) == 2
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": msgs, "max_tokens": 3, "n": 2, "stream": True},
        )
        assert r.status == 400

    run_async(_client(service, scenario))


def test_metrics_endpoint_exports_engine_gauges():
    # spec gauges export only when the feature is on (no dead series)
    svc = EngineService(
        parse_engine_options(
            "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
            "--max-model-len 64 --speculative-ngram 4"
        )
    )
    try:
        async def scenario(client):
            await client.post(
                "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 2}
            )
            r = await client.get("/metrics")
            assert r.status == 200
            text = await r.text()
            for family in (
                "fma_engine_queue_depth{",
                "fma_engine_prefix_cache_hit_tokens{",
                "fma_engine_spec_proposed_tokens{",
                "fma_engine_spec_accepted_tokens{",
            ):
                assert family in text, f"{family} missing from /metrics"

        run_async(_client(svc, scenario))
    finally:
        svc.shutdown()


def test_max_tokens_validation(service):
    async def scenario(client):
        for bad in (0, -3):
            r = await client.post(
                "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": bad},
            )
            assert r.status == 400, await r.text()
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 0, "stream": True},
        )
        assert r.status == 400
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 1}
        )
        body = await r.json()
        assert r.status == 200 and len(body["choices"][0]["token_ids"]) == 1

    run_async(_client(service, scenario))


def test_top_logprobs_completions_and_chat(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 3, "logprobs": 3},
        )
        body = await r.json()
        assert r.status == 200, body
        lp = body["choices"][0]["logprobs"]
        toks = body["choices"][0]["token_ids"]
        assert len(lp["top_logprobs"]) == len(toks)
        for t, tlp, alts in zip(toks, lp["token_logprobs"], lp["top_logprobs"]):
            # dict keyed by decoded token text (OpenAI shape): distinct ids
            # can decode to the same string under the byte fallback
            assert 1 <= len(alts) <= 3
            # greedy: the sampled token IS the argmax, so its logprob
            # equals the best alternative's
            best = max(alts.values())
            assert abs(best - tlp) < 1e-4
            assert all(v <= best + 1e-6 for v in alts.values())

        # out of range -> 400
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 2, "logprobs": 50},
        )
        assert r.status == 400

        # int logprobs with stream: rejected up front, not silently dropped
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 2, "logprobs": 2,
                  "stream": True},
        )
        assert r.status == 400
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "x"}],
                  "max_tokens": 2, "logprobs": True, "top_logprobs": 2,
                  "stream": True},
        )
        assert r.status == 400
        # bad top_logprobs 400 names the right field
        r = await client.post(
            "/v1/chat/completions",
            json={"messages": [{"role": "user", "content": "x"}],
                  "max_tokens": 2, "logprobs": True, "top_logprobs": 50},
        )
        assert r.status == 400 and "top_logprobs" in await r.text()

        # chat: OpenAI content shape with top_logprobs
        r = await client.post(
            "/v1/chat/completions",
            json={
                "messages": [{"role": "user", "content": "hi"}],
                "max_tokens": 3,
                "logprobs": True,
                "top_logprobs": 2,
            },
        )
        body = await r.json()
        assert r.status == 200, body
        content = body["choices"][0]["logprobs"]["content"]
        assert len(content) == len(body["choices"][0]["message"]["token_ids"])
        for entry in content:
            assert isinstance(entry["token"], str)
            assert len(entry["top_logprobs"]) == 2

        # logprobs: true (bool) keeps the legacy sampled-only shape
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 2, "logprobs": True},
        )
        body = await r.json()
        assert "top_logprobs" not in body["choices"][0]["logprobs"]

    run_async(_client(service, scenario))


def test_echo_with_prompt_logprobs(service):
    async def scenario(client):
        prompt = [7, 8, 9, 10]
        r = await client.post(
            "/v1/completions",
            json={"prompt": prompt, "max_tokens": 3, "echo": True,
                  "logprobs": 2},
        )
        body = await r.json()
        assert r.status == 200, body
        c = body["choices"][0]
        lp = c["logprobs"]
        # arrays cover prompt + completion; first prompt entry is null
        assert lp["tokens"] == prompt + c["token_ids"]
        assert lp["token_logprobs"][0] is None
        assert len(lp["token_logprobs"]) == len(prompt) + len(c["token_ids"])
        assert all(
            v is None or v <= 0.0 for v in lp["token_logprobs"]
        )
        # prompt positions carry empty top_logprobs, completions real ones
        assert lp["top_logprobs"][: len(prompt)] == [{}] * len(prompt)
        assert all(len(d) >= 1 for d in lp["top_logprobs"][len(prompt):])
        # echoed text starts with the decoded prompt
        assert c["text"].startswith(
            service.tokenizer.decode(prompt)
        )

        # prompt logprobs must agree with a prefix-cache-off rerun of the
        # same prompt (the cache is bypassed for these requests)
        r2 = await client.post(
            "/v1/completions",
            json={"prompt": prompt, "max_tokens": 3, "echo": True,
                  "logprobs": 2},
        )
        body2 = await r2.json()
        assert (
            body2["choices"][0]["logprobs"]["token_logprobs"]
            == lp["token_logprobs"]
        )

        # echo + stream -> 400
        r = await client.post(
            "/v1/completions",
            json={"prompt": prompt, "max_tokens": 2, "echo": True,
                  "stream": True},
        )
        assert r.status == 400

        # n > 1: all choices carry the (identical) prompt scores; only
        # the first sibling paid the uncached prompt forward
        r = await client.post(
            "/v1/completions",
            json={"prompt": prompt, "max_tokens": 2, "echo": True,
                  "logprobs": True, "n": 2},
        )
        body = await r.json()
        assert r.status == 200, body
        c0, c1 = body["choices"]
        np_ = len(prompt)
        assert (
            c0["logprobs"]["token_logprobs"][:np_]
            == c1["logprobs"]["token_logprobs"][:np_]
        )
        assert c1["logprobs"]["token_logprobs"][0] is None
    run_async(_client(service, scenario))


def test_seed_parameter_over_http(service):
    async def scenario(client):
        body = {"prompt": [1, 2, 3], "max_tokens": 6, "temperature": 0.9,
                "seed": 42}
        r1 = await client.post("/v1/completions", json=body)
        r2 = await client.post("/v1/completions", json=body)
        t1 = (await r1.json())["choices"][0]["token_ids"]
        t2 = (await r2.json())["choices"][0]["token_ids"]
        assert r1.status == r2.status == 200
        assert t1 == t2, "same seed must reproduce the same sample"

        r3 = await client.post(
            "/v1/completions",
            json={**body, "seed": 43},
        )
        t3 = (await r3.json())["choices"][0]["token_ids"]
        assert t3 != t1, "different seed, different sample"

        # n>1 with seed: choices distinct from each other, but the SET of
        # choices reproduces
        r4 = await client.post("/v1/completions", json={**body, "n": 2})
        r5 = await client.post("/v1/completions", json={**body, "n": 2})
        c4 = [c["token_ids"] for c in (await r4.json())["choices"]]
        c5 = [c["token_ids"] for c in (await r5.json())["choices"]]
        assert c4 == c5
        assert c4[0] != c4[1]

        # invalid seed -> 400 (type and range: an out-of-int64 seed
        # would otherwise overflow inside the engine thread)
        for bad in ("abc", 2**63, -(2**63) - 1):
            r = await client.post(
                "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": 2, "seed": bad},
            )
            assert r.status == 400, bad

        # chat honors seed too
        cbody = {"messages": [{"role": "user", "content": "hi"}],
                 "max_tokens": 5, "temperature": 0.9, "seed": 7}
        r1 = await client.post("/v1/chat/completions", json=cbody)
        r2 = await client.post("/v1/chat/completions", json=cbody)
        a = (await r1.json())["choices"][0]["message"]["token_ids"]
        b = (await r2.json())["choices"][0]["message"]["token_ids"]
        assert a == b

    run_async(_client(service, scenario))


def test_stop_token_ids_param(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 6}
        )
        toks = (await r.json())["choices"][0]["token_ids"]
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 6,
                  "stop_token_ids": [toks[0]]},
        )
        body = await r.json()
        assert body["choices"][0]["token_ids"] == []
        assert body["choices"][0]["finish_reason"] == "stop"
        for bad in ("nope", [99999], [-1], [True], [1.5]):
            r = await client.post(
                "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": 2,
                      "stop_token_ids": bad},
            )
            assert r.status == 400, bad

    run_async(_client(service, scenario))


def test_ignore_eos_over_http():
    """Two services: one learns the greedy stream, the second is BUILT
    with that stream's second token as eos (set before first compile, so
    the device-side eos budget-zeroing is genuinely in the programs)."""
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    base = (
        "--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        "--max-model-len 64"
    )
    svc = EngineService(parse_engine_options(base))
    try:
        async def learn(client):
            r = await client.post(
                "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 6}
            )
            return (await r.json())["choices"][0]["token_ids"]

        toks = run_async(_client(svc, learn))
    finally:
        svc.shutdown()

    svc = EngineService(
        parse_engine_options(base + f" --eos-token-id {toks[1]}")
    )
    try:
        async def scenario(client):
            r = await client.post(
                "/v1/completions", json={"prompt": [1, 2, 3], "max_tokens": 6}
            )
            short = (await r.json())["choices"][0]
            r = await client.post(
                "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": 6,
                      "ignore_eos": True},
            )
            full = (await r.json())["choices"][0]
            assert len(short["token_ids"]) < 6
            assert short["finish_reason"] == "stop"
            assert len(full["token_ids"]) == 6
            assert full["finish_reason"] == "length"

            # junk values are 400s, not silently truthy
            r = await client.post(
                "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": 2,
                      "ignore_eos": "false"},
            )
            assert r.status == 400

        run_async(_client(svc, scenario))
    finally:
        svc.shutdown()


def test_logit_bias_over_http(service):
    async def scenario(client):
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 3,
                  "logit_bias": {"23": 100}},
        )
        body = await r.json()
        assert r.status == 200, body
        assert body["choices"][0]["token_ids"] == [23, 23, 23]
        for bad in ({"23": 101}, {"99999": 1}, {"x": 1}, [1, 2], {"1": "y"}):
            r = await client.post(
                "/v1/completions",
                json={"prompt": [1, 2, 3], "max_tokens": 2,
                      "logit_bias": bad},
            )
            assert r.status == 400, bad

        # streamed completions honor the bias too
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 3,
                  "logit_bias": {"23": 100}, "stream": True},
        )
        assert r.status == 200
        events, done = await _read_sse(r)
        assert done
        toks = [
            t for e in events if e.get("choices")
            for t in e["choices"][0]["token_ids"]
        ]
        assert toks == [23, 23, 23]

    run_async(_client(service, scenario))


def test_echo_text_prompt_is_verbatim(service):
    """echo of a STRING prompt must return the exact text the client sent,
    not a re-decode of its encoding — a real tokenizer auto-adds BOS on
    encode, and rendering it (skip_special=False) or stripping legitimate
    specials (skip_special=True) both corrupt the echo."""

    class BosTokenizer:
        """Wraps the service tokenizer, prepending a BOS id on encode the
        way HF Llama-family tokenizers do."""

        BOS = 199

        def __init__(self, inner):
            self._inner = inner
            self.eos_token_id = inner.eos_token_id

        def encode(self, text, special=True):
            return [self.BOS] + self._inner.encode(text, special)

        def decode(self, tokens, skip_special=True):
            toks = list(tokens)
            if skip_special and toks and toks[0] == self.BOS:
                toks = toks[1:]
            prefix = "<s>" if not skip_special and toks[:1] == [self.BOS] else ""
            if toks[:1] == [self.BOS]:
                toks = toks[1:]
            return prefix + self._inner.decode(toks)

        def chat_tokens(self, messages):
            return self._inner.chat_tokens(messages)

    service.tokenizer = BosTokenizer(service.tokenizer)

    async def scenario(client):
        r = await client.post(
            "/v1/completions",
            json={"prompt": "hi", "max_tokens": 2, "echo": True,
                  "temperature": 0},
        )
        body = await r.json()
        assert r.status == 200, body
        text = body["choices"][0]["text"]
        assert text.startswith("hi"), (
            f"echoed text must start with the verbatim prompt, got {text!r}"
        )

        # token-id prompts echo their literal decode, specials included
        r = await client.post(
            "/v1/completions",
            json={"prompt": [BosTokenizer.BOS, 104, 105], "max_tokens": 2,
                  "echo": True, "temperature": 0},
        )
        body = await r.json()
        assert r.status == 200, body
        assert body["choices"][0]["text"].startswith("<s>"), body

    run_async(_client(service, scenario))
