"""Launcher: instance lifecycle, chip translation, manager CRUDL, REST API.

Test strategy mirrors the reference's (SURVEY.md §4.2): no real engine is
spawned — instances run a lightweight fake child; sentinel crash detection is
exercised with a child that exits on its own.
"""

import asyncio
import json
import os
import time

import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_fast_model_actuation_tpu.launcher.chiptranslator import ChipTranslator
from llm_d_fast_model_actuation_tpu.launcher.instance import (
    EngineInstance,
    HalfMade,
    InstanceConfig,
    LogRangeNotAvailable,
)
from llm_d_fast_model_actuation_tpu.launcher.manager import EngineProcessManager
from llm_d_fast_model_actuation_tpu.launcher.rest import (
    build_app,
    parse_range_header,
)


def fake_kickoff(config: InstanceConfig, log_path: str) -> None:
    """Child body: write some log lines, then sleep until killed."""
    with open(log_path, "ab", buffering=0) as f:
        f.write(b"engine starting\n")
        f.write(f"options={config.options}\n".encode())
    time.sleep(300)


def crashing_kickoff(config: InstanceConfig, log_path: str) -> None:
    with open(log_path, "ab", buffering=0) as f:
        f.write(b"about to crash\n")
    os._exit(17)


@pytest.fixture
def translator():
    return ChipTranslator.create(mock_chips=True, mock_chip_count=8, mock_topology="2x4")


@pytest.fixture
def manager(translator, tmp_path):
    m = EngineProcessManager(translator, log_dir=str(tmp_path), kickoff=fake_kickoff)
    yield m
    m.stop_all_instances(timeout=2)


def run_async(coro):
    return asyncio.run(coro)


# -- config / translator ------------------------------------------------------


def test_instance_config_wire_compat():
    # reference field names in, reference field names out
    c = InstanceConfig.from_dict(
        {"options": "--model tiny", "gpu_uuids": ["a", "b"], "env_vars": {"X": "1"}}
    )
    assert c.chip_ids == ["a", "b"]
    d = c.to_dict()
    assert d["gpu_uuids"] == ["a", "b"] and "chip_ids" not in d
    # chip_ids alias accepted
    c2 = InstanceConfig.from_dict({"options": "", "chip_ids": ["z"]})
    assert c2.chip_ids == ["z"]
    with pytest.raises(ValueError):
        InstanceConfig.from_dict({"gpu_uuids": ["a"]})


def test_translator_modes(tmp_path):
    t = ChipTranslator.create(mock_chips=True, mock_chip_count=4)
    assert t.mode == "naive-mock" and len(t.chip_ids()) == 4

    # chip-map mock via file + NODE_NAME
    from llm_d_fast_model_actuation_tpu.parallel.topology import ChipMap, HostTopology

    cm = ChipMap()
    cm.set_host("node-a", HostTopology.make("2x2", node="node-a"))
    path = tmp_path / "chipmap.json"
    path.write_text(json.dumps(cm.dump()))
    t2 = ChipTranslator.create(
        mock_chips=True, chip_map_path=str(path), node_name="node-a"
    )
    assert t2.mode == "chip-map-mock"
    assert len(t2.chip_ids()) == 4
    env = t2.env_for(t2.chip_ids()[:2])
    assert env["TPU_VISIBLE_DEVICES"] == "0,1"

    # unknown node falls back to naive
    t3 = ChipTranslator.create(
        mock_chips=True, chip_map_path=str(path), node_name="nope", mock_chip_count=2
    )
    assert t3.mode == "naive-mock"


def test_translator_env_injection(translator):
    ids = translator.chip_ids()
    env = translator.env_for(ids[4:8])
    assert env["TPU_VISIBLE_DEVICES"] == "4,5,6,7"
    with pytest.raises(KeyError):
        translator.id_to_index("bogus")


# -- instance lifecycle -------------------------------------------------------


def test_instance_lifecycle(translator, tmp_path):
    cfg = InstanceConfig(options="--model tiny", chip_ids=[translator.chip_ids()[0]])
    inst = EngineInstance("i1", cfg, translator, log_dir=str(tmp_path), kickoff=fake_kickoff)
    with pytest.raises(HalfMade):
        inst.get_status()
    with pytest.raises(HalfMade):
        inst.stop()

    st = inst.start()
    assert st["status"] == "started"
    assert st["gpu_uuids"] == cfg.chip_ids
    # chip env was injected
    assert inst.config.env_vars["TPU_VISIBLE_DEVICES"] == "0"
    assert inst.start()["status"] == "already_running"
    assert inst.get_status()["status"] == "running"

    # log written by the child
    deadline = time.time() + 5
    while time.time() < deadline:
        try:
            data, total = inst.get_log_bytes()
            if b"engine starting" in data:
                break
        except LogRangeNotAvailable:
            pass
        time.sleep(0.05)
    else:
        pytest.fail("child log never appeared")

    st = inst.stop(timeout=2)
    assert st["status"] == "terminated"
    assert not os.path.exists(inst._log_file_path)
    assert inst.stop(timeout=1)["status"] == "not_running"


def test_log_ranges(translator, tmp_path):
    cfg = InstanceConfig(options="abc")
    inst = EngineInstance("i2", cfg, translator, log_dir=str(tmp_path), kickoff=fake_kickoff)
    inst.start()
    try:
        deadline = time.time() + 5
        while time.time() < deadline:
            try:
                full, total = inst.get_log_bytes()
                if total >= 10:
                    break
            except LogRangeNotAvailable:
                pass
            time.sleep(0.05)
        data, t2 = inst.get_log_bytes(0, 5)
        assert data == full[:6]  # end inclusive
        data, _ = inst.get_log_bytes(7)
        assert data == full[7:]
        with pytest.raises(LogRangeNotAvailable):
            inst.get_log_bytes(10**9)
    finally:
        inst.stop(timeout=2)


def test_replace_model_option():
    from llm_d_fast_model_actuation_tpu.launcher.instance import (
        replace_model_option,
    )

    # rewrites --model wherever it sits, both spellings
    assert replace_model_option("--model tiny --port 80", "tiny-gemma") == (
        "--model tiny-gemma --port 80"
    )
    assert replace_model_option("--port 80 --model=tiny", "x") == (
        "--port 80 --model=x"
    )
    # a missing --model is prepended
    assert replace_model_option("--port 80", "tiny") == "--model tiny --port 80"
    # the OLD model's checkpoint dir never survives a swap (a restart
    # would load shape-mismatched weights); a new one is recorded
    assert replace_model_option(
        "--model a --checkpoint-dir /ckpt/a --port 80", "b"
    ) == "--model b --port 80"
    assert replace_model_option(
        "--model a --checkpoint-dir=/ckpt/a", "b", checkpoint_dir="/ckpt/b"
    ) == "--model b --checkpoint-dir /ckpt/b"


def test_parse_range_header():
    assert parse_range_header("bytes=0-99") == (0, 99)
    assert parse_range_header("bytes=100-") == (100, None)
    for bad in ("bytes=-500", "lines=1-2", "bytes=5-2", "bytes=a-b"):
        with pytest.raises(ValueError):
            parse_range_header(bad)


# -- manager ------------------------------------------------------------------


def test_manager_crudl(manager):
    st = manager.create_instance(InstanceConfig(options="--model tiny"), "a")
    assert st["status"] == "started" and st["revision"] == 1
    with pytest.raises(ValueError):
        manager.create_instance(InstanceConfig(options="x"), "a")
    st2 = manager.create_instance(InstanceConfig(options="y"))
    assert st2["instance_id"] != "a"

    allst = manager.get_all_instances_status()
    assert allst["total_instances"] == 2
    assert allst["running_instances"] == 2
    assert sorted(manager.list_instances()) == sorted(["a", st2["instance_id"]])

    with pytest.raises(KeyError):
        manager.get_instance_status("nope")

    res = manager.stop_instance("a", timeout=2)
    assert res["status"] == "terminated"
    assert manager.list_instances() == [st2["instance_id"]]
    out = manager.stop_all_instances(timeout=2)
    assert out["status"] == "all_stopped"
    assert manager.list_instances() == []


def test_manager_chip_ledger(manager, translator):
    ids = translator.chip_ids()
    manager.create_instance(InstanceConfig(options="a", chip_ids=ids[:4]), "x")
    overlaps = manager.ledger.acquire("probe", ids[3:5])
    assert overlaps == ["x"]
    manager.stop_instance("x", timeout=2)
    assert manager.ledger.holders().get("x") is None


def test_chip_exclusivity_refuses_awake_overlap(translator, tmp_path):
    """A TPU chip has one holder: creating an instance whose chips overlap
    an AWAKE (or unprobeable) holder must 409, not silently double-book."""
    from llm_d_fast_model_actuation_tpu.launcher.manager import ChipConflict

    awake = {"x": True}
    m = EngineProcessManager(
        translator,
        log_dir=str(tmp_path),
        kickoff=fake_kickoff,
        awake_probe=lambda inst: awake.get(inst.instance_id),
    )
    try:
        ids = translator.chip_ids()
        m.create_instance(InstanceConfig(options="a", chip_ids=ids[:4]), "x")
        with pytest.raises(ChipConflict):
            m.create_instance(InstanceConfig(options="b", chip_ids=ids[3:5]), "y")
        assert "y" not in m.ledger.holders(), "refused create must not hold chips"

        # unknown sleep state (probe None) is treated as awake: still refused
        awake["x"] = None
        with pytest.raises(ChipConflict):
            m.create_instance(InstanceConfig(options="b", chip_ids=ids[3:5]), "y")

        # all overlapping holders verifiably asleep -> time-sharing allowed
        awake["x"] = False
        st = m.create_instance(InstanceConfig(options="b", chip_ids=ids[3:5]), "y")
        assert st["instance_id"] == "y"
        # disjoint chips never consult the probe
        st2 = m.create_instance(InstanceConfig(options="c", chip_ids=ids[5:7]), "z")
        assert st2["instance_id"] == "z"
    finally:
        m.stop_all_instances(timeout=2)


def test_chip_exclusivity_enforcement_can_be_disabled(translator, tmp_path):
    m = EngineProcessManager(
        translator,
        log_dir=str(tmp_path),
        kickoff=fake_kickoff,
        enforce_chip_exclusivity=False,
    )
    try:
        ids = translator.chip_ids()
        m.create_instance(InstanceConfig(options="a", chip_ids=ids[:4]), "x")
        # overlap only warns (round-2 behavior), preserved behind the flag
        m.create_instance(InstanceConfig(options="b", chip_ids=ids[3:5]), "y")
        assert set(m.ledger.holders()) == {"x", "y"}
    finally:
        m.stop_all_instances(timeout=2)


# -- REST API -----------------------------------------------------------------


async def _with_client(manager, fn):
    app = build_app(manager)
    server = TestServer(app)
    client = TestClient(server)
    await client.start_server()
    try:
        return await fn(client)
    finally:
        await client.close()


def test_rest_crudl(manager):
    async def scenario(client):
        r = await client.get("/health")
        assert r.status == 200 and (await r.json())["status"] == "OK"

        r = await client.get("/")
        assert "endpoints" in await r.json()

        r = await client.put(
            "/v2/vllm/instances/inst1", json={"options": "--model tiny"}
        )
        assert r.status == 201
        body = await r.json()
        assert body["instance_id"] == "inst1" and body["status"] == "started"

        r = await client.put("/v2/vllm/instances/inst1", json={"options": "x"})
        assert r.status == 409

        r = await client.post("/v2/vllm/instances", json={"options": "y"})
        assert r.status == 201
        auto_id = (await r.json())["instance_id"]

        r = await client.get("/v2/vllm/instances")
        body = await r.json()
        assert body["total_instances"] == 2

        r = await client.get("/v2/vllm/instances", params={"detail": "false"})
        body = await r.json()
        assert set(body["instance_ids"]) == {"inst1", auto_id}
        assert body["count"] == 2 and body["revision"] >= 2

        r = await client.get("/v2/vllm/instances/inst1")
        assert (await r.json())["status"] == "running"
        r = await client.get("/v2/vllm/instances/ghost")
        assert r.status == 404

        r = await client.post("/v2/vllm/instances", data=b"not json")
        assert r.status == 422

        r = await client.delete("/v2/vllm/instances/inst1")
        assert r.status == 200 and (await r.json())["status"] == "terminated"
        r = await client.delete("/v2/vllm/instances/inst1")
        assert r.status == 404

        r = await client.delete("/v2/vllm/instances")
        assert (await r.json())["status"] == "all_stopped"

    run_async(_with_client(manager, scenario))


def test_rest_ranged_log(manager):
    async def scenario(client):
        r = await client.put("/v2/vllm/instances/L", json={"options": "opts"})
        assert r.status == 201
        # wait for the child to write
        for _ in range(100):
            r = await client.get("/v2/vllm/instances/L/log")
            if r.status == 200 and len(await r.read()) > 10:
                break
            await asyncio.sleep(0.05)
        full = await r.read()
        assert r.headers["Accept-Ranges"] == "bytes"
        assert r.headers["Content-Range"] == f"bytes 0-{len(full)-1}/{len(full)}"

        r = await client.get(
            "/v2/vllm/instances/L/log", headers={"Range": "bytes=2-5"}
        )
        assert r.status == 206
        assert await r.read() == full[2:6]

        r = await client.get(
            "/v2/vllm/instances/L/log", headers={"Range": "bytes=3-"}
        )
        assert r.status == 206 and await r.read() == full[3:]

        r = await client.get(
            "/v2/vllm/instances/L/log", headers={"Range": "bytes=-5"}
        )
        assert r.status == 400  # suffix ranges rejected

        r = await client.get(
            "/v2/vllm/instances/L/log", headers={"Range": "bytes=999999-"}
        )
        assert r.status == 416
        assert r.headers["Content-Range"] == f"bytes */{len(full)}"

    run_async(_with_client(manager, scenario))


def test_rest_watch_and_crash(translator, tmp_path):
    """Watch stream sees CREATED, then a crash produces STOPPED with the
    child's exit code (sentinel fd, no polling)."""
    manager = EngineProcessManager(
        translator, log_dir=str(tmp_path), kickoff=crashing_kickoff
    )

    async def scenario(client):
        resp = await client.get("/v2/vllm/instances/watch")
        assert resp.status == 200

        r = await client.put("/v2/vllm/instances/C", json={"options": "x"})
        assert r.status == 201

        events = []
        deadline = time.time() + 10
        while len(events) < 2 and time.time() < deadline:
            line = await asyncio.wait_for(resp.content.readline(), timeout=5)
            if line.strip():
                events.append(json.loads(line))
        assert events[0]["type"] == "CREATED"
        assert events[0]["object"]["instance_id"] == "C"
        assert events[1]["type"] == "STOPPED"
        assert events[1]["object"]["exit_code"] == 17
        assert events[1]["object"]["status"] == "stopped"
        assert events[1]["object"]["revision"] > events[0]["object"]["revision"]

    try:
        run_async(_with_client(manager, scenario))
    finally:
        manager.stop_all_instances(timeout=2)


def test_rest_watch_resume_and_gone(manager):
    async def scenario(client):
        for i in range(3):
            r = await client.put(f"/v2/vllm/instances/w{i}", json={"options": "x"})
            assert r.status == 201

        # resume from revision 1: should see events with revision > 1
        resp = await client.get("/v2/vllm/instances/watch", params={"since": "1"})
        assert resp.status == 200
        seen = []
        for _ in range(2):
            line = await asyncio.wait_for(resp.content.readline(), timeout=5)
            seen.append(json.loads(line))
        assert [e["object"]["instance_id"] for e in seen] == ["w1", "w2"]

        # no since: initial CREATED dump of all current instances
        resp2 = await client.get("/v2/vllm/instances/watch")
        dump = []
        for _ in range(3):
            line = await asyncio.wait_for(resp2.content.readline(), timeout=5)
            dump.append(json.loads(line))
        assert {e["object"]["instance_id"] for e in dump} == {"w0", "w1", "w2"}
        assert all(e["type"] == "CREATED" for e in dump)

    run_async(_with_client(manager, scenario))


def test_rest_watch_410(translator, tmp_path):
    manager = EngineProcessManager(translator, log_dir=str(tmp_path), kickoff=fake_kickoff)
    manager.broadcaster._buf.maxlen  # default 1000
    # simulate an old, evicted revision by publishing many events
    for i in range(5):
        manager._publish("CREATED", {"instance_id": f"e{i}", "revision": None})
    # drop the buffer's head artificially
    while len(manager.broadcaster._buf) > 2:
        manager.broadcaster._buf.popleft()

    async def scenario(client):
        resp = await client.get("/v2/vllm/instances/watch", params={"since": "1"})
        assert resp.status == 410

    try:
        run_async(_with_client(manager, scenario))
    finally:
        manager.stop_all_instances(timeout=2)


def test_forked_child_applies_per_instance_jax_env():
    """jax read JAX_* when the launcher imported it, before the fork: the
    child must point the imported module at its instance's values, or a
    child told JAX_PLATFORMS=cpu takes the chip."""
    import jax

    from llm_d_fast_model_actuation_tpu.launcher.instance import _apply_jax_env

    was = (jax.config.jax_platforms, jax.config.jax_log_compiles)
    try:
        _apply_jax_env(
            {"JAX_PLATFORMS": "cpu", "JAX_LOG_COMPILES": "1",
             "TPU_VISIBLE_DEVICES": "0", "JAX_NOT_AN_OPTION": "x"}
        )
        assert jax.config.jax_platforms == "cpu"
        assert jax.config.jax_log_compiles is True
    finally:
        jax.config.update("jax_platforms", was[0])
        jax.config.update("jax_log_compiles", was[1])
