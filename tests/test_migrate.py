"""Live request migration: transactional parked-bundle handoff between
instances, with node drain (GET/POST /v1/parked, the single-use fence,
launcher migrate/drain verbs; docs/operations.md "Draining a node
without dropping streams").

The contract under test:
  * a mid-decode stream migrated to a sibling finishes BIT-EXACT vs an
    uninterrupted run — greedy AND seeded — and every streamed token is
    delivered exactly once across the handoff (no replay, no gap);
  * the fence is single-use (double release and abort-after-release are
    refused) and the import is idempotent under it: a repeated import
    replays the stored ack instead of seating a duplicate;
  * every drilled fault point recovers as documented — migrate.export
    resumes locally, migrate.import leaves the destination rolled back
    clean, migrate.ack makes the retry a fenced ack replay — and only
    the abort-after-double-fault path can degrade further;
  * identity is proved, not assumed: a sibling with different weights
    (or a tampered KV chunk) is refused before anything is displaced;
  * the launcher verbs (POST /v2/vllm/instances/{id}/migrate, /drain)
    drive export -> import -> release with the engine's recovery
    discipline (one fenced blind retry on a 5xx import; abort on
    refusal/timeout) and drain loops migrate passes to queue_depth 0.
"""

import threading
import time

import jax
import numpy as np
import pytest
from prometheus_client import REGISTRY, generate_latest

from llm_d_fast_model_actuation_tpu.engine.server import (
    EngineService,
    MigrationFailed,
    MigrationRejected,
    parse_engine_options,
)
from llm_d_fast_model_actuation_tpu.models import checkpoint, llama
from llm_d_fast_model_actuation_tpu.utils import faults

pytestmark = pytest.mark.migrate


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def ckpts(tmp_path_factory):
    """Base checkpoint A plus sibling B differing only in ``lm_head`` —
    same model name, provably different weights (the identity gate's
    refusal case)."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(42), cfg)
    da = str(tmp_path_factory.mktemp("mig-base"))
    checkpoint.save_params(da, cfg, params)
    pb = dict(params)
    head = np.asarray(params["lm_head"])
    pb["lm_head"] = (head * 1.5 + 0.25).astype(np.float32)
    db = str(tmp_path_factory.mktemp("mig-sib"))
    checkpoint.save_params(db, cfg, pb)
    return da, db


def _service(ckpt_dir: str, extra: str = "") -> EngineService:
    return EngineService(
        parse_engine_options(
            f"--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
            f"--max-model-len 64 --swap-bucket-mib 1 --zero-drain on "
            f"--checkpoint-dir {ckpt_dir} {extra}"
        )
    )


def _wire(src: EngineService, dst: EngineService) -> None:
    """In-process transport seam: the source's claim proxy reads the
    destination's claim_view directly instead of going over HTTP."""
    src._claim_fetch = lambda dest, cid, have, wait_s: dst.claim_view(
        cid, wait_s=wait_s, have=have
    )


@pytest.fixture
def pair(ckpts):
    """Source + destination serving the SAME checkpoint, claim-wired."""
    src, dst = _service(ckpts[0]), _service(ckpts[0])
    _wire(src, dst)
    yield src, dst
    src.shutdown()
    dst.shutdown()


def _balance(svc: EngineService) -> None:
    """The ledger invariant every handoff must preserve: each preempted
    stream ends exactly one way."""
    zd = svc.stats()["zero_drain"]
    assert (
        zd["preempted"] == zd["resumed"] + zd["aborted"] + zd["migrated"]
    ), zd


def _live_stream(svc: EngineService, prompt, max_tokens=8, **kw):
    """A stream that is provably mid-decode at export time: on_tokens
    runs inline in the decode loop, so the sleep throttles the whole
    batch while the export parks it."""
    toks: list = []
    started = threading.Event()

    def slow(req, run):
        toks.extend(run)
        started.set()
        time.sleep(0.05 * len(run))

    fut = svc.submit(
        list(prompt), max_tokens, kw.pop("temperature", 0.0),
        on_tokens=slow, **kw,
    )
    assert started.wait(timeout=60), "stream never produced a token"
    return fut, toks


def _counter(name, labels):
    return REGISTRY.get_sample_value(name, labels) or 0.0


# ------------------------------------------------ happy path, bit-exact


def test_migrate_mid_decode_bit_exact_exactly_once(pair):
    src, dst = pair
    gold_g = src.submit([1, 2, 3], 8, 0.0).result(timeout=120).out_tokens
    gold_s = (
        src.submit([4, 5, 6], 8, 0.9, seed=11).result(timeout=120).out_tokens
    )
    pre_mig = _counter(
        "fma_engine_preempted_requests_total",
        {"model": "tiny", "outcome": "migrated"},
    )
    pre_bytes = _counter("fma_engine_migrate_bytes_total", {"dir": "export"})

    f1, toks = _live_stream(src, [1, 2, 3])
    f2 = src.submit([4, 5, 6], 8, 0.9, seed=11)

    doc = src.export_parked("tiny")
    token = doc["fence"]["token"]
    assert doc["nbytes"] > 0 and doc["requests"]["live"]
    ack = dst.import_parked(doc)
    assert ack["ok"] and ack["requests"] == 2
    rel = src.release_parked(token, dest="local", claims=ack["claims"])
    assert rel["ok"] and rel["fence_token"] == token
    assert rel["migrated"] == 2

    # bit-exact vs the uninterrupted runs, on both sampling paths
    assert f1.result(timeout=120).out_tokens == gold_g
    assert f2.result(timeout=120).out_tokens == gold_s
    # the streaming hook fired exactly once per token across the handoff
    assert toks == gold_g

    s = src.stats()
    assert s["migration"]["committed"] == 1
    assert s["migration"]["state_loss"] == 0
    assert s["migration"]["exported"] == 1
    assert s["migration"]["bytes_out"] == doc["nbytes"]
    assert s["zero_drain"]["migrated"] == 2
    _balance(src)
    d = dst.stats()["migration"]
    assert d["imported"] == 1 and d["requests_in"] == 2
    assert d["bytes_in"] == doc["nbytes"]

    # observability satellites: preempted outcome label, byte counter,
    # exposition families, and the cost oracle's migrate row
    assert (
        _counter(
            "fma_engine_preempted_requests_total",
            {"model": "tiny", "outcome": "migrated"},
        )
        - pre_mig
        == 2
    )
    assert (
        _counter("fma_engine_migrate_bytes_total", {"dir": "export"})
        - pre_bytes
        == doc["nbytes"]
    )
    exposition = generate_latest(REGISTRY).decode()
    assert "fma_engine_migrations_total" in exposition
    assert "fma_engine_migrate_bytes_total" in exposition
    row = src.costs_view()["migrate"]
    assert row["kind"] == "migrate" and row["enabled"]

    # the fence is spent but the source is fully live: same bits again
    assert (
        src.submit([1, 2, 3], 8, 0.0).result(timeout=120).out_tokens
        == gold_g
    )


# ------------------------------------------------ fence semantics


def test_fence_single_use_and_idempotent_import_replay(pair):
    src, dst = pair
    f, _ = _live_stream(src, [5, 6, 7])
    doc = src.export_parked("tiny")
    token = doc["fence"]["token"]
    ack = dst.import_parked(doc)
    # a lost-ack style repeat BEFORE release replays the stored ack —
    # same claims, no second seat
    ack2 = dst.import_parked(doc)
    assert ack2["claims"] == ack["claims"]
    assert dst.stats()["migration"]["imported"] == 1
    assert src.release_parked(token, dest="local", claims=ack["claims"])[
        "ok"
    ]
    f.result(timeout=120)
    # the fence is single-use: double resume and late abort are refused
    with pytest.raises(MigrationRejected, match="spent or unknown"):
        src.release_parked(token, dest="local", claims=ack["claims"])
    with pytest.raises(MigrationRejected, match="spent or unknown"):
        src.abort_migration(token)
    _balance(src)


# ------------------------------------------------ drilled fault points


def test_export_fault_resumes_streams_locally(ckpts):
    src = _service(ckpts[0])
    try:
        gold = (
            src.submit([7, 8, 9], 8, 0.0).result(timeout=120).out_tokens
        )
        f, _ = _live_stream(src, [7, 8, 9])
        faults.arm("migrate.export", mode="fail", count=1)
        with pytest.raises(MigrationFailed, match="resumed locally"):
            src.export_parked("tiny")
        # the bundle never left the process: the stream finishes at home
        assert f.result(timeout=120).out_tokens == gold
        s = src.stats()["migration"]
        assert s["resumed_local"] == 1 and s["exported"] == 0
        _balance(src)
    finally:
        src.shutdown()


def test_import_fault_rolls_back_destination_clean(pair):
    src, dst = pair
    gold = src.submit([2, 4, 6], 8, 0.0).result(timeout=120).out_tokens
    f, _ = _live_stream(src, [2, 4, 6])
    doc = src.export_parked("tiny")
    faults.arm("migrate.import", mode="fail", count=1)
    with pytest.raises(MigrationFailed, match="clean"):
        dst.import_parked(doc)
    d = dst.stats()["migration"]
    assert d["rolled_back"] == 1 and d["requests_in"] == 0
    assert dst.queue_depth() == 0  # nothing foreign was left seated
    # the fence is still live: a plain retry seats the bundle
    ack = dst.import_parked(doc)
    assert src.release_parked(
        doc["fence"]["token"], dest="local", claims=ack["claims"]
    )["ok"]
    assert f.result(timeout=120).out_tokens == gold
    _balance(src)


def test_import_double_fault_aborts_to_local_resume(pair):
    src, dst = pair
    gold = src.submit([9, 9, 2], 8, 0.0).result(timeout=120).out_tokens
    f, _ = _live_stream(src, [9, 9, 2])
    doc = src.export_parked("tiny")
    token = doc["fence"]["token"]
    faults.arm("migrate.import", mode="fail", count=2)
    for _ in range(2):
        with pytest.raises(MigrationFailed):
            dst.import_parked(doc)
    # the launcher's last resort: abort the fence, resume at home
    ab = src.abort_migration(token)
    assert ab["ok"] and ab["outcome"] == "resumed_local"
    assert f.result(timeout=120).out_tokens == gold
    # an abort spends the fence too
    with pytest.raises(MigrationRejected, match="spent or unknown"):
        src.release_parked(token, dest="local", claims={})
    _balance(src)


def test_ack_lost_retry_replays_stored_ack(pair):
    src, dst = pair
    gold = src.submit([3, 2, 1], 6, 0.0).result(timeout=120).out_tokens
    f, _ = _live_stream(src, [3, 2, 1], max_tokens=6)
    doc = src.export_parked("tiny")
    faults.arm("migrate.ack", mode="fail", count=1)
    with pytest.raises(MigrationFailed, match="ack lost"):
        dst.import_parked(doc)
    # the seat SUCCEEDED; the fenced retry replays the ack verbatim
    ack = dst.import_parked(doc)
    assert dst.stats()["migration"]["imported"] == 1
    assert src.release_parked(
        doc["fence"]["token"], dest="local", claims=ack["claims"]
    )["ok"]
    assert f.result(timeout=120).out_tokens == gold
    _balance(src)


# ------------------------------------------------ identity / integrity


def test_foreign_weights_refused_then_local_resume(ckpts):
    da, db = ckpts
    src, dst = _service(da), _service(db)
    _wire(src, dst)
    try:
        gold = (
            src.submit([6, 5, 4], 8, 0.0).result(timeout=120).out_tokens
        )
        f, _ = _live_stream(src, [6, 5, 4])
        doc = src.export_parked("tiny")
        with pytest.raises(MigrationRejected, match="fingerprint mismatch"):
            dst.import_parked(doc)
        assert dst.queue_depth() == 0
        ab = src.abort_migration(doc["fence"]["token"])
        assert ab["outcome"] == "resumed_local"
        assert f.result(timeout=120).out_tokens == gold
        _balance(src)
    finally:
        src.shutdown()
        dst.shutdown()


def test_tampered_kv_chunk_refused(pair):
    src, dst = pair
    gold = src.submit([8, 7, 6], 8, 0.0).result(timeout=120).out_tokens
    f, _ = _live_stream(src, [8, 7, 6])
    doc = src.export_parked("tiny")
    chunk = doc["kv"]["chunks"][0]
    chunk["k"] = chunk["k"][:-8] + "AAAAAAA="
    with pytest.raises(ValueError, match="digest"):
        dst.import_parked(doc)
    assert dst.queue_depth() == 0
    ab = src.abort_migration(doc["fence"]["token"])
    assert ab["outcome"] == "resumed_local"
    assert f.result(timeout=120).out_tokens == gold
    _balance(src)


# ------------------------------------------------ launcher verbs


def _stub_engine(behavior):
    """One fake engine child for launcher-level tests. ``behavior`` is a
    mutable dict: ``depths`` scripts successive /v1/stats queue depths
    (last value repeats), ``import_fail``/``import_status`` make the
    next N POST /v1/parked calls fail with that HTTP status."""
    import http.server
    import json as _json
    import socket

    class Handler(http.server.BaseHTTPRequestHandler):
        calls: list = []

        def _reply(self, obj, status=200):
            data = _json.dumps(obj).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def do_GET(self):
            type(self).calls.append(("GET", self.path, None))
            if self.path.startswith("/v1/parked/"):
                n = behavior["exports"] = behavior.get("exports", 0) + 1
                self._reply(
                    {
                        "fence": {"token": f"mig-{n}-stub"},
                        "identity": {"model": "tiny"},
                        "nbytes": 4096,
                        "requests": {
                            "live": [{}], "waiting": [], "pending": [],
                        },
                    }
                )
            elif self.path == "/v1/stats":
                depths = behavior.setdefault("depths", [0])
                depth = depths.pop(0) if len(depths) > 1 else depths[0]
                self._reply({"queue_depth": depth})
            else:
                self._reply({"error": "not found"}, status=404)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = _json.loads(self.rfile.read(n) or b"{}")
            type(self).calls.append(("POST", self.path, body))
            if self.path == "/v1/parked":
                fail = behavior.get("import_fail", 0)
                if fail:
                    behavior["import_fail"] = fail - 1
                    self._reply(
                        {"error": "injected import failure"},
                        status=behavior.get("import_status", 500),
                    )
                else:
                    self._reply(
                        {
                            "ok": True,
                            "fence_token": (body.get("fence") or {}).get(
                                "token"
                            ),
                            "requests": 2,
                            "claims": {"5": "aa", "p0": "bb"},
                        }
                    )
            elif self.path == "/v1/parked/release":
                self._reply(
                    {
                        "ok": True,
                        "fence_token": body.get("fence_token"),
                        "migrated": 2,
                        "proxied": 1,
                    }
                )
            elif self.path == "/v1/parked/abort":
                self._reply({"ok": True, "outcome": "resumed_local"})
            else:
                self._reply({"error": "not found"}, status=404)

        def log_message(self, *a):  # quiet
            pass

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()
    return srv, port, Handler


@pytest.fixture
def stub_fleet(tmp_path):
    """Two stub engine children behind a fake-kickoff launcher: i0 the
    migration source, i1 the sibling destination."""
    from llm_d_fast_model_actuation_tpu.launcher.chiptranslator import (
        ChipTranslator,
    )
    from llm_d_fast_model_actuation_tpu.launcher.instance import (
        InstanceConfig,
    )
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        EngineProcessManager,
    )

    src_b: dict = {}
    dst_b: dict = {}
    src_srv, src_port, src_h = _stub_engine(src_b)
    dst_srv, dst_port, dst_h = _stub_engine(dst_b)
    translator = ChipTranslator.create(mock_chips=True, mock_chip_count=2)
    manager = EngineProcessManager(
        translator,
        log_dir=str(tmp_path),
        kickoff=lambda config, log_path: time.sleep(300),
        enforce_chip_exclusivity=False,
    )
    for i, port in enumerate((src_port, dst_port)):
        manager.create_instance(
            InstanceConfig(
                options=f"--model tiny --port {port}",
                chip_ids=[translator.chip_ids()[i]],
            ),
            instance_id=f"i{i}",
        )

    class Fleet:
        pass

    fl = Fleet()
    fl.manager = manager
    fl.src_b, fl.dst_b = src_b, dst_b
    fl.src_h, fl.dst_h = src_h, dst_h
    fl.dst_port = dst_port
    yield fl
    manager.stop_all_instances(timeout=2)
    for srv in (src_srv, dst_srv):
        srv.shutdown()
        srv.server_close()


def test_launcher_migrate_export_import_release(stub_fleet):
    fl = stub_fleet
    out = fl.manager.migrate_instance("i0")
    assert out["dest_id"] == "i1" and out["model"] == "tiny"
    assert out["fence_token"] == "mig-1-stub"
    assert out["migrated"] == 2 and out["proxied"] == 1
    assert out["bytes"] == 4096 and out["revision"]
    # export doc forwarded verbatim to the destination
    posts = [c for c in fl.dst_h.calls if c[1] == "/v1/parked"]
    assert len(posts) == 1
    assert posts[0][2]["fence"]["token"] == "mig-1-stub"
    # release carried the fence, the sibling's URL, and the claims map
    rel = [c for c in fl.src_h.calls if c[1] == "/v1/parked/release"]
    assert rel[0][2] == {
        "fence_token": "mig-1-stub",
        "dest": f"http://127.0.0.1:{fl.dst_port}",
        "claims": {"5": "aa", "p0": "bb"},
    }


def test_launcher_import_5xx_gets_one_fenced_retry(stub_fleet):
    fl = stub_fleet
    fl.dst_b.update(import_fail=1, import_status=500)
    out = fl.manager.migrate_instance("i0")
    assert out["migrated"] == 2
    posts = [c for c in fl.dst_h.calls if c[1] == "/v1/parked"]
    assert len(posts) == 2  # the one blind retry (fence-idempotent)
    assert not [c for c in fl.src_h.calls if c[1] == "/v1/parked/abort"]


def test_launcher_import_double_failure_aborts_on_source(stub_fleet):
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        MigrateFailed,
    )

    fl = stub_fleet
    fl.dst_b.update(import_fail=2, import_status=500)
    with pytest.raises(MigrateFailed) as ei:
        fl.manager.migrate_instance("i0")
    assert ei.value.status == 500
    aborts = [c for c in fl.src_h.calls if c[1] == "/v1/parked/abort"]
    assert aborts and aborts[0][2] == {"fence_token": "mig-1-stub"}


def test_launcher_import_refusal_aborts_without_retry(stub_fleet):
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        MigrateFailed,
    )

    fl = stub_fleet
    fl.dst_b.update(import_fail=1, import_status=409)
    with pytest.raises(MigrateFailed) as ei:
        fl.manager.migrate_instance("i0")
    assert ei.value.status == 409
    # a refusal is never blindly re-sent — abort straight away
    posts = [c for c in fl.dst_h.calls if c[1] == "/v1/parked"]
    assert len(posts) == 1
    assert [c for c in fl.src_h.calls if c[1] == "/v1/parked/abort"]


def test_launcher_migrate_needs_a_sibling(tmp_path):
    from llm_d_fast_model_actuation_tpu.launcher.chiptranslator import (
        ChipTranslator,
    )
    from llm_d_fast_model_actuation_tpu.launcher.instance import (
        InstanceConfig,
    )
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        EngineProcessManager,
        MigrateFailed,
    )

    translator = ChipTranslator.create(mock_chips=True, mock_chip_count=1)
    manager = EngineProcessManager(
        translator,
        log_dir=str(tmp_path),
        kickoff=lambda config, log_path: time.sleep(300),
        enforce_chip_exclusivity=False,
    )
    try:
        manager.create_instance(
            InstanceConfig(
                options="--model tiny --port 1",
                chip_ids=[translator.chip_ids()[0]],
            ),
            instance_id="only",
        )
        with pytest.raises(MigrateFailed) as ei:
            manager.migrate_instance("only")
        assert ei.value.status == 409
        assert "nothing to migrate to" in str(ei.value)
    finally:
        manager.stop_all_instances(timeout=2)


def test_launcher_drain_loops_migrate_passes_to_empty(stub_fleet):
    fl = stub_fleet
    fl.src_b["depths"] = [3, 2, 0]
    out = fl.manager.drain_instance("i0")
    assert out["drained"] is True
    assert len(out["passes"]) == 2
    assert out["migrated"] == 4 and out["bytes"] == 8192
    assert out["revision"]
    # two full export->import->release rounds really happened
    assert len(
        [c for c in fl.src_h.calls if c[1] == "/v1/parked/release"]
    ) == 2
