"""Multi-tenant co-residency: device-resident sibling variants with
route-per-request (POST /v1/residents, ``--resident-variants``,
``--variant-hbm-mib``; docs/perf.md "Co-resident sibling variants").

The contract under test:
  * an interleaved packed mixed batch across >= 2 attached variants is
    BIT-EXACT per request vs each variant served solo — greedy AND
    seeded sampling;
  * admission is explicit: over the resident-set cap or the HBM budget
    (or an unresolvable cold source) raises ResidentRejected — the
    caller falls back to the swap path, never OOM;
  * detach-then-reattach round-trips (delta re-upload from the pool,
    outputs still bit-exact) and a detached rid stops routing;
  * the ResidentSetLedger refcounts shared base digests across members
    and answers the acceptance question: N siblings' device bytes are
    measurably below N full copies;
  * attach/detach pricing is byte-exact (delta wire bytes from the
    digest diff; detach moves zero bytes) and lands in the decision
    flight recorder as tier="coresident";
  * ``--resident-variants 1`` (the default) is inert: same outputs,
    attach verb refused, cap 1 in the stats block;
  * q:-digest (transfer-quantized) chunks spill to the disk tier and
    reload content-verified — corruption is a miss, never wrong bytes.
"""

import asyncio
import glob
import os

import jax
import numpy as np
import pytest
from aiohttp.test_utils import TestClient, TestServer

from llm_d_fast_model_actuation_tpu.engine.chunk_store import (
    QUANT_DIGEST_PREFIX,
    ChunkStore,
    digest_spillable,
    leaf_digest,
)
from llm_d_fast_model_actuation_tpu.engine.server import (
    EngineService,
    ResidentRejected,
    build_app,
    parse_engine_options,
)
from llm_d_fast_model_actuation_tpu.models import checkpoint, llama

pytestmark = pytest.mark.coresident

LM_HEAD_BYTES = None  # filled by the fixture; the per-sibling delta size


@pytest.fixture(scope="module")
def sibling_ckpts(tmp_path_factory):
    """Three Orbax checkpoints of the tiny model: base A plus siblings B
    and C that differ from A (and from each other) only in ``lm_head`` —
    the digest diff every attach moves."""
    global LM_HEAD_BYTES
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(42), cfg)
    head = np.asarray(params["lm_head"])
    LM_HEAD_BYTES = int(head.nbytes)
    rng = np.random.default_rng(7)
    dirs = []
    for i in range(3):
        p = dict(params)
        if i:
            p["lm_head"] = (
                head + rng.standard_normal(head.shape)
            ).astype(np.float32)
        d = str(tmp_path_factory.mktemp(f"sib-{i}"))
        checkpoint.save_params(d, cfg, p)
        dirs.append(d)
    shared = sum(
        int(np.asarray(v).nbytes)
        for k, v in params.items()
        if k != "lm_head"
        for v in (jax.tree.leaves(v) if isinstance(v, dict) else [v])
    )
    return dirs, shared


def _service(ckpt_dir: str, extra: str = "--resident-variants 3"):
    args = parse_engine_options(
        f"--model tiny --num-pages 64 --page-size 8 --max-batch 4 "
        f"--max-model-len 64 --swap-bucket-mib 1 "
        f"--checkpoint-dir {ckpt_dir} "
        f"--packed-serving on --variant-hbm-mib 16 {extra}"
    )
    return EngineService(args)


def _pool_siblings(svc, dirs):
    """Swap through each sibling and back to the base, leaving every
    sibling pooled (slept, digests known) so attach resolves from the
    ``pool`` tier."""
    for d in dirs[1:]:
        svc.swap("tiny", checkpoint_dir=d)
    svc.swap("tiny", checkpoint_dir=dirs[0])


_GREEDY = dict(temperature=0.0)
_SEEDED = dict(temperature=0.8, top_p=0.9, seed=1234)


def _gen(svc, prompt, variant=0, **kw):
    kw = dict(kw)
    t = kw.pop("temperature", 0.0)
    fut = svc.submit(list(prompt), 6, t, variant=variant, **kw)
    return fut.result(timeout=120).out_tokens


# ------------------------------------------------ interleaved bit-exact


def test_interleaved_mixed_batch_bit_exact_vs_solo(sibling_ckpts):
    dirs, shared = sibling_ckpts
    prompts = ([1, 2, 3, 4], [5, 6, 7], [9, 8, 7, 6, 5])

    # solo golds: each variant generates as THE resident model
    gold = {}
    svc = _service(dirs[0])
    try:
        for i, d in enumerate(dirs):
            if i:
                svc.swap("tiny", checkpoint_dir=d)
            gold[i] = {
                "greedy": _gen(svc, prompts[i], **_GREEDY),
                "seeded": _gen(svc, prompts[i], **_SEEDED),
            }
        svc.swap("tiny", checkpoint_dir=dirs[0])

        # the siblings differ: interleaving has something to get wrong
        assert gold[0]["greedy"] != gold[1]["greedy"]

        out_b = svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        out_c = svc.attach_resident("tiny", checkpoint_dir=dirs[2])
        assert out_b["attached"] and out_c["attached"]
        assert out_b["source_tier"] == "pool"
        vb = svc.resolve_request_model(out_b["model"])
        vc = svc.resolve_request_model(out_c["model"])
        assert 0 != vb != vc != 0

        # one interleaved wave: every (variant, sampling) pair in flight
        # at once — packed mixed-batch decode across all three variants
        futs = []
        for kw, which in ((_GREEDY, "greedy"), (_SEEDED, "seeded")):
            for i, v in ((0, 0), (1, vb), (2, vc)):
                k = dict(kw)
                t = k.pop("temperature")
                futs.append(
                    (
                        i,
                        which,
                        svc.submit(
                            list(prompts[i]), 6, t, variant=v, **k
                        ),
                    )
                )
        for i, which, fut in futs:
            assert fut.result(timeout=120).out_tokens == gold[i][which], (
                f"variant {i} {which} diverged under interleaving"
            )

        # the acceptance arithmetic: 3 co-resident siblings cost the base
        # plus two lm_head deltas, measurably below 3 full copies
        view = svc.residents_view()
        assert view["resident_variants"] == 3
        assert view["variant_hbm_bytes"] == 2 * LM_HEAD_BYTES
        led = view["ledger"]
        assert led["bytes_device"] == 2 * LM_HEAD_BYTES
        assert led["bytes_if_duplicated"] == 2 * (shared + LM_HEAD_BYTES)
        assert led["bytes_saved"] == 2 * shared
        assert led["bytes_device"] < led["bytes_if_duplicated"]
    finally:
        svc.shutdown()


# ------------------------------------------------ admission / rejection


def test_admission_rejected_at_cap_budget_and_cold_source(sibling_ckpts):
    dirs, _shared = sibling_ckpts
    svc = _service(dirs[0], extra="--resident-variants 2")
    try:
        _pool_siblings(svc, dirs)

        # HBM budget: admission is priced BEFORE bytes move — shrink the
        # budget below one lm_head delta and the attach must reject
        # (the flag is MiB-granular; the tiny model's delta is ~32 KiB)
        svc._variant_hbm_budget = LM_HEAD_BYTES // 2
        with pytest.raises(ResidentRejected, match="variant delta"):
            svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        svc._variant_hbm_budget = 16 << 20

        out = svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        assert out["attached"]

        # resident-set cap (2 includes the base): a second sibling is
        # explicitly rejected — the caller's cue to take the swap path
        with pytest.raises(ResidentRejected, match="cap"):
            svc.attach_resident("tiny", checkpoint_dir=dirs[2])

        # idempotent re-attach of an attached rid is NOT a rejection
        again = svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        assert again["attached"] is False
        assert again["handle"] == out["handle"]

        # swap/sleep are refused while variants are attached: the base
        # is pinned (its tensors are shared device state)
        with pytest.raises(ValueError, match="resident"):
            svc.swap("tiny", checkpoint_dir=dirs[2])
        with pytest.raises(ValueError, match="resident"):
            svc.sleep(1)

        # rejected admissions land in the flight recorder as outcome
        # "rejected" under tier "coresident" — priced, refused, recorded
        recs = [
            r
            for r in svc.actuations_view()["records"]
            if r["kind"] == "attach" and r["outcome"] == "rejected"
        ]
        assert recs and all(r["tier"] == "coresident" for r in recs)
    finally:
        svc.shutdown()


def test_attach_unresolvable_source_is_rejected(sibling_ckpts):
    dirs, _shared = sibling_ckpts
    svc = _service(dirs[0])
    try:
        # dirs[2] was never swapped/prefetched in THIS service: no pool
        # entry, no staged manifest — cold means reject, not a stall
        with pytest.raises(ResidentRejected, match="not resolvable"):
            svc.attach_resident("tiny", checkpoint_dir=dirs[2])
    finally:
        svc.shutdown()


# ------------------------------------------------ detach / reattach


def test_detach_then_reattach_round_trip(sibling_ckpts):
    dirs, _shared = sibling_ckpts
    svc = _service(dirs[0])
    try:
        _pool_siblings(svc, dirs[:2])
        pred = svc.price_attach("tiny", checkpoint_dir=dirs[1])
        out = svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        rid = out["model"]

        # satellite: pricing is byte-exact — the digest diff IS the wire
        assert pred["predicted_bytes"] == out["wire_bytes"] == LM_HEAD_BYTES
        v = svc.resolve_request_model(rid)
        gold = _gen(svc, [1, 2, 3], variant=v, **_GREEDY)

        det = svc.detach_resident("tiny", checkpoint_dir=dirs[1])
        assert det["detached"] and det["freed_bytes"] == LM_HEAD_BYTES
        assert svc.residents_view()["resident_variants"] == 1
        assert svc.engine.variant_hbm_bytes() == 0
        # a detached rid stops routing
        with pytest.raises(ValueError, match="not resident"):
            svc.resolve_request_model(rid)

        # detach priced at zero bytes (the host tiers kept every chunk)
        det_recs = [
            r
            for r in svc.actuations_view()["records"]
            if r["kind"] == "detach"
        ]
        assert det_recs
        assert det_recs[-1]["predicted_bytes"] == 0
        assert det_recs[-1]["actual_bytes"] == 0

        # reattach: another delta-only upload, outputs still bit-exact
        out2 = svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        assert out2["attached"] and out2["wire_bytes"] == LM_HEAD_BYTES
        v2 = svc.resolve_request_model(out2["model"])
        assert _gen(svc, [1, 2, 3], variant=v2, **_GREEDY) == gold
    finally:
        svc.shutdown()


# ------------------------------------------------ ledger refcounts


def test_shared_base_refcount_accounting(sibling_ckpts):
    dirs, shared = sibling_ckpts
    svc = _service(dirs[0])
    try:
        _pool_siblings(svc, dirs)
        svc.attach_resident("tiny", checkpoint_dir=dirs[1])
        svc.attach_resident("tiny", checkpoint_dir=dirs[2])
        led = svc.resident_ledger
        desc = led.describe()
        assert sorted(desc["members"]) == sorted(
            [f"tiny@{dirs[1]}", f"tiny@{dirs[2]}"]
        )
        for m in desc["members"].values():
            assert m["shared_bytes"] == shared
            assert m["delta_bytes"] == LM_HEAD_BYTES
        # every shared base digest is held by BOTH members
        assert all(
            refs == 2 for refs, _n in led._shared.values()
        )
        assert led.bytes_saved() == 2 * shared

        svc.detach_resident("tiny", checkpoint_dir=dirs[1])
        assert all(
            refs == 1 for refs, _n in led._shared.values()
        )
        assert led.bytes_saved() == shared

        svc.detach_resident("tiny", checkpoint_dir=dirs[2])
        assert not led._shared and not led.members()
        assert led.bytes_saved() == 0
    finally:
        svc.shutdown()


# ------------------------------------------------ off-inert default


def test_resident_variants_1_is_inert(sibling_ckpts):
    dirs, _shared = sibling_ckpts
    base = _service(dirs[0], extra="")  # no --resident-variants at all
    one = _service(dirs[0], extra="--resident-variants 1")
    try:
        p = [1, 2, 3, 4]
        assert _gen(base, p, **_GREEDY) == _gen(one, p, **_GREEDY)
        assert _gen(base, p, **_SEEDED) == _gen(one, p, **_SEEDED)
        for svc in (base, one):
            # no resident set -> no stats block, no gauge noise
            assert "residents" not in svc.stats()
            assert svc.resolve_request_model("tiny") == 0
            assert svc.resolve_request_model(None) == 0
            with pytest.raises(ValueError, match="co-residency is off"):
                svc.attach_resident("tiny", checkpoint_dir=dirs[1])
    finally:
        base.shutdown()
        one.shutdown()


def test_flag_validation():
    with pytest.raises(ValueError, match="packed-serving"):
        parse_engine_options(
            "--model tiny --resident-variants 2"
        )
    with pytest.raises(ValueError, match="content-hash"):
        parse_engine_options(
            "--model tiny --resident-variants 2 --packed-serving on "
            "--content-hash off"
        )
    with pytest.raises(ValueError, match=">= 1"):
        parse_engine_options("--model tiny --resident-variants 0")
    with pytest.raises(ValueError, match=">= 0"):
        parse_engine_options("--model tiny --variant-hbm-mib -1")


# ------------------------------------------------ HTTP verbs


def test_http_residents_verbs(sibling_ckpts):
    dirs, _shared = sibling_ckpts
    svc = _service(dirs[0], extra="--resident-variants 2")
    _pool_siblings(svc, dirs)

    async def scenario(client):
        r = await client.post(
            "/v1/residents",
            json={"model": "tiny", "checkpoint_dir": dirs[1]},
        )
        assert r.status == 200
        body = await r.json()
        rid = body["model"]
        assert body["attached"] and rid == f"tiny@{dirs[1]}"

        # route-per-request: the completions "model" field picks the
        # resident; an unknown model is a client error naming the set
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 4, "model": rid},
        )
        assert r.status == 200
        routed = (await r.json())["choices"][0]["token_ids"]
        r = await client.post(
            "/v1/completions",
            json={"prompt": [1, 2, 3], "max_tokens": 4, "model": "nope"},
        )
        assert r.status == 400

        # over-cap admission is HTTP 409 — the swap-fallback signal
        r = await client.post(
            "/v1/residents",
            json={"model": "tiny", "checkpoint_dir": dirs[2]},
        )
        assert r.status == 409

        r = await client.get("/v1/residents")
        assert r.status == 200
        view = await r.json()
        assert rid in view["residents"]
        assert view["resident_variants"] == 2

        # resident gauges export
        r = await client.get("/metrics")
        text = await r.text()
        assert "fma_engine_resident_variants 2.0" in text
        assert "fma_engine_variant_hbm_bytes" in text
        assert "fma_engine_coresident_saved_bytes" in text

        r = await client.delete(
            "/v1/residents",
            json={"model": "tiny", "checkpoint_dir": dirs[1]},
        )
        assert r.status == 200
        assert (await r.json())["detached"]
        return routed

    async def run():
        app = build_app(svc)
        client = TestClient(TestServer(app))
        await client.start_server()
        try:
            return await scenario(client)
        finally:
            await client.close()

    try:
        routed = asyncio.run(run())
        assert routed  # the routed variant really generated
    finally:
        svc.shutdown()


# ------------------------------------------------ launcher verbs


def _stub_residents_server():
    import http.server
    import json as _json
    import socket

    class Handler(http.server.BaseHTTPRequestHandler):
        calls = []

        def _reply(self, obj, status=200):
            data = _json.dumps(obj).encode()
            self.send_response(status)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)

        def _view(self, **extra):
            return {
                "base": "tiny",
                "resident_variants": 2,
                "resident_variants_cap": 3,
                "variant_hbm_bytes": 128,
                "variant_hbm_budget_bytes": 1 << 20,
                "residents": {"tiny@/ck/b": {"handle": 1}},
                "ledger": {"bytes_saved": 427008},
                **extra,
            }

        def do_POST(self):
            n = int(self.headers.get("Content-Length", 0))
            body = _json.loads(self.rfile.read(n) or b"{}")
            type(self).calls.append(("POST", self.path, body))
            if self.path == "/v1/residents":
                if body.get("model") == "over-cap":
                    self._reply({"error": "resident-set cap"}, status=409)
                else:
                    self._reply(
                        self._view(
                            model="tiny@/ck/b", attached=True,
                            wire_bytes=128, handle=1,
                        )
                    )
            else:
                self._reply({}, status=404)

        def do_DELETE(self):
            n = int(self.headers.get("Content-Length", 0))
            body = _json.loads(self.rfile.read(n) or b"{}")
            type(self).calls.append(("DELETE", self.path, body))
            self._reply(
                self._view(
                    resident_variants=1, variant_hbm_bytes=0,
                    residents={}, ledger={"bytes_saved": 0},
                    model="tiny@/ck/b", detached=True, freed_bytes=128,
                )
            )

        def do_GET(self):
            type(self).calls.append(("GET", self.path, None))
            self._reply(self._view())

        def log_message(self, *a):  # quiet
            pass

    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]
    srv = http.server.ThreadingHTTPServer(("127.0.0.1", port), Handler)
    return srv, port, Handler


def test_launcher_residents_verbs_and_ledger(tmp_path):
    """manager.attach/get/detach_instance_resident forward to the engine
    child, compact the answer into the ChipLedger's resident-set row,
    and surface an engine 409 (admission rejection) as ResidentsFailed
    with the status preserved — the swap-fallback signal."""
    import threading
    import time as _time

    from llm_d_fast_model_actuation_tpu.launcher.chiptranslator import (
        ChipTranslator,
    )
    from llm_d_fast_model_actuation_tpu.launcher.instance import (
        InstanceConfig,
    )
    from llm_d_fast_model_actuation_tpu.launcher.manager import (
        EngineProcessManager,
        ResidentsFailed,
    )

    srv, port, handler = _stub_residents_server()
    t = threading.Thread(target=srv.serve_forever, daemon=True)
    t.start()

    translator = ChipTranslator.create(mock_chips=True, mock_chip_count=2)
    manager = EngineProcessManager(
        translator,
        log_dir=str(tmp_path),
        kickoff=lambda config, log_path: _time.sleep(300),
        enforce_chip_exclusivity=False,
    )
    try:
        manager.create_instance(
            InstanceConfig(
                options=f"--model tiny --port {port}",
                chip_ids=[translator.chip_ids()[0]],
            ),
            instance_id="i1",
        )
        out = manager.attach_instance_resident(
            "i1", "tiny", checkpoint_dir="/ck/b"
        )
        assert out["residents"]["attached"]
        assert (
            "POST",
            "/v1/residents",
            {"model": "tiny", "checkpoint_dir": "/ck/b"},
        ) in handler.calls

        row = manager.ledger.residents()["i1"]
        assert row["base"] == "tiny"
        assert row["resident_variants"] == 2
        assert row["residents"] == ["tiny@/ck/b"]
        assert row["bytes_saved"] == 427008

        # engine admission rejection passes through with its status
        with pytest.raises(ResidentsFailed) as ei:
            manager.attach_instance_resident("i1", "over-cap")
        assert ei.value.status == 409

        st = manager.get_instance_residents("i1")
        assert st["residents"]["resident_variants"] == 2

        manager.detach_instance_resident("i1", "tiny", "/ck/b")
        row = manager.ledger.residents()["i1"]
        assert row["resident_variants"] == 1 and row["residents"] == []
        assert row["bytes_saved"] == 0

        # release drops the resident row with the holder
        manager.stop_instance("i1", timeout=2)
        assert manager.ledger.residents() == {}
    finally:
        manager.stop_all_instances(timeout=2)
        srv.shutdown()
        srv.server_close()


# ------------------------------------------------ q: spill regression


def test_quant_digest_chunks_spill_and_reload_verified(tmp_path):
    """Satellite regression: transfer-quantized (q:) chunks used to be
    pinned host-only (their digest is not recomputable from the blob);
    now they spill with a header-carried content hash and reload
    verified — corruption is a miss, never silently wrong bytes."""
    payload = np.arange(512, dtype=np.int8)
    digest = QUANT_DIGEST_PREFIX + "deadbeef" * 8
    assert digest_spillable(digest)

    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    cs.intern(digest, payload)
    assert cs.release(digest) == payload.nbytes  # last ref -> spill
    assert cs.peek_tier(digest) == "disk"

    got = cs.fetch(digest)
    assert got is not None and np.array_equal(got, payload)
    assert cs.disk_hits == 1 and cs.verify_failures == 0

    # a fresh store adopting the same disk dir verifies too (restart)
    cs2 = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    got2 = cs2.fetch(digest)
    assert got2 is not None and np.array_equal(got2, payload)

    # flip payload bytes on disk: the content verify must turn the
    # reload into a miss and drop the blob
    (path,) = glob.glob(os.path.join(str(tmp_path), "*"))
    with open(path, "r+b") as f:
        f.seek(-8, os.SEEK_END)
        f.write(b"\xff" * 8)
    cs3 = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    assert cs3.fetch(digest) is None
    assert cs3.verify_failures == 1
    assert not os.path.exists(path)
