"""Delta-aware hot-swap (engine/sleep.py swap_states digests + the tiered
pool): sibling fine-tune variants move only their content delta over the
device boundary — bit-exact with the full transfer, transactional under
mid-flight faults, and rebuildable from the disk tier after eviction."""

import os
import shutil

import jax
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import sleep
from llm_d_fast_model_actuation_tpu.engine.chunk_store import digest_tree
from llm_d_fast_model_actuation_tpu.engine.sleep import (
    SleepManager,
    SwapRolledBack,
    swap_states,
)
from llm_d_fast_model_actuation_tpu.models import checkpoint, llama
from llm_d_fast_model_actuation_tpu.utils import faults

pytestmark = pytest.mark.deltaswap


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


# -- swap_states unit level ---------------------------------------------------


def _variant_params(seed: int, perturb: bool):
    """Two fine-tune variants of one base: identical except ``head`` (the
    delta a LoRA merge or a fine-tune head produces)."""
    rng = np.random.default_rng(seed)
    base = {
        "embed": rng.standard_normal((64, 32)).astype(np.float32),
        "layers": {
            "wq": rng.standard_normal((2, 32, 32)).astype(np.float32),
            "wk": rng.standard_normal((2, 32, 16)).astype(np.float32),
        },
        "head": rng.standard_normal((32, 64)).astype(np.float32),
    }
    if perturb:
        base["head"] = base["head"] * 1.5 + 0.25
    return base


def _mgr(params, kv_seed: int):
    """An awake SleepManager over {"params", "kv"} — the engine's
    offloadable state shape (attach_sleep)."""
    rng = np.random.default_rng(kv_seed)
    kv = (
        rng.standard_normal((2, 8, 16)).astype(np.float32),
        rng.standard_normal((2, 8, 16)).astype(np.float32),
    )
    box = {
        "state": jax.device_put(
            {"params": params, "kv": kv}, jax.devices()[0]
        )
    }
    mgr = SleepManager(
        lambda: box["state"], lambda s: box.__setitem__("state", s)
    )
    return mgr, box


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _pair():
    """Awake variant-A manager + slept (level-1) variant-B manager, plus
    both digest maps — true siblings sharing everything but ``head``."""
    pa = _variant_params(0, perturb=False)
    pb = _variant_params(0, perturb=True)
    dga, dgb = digest_tree(pa), digest_tree(pb)
    out_mgr, out_box = _mgr(pa, kv_seed=1)
    in_mgr, in_box = _mgr(pb, kv_seed=2)
    in_mgr.sleep(1)
    return out_mgr, out_box, in_mgr, in_box, dga, dgb


def test_delta_swap_numerics_identity_vs_full_swap():
    """The delta schedule (shared leaves never cross the device boundary)
    commits exactly the same awake and slept states as the full transfer."""
    # full-transfer control
    f_out, _, f_in, f_in_box, _, _ = _pair()
    swap_states(f_out, f_in, bucket_bytes=4096)
    full_awake = _leaves(f_in_box["state"])
    full_slept = _leaves(f_out._host_state)
    assert full_awake and full_slept

    # delta run over identical content
    d_out, _, d_in, d_in_box, dga, dgb = _pair()
    m = swap_states(
        d_out, d_in, bucket_bytes=4096, out_digests=dga, in_digests=dgb
    )
    # embed/wq/wk shared (x2 directions); head + both kv legs moved
    pa = _variant_params(0, perturb=False)
    shared = (
        pa["embed"].nbytes + pa["layers"]["wq"].nbytes
        + pa["layers"]["wk"].nbytes
    )
    assert m["deduped_leaves"] == 3
    assert m["bytes_deduped"] == 2 * shared
    assert m["bytes_moved"] == m["bytes_out"] + m["bytes_in"] - 2 * shared
    assert 0 < m["bytes_moved"] < m["bytes_out"] + m["bytes_in"]

    # numerics identity: both schedules commit the same bits
    for got, want in zip(_leaves(d_in_box["state"]), full_awake):
        assert np.array_equal(got, want), "delta awake state != full swap"
    for got, want in zip(_leaves(d_out._host_state), full_slept):
        assert np.array_equal(got, want), "delta slept state != full swap"
    assert d_in._host_state is None  # incoming committed awake


def test_delta_swap_shared_leaf_device_array_handed_over():
    """A content-matched leaf takes over the outgoing model's live device
    array — the same buffer, not a re-upload."""
    d_out, _, d_in, d_in_box, dga, dgb = _pair()
    before = jax.tree.leaves(d_out._get_state())
    swap_states(d_out, d_in, out_digests=dga, in_digests=dgb)
    after = jax.tree.leaves(d_in_box["state"])
    handed = sum(1 for a in after for b in before if a is b)
    assert handed == 3, "shared embed/wq/wk must reuse the live arrays"


def test_delta_swap_no_digests_is_full_transfer():
    out_mgr, _, in_mgr, _, _, _ = _pair()
    m = swap_states(out_mgr, in_mgr)
    assert m["bytes_deduped"] == 0 and m["deduped_leaves"] == 0
    assert m["bytes_moved"] == m["bytes_out"] + m["bytes_in"]


def test_delta_swap_shape_dtype_mismatch_never_matches():
    """Equal digests are necessary but not sufficient: a (fabricated)
    digest collision across different shapes must not pair leaves."""
    pa = {"w": np.zeros((4, 4), np.float32)}
    pb = {"w": np.zeros((16,), np.float32)}
    out_mgr, _ = _mgr(pa, kv_seed=1)
    in_mgr, _ = _mgr(pb, kv_seed=2)
    in_mgr.sleep(1)
    fake = {"w": "same-digest"}
    m = swap_states(out_mgr, in_mgr, out_digests=fake, in_digests=fake)
    assert m["deduped_leaves"] == 0 and m["bytes_deduped"] == 0


def test_delta_swap_rollback_leaves_both_models_intact():
    """A mid-transfer fault during a delta swap rolls back to the exact
    pre-swap states: the handover is commit-only, so matched leaves were
    never touched and the incoming pool entry survives bit-exact."""
    d_out, d_out_box, d_in, _, dga, dgb = _pair()
    awake_before = _leaves(d_out_box["state"])
    slept_before = _leaves(d_in._host_state)
    faults.arm("swap.h2d", mode="fail", count=1)
    with pytest.raises(SwapRolledBack):
        swap_states(
            d_out, d_in, bucket_bytes=4096,
            out_digests=dga, in_digests=dgb,
        )
    for got, want in zip(_leaves(d_out_box["state"]), awake_before):
        assert np.array_equal(got, want), "outgoing model corrupted"
    for got, want in zip(_leaves(d_in._host_state), slept_before):
        assert np.array_equal(got, want), "incoming pool entry corrupted"
    assert not d_out.is_sleeping and d_in.is_sleeping


# -- engine service level -----------------------------------------------------


@pytest.fixture(scope="module")
def variant_ckpts(tmp_path_factory):
    """Two Orbax checkpoints of the tiny model sharing every tensor except
    ``lm_head`` — sibling fine-tunes of one base."""
    cfg = llama.LlamaConfig.tiny()
    params = llama.init_params(jax.random.key(42), cfg)
    da = str(tmp_path_factory.mktemp("ckpt-a"))
    checkpoint.save_params(da, cfg, params)
    params_b = dict(params)
    rng = np.random.default_rng(7)
    params_b["lm_head"] = np.asarray(params["lm_head"]) + rng.standard_normal(
        np.asarray(params["lm_head"]).shape
    ).astype(np.float32)
    db = str(tmp_path_factory.mktemp("ckpt-b"))
    checkpoint.save_params(db, cfg, params_b)
    shared = sum(
        np.asarray(v).nbytes
        for k, v in params.items()
        if k != "lm_head"
        for v in (jax.tree.leaves(v) if isinstance(v, dict) else [v])
    )
    return da, db, shared


def _service(ckpt_dir: str, extra: str = ""):
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    args = parse_engine_options(
        f"--model tiny --num-pages 32 --page-size 8 --max-batch 2 "
        f"--max-model-len 64 --swap-bucket-mib 1 "
        f"--checkpoint-dir {ckpt_dir} {extra}"
    )
    return EngineService(args)


def _gen(svc):
    return svc.submit([1, 2, 3], 4, 0.0).result(timeout=120).out_tokens


def test_service_sibling_variant_swap_moves_only_the_delta(
    variant_ckpts, numpy_host_staging
):
    """POST /v1/swap between two fine-tune variants: the shared tensors
    are content-matched away (< 50% of full-swap bytes move), generations
    stay bit-exact per variant, and the pooled pair dedupes in host RAM."""
    da, db, shared = variant_ckpts
    svc = _service(da)
    try:
        gold_a = _gen(svc)

        # cold build of variant B: full transfer, manifest digests loaded
        out = svc.swap("tiny", checkpoint_dir=db)
        assert out["swapped"] and not out["pool_hit"]
        assert out["tier"] == "cold" and out["bytes_deduped"] == 0
        gold_b = _gen(svc)
        assert gold_b != gold_a

        # swap back to A: pool hit + DELTA — only lm_head (and kv) moves
        out = svc.swap("tiny", checkpoint_dir=da)
        assert out["pool_hit"] and out["tier"] == "pool"
        assert out["bytes_deduped"] >= 2 * shared > 0
        full = out["bytes_out"] + out["bytes_in"]
        assert out["bytes_moved"] < 0.5 * full, (
            f"delta swap moved {out['bytes_moved']} of {full}"
        )
        assert _gen(svc) == gold_a, "delta swap changed the numerics"

        # and forward again: sibling delta in the other direction
        out = svc.swap("tiny", checkpoint_dir=db)
        assert out["pool_hit"] and out["bytes_moved"] < 0.5 * (
            out["bytes_out"] + out["bytes_in"]
        )
        assert _gen(svc) == gold_b

        # park B too (swap to a third model): both variants pooled — the
        # shared base is held ONCE (dedup visible in the pool stats)
        svc.swap("tiny-gemma")
        pool = svc.model_pool.describe()
        assert set(f"tiny@{d}" for d in (da, db)) <= set(pool["models"])
        assert pool["chunks"]["dedup_saved_bytes"] >= shared
        nb = {e["model_id"]: e["nbytes"] for e in pool["entries"]}
        both = nb[f"tiny@{da}"] + nb[f"tiny@{db}"]
        assert pool["bytes_used"] <= both - shared, (
            "two pooled siblings must occupy less than the sum of their "
            "nominal sizes"
        )

        # tier + delta metrics exported on a /metrics scrape
        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from llm_d_fast_model_actuation_tpu.engine.server import build_app

        async def scrape():
            client = TestClient(TestServer(build_app(svc)))
            await client.start_server()
            try:
                r = await client.get("/metrics")
                return await r.text()
            finally:
                await client.close()

        text = asyncio.run(scrape())
        assert 'fma_engine_model_pool_tier_bytes{tier="host"}' in text
        assert 'fma_engine_model_pool_tier_chunks{tier="host"}' in text
        assert "fma_engine_model_pool_dedup_saved_bytes" in text
        assert 'fma_engine_swap_delta_bytes{kind="deduped",model="tiny"}' in text
        saved = [
            ln for ln in text.splitlines()
            if ln.startswith("fma_engine_model_pool_dedup_saved_bytes ")
        ]
        assert saved and float(saved[0].split()[-1]) >= shared
    finally:
        svc.shutdown()


def test_service_trace_has_delta_span(variant_ckpts):
    da, db, _ = variant_ckpts
    from llm_d_fast_model_actuation_tpu.utils import tracing

    svc = _service(da)
    try:
        svc.swap("tiny", checkpoint_dir=db)
        tracing.clear()
        out = svc.swap("tiny", checkpoint_dir=da)
        assert out["bytes_deduped"] > 0
        spans = [s for s in tracing.snapshot() if s.name == "swap.delta"]
        assert len(spans) == 1
        assert spans[0].attrs["bytes_deduped"] == out["bytes_deduped"]
        assert spans[0].attrs["bytes_moved"] == out["bytes_moved"]
        assert spans[0].attrs["leaves_shared"] == out["deduped_leaves"]
    finally:
        svc.shutdown()


def test_service_disk_tier_rebuild_after_eviction(
    variant_ckpts, tmp_path, numpy_host_staging
):
    """An evicted model whose chunks spilled to the disk tier swaps back
    bit-exact with ZERO checkpoint re-reads — the checkpoint directory is
    deleted out from under it to prove the bytes came from the tier."""
    da, db, _ = variant_ckpts
    ckpt_copy = str(tmp_path / "ckpt-a-copy")
    shutil.copytree(da, ckpt_copy)
    disk = str(tmp_path / "pool-tier")
    svc = _service(ckpt_copy, extra=f"--pool-disk-dir {disk} --pool-disk-mib 64")
    try:
        gold = _gen(svc)
        svc.swap("tiny", checkpoint_dir=db)  # parks A in the pool
        # evict everything: chunks spill to the disk tier, manifests stay
        svc._free_pooled(svc.model_pool.drain(), "test eviction")
        assert svc.model_pool.staged_keys() == [f"tiny@{ckpt_copy}"]
        assert os.listdir(disk), "eviction must spill chunks to disk"
        shutil.rmtree(ckpt_copy)  # no checkpoint to re-read

        out = svc.swap("tiny", checkpoint_dir=ckpt_copy)
        assert out["swapped"] and out["tier"] == "disk"
        assert not out["pool_hit"]
        assert _gen(svc) == gold, "disk-tier rebuild not bit-exact"
    finally:
        svc.shutdown()


def test_chip_ledger_tracks_pool_summaries():
    """The launcher ledger keeps each holder's tiered-pool shape from
    swap/prefetch answers — the one-call view a multi-model scheduler
    reads — and drops it with the chip hold."""
    from llm_d_fast_model_actuation_tpu.launcher.manager import ChipLedger

    led = ChipLedger()
    led.acquire("i1", ["c0", "c1"])
    pool = {
        "models": ["tiny@a", "tiny@b"],
        "bytes_used": 1000,
        "budget_bytes": 4096,
        "staged_manifests": ["old@c"],
        "chunks": {"dedup_saved_bytes": 400, "disk_bytes": 77},
    }
    led.set_pool("i1", pool)
    got = led.pools()["i1"]
    assert got["models"] == ["tiny@a", "tiny@b"]
    assert got["dedup_saved_bytes"] == 400 and got["disk_bytes"] == 77
    assert got["staged_manifests"] == ["old@c"]
    # a pool-less answer keeps the last known summary; unknown holders
    # and None are ignored
    led.set_pool("i1", None)
    led.set_pool("ghost", pool)
    assert "i1" in led.pools() and "ghost" not in led.pools()
    led.release("i1")
    assert led.pools() == {}


# -- sharded meshes: mesh-qualified digests + delta swap ----------------------


def test_service_sibling_delta_swap_tp2_mesh(variant_ckpts, numpy_host_staging):
    """The mesh parity bar (ROADMAP item 4): a sibling pool-hit swap on
    a single-process tp=2 CPU mesh content-matches the shared tensors
    away — < 50% of full-swap bytes move, generations stay bit-exact on
    both sides — and every digest is mesh-qualified (content + mesh
    shape + per-leaf sharding spec), so sharded identity can never
    collide with a single-device entry of the same bytes."""
    da, db, shared = variant_ckpts
    svc = _service(da, extra="--tensor-parallel-size 2")
    try:
        assert svc._content_hash, "content hashing must be ON for tp=2"
        gold_a = _gen(svc)

        dg = svc._current_runtime().digests
        assert dg and all(v.startswith("m:") for v in dg.values())
        # qualified digests still carry the verifiable content suffix
        from llm_d_fast_model_actuation_tpu.engine.chunk_store import (
            digest_content_hash,
        )

        assert all(
            len(digest_content_hash(v)) == 64 and ":" not in
            digest_content_hash(v)
            for v in dg.values()
        )

        out = svc.swap("tiny", checkpoint_dir=db)  # cold: parks A
        assert out["swapped"] and out["tier"] == "cold"
        gold_b = _gen(svc)
        assert gold_b != gold_a

        out = svc.swap("tiny", checkpoint_dir=da)  # sibling pool hit
        assert out["pool_hit"] and out["tier"] == "pool"
        assert out["bytes_deduped"] >= 2 * shared > 0
        full = out["bytes_out"] + out["bytes_in"]
        assert out["bytes_moved"] < 0.5 * full, (
            f"tp=2 delta swap moved {out['bytes_moved']} of {full}"
        )
        assert _gen(svc) == gold_a, "tp=2 delta swap changed the numerics"

        out = svc.swap("tiny", checkpoint_dir=db)  # and back
        assert out["pool_hit"] and out["bytes_moved"] < 0.5 * (
            out["bytes_out"] + out["bytes_in"]
        )
        assert _gen(svc) == gold_b

        # both siblings pooled: the shared base dedupes on the mesh too
        svc.swap("tiny-gemma")
        pool = svc.model_pool.describe()
        assert pool["chunks"]["dedup_saved_bytes"] >= shared
    finally:
        svc.shutdown()


def test_service_delta_swap_rollback_tp2_mesh(variant_ckpts):
    """A mid-transfer fault during a tp=2 sibling delta swap rolls back
    with BOTH models bit-exact: the outgoing model keeps serving its
    exact weights, the incoming pool entry is re-pooled intact, and the
    retried swap completes bit-exact."""
    from llm_d_fast_model_actuation_tpu.engine.sleep import SwapRolledBack

    da, db, _ = variant_ckpts
    svc = _service(da, extra="--tensor-parallel-size 2")
    try:
        gold_a = _gen(svc)
        svc.swap("tiny", checkpoint_dir=db)  # parks A
        gold_b = _gen(svc)

        faults.arm("swap.h2d", mode="fail", count=1)
        with pytest.raises(SwapRolledBack):
            svc.swap("tiny", checkpoint_dir=da)
        assert svc.degraded  # visible, but still serving
        assert _gen(svc) == gold_b, "outgoing mesh model corrupted"

        out = svc.swap("tiny", checkpoint_dir=da)  # retry: pool intact
        assert out["pool_hit"]
        assert _gen(svc) == gold_a, "re-pooled mesh entry corrupted"
        assert svc.degraded is None  # committed swap clears the marker
    finally:
        svc.shutdown()


def test_service_disk_tier_rebuild_tp2_mesh(
    variant_ckpts, tmp_path, numpy_host_staging
):
    """Mesh restart-shape: an evicted tp=2 model rebuilds bit-exact from
    the disk tier under its shard-qualified digests, checkpoint deleted
    (content re-verification covers the qualified digest's content
    suffix)."""
    da, db, _ = variant_ckpts
    ckpt_copy = str(tmp_path / "ckpt-a-tp2")
    shutil.copytree(da, ckpt_copy)
    disk = str(tmp_path / "pool-tier-tp2")
    svc = _service(
        ckpt_copy,
        extra=f"--tensor-parallel-size 2 --pool-disk-dir {disk} "
        "--pool-disk-mib 64",
    )
    try:
        gold = _gen(svc)
        svc.swap("tiny", checkpoint_dir=db)
        svc._free_pooled(svc.model_pool.drain(), "test eviction")
        assert os.listdir(disk), "mesh eviction must spill chunks"
        shutil.rmtree(ckpt_copy)

        out = svc.swap("tiny", checkpoint_dir=ckpt_copy)
        assert out["swapped"] and out["tier"] == "disk"
        assert _gen(svc) == gold, "tp=2 disk-tier rebuild not bit-exact"
    finally:
        svc.shutdown()


# -- the backend's own staging ------------------------------------------------
#
# Where the backend has a ``pinned_host`` memory space (the TPU, and this
# jax's CPU) a slept model's host state is jax arrays in that space, which
# ``model_pool.intern_tree`` leaves alone: the delta swap works from the
# digests all the same, but the ChunkStore, its dedup and its disk tier see
# nothing of a model that was served and slept. The tests above that assert
# interning name ``numpy_host_staging``; these pin what the chip runs.


def _own_staging_is_pinned_host():
    if not sleep._platform_supports_host_memory():
        pytest.skip(
            "this backend stages a slept model in numpy: what that does is "
            "what the numpy_host_staging tests assert"
        )


@pytest.mark.parametrize("staging", ["numpy_host_staging", "own_staging"])
def test_host_staging_follows_the_probe_unless_the_fixture_holds(
    staging, request
):
    """The fixture of tests/conftest.py decides the staging of every
    manager built after it; without it a manager stages as the backend's
    probe says."""
    if staging == "numpy_host_staging":
        request.getfixturevalue(staging)
        want_memory_kind = False
    else:
        want_memory_kind = sleep._platform_supports_host_memory()
    mgr, _ = _mgr(_variant_params(0, perturb=False), kv_seed=1)
    assert mgr._use_memory_kind is want_memory_kind
    mgr.sleep(1)
    for leaf in jax.tree.leaves(mgr._host_state):
        if want_memory_kind:
            assert isinstance(leaf, jax.Array)
            assert leaf.sharding.memory_kind == "pinned_host"
        else:
            assert isinstance(leaf, np.ndarray)


@pytest.mark.parametrize(
    "extra", ["", "--tensor-parallel-size 2"], ids=["single", "tp2_mesh"]
)
def test_service_sibling_swap_on_own_staging(variant_ckpts, extra):
    """A -> B -> A between siblings is a delta swap whatever the staging
    (the digests ride the runtimes, not the store): under half the bytes
    move and both generate bit-exact. But nothing of either slept model
    enters the ChunkStore: no dedup, and each pool entry is charged its
    whole size."""
    _own_staging_is_pinned_host()
    da, db, shared = variant_ckpts
    svc = _service(da, extra=extra)
    try:
        gold_a = _gen(svc)
        out = svc.swap("tiny", checkpoint_dir=db)
        assert out["tier"] == "cold" and out["bytes_deduped"] == 0
        gold_b = _gen(svc)
        assert gold_b != gold_a

        slept = svc.model_pool.peek(f"tiny@{da}").runtime.sleeper
        assert slept._use_memory_kind
        assert all(
            leaf.sharding.memory_kind == "pinned_host"
            for leaf in jax.tree.leaves(slept._host_state)
        )

        out = svc.swap("tiny", checkpoint_dir=da)
        assert out["pool_hit"] and out["tier"] == "pool"
        assert out["bytes_deduped"] >= 2 * shared > 0
        assert out["bytes_moved"] < 0.5 * (out["bytes_out"] + out["bytes_in"])
        assert _gen(svc) == gold_a, "delta swap changed the numerics"
        out = svc.swap("tiny", checkpoint_dir=db)
        assert out["pool_hit"] and out["bytes_moved"] < 0.5 * (
            out["bytes_out"] + out["bytes_in"]
        )
        assert _gen(svc) == gold_b

        svc.swap("tiny-gemma")  # parks B beside A
        pool = svc.model_pool.describe()
        nb = {e["model_id"]: e for e in pool["entries"]}
        assert set(nb) == {f"tiny@{da}", f"tiny@{db}"}
        assert all(e["resident_bytes"] == e["nbytes"] for e in nb.values())
        assert pool["bytes_used"] == sum(e["nbytes"] for e in nb.values())
        assert pool["chunks"]["host_chunks"] == 0
        assert pool["chunks"]["dedup_saved_bytes"] == 0
    finally:
        svc.shutdown()


def test_service_eviction_on_own_staging_leaves_no_manifest(
    variant_ckpts, tmp_path
):
    """An evicted model that was slept in ``pinned_host`` spills nothing and
    leaves no manifest (every chunk of it would be a miss): the swap back is
    priced and served as a cold load, never from the disk tier."""
    _own_staging_is_pinned_host()
    da, db, _ = variant_ckpts
    disk = str(tmp_path / "pool-tier")
    svc = _service(da, extra=f"--pool-disk-dir {disk} --pool-disk-mib 64")
    try:
        gold = _gen(svc)
        svc.swap("tiny", checkpoint_dir=db)  # parks A in the pool
        svc._free_pooled(svc.model_pool.drain(), "test eviction")
        assert svc.model_pool.staged_keys() == []
        assert not os.path.isdir(disk) or not os.listdir(disk)

        assert svc.price_swap("tiny", da)["tier"] == "cold"
        out = svc.swap("tiny", checkpoint_dir=da)
        assert out["swapped"] and out["tier"] == "cold"
        assert not out["pool_hit"] and out["bytes_deduped"] == 0
        pool = svc.model_pool.describe()
        assert pool["staged_hits"] == 0 and pool["chunks"]["disk_hits"] == 0
        assert _gen(svc) == gold
    finally:
        svc.shutdown()


def test_service_content_hash_off_disables_delta(variant_ckpts):
    da, db, _ = variant_ckpts
    svc = _service(da, extra="--content-hash off")
    try:
        assert svc.model_pool.chunks is None
        svc.swap("tiny", checkpoint_dir=db)
        out = svc.swap("tiny", checkpoint_dir=da)
        assert out["pool_hit"] and out["bytes_deduped"] == 0
        assert out["bytes_moved"] == out["bytes_out"] + out["bytes_in"]
    finally:
        svc.shutdown()
