"""Compressed actuation transfers (--sleep-quant, models/quant.py +
engine/sleep.py): int8/fp8 sleep/wake/swap payloads with on-device dequant.

Pins the numerics contract (docs/perf.md "Compressed actuation"):

  * bit-exact default: with the mode off nothing changes, wire == full;
  * lossy-ONCE: the first quantized offload rounds the weights, every
    later cycle reproduces the exact same post-quantization bits (cached
    int8 scales / pure-dtype fp8 round trip);
  * transactional: a mid-transfer fault during a quantized swap rolls
    back with BOTH models bit-exact — the quantized staging copy never
    overwrites a full-precision slept state, and rolled-back outgoing
    leaves re-upload + dequantize to their exact pre-swap bits;
  * capacity: quantized entries pool at payload bytes (~2x models/GiB),
    and the prefetch admission estimate agrees (no 2x over-reserve).
"""

import jax
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine.chunk_store import digest_tree
from llm_d_fast_model_actuation_tpu.engine.sleep import (
    SleepManager,
    SwapRolledBack,
    swap_states,
)
from llm_d_fast_model_actuation_tpu.models import quant
from llm_d_fast_model_actuation_tpu.utils import faults

pytestmark = pytest.mark.quantswap


@pytest.fixture(autouse=True)
def _clean_faults():
    faults.reset()
    yield
    faults.reset()


def _params(seed: int, dtype=np.float32, perturb: bool = False):
    """A llama-shaped host tree: quantizable layer stacks + hot-head
    leaves (embed / final_norm / lm_head) + a norm stack that must never
    quantize."""
    rng = np.random.default_rng(seed)
    p = {
        "embed": rng.standard_normal((64, 32)).astype(dtype),
        "layers": {
            "wq": rng.standard_normal((2, 32, 32)).astype(dtype),
            "w_up": rng.standard_normal((2, 32, 64)).astype(dtype),
            "attn_norm": rng.standard_normal((2, 32)).astype(dtype),
        },
        "final_norm": rng.standard_normal((32,)).astype(dtype),
        "lm_head": rng.standard_normal((32, 64)).astype(dtype),
    }
    if perturb:
        p["lm_head"] = (p["lm_head"] * 1.5 + 0.25).astype(dtype)
    return p


def _mgr(params, kv_seed: int, **kw):
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
        lambda: box["state"],
        lambda s: box.__setitem__("state", s),
        **kw,
    )
    return mgr, box


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def _bits(a: np.ndarray) -> np.ndarray:
    return np.asarray(a).view(np.uint8)


# -- primitives ---------------------------------------------------------------


def test_transfer_quant_plan_eligibility():
    state = {"params": _params(0), "kv": (np.zeros((2, 4), np.float32),)}
    from jax.tree_util import tree_flatten_with_path

    flat, _ = tree_flatten_with_path(state)
    names = ["/".join(str(getattr(k, "key", k)) for k in p) for p, _ in flat]

    plan = quant.transfer_quant_plan(state, hot_head=True)
    by_name = dict(zip(names, plan))
    assert by_name["params/layers/wq"] and by_name["params/layers/w_up"]
    # hot head + norms + 1-D + KV never quantize with the default head
    for n, v in by_name.items():
        if n.startswith("kv") or n in (
            "params/embed", "params/lm_head", "params/final_norm",
            "params/layers/attn_norm",
        ):
            assert not v, n

    plan2 = quant.transfer_quant_plan(state, hot_head=False)
    by_name2 = dict(zip(names, plan2))
    assert by_name2["params/embed"] and by_name2["params/lm_head"]
    assert not by_name2["params/layers/attn_norm"]  # norms stay fp always
    assert not by_name2["params/final_norm"]  # 1-D


@pytest.mark.parametrize("dtype", [np.float32, "bfloat16"])
def test_int8_requantization_is_bit_idempotent(dtype):
    """dequant(quant(w)) re-quantized with the CACHED scale reproduces the
    payload exactly, and a second dequant reproduces the weights exactly —
    the lossy-once contract, in both f32 and bf16."""
    import ml_dtypes

    dt = ml_dtypes.bfloat16 if dtype == "bfloat16" else dtype
    w = np.random.default_rng(0).standard_normal((4, 16, 8)).astype(dt)
    p1, m1 = quant.quantize_leaf_np(w, "int8")
    w1 = quant.dequantize_leaf_np(p1, m1)
    p2, m2 = quant.quantize_leaf_np(w1, "int8", scale=m1.scale)
    assert np.array_equal(p1, p2)
    w2 = quant.dequantize_leaf_np(p2, m2)
    assert np.array_equal(_bits(w1), _bits(w2))
    # device and host paths produce identical payloads for identical bits
    pd, md = quant.quantize_leaf(jax.device_put(w), "int8")
    assert np.array_equal(np.asarray(pd), p1)
    assert np.array_equal(md.scale, m1.scale)


def test_fp8_round_trip_idempotent_and_half_bytes():
    import ml_dtypes

    w = np.random.default_rng(1).standard_normal((2, 8, 8)).astype(
        ml_dtypes.bfloat16
    )
    p, m = quant.quantize_leaf_np(w, "fp8")
    assert p.dtype == quant.fp8_dtype() and m.scale is None
    assert p.nbytes == w.nbytes // 2
    w1 = quant.dequantize_leaf_np(p, m)
    p2, _ = quant.quantize_leaf_np(w1, "fp8")
    assert np.array_equal(_bits(p), _bits(p2))


def test_transfer_digest_space_is_disjoint_from_content_digests():
    """A payload's transfer digest must never collide with the plain
    content-digest namespace (a quantized chunk handed out as the fp
    tensor it approximates would be silent corruption)."""
    from llm_d_fast_model_actuation_tpu.engine.chunk_store import leaf_digest

    w = np.random.default_rng(2).standard_normal((8, 8)).astype(np.float32)
    p, m = quant.quantize_leaf_np(w, "int8")
    td = quant.transfer_digest(p, m)
    assert td.startswith("q:")
    assert td != leaf_digest(w) and td != leaf_digest(p)
    # scale participates: same payload, different scale = different chunk
    m2 = quant.TransferQuant(
        mode="int8", orig_dtype=m.orig_dtype, scale=m.scale * 2
    )
    assert quant.transfer_digest(p, m2) != td


# -- SleepManager level -------------------------------------------------------


@pytest.mark.parametrize("mode", ["int8", "fp8"])
def test_quantized_sleep_halves_host_bytes_and_cycles_bit_stable(mode):
    import ml_dtypes

    m, box = _mgr(
        _params(0, dtype=ml_dtypes.bfloat16), kv_seed=1, quant_mode=mode
    )
    info = m.sleep(1)
    assert info["quant"] == mode
    assert info["bytes_offloaded"] < info["bytes_offloaded_full"]
    # the quantizable layer stacks dominate this tree: real savings
    assert info["bytes_offloaded"] < 0.85 * info["bytes_offloaded_full"]
    m.wake_up()
    first = _leaves(box["state"])
    # weights changed once (lossy), dtype/shape preserved
    assert all(
        a.dtype == b.dtype and a.shape == b.shape
        for a, b in zip(first, _leaves(box["state"]))
    )
    # every later cycle is bit-stable (cached scales / fp8 round trip)
    m.sleep(1)
    m.wake_up()
    second = _leaves(box["state"])
    for a, b in zip(first, second):
        assert np.array_equal(_bits(a), _bits(b))


def test_quantized_release_sleep_round_trip():
    """Device-release sleep with quant: numpy payload staging survives the
    client teardown, wake dequantizes on the fresh client."""
    m, box = _mgr(_params(3), kv_seed=2, quant_mode="int8")
    info = m.sleep(1, release=True)
    assert m._released and info["quant"] == "int8"
    m.wake_up()
    first = _leaves(box["state"])
    m.sleep(1, release=True)
    m.wake_up()
    for a, b in zip(first, _leaves(box["state"])):
        assert np.array_equal(_bits(a), _bits(b))


def test_escalation_drops_quant_metadata():
    m, _ = _mgr(_params(4), kv_seed=2, quant_mode="int8")
    m.sleep(1)
    assert m._quant_meta is not None
    m.sleep(2)  # escalate: host RAM (payloads + metadata) freed
    assert m._quant_meta is None and m._quant_scales is None
    assert m._host_state is None


# -- swap_states level --------------------------------------------------------


def test_quantized_swap_moves_fewer_bytes_both_directions():
    """Outgoing quantizes on device, incoming slept-quantized moves its
    payload: wire bytes in both directions under the full-precision
    total."""
    ma, _ = _mgr(_params(1), kv_seed=1, quant_mode="int8")
    mb, bb = _mgr(_params(2), kv_seed=2, quant_mode="int8")
    mb.sleep(1)  # slept quantized (payload host state)
    out = swap_states(ma, mb, bucket_bytes=4096, quant="int8")
    assert out["quant"] == "int8" and out["quant_leaves"] > 0
    assert out["bytes_out"] + out["bytes_in"] < out["bytes_full"]
    assert out["bytes_saved_quant"] > 0
    assert ma.is_sleeping and not mb.is_sleeping
    assert ma.quant_state() == "int8"
    # the woken model's weights are plain full-precision arrays
    for x in jax.tree.leaves(bb["state"]):
        assert x.dtype != np.int8


def test_quantized_swap_of_fp_entry_stages_copy_and_wakes_dequantized():
    """A full-precision pool entry under quant mode transfers via a
    host-side quantized staging copy; the woken weights equal
    dequant(quant(fp)) and the fp host state was consumed only at
    commit."""
    ma, _ = _mgr(_params(1), kv_seed=1, quant_mode="int8")
    mb, bb = _mgr(_params(2), kv_seed=2)  # NO quant mode: fp slept state
    mb.sleep(1)
    fp_before = _leaves(mb._host_state)
    out = swap_states(ma, mb, bucket_bytes=4096, quant="int8")
    assert out["quant"] == "int8" and out["bytes_saved_quant"] > 0
    woken = _leaves(bb["state"])
    # quantized leaves: equal to the host-side round trip of the fp state
    state_shape = {"params": _params(2), "kv": (fp_before[-2], fp_before[-1])}
    plan = quant.transfer_quant_plan(state_shape)
    changed = sum(
        1
        for q, a, b in zip(plan, woken, fp_before)
        if q and not np.array_equal(a, b)
    )
    assert changed > 0, "quantized transfer should round the weights"
    for q, a, b in zip(plan, woken, fp_before):
        if not q:
            assert np.array_equal(a, b), "unquantized leaf must move exact"
        else:
            p, m = quant.quantize_leaf_np(b, "int8")
            assert np.array_equal(a, quant.dequantize_leaf_np(p, m))


def test_quantized_swap_rollback_both_models_bit_exact():
    """THE transactional contract under quant (ISSUE satellite): fault the
    incoming transfer mid-swap — the fp slept entry is untouched by its
    quantized staging copy, and the outgoing model (already on the
    quantized contract from a previous cycle) comes back bit-exact from
    payload re-upload + on-device dequant."""
    ma, ba = _mgr(_params(1), kv_seed=1, quant_mode="int8")
    # pre-cycle: outgoing joins the lossy-once contract (its live weights
    # are post-quantization bits; later cycles are exact)
    ma.sleep(1)
    ma.wake_up()
    awake_before = _leaves(ba["state"])
    mb, _ = _mgr(_params(2), kv_seed=2)
    mb.sleep(1)  # full-precision slept entry
    slept_before = _leaves(mb._host_state)

    # overlapped=False: every outgoing bucket lands (and its HBM is freed
    # eagerly) before the first incoming bucket — the rollback must
    # re-upload quantized payloads, the hardest path
    faults.arm("swap.h2d", mode="fail", count=1)
    with pytest.raises(SwapRolledBack):
        swap_states(
            ma, mb, bucket_bytes=2048, overlapped=False, quant="int8"
        )
    for got, want in zip(_leaves(ba["state"]), awake_before):
        assert np.array_equal(_bits(got), _bits(want)), (
            "outgoing model not bit-exact after quantized rollback"
        )
    for got, want in zip(_leaves(mb._host_state), slept_before):
        assert np.array_equal(_bits(got), _bits(want)), (
            "fp slept entry corrupted by its quantized staging copy"
        )
    assert not ma.is_sleeping and mb.is_sleeping
    assert mb._quant_meta is None  # still a full-precision entry


def test_quant_composes_with_delta_swap():
    """Digest-matched sibling leaves skip both directions entirely; only
    the quantized delta crosses."""
    pa = _params(7, perturb=False)
    pb = _params(7, perturb=True)  # same bits except lm_head
    dga, dgb = digest_tree(pa), digest_tree(pb)
    ma, _ = _mgr(pa, kv_seed=1, quant_mode="int8")
    mb, _ = _mgr(pb, kv_seed=2, quant_mode="int8")
    mb.sleep(1)
    out = swap_states(
        ma, mb, bucket_bytes=4096,
        out_digests=dga, in_digests=dgb, quant="int8",
    )
    # embed / wq / w_up / attn_norm / final_norm shared; lm_head + kv move
    assert out["deduped_leaves"] >= 3
    assert out["bytes_deduped"] > 0
    assert out["bytes_moved"] < out["bytes_out"] + out["bytes_in"] + 1
    assert out["quant"] == "int8" and out["bytes_saved_quant"] > 0


def test_delta_matches_quantized_slept_entry_by_origin_dtype():
    """A quantized-slept incoming leaf carries int8 bits but its digest
    names the fp origin: the dtype check must compare against the origin
    dtype, or siblings would never dedupe under quant."""
    pa = _params(9)
    dg = digest_tree(pa)
    ma, _ = _mgr(pa, kv_seed=1, quant_mode="int8")
    mb, _ = _mgr(_params(9), kv_seed=2, quant_mode="int8")
    mb.sleep(1)  # payload host state, fp digests
    out = swap_states(
        ma, mb, out_digests=dg, in_digests=dg, quant="int8"
    )
    assert out["deduped_leaves"] >= 5, out


def test_rollback_of_first_quantized_offload_keeps_scales():
    """A rolled-back FIRST quantized swap already rounded the re-uploaded
    outgoing leaves; the scales it used must be cached so the next
    offload reproduces identical bits (no second lossy step from a
    recomputed, bf16-perturbed scale)."""
    import ml_dtypes

    ma, ba = _mgr(
        _params(11, dtype=ml_dtypes.bfloat16), kv_seed=1, quant_mode="int8"
    )
    assert ma._quant_scales is None  # never quantized yet
    mb, _ = _mgr(_params(12, dtype=ml_dtypes.bfloat16), kv_seed=2)
    mb.sleep(1)
    faults.arm("swap.h2d", mode="fail", count=1)
    with pytest.raises(SwapRolledBack):
        swap_states(ma, mb, bucket_bytes=2048, overlapped=False, quant="int8")
    assert ma._quant_scales is not None, "rollback must cache the scales"
    rolled = _leaves(ba["state"])
    ma.sleep(1)
    ma.wake_up()
    for a, b in zip(rolled, _leaves(ba["state"])):
        assert np.array_equal(_bits(a), _bits(b)), (
            "post-rollback cycle not bit-stable"
        )


def test_quant_digest_chunks_spill_content_verified(tmp_path):
    """Transfer-digest ("q:") chunks spill to the disk tier like fp
    digests: the spill header's ``content`` field (leaf_digest of the
    payload bytes, written by the process holding the genuine chunk)
    restores a content-verified reload even though the q: digest itself
    is not recomputable from the blob. Both schemes round-trip."""
    from llm_d_fast_model_actuation_tpu.engine.chunk_store import (
        ChunkStore,
        digest_spillable,
        leaf_digest,
    )

    disk = str(tmp_path / "tier")
    store = ChunkStore(disk_dir=disk, disk_budget_bytes=1 << 20)
    arr = np.random.default_rng(0).standard_normal((8, 8)).astype(np.float32)
    p, m = quant.quantize_leaf_np(arr, "int8")
    qd = quant.transfer_digest(p, m)
    fd = leaf_digest(arr)
    assert digest_spillable(qd) and digest_spillable(fd)
    store.intern(qd, p)
    store.intern(fd, arr)
    assert store.release(qd, spill=True) == p.nbytes
    assert store.release(fd, spill=True) == arr.nbytes
    import os

    files = os.listdir(disk)
    assert len(files) == 2, f"both chunk schemes spill now, got {files}"
    got_fp = store.fetch(fd)
    assert got_fp is not None and np.array_equal(got_fp, arr)
    got_q = store.fetch(qd)  # content-verified reload via header field
    assert got_q is not None and np.array_equal(got_q, p)
    assert store.verify_failures == 0


# -- estimate / admission (ISSUE satellite) -----------------------------------


def test_estimate_param_bytes_quant_aware():
    from llm_d_fast_model_actuation_tpu.models import hf as hf_models
    from llm_d_fast_model_actuation_tpu.models import llama

    cfg = llama.LlamaConfig.tiny()
    est_fp = hf_models.estimate_param_bytes(cfg)
    est_q = hf_models.estimate_param_bytes(cfg, transfer_quant="int8")
    est_q_nohead = hf_models.estimate_param_bytes(
        cfg, transfer_quant="int8", hot_head=False
    )
    assert est_q < est_fp, "int8 staging must not reserve fp bytes"
    assert est_q_nohead < est_q, "quantizing the head saves more"
    # the quantizable stacks dominate tiny: the estimate must reflect a
    # real (not cosmetic) reduction
    assert est_q < 0.85 * est_fp
    assert hf_models.estimate_param_bytes(cfg, transfer_quant="off") == est_fp


def test_quantized_prefetch_admission_does_not_over_reserve(tmp_path):
    """A model whose int8-staged footprint fits the pool budget but whose
    fp footprint does not must be admitted under --sleep-quant int8 and
    rejected without it — the no-2x-over-reserve satellite."""
    import time

    from conftest import build_sharded_hf_model_dir

    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )
    from llm_d_fast_model_actuation_tpu.models import hf as hf_models

    d = build_sharded_hf_model_dir(str(tmp_path / "m"))
    cfg = hf_models.config_from_hf(d)
    est_fp = hf_models.estimate_param_bytes(cfg)
    est_q = hf_models.estimate_param_bytes(cfg, transfer_quant="int8")
    budget = (est_fp + est_q) // 2  # fits quantized, not full precision

    base = (
        "--model tiny --num-pages 8 --page-size 8 --max-batch 2 "
        "--max-model-len 32 --model-pool-mib 512 --content-hash off "
    )
    svc = EngineService(parse_engine_options(base))
    try:
        svc.model_pool.budget_bytes = budget
        with pytest.raises(ValueError, match="exceeds"):
            svc.prefetch(f"hf:{d}")
    finally:
        svc.shutdown()

    svc = EngineService(parse_engine_options(base + "--sleep-quant int8"))
    try:
        svc.model_pool.budget_bytes = budget
        svc.prefetch(f"hf:{d}")
        deadline = time.monotonic() + 120
        while (
            svc.last_prefetch.get("state") == "running"
            and time.monotonic() < deadline
        ):
            time.sleep(0.05)
        assert svc.last_prefetch["state"] == "completed", svc.last_prefetch
        assert svc.last_prefetch["quant"] == "int8"
        staged = svc.last_prefetch["bytes"]
        assert staged <= budget, "staged payload must fit the budget"
        # the estimate is honest: within 25% of the actual staged bytes
        assert abs(staged - est_q) <= 0.25 * est_q, (staged, est_q)
        # and the consuming swap serves the dequantized model
        out = svc.swap(f"hf:{d}")
        assert out["pool_hit"] and out["prefetched"]
        req = svc.submit([1, 2, 3], 2, 0.0).result(timeout=120)
        assert len(req.out_tokens) == 2
    finally:
        svc.shutdown()


# -- engine service level -----------------------------------------------------


def _service(extra: str = ""):
    from llm_d_fast_model_actuation_tpu.engine.server import (
        EngineService,
        parse_engine_options,
    )

    return EngineService(
        parse_engine_options(
            "--model tiny --num-pages 8 --page-size 8 --max-batch 2 "
            "--max-model-len 64 --swap-bucket-mib 1 --model-pool-mib 512 "
            "--content-hash off " + extra
        )
    )


def _gen(svc, n=4):
    return svc.submit([1, 2, 3], n, 0.0).result(timeout=120).out_tokens


def test_service_quantized_swap_cycle_bytes_and_numerics():
    """The acceptance shape: int8 pool-hit swap moves < 0.75x the fp16
    baseline bytes (hot head kept), greedy outputs stay stable across
    cycles, and the response carries the mode."""
    fp = _service()
    try:
        gold = _gen(fp)
        fp.swap("tiny-gemma")
        out_fp = fp.swap("tiny")
        assert out_fp["quant"] == "off"
        assert out_fp["bytes_saved_quant"] == 0
        assert out_fp["bytes_moved"] == out_fp["bytes_full"]
        assert _gen(fp) == gold, "default path must stay bit-exact"
        fp_entry = out_fp["bytes_out"]
    finally:
        fp.shutdown()

    q = _service("--sleep-quant int8")
    try:
        gold_q = _gen(q)
        q.swap("tiny-gemma")
        out_q = q.swap("tiny")  # pool hit: quantized both directions
        assert out_q["quant"] == "int8"
        assert out_q["bytes_saved_quant"] > 0
        assert out_q["bytes_moved"] < 0.75 * out_fp["bytes_moved"]
        # quantized pool entry parked at payload bytes: ~2x models/GiB
        assert out_q["bytes_out"] < 0.75 * fp_entry
        t1 = _gen(q)
        assert t1 == gold_q, "tiny greedy outputs changed under int8"
        q.swap("tiny-gemma")
        out_q2 = q.swap("tiny")
        assert out_q2["quant"] == "int8"
        assert _gen(q) == t1, "outputs drifted across quantized cycles"
    finally:
        q.shutdown()


def test_service_int8_hot_head_off_bytes_drift_and_price():
    """Hot head off, the most bytes int8 saves: a pool-hit swap moves
    under 0.6x its full-precision bytes, the oracle priced exactly the
    bytes that crossed (tier "pool"), and the logprobs of the same greedy
    tokens stay within the documented 0.25 of the full-precision ones."""
    q = _service("--sleep-quant int8 --sleep-quant-hot-head off")
    try:
        gold = q.submit([1, 2, 3], 4, 0.0).result(timeout=120)
        q.swap("tiny-gemma")  # parks tiny quantized
        q.swap("tiny")  # the first cycle primes the bandwidth EWMAs
        q.swap("tiny-gemma")
        pred = q.price_swap("tiny")
        out = q.swap("tiny")
        assert out["quant"] == "int8" and out["pool_hit"]
        assert out["bytes_moved"] < 0.6 * out["bytes_full"], out
        assert pred["tier"] == "pool" and pred["measured"] is True
        assert pred["predicted_bytes"] == out["bytes_moved"]
        rec = out["costs"]
        assert rec["predicted_bytes"] == rec["actual_bytes"], rec
        got = q.submit([1, 2, 3], 4, 0.0).result(timeout=120)
        assert got.out_tokens == gold.out_tokens
        assert len(got.out_logprobs) == len(gold.out_logprobs) == 4
        drift = max(
            abs(a - b) for a, b in zip(got.out_logprobs, gold.out_logprobs)
        )
        assert drift < 0.25, drift
    finally:
        q.shutdown()


def test_service_quant_metrics_and_pool_accounting():
    q = _service("--sleep-quant int8 --sleep-quant-hot-head off")
    try:
        _gen(q)
        q.swap("tiny-gemma")
        q.swap("tiny")
        pool = q.model_pool.describe()
        assert len(pool["models"]) == 1  # tiny-gemma parked quantized

        import asyncio

        from aiohttp.test_utils import TestClient, TestServer

        from llm_d_fast_model_actuation_tpu.engine.server import build_app

        async def scrape():
            client = TestClient(TestServer(build_app(q)))
            await client.start_server()
            try:
                r = await client.get("/metrics")
                return await r.text()
            finally:
                await client.close()

        text = asyncio.run(scrape())
        assert 'fma_engine_actuation_bytes{dir="d2h",mode="int8"}' in text
        assert 'fma_engine_actuation_bytes{dir="h2d",mode="int8"}' in text
        d2h = [
            float(ln.split()[-1])
            for ln in text.splitlines()
            if ln.startswith(
                'fma_engine_actuation_bytes{dir="d2h",mode="int8"}'
            )
        ]
        assert d2h and d2h[0] > 0
        # the swap.quant span rode the trace
        from llm_d_fast_model_actuation_tpu.utils import tracing

        spans = [s for s in tracing.snapshot() if s.name == "swap.quant"]
        assert spans, "quantized swap must emit a swap.quant span"
        assert spans[-1].attrs["mode"] == "int8"
        assert spans[-1].attrs["bytes_saved"] > 0
    finally:
        q.shutdown()


def test_service_quantized_sleep_wake_over_admin_api():
    q = _service("--sleep-quant int8")
    try:
        gold = _gen(q)
        info = q.sleep(1)
        assert info["quant"] == "int8"
        assert info["bytes_offloaded"] < info["bytes_offloaded_full"]
        q.wake_up()
        t1 = _gen(q)
        assert t1 == gold
        # second cycle: stable
        q.sleep(1)
        q.wake_up()
        assert _gen(q) == t1
    finally:
        q.shutdown()


def test_sleep_quant_flag_validation():
    from llm_d_fast_model_actuation_tpu.engine.server import (
        parse_engine_options,
    )

    parse_engine_options("--model tiny --sleep-quant int8")
    parse_engine_options("--model tiny --sleep-quant fp8")
    # single-process tp meshes compose (shard-local quant/dequant)
    parse_engine_options(
        "--model tiny --sleep-quant int8 --tensor-parallel-size 2"
    )
    with pytest.raises(SystemExit):  # argparse rejects unknown choices
        parse_engine_options("--model tiny --sleep-quant int4")
    with pytest.raises(ValueError, match="full-precision serving"):
        parse_engine_options(
            "--model tiny --sleep-quant int8 --quantization int8"
        )
    # multi-host gangs keep their explicit rejection
    with pytest.raises(ValueError, match="multi-host gangs"):
        parse_engine_options(
            "--model tiny --sleep-quant int8 --num-processes 2"
        )


# -- sharded meshes: shard-local quantized transfers --------------------------


def test_service_quantized_swap_cycle_tp2_mesh():
    """Quantized actuation on a single-process tp=2 CPU mesh: the int8
    pool-hit swap moves < 0.75x the fp16 mesh baseline's wire bytes
    (hot head kept; < 0.6x with it off is the bench/CI bar) and repeated
    cycles are bit-stable — the lossy-once cached-scale contract holds
    per shard, because quantization is shard-local and the cached scale
    is reused on every later offload."""
    fp = _service("--tensor-parallel-size 2")
    try:
        gold = _gen(fp)
        fp.swap("tiny-gemma")
        out_fp = fp.swap("tiny")
        assert out_fp["quant"] == "off"
        assert _gen(fp) == gold, "mesh default path must stay bit-exact"
        fp_moved = out_fp["bytes_moved"]
    finally:
        fp.shutdown()

    q = _service("--sleep-quant int8 --tensor-parallel-size 2")
    try:
        gold_q = _gen(q)
        q.swap("tiny-gemma")
        out_q = q.swap("tiny")
        assert out_q["quant"] == "int8"
        assert out_q["bytes_saved_quant"] > 0
        assert out_q["bytes_moved"] < 0.75 * fp_moved, (
            out_q["bytes_moved"], fp_moved,
        )
        t1 = _gen(q)
        assert t1 == gold_q, "tiny greedy outputs changed under mesh int8"
        q.swap("tiny-gemma")
        q.swap("tiny")
        assert _gen(q) == t1, "outputs drifted across mesh quantized cycles"
    finally:
        q.shutdown()


def test_quantized_sleep_wake_idempotent_per_shard_tp2_mesh():
    """Lossy-once ON THE MESH, asserted at the payload-bit level: the
    second quantized offload reproduces the first one's exact int8
    payload bytes (cached shard-local scales), the metadata records each
    sharded leaf's shard view, and wake restores the original
    NamedShardings."""
    import jax
    import numpy as np

    q = _service("--sleep-quant int8 --tensor-parallel-size 2")
    try:
        gold = _gen(q)
        q.sleep(1)
        sleeper = q.sleeper
        metas = sleeper._quant_meta
        assert metas is not None and any(m is not None for m in metas)
        # sharded weight stacks record their shard view
        specs = [m.spec for m in metas if m is not None]
        assert any(s is not None and "'tp'" in s for s in specs), specs
        first = [
            np.asarray(leaf).copy()
            for leaf, m in zip(
                jax.tree.leaves(sleeper._host_state), metas
            )
            if m is not None
        ]
        q.wake_up()
        # weights still sharded over the mesh after the dequant
        wq = q.engine.params["layers"]["wq"]
        assert wq.sharding.num_devices == 2
        t1 = _gen(q)
        assert t1 == gold

        q.sleep(1)
        second = [
            np.asarray(leaf)
            for leaf, m in zip(
                jax.tree.leaves(sleeper._host_state),
                sleeper._quant_meta,
            )
            if m is not None
        ]
        assert len(first) == len(second)
        for a, b in zip(first, second):
            assert a.dtype == np.int8 and np.array_equal(a, b), (
                "per-shard payload bits drifted across cycles"
            )
        q.wake_up()
        assert _gen(q) == t1
    finally:
        q.shutdown()


def test_ledger_tracks_swap_quant_mode():
    from llm_d_fast_model_actuation_tpu.launcher.manager import ChipLedger

    led = ChipLedger()
    led.acquire("i1", ["c0"])
    led.set_quant("i1", "int8")
    led.set_quant("ghost", "fp8")  # unknown holders ignored
    led.set_quant("i1", None)  # None keeps the last known value
    assert led.quants() == {"i1": "int8"}
    led.release("i1")
    assert led.quants() == {}
