"""Chunked sleep transfers, the overlapped swap engine, and the host model
pool — the sleep edge cases the hot-swap path relies on."""

import jax
import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine import EngineConfig, InferenceEngine
from llm_d_fast_model_actuation_tpu.engine.model_pool import HostModelPool
from llm_d_fast_model_actuation_tpu.engine.sleep import (
    SleepLevel,
    SleepManager,
    attach_sleep,
    partition_buckets,
    swap_states,
)
from llm_d_fast_model_actuation_tpu.models import llama


def _tiny_cfg(**kw):
    base = dict(
        model=llama.LlamaConfig.tiny(),
        max_batch=4,
        page_size=8,
        num_pages=64,
        max_seq_len=64,
    )
    base.update(kw)
    return EngineConfig(**base)


def _tree_mgr(seed: int, bucket_bytes=None):
    """A bare SleepManager over a pytree of committed arrays."""
    rng = np.random.default_rng(seed)
    box = {
        "state": jax.device_put(
            {
                "a": rng.standard_normal((64, 32)).astype(np.float32),
                "b": {
                    "w": rng.standard_normal((257,)).astype(np.float32),
                    "k": rng.integers(0, 100, (33, 3)).astype(np.int32),
                },
            },
            jax.devices()[0],
        )
    }
    mgr = SleepManager(
        lambda: box["state"],
        lambda s: box.__setitem__("state", s),
        bucket_bytes=bucket_bytes,
    )
    return mgr, box


def _snapshot(tree):
    return [np.array(x) for x in jax.tree.leaves(tree)]


def _equal(tree, snap) -> bool:
    leaves = jax.tree.leaves(tree)
    return len(leaves) == len(snap) and all(
        np.array_equal(np.asarray(x), s) for x, s in zip(leaves, snap)
    )


# -- bucket partitioning ------------------------------------------------------


def test_partition_buckets():
    assert partition_buckets([], 10) == []
    # None / <= 0 -> whole tree in one bucket (legacy path)
    assert partition_buckets([1, 2, 3], None) == [[0, 1, 2]]
    assert partition_buckets([1, 2, 3], 0) == [[0, 1, 2]]
    # size-bounded, contiguous, order-preserving
    assert partition_buckets([4, 4, 4], 8) == [[0, 1], [2]]
    # an oversized leaf forms its own bucket (leaves are never split)
    assert partition_buckets([100, 1, 1], 8) == [[0], [1, 2]]
    assert partition_buckets([1, 100, 1], 8) == [[0], [1], [2]]
    # every index appears exactly once
    got = [i for b in partition_buckets([3, 9, 1, 7, 2], 10) for i in b]
    assert got == list(range(5))


# -- chunked offload/restore identity ----------------------------------------


def test_chunked_offload_identity_vs_whole_tree():
    """Chunked (many tiny buckets) and whole-tree offload stage bit-exact
    host state, and both wake back to the original arrays."""
    whole, _ = _tree_mgr(0)
    chunked, chunked_box = _tree_mgr(0, bucket_bytes=512)  # forces splits
    snap = _snapshot(chunked_box["state"])

    whole.sleep(1)
    chunked.sleep(1)
    assert whole.stats.bytes_offloaded == chunked.stats.bytes_offloaded > 0
    whole_host = jax.tree.leaves(whole._host_state)
    chunk_host = jax.tree.leaves(chunked._host_state)
    assert all(
        np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(whole_host, chunk_host)
    )
    chunked.wake_up()
    assert chunked.level == SleepLevel.AWAKE
    assert _equal(chunked_box["state"], snap)


def test_chunked_release_wake_restores_bucket_by_bucket():
    """Device-releasing sleep + chunked wake: sharding specs are rebuilt
    on the fresh client and restored bucket-by-bucket, and a real engine's
    generation is bit-identical across the cycle."""
    eng = InferenceEngine(_tiny_cfg(), seed=0)
    gold = eng.generate([[1, 2, 3, 4]], max_new_tokens=6)[0]
    mgr = attach_sleep(eng, bucket_bytes=1024)  # many buckets
    info = mgr.sleep(1, release=True)
    assert mgr._released
    mgr.wake_up()
    assert eng.generate([[1, 2, 3, 4]], max_new_tokens=6)[0] == gold


def test_escalation_frees_staged_multihost_shards(monkeypatch):
    """level-1 -> level-2 escalation must drop the staged per-process
    shards AND their reassembly metadata (they are host RAM the caller
    asked to give back)."""
    mgr, box = _tree_mgr(3)
    snap = _snapshot(box["state"])
    # pretend to be one process of a gang: sleep takes the staged path
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    mgr.sleep(1)
    assert mgr._staged is not None and mgr._staged_meta is not None
    assert mgr._treedef is not None
    mgr.sleep(2)  # escalate
    assert mgr._staged is None and mgr._staged_meta is None
    assert mgr._treedef is None
    assert mgr.stats.bytes_offloaded == 0
    monkeypatch.undo()
    # level-2 wake rebuilds via reinit
    mgr.wake_up(
        reinit=lambda: jax.device_put(
            {
                "a": snap[0],
                "b": {"w": snap[2], "k": snap[1]},
            },
            jax.devices()[0],
        )
    )
    assert mgr.level == SleepLevel.AWAKE


def test_multihost_staged_roundtrip_single_process(monkeypatch):
    """The staged (per-process shards) offload restores bit-exact when
    exercised single-process."""
    mgr, box = _tree_mgr(4)
    snap = _snapshot(box["state"])
    monkeypatch.setattr(jax, "process_count", lambda: 2)
    mgr.sleep(1)
    assert mgr._staged is not None
    mgr.wake_up()
    assert _equal(box["state"], snap)


# -- the overlapped swap engine ----------------------------------------------


def test_swap_states_bit_exact_roundtrip():
    mgr_a, box_a = _tree_mgr(10, bucket_bytes=512)
    mgr_b, box_b = _tree_mgr(11, bucket_bytes=512)
    snap_a = _snapshot(box_a["state"])
    snap_b = _snapshot(box_b["state"])

    mgr_b.sleep(1)
    metrics = swap_states(mgr_a, mgr_b, bucket_bytes=512)  # A out, B in
    assert mgr_a.level == SleepLevel.L1_HOST_OFFLOAD
    assert mgr_a.stats.bytes_offloaded > 0
    assert mgr_b.level == SleepLevel.AWAKE
    assert _equal(box_b["state"], snap_b)
    assert metrics["buckets_out"] >= 2 and metrics["buckets_in"] >= 2
    assert metrics["bytes_out"] == sum(s.nbytes for s in snap_a)
    assert metrics["bytes_in"] == sum(s.nbytes for s in snap_b)
    assert 0.0 <= metrics["overlap_frac"] <= 1.0
    assert metrics["peak_bytes_in_flight"] > 0

    swap_states(mgr_b, mgr_a, bucket_bytes=512)  # and back
    assert mgr_a.level == SleepLevel.AWAKE
    assert _equal(box_a["state"], snap_a)


def test_swap_states_sequential_mode_identical_result():
    mgr_a, box_a = _tree_mgr(12, bucket_bytes=512)
    mgr_b, box_b = _tree_mgr(13, bucket_bytes=512)
    snap_b = _snapshot(box_b["state"])
    mgr_b.sleep(1)
    metrics = swap_states(mgr_a, mgr_b, bucket_bytes=512, overlapped=False)
    assert metrics["overlap_s"] == 0.0 or metrics["overlap_frac"] >= 0.0
    assert _equal(box_b["state"], snap_b)
    assert mgr_a.is_sleeping and not mgr_b.is_sleeping


def test_swap_states_engine_level_generation_identity():
    """Two real engines trade the chip repeatedly; each serves bit-exact
    outputs whenever it is the awake one."""
    a = InferenceEngine(_tiny_cfg(), seed=0)
    b = InferenceEngine(_tiny_cfg(), seed=1)
    prompt = [7, 8, 9]
    gold_a = a.generate([prompt], max_new_tokens=8)[0]
    gold_b = b.generate([prompt], max_new_tokens=8)[0]
    assert gold_a != gold_b  # different weights, different outputs
    mgr_a, mgr_b = attach_sleep(a), attach_sleep(b)
    mgr_b.sleep(1)
    for _ in range(2):
        swap_states(mgr_a, mgr_b, bucket_bytes=2048)
        assert b.generate([prompt], max_new_tokens=8)[0] == gold_b
        swap_states(mgr_b, mgr_a, bucket_bytes=2048)
        assert a.generate([prompt], max_new_tokens=8)[0] == gold_a


def test_swap_states_rejects_bad_states():
    mgr_a, _ = _tree_mgr(20)
    mgr_b, _ = _tree_mgr(21)
    with pytest.raises(ValueError):  # B not asleep
        swap_states(mgr_a, mgr_b)
    mgr_b.sleep(2)
    with pytest.raises(ValueError):  # level-2: no host state to stream in
        swap_states(mgr_a, mgr_b)
    mgr_a.sleep(1)
    mgr_c, _ = _tree_mgr(22)
    with pytest.raises(ValueError):  # A asleep: nothing awake to stream out
        swap_states(mgr_a, mgr_c)


# -- host model pool ----------------------------------------------------------


def test_model_pool_lru_budget():
    pool = HostModelPool(budget_bytes=100)
    assert pool.put("a", "rt-a", 40) == []
    assert pool.put("b", "rt-b", 40) == []
    assert pool.models() == ["a", "b"]
    # exceeding the budget evicts the least recently parked
    evicted = pool.put("c", "rt-c", 40)
    assert [e.model_id for e in evicted] == ["a"]
    assert pool.evictions == 1 and pool.bytes_used == 80
    # a hit removes the entry (the caller wakes it)
    hit = pool.take("b")
    assert hit is not None and hit.runtime == "rt-b"
    assert pool.hits == 1 and "b" not in pool
    assert pool.take("zzz") is None and pool.misses == 1
    # re-parking refreshes recency
    pool.put("b", "rt-b2", 40)
    pool.put("c", "rt-c2", 40)  # re-register moves c to MRU
    evicted = pool.put("d", "rt-d", 40)
    assert [e.model_id for e in evicted] == ["b"]
    d = pool.describe()
    assert d["budget_bytes"] == 100 and d["models"] == ["c", "d"]


def test_model_pool_take_match_checkpoint_qualified():
    """A swap request without a checkpoint_dir must find a pooled entry
    keyed with one (most-recent first) — the natural swap-back
    {"model": X} after pooling X@/ckpt."""
    pool = HostModelPool(budget_bytes=100)
    pool.put("m@/ckpt/a", "rt-a", 10)
    pool.put("m@/ckpt/b", "rt-b", 10)
    pool.put("other", "rt-o", 10)
    hit = pool.take_match("m")
    assert hit is not None and hit.runtime == "rt-b"  # most recent m
    assert pool.take_match("m").runtime == "rt-a"
    assert pool.take_match("m") is None  # only "other" left
    assert pool.take_match("other").runtime == "rt-o"  # exact key matches too
    # no prefix confusion: "m" must not match "mx"
    pool.put("mx@/c", "rt-x", 10)
    assert pool.take_match("m") is None


def test_model_pool_disabled_and_oversize():
    pool = HostModelPool(budget_bytes=0)
    evicted = pool.put("a", "rt", 1)
    assert [e.model_id for e in evicted] == ["a"] and len(pool) == 0
    pool = HostModelPool(budget_bytes=10)
    # a single entry larger than the budget cannot be pooled
    evicted = pool.put("big", "rt", 11)
    assert [e.model_id for e in evicted] == ["big"] and pool.bytes_used == 0
    # ... and an oversized newcomer must NOT flush the resident models
    pool.put("small", "rt-s", 5)
    evicted = pool.put("big2", "rt-b", 11)
    assert [e.model_id for e in evicted] == ["big2"]
    assert pool.models() == ["small"] and pool.bytes_used == 5
