"""Content-addressed chunk store (engine/chunk_store.py): refcount
lifecycle, dedup accounting, disk-tier round trips, and the
content-verify-on-reload guarantee (a stale/corrupt/colliding blob is a
miss, never wrong weights)."""

import glob
import os

import numpy as np
import pytest

from llm_d_fast_model_actuation_tpu.engine.chunk_store import (
    QUANT_DIGEST_PREFIX,
    ChunkStore,
    aligned_digests,
    digest_content_hash,
    digest_spillable,
    digest_tree,
    leaf_digest,
    qualify_digest,
)
from llm_d_fast_model_actuation_tpu.engine.model_pool import HostModelPool

pytestmark = pytest.mark.deltaswap


def test_leaf_digest_content_shape_dtype_sensitive():
    a = np.arange(8, dtype=np.float32)
    assert leaf_digest(a) == leaf_digest(a.copy())
    assert leaf_digest(a) != leaf_digest(a.astype(np.float64))
    assert leaf_digest(a) != leaf_digest(a.reshape(2, 4))
    b = a.copy()
    b[3] += 1
    assert leaf_digest(a) != leaf_digest(b)
    # non-contiguous views hash by content, not memory layout
    m = np.arange(16, dtype=np.float32).reshape(4, 4)
    assert leaf_digest(m.T) == leaf_digest(np.ascontiguousarray(m.T))


def test_mesh_qualified_digests_identity_and_spill_round_trip(tmp_path):
    """Shard-qualified digests (sharded engines): same content under the
    same qualifier matches, any qualifier difference (mesh shape or
    per-leaf spec) does not, the plain content hash is recoverable for
    reload verification, qualification is idempotent, and a qualified
    chunk survives a verified disk round trip — the mesh-restart rebuild
    path."""
    a = np.arange(64, dtype=np.float32)
    content = leaf_digest(a)
    q1 = qualify_digest(content, "tp=2|PartitionSpec(None, 'tp')")
    q2 = qualify_digest(content, "tp=2|PartitionSpec(None, 'tp')")
    q3 = qualify_digest(content, "tp=4|PartitionSpec(None, 'tp')")
    q4 = qualify_digest(content, "tp=2|PartitionSpec('tp', None)")
    assert q1 == q2
    assert len({q1, q3, q4, content}) == 4  # qualifier-sensitive
    assert q1.startswith("m:")
    assert digest_content_hash(q1) == content
    assert digest_content_hash(content) == content
    # idempotent: re-qualifying a qualified (or quant) digest is a no-op
    assert qualify_digest(q1, "tp=8|whatever") == q1
    assert qualify_digest("q:abc", "tp=2|x") == "q:abc"

    # qualified chunks spill and reload content-verified
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    cs.intern(q1, a)
    cs.release(q1)  # last ref: spills
    assert cs.disk_spills == 1
    got = cs.fetch(q1)
    assert got is not None and np.array_equal(got, a)
    assert cs.disk_hits == 1 and cs.verify_failures == 0
    # a corrupted blob is a verified miss, qualified or not
    path = glob.glob(os.path.join(str(tmp_path), "*.chunk"))[0]
    raw = open(path, "rb").read()
    open(path, "wb").write(raw[:-4] + b"\x00\x00\x00\x01")
    assert cs.fetch(q1) is None
    assert cs.verify_failures == 1


def test_intern_refcount_and_dedup_accounting():
    cs = ChunkStore()
    a = np.arange(64, dtype=np.float32)
    d = leaf_digest(a)
    c1, added1 = cs.intern(d, a)
    assert c1 is a and added1 == a.nbytes and cs.host_bytes == a.nbytes
    dup = a.copy()
    c2, added2 = cs.intern(d, dup)
    # the canonical array is the FIRST one: the duplicate is dropped by
    # the caller — that is the host-DRAM dedup
    assert c2 is a and added2 == 0
    assert cs.dedup_saved_bytes == a.nbytes and cs.dedup_hits == 1
    # first release: still referenced, nothing freed
    assert cs.release(d) == 0 and cs.host_bytes == a.nbytes
    assert cs.dedup_saved_bytes == 0
    # last release frees the host bytes (no disk tier configured)
    assert cs.release(d) == a.nbytes and cs.host_bytes == 0
    assert cs.fetch(d) is None  # genuinely gone


def test_disk_tier_round_trip_bit_exact(tmp_path):
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    import ml_dtypes

    arrays = [
        np.arange(32, dtype=np.float32).reshape(4, 8),
        (np.linspace(-1, 1, 24).astype(ml_dtypes.bfloat16)).reshape(2, 3, 4),
        np.array([], dtype=np.int32),
    ]
    digests = [leaf_digest(a) for a in arrays]
    for d, a in zip(digests, arrays):
        cs.intern(d, a)
        cs.release(d)  # last ref -> spill
    assert cs.disk_spills == len(arrays)
    for d, a in zip(digests, arrays):
        got = cs.fetch(d)
        assert got is not None
        assert got.dtype == a.dtype and got.shape == a.shape
        assert np.array_equal(
            got.view(np.uint8) if got.size else got, a.view(np.uint8) if a.size else a
        ), "disk round trip not bit-exact"
    assert cs.disk_hits == len(arrays)


def test_disk_reload_content_verify_rejects_corruption(tmp_path):
    """Hash-collision / bitrot safety: the reload recomputes the content
    digest over what the file actually holds — any mismatch is a miss and
    the blob is deleted, never served."""
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    a = np.arange(100, dtype=np.float32)
    d = leaf_digest(a)
    cs.intern(d, a)
    cs.release(d)
    (path,) = glob.glob(str(tmp_path / "*.chunk"))
    raw = open(path, "rb").read()
    # flip one payload bit — the header (and so the claimed digest) is
    # untouched, exactly the collision shape the verify must catch
    with open(path, "wb") as f:
        f.write(raw[:-1] + bytes([raw[-1] ^ 1]))
    assert cs.fetch(d) is None
    assert cs.verify_failures == 1
    assert not os.path.exists(path), "corrupt blob must be deleted"
    assert cs.fetch(d) is None  # and stays a miss


def test_disk_tier_lru_budget(tmp_path):
    a = np.zeros(256, dtype=np.uint8)
    b = np.ones(256, dtype=np.uint8)
    c = np.full(256, 2, dtype=np.uint8)
    da, db, dc = leaf_digest(a), leaf_digest(b), leaf_digest(c)
    # budget fits ~two spilled chunks (payload + small json header)
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=800)
    for d, arr in ((da, a), (db, b), (dc, c)):
        cs.intern(d, arr)
        cs.release(d)
    assert cs.disk_evictions >= 1
    assert cs.fetch(da) is None  # oldest evicted
    assert cs.fetch(dc) is not None


def test_disk_tier_survives_restart(tmp_path):
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    a = np.arange(10, dtype=np.int64)
    d = leaf_digest(a)
    cs.intern(d, a)
    cs.release(d)
    # a fresh store over the same dir adopts the spilled chunk
    cs2 = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    got = cs2.fetch(d)
    assert got is not None and np.array_equal(got, a)
    assert cs2.disk_bytes > 0


def test_aligned_digests_params_prefix():
    state = {
        "params": {"embed": np.zeros(2), "layers": {"wq": np.ones(2)}},
        "kv": (np.zeros(3), np.zeros(3)),
    }
    digests = {"embed": "d-embed", "layers/wq": "d-wq"}
    out = aligned_digests(state, digests, prefix="params")
    import jax

    leaves, _ = jax.tree.flatten(state)
    assert len(out) == len(leaves)
    # KV leaves carry None (never content-matched); params align by key
    assert sorted(x for x in out if x) == ["d-embed", "d-wq"]
    assert out.count(None) == 2
    assert aligned_digests(state, None) == [None] * len(leaves)


def test_pool_intern_two_variants_share_base_evict_one_bit_exact():
    """Refcount lifecycle through the pool: two variants sharing a base
    tensor hold it once; evicting one leaves the other's tree bit-exact
    and still host-resident."""
    cs = ChunkStore()
    pool = HostModelPool(budget_bytes=1 << 20, chunks=cs)
    base = np.arange(1000, dtype=np.float32)
    delta_a = np.zeros(10, dtype=np.float32)
    delta_b = np.ones(10, dtype=np.float32)
    tree_a = {"base": base.copy(), "head": delta_a}
    tree_b = {"base": base.copy(), "head": delta_b}
    dg_a = digest_tree(tree_a)
    dg_b = digest_tree(tree_b)
    ia, held_a, nom_a = pool.intern_tree(tree_a, dg_a, prefix="")
    ib, held_b, nom_b = pool.intern_tree(tree_b, dg_b, prefix="")
    # the shared base is ONE chunk: variant B's tree points at A's array
    assert ib["base"] is ia["base"]
    assert cs.host_bytes == base.nbytes + delta_a.nbytes + delta_b.nbytes
    assert cs.dedup_saved_bytes == base.nbytes
    pool.put("a", "rt-a", base.nbytes + delta_a.nbytes,
             chunk_digests=held_a, weight_digests=dg_a,
             interned_bytes=nom_a)
    pool.put("b", "rt-b", base.nbytes + delta_b.nbytes,
             chunk_digests=held_b, weight_digests=dg_b,
             interned_bytes=nom_b)
    two = pool.bytes_used
    assert two < 1.2 * (base.nbytes + delta_a.nbytes), "dedup not working"
    # evict A wholesale: B's shared chunk keeps its reference
    entry = pool.take("a")
    assert entry is not None
    assert cs.fetch(dg_a["base"]) is ib["base"]
    assert np.array_equal(ib["base"], base) and np.array_equal(
        ib["head"], delta_b
    ), "surviving variant no longer bit-exact"


@pytest.mark.parametrize("staging", ["numpy", "pinned_host"])
def test_intern_tree_takes_numpy_leaves_and_leaves_pinned_host_alone(staging):
    """What of a slept model the content-addressed tiers see hangs on how it
    was staged: numpy leaves are replaced by the store's canonical chunks,
    ``pinned_host`` jax arrays (the staging of a backend with that memory
    space: the TPU, and this jax's CPU) come back as they went in, with
    nothing held and nothing interned."""
    import jax

    from llm_d_fast_model_actuation_tpu.engine import sleep

    tree = {
        "base": np.arange(1000, dtype=np.float32),
        "head": np.ones(10, dtype=np.float32),
    }
    digests = digest_tree(tree)
    nbytes = sum(a.nbytes for a in tree.values())
    if staging == "pinned_host":
        if not sleep._platform_supports_host_memory():
            pytest.skip("this backend has no pinned_host memory space")
        tree = jax.device_put(
            tree,
            jax.sharding.SingleDeviceSharding(
                jax.devices()[0], memory_kind="pinned_host"
            ),
        )
    cs = ChunkStore()
    pool = HostModelPool(budget_bytes=1 << 20, chunks=cs)
    out, held, nominal = pool.intern_tree(tree, digests, prefix="")
    if staging == "numpy":
        assert sorted(held) == sorted(digests.values())
        assert nominal == cs.host_bytes == nbytes
        assert all(out[k] is cs.fetch(digests[k]) for k in tree)
    else:
        assert (held, nominal) == ([], 0)
        assert all(out[k] is tree[k] for k in tree)
        assert cs.host_bytes == 0 and digests["base"] not in cs


def test_pool_manifest_reconstruction_and_stale_miss(tmp_path):
    """An evicted entry leaves a manifest; take_staged rebuilds the whole
    tree from the tiers, and ANY unresolvable chunk is a miss for the
    whole model."""
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    pool = HostModelPool(budget_bytes=4096, chunks=cs)
    tree = {"w": np.arange(512, dtype=np.float32),
            "nested": {"b": np.ones(4, dtype=np.float32)}}
    dg = digest_tree(tree)
    it, held, nom = pool.intern_tree(tree, dg, prefix="")
    # oversize for the pool budget: bounces straight through to the disk
    # tier, manifest recorded
    evicted = pool.put("m@ck", "rt", 4097, chunk_digests=held,
                       weight_digests=dg, interned_bytes=nom)
    assert [e.model_id for e in evicted] == ["m@ck"]
    assert cs.disk_spills == 2
    got = pool.take_staged_match("m")
    assert got is not None
    rebuilt, digests, key, tier = got
    assert key == "m@ck" and digests == dg
    # the bounce released every host reference, so the rebuild came from
    # verified disk reloads — and must say so
    assert tier == "disk"
    assert np.array_equal(rebuilt["w"], tree["w"])
    assert np.array_equal(rebuilt["nested"]["b"], tree["nested"]["b"])
    assert pool.staged_hits == 1
    # manifest consumed: a second staged take is a miss
    assert pool.take_staged("m@ck") is None

    # stale-blob-is-a-miss: re-evict, then delete one blob on disk
    it2, held2, nom2 = pool.intern_tree(tree, dg, prefix="")
    pool.put("m@ck", "rt", 4097, chunk_digests=held2, weight_digests=dg,
             interned_bytes=nom2)
    for f in glob.glob(str(tmp_path / "*.chunk"))[:1]:
        os.unlink(f)
    assert pool.take_staged("m@ck") is None
    assert pool.staged_misses == 1


def test_pool_staged_rebuild_from_host_tier_via_sibling(tmp_path):
    """An evicted model whose chunks a pooled sibling still references
    rebuilds zero-copy from host DRAM — and the tier label says "host",
    not "disk" (the per-tier cost signal must not attribute DRAM-speed
    rebuilds to the disk tier)."""
    cs = ChunkStore(disk_dir=str(tmp_path), disk_budget_bytes=1 << 20)
    pool = HostModelPool(budget_bytes=4096, chunks=cs)
    tree = {"w": np.arange(512, dtype=np.float32)}
    dg = digest_tree(tree)
    it_s, held_s, nom_s = pool.intern_tree(tree, dg, prefix="")
    pool.put("s", "rt-s", 2048, chunk_digests=held_s, weight_digests=dg,
             interned_bytes=nom_s)
    it_m, held_m, nom_m = pool.intern_tree(dict(tree), dg, prefix="")
    # oversize: bounces straight out, manifest recorded; the shared chunk
    # keeps the sibling's reference and stays host-resident
    pool.put("m@ck", "rt-m", 4097, chunk_digests=held_m, weight_digests=dg,
             interned_bytes=nom_m)
    got = pool.take_staged("m@ck")
    assert got is not None
    rebuilt, _digests, tier = got
    assert tier == "host", "sibling-held chunks must label the host tier"
    assert rebuilt["w"] is it_s["w"], "host-tier rebuild must be zero-copy"
    assert cs.disk_hits == 0


def test_pool_bytes_used_running_counter():
    """The flat pool re-summed every entry per eviction victim and per
    /metrics read; the rebuild keeps running counters — pin the numbers
    through put/take/evict cycles."""
    pool = HostModelPool(budget_bytes=100)
    pool.put("a", "rt", 30)
    pool.put("b", "rt", 50)
    assert pool.bytes_used == 80
    evicted = pool.put("c", "rt", 40)  # evicts a
    assert [e.model_id for e in evicted] == ["a"]
    assert pool.bytes_used == 90
    pool.take("b")
    assert pool.bytes_used == 40
    pool.drain()
    assert pool.bytes_used == 0


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
