"""Tiered host-side model pool: LRU registry of slept/staged models over a
content-addressed chunk store.

The hot-swap path (docs/engine.md "Model hot-swap") lets N models time-share
one chip: the model being swapped out goes to sleep (level 1, host-resident
state) and is *pooled* here instead of discarded, keyed by model id and
bounded by a host byte budget. A later swap back is then a pure host->HBM
restore — no checkpoint re-read, no recompile.

Since the tiered rebuild (docs/perf.md "Tiered weight cache and delta
swap") the pool is two tiers deep and content-addressed:

  * **Host DRAM (hot tier)** — pooled entries whose weight leaves carry
    content digests are *interned* into a :class:`~.chunk_store.ChunkStore`:
    two fine-tunes of one base model hold their common tensors in host
    memory exactly once (refcounted), and ``bytes_used`` is the real
    deduped residency, maintained as a RUNNING counter (no O(n) re-sum per
    eviction step or per /metrics scrape).
  * **Local disk (spill tier)** — an evicted entry leaves behind a
    *manifest* (flat key -> digest) while its last-reference chunks spill
    to disk (atomic rename, content-verified reload). A later swap to the
    evicted model reconstructs its weights from the tiers
    (``take_staged``) — local SSD instead of a network checkpoint re-read;
    any unresolvable chunk makes the whole reconstruction a miss.

The pool stores opaque runtime entries (the engine server's model-runtime
bundle); the only contract is that an evicted entry's host bytes are freed
by the caller (the server escalates the evicted sleeper to level 2). LRU
order is by swap-out recency — mirroring the recency-driven victim
selection in "Towards Multi-Model LLM Schedulers" (PAPERS.md); tier
placement follows 10Cache's cost-aware migration (PAPERS.md).

Mutations happen under the engine server's step lock, but observability
reads (/metrics) come from other threads — an internal mutex makes every
operation safe to call concurrently.
"""

from __future__ import annotations

import os
import threading
import time
from collections import OrderedDict
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from .chunk_store import ChunkStore, aligned_digests, unflatten_tree

#: ceiling on remembered manifests of evicted entries: each is a small
#: dict of digests, but an unbounded registry would grow with every model
#: ever served
MAX_MANIFESTS = 64


@dataclass
class PoolEntry:
    model_id: str
    runtime: Any  #: opaque bundle (engine + sleeper + tokenizer + ...)
    nbytes: int  #: nominal host bytes the slept state occupies (pre-dedup)
    stored_at: float = field(default_factory=time.monotonic)
    #: digests whose chunk-store references this entry holds (interned)
    chunk_digests: List[str] = field(default_factory=list)
    #: flat weight key -> digest: the manifest an eviction leaves behind
    weight_digests: Optional[Dict[str, str]] = None
    #: bytes this entry adds OUTSIDE the chunk store (non-digested leaves
    #: — KV pages, scheduler state — plus everything when not interned)
    resident_bytes: int = 0


class HostModelPool:
    """Tiered LRU registry of slept models under a host byte budget.

    ``budget_bytes <= 0`` disables pooling: every ``put`` immediately
    returns its own entry as evicted, so the caller frees it and the next
    swap-in is a cold build — the same code path, just with a zero cache.

    ``chunks`` (a ChunkStore) enables the content-addressed tiers; without
    it the pool behaves exactly like the pre-tier flat LRU.
    """

    def __init__(
        self, budget_bytes: int = 0, chunks: Optional[ChunkStore] = None
    ) -> None:
        self.budget_bytes = int(budget_bytes)
        self.chunks = chunks
        self._mu = threading.Lock()
        self._entries: "OrderedDict[str, PoolEntry]" = OrderedDict()
        #: manifests of evicted entries whose chunks may still be
        #: resolvable from the tiers: key -> (weight_digests, nbytes)
        self._manifests: "OrderedDict[str, Tuple[Dict[str, str], int]]" = (
            OrderedDict()
        )
        #: running non-interned residency — with the chunk store's own
        #: running host_bytes this makes bytes_used O(1) (the flat pool
        #: re-summed every entry per eviction victim AND per scrape)
        self._resident_bytes = 0
        self.hits = 0
        self.misses = 0
        self.evictions = 0
        self.staged_hits = 0
        self.staged_misses = 0

    def __len__(self) -> int:
        return len(self._entries)

    def __contains__(self, model_id: str) -> bool:
        return model_id in self._entries

    @property
    def bytes_used(self) -> int:
        """Actual (deduped) host residency: running counters only."""
        base = self._resident_bytes
        if self.chunks is not None:
            base += self.chunks.host_bytes
        return base

    def models(self) -> List[str]:
        """Pooled model ids, LRU first."""
        with self._mu:
            return list(self._entries)

    # -- interning ------------------------------------------------------------

    def intern_tree(
        self,
        tree: Any,
        digests: Optional[Dict[str, str]],
        prefix: str = "params",
    ) -> Tuple[Any, List[str], int]:
        """Replace digested numpy leaves of ``tree`` with canonical
        chunk-store arrays (dedup across pooled variants). Returns
        ``(interned_tree, held_digests, interned_nominal_bytes)`` — the
        caller passes the latter two to :meth:`put`. A disabled store (or
        no digests) returns the tree untouched.

        Only plain numpy leaves intern: pinned-host jax arrays (TPU sleep
        staging) are client-owned and cannot be shared across trees, so
        they keep per-entry residency (documented in docs/perf.md).
        Transfer-quantized payloads intern under ``"q:"`` digests and
        spill to disk like any other chunk — the spill header's content
        hash makes the reload verifiable (chunk_store._load_spilled)."""
        if self.chunks is None or not digests or self.budget_bytes <= 0:
            return tree, [], 0
        import numpy as np
        from jax.tree_util import tree_flatten, tree_unflatten

        leaves, treedef = tree_flatten(tree)
        dlist = aligned_digests(tree, digests, prefix=prefix)
        held: List[str] = []
        nominal = 0
        out = list(leaves)
        for i, (leaf, d) in enumerate(zip(leaves, dlist)):
            if d is None or not isinstance(leaf, np.ndarray):
                continue
            canonical, _added = self.chunks.intern(d, leaf)
            out[i] = canonical
            held.append(d)
            nominal += int(leaf.nbytes)
        return tree_unflatten(treedef, out), held, nominal

    def _release_refs(self, entry: PoolEntry, spill: bool) -> None:
        if self.chunks is None:
            return
        for d in entry.chunk_digests:
            self.chunks.release(d, spill=spill)
        entry.chunk_digests = []

    def _record_manifest(self, entry: PoolEntry) -> None:
        if (
            self.chunks is None
            or not entry.weight_digests
            or self.budget_bytes <= 0
        ):
            return
        self._manifests.pop(entry.model_id, None)
        self._manifests[entry.model_id] = (
            dict(entry.weight_digests),
            entry.nbytes,
        )
        while len(self._manifests) > MAX_MANIFESTS:
            self._manifests.popitem(last=False)

    # -- take / put -----------------------------------------------------------

    def peek(self, model_id: str) -> Optional[PoolEntry]:
        """Non-consuming :meth:`take`: the entry stays pooled, LRU order
        and hit/miss counters untouched. The cost oracle prices pooled
        candidates through this — pricing must never change pool state.
        The returned entry is live and may be taken by a concurrent
        swap; callers treat it as an advisory snapshot."""
        with self._mu:
            return self._entries.get(model_id)

    def peek_match(self, model_id: str) -> Optional[PoolEntry]:
        """Non-consuming :meth:`take_match` (same key-or-qualified rule,
        most recently parked first)."""
        with self._mu:
            for key in reversed(self._entries):
                if key == model_id or key.startswith(model_id + "@"):
                    return self._entries[key]
        return None

    def peek_staged(self, key: str) -> Optional[Tuple[int, str, int]]:
        """Non-consuming tier probe of an evicted model's manifest:
        ``(nbytes, tier, chunks)`` where tier is ``"host"`` (every chunk
        still DRAM-resident via a sibling's references) or ``"disk"`` (at
        least one chunk would need a verified disk reload), or None when
        there is no manifest or any chunk is a miss on both tiers (a
        rebuild would fall through to a cold load). Unlike
        :meth:`take_staged` this never pops the manifest, reads no file,
        and rebuilds nothing — the cost oracle's pre-transfer pricing."""
        with self._mu:
            manifest = self._manifests.get(key)
        if manifest is None or self.chunks is None:
            return None
        digests, nbytes = manifest
        tier = "host"
        for d in digests.values():
            t = self.chunks.peek_tier(d)
            if t is None:
                return None
            if t == "disk":
                tier = "disk"
        return int(nbytes), tier, len(digests)

    def peek_staged_match(
        self, model_id: str
    ) -> Optional[Tuple[str, int, str, int]]:
        """:meth:`peek_staged` under any checkpoint qualifier (most
        recently evicted first); returns (key, nbytes, tier, chunks)."""
        with self._mu:
            keys = [
                k
                for k in reversed(self._manifests)
                if k == model_id or k.startswith(model_id + "@")
            ]
        for k in keys:
            got = self.peek_staged(k)
            if got is not None:
                return k, got[0], got[1], got[2]
        return None

    def take(self, model_id: str) -> Optional[PoolEntry]:
        """Remove and return the entry for ``model_id`` (a pool hit — the
        caller wakes it, so it leaves the pool), or None (miss)."""
        with self._mu:
            entry = self._entries.pop(model_id, None)
            if entry is None:
                self.misses += 1
                return None
            self.hits += 1
            self._resident_bytes -= entry.resident_bytes
        # no spill: the model is about to go live; its weights come back
        # at the next swap-out (and sibling-shared chunks keep their refs)
        self._release_refs(entry, spill=False)
        return entry

    def contains_match(self, model_id: str) -> bool:
        """Non-mutating ``take_match`` probe: is anything pooled under this
        model name, with or without a checkpoint qualifier? (Used by
        prefetch to skip re-staging an already-resident model; counts no
        hit/miss.)"""
        with self._mu:
            return any(
                key == model_id or key.startswith(model_id + "@")
                for key in self._entries
            )

    def take_match(self, model_id: str) -> Optional[PoolEntry]:
        """Remove and return the most-recently-parked entry pooled under
        this model name regardless of checkpoint qualifier (keys are
        ``name`` or ``name@checkpoint_dir``): a swap request that omits
        checkpoint_dir means "this model, whatever source it came from"."""
        with self._mu:
            found = None
            for key in reversed(self._entries):
                if key == model_id or key.startswith(model_id + "@"):
                    found = key
                    break
            if found is None:
                self.misses += 1
                return None
            self.hits += 1
            entry = self._entries.pop(found)
            self._resident_bytes -= entry.resident_bytes
        self._release_refs(entry, spill=False)
        return entry

    def put(
        self,
        model_id: str,
        runtime: Any,
        nbytes: int,
        chunk_digests: Optional[List[str]] = None,
        weight_digests: Optional[Dict[str, str]] = None,
        interned_bytes: int = 0,
    ) -> List[PoolEntry]:
        """Register a just-slept model as most-recently-used and evict LRU
        entries until the byte budget holds. Returns the evicted entries
        (possibly including the new one, when it alone exceeds the budget
        or pooling is disabled); the caller must free their host state.

        ``chunk_digests``/``interned_bytes`` come from :meth:`intern_tree`
        (the entry's weight leaves already point at canonical chunk-store
        arrays); ``weight_digests`` is the flat manifest an eviction
        records so the disk tier can later rebuild this model."""
        entry = PoolEntry(
            model_id=model_id,
            runtime=runtime,
            nbytes=int(nbytes),
            chunk_digests=list(chunk_digests or []),
            weight_digests=weight_digests,
            resident_bytes=max(0, int(nbytes) - int(interned_bytes)),
        )
        evicted: List[PoolEntry] = []
        bounced: Optional[List[PoolEntry]] = None
        spills: List[Tuple[str, Any]] = []
        with self._mu:
            # replacing an id re-registers it as most recent
            old = self._entries.pop(model_id, None)
            if old is not None:
                self._resident_bytes -= old.resident_bytes
                # a same-id replace drops the old entry's chunk refs
                # without spilling: the new entry just re-interned the
                # same content
                self._release_refs(old, spill=False)
                evicted.append(old)
            if entry.nbytes > self.budget_bytes:
                # the newcomer alone can never fit: evict IT, not the
                # resident models that still can be hit
                self.evictions += 1 + len(evicted)
                bounced = evicted + [entry]
            else:
                self._entries[model_id] = entry
                self._resident_bytes += entry.resident_bytes
                while self.bytes_used > self.budget_bytes:
                    _, victim = self._entries.popitem(last=False)
                    self._resident_bytes -= victim.resident_bytes
                    # refs drop under the lock (keeps bytes_used coherent
                    # with the loop condition) but the spill's DISK I/O is
                    # deferred past it: a multi-GiB victim's write must
                    # not block every other pool op on this mutex
                    if self.chunks is not None:
                        for d in victim.chunk_digests:
                            freed = self.chunks.release_deferred(d)
                            if freed is not None:
                                spills.append(freed)
                        victim.chunk_digests = []
                    self._record_manifest(victim)
                    evicted.append(victim)
                    self.evictions += 1
        if bounced is None:
            for d, data in spills:
                self.chunks.spill(d, data)
            return evicted
        # bounce path (pool disabled / oversize): refs released outside
        # the lock; the spill keeps the weights reachable via the manifest
        for e in bounced:
            self._release_refs(e, spill=True)
            with self._mu:
                self._record_manifest(e)
        return bounced

    def drain(self) -> List[PoolEntry]:
        """Remove and return every entry (counted as evictions): the caller
        is invalidating the pool wholesale — e.g. a device-releasing sleep
        is about to destroy the client that owns the pooled states' pinned
        host buffers and compiled programs. Chunked numpy weights are NOT
        client-owned: they spill to the disk tier and stay reconstructable
        through their manifests."""
        with self._mu:
            out = list(self._entries.values())
            self._entries.clear()
            self._resident_bytes = 0
            self.evictions += len(out)
        for entry in out:
            self._release_refs(entry, spill=True)
            with self._mu:
                self._record_manifest(entry)
        return out

    # -- the spill tier: manifest reconstruction ------------------------------

    def staged_keys(self) -> List[str]:
        with self._mu:
            return list(self._manifests)

    def take_staged(
        self, key: str
    ) -> Optional[Tuple[Dict[str, Any], Dict[str, str], str]]:
        """Rebuild an evicted model's host weight tree from the tiers.
        Returns ``(params_tree, weight_digests, tier)`` — tier ``"host"``
        when every chunk was still host-resident via a sibling's live
        references, ``"disk"`` when any verified disk reload was needed —
        or None: any unresolvable chunk is a miss for the WHOLE model (a
        partial tree must never serve), and drops the stale manifest.
        Disk fetches (read + content re-hash) run on a small thread pool:
        the rebuild sits on the swap critical path, and serial hash-bound
        reloads of a multi-GiB model would undo the tier's win over the
        parallel cold loader."""
        with self._mu:
            manifest = self._manifests.pop(key, None)
        if manifest is None or self.chunks is None:
            return None
        digests, _nbytes = manifest
        items = list(digests.items())
        from_disk = any(d not in self.chunks for _, d in items)
        workers = min(8, os.cpu_count() or 1, max(1, len(items)))
        if workers > 1 and from_disk:
            with ThreadPoolExecutor(
                workers, thread_name_prefix="pool-tier-fetch"
            ) as ex:
                arrs = list(
                    ex.map(lambda kv: self.chunks.fetch(kv[1]), items)
                )
        else:
            arrs = [self.chunks.fetch(d) for _, d in items]
        if any(a is None for a in arrs):
            with self._mu:
                self.staged_misses += 1
            return None
        flat = {k: a for (k, _), a in zip(items, arrs)}
        with self._mu:
            self.staged_hits += 1
        return (
            unflatten_tree(flat),
            dict(digests),
            "disk" if from_disk else "host",
        )

    def take_staged_match(
        self, model_id: str
    ) -> Optional[Tuple[Dict[str, Any], Dict[str, str], str, str]]:
        """``take_staged`` under any checkpoint qualifier (most recently
        evicted first); returns (tree, digests, matched_key, tier)."""
        with self._mu:
            keys = [
                k
                for k in reversed(self._manifests)
                if k == model_id or k.startswith(model_id + "@")
            ]
        for k in keys:
            got = self.take_staged(k)
            if got is not None:
                return got[0], got[1], k, got[2]
        return None

    def describe(self) -> Dict[str, Any]:
        with self._mu:
            entries = [
                {
                    "model_id": e.model_id,
                    "nbytes": e.nbytes,
                    "resident_bytes": e.resident_bytes,
                }
                for e in self._entries.values()
            ]
            manifests = list(self._manifests)
        out = {
            "models": self.models(),
            "bytes_used": self.bytes_used,
            "budget_bytes": self.budget_bytes,
            "hits": self.hits,
            "misses": self.misses,
            "evictions": self.evictions,
            "entries": entries,
            "staged_manifests": manifests,
            "staged_hits": self.staged_hits,
            "staged_misses": self.staged_misses,
        }
        if self.chunks is not None:
            out["chunks"] = self.chunks.describe()
        return out
