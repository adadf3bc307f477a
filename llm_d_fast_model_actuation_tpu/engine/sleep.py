"""Sleep/wake: move live model state HBM <-> host without killing the
process — optionally releasing the TPU itself.

The reference's headline capability (vLLM sleep mode: ~3 s wake for 64 GiB,
README.md:16-26), rebuilt on XLA memory kinds: every array keeps its sharding
but changes memory space to ``pinned_host`` on sleep and back to ``device``
on wake — on TPU this is a DMA over PCIe into pinned buffers, and on
multi-chip meshes each chip's shard moves independently (no resharding, no
gather). Wake does NOT recompile: compiled executables are host-resident and
keyed by sharding+shape, which are unchanged.

**Device release** (`release=True`) goes further than the reference can on
GPU: the state is snapshotted to plain host numpy and the process's PJRT
client is destroyed (`engine/device.py`), so the chip is actually free for
another process — the TPU-correct form of the dual-pods time-sharing
contract (docs/dual-pods.md:20-56; on TPU a chip has exactly one holder, so
an HBM-empty-but-client-open sleeper still blocks every other server). Wake
then re-creates the client, restores state, and re-lowers programs through
the persistent XLA compile cache instead of recompiling from scratch.

Sleep levels (vLLM vocabulary):
  level 1 — weights and KV pages offloaded to host; wake restores both.
  level 2 — weights discarded entirely (re-init/reload on wake), KV dropped.

Backends without host memory-space support (CPU tests) fall back to
numpy staging buffers — same state machine, same API. Release mode works on
every backend (CPU client re-init is supported), so the full release state
machine is exercised by the CPU suite.

**Chunked transfers** (``bucket_bytes``): the offloadable pytree is split
into size-bounded buckets of whole leaves and moved bucket-by-bucket, each
bucket's HBM freed (offload) or host copy released (wake) as soon as it
lands. This bounds the peak duplicated state to ~one bucket instead of a
whole model tree, and bounds the in-flight transfer window (the
SLO-guarantee lever from "Memory Offloading for LLM Inference with Latency
SLO Guarantees", PAPERS.md). ``bucket_bytes=None`` keeps the legacy
whole-tree single batched transfer. ``swap_states`` builds on the same
buckets to overlap one model's offload with another's restore — the
hot-swap fast path (docs/engine.md "Model hot-swap").
"""

from __future__ import annotations

import enum
import time
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional, Sequence

import jax
import numpy as np

from ..models import quant as transfer_quant
from ..utils import faults, tracing
from .device import (
    rebuild_spec,
    reacquire_devices,
    release_devices,
    sharding_spec,
)


class SwapRolledBack(RuntimeError):
    """A mid-transfer hot-swap failure was rolled back: the outgoing model
    is fully back on device (awake, serving) and the incoming model's
    host-resident state is intact (re-poolable). The swap did not happen,
    but nothing was lost — retryable."""


class SwapRollbackFailed(RuntimeError):
    """A mid-transfer hot-swap failure could NOT be rolled back: device
    state is partially moved and unrecoverable in-process. The service
    must fail loudly (flip /health) so the controller heals the process."""

#: Default transfer bucket for chunked/overlapped swaps: large enough to
#: amortize per-transfer dispatch, small enough that peak extra HBM and the
#: in-flight window stay a fraction of any serving-size model.
DEFAULT_SWAP_BUCKET_BYTES = 256 << 20


def partition_buckets(
    nbytes: Sequence[int], bucket_bytes: Optional[int]
) -> List[List[int]]:
    """Greedy contiguous partition of leaf indices into buckets of at most
    ``bucket_bytes`` each. Leaves are never split (bit-exactness is then
    structural), so a single leaf larger than the bound forms its own
    bucket. ``bucket_bytes=None`` (or <= 0) returns one bucket holding
    everything — the whole-tree legacy path.

    Shared transfer discipline: the streaming cold-start loader
    (models/hf.py) buckets its host->device stream with this same
    partition, so sleep/wake, hot-swap, and cold load all bound their
    in-flight window the same way."""
    if not nbytes:
        return []
    if not bucket_bytes or bucket_bytes <= 0:
        return [list(range(len(nbytes)))]
    buckets: List[List[int]] = []
    cur: List[int] = []
    cur_bytes = 0
    for i, nb in enumerate(nbytes):
        if cur and cur_bytes + nb > bucket_bytes:
            buckets.append(cur)
            cur, cur_bytes = [], 0
        cur.append(i)
        cur_bytes += nb
    if cur:
        buckets.append(cur)
    return buckets


def _aligned(state, digests):
    """Per-leaf digest list aligned with the flatten order of ``state``
    (weight digests live under the "params" subtree; KV and scheduler
    leaves get None and always move)."""
    from .chunk_store import aligned_digests

    return aligned_digests(state, digests, prefix="params")


class SleepLevel(enum.IntEnum):
    AWAKE = 0
    L1_HOST_OFFLOAD = 1
    L2_DISCARD = 2


def _platform_supports_host_memory() -> bool:
    """Does the backend expose a ``pinned_host`` memory space? An error here
    is a backend that did not come up; it raises rather than silently
    switching sleep to numpy staging."""
    dev = jax.devices()[0]
    return any(m.kind == "pinned_host" for m in dev.addressable_memories())


@dataclass
class _Stats:
    last_sleep_seconds: float = 0.0
    last_wake_seconds: float = 0.0
    last_reacquire_seconds: float = 0.0
    #: host bytes the slept state actually occupies (the quantized payload
    #: bytes when --sleep-quant compressed the offload)
    bytes_offloaded: int = 0
    #: full-precision bytes of the state that went to sleep (==
    #: bytes_offloaded for uncompressed offloads)
    bytes_offloaded_full: int = 0
    #: transfer mode of the last level-1 offload: "off" | "int8" | "fp8"
    last_quant: str = "off"
    #: wire bytes the last wake moved host->device
    last_wake_bytes: int = 0
    #: the pure d2h transfer window of the last level-1 offload (the
    #: engine quiesce and device release that last_sleep_seconds also
    #: covers are excluded) — what the cost oracle's bandwidth EWMA and
    #: the phase=d2h histogram observe
    last_sleep_transfer_s: float = 0.0
    #: the pure h2d window of the last wake (client reacquisition
    #: excluded) — the phase=h2d / wake.h2d figure
    last_wake_transfer_s: float = 0.0
    sleeps_total: int = 0
    wakes_total: int = 0
    releases_total: int = 0


class SleepManager:
    """Owns the awake/asleep state of one engine's device arrays.

    Usage: ``mgr = SleepManager(get_state, set_state)`` where get/set move a
    pytree of device arrays out of / into the engine. The manager guarantees
    the engine never holds both copies (donation/delete on each edge).

    ``on_reacquire`` (optional) runs after a released client is re-created,
    before state restore — the engine uses it to rebuild device-bound
    objects (its mesh).

    ``bucket_bytes`` (optional) chunks offload and restore into size-bounded
    transfer buckets (see module docstring); None = whole-tree transfers.
    """

    def __init__(
        self,
        get_state,
        set_state,
        on_reacquire: Optional[Callable[[], None]] = None,
        bucket_bytes: Optional[int] = None,
        quant_mode: str = "off",
        quant_hot_head: bool = True,
        on_transfer: Optional[Callable[[str, int, float], None]] = None,
        peek_state: Optional[Callable[[], Any]] = None,
    ) -> None:
        self._get_state = get_state
        self._set_state = set_state
        self._on_reacquire = on_reacquire
        self.bucket_bytes = bucket_bytes
        #: cost-oracle feed (utils/costs.py): ``on_transfer(kind, bytes,
        #: seconds)`` fires after each completed transfer window
        #: (sleep.d2h / wake.h2d / swap.d2h / swap.h2d) with the WIRE
        #: bytes and wall seconds that window actually took — the
        #: measured GiB/s the pre-transfer pricing divides by. Best
        #: effort: a raising callback never fails an actuation.
        self.on_transfer = on_transfer
        #: side-effect-free state reader for pricing (``plan_swap``):
        #: the default ``get_state`` may quiesce the engine (drain an
        #: in-flight decode chunk), which a dry-run must never do
        self._peek_state = peek_state or get_state
        #: compressed actuation (docs/perf.md "Compressed actuation"):
        #: level-1 offloads quantize eligible weight leaves to int8/fp8 on
        #: device, only the payload crosses the boundary, and wake
        #: dequantizes on device. "off" (default) keeps every transfer
        #: bit-exact.
        self.quant_mode = "" if quant_mode in ("", "off") else quant_mode
        self.quant_hot_head = quant_hot_head
        #: per-leaf TransferQuant-or-None aligned with the flatten order of
        #: ``_host_state`` while quantized-slept (None = fully fp sleep)
        self._quant_meta: Optional[list] = None
        #: int8 scales cached across cycles (aligned with the state's
        #: flatten order): re-quantizing with the SAME scale makes every
        #: cycle after the first reproduce identical payload bits
        self._quant_scales: Optional[list] = None
        self._level = SleepLevel.AWAKE
        self._host_state: Optional[Any] = None
        self._shardings: Optional[Any] = None  # sharding objects (no release)
        self._sharding_specs: Optional[Any] = None  # device-free (release)
        #: multi-process offload: per-leaf [(device, np shard), ...] — a
        #: cross-process array is not fully addressable, so each gang
        #: process stages exactly its own shards
        self._staged: Optional[list] = None
        self._staged_meta: Optional[list] = None  # per-leaf (shape, sharding)
        self._treedef: Optional[Any] = None
        self._released = False
        self._client_gone = False
        self._use_memory_kind = _platform_supports_host_memory()
        self.stats = _Stats()

    @property
    def is_sleeping(self) -> bool:
        return self._level != SleepLevel.AWAKE

    @property
    def level(self) -> SleepLevel:
        return self._level

    @property
    def devices_released(self) -> bool:
        """The chip is free for another process: the sleep released the
        backend AND the client object really died (engine/device.py)."""
        return self._released and self._client_gone

    def _notify_transfer(
        self, kind: str, nbytes: int, seconds: float
    ) -> None:
        """Feed one completed transfer window to the cost oracle's
        bandwidth EWMAs; zero-byte / zero-time windows and callback
        failures are dropped (telemetry must never fail an edge)."""
        if self.on_transfer is None or nbytes <= 0 or seconds <= 0:
            return
        try:
            self.on_transfer(kind, nbytes, seconds)
        except Exception:  # noqa: BLE001 — telemetry is best-effort
            pass

    # -- chunked transfer primitives -----------------------------------------

    def _quant_plan(self, state) -> Optional[list]:
        """Per-leaf quantize-for-transfer flags for this state, or None
        when the mode is off / nothing is eligible (multi-host staged
        offloads never quantize — shards reassemble bit-for-bit).
        Single-process tp meshes DO quantize: the quantize/dequantize
        ops run shard-local on device (models/quant.py:quantize_leaf)
        and only the payload's shards cross the boundary."""
        if not self.quant_mode or jax.process_count() > 1:
            return None
        plan = transfer_quant.transfer_quant_plan(
            state, hot_head=self.quant_hot_head
        )
        return plan if any(plan) else None

    def _cached_scale(self, i: int, leaf) -> Optional[Any]:
        """The int8 scale this leaf quantized with on its first offload
        (idempotence: same scale -> same payload bits every cycle); None
        until then or when the state structure changed."""
        if self._quant_scales is None or i >= len(self._quant_scales):
            return None
        s = self._quant_scales[i]
        if s is None:
            return None
        want = tuple(leaf.shape[: len(leaf.shape) - 2]) + (
            1,
            leaf.shape[-1],
        )
        return s if tuple(s.shape) == want else None

    def _note_wake_quant(self, metas: Optional[list]) -> None:
        """After a quantized wake (or swap commit): remember the scales so
        the next offload re-quantizes to identical bits, and drop the
        now-consumed payload metadata."""
        if metas is not None and any(m is not None for m in metas):
            self._quant_scales = [
                (m.scale if m is not None else None) for m in metas
            ]
        self._quant_meta = None

    def _offload_leaves(
        self, leaves: list, to_numpy: bool, plan: Optional[list] = None
    ) -> tuple:
        """Device -> host, bucket by bucket: each bucket's device HBM is
        freed as soon as its host copy lands, so peak duplicated state is
        ~one bucket (whole tree when bucket_bytes is None — one batched
        transfer, the round-trip-optimal default on high-latency links).

        ``to_numpy`` stages into plain numpy (release path / no
        memory-kind backend); otherwise into pinned_host jax arrays.

        ``plan`` (per-leaf flags from :meth:`_quant_plan`) quantizes the
        flagged leaves ON DEVICE first, so only the int8/fp8 payload
        crosses the boundary. Returns ``(host_leaves, metas)`` — metas is
        the aligned TransferQuant-or-None list (None when no plan)."""
        host: list = [None] * len(leaves)
        metas: Optional[list] = [None] * len(leaves) if plan else None
        mode = self.quant_mode

        def wire_nb(i):
            if plan and plan[i]:
                return transfer_quant.payload_nbytes(leaves[i].shape, mode)
            return leaves[i].nbytes

        buckets = partition_buckets(
            [wire_nb(i) for i in range(len(leaves))], self.bucket_bytes
        )
        # tracing hoisted out of the bucket loop: disabled = zero per-chunk
        # allocations on this hot path (utils/tracing.py)
        traced = tracing.enabled()
        parent = tracing.current_context() if traced else None
        for bucket in buckets:
            sp = None
            if traced:
                sp = tracing.begin(
                    "sleep.d2h", parent=parent, activate=False,
                    bytes=sum(wire_nb(i) for i in bucket),
                    leaves=len(bucket),
                )
            payload_devs: list = []
            try:
                srcs = []
                for i in bucket:
                    if plan and plan[i]:
                        p, meta = transfer_quant.quantize_leaf(
                            leaves[i], mode,
                            scale=self._cached_scale(i, leaves[i]),
                        )
                        metas[i] = meta
                        payload_devs.append(p)
                        srcs.append(p)
                    else:
                        srcs.append(leaves[i])
                if to_numpy:
                    # force materialized copies: device_get can return
                    # views aliasing the device buffer on CPU-family
                    # backends, and a staging buffer must survive the
                    # buffer delete below (and client destruction on the
                    # release path) on its own
                    copies = [
                        np.array(h, copy=True)
                        for h in jax.device_get(srcs)
                    ]
                else:
                    copies = jax.device_put(
                        srcs,
                        [
                            s.sharding.with_memory_kind("pinned_host")
                            for s in srcs
                        ],
                    )
                    copies = jax.block_until_ready(copies)
            except BaseException as e:
                # the failing bucket is what a failed-sleep trace must
                # show (same discipline as the swap/coldload paths)
                if sp is not None:
                    sp.set(error=f"{type(e).__name__}: {e}")
                    sp.end()
                raise
            for i, h in zip(bucket, copies):
                host[i] = h
            for p in payload_devs:
                p.delete()  # the on-device staging payload served its copy
            for i in bucket:
                leaves[i].delete()
            if sp is not None:
                sp.end()
        return host, metas

    def _restore_leaves(
        self,
        leaves: list,
        targets: list,
        free_host: bool,
        metas: Optional[list] = None,
    ) -> list:
        """Host -> device, bucket by bucket: each bucket blocks before the
        next is issued (bounds the in-flight transfer window) and, with
        ``free_host``, releases its pinned-host source as it lands.

        ``metas`` (aligned TransferQuant-or-None) marks quantized-payload
        leaves: the payload moves H2D, then dequantizes ON DEVICE — the
        dequant of bucket k is dispatched async and rides under bucket
        k+1's transfer, the same overlap discipline AOT warmup uses. On
        meshes the payload lands pre-sharded (device_put to the leaf's
        original NamedSharding) and the expansion runs shard-local; a
        payload recording a shard view (meta.spec) is cross-checked
        against its placement target — expanding under a different
        sharding than it quantized from must fail loudly, never serve."""
        if metas is not None:
            for i, m in enumerate(metas):
                if m is None or m.spec is None:
                    continue
                tspec = getattr(targets[i], "spec", None)
                if tspec is not None and str(tspec) != m.spec:
                    raise RuntimeError(
                        f"quantized payload {i} was sharded {m.spec} but "
                        f"would restore to {tspec}"
                    )
        out: list = [None] * len(leaves)
        buckets = partition_buckets(
            [x.nbytes for x in leaves], self.bucket_bytes
        )
        traced = tracing.enabled()
        parent = tracing.current_context() if traced else None
        deq_payloads: list = []  # device payloads to free once dequants land
        for bucket in buckets:
            sp = None
            if traced:
                sp = tracing.begin(
                    "wake.h2d", parent=parent, activate=False,
                    bytes=sum(leaves[i].nbytes for i in bucket),
                    leaves=len(bucket),
                )
            try:
                restored = jax.device_put(
                    [leaves[i] for i in bucket],
                    [targets[i] for i in bucket],
                )
                restored = jax.block_until_ready(restored)
            except BaseException as e:
                if sp is not None:
                    sp.set(error=f"{type(e).__name__}: {e}")
                    sp.end()
                raise
            for i, d in zip(bucket, restored):
                if metas is not None and metas[i] is not None:
                    # async dispatch: the expansion runs while the next
                    # bucket's H2D is in flight
                    out[i] = transfer_quant.dequantize_leaf(d, metas[i])
                    deq_payloads.append(d)
                else:
                    out[i] = d
            if free_host:
                for i in bucket:
                    leaves[i].delete()
            if sp is not None:
                sp.end()
        if deq_payloads:
            t_dq = time.monotonic()
            jax.block_until_ready([o for o in out if o is not None])
            dq_bytes = sum(p.nbytes for p in deq_payloads)
            for p in deq_payloads:
                p.delete()
            # the non-hidden dequant tail (most expansion rode under the
            # bucket transfers): the cost oracle's quant-overhead signal
            self._notify_transfer(
                "quant.dequant", dq_bytes, time.monotonic() - t_dq
            )
        return out

    # -- edges ---------------------------------------------------------------

    def sleep(self, level: int = 1, release: bool = False) -> Dict[str, Any]:
        level = SleepLevel(level)
        if level == SleepLevel.AWAKE:
            raise ValueError("sleep level must be 1 or 2")
        if release and jax.process_count() > 1:
            raise ValueError(
                "device release is not supported for multi-host gangs: "
                "every process would have to drop and re-join the "
                "distributed client in lockstep"
            )
        if self._level != SleepLevel.AWAKE:
            if level == SleepLevel.L2_DISCARD and self._level == SleepLevel.L1_HOST_OFFLOAD:
                # Escalate 1 -> 2: give the host RAM back too.
                if self._use_memory_kind and not self._released and self._host_state is not None:
                    for leaf in jax.tree.leaves(self._host_state):
                        leaf.delete()
                self._host_state = None
                # staged multi-host shards (and their reassembly metadata)
                # are host RAM too: escalation must free all of it
                self._staged = None
                self._staged_meta = None
                self._treedef = None
                # the payload metadata dies with the host state; the scale
                # cache too — a level-2 wake reinitializes weights, and
                # stale scales must never quantize fresh content
                self._quant_meta = None
                self._quant_scales = None
                self._level = SleepLevel.L2_DISCARD
                self.stats.bytes_offloaded = 0
                self.stats.bytes_offloaded_full = 0
                self.stats.last_quant = "off"
            return self.describe()
        t0 = time.monotonic()
        state = self._get_state()
        nbytes = sum(x.nbytes for x in jax.tree.leaves(state))
        plan = self._quant_plan(state) if level == SleepLevel.L1_HOST_OFFLOAD else None
        #: the pure offload window (quiesce/release excluded): the
        #: bandwidth figure the cost oracle divides by
        off_window = 0.0
        if release:
            # Plain numpy staging: pinned_host buffers belong to the client
            # we are about to destroy. Save device-free sharding specs as a
            # flat list (the specs are tuples, which pytrees would flatten).
            self._sharding_specs = [
                sharding_spec(x) for x in jax.tree.leaves(state)
            ]
            self._shardings = None
            if level == SleepLevel.L1_HOST_OFFLOAD:
                # batched fetch per bucket (per-leaf np.asarray pays one
                # round trip per array); returns plain numpy, which
                # survives the client destruction below
                leaves, treedef = jax.tree.flatten(state)
                off_t0 = time.monotonic()
                host_leaves, metas = self._offload_leaves(
                    leaves, to_numpy=True, plan=plan
                )
                off_window = time.monotonic() - off_t0
                self._host_state = jax.tree.unflatten(treedef, host_leaves)
                self._quant_meta = metas
            else:
                self._host_state = None
        elif jax.process_count() > 1:
            # Multi-host gang: every process sleeps in lockstep
            # (engine/multihost.py broadcasts the sleep), each staging its
            # OWN shards — the array is not fully addressable, so neither
            # the memory-kind transfer nor np.asarray of the whole can run.
            self._shardings = None
            self._sharding_specs = None
            if level == SleepLevel.L1_HOST_OFFLOAD:
                leaves, self._treedef = jax.tree.flatten(state)
                shard_lists = [list(x.addressable_shards) for x in leaves]
                # one batched fetch across every leaf's local shards
                datas = jax.device_get(
                    [[s.data for s in shards] for shards in shard_lists]
                )
                self._staged = [
                    [(s.device, d) for s, d in zip(shards, ds)]
                    for shards, ds in zip(shard_lists, datas)
                ]
                self._staged_meta = [(x.shape, x.sharding) for x in leaves]
            else:
                self._staged = None
            self._host_state = None
        else:
            self._shardings = jax.tree.map(lambda x: x.sharding, state)
            self._sharding_specs = None
            if level == SleepLevel.L1_HOST_OFFLOAD:
                # batched transfer per bucket (whole tree = one bucket by
                # default: per-leaf device_puts pay one round trip per
                # array on high-latency links); device HBM is freed
                # bucket-by-bucket inside _offload_leaves
                leaves, treedef = jax.tree.flatten(state)
                off_t0 = time.monotonic()
                host_leaves, metas = self._offload_leaves(
                    leaves, to_numpy=not self._use_memory_kind, plan=plan
                )
                off_window = time.monotonic() - off_t0
                self._host_state = jax.tree.unflatten(treedef, host_leaves)
                self._quant_meta = metas
            else:
                self._host_state = None
        # Release HBM now, not at GC time (chunked offload already deleted
        # its leaves bucket-by-bucket; delete() is idempotent on them).
        for leaf in jax.tree.leaves(state):
            leaf.delete()
        del state
        self._set_state(None)
        if release:
            # this frame's last references to (deleted) device arrays: a
            # client that is still referenced keeps the chip open
            leaves = leaf = None  # noqa: F841
            self._client_gone = release_devices()
            self._released = True
            self.stats.releases_total += 1
        self._level = level
        self.stats.last_sleep_seconds = time.monotonic() - t0
        if level == SleepLevel.L1_HOST_OFFLOAD:
            self.stats.bytes_offloaded_full = nbytes
            if self._host_state is not None and self._quant_meta is not None:
                # actual host residency: payload + scale bytes for the
                # quantized leaves, full precision for the rest
                self.stats.bytes_offloaded = sum(
                    x.nbytes for x in jax.tree.leaves(self._host_state)
                ) + sum(
                    m.scale_nbytes
                    for m in self._quant_meta
                    if m is not None
                )
                self.stats.last_quant = self.quant_mode or "off"
            else:
                self.stats.bytes_offloaded = nbytes
                self.stats.last_quant = "off"
        else:
            self.stats.bytes_offloaded = 0
            self.stats.bytes_offloaded_full = 0
            self.stats.last_quant = "off"
        self.stats.sleeps_total += 1
        self.stats.last_sleep_transfer_s = off_window
        if level == SleepLevel.L1_HOST_OFFLOAD and self._staged is None:
            # gang-staged offloads excluded: per-shard staging is not the
            # single-link d2h the oracle prices. The EWMA sees the pure
            # offload window — the engine quiesce (drain_inflight) and a
            # device release also inside last_sleep_seconds would
            # otherwise anchor the d2h bandwidth arbitrarily low.
            self._notify_transfer(
                "sleep.d2h", self.stats.bytes_offloaded, off_window
            )
        return self.describe()

    def wake_up(self, reinit=None) -> Dict[str, Any]:
        """Restore device state. For level-2 sleep, `reinit()` must rebuild
        the state (e.g. re-read the checkpoint)."""
        if self._level == SleepLevel.AWAKE:
            return self.describe()
        restored_from_staged = (
            self._level == SleepLevel.L1_HOST_OFFLOAD
            and self._staged is not None
        )
        t0 = time.monotonic()
        if self._released:
            reacquire_devices()
            self.stats.last_reacquire_seconds = time.monotonic() - t0
            if self._on_reacquire is not None:
                self._on_reacquire()
        if self._level == SleepLevel.L1_HOST_OFFLOAD and self._staged is not None:
            # multi-process restore: reassemble each global array from this
            # process's staged shards (every gang process does the same)
            from jax import make_array_from_single_device_arrays

            # this process's restore figures (the _host_state branch sets
            # its own below): without them a gang wake's flight record
            # would carry stale/zero bytes
            self.stats.last_wake_bytes = sum(
                buf.nbytes for shards in self._staged for _, buf in shards
            )
            t_restore0 = time.monotonic()
            # one batched upload of every leaf's local shards
            all_arrs = jax.device_put(
                [[buf for _, buf in shards] for shards in self._staged],
                [[d for d, _ in shards] for shards in self._staged],
            )
            restored = []
            for (shape, sharding), arrs in zip(self._staged_meta, all_arrs):
                restored.append(
                    make_array_from_single_device_arrays(shape, sharding, arrs)
                )
            state = jax.tree.unflatten(self._treedef, restored)
            state = jax.block_until_ready(state)
            self.stats.last_wake_transfer_s = time.monotonic() - t_restore0
            self._staged = None
            self._staged_meta = None
            self._treedef = None
        elif self._level == SleepLevel.L1_HOST_OFFLOAD:
            assert self._host_state is not None
            leaves, treedef = jax.tree.flatten(self._host_state)
            metas = self._quant_meta
            self.stats.last_wake_bytes = sum(x.nbytes for x in leaves) + (
                sum(m.scale_nbytes for m in metas if m is not None)
                if metas is not None
                else 0
            )
            if self._released:
                assert self._sharding_specs is not None
                # bucket-by-bucket: shardings are rebuilt on the fresh
                # client and each bucket lands before the next is issued
                # (bounded in-flight window; whole tree = one bucket by
                # default)
                restored = self._restore_leaves(
                    leaves,
                    [rebuild_spec(spec) for spec in self._sharding_specs],
                    free_host=False,
                    metas=metas,
                )
                state = jax.tree.unflatten(treedef, restored)
            else:
                # batched transfer per bucket (see sleep); pinned-host
                # sources are released as their bucket lands
                shardings, _ = jax.tree.flatten(self._shardings)
                restored = self._restore_leaves(
                    leaves, shardings, free_host=self._use_memory_kind,
                    metas=metas,
                )
                state = jax.tree.unflatten(treedef, restored)
            self._note_wake_quant(metas)
        else:
            if reinit is None:
                raise ValueError("level-2 wake requires a reinit callback")
            # fresh state: cached scales describe weights that no longer
            # exist and must never quantize the reinitialized content
            self._quant_scales = None
            self._quant_meta = None
            state = reinit()
        restored_from_host = (
            self._level == SleepLevel.L1_HOST_OFFLOAD
            and self._host_state is not None
        )
        was_released = self._released
        self._host_state = None
        self._sharding_specs = None
        self._shardings = None
        self._released = False
        self._set_state(state)
        self._level = SleepLevel.AWAKE
        self.stats.last_wake_seconds = time.monotonic() - t0
        self.stats.wakes_total += 1
        if restored_from_host:
            # the h2d window excludes client reacquisition (release
            # path): the oracle prices bytes-over-the-link, and a wake
            # after device release pays reacquire separately
            self.stats.last_wake_transfer_s = max(
                0.0,
                self.stats.last_wake_seconds
                - (
                    self.stats.last_reacquire_seconds
                    if was_released
                    else 0.0
                ),
            )
            self._notify_transfer(
                "wake.h2d",
                self.stats.last_wake_bytes,
                self.stats.last_wake_transfer_s,
            )
        elif not restored_from_staged:
            # reinit (level-2) wake: no host payload moved; the staged
            # (gang) branch set its own figures and stays out of the
            # single-link EWMA by design
            self.stats.last_wake_transfer_s = 0.0
            self.stats.last_wake_bytes = 0
        return self.describe()

    def warm_quant_ops(self) -> int:
        """Run the transfer quantize/dequantize graphs once per distinct
        eligible (shape, dtype) over the engine's REAL leaves (the op
        cache distinguishes the live committed arrays from synthetic
        stand-ins), so the FIRST real quantized actuation doesn't pay
        their one-time op compiles inside its transfer window — and the
        cost oracle's first measured bandwidth windows describe
        steady-state transfer, not compile stalls (utils/costs.py). All
        three graphs warm: fresh-scale quantize, cached-scale
        re-quantize (what every cycle after the first runs), and the
        on-device dequant. quantize_leaf is pure — the weights are read,
        never changed; peak extra HBM is one payload per shape, freed
        leaf-by-leaf. No-op when quant is off or in a gang. Returns the
        number of distinct shapes warmed."""
        if not self.quant_mode or jax.process_count() > 1:
            return 0
        state = self._peek_state()
        plan = self._quant_plan(state)
        if not plan:
            return 0
        leaves = jax.tree.leaves(state)
        seen = set()
        for leaf, flagged in zip(leaves, plan):
            if not flagged:
                continue
            key = (tuple(leaf.shape), str(leaf.dtype))
            if key in seen:
                continue
            seen.add(key)
            p, meta = transfer_quant.quantize_leaf(leaf, self.quant_mode)
            p2, _ = transfer_quant.quantize_leaf(
                leaf, self.quant_mode, scale=meta.scale
            )
            d = transfer_quant.dequantize_leaf(p, meta)
            jax.block_until_ready(d)
            for a in (p, p2, d):
                a.delete()
        return len(seen)

    def quant_state(self) -> str:
        """Transfer mode of the currently-slept payload ("off" when the
        host state is full precision / not level-1 slept)."""
        if self._quant_meta is not None and any(
            m is not None for m in self._quant_meta
        ):
            return self._quant_meta[
                next(
                    i for i, m in enumerate(self._quant_meta)
                    if m is not None
                )
            ].mode
        return "off"

    def describe(self) -> Dict[str, Any]:
        return {
            "is_sleeping": self.is_sleeping,
            "level": int(self._level),
            "devices_released": self.devices_released,
            # the slept state sits in pinned_host jax arrays (a released
            # or gang-staged sleep stages through numpy instead)
            "pinned_host": bool(
                self._level == SleepLevel.L1_HOST_OFFLOAD
                and self._use_memory_kind
                and not self._released
                and self._staged is None
            ),
            "bytes_offloaded": self.stats.bytes_offloaded,
            "bytes_offloaded_full": self.stats.bytes_offloaded_full,
            "quant": self.stats.last_quant,
            "last_sleep_seconds": self.stats.last_sleep_seconds,
            "last_wake_seconds": self.stats.last_wake_seconds,
            "last_reacquire_seconds": self.stats.last_reacquire_seconds,
        }


@dataclass
class _TransferPlan:
    """Byte-exact schedule of one hot-swap transfer, computed from
    shapes / dtypes / shardings / digests alone — no data read, no byte
    moved. Shared by the executing :func:`swap_states` and the dry-run
    :func:`plan_swap` (the cost oracle's pre-transfer pricing), so a
    priced swap and the swap it prices can never disagree on bytes."""

    qmode: str  #: "" or the transfer-quant mode in effect
    out_plan: Optional[list]  #: per-leaf on-device quantize flags (out)
    #: per-leaf host-staging quantize flags for a full-precision
    #: incoming entry under quant mode (None when not applicable); only
    #: the moving leaves are actually staged
    in_stage_plan: Optional[list]
    in_metas: list  #: pre-existing TransferQuant-or-None (quantized-slept)
    reuse_pairs: List[tuple]  #: (incoming idx, outgoing idx) digest matches
    move_out: List[int]
    move_in: List[int]
    nb_out: List[int]
    nb_in: List[int]
    wnb_out: List[int]  #: wire bytes per outgoing leaf
    wnb_in: List[int]  #: wire bytes per incoming leaf
    buckets_out: List[List[int]]
    buckets_in: List[List[int]]
    bytes_out: int
    bytes_in: int
    bytes_full: int
    deduped_bytes: int
    moved_bytes: int
    quant_leaves: int
    quant_active: bool
    quant_mode_used: str


def _plan_transfer(
    out_mgr: SleepManager,
    in_mgr: SleepManager,
    state_out: Any,
    leaves_out: list,
    shard_out: list,
    nb_out: List[int],
    in_host_state: Any,
    leaves_in: list,
    shard_in: list,
    nb_in: List[int],
    bucket_bytes: int,
    out_digests: Optional[Dict[str, str]],
    in_digests: Optional[Dict[str, str]],
    quant: Optional[str],
) -> _TransferPlan:
    """The planning phase of a hot-swap (see :func:`swap_states` for the
    semantics of delta matching and quantized staging): which leaves
    move, which are digest-matched away, and exactly how many wire bytes
    each direction carries. Pure — reads shapes/digests only."""
    qmode = quant if quant is not None else (out_mgr.quant_mode or "off")
    qmode = "" if qmode in ("", "off") else qmode
    out_plan = out_mgr._quant_plan(state_out) if qmode else None
    in_metas: list = (
        list(in_mgr._quant_meta)
        if in_mgr._quant_meta is not None
        else [None] * len(leaves_in)
    )

    # Delta matching (swap_states docstring): pair incoming leaves with
    # content-identical live outgoing leaves by digest; matched pairs are
    # excluded from BOTH transfer directions. A quantized-slept incoming
    # leaf's digest names its ORIGINAL full-precision content, so the
    # dtype check compares against the payload's origin dtype.
    reuse_pairs: List[tuple] = []
    if out_digests and in_digests:
        dl_out = _aligned(state_out, out_digests)
        dl_in = _aligned(in_host_state, in_digests)
        by_digest: Dict[str, List[int]] = {}
        for j, d in enumerate(dl_out):
            if d is not None:
                by_digest.setdefault(d, []).append(j)
        for i, d in enumerate(dl_in):
            cands = by_digest.get(d) if d is not None else None
            if not cands:
                continue
            j = cands[0]
            lo, li = leaves_out[j], leaves_in[i]
            li_dtype = (
                np.dtype(in_metas[i].orig_dtype)
                if in_metas[i] is not None
                else li.dtype
            )
            if (
                tuple(lo.shape) == tuple(li.shape)
                and lo.dtype == li_dtype
                and shard_out[j] == shard_in[i]
            ):
                reuse_pairs.append((i, j))
                cands.pop(0)
    reused_in = {i for i, _ in reuse_pairs}
    reused_out = {j for _, j in reuse_pairs}
    move_out = [i for i in range(len(leaves_out)) if i not in reused_out]
    move_in = [i for i in range(len(leaves_in)) if i not in reused_in]
    move_in_set = set(move_in)

    # Host-side staging quantization applies to a full-precision incoming
    # entry under quant mode — but only its MOVING leaves are staged; the
    # wire bytes of a to-be-staged leaf are exactly payload_nbytes (the
    # int8/fp8 payload plus its scale), predictable from the shape alone.
    in_stage_plan: Optional[list] = None
    if qmode and in_mgr._quant_meta is None:
        in_stage_plan = transfer_quant.transfer_quant_plan(
            in_host_state, hot_head=in_mgr.quant_hot_head
        )

    wnb_out = [
        transfer_quant.payload_nbytes(leaves_out[i].shape, qmode)
        if out_plan and out_plan[i]
        else nb_out[i]
        for i in range(len(leaves_out))
    ]

    def _wire_in(i: int) -> int:
        if in_metas[i] is not None:
            # already a payload (quantized-slept): leaf bytes + scale
            return nb_in[i] + in_metas[i].scale_nbytes
        if in_stage_plan and in_stage_plan[i] and i in move_in_set:
            return transfer_quant.payload_nbytes(leaves_in[i].shape, qmode)
        return nb_in[i]

    wnb_in = [_wire_in(i) for i in range(len(leaves_in))]
    buckets_out = [
        [move_out[k] for k in b]
        for b in partition_buckets(
            [wnb_out[i] for i in move_out], bucket_bytes
        )
    ]
    buckets_in = [
        [move_in[k] for k in b]
        for b in partition_buckets(
            [wnb_in[i] for i in move_in], bucket_bytes
        )
    ]
    bytes_out = sum(wnb_out)
    bytes_in = sum(wnb_in)
    bytes_full = sum(nb_out) + sum(
        nb_in[i]
        if in_metas[i] is None
        else int(
            np.prod(leaves_in[i].shape)
            * np.dtype(in_metas[i].orig_dtype).itemsize
        )
        for i in range(len(leaves_in))
    )
    deduped_bytes = sum(wnb_out[j] for j in reused_out) + sum(
        wnb_in[i] for i in reused_in
    )
    quant_leaves = sum(
        1 for i in move_out if out_plan and out_plan[i]
    ) + sum(
        1
        for i in move_in
        if in_metas[i] is not None
        or (in_stage_plan and in_stage_plan[i])
    )
    quant_active = (
        bool(out_plan)
        or any(m is not None for m in in_metas)
        or bool(
            in_stage_plan
            and any(in_stage_plan[i] for i in move_in)
        )
    )
    quant_mode_used = (
        qmode or next((m.mode for m in in_metas if m is not None), "off")
        if quant_active
        else "off"
    )
    return _TransferPlan(
        qmode=qmode,
        out_plan=out_plan,
        in_stage_plan=in_stage_plan,
        in_metas=in_metas,
        reuse_pairs=reuse_pairs,
        move_out=move_out,
        move_in=move_in,
        nb_out=nb_out,
        nb_in=nb_in,
        wnb_out=wnb_out,
        wnb_in=wnb_in,
        buckets_out=buckets_out,
        buckets_in=buckets_in,
        bytes_out=bytes_out,
        bytes_in=bytes_in,
        bytes_full=bytes_full,
        deduped_bytes=deduped_bytes,
        moved_bytes=bytes_out + bytes_in - deduped_bytes,
        quant_leaves=quant_leaves,
        quant_active=quant_active,
        quant_mode_used=quant_mode_used,
    )


def _check_swap_preconditions(
    out_mgr: SleepManager, in_mgr: SleepManager
) -> None:
    if out_mgr.is_sleeping:
        raise ValueError("swap-out model must be awake")
    if (
        in_mgr.level != SleepLevel.L1_HOST_OFFLOAD
        or in_mgr._host_state is None
    ):
        raise ValueError(
            "swap-in model must be asleep at level 1 with host-resident "
            "state (level-2 / multi-host-staged states cannot hot-swap)"
        )
    if in_mgr._released:
        raise ValueError(
            "swap-in model was released; hot-swap keeps one live client"
        )
    if jax.process_count() > 1:
        raise ValueError("hot-swap is not supported for multi-host gangs")


def plan_swap(
    out_mgr: SleepManager,
    in_mgr: SleepManager,
    bucket_bytes: Optional[int] = None,
    out_digests: Optional[Dict[str, str]] = None,
    in_digests: Optional[Dict[str, str]] = None,
    quant: Optional[str] = None,
) -> Dict[str, Any]:
    """Price a hot-swap WITHOUT moving a byte: the identical planning
    code :func:`swap_states` executes (same preconditions, same delta
    matching, same quantized-payload sizing), run against a
    side-effect-free peek of the outgoing state — so the predicted wire
    bytes are **exact by construction** for any swap the planner can
    see (the delta-sibling and quantized CI gates pin this). Returns the
    byte keys of the swap metrics dict plus bucket counts (what the
    seconds model divides by measured bandwidth)."""
    _check_swap_preconditions(out_mgr, in_mgr)
    bucket_bytes = bucket_bytes or DEFAULT_SWAP_BUCKET_BYTES
    state_out = out_mgr._peek_state()
    leaves_out, _ = jax.tree.flatten(state_out)
    shard_out = [x.sharding for x in leaves_out]
    nb_out = [x.nbytes for x in leaves_out]
    leaves_in, _ = jax.tree.flatten(in_mgr._host_state)
    shard_in, _ = jax.tree.flatten(in_mgr._shardings)
    nb_in = [x.nbytes for x in leaves_in]
    plan = _plan_transfer(
        out_mgr, in_mgr, state_out, leaves_out, shard_out, nb_out,
        in_mgr._host_state, leaves_in, shard_in, nb_in,
        bucket_bytes, out_digests, in_digests, quant,
    )
    return {
        "bytes_out": plan.bytes_out,
        "bytes_in": plan.bytes_in,
        "bytes_moved": plan.moved_bytes,
        "bytes_deduped": plan.deduped_bytes,
        # per-direction bytes that actually cross the device boundary
        # (totals minus the digest-matched leaves): what the seconds
        # model divides by measured per-direction bandwidth
        "wire_out": sum(plan.wnb_out[i] for i in plan.move_out),
        "wire_in": sum(plan.wnb_in[i] for i in plan.move_in),
        "deduped_leaves": len(plan.reuse_pairs),
        "quant": plan.quant_mode_used,
        "quant_leaves": plan.quant_leaves,
        "bytes_full": plan.bytes_full,
        "bytes_saved_quant": max(
            0, plan.bytes_full - (plan.bytes_out + plan.bytes_in)
        ),
        "buckets_out": len(plan.buckets_out),
        "buckets_in": len(plan.buckets_in),
        "bucket_bytes": bucket_bytes,
        "leaves_out": len(leaves_out),
        "leaves_in": len(leaves_in),
    }


def swap_states(
    out_mgr: SleepManager,
    in_mgr: SleepManager,
    bucket_bytes: Optional[int] = None,
    overlapped: bool = True,
    out_digests: Optional[Dict[str, str]] = None,
    in_digests: Optional[Dict[str, str]] = None,
    quant: Optional[str] = None,
) -> Dict[str, Any]:
    """Overlapped model hot-swap: stream the awake model behind ``out_mgr``
    to host while restoring ``in_mgr``'s slept (level-1, non-released) state
    to device, double-buffered over size-bounded buckets.

    Schedule: the device->host DMA of outgoing bucket k runs concurrently
    with the host->device DMA of incoming bucket k-1 (issued into the HBM
    bucket k-1's completion just freed), so swap latency approaches
    max(sleep, wake) instead of sleep + wake and peak extra HBM is bounded
    by ~one bucket. In-flight bytes are bounded by ~3 buckets — the
    double-buffered outgoing pair plus one incoming (the SLO window;
    `peak_bytes_in_flight` in the returned metrics reports the measured
    value).

    On memory-kind backends (TPU) the concurrency comes from jax's async
    transfer dispatch; on the numpy-staging fallback (CPU tests) transfers
    are synchronous, so the incoming direction runs on a worker thread —
    the staging copies release the GIL, making the overlap real there too.

    Ends with ``out_mgr`` asleep at level 1 (host-resident, poolable) and
    ``in_mgr`` awake. Bit-exact: whole leaves move, nothing is recomputed.
    Returns a metrics dict (timings, overlap fraction, bytes, buckets).

    **Transactional**: no destructive operation on the incoming model's
    host state happens before the swap commits (its pinned-host copies are
    freed at commit, not bucket-by-bucket — peak pinned-host during the
    swap is therefore the full incoming model plus the growing outgoing
    copy, the price of recoverability), and the outgoing model's host
    copies always land before their device HBM is freed. A mid-transfer
    failure (HBM OOM, injected ``swap.d2h``/``swap.h2d`` fault) is rolled
    back: partially-restored incoming device buckets are dropped, the
    outgoing model's already-freed device leaves are re-uploaded from
    their host copies, and :class:`SwapRolledBack` is raised — both models
    end exactly as they began. Only a failure *during that rollback*
    raises :class:`SwapRollbackFailed` (state genuinely lost).

    ``overlapped=False`` runs the identical code path on a strictly
    sequential schedule (every outgoing bucket lands before the first
    incoming one is issued) — the reference tests/test_swap.py and
    tests/test_quant_swap.py compare the overlapped schedule against.

    **Delta-aware** (``out_digests``/``in_digests``, flat weight key ->
    content digest — engine/chunk_store.py): leaves the two models share
    by content hash never cross the device boundary at all. A matched
    incoming leaf takes OVER the outgoing model's live device array (same
    bytes, by digest), and the incoming pool entry's host copy becomes
    the outgoing model's slept host state — so only the *delta* between
    sibling fine-tune variants moves over PCIe, in both directions.
    Matches additionally require equal shape/dtype/sharding, and the
    reuse is applied only at commit: a rollback sees untouched leaves.
    Reported as ``bytes_moved`` / ``bytes_deduped`` (and the
    ``swap.delta`` trace span). ``None`` digests = the pre-delta full
    transfer, bit-for-bit the old behavior.

    **Quantized transfers** (``quant="int8"|"fp8"``, default = the
    outgoing manager's mode; docs/perf.md "Compressed actuation"):
    eligible outgoing weight leaves quantize ON DEVICE and only the
    payload crosses PCIe; an incoming model slept quantized moves its
    payload and dequantizes ON DEVICE after each bucket lands (the
    expansion rides under the next bucket's transfer); an incoming model
    slept at full precision gets a host-side quantized *staging copy* for
    the transfer while its pooled host state is never touched — a
    rollback re-pools it bit-exact. The transactional contract holds:
    rolled-back outgoing leaves are re-uploaded from their payloads and
    dequantized with the same cached scales, reproducing the exact
    post-quantization bits every cycle after a model's first quantized
    offload (the lossy-once contract). Composes with the delta path:
    digest-matched leaves still skip both directions entirely. Byte
    metrics (``bytes_out``/``bytes_in``/``bytes_moved``) count WIRE
    bytes; ``bytes_full`` carries the uncompressed total and
    ``bytes_saved_quant`` the difference (the ``swap.quant`` span mirrors
    them).
    """
    _check_swap_preconditions(out_mgr, in_mgr)
    bucket_bytes = bucket_bytes or DEFAULT_SWAP_BUCKET_BYTES
    use_mk = out_mgr._use_memory_kind
    # Root span for the transfer phase; per-bucket child spans are created
    # only when tracing is enabled (`traced` hoisted out of the hot loop:
    # the disabled path adds no per-chunk allocations). activate=False:
    # begin/end straddle exception paths, and a leaked ContextVar token
    # would misparent later spans on this (reused executor) thread.
    root = tracing.begin("swap.transfer", activate=False, overlapped=overlapped)
    traced = root is not tracing.NOOP_SPAN
    root_ctx = root.context() if traced else None
    t_begin = time.monotonic()

    state_out = out_mgr._get_state()
    leaves_out, treedef_out = jax.tree.flatten(state_out)
    shard_out = [x.sharding for x in leaves_out]
    # leaf byte counts computed once (nbytes is a non-trivial property on
    # jax arrays) and reused for partitioning, totals, and the in-flight
    # accounting inside the transfer loop
    nb_out = [x.nbytes for x in leaves_out]
    leaves_in, treedef_in = jax.tree.flatten(in_mgr._host_state)
    shard_in, _ = jax.tree.flatten(in_mgr._shardings)
    nb_in = [x.nbytes for x in leaves_in]

    # Planning — quantized-transfer flags, delta matching, and wire-byte
    # sizing — is shared with the cost oracle's dry-run (plan_swap): the
    # exact code that prices a swap is the code that executes it, so
    # predicted and actual wire bytes can never disagree.
    plan = _plan_transfer(
        out_mgr, in_mgr, state_out, leaves_out, shard_out, nb_out,
        in_mgr._host_state, leaves_in, shard_in, nb_in,
        bucket_bytes, out_digests, in_digests, quant,
    )
    qmode = plan.qmode
    out_plan = plan.out_plan
    meta_out: list = [None] * len(leaves_out)
    in_metas = plan.in_metas
    reuse_pairs = plan.reuse_pairs
    reused_in = {i for i, _ in reuse_pairs}
    reused_out = {j for _, j in reuse_pairs}
    move_in = plan.move_in
    wnb_out, wnb_in = plan.wnb_out, plan.wnb_in
    buckets_out, buckets_in = plan.buckets_out, plan.buckets_in

    # Host-side staging quantization for a full-precision incoming entry
    # under quant mode: the payload staging copies move instead of the fp
    # host state, which stays untouched until commit (rollback re-pools it
    # bit-exact). Only leaves that actually move are staged; their wire
    # bytes were already sized by the planner (payload_nbytes — payload
    # plus scale — equals the staged array plus its metadata exactly).
    stage_in: list = [None] * len(leaves_in)
    if plan.in_stage_plan is not None:
        for i in move_in:
            if plan.in_stage_plan[i]:
                stage_in[i], in_metas[i] = transfer_quant.quantize_leaf_np(
                    np.asarray(leaves_in[i]), qmode
                )

    host_out: list = [None] * len(leaves_out)
    dev_in: list = [None] * len(leaves_in)
    bytes_out = plan.bytes_out
    bytes_in = plan.bytes_in
    bytes_full = plan.bytes_full
    deduped_bytes = plan.deduped_bytes
    moved_bytes = plan.moved_bytes
    quant_leaves = plan.quant_leaves
    if reuse_pairs and traced:
        dsp = tracing.begin(
            "swap.delta",
            parent=root_ctx,
            activate=False,
            leaves_shared=len(reuse_pairs),
            bytes_deduped=deduped_bytes,
            bytes_moved=moved_bytes,
        )
        dsp.end()
    quant_active = plan.quant_active
    quant_mode_used = plan.quant_mode_used
    if quant_active and traced:
        qsp = tracing.begin(
            "swap.quant",
            parent=root_ctx,
            activate=False,
            mode=quant_mode_used,
            leaves=quant_leaves,
            bytes_wire=bytes_out + bytes_in,
            bytes_full=bytes_full,
            bytes_saved=max(0, bytes_full - (bytes_out + bytes_in)),
        )
        qsp.end()
    bsize_out = [sum(wnb_out[i] for i in b) for b in buckets_out]
    bsize_in = [sum(wnb_in[i] for i in b) for b in buckets_in]

    in_flight = 0
    peak_in_flight = 0
    d2h_t0 = d2h_t1 = h2d_t0 = h2d_t1 = None

    #: outgoing leaf indices whose device HBM was freed (what a rollback
    #: must re-upload from host_out)
    deleted_out: set = set()
    #: incoming leaf indices whose pinned-host copies are due at commit
    #: (deferred so a rollback can re-pool the incoming entry intact)
    deferred_in_frees: List[int] = []

    def _fail_span(sp, e) -> None:
        """Record a bucket span whose transfer raised: the failing bucket
        is exactly the one a fault-drill trace must show."""
        if sp is not None:
            sp.set(error=f"{type(e).__name__}: {e}")
            sp.end()

    def _issue_d2h(k):
        nonlocal in_flight, peak_in_flight
        sp = None
        if traced:
            sp = tracing.begin(
                "swap.d2h", parent=root_ctx, activate=False,
                bucket=k, bytes=bsize_out[k],
            )
        payload_devs: list = []
        try:
            faults.fire("swap.d2h")
            bucket = buckets_out[k]
            srcs = []
            for i in bucket:
                if out_plan and out_plan[i]:
                    # on-device quantization: only the payload crosses
                    # PCIe; cached scales keep re-quantization bit-stable
                    p, meta = transfer_quant.quantize_leaf(
                        leaves_out[i], qmode,
                        scale=out_mgr._cached_scale(i, leaves_out[i]),
                    )
                    meta_out[i] = meta
                    payload_devs.append(p)
                    srcs.append(p)
                else:
                    srcs.append(leaves_out[i])
            if use_mk:
                copies = jax.device_put(
                    srcs,
                    [
                        s.sharding.with_memory_kind("pinned_host")
                        for s in srcs
                    ],
                )
            else:
                # real copies (not views of the buffers deleted below),
                # same as the SleepManager staging path
                copies = [np.array(s, copy=True) for s in srcs]
        except BaseException as e:
            _fail_span(sp, e)
            raise
        in_flight += bsize_out[k]
        if in_flight > peak_in_flight:
            peak_in_flight = in_flight
        return k, copies, payload_devs, sp

    #: threaded (numpy-staging) mode: outgoing buffer deletes are deferred
    #: to the commit phase so the main thread never mutates client buffer
    #: state while the worker thread is mid-device_put — on these backends
    #: "device" memory is host RAM, so nothing is gained by eager frees
    deferred_deletes: List[int] = []

    #: on-device staging payloads whose frees are deferred in threaded
    #: (numpy-staging) mode — same rule as deferred_deletes below: the
    #: main thread must not mutate client buffer state mid-device_put
    deferred_payload_frees: List[Any] = []

    def _finish_d2h(pending):
        nonlocal in_flight
        k, copies, payload_devs, sp = pending
        bucket = buckets_out[k]
        if use_mk:
            try:
                copies = jax.block_until_ready(copies)
            except BaseException as e:
                _fail_span(sp, e)
                raise
        for i, h in zip(bucket, copies):
            host_out[i] = h
        if h2d_pool is None:
            for p in payload_devs:
                p.delete()  # staging payload: its host copy just landed
            for i in bucket:
                leaves_out[i].delete()  # the HBM the next h2d bucket fills
            deleted_out.update(bucket)
        else:
            deferred_payload_frees.extend(payload_devs)
            deferred_deletes.extend(bucket)
        in_flight -= bsize_out[k]
        if sp is not None:
            sp.end()

    # The incoming direction: async transfer dispatch where the backend
    # has it (memory kinds); a single worker thread where transfers are
    # synchronous (numpy staging), so the overlap stays real. EXCEPT in
    # forked children (the launcher's process model): a fork from a
    # multi-threaded parent inherits a single-threaded snapshot whose
    # other-thread lock state is frozen mid-flight, and spawning transfer
    # threads there intermittently aborts the child — the threaded overlap
    # matters at large transfers on this fallback, not on the serving path.
    import multiprocessing

    use_thread = (
        overlapped
        and not use_mk
        and multiprocessing.parent_process() is None
    )
    h2d_pool = (
        ThreadPoolExecutor(1, thread_name_prefix="swap-h2d")
        if use_thread
        else None
    )

    def _h2d_transfer(j):
        bucket = buckets_in[j]
        # staged payload (host-quantized fp entry) or the host leaf itself
        # (a payload already, for a quantized-slept entry; fp otherwise)
        return jax.device_put(
            [
                stage_in[i] if stage_in[i] is not None else leaves_in[i]
                for i in bucket
            ],
            [shard_in[i] for i in bucket],
        )

    def _issue_h2d(j):
        nonlocal in_flight, peak_in_flight, h2d_t0
        sp = None
        if traced:
            sp = tracing.begin(
                "swap.h2d", parent=root_ctx, activate=False,
                bucket=j, bytes=bsize_in[j],
            )
        try:
            faults.fire("swap.h2d")
            if h2d_t0 is None:
                h2d_t0 = time.monotonic()
            if h2d_pool is not None:
                restored = h2d_pool.submit(_h2d_transfer, j)
            else:
                restored = _h2d_transfer(j)
        except BaseException as e:
            _fail_span(sp, e)
            raise
        in_flight += bsize_in[j]
        if in_flight > peak_in_flight:
            peak_in_flight = in_flight
        return j, restored, sp

    #: device payloads of incoming quantized leaves, freed once their
    #: dequant (dispatched async below) has landed
    in_payload_devs: List[Any] = []

    def _finish_h2d(pending):
        nonlocal in_flight
        j, restored, sp = pending
        bucket = buckets_in[j]
        try:
            if h2d_pool is not None:
                restored = restored.result()
            restored = jax.block_until_ready(restored)
        except BaseException as e:
            _fail_span(sp, e)
            raise
        for i, d in zip(bucket, restored):
            if in_metas[i] is not None:
                # on-device dequant, dispatched async: the expansion to
                # full precision rides under the next bucket's transfers
                dev_in[i] = transfer_quant.dequantize_leaf(d, in_metas[i])
                in_payload_devs.append(d)
            else:
                dev_in[i] = d
        if use_mk:
            # NOT freed here: the incoming pool entry must survive intact
            # until the swap commits, so a mid-transfer failure can put it
            # back untouched
            deferred_in_frees.extend(bucket)
        in_flight -= bsize_in[j]
        if sp is not None:
            sp.end()

    # Double-buffered main loop: while outgoing bucket k drains, incoming
    # bucket k-1 rides the opposite direction into the space k-1 freed.
    # (Sequential mode: the same loop, minus the interleaved h2d issues.)
    pend_d2h = pend_h2d = None
    next_in = 0

    def _rollback() -> None:
        """Undo every side effect of a partial transfer: drop what the
        incoming model landed on device, re-upload the outgoing leaves
        whose HBM was already freed (their host copies land before the
        free, by construction), and reinstall the outgoing state. The
        incoming host tree was never touched (frees are deferred to
        commit), so the pool entry goes back intact."""
        # quiesce the in-flight incoming transfer first: its device_put
        # must land (or fail) before any buffer it touches is reclaimed
        if pend_h2d is not None:
            _, restored, _sp = pend_h2d
            if _sp is not None and not _sp.ended:
                # a span already failed by _finish_h2d keeps its error
                # attr; a genuinely in-flight one is recorded as cut
                # short by the rollback
                _sp.set(error="rolled_back")
                _sp.end()
            try:
                if h2d_pool is not None:
                    restored = restored.result()
                for a in jax.block_until_ready(restored):
                    a.delete()
            except Exception:  # noqa: BLE001 — the failed transfer itself
                pass
        if h2d_pool is not None:
            h2d_pool.shutdown(wait=True)
        # the in-flight outgoing copy: let it land and keep the host copy
        # (its device leaves are only deleted by _finish_d2h, which did
        # not run for a still-pending bucket)
        if pend_d2h is not None:
            k, copies, pdevs, _sp = pend_d2h
            if _sp is not None and not _sp.ended:
                _sp.set(error="rolled_back")
                _sp.end()
            try:
                if use_mk:
                    copies = jax.block_until_ready(copies)
                for i, h in zip(buckets_out[k], copies):
                    host_out[i] = h
                for p in pdevs:
                    p.delete()
            except Exception:  # noqa: BLE001 — the failed transfer itself
                pass
        try:
            # quantized incoming leaves have async dequants in flight:
            # they must land (or fail) before their arrays are reclaimed
            jax.block_until_ready([a for a in dev_in if a is not None])
        except Exception:  # noqa: BLE001 — a failed dequant is dropped too
            pass
        for a in dev_in:
            if a is not None:
                a.delete()
        for p in in_payload_devs:
            p.delete()
        # re-upload freed outgoing leaves, bucket-by-bucket (same bounded
        # in-flight window as the forward direction). Quantized leaves
        # re-upload their payload and dequantize on device: the cached
        # scales make the result bit-identical to the post-quantization
        # weights every cycle after the model's first quantized offload
        # (the lossy-once contract, docs/perf.md).
        idxs = sorted(deleted_out)
        for b in partition_buckets([wnb_out[i] for i in idxs], bucket_bytes):
            bidx = [idxs[i] for i in b]
            back = jax.device_put(
                [host_out[i] for i in bidx], [shard_out[i] for i in bidx]
            )
            back = jax.block_until_ready(back)
            expanded = []
            for i, a in zip(bidx, back):
                if meta_out[i] is not None:
                    d = transfer_quant.dequantize_leaf(a, meta_out[i])
                    expanded.append((a, d))
                    leaves_out[i] = d
                else:
                    leaves_out[i] = a
            if expanded:
                jax.block_until_ready([d for _, d in expanded])
                for a, _ in expanded:
                    a.delete()
        if use_mk:
            # staging copies served their purpose (re-upload done): free
            # the pinned-host bytes
            for h in host_out:
                if h is not None:
                    h.delete()
        # the re-uploaded leaves are NEW arrays; the engine must point at
        # them (their originals are deleted)
        out_mgr._set_state(jax.tree.unflatten(treedef_out, leaves_out))
        if any(m is not None for m in meta_out):
            # a rolled-back FIRST quantized offload already rounded the
            # re-uploaded leaves: cache the scales it used, so the next
            # offload re-quantizes to the identical bits instead of
            # recomputing a perturbed scale from the rounded weights
            # (which could flip roundings — a second lossy step)
            out_mgr._quant_scales = [
                (m.scale if m is not None else None) for m in meta_out
            ]

    d2h_t0 = time.monotonic()
    try:
        for k in range(len(buckets_out)):
            cur = _issue_d2h(k)
            if pend_d2h is not None:
                _finish_d2h(pend_d2h)
                pend_d2h = None
                if overlapped and next_in < len(buckets_in):
                    if pend_h2d is not None:
                        _finish_h2d(pend_h2d)
                        pend_h2d = None
                    pend_h2d = _issue_h2d(next_in)
                    next_in += 1
            pend_d2h = cur
        if pend_d2h is not None:
            _finish_d2h(pend_d2h)
            pend_d2h = None
        d2h_t1 = time.monotonic()
        while next_in < len(buckets_in):
            if pend_h2d is not None:
                _finish_h2d(pend_h2d)
                pend_h2d = None
            pend_h2d = _issue_h2d(next_in)
            next_in += 1
        if pend_h2d is not None:
            _finish_h2d(pend_h2d)
            pend_h2d = None
    except Exception as exc:
        rb_sp = tracing.begin(
            "swap.rollback", parent=root_ctx,
            error=f"{type(exc).__name__}: {exc}",
        )
        try:
            _rollback()
        except Exception as rb_exc:
            rb_sp.set(rollback_failed=True)
            rb_sp.end()
            root.set(error="rollback_failed")
            root.end()
            raise SwapRollbackFailed(
                f"hot-swap transfer failed "
                f"({type(exc).__name__}: {exc}) and the rollback failed "
                f"({type(rb_exc).__name__}: {rb_exc}); device state is "
                "partially moved"
            ) from rb_exc
        rb_sp.end()
        root.set(error="rolled_back")
        root.end()
        raise SwapRolledBack(
            f"hot-swap transfer failed mid-flight; rolled back "
            f"({type(exc).__name__}: {exc})"
        ) from exc
    if in_payload_devs:
        # the last buckets' async dequants are part of the wake window:
        # land them, then free the device payload staging
        t_dq = time.monotonic()
        jax.block_until_ready([a for a in dev_in if a is not None])
        dq_bytes = sum(p.nbytes for p in in_payload_devs)
        for p in in_payload_devs:
            p.delete()
        # the non-hidden dequant tail: the quant-overhead EWMA kind
        out_mgr._notify_transfer(
            "quant.dequant", dq_bytes, time.monotonic() - t_dq
        )
    h2d_t1 = time.monotonic()
    if h2d_t0 is None:  # empty incoming tree (degenerate)
        h2d_t0 = h2d_t1
    if h2d_pool is not None:
        h2d_pool.shutdown(wait=True)  # no transfer outlives the swap
        for p in deferred_payload_frees:
            p.delete()
        for i in deferred_deletes:
            leaves_out[i].delete()
    if use_mk:
        # commit point for the incoming pool entry's pinned-host copies:
        # deferred from _finish_h2d so a rollback could re-pool it intact
        for i in deferred_in_frees:
            leaves_in[i].delete()

    # Delta handover, at commit only: each matched incoming leaf takes
    # over the outgoing model's live device array (content-identical by
    # digest), and the incoming host copy becomes the outgoing model's
    # slept host state — zero bytes crossed the device boundary for them.
    # A quantized incoming host copy carries its payload metadata along to
    # the outgoing model's slept state.
    for i, j in reuse_pairs:
        dev_in[i] = leaves_out[j]
        host_out[j] = leaves_in[i]
        meta_out[j] = in_metas[i]

    # Commit the state-machine edges: outgoing asleep (poolable host
    # state), incoming awake.
    out_mgr._host_state = jax.tree.unflatten(treedef_out, host_out)
    out_mgr._quant_meta = (
        meta_out if any(m is not None for m in meta_out) else None
    )
    out_mgr._shardings = jax.tree.unflatten(treedef_out, shard_out)
    out_mgr._sharding_specs = None
    out_mgr._staged = None
    out_mgr._set_state(None)
    out_mgr._level = SleepLevel.L1_HOST_OFFLOAD
    out_mgr.stats.last_sleep_seconds = d2h_t1 - d2h_t0
    out_mgr.stats.last_sleep_transfer_s = d2h_t1 - d2h_t0
    out_mgr.stats.bytes_offloaded = sum(
        x.nbytes for x in host_out if x is not None
    ) + sum(m.scale_nbytes for m in meta_out if m is not None)
    out_mgr.stats.bytes_offloaded_full = sum(nb_out)
    out_mgr.stats.last_quant = (
        quant_mode_used if out_mgr._quant_meta is not None else "off"
    )
    out_mgr.stats.sleeps_total += 1

    in_mgr._host_state = None
    in_mgr._shardings = None
    in_mgr._sharding_specs = None
    in_mgr._set_state(jax.tree.unflatten(treedef_in, dev_in))
    in_mgr._level = SleepLevel.AWAKE
    # scales cached for the incoming model's NEXT offload (bit-stable
    # re-quantization); payload metadata is consumed by this wake
    in_mgr._note_wake_quant(in_metas)
    in_mgr.stats.last_wake_seconds = h2d_t1 - h2d_t0
    in_mgr.stats.last_wake_transfer_s = h2d_t1 - h2d_t0
    in_mgr.stats.last_wake_bytes = bytes_in
    in_mgr.stats.bytes_offloaded = 0
    in_mgr.stats.bytes_offloaded_full = 0
    in_mgr.stats.wakes_total += 1

    total = time.monotonic() - t_begin
    # Overlap = intersection of the two directions' issue->complete
    # windows. Positive whenever an h2d was issued before the last d2h
    # completed — i.e. for any >= 2-bucket swap, by construction.
    overlap = max(0.0, min(d2h_t1, h2d_t1) - max(d2h_t0, h2d_t0))
    root.set(
        bytes_out=bytes_out,
        bytes_in=bytes_in,
        bytes_moved=moved_bytes,
        bytes_deduped=deduped_bytes,
        buckets_out=len(buckets_out),
        buckets_in=len(buckets_in),
        overlap_frac=round(overlap / total, 6) if total > 0 else 0.0,
        peak_bytes_in_flight=peak_in_flight,
    )
    root.end()
    # bandwidth EWMA feed (utils/costs.py): the two directions' measured
    # windows, over the bytes that actually crossed the boundary (totals
    # minus digest-matched leaves) — what pre-transfer pricing divides by
    out_mgr._notify_transfer(
        "swap.d2h",
        sum(wnb_out[i] for i in plan.move_out),
        d2h_t1 - d2h_t0,
    )
    out_mgr._notify_transfer(
        "swap.h2d",
        sum(wnb_in[i] for i in move_in),
        h2d_t1 - h2d_t0,
    )
    # effective whole-verb bandwidth (moved bytes over the full wall,
    # planning/staging/commit included): what pool-hit pricing prefers —
    # for repeated same-shape swaps it predicts the wall directly,
    # absorbing the fixed per-swap overhead the window EWMAs can't see
    out_mgr._notify_transfer("swap.total", moved_bytes, total)
    return {
        "swap_total_s": total,
        "d2h_s": d2h_t1 - d2h_t0,
        "h2d_s": h2d_t1 - h2d_t0,
        "overlap_s": overlap,
        "overlap_frac": overlap / total if total > 0 else 0.0,
        "bytes_out": bytes_out,
        "bytes_in": bytes_in,
        "bytes_moved": moved_bytes,
        "bytes_deduped": deduped_bytes,
        "deduped_leaves": len(reuse_pairs),
        # compressed-actuation accounting (docstring): wire vs full bytes
        "quant": quant_mode_used,
        "quant_leaves": quant_leaves,
        "bytes_full": bytes_full,
        "bytes_saved_quant": max(0, bytes_full - (bytes_out + bytes_in)),
        "buckets_out": len(buckets_out),
        "buckets_in": len(buckets_in),
        "bucket_bytes": bucket_bytes,
        "peak_bytes_in_flight": peak_in_flight,
    }


def attach_sleep(
    engine,
    bucket_bytes: Optional[int] = None,
    quant_mode: str = "off",
    quant_hot_head: bool = True,
    on_transfer: Optional[Callable[[str, int, float], None]] = None,
) -> SleepManager:
    """Wire a SleepManager to an InferenceEngine: the offloadable state is
    (params, kv page pool). Page tables / host bookkeeping stay put, so the
    wake fast path resumes in-flight sequences. Under zero-drain
    (``engine.kv_detached`` after a park) the state is weights-only — the
    live KV left compactly via engine/parked.py and the restore rebuilds a
    fresh pool for the bundle to scatter back into.

    ``quant_mode`` opts the level-1 offload path into compressed transfers
    (int8/fp8 payloads + on-device dequant; docs/perf.md "Compressed
    actuation"); ``quant_hot_head`` keeps embeddings / final norm /
    lm_head at full precision (the default). ``on_transfer`` feeds each
    completed transfer window's (kind, bytes, seconds) to the cost
    oracle's bandwidth EWMAs (utils/costs.py)."""

    def get_state():
        # a dispatched-but-unread decode chunk would be lost with the
        # device state: complete it (emitting its tokens) before offload
        engine.drain_inflight()
        if engine.kv_detached:
            # zero-drain park (engine/parked.py) already paged the live
            # KV out compactly and dropped the pool arrays: the slept
            # state is weights-only, and set_state rebuilds a fresh pool
            return {"params": engine.params}
        return {"params": engine.params, "kv": engine.pool.as_tuple()}

    def peek_state():
        # pricing reads shapes only: same tree, no quiesce. Under
        # zero-drain the L1 offload this prices will run AFTER a park,
        # so the peeked tree must exclude the pool too (the parked-KV
        # bytes are priced separately from parked_page_ids).
        if engine.kv_detached or engine.zero_drain_park:
            return {"params": engine.params}
        return {"params": engine.params, "kv": engine.pool.as_tuple()}

    def set_state(state):
        if state is None:
            engine.params = None
            engine.pool.drop()
            # Scheduler arrays (tokens/positions/budgets/key) are device
            # state too — a sleeping engine must hold zero HBM. Host mirrors
            # stay authoritative; the first post-wake chunk re-uploads them.
            engine.drop_device_sched_state()
        else:
            engine.params = state["params"]
            if "kv" in state:
                engine.pool.replace(state["kv"])
            else:
                # weights-only state (zero-drain park): fresh pool +
                # allocator; the service re-seats the parked bundle next
                engine.rebuild_kv_pool()

    mgr = SleepManager(
        get_state,
        set_state,
        on_reacquire=engine.on_device_reacquire,
        bucket_bytes=bucket_bytes,
        quant_mode=quant_mode,
        quant_hot_head=quant_hot_head,
        on_transfer=on_transfer,
        peek_state=peek_state,
    )
    return mgr
