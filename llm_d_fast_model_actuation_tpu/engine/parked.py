"""Zero-drain actuation: live request state, paged out like weights.

Today an actuation and the requests it preempts are mutually exclusive:
a swap aborts every queued and in-flight request of the outgoing model.
The paged KV cache makes request state chunkable exactly the way weights
are — a request's KV lives in whole pages, its scheduler state in small
per-slot host rows — so the transactional sleep/swap discipline extends
to requests: **park** them (page the live KV pages to host, capture the
per-slot scheduler rows and RNG key state), store the bundle alongside
the slept weights in the model pool, and **resume** them bit-exact after
the wake/swap-back (page the KV back in, re-seat page tables and slots).

This module holds the data shapes and the two transfer primitives; the
park/resume *orchestration* lives on :class:`~.engine.InferenceEngine`
(it owns the scheduler state being detached/re-seated) and the service
wires it into the swap/sleep verbs behind ``--zero-drain``
(engine/server.py).

Transfer discipline matches engine/sleep.py: size-bounded chunks (whole
pages, never split), each chunk landed before the next is issued, with
named fault-injection points (``kvsave.d2h`` on page-out,
``kvrestore.h2d`` on page-in — utils/faults.py) so the failure paths are
deterministically drillable. A page-out failure leaves the engine
untouched (the caller falls back to the abort path); a page-in failure
is rolled back to a *clean* abort of the parked requests with the
existing ``state_loss`` cause — never a wedged slot or a corrupted page
table.
"""

from __future__ import annotations

import base64
import hashlib
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from ..utils import faults, tracing

#: chunk bound fallback when the caller passes none: matches the swap
#: bucket default (engine/sleep.py DEFAULT_SWAP_BUCKET_BYTES)
DEFAULT_KV_CHUNK_BYTES = 256 << 20

#: parked-bundle wire format version (GET/POST /v1/parked): bumped on
#: any incompatible change so a mixed-version fleet rejects the handoff
#: instead of mis-seating state. 2: ``kv.shape`` is the pool's stored
#: layout [layers, pages, page_size, kv_heads * head_dim] (1 had the two
#: minor axes apart; the chunk bytes are the same). 3: a request spec no
#: longer carries ``variant`` (an importer of 2 requires the field)
WIRE_VERSION = 3


class ParkedResumeFailed(RuntimeError):
    """A zero-drain resume failed mid page-in and was rolled back: no
    slot was seated, every allocated page was returned, and the engine
    is healthy with an empty (fresh) KV pool. The parked requests' KV is
    unrecoverable — the caller aborts them with cause ``state_loss``."""


@dataclass
class ParkedRequest:
    """One preempted mid-generation request: the pure-host Request
    object plus the device-derived state a bit-exact resume needs."""

    req: Any  #: engine.Request — prompt, emitted tokens, sampling knobs
    #: pool page ids (old pool) holding this request's live KV, page-table
    #: order — the first ``ceil(pos / page_size)`` of its allocation
    old_pages: List[int] = field(default_factory=list)
    #: [vocab] int32 token-count row (penalties input). NOT recomputable
    #: from the Request: stop-stripped tokens stay counted.
    counts_row: Optional[np.ndarray] = None
    #: [2] uint32 RNG key data — the slot's key stream position
    key_data: Optional[np.ndarray] = None


@dataclass
class ParkedRequests:
    """Everything a preemption displaced, host-resident: what the model
    pool byte-counts alongside the slept weights and what
    ``resume_parked`` re-seats after the wake/swap-back."""

    #: mid-decode requests with live KV (ParkedRequest each)
    live: List[ParkedRequest] = field(default_factory=list)
    #: queued requests with no device state yet (engine Request objects;
    #: includes mid-prefill requests demoted back to the queue — prefill
    #: is a pure function of the prompt and consumes no key split until
    #: its final segment, so re-running it is bit-exact)
    waiting: List[Any] = field(default_factory=list)
    #: unique old-pool page ids in gather order (axis 1 of k/v_host)
    page_ids: List[int] = field(default_factory=list)
    #: gathered live pages, the pool's own layout (kv_cache.PagePool):
    #: [num_layers, len(page_ids), page_size, kvh * hd]
    k_host: Optional[np.ndarray] = None
    v_host: Optional[np.ndarray] = None
    kv_nbytes: int = 0
    #: pool-budget accounting: KV payload + scheduler-row metadata
    nbytes: int = 0
    #: service-owned: seq_id -> concurrent Future for live+waiting
    futures: Dict[int, Any] = field(default_factory=dict)
    #: service-owned: raw ``_pending`` submit tuples parked on swap
    pending: List[Any] = field(default_factory=list)
    #: the PURE d2h page-out window (gather_pages_d2h only — the engine
    #: quiesce and host bookkeeping around it excluded): what the
    #: kvsave.d2h bandwidth EWMA observes and priced sleep records score
    #: against, same discipline as sleep.d2h's pure transfer window
    pageout_s: float = 0.0

    @property
    def preempted(self) -> int:
        return len(self.live) + len(self.waiting) + len(self.pending)


def _pool_page_nbytes(k_pages: Any, v_pages: Any) -> int:
    """Bytes one page occupies across k+v and all layers, derived from
    the live pool arrays (shape [layers, num_pages, page_size, kvh * hd])."""
    n = max(1, int(k_pages.shape[1]))
    return (int(k_pages.nbytes) + int(v_pages.nbytes)) // n


def _chunks(n: int, per_chunk: int) -> List[Tuple[int, int]]:
    out = []
    i = 0
    while i < n:
        j = min(n, i + per_chunk)
        out.append((i, j))
        i = j
    return out


#: ONE jitted donated scatter for every resume (lazy: module import must
#: not touch a backend): jit's cache keys on function identity, so a
#: per-call lambda would recompile the scatter inside every resume
#: window — the compile-in-transfer-window cost warm_quant_ops exists to
#: avoid — and pollute the kvrestore.h2d bandwidth EWMA with compile time
_SCATTER = None


def _scatter_fn():
    global _SCATTER
    if _SCATTER is None:
        import jax

        _SCATTER = jax.jit(
            lambda pages, idx, vals: pages.at[:, idx].set(vals),
            donate_argnums=(0,),
        )
    return _SCATTER


def gather_pages_d2h(
    pool: Any,
    page_ids: Sequence[int],
    bucket_bytes: Optional[int] = None,
    span_name: str = "swap.kv_pageout",
) -> Tuple[np.ndarray, np.ndarray]:
    """Page the listed pool pages to host, chunk by chunk: gather a
    chunk's pages on device, move it D2H, free the device staging, then
    issue the next chunk — peak extra HBM is one chunk. Fires the
    ``kvsave.d2h`` fault point per chunk. Pure: the pool is read, never
    written, so a mid-transfer failure leaves the engine untouched and
    the caller falls back to the abort path."""
    import jax
    import jax.numpy as jnp

    ids = list(page_ids)
    per_page = _pool_page_nbytes(pool.k_pages, pool.v_pages)
    bucket = bucket_bytes or DEFAULT_KV_CHUNK_BYTES
    per_chunk = max(1, int(bucket) // max(1, per_page))
    layers, _, ps, fused = pool.k_pages.shape
    k_host = np.empty((layers, len(ids), ps, fused), pool.k_pages.dtype)
    v_host = np.empty_like(k_host)
    traced = tracing.enabled()
    parent = tracing.current_context() if traced else None
    for lo, hi in _chunks(len(ids), per_chunk):
        sp = None
        if traced:
            sp = tracing.begin(
                span_name, parent=parent, activate=False,
                pages=hi - lo, bytes=(hi - lo) * per_page,
            )
        try:
            faults.fire("kvsave.d2h")
            idx = jnp.asarray(ids[lo:hi], jnp.int32)
            k_sel = jnp.take(pool.k_pages, idx, axis=1)
            v_sel = jnp.take(pool.v_pages, idx, axis=1)
            kh, vh = jax.device_get((k_sel, v_sel))
            # materialized copies: device_get can return views aliasing
            # buffers on CPU-family backends (same rule as sleep staging)
            k_host[:, lo:hi] = np.asarray(kh)
            v_host[:, lo:hi] = np.asarray(vh)
            k_sel.delete()
            v_sel.delete()
        except BaseException as e:
            if sp is not None:
                sp.set(error=f"{type(e).__name__}: {e}")
                sp.end()
            raise
        if sp is not None:
            sp.end()
    return k_host, v_host


def scatter_pages_h2d(
    pool: Any,
    pairs: Sequence[Tuple[int, int]],
    k_host: np.ndarray,
    v_host: np.ndarray,
    bucket_bytes: Optional[int] = None,
    span_name: str = "wake.kv_pagein",
) -> int:
    """Page parked KV back into the (fresh) pool: ``pairs`` maps source
    index (axis 1 of k/v_host) -> destination page id. Chunked H2D with
    the ``kvrestore.h2d`` fault point per chunk; the pool arrays are
    updated in place via donated jit scatters (no whole-pool copy per
    chunk). Returns the wire bytes moved. A failure propagates with the
    pool left VALID (partially restored pages are only reachable once
    the caller seats page tables, which it never does after a failure)."""
    import jax
    import jax.numpy as jnp

    if not pairs:
        return 0
    per_page = _pool_page_nbytes(pool.k_pages, pool.v_pages)
    bucket = bucket_bytes or DEFAULT_KV_CHUNK_BYTES
    per_chunk = max(1, int(bucket) // max(1, per_page))
    scat = _scatter_fn()
    sharding = getattr(pool.k_pages, "sharding", None)
    moved = 0
    traced = tracing.enabled()
    parent = tracing.current_context() if traced else None
    for lo, hi in _chunks(len(pairs), per_chunk):
        chunk = pairs[lo:hi]
        sp = None
        if traced:
            sp = tracing.begin(
                span_name, parent=parent, activate=False,
                pages=len(chunk), bytes=len(chunk) * per_page,
            )
        try:
            faults.fire("kvrestore.h2d")
            src = [s for s, _ in chunk]
            dst = jnp.asarray([d for _, d in chunk], jnp.int32)
            kh = np.ascontiguousarray(k_host[:, src])
            vh = np.ascontiguousarray(v_host[:, src])
            if sharding is not None:
                # land the chunk pre-sharded like the pool it joins (the
                # fused kvh*hd axis is 'tp'-sharded on meshes; NamedSharding
                # is shape-agnostic, so the pool's own sharding applies)
                kd, vd = jax.device_put((kh, vh), (sharding, sharding))
            else:
                kd, vd = jax.device_put((kh, vh))
            pool.k_pages = scat(pool.k_pages, dst, kd)
            pool.v_pages = scat(pool.v_pages, dst, vd)
            jax.block_until_ready((pool.k_pages, pool.v_pages))
            moved += kh.nbytes + vh.nbytes
        except BaseException as e:
            if sp is not None:
                sp.set(error=f"{type(e).__name__}: {e}")
                sp.end()
            raise
        if sp is not None:
            sp.end()
    return moved


# -- wire format: transactional parked-bundle handoff between instances
# (GET /v1/parked/{model} export, POST /v1/parked import; ROADMAP item 3a,
# docs/operations.md "Draining a node without dropping streams") ------------
#
# A bundle on the wire is a single JSON document: the KV page payload is
# chunked (whole pages, the same bucket discipline as the transfers above)
# with a sha256 content digest PER CHUNK — the importer verifies every
# digest before any device mutation, so a corrupted or truncated handoff is
# rejected with the destination untouched. Scheduler rows and the RNG key
# stream position ride per request, so the importer's ``resume_parked``
# continues the stream bit-exact on other silicon. The ``identity`` block
# (model name @ checkpoint + weight-digest fingerprint) pins which weights
# the bundle may seat onto; the ``fence`` block (added by the exporting
# service) makes the handoff single-use.

#: Request fields that serialize verbatim (JSON-able scalars/lists).
#: ``stop_seqs``/``logit_bias``/``out_top_logprobs`` need shape fixups and
#: are handled explicitly; device-derived state (pages, slot) never travels
#: — the importer re-derives it through resume_parked's old->new page map.
_REQ_WIRE_FIELDS = (
    "prompt", "max_new_tokens", "temperature", "top_p",
    "presence_penalty", "frequency_penalty", "want_top_logprobs",
    "want_prompt_logprobs", "seed", "ignore_eos", "out_tokens",
    "out_logprobs", "prompt_logprobs", "pos", "cached_tokens",
    "streamed", "stop_requested",
)


def pack_array(a: np.ndarray) -> Dict[str, Any]:
    """One small host array as a JSON-able {b64, dtype, shape} triple
    (scheduler counts rows, RNG key data)."""
    a = np.ascontiguousarray(a)
    return {
        "b64": base64.b64encode(a.tobytes()).decode("ascii"),
        "dtype": str(a.dtype),
        "shape": list(a.shape),
    }


def unpack_array(d: Dict[str, Any]) -> np.ndarray:
    return (
        np.frombuffer(base64.b64decode(d["b64"]), dtype=_np_dtype(d["dtype"]))
        .reshape(tuple(int(x) for x in d["shape"]))
        .copy()
    )


def _np_dtype(name: str) -> np.dtype:
    try:
        return np.dtype(name)
    except TypeError:
        # bfloat16 and friends are registered by ml_dtypes (a jax
        # dependency), reachable by attribute even when the string
        # lookup is not
        import ml_dtypes

        return np.dtype(getattr(ml_dtypes, name))


def weight_fingerprint(digests: Dict[str, str]) -> str:
    """Order-independent sha256 fingerprint over a checkpoint's
    flat-key -> content-digest map: two engines hold the SAME weights
    iff their fingerprints match, which is what gates seating a
    migrated bundle (a bundle on mismatched weights would decode
    garbage from valid-looking KV)."""
    h = hashlib.sha256()
    for k in sorted(digests):
        h.update(f"{k}:{digests[k]}\n".encode())
    return h.hexdigest()


def encode_request(req: Any) -> Dict[str, Any]:
    """One engine Request as a JSON-able spec (host state only)."""
    spec = {k: getattr(req, k) for k in _REQ_WIRE_FIELDS}
    spec["seq_id"] = int(req.seq_id)
    spec["stop_seqs"] = [list(s) for s in req.stop_seqs]
    spec["logit_bias"] = {str(t): float(v) for t, v in req.logit_bias.items()}
    spec["out_top_logprobs"] = [
        [[int(t), float(v)] for t, v in alts] for alts in req.out_top_logprobs
    ]
    if getattr(req, "trace", None) is not None:
        # origin trace context: destination request.* spans parent on the
        # source's lifecycle root, so one trace_id covers both chips.
        # Optional field — WIRE_VERSION unchanged; old importers ignore it.
        ctx = req.trace.context()
        spec["trace"] = {"trace_id": ctx.trace_id, "span_id": ctx.span_id}
    return spec


def decode_request(spec: Dict[str, Any], request_cls: Any) -> Any:
    """Rebuild an engine Request from its wire spec. The seq_id is the
    EXPORTER'S — the importing service re-keys it with a fresh local id
    before seating (two engines' id spaces are unrelated)."""
    req = request_cls(
        seq_id=int(spec["seq_id"]),
        prompt=[int(t) for t in spec["prompt"]],
        max_new_tokens=int(spec["max_new_tokens"]),
        temperature=float(spec["temperature"]),
    )
    req.top_p = float(spec["top_p"])
    req.presence_penalty = float(spec["presence_penalty"])
    req.frequency_penalty = float(spec["frequency_penalty"])
    req.want_top_logprobs = bool(spec["want_top_logprobs"])
    req.want_prompt_logprobs = bool(spec["want_prompt_logprobs"])
    req.seed = None if spec["seed"] is None else int(spec["seed"])
    req.ignore_eos = bool(spec["ignore_eos"])
    req.out_tokens = [int(t) for t in spec["out_tokens"]]
    req.out_logprobs = [float(v) for v in spec["out_logprobs"]]
    req.prompt_logprobs = [
        None if v is None else float(v) for v in spec["prompt_logprobs"]
    ]
    req.pos = int(spec["pos"])
    req.cached_tokens = int(spec["cached_tokens"])
    req.streamed = int(spec["streamed"])
    req.stop_requested = bool(spec["stop_requested"])
    req.stop_seqs = tuple(tuple(int(t) for t in s) for s in spec["stop_seqs"])
    req.logit_bias = {int(t): float(v) for t, v in spec["logit_bias"].items()}
    req.out_top_logprobs = [
        [(int(t), float(v)) for t, v in alts]
        for alts in spec["out_top_logprobs"]
    ]
    tr = spec.get("trace")
    if isinstance(tr, dict) and tr.get("trace_id"):
        req.trace_parent = {
            "trace_id": str(tr["trace_id"]),
            "span_id": str(tr.get("span_id", "")),
        }
    return req


def encode_wire(
    bundle: ParkedRequests,
    identity: Dict[str, Any],
    chunk_bytes: Optional[int] = None,
) -> Dict[str, Any]:
    """Serialize a parked bundle for the handoff wire. ``identity`` is
    the exporting service's model-identity block (weight_fingerprint et
    al.); the caller adds the fence and the service-level request lists
    (pending submissions, seed-None RNG carry-over) it alone owns."""
    chunks: List[Dict[str, Any]] = []
    kv: Dict[str, Any] = {
        "page_ids": [int(p) for p in bundle.page_ids],
        "nbytes": int(bundle.kv_nbytes),
        "chunks": chunks,
    }
    if bundle.page_ids:
        k_host, v_host = bundle.k_host, bundle.v_host
        kv["dtype"] = str(k_host.dtype)
        kv["shape"] = list(k_host.shape)
        per_page = (int(k_host.nbytes) + int(v_host.nbytes)) // max(
            1, len(bundle.page_ids)
        )
        per_chunk = max(
            1,
            int(chunk_bytes or DEFAULT_KV_CHUNK_BYTES) // max(1, per_page),
        )
        for lo, hi in _chunks(len(bundle.page_ids), per_chunk):
            kb = np.ascontiguousarray(k_host[:, lo:hi]).tobytes()
            vb = np.ascontiguousarray(v_host[:, lo:hi]).tobytes()
            h = hashlib.sha256(kb)
            h.update(vb)
            chunks.append(
                {
                    "lo": lo,
                    "hi": hi,
                    "k": base64.b64encode(kb).decode("ascii"),
                    "v": base64.b64encode(vb).decode("ascii"),
                    "sha256": h.hexdigest(),
                }
            )
    live = []
    for pr in bundle.live:
        spec = encode_request(pr.req)
        spec["old_pages"] = [int(p) for p in pr.old_pages]
        spec["counts_row"] = pack_array(pr.counts_row)
        spec["key_data"] = pack_array(pr.key_data)
        live.append(spec)
    return {
        "version": WIRE_VERSION,
        "identity": dict(identity),
        "kv": kv,
        "requests": {
            "live": live,
            "waiting": [encode_request(r) for r in bundle.waiting],
            "pending": [],
        },
        "pageout_s": float(bundle.pageout_s),
        "nbytes": int(bundle.nbytes),
    }


def decode_wire(
    doc: Dict[str, Any], request_cls: Any
) -> Tuple[ParkedRequests, List[Dict[str, Any]]]:
    """Rebuild a parked bundle from a wire document, verifying EVERY KV
    chunk's content digest before returning — the caller touches no
    device state until this succeeds, so a bad handoff is rejected with
    the importer clean. Raises ValueError on any mismatch. Returns
    ``(bundle, pending_specs)``; pending submissions are service-level
    and the caller rebuilds their queue entries itself."""
    if int(doc.get("version", -1)) != WIRE_VERSION:
        raise ValueError(
            f"parked wire version {doc.get('version')!r} != {WIRE_VERSION}"
        )
    kv = doc["kv"]
    page_ids = [int(p) for p in kv["page_ids"]]
    k_host = v_host = None
    if page_ids:
        dtype = _np_dtype(kv["dtype"])
        shape = tuple(int(x) for x in kv["shape"])
        if len(shape) != 4 or shape[1] != len(page_ids):
            raise ValueError("KV shape does not match the page list")
        k_host = np.empty(shape, dtype)
        v_host = np.empty_like(k_host)
        covered = 0
        for ch in kv["chunks"]:
            lo, hi = int(ch["lo"]), int(ch["hi"])
            kb = base64.b64decode(ch["k"])
            vb = base64.b64decode(ch["v"])
            h = hashlib.sha256(kb)
            h.update(vb)
            if h.hexdigest() != ch["sha256"]:
                raise ValueError(
                    f"KV chunk [{lo}:{hi}] content digest mismatch"
                )
            sub = (shape[0], hi - lo) + shape[2:]
            k_host[:, lo:hi] = np.frombuffer(kb, dtype).reshape(sub)
            v_host[:, lo:hi] = np.frombuffer(vb, dtype).reshape(sub)
            covered += hi - lo
        if covered != len(page_ids):
            raise ValueError("KV chunks do not cover the page list")
    bundle = ParkedRequests(
        page_ids=page_ids,
        k_host=k_host,
        v_host=v_host,
        kv_nbytes=int(kv.get("nbytes", 0)),
        nbytes=int(doc.get("nbytes", 0)),
        pageout_s=float(doc.get("pageout_s", 0.0)),
    )
    reqs = doc["requests"]
    for spec in reqs["live"]:
        req = decode_request(spec, request_cls)
        bundle.live.append(
            ParkedRequest(
                req=req,
                old_pages=[int(p) for p in spec["old_pages"]],
                counts_row=unpack_array(spec["counts_row"]),
                key_data=unpack_array(spec["key_data"]),
            )
        )
    for spec in reqs["waiting"]:
        req = decode_request(spec, request_cls)
        if spec.get("rng_key_data") is not None:
            # seed-None sampled requests: the exporter pins the exact
            # initial key its own engine would have derived from
            # (engine seed, seq_id) — the importer's ids differ, and
            # without this the resumed stream would sample differently
            req.rng_key_data = unpack_array(spec["rng_key_data"])
        bundle.waiting.append(req)
    return bundle, list(reqs.get("pending", ()))
