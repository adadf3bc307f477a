"""Weight-only int8 quantization (W8A16) for the serving path.

Decode on TPU is HBM-bandwidth-bound: every step reads every weight byte
(SURVEY.md §6; the reference's engine, vLLM, ships the same technique for
the same reason). Symmetric per-output-channel int8 halves the weight
bytes — near-2x on the decode roofline — while activations stay bf16 and
matmuls run on the MXU: XLA fuses the int8->bf16 upconvert into the
matmul's operand read, so HBM traffic is the int8 bytes.

Representation: a quantized weight is the dict ``{"q": int8[..., out],
"s": f32[out-broadcastable]}`` with ``W ≈ q * s``. Since the scale is
per OUTPUT channel, ``x @ W == (x @ q) * s`` — the matmul result is
rescaled, not the weight, so no dequantized copy ever materializes.

Quantized and plain weights coexist: every matmul in the model forward
goes through `qmat`, which dispatches on the leaf shape. Norms and the
embedding table stay bf16 (the embedding is a gather, not a matmul; its
tied-head use stays bf16 too).
"""

from __future__ import annotations

import hashlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

#: weight stacks quantized in a llama-family layer pytree + top level
LAYER_WEIGHTS = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def is_quantized(w: Any) -> bool:
    return isinstance(w, dict) and "q" in w and "s" in w


def qmat(x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """``x @ w`` for a plain or quantized weight."""
    if is_quantized(w):
        return (x @ w["q"].astype(x.dtype)) * w["s"].astype(x.dtype)
    return x @ w


def quantize_weight(w: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """Symmetric per-output-channel int8: scale over all axes but the last.

    Handles both single (in, out) and layer-stacked (L, in, out) weights —
    the scale keeps a broadcastable shape so `lax.scan` slicing a layer
    slices the scale with it.
    """
    # reduce ONLY the fan-in axis: leading stack axes (the scan's layer
    # axis) keep their own scales, so slicing a layer slices its scale
    amax = jnp.max(jnp.abs(w.astype(jnp.float32)), axis=w.ndim - 2, keepdims=True)
    scale = jnp.maximum(amax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(w.astype(jnp.float32) / scale), -127, 127).astype(
        jnp.int8
    )
    return {"q": q, "s": scale.astype(jnp.float32)}


def quantize_params(params: Dict[str, Any]) -> Dict[str, Any]:
    """Quantize a llama-family param pytree in place of the bf16 stacks.
    Embedding and norms stay bf16. MoE trees reuse the dense names for
    their 4-D expert stacks ([L, E, in, out]; moe_ffn's _qeinsum consumes
    the quantized form); the router stays bf16 (its output feeds a
    softmax — precision matters and it is tiny)."""
    out = dict(params)
    layers = dict(params["layers"])
    for name in LAYER_WEIGHTS:
        w = layers.get(name)
        if w is not None and not is_quantized(w) and w.ndim in (3, 4):
            layers[name] = quantize_weight(w)
    out["layers"] = layers
    head = params.get("lm_head")
    if head is not None and not is_quantized(head):
        out["lm_head"] = quantize_weight(head)
    return out


# -- compressed actuation transfers (docs/perf.md "Compressed actuation") ----
#
# The serving-path W8A16 above changes what the MODEL computes; the
# transfer quantization below changes only how weight bytes CROSS the
# PCIe/host boundary on sleep/wake/swap (engine/sleep.py). A leaf is
# quantized right before it leaves HBM (or host-side when staging a
# full-precision pool entry), the half-size payload moves, and the wake
# dequantizes on device — the engine always serves plain cfg.dtype arrays,
# so no program recompiles and `qmat` never sees these.
#
# Numerics contract: opt-in and lossy-ONCE. The first quantized offload
# rounds each eligible weight to its int8/fp8 representation; every later
# cycle reproduces the exact same post-quantization bits, because (a) the
# int8 scale is cached by the sleeper and reused (re-quantizing
# dequant(q, s) with the same s recovers q exactly: |q|<=127 and the
# bf16/f32 round-trip error is < 0.25 of a quantization step) and (b) the
# fp8 path is a plain dtype round trip, exact by construction.

#: transfer quantization modes (--sleep-quant)
TRANSFER_MODES = ("off", "int8", "fp8")

#: top-level leaves the default "hot head" keeps at full precision
HOT_HEAD_KEYS = ("embed", "lm_head")


def fp8_dtype():
    """The fp8 transfer dtype (e4m3: weight-shaped range, 3 mantissa
    bits). Raises ImportError where ml_dtypes lacks it."""
    import ml_dtypes

    return ml_dtypes.float8_e4m3fn


def transfer_quant_supported(mode: str) -> Optional[str]:
    """None when `mode` can run here, else a human reason (the flag
    validation surface)."""
    if mode in ("", "off"):
        return None
    if mode not in TRANSFER_MODES:
        return f"unknown sleep-quant mode {mode!r} (want {TRANSFER_MODES})"
    if mode == "fp8":
        try:
            fp8_dtype()
        except Exception as e:  # noqa: BLE001 — report, caller rejects
            return f"fp8 transfers need ml_dtypes float8_e4m3fn: {e}"
    return None


@dataclass
class TransferQuant:
    """Per-leaf metadata for a transfer-quantized payload: what the wake
    needs to rebuild the full-precision array on device. Rides NEXT TO the
    host state tree (an aligned flat list), never inside it — the tree
    keeps its structure so digest alignment and sharding trees stay valid."""

    mode: str  #: "int8" | "fp8"
    orig_dtype: str  #: numpy dtype string of the full-precision leaf
    #: float32 per-output-channel scale, broadcastable (int8 only)
    scale: Optional[np.ndarray] = None
    #: shard view of the leaf this payload was quantized FROM (the
    #: ``str(PartitionSpec)`` of a mesh-sharded device leaf; None for
    #: single-device / host-staged payloads): quantize/dequantize run
    #: shard-locally on device — the per-output-channel scale reduction
    #: is over the fan-in axis, which XLA computes shard-local where
    #: that axis is unsharded and via one exact all-reduce max where it
    #: is ('tp'-sharded ``w_down``) — and the restore path cross-checks
    #: this spec against its placement target so a payload can never be
    #: silently expanded under a different sharding than it came from
    spec: Optional[str] = None

    @property
    def scale_nbytes(self) -> int:
        return int(self.scale.nbytes) if self.scale is not None else 0


def _is_float_dtype(dt: Any) -> bool:
    try:
        return jnp.issubdtype(np.dtype(dt), jnp.floating)
    except TypeError:
        return False


def transfer_quant_plan(
    state: Any, hot_head: bool = True, prefix: str = "params"
) -> List[bool]:
    """Which leaves of ``state`` a quantized transfer compresses, aligned
    with ``jax.tree.flatten(state)`` order (the same alignment contract as
    chunk_store.aligned_digests).

    Eligible: floating-point weight stacks under the ``prefix`` subtree —
    the layer matmul weights (LAYER_WEIGHTS, ndim 3/4), plus ``embed`` and
    ``lm_head`` (ndim 2) when ``hot_head`` is False. Norms, biases, the
    KV pool, and scheduler arrays never quantize; with the default hot
    head on, embeddings / final norm / lm_head stay full precision."""
    from jax.tree_util import tree_flatten_with_path

    flat, _ = tree_flatten_with_path(state)
    out: List[bool] = []
    for path, leaf in flat:
        keys = []
        for k in path:
            if hasattr(k, "key"):
                keys.append(str(k.key))
            elif hasattr(k, "idx"):
                keys.append(str(k.idx))
            else:  # pragma: no cover — exotic pytree key types
                keys.append(str(k))
        if prefix:
            if not keys or keys[0] != prefix:
                out.append(False)
                continue
            keys = keys[1:]
        ndim = len(getattr(leaf, "shape", ()))
        dt = getattr(leaf, "dtype", None)
        if not keys or ndim < 2 or dt is None or not _is_float_dtype(dt):
            out.append(False)
        elif keys[0] == "layers" and keys[-1] in LAYER_WEIGHTS and ndim in (3, 4):
            out.append(True)
        elif not hot_head and keys[-1] in HOT_HEAD_KEYS and ndim == 2:
            out.append(True)
        else:
            out.append(False)
    return out


def payload_nbytes(shape: Tuple[int, ...], mode: str) -> int:
    """Wire bytes of one quantized leaf: 1-byte payload + the int8 path's
    f32 scale (axis ndim-2 reduced to 1). Shapes only — the swap's bucket
    partitioner and the prefetch admission estimate both size transfers
    without materializing anything."""
    elems = 1
    for d in shape:
        elems *= int(d)
    scale = 0
    if mode == "int8":
        scale = (elems // max(1, int(shape[-2]))) * 4
    return elems + scale


def _shard_spec_str(arr: Any) -> Optional[str]:
    """``str(PartitionSpec)`` of a mesh-sharded device array (the shard
    view recorded in :class:`TransferQuant`); None for single-device and
    host arrays."""
    sh = getattr(arr, "sharding", None)
    spec = getattr(sh, "spec", None)
    if spec is None or getattr(sh, "num_devices", 1) <= 1:
        return None
    return str(spec)


def quantize_leaf(
    arr: Any, mode: str, scale: Optional[Any] = None
) -> Tuple[Any, TransferQuant]:
    """Quantize one leaf for transfer with jnp ops — ON DEVICE when `arr`
    is a device array, so only the payload crosses the boundary. A
    mesh-sharded leaf quantizes SHARD-LOCALLY (elementwise ops keep the
    input's sharding; the amax reduction is shard-local except over a
    'tp'-sharded fan-in axis, where XLA inserts one exact all-reduce
    max), and the leaf's shard view is recorded in the metadata.

    ``scale`` (the sleeper's cached scale from this leaf's first
    quantization) makes re-quantization bit-idempotent: round(w'/s) with
    w' = dequant(q, s) recovers exactly q. Returns (payload, meta); the
    meta's scale is normalized to host numpy."""
    orig = str(np.dtype(arr.dtype))
    spec = _shard_spec_str(arr)
    if mode == "fp8":
        return jnp.asarray(arr).astype(fp8_dtype()), TransferQuant(
            mode="fp8", orig_dtype=orig, spec=spec
        )
    w = jnp.asarray(arr).astype(jnp.float32)
    if scale is None:
        amax = jnp.max(jnp.abs(w), axis=w.ndim - 2, keepdims=True)
        s = jnp.maximum(amax / 127.0, 1e-8)
    else:
        s = jnp.asarray(scale, dtype=jnp.float32)
    q = jnp.clip(jnp.round(w / s), -127, 127).astype(jnp.int8)
    return q, TransferQuant(
        mode="int8",
        orig_dtype=orig,
        # an owned copy: on CPU-family backends np.asarray can alias the
        # device buffer, and a scale that outlives a device release must
        # not keep the client alive (engine/device.py)
        scale=np.array(s, dtype=np.float32),
        spec=spec,
    )


def quantize_leaf_np(
    arr: np.ndarray, mode: str, scale: Optional[np.ndarray] = None
) -> Tuple[np.ndarray, TransferQuant]:
    """Host-side twin of :func:`quantize_leaf` (pure numpy): the staging
    path for full-precision pool entries and prefetched weights, where no
    device round trip is wanted. Same rounding (half-to-even), so both
    paths produce identical payloads for identical input bits."""
    orig = str(np.dtype(arr.dtype))
    if mode == "fp8":
        return np.asarray(arr).astype(fp8_dtype()), TransferQuant(
            mode="fp8", orig_dtype=orig
        )
    w = np.asarray(arr).astype(np.float32)
    if scale is None:
        amax = np.max(np.abs(w), axis=w.ndim - 2, keepdims=True)
        s = np.maximum(amax / 127.0, np.float32(1e-8)).astype(np.float32)
    else:
        s = np.asarray(scale, dtype=np.float32)
    q = np.clip(np.rint(w / s), -127, 127).astype(np.int8)
    return q, TransferQuant(mode="int8", orig_dtype=orig, scale=s)


def dequantize_leaf(payload: Any, meta: TransferQuant) -> Any:
    """Rebuild the full-precision array from a payload with jnp ops — ON
    DEVICE when the payload is a device array (the wake-side dequant that
    rides under the remaining H2D stream)."""
    dt = np.dtype(meta.orig_dtype)
    if meta.mode == "fp8":
        return jnp.asarray(payload).astype(dt)
    w = jnp.asarray(payload).astype(jnp.float32) * jnp.asarray(meta.scale)
    return w.astype(dt)


def dequantize_leaf_np(payload: np.ndarray, meta: TransferQuant) -> np.ndarray:
    """Host-side twin of :func:`dequantize_leaf`."""
    dt = np.dtype(meta.orig_dtype)
    if meta.mode == "fp8":
        return np.asarray(payload).astype(dt)
    w = np.asarray(payload).astype(np.float32) * meta.scale
    return w.astype(dt)


def transfer_digest(payload: Any, meta: TransferQuant) -> str:
    """Content digest of a quantized chunk (payload + scale + mode + the
    dtype it dequantizes to): what the tiered pool dedupes quantized
    entries on. A distinct digest space from the full-precision leaf
    digests — a quantized payload must never content-match (and be handed
    out as) the full-precision tensor it came from. Because the preimage
    includes leaf_digest(payload), equal "q:" digests imply bit-equal
    payloads, which is what lets the disk spill tier content-verify a
    reloaded quant chunk against the ``content`` field its spill header
    recorded (chunk_store._load_spilled)."""
    from ..engine.chunk_store import QUANT_DIGEST_PREFIX, leaf_digest

    h = hashlib.sha256()
    h.update(f"tq|{meta.mode}|{meta.orig_dtype}|".encode())
    h.update(leaf_digest(np.asarray(payload)).encode())
    if meta.scale is not None:
        h.update(leaf_digest(np.asarray(meta.scale)).encode())
    return QUANT_DIGEST_PREFIX + h.hexdigest()


def transfer_digest_map(
    state: Any, metas: list, prefix: str = "params"
) -> Dict[str, str]:
    """Flat weight key -> :func:`transfer_digest` for the quantized leaves
    of a slept/staged tree (``metas`` aligned with its flatten order).
    These live in a digest space disjoint from the full-precision leaf
    digests, so the tiered pool dedupes quantized siblings against each
    other and NEVER against the fp tensors they approximate."""
    from jax.tree_util import tree_flatten_with_path

    flat, _ = tree_flatten_with_path(state)
    out: Dict[str, str] = {}
    for (path, leaf), meta in zip(flat, metas):
        if meta is None:
            continue
        keys = []
        for k in path:
            if hasattr(k, "key"):
                keys.append(str(k.key))
            elif hasattr(k, "idx"):
                keys.append(str(k.idx))
            else:  # pragma: no cover — exotic pytree key types
                keys.append(str(k))
        if prefix:
            if not keys or keys[0] != prefix:
                continue
            keys = keys[1:]
        out["/".join(keys)] = transfer_digest(leaf, meta)
    return out


def quantized_axes(axes: Dict[str, Any]) -> Dict[str, Any]:
    """Logical-axis pytree matching `quantize_params`' structure: q keeps
    the original weight's axes; the broadcast scale shards only its output
    axis (other dims are size-1)."""
    out = dict(axes)
    layers = dict(axes["layers"])
    for name in LAYER_WEIGHTS:
        ax = layers.get(name)
        if ax is not None and len(ax) in (3, 4):
            # scale keeps every axis except fan-in (size-1 there):
            # (L, 1, out) for dense stacks, (L, E, 1, out) for experts
            layers[name] = {
                "q": ax,
                "s": ax[:-2] + (None, ax[-1]),
            }
    out["layers"] = layers
    if "lm_head" in axes:
        ax = axes["lm_head"]
        out["lm_head"] = {"q": ax, "s": (None, ax[-1])}
    return out





