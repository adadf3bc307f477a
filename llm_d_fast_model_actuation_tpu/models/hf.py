"""Hugging Face checkpoint import: config + safetensors -> our param tree.

The reference actuates vLLM servers, which load Hugging Face model
directories directly (`--model <hf-dir>`); a user switching to this
framework brings the same directories. This module maps an HF Llama-family
checkpoint (config.json + *.safetensors) onto the stacked-layer param tree
`models/llama.py` scans over, so `--model hf:<dir>` serves the same weights.

Supported architectures: LlamaForCausalLM (Llama 2/3, TinyLlama),
MistralForCausalLM, Qwen2ForCausalLM (q/k/v biases), Qwen3ForCausalLM
(per-head q/k norms), GemmaForCausalLM, MixtralForCausalLM (routed MoE:
expert stacks + router, models/moe.py). Numeric parity with the
`transformers` forward pass is pinned by `tests/test_hf_import.py`.

Layout notes:
  * HF stores per-layer `model.layers.{i}.<name>.weight` with shape
    (out, in); our tree stacks all layers into one (L, in, out) array per
    weight (transpose + stack) so one compiled `lax.scan` body serves
    every layer.
  * HF Llama checkpoints use the rotate-half RoPE layout, which is exactly
    `ops/rope.py`'s convention — weights copy over without re-permutation.
  * Gemma stores zero-centered RMSNorm weights (the (1+w) convention) and
    scales embeddings by sqrt(hidden); both map onto config knobs
    (`norm_offset`, `embed_scale`) — values copy verbatim.
"""

from __future__ import annotations

import dataclasses
import json
import os
import queue
import threading
import time
from typing import Any, Dict, Iterator, List, Optional, Set, Tuple

import numpy as np

from ..utils import faults, tracing
from .llama import LlamaConfig

#: HF `architectures[0]` -> config-knob overrides for our shared forward
ARCHITECTURES: Dict[str, Dict[str, Any]] = {
    "LlamaForCausalLM": {},
    "MistralForCausalLM": {},
    "Qwen2ForCausalLM": {"attn_bias": True},
    "Qwen3ForCausalLM": {"qk_norm": True},
    "GemmaForCausalLM": {
        "hidden_activation": "gelu",
        "norm_offset": 1.0,
        "embed_scale": True,
        # gemma ties embeddings by default, and config.json omits defaults
        "tie_embeddings": True,
    },
    "MixtralForCausalLM": {},
}


def _read_config(path: str) -> Dict[str, Any]:
    cfg_path = os.path.join(path, "config.json")
    if not os.path.isfile(cfg_path):
        raise FileNotFoundError(f"no config.json under {path!r}")
    with open(cfg_path) as f:
        return json.load(f)


def _int_list(v: Any) -> list:
    """HF eos_token_id may be an int or a list (Llama-3's [eos, eom,
    eot]); normalize to a list of ints."""
    if isinstance(v, list):
        return [int(t) for t in v]
    if isinstance(v, (int, float)):
        return [int(v)]
    return []


def config_from_hf(path: str, **overrides: Any) -> LlamaConfig:
    """Build a LlamaConfig from an HF model directory's config.json.

    `overrides` lets callers force serving knobs (dtype, attention_impl,
    quantization, max_seq_len) without a second config source.
    """
    import dataclasses

    hf = _read_config(path)
    archs = hf.get("architectures") or []
    arch = archs[0] if archs else "LlamaForCausalLM"
    if arch not in ARCHITECTURES:
        raise ValueError(
            f"unsupported architecture {arch!r}; supported: "
            f"{sorted(ARCHITECTURES)}"
        )
    base: LlamaConfig = LlamaConfig()
    if arch == "MixtralForCausalLM":
        from .moe import MoeConfig

        base = MoeConfig()
    heads = int(hf["num_attention_heads"])
    hidden = int(hf["hidden_size"])
    fields: Dict[str, Any] = {
        "vocab_size": int(hf["vocab_size"]),
        "hidden_size": hidden,
        "num_layers": int(hf["num_hidden_layers"]),
        "num_heads": heads,
        "num_kv_heads": int(hf.get("num_key_value_heads", heads)),
        "head_dim": int(hf.get("head_dim") or hidden // heads),
        "intermediate_size": int(hf["intermediate_size"]),
        "rope_theta": float(hf.get("rope_theta", 10000.0)),
        "rms_eps": float(hf.get("rms_norm_eps", 1e-5)),
        "max_seq_len": int(hf.get("max_position_embeddings", 8192)),
    }
    scaling = hf.get("rope_scaling")
    if scaling:
        rtype = scaling.get("rope_type") or scaling.get("type")
        if rtype == "llama3":
            fields["rope_scaling"] = (
                "llama3",
                float(scaling["factor"]),
                float(scaling["low_freq_factor"]),
                float(scaling["high_freq_factor"]),
                int(scaling["original_max_position_embeddings"]),
            )
        elif rtype == "linear":
            fields["rope_scaling"] = ("linear", float(scaling["factor"]))
        elif rtype not in (None, "default"):
            # an ignored scaling spec would serve silently-wrong logits
            raise ValueError(
                f"unsupported rope_scaling type {rtype!r} "
                "(supported: llama3, linear)"
            )
    sw = hf.get("sliding_window")
    if sw:
        # Mistral-style sliding-window attention: within the window our
        # full attention is exactly equivalent, so cap the servable
        # context at the window instead of silently attending past it.
        fields["max_seq_len"] = min(fields["max_seq_len"], int(sw))
    if arch == "MixtralForCausalLM":
        fields["num_experts"] = int(hf["num_local_experts"])
        fields["experts_per_token"] = int(hf["num_experts_per_tok"])
    arch_defaults = dict(ARCHITECTURES[arch])
    fields["tie_embeddings"] = bool(
        hf.get(
            "tie_word_embeddings", arch_defaults.pop("tie_embeddings", False)
        )
    )
    fields.update(arch_defaults)
    fields.update(overrides)
    return dataclasses.replace(base, **fields)


def eos_token_ids_from_hf(path: str) -> list:
    """ALL declared eos ids (config.json union generation_config.json,
    order-preserving) — Llama-3-Instruct ends chat turns with <|eot_id|>,
    which is a SECOND eos id; stopping on just the first would decode
    every chat request to max_tokens. Empty when neither file declares
    one."""
    ids = _int_list(_read_config(path).get("eos_token_id"))
    gen_path = os.path.join(path, "generation_config.json")
    if os.path.isfile(gen_path):
        with open(gen_path) as f:
            for t in _int_list(json.load(f).get("eos_token_id")):
                if t not in ids:
                    ids.append(t)
    return ids


def eos_token_id_from_hf(path: str, default: int = 2) -> int:
    ids = eos_token_ids_from_hf(path)
    return ids[0] if ids else default


# -- weight loading ----------------------------------------------------------

#: bytes-in-flight bound for the streaming loader's host->device transfers
#: (~two 256 MiB buckets double-buffered, the same window discipline as
#: engine/sleep.py's chunked swap transfers)
DEFAULT_LOAD_INFLIGHT_BYTES = 512 << 20


class LoadAborted(RuntimeError):
    """A cold load / prefetch was cancelled through its abort event."""


@dataclasses.dataclass
class LoadStats:
    """Cold-load phase breakdown, filled in place by ``load_params(...,
    stats=...)``. Wall windows can overlap: ``overlap_s`` is the time both
    the disk-read pipeline and host->device transfers were in flight — the
    streaming win over a read-everything-then-transfer schedule."""

    total_s: float = 0.0
    read_s: float = 0.0  #: wall window: load start -> last tensor staged
    convert_s: float = 0.0  #: cumulative casted-copy time (sum over readers)
    h2d_s: float = 0.0  #: wall window: first transfer issued -> last landed
    overlap_s: float = 0.0
    overlap_frac: float = 0.0  #: overlap_s / total_s
    bytes_read: int = 0  #: native source bytes staged
    bytes_h2d: int = 0  #: device bytes transferred
    buckets_h2d: int = 0
    shards: int = 0
    workers: int = 0
    streaming: bool = False
    #: flat weight key -> content digest (engine/chunk_store.py), computed
    #: once per stacked buffer as its last slice lands — the identity the
    #: tiered pool dedupes on and the delta-swap matches by. Filled only
    #: with ``load_params(..., want_digests=True)``.
    digests: Dict[str, str] = dataclasses.field(default_factory=dict)

    def transfer_figures(self):
        """``(kind, bytes, seconds)`` rows for the cost oracle's
        bandwidth EWMAs (utils/costs.py): the disk-read and H2D windows
        this load already measured, in the kind vocabulary the oracle
        prices with. Zero-byte / zero-time windows are omitted."""
        out = []
        if self.bytes_read > 0 and self.read_s > 0:
            out.append(("coldload.read", self.bytes_read, self.read_s))
        if self.bytes_h2d > 0 and self.h2d_s > 0:
            out.append(("coldload.h2d", self.bytes_h2d, self.h2d_s))
        return out


def _shard_files(path: str) -> Tuple[str, List[str]]:
    """Resolve the checkpoint's shard layout WITHOUT reading tensor data:
    ``("safetensors" | "bin", ordered file list)``.

    A sharded checkpoint declares its shard set in the index file; a
    missing shard would otherwise just mean fewer tensors iterated (and
    silently zeroed layers, before load_params grew slice tracking). Fail
    up front — before any staging work — with the exact absent files."""
    st_files = sorted(
        f for f in os.listdir(path) if f.endswith(".safetensors")
    )
    idx_path = os.path.join(path, "model.safetensors.index.json")
    if os.path.isfile(idx_path):
        with open(idx_path) as f:
            declared = sorted(set(json.load(f).get("weight_map", {}).values()))
        present = set(st_files)
        absent = [s for s in declared if s not in present]
        if absent:
            raise FileNotFoundError(
                f"checkpoint {path!r} index declares shard files that are "
                f"not present: {absent}"
            )
        # iterate exactly the declared shard set: directories often carry
        # extra safetensors (consolidated.*, partial downloads) that are
        # not part of the indexed checkpoint
        if declared:
            st_files = declared
    if st_files:
        return "safetensors", st_files
    bin_files = sorted(
        f
        for f in os.listdir(path)
        if f.startswith("pytorch_model") and f.endswith(".bin")
    )
    if not bin_files:
        raise FileNotFoundError(
            f"no *.safetensors or pytorch_model*.bin under {path!r}"
        )
    return "bin", bin_files


def _native_numpy(t) -> np.ndarray:
    """torch tensor -> numpy in the tensor's OWN dtype. bfloat16 (which
    numpy cannot express natively) goes through a bit-level uint16 view
    onto ml_dtypes.bfloat16 — never an fp32 copy. Every tensor the loader
    stages passes through here, so this is the choke point the
    no-fp32-transient regression test instruments."""
    import torch

    if t.layout != torch.strided:
        t = t.to_dense()
    if t.dtype == torch.bfloat16:
        import ml_dtypes

        return t.view(torch.uint16).numpy().view(ml_dtypes.bfloat16)
    return t.numpy()


def _iter_shard_tensors(
    path: str, kind: str, fname: str
) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (hf_name, native-dtype numpy array) for one shard file.
    safetensors shards are mmap-backed (one tensor resident at a time);
    the legacy .bin path drops each tensor's state-dict reference as it is
    consumed, so its peak host memory matches the safetensors path's
    one-tensor transient instead of holding the whole shard alive."""
    if kind == "safetensors":
        from safetensors import safe_open

        with safe_open(
            os.path.join(path, fname), framework="pt", device="cpu"
        ) as f:
            for name in f.keys():
                yield name, _native_numpy(f.get_tensor(name))
        return
    import torch

    sd = torch.load(
        os.path.join(path, fname), map_location="cpu", weights_only=True
    )
    for name in sorted(sd.keys()):
        yield name, _native_numpy(sd.pop(name))


def _iter_tensors(path: str) -> Iterator[Tuple[str, np.ndarray]]:
    """Yield (hf_name, native-dtype numpy array) over every tensor in the
    checkpoint, shard by shard (single-file, indexed-shard, or legacy
    pytorch_model.bin layouts)."""
    kind, files = _shard_files(path)
    for fname in files:
        yield from _iter_shard_tensors(path, kind, fname)


#: per-layer HF suffix -> (our key, transpose?)
_LAYER_MAP: Dict[str, Tuple[str, bool]] = {
    "self_attn.q_proj.weight": ("wq", True),
    "self_attn.k_proj.weight": ("wk", True),
    "self_attn.v_proj.weight": ("wv", True),
    "self_attn.o_proj.weight": ("wo", True),
    "self_attn.q_proj.bias": ("bq", False),
    "self_attn.k_proj.bias": ("bk", False),
    "self_attn.v_proj.bias": ("bv", False),
    "mlp.gate_proj.weight": ("w_gate", True),
    "mlp.up_proj.weight": ("w_up", True),
    "mlp.down_proj.weight": ("w_down", True),
    "self_attn.q_norm.weight": ("q_norm", False),
    "self_attn.k_norm.weight": ("k_norm", False),
    "input_layernorm.weight": ("attn_norm", False),
    "post_attention_layernorm.weight": ("mlp_norm", False),
}

#: mixtral block-sparse FFN: per-expert suffix -> (our key, transpose?)
_EXPERT_MAP: Dict[str, Tuple[str, bool]] = {
    "w1.weight": ("w_gate", True),
    "w2.weight": ("w_down", True),
    "w3.weight": ("w_up", True),
}

#: harmless checkpoint extras (precomputed buffers, not weights)
_IGNORED_SUFFIXES = ("rotary_emb.inv_freq",)

_TOP_MAP: Dict[str, Tuple[str, bool]] = {
    "model.embed_tokens.weight": ("embed", False),
    "model.norm.weight": ("final_norm", False),
    "lm_head.weight": ("lm_head", True),
}


def _route(
    name: str, tie_embeddings: bool
) -> Optional[Tuple[Tuple[str, ...], Optional[int], Optional[int], bool]]:
    """Map an HF tensor name -> (tree_key, layer, expert, transpose);
    None for deliberately-ignored tensors (precomputed buffers, tied
    lm_head); ValueError for anything unrecognized — a silently-dropped
    weight would serve wrong logits."""
    if name in _TOP_MAP:
        ours, transpose = _TOP_MAP[name]
        if ours == "lm_head" and tie_embeddings:
            return None  # tied: the forward reuses embed.T
        return (ours,), None, None, transpose
    if not name.startswith("model.layers."):
        if name.endswith(_IGNORED_SUFFIXES):
            return None
        raise ValueError(f"unrecognized checkpoint tensor {name!r}")
    rest = name[len("model.layers.") :]
    idx, _, suffix = rest.partition(".")
    if not idx.isdigit():
        raise ValueError(f"unrecognized checkpoint tensor {name!r}")
    layer = int(idx)
    if suffix in _LAYER_MAP:
        ours, transpose = _LAYER_MAP[suffix]
        return ("layers", ours), layer, None, transpose
    if suffix == "block_sparse_moe.gate.weight":
        return ("layers", "router"), layer, None, True
    if suffix.startswith("block_sparse_moe.experts."):
        rest2 = suffix[len("block_sparse_moe.experts.") :]
        e_str, _, w = rest2.partition(".")
        if w not in _EXPERT_MAP:
            raise ValueError(f"unrecognized expert tensor {name!r}")
        ours, transpose = _EXPERT_MAP[w]
        return ("layers", ours), layer, int(e_str), transpose
    if suffix.endswith(_IGNORED_SUFFIXES):
        return None
    raise ValueError(f"unrecognized checkpoint tensor {name!r}")


def _want_slices(flat: str, node: Any, n_experts: int) -> Set[tuple]:
    """Every (layer[, expert]) slice the model expects a checkpoint tensor
    to write for this stacked key (``("*",)`` = one whole-key write)."""
    parts = flat.split("/")
    if parts[0] == "layers":
        n_layers = node.shape[0]
        if n_experts and parts[-1] in ("w_gate", "w_up", "w_down"):
            return {
                (l, e) for l in range(n_layers) for e in range(n_experts)
            }
        return {(l,) for l in range(n_layers)}
    return {("*",)}


def _flat_targets(cfg: LlamaConfig, shapes: Dict[str, Any], mesh) -> Dict[str, Any]:
    """Per-flat-key device_put target: the serving NamedSharding on a mesh
    (same logical-axis rules the engine serves with), the default device
    otherwise."""
    import jax

    if mesh is None:
        dev = jax.devices()[0]
        return {"/".join(p): dev for p, _ in _flatten(shapes)}
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..parallel.mesh import named_sharding
    from .registry import logical_axes_for

    axes = {"/".join(p): ax for p, ax in _flatten(logical_axes_for(cfg))}
    return {
        "/".join(p): (
            NamedSharding(mesh, P())
            if axes.get("/".join(p)) is None
            else named_sharding(mesh, axes["/".join(p)])
        )
        for p, _ in _flatten(shapes)
    }


def _quantize_and_repin(
    cfg: LlamaConfig, params: Dict[str, Any], mesh: Any
) -> Dict[str, Any]:
    """Shared device-placement epilogue: apply the config's runtime
    quantization and — because the eager quantize ops don't all preserve
    the serving sharding (scale reductions in particular) — re-pin the
    quantized tree onto the mesh."""
    from .registry import maybe_quantize

    params = maybe_quantize(cfg, params)
    if getattr(cfg, "quantization", "") and mesh is not None:
        from ..parallel.mesh import shard_pytree

        from .registry import logical_axes_for

        params = shard_pytree(params, mesh, logical_axes_for(cfg))
    return params


def load_params(
    path: str,
    cfg: LlamaConfig,
    *,
    mesh: Any = None,
    workers: Optional[int] = None,
    streaming: Optional[bool] = None,
    place: bool = True,
    max_inflight_bytes: Optional[int] = None,
    abort_event: Optional[threading.Event] = None,
    throttle_bytes_per_s: float = 0.0,
    stats: Optional[LoadStats] = None,
    want_digests: bool = False,
) -> Dict[str, Any]:
    """Load an HF checkpoint into the stacked (L, ...) param tree — the
    pipelined, parallel cold-start path.

    Three overlapped stages:
      * **parallel shard readers** — a bounded thread pool over shard
        files (``workers``; <=0/None = auto). safetensors shards are
        mmap-backed and both the rust reads and the numpy casted copies
        release the GIL, so readers genuinely run concurrently.
      * **direct dtype staging** — each tensor is casted-copied from its
        NATIVE source dtype straight into the cfg.dtype stacked buffer
        (bfloat16 via ml_dtypes bit views): the per-tensor fp32 transient
        of the old loader is gone, and peak host memory stays ~one model
        in target dtype plus one source tensor.
      * **streaming device placement** (``streaming``, default on when
        ``place``) — the moment a stacked buffer's last slice lands, its
        host->device transfer is issued on a dedicated thread, bucketed
        and double-buffered with in-flight bytes bounded by
        ``max_inflight_bytes`` (engine/sleep.py's transfer discipline), so
        the disk read of layer k+1 overlaps the H2D of layer k. Each
        buffer's host copy is freed as its transfer lands.

    ``streaming=False`` runs the identical machinery on a strictly
    sequential schedule (all reads, then all transfers) — the reference
    tests/test_coldload.py compares against. ``place=False``
    skips device placement entirely and returns the host-staged plain
    (unquantized) numpy tree — the background-prefetch path, which must
    never touch HBM; ``place_staged_params`` is its deferred second half.

    ``abort_event`` (checked between tensors) raises LoadAborted;
    ``throttle_bytes_per_s`` bounds read bandwidth (prefetch I/O
    throttle). ``stats`` (a LoadStats) is filled in place.

    ``want_digests`` computes each stacked buffer's content digest
    (engine/chunk_store.py) the moment its last slice lands — on the
    reader threads, so hashing overlaps other shards' reads and (in
    streaming mode) the H2D stream — into ``stats.digests``. This is the
    ONE place weight content is hashed: the tiered pool's dedup and the
    delta-swap both reuse these digests.

    Bit-exactness: staging writes disjoint slices whose values do not
    depend on schedule, so any (workers, streaming) combination produces
    the same tree as the sequential loader.
    """
    import jax

    from .registry import init_params_for  # shape source of truth

    t_begin = time.monotonic()
    st = stats if stats is not None else LoadStats()
    # eval_shape over the UNquantized tree: staging happens in cfg.dtype,
    # quantization (if any) runs once at the end like the serving path
    plain = (
        dataclasses.replace(cfg, quantization="")
        if getattr(cfg, "quantization", "")
        else cfg
    )
    shapes = jax.eval_shape(
        lambda: init_params_for(jax.random.key(0), plain)
    )
    np_dtype = np.dtype(cfg.dtype)  # ml_dtypes registers bfloat16
    # shard layout first: a declared-but-absent shard must fail before any
    # staging work starts
    kind, files = _shard_files(path)
    if workers is None or int(workers) <= 0:
        workers = min(8, os.cpu_count() or 1)
    workers = max(1, min(int(workers), len(files)))
    if streaming is None:
        streaming = place
    streaming = bool(streaming and place)
    inflight_bound = int(max_inflight_bytes or DEFAULT_LOAD_INFLIGHT_BYTES)
    st.workers, st.shards, st.streaming = workers, len(files), streaming
    # Cold-load tracing (utils/tracing.py): one root span for the whole
    # load; shard reads and H2D buckets are child spans. Reader threads
    # and the transfer thread get the parent EXPLICITLY — ContextVars do
    # not cross thread starts.
    load_sp = tracing.begin(
        "coldload.load",
        activate=False,
        path=path,
        shards=len(files),
        workers=workers,
        streaming=streaming,
        place=place,
    )
    traced = load_sp is not tracing.NOOP_SPAN
    load_ctx = load_sp.context() if traced else None

    flat_shapes = {"/".join(p): n for p, n in _flatten(shapes)}
    n_experts = int(getattr(cfg, "num_experts", 0) or 0)
    want = {k: _want_slices(k, n, n_experts) for k, n in flat_shapes.items()}

    buffers: Dict[str, np.ndarray] = {}
    # Stacked buffers start zeroed, so "the key exists" is not evidence the
    # checkpoint supplied every layer/expert slice — a shard missing from an
    # un-indexed checkpoint would serve zeroed layers. Track exactly which
    # slices each staged tensor wrote; completeness is checked per slice
    # below. (transformers/vLLM get this via the safetensors index; we also
    # verify that in _shard_files when the index file exists.)
    staged: Dict[str, set] = {k: set() for k in flat_shapes}
    remaining = {k: len(s) for k, s in want.items()}
    mu = threading.Lock()
    ready: "queue.Queue[Optional[str]]" = queue.Queue()
    tie = bool(getattr(cfg, "tie_embeddings", False))
    convert_s = [0.0]
    bytes_read = [0]
    stop = threading.Event()  # internal: first reader error stops siblings

    def _aborted() -> bool:
        return stop.is_set() or (
            abort_event is not None and abort_event.is_set()
        )

    def stage(name: str, arr: np.ndarray) -> None:
        route = _route(name, tie)
        if route is None:
            return
        tree_key, layer, expert, transpose = route
        node = shapes
        for k in tree_key:
            if not isinstance(node, dict) or k not in node:
                # a tensor the config does not expect would be silently
                # dropped weight otherwise (e.g. biases with
                # attn_bias=False, q_norm without qk_norm)
                raise ValueError(
                    f"checkpoint tensor {name} has no "
                    f"place in the model config (architecture mismatch?)"
                )
            node = node[k]
        flat = "/".join(tree_key)
        if transpose:
            arr = arr.T
        if expert is not None:
            want_shape, sl = node.shape[2:], (layer, expert)
        elif layer is not None:
            want_shape, sl = node.shape[1:], (layer,)
        else:
            want_shape, sl = node.shape, ("*",)
        if arr.shape != tuple(want_shape):
            raise ValueError(
                f"{flat}: checkpoint shape {arr.shape} != model "
                f"{tuple(want_shape)}"
            )
        with mu:
            buf = buffers.get(flat)
            if buf is None:
                buf = buffers[flat] = np.zeros(node.shape, dtype=np_dtype)
        t0 = time.monotonic()
        # the ONLY conversion on the path: a casted copy from the native
        # source dtype into the cfg.dtype buffer slice (no fp32 transient;
        # disjoint slices, so concurrent readers need no lock here)
        if sl == ("*",):
            buf[...] = arr
        elif expert is not None:
            buf[layer, expert] = arr
        else:
            buf[layer] = arr
        dt = time.monotonic() - t0
        completed = False
        with mu:
            convert_s[0] += dt
            bytes_read[0] += arr.nbytes
            got = staged[flat]
            if sl not in got:
                got.add(sl)
                remaining[flat] -= 1
                completed = remaining[flat] == 0
        if completed:
            if want_digests:
                # hashed HERE — before the buffer is queued for transfer
                # (the streaming thread frees host buffers as they land),
                # and off the lock so sibling readers keep staging
                from ..engine.chunk_store import leaf_digest

                dg = leaf_digest(buf)
                with mu:
                    st.digests[flat] = dg
            if streaming:
                ready.put(flat)

    throttle_t0 = time.monotonic()

    def read_shard(fname: str) -> None:
        sp = (
            tracing.begin(
                "coldload.read_shard", parent=load_ctx, activate=False,
                shard=fname,
            )
            if traced
            else None
        )
        try:
            _read_shard(fname)
            if sp is not None:
                sp.end()
        except LoadAborted:
            # the failing shard is exactly what a failed-load trace must
            # show: record it with the error before unwinding
            if sp is not None:
                sp.set(error="aborted")
                sp.end()
            raise
        except BaseException as e:
            # fail fast from INSIDE the failing worker: the main thread
            # collects futures in submission order, so without this a
            # wrong tensor in the last shard would let every earlier
            # shard read (and stream to device) to completion first
            stop.set()
            if sp is not None:
                sp.set(error=f"{type(e).__name__}: {e}")
                sp.end()
            raise

    def _read_shard(fname: str) -> None:
        faults.fire("coldload.read")
        for name, arr in _iter_shard_tensors(path, kind, fname):
            if _aborted():
                raise LoadAborted(f"load of {path!r} aborted")
            stage(name, arr)
            if throttle_bytes_per_s and throttle_bytes_per_s > 0:
                with mu:
                    b = bytes_read[0]
                ahead = b / throttle_bytes_per_s - (
                    time.monotonic() - throttle_t0
                )
                while ahead > 0 and not _aborted():
                    time.sleep(min(ahead, 0.2))
                    ahead = b / throttle_bytes_per_s - (
                        time.monotonic() - throttle_t0
                    )

    # -- streaming h2d transfer thread (bucketed, double-buffered) ----------
    placed: Dict[str, Any] = {}
    xfer_err: List[BaseException] = []
    h2d_win: List[Optional[float]] = [None, None]
    h2d_counts = [0, 0]  # buckets, bytes
    targets = _flat_targets(plain, shapes, mesh) if place else {}

    def run_transfers() -> None:
        from ..engine.sleep import partition_buckets

        # double-buffered: bucket k+1 is issued while bucket k drains, so
        # in-flight bytes stay ~<= inflight_bound (two buckets)
        bucket_bytes = max(1, inflight_bound // 2)
        pending = None  # (flats, puts, nbytes, span)

        def finish(p) -> None:
            flats, puts, nb, sp = p
            try:
                puts = jax.block_until_ready(puts)
            except BaseException as e:
                if sp is not None:
                    sp.set(error=f"{type(e).__name__}: {e}")
                    sp.end()
                raise
            with mu:
                for f, a in zip(flats, puts):
                    placed[f] = a
                    buffers.pop(f, None)  # host copy freed as it lands
            h2d_counts[0] += 1
            h2d_counts[1] += nb
            h2d_win[1] = time.monotonic()
            if sp is not None:
                sp.end()

        try:
            draining = False
            while not draining:
                item = ready.get()
                if item is None:
                    break
                flats = [item]
                while True:
                    try:
                        nxt = ready.get_nowait()
                    except queue.Empty:
                        break
                    if nxt is None:
                        draining = True
                        break
                    flats.append(nxt)
                with mu:
                    arrs = {f: buffers[f] for f in flats}
                nbs = [arrs[f].nbytes for f in flats]
                for bucket in partition_buckets(nbs, bucket_bytes):
                    bflats = [flats[i] for i in bucket]
                    bsp = (
                        tracing.begin(
                            "coldload.h2d", parent=load_ctx,
                            activate=False,
                            bytes=sum(nbs[i] for i in bucket),
                            leaves=len(bflats),
                        )
                        if traced
                        else None
                    )
                    try:
                        faults.fire("coldload.h2d")
                        if h2d_win[0] is None:
                            h2d_win[0] = time.monotonic()
                        puts = jax.device_put(
                            [arrs[f] for f in bflats],
                            [targets[f] for f in bflats],
                        )
                    except BaseException as e:
                        if bsp is not None:
                            bsp.set(error=f"{type(e).__name__}: {e}")
                            bsp.end()
                        raise
                    cur = (bflats, puts, sum(nbs[i] for i in bucket), bsp)
                    if pending is not None:
                        finish(pending)
                    pending = cur
            if pending is not None:
                pending_, pending = pending, None
                finish(pending_)
        except BaseException as e:  # noqa: BLE001 — re-raised on the caller
            if (
                pending is not None
                and pending[3] is not None
                and not pending[3].ended
            ):
                # the double-buffered predecessor never finished: record
                # it as cut short so the failed load's trace is complete
                # (a span finish() already failed keeps its real error)
                pending[3].set(error="aborted by transfer failure")
                pending[3].end()
            xfer_err.append(e)

    xfer_thread = None
    if place:
        xfer_thread = threading.Thread(
            target=run_transfers, name="hf-load-h2d", daemon=True
        )
        xfer_thread.start()

    # -- reads ---------------------------------------------------------------
    err: Optional[BaseException] = None
    try:
        if workers == 1:
            for fname in files:
                read_shard(fname)
        else:
            from concurrent.futures import ThreadPoolExecutor

            with ThreadPoolExecutor(
                workers, thread_name_prefix="hf-load-read"
            ) as pool:
                futs = [pool.submit(read_shard, f) for f in files]
                for fut in futs:
                    try:
                        fut.result()
                    except LoadAborted as e:
                        if err is None:
                            err = e
                    except BaseException as e:  # noqa: BLE001
                        # the first REAL error wins (in file order);
                        # sibling LoadAborted from the stop signal is noise
                        if err is None or isinstance(err, LoadAborted):
                            err = e
                        stop.set()
    except BaseException as e:  # noqa: BLE001 — single-worker path
        err = e
    read_t1 = time.monotonic()

    if err is None:
        # Per-slice completeness: every (key, layer[, expert]) the model
        # expects must have been written by some checkpoint tensor —
        # whole-key presence is not enough (stacked buffers zero-init, so
        # one staged layer would mask the rest being absent).
        problems = []
        for flat in flat_shapes:
            absent = want[flat] - staged[flat]
            if absent:
                ex = sorted(absent)[:4]
                problems.append(
                    f"{flat}: {len(absent)}/{len(want[flat])} slices never "
                    f"staged (e.g. {ex})"
                )
        if problems:
            err = ValueError(
                f"checkpoint {path!r} is incomplete: "
                + "; ".join(sorted(problems))
            )

    if place:
        if err is None and not streaming:
            # sequential schedule: every transfer happens after every read
            for flat in flat_shapes:
                ready.put(flat)
        ready.put(None)
        xfer_thread.join()
        if err is None and xfer_err:
            err = xfer_err[0]
    # read-side stats are valid even on the error paths (an aborted
    # prefetch reports how many bytes it actually spent)
    st.read_s = read_t1 - t_begin
    st.convert_s = convert_s[0]
    st.bytes_read = bytes_read[0]
    if err is not None:
        load_sp.set(error=f"{type(err).__name__}: {err}")
        load_sp.end()
        raise err

    if not place:
        st.total_s = time.monotonic() - t_begin
        load_sp.set(bytes_read=st.bytes_read)
        load_sp.end()
        return _unflatten(dict(buffers))

    st.h2d_s = (
        (h2d_win[1] - h2d_win[0]) if h2d_win[0] is not None else 0.0
    )
    st.buckets_h2d, st.bytes_h2d = h2d_counts
    params = _quantize_and_repin(cfg, _unflatten(placed), mesh)
    st.total_s = time.monotonic() - t_begin
    # overlap: time the read pipeline and the h2d stream were BOTH in
    # flight — what the streaming schedule saves over read-then-transfer
    if h2d_win[0] is not None:
        st.overlap_s = max(
            0.0, min(read_t1, h2d_win[1]) - max(t_begin, h2d_win[0])
        )
    st.overlap_frac = st.overlap_s / st.total_s if st.total_s > 0 else 0.0
    load_sp.set(
        bytes_read=st.bytes_read,
        bytes_h2d=st.bytes_h2d,
        buckets_h2d=st.buckets_h2d,
        overlap_frac=round(st.overlap_frac, 6),
    )
    load_sp.end()
    return params


def place_staged_params(
    staged: Dict[str, Any],
    cfg: LlamaConfig,
    *,
    mesh: Any = None,
    max_inflight_bytes: Optional[int] = None,
    stats: Optional[LoadStats] = None,
) -> Dict[str, Any]:
    """The H2D half of the streaming loader, standalone: device-place a
    host tree produced by ``load_params(..., place=False)`` (the prefetch
    path), bucketed and double-buffered with the same in-flight bound.
    The host arrays are left intact (the caller owns them)."""
    import jax

    from ..engine.sleep import partition_buckets

    t_begin = time.monotonic()
    st = stats if stats is not None else LoadStats()
    plain = (
        dataclasses.replace(cfg, quantization="")
        if getattr(cfg, "quantization", "")
        else cfg
    )
    flat = {"/".join(p): a for p, a in _flatten(staged)}
    targets = _flat_targets(plain, staged, mesh)
    keys = list(flat)
    nbs = [flat[k].nbytes for k in keys]
    bucket_bytes = max(
        1, int(max_inflight_bytes or DEFAULT_LOAD_INFLIGHT_BYTES) // 2
    )
    placed: Dict[str, Any] = {}
    pending = None
    stage_sp = tracing.begin(
        "coldload.place_staged", activate=False, leaves=len(keys)
    )
    traced = stage_sp is not tracing.NOOP_SPAN
    stage_ctx = stage_sp.context() if traced else None

    def finish(p) -> None:
        bkeys, puts, nb, sp = p
        try:
            puts = jax.block_until_ready(puts)
        except BaseException as e:
            if sp is not None:
                sp.set(error=f"{type(e).__name__}: {e}")
                sp.end()
            raise
        for k, a in zip(bkeys, puts):
            placed[k] = a
        st.buckets_h2d += 1
        st.bytes_h2d += nb
        if sp is not None:
            sp.end()

    try:
        for bucket in partition_buckets(nbs, bucket_bytes):
            bkeys = [keys[i] for i in bucket]
            bsp = (
                tracing.begin(
                    "coldload.h2d", parent=stage_ctx, activate=False,
                    bytes=sum(nbs[i] for i in bucket), leaves=len(bkeys),
                )
                if traced
                else None
            )
            try:
                faults.fire("coldload.h2d")
                puts = jax.device_put(
                    [flat[k] for k in bkeys], [targets[k] for k in bkeys]
                )
            except BaseException as e:
                if bsp is not None:
                    bsp.set(error=f"{type(e).__name__}: {e}")
                    bsp.end()
                raise
            cur = (bkeys, puts, sum(nbs[i] for i in bucket), bsp)
            if pending is not None:
                finish(pending)
            pending = cur
        if pending is not None:
            pending_, pending = pending, None
            finish(pending_)
    except BaseException as e:
        if (
            pending is not None
            and pending[3] is not None
            and not pending[3].ended
        ):
            pending[3].set(error="aborted by transfer failure")
            pending[3].end()
        stage_sp.set(error=f"{type(e).__name__}: {e}")
        stage_sp.end()
        raise

    params = _quantize_and_repin(cfg, _unflatten(placed), mesh)
    st.h2d_s = st.total_s = time.monotonic() - t_begin
    stage_sp.set(bytes_h2d=st.bytes_h2d, buckets_h2d=st.buckets_h2d)
    stage_sp.end()
    return params


def estimate_param_bytes(
    cfg: LlamaConfig,
    transfer_quant: str = "off",
    hot_head: bool = True,
) -> int:
    """Host bytes a staged copy of the model occupies — the prefetch
    budget pre-check. Shapes only; nothing read.

    ``transfer_quant`` ("int8"/"fp8", --sleep-quant) sizes the leaves the
    compressed staging path quantizes at their payload+scale bytes instead
    of cfg.dtype — without it the admission check would over-reserve ~2x
    for an int8-staged model and reject prefetches that actually fit."""
    import jax

    from .registry import init_params_for
    from . import quant as quant_mod

    plain = (
        dataclasses.replace(cfg, quantization="")
        if getattr(cfg, "quantization", "")
        else cfg
    )
    shapes = jax.eval_shape(
        lambda: init_params_for(jax.random.key(0), plain)
    )
    itemsize = np.dtype(cfg.dtype).itemsize
    mode = transfer_quant if transfer_quant not in ("", "off") else ""
    if not mode:
        return sum(
            int(np.prod(node.shape)) * itemsize
            for _, node in _flatten(shapes)
        )
    import jax.tree_util as jtu

    flat_leaves = jtu.tree_flatten(shapes)[0]
    plan = quant_mod.transfer_quant_plan(shapes, hot_head=hot_head, prefix="")
    total = 0
    for leaf, q in zip(flat_leaves, plan):
        if q:
            total += quant_mod.payload_nbytes(leaf.shape, mode)
        else:
            total += int(np.prod(leaf.shape)) * itemsize
    return total


def load_model(
    path: str, **overrides: Any
) -> Tuple[LlamaConfig, Dict[str, Any]]:
    cfg = config_from_hf(path, **overrides)
    return cfg, load_params(path, cfg)


def _flatten(tree: Dict[str, Any], prefix: Tuple[str, ...] = ()):
    for k, v in tree.items():
        if isinstance(v, dict):
            yield from _flatten(v, prefix + (k,))
        else:
            yield prefix + (k,), v


def _unflatten(flat: Dict[str, Any]) -> Dict[str, Any]:
    # one definition of the '/'-joined flat-key convention, shared with
    # the digest maps / tier manifests (lazy import: parse-time must not
    # pull the engine package)
    from ..engine.chunk_store import unflatten_tree

    return unflatten_tree(flat)
