"""Checkpoint save/load for engine weights — the real cold-start path.

The reference's dominant cold-start cost is model download + load into HBM
(vLLM's weight loading; the launcher exists to hide exactly this). Here the
serving engine loads params from an Orbax checkpoint directory:

  * sharding-aware restore: each leaf is restored DIRECTLY into its target
    NamedSharding/device placement (no host-then-scatter double copy) —
    Orbax on TPU reads from disk into per-device buffers;
  * level-2 wake (`engine/sleep.py` L2_DISCARD) re-loads from the same
    checkpoint, so a discard-sleep's wake is a disk read, not a re-init;
  * `save_params` exists so deployments can seed checkpoints from any
    source (HF export scripts, trainers) in the exact pytree layout
    `llama.init_params` defines.

Format: one Orbax StandardCheckpoint under ``<dir>/params`` plus a
``config.json`` carrying the LlamaConfig fields it was written with, so a
mismatched ISC option string fails loudly at load time instead of silently
serving shape-mangled weights.
"""

from __future__ import annotations

import dataclasses
import json
import os
from typing import Any, Dict, Optional

import jax

from . import llama

CONFIG_FILE = "config.json"
PARAMS_DIR = "params"
#: per-leaf content digests (engine/chunk_store.py), written at save time
#: so a load gets every weight's identity WITHOUT hashing restored device
#: arrays — the "hash computed once" contract for the Orbax path
MANIFEST_FILE = "manifest.json"

#: LlamaConfig fields that must match between checkpoint and engine config
#: (dtype/attention_impl are runtime choices, not weight-layout facts).
_SHAPE_FIELDS = (
    "vocab_size",
    "hidden_size",
    "num_layers",
    "num_heads",
    "num_kv_heads",
    "head_dim",
    "intermediate_size",
    "tie_embeddings",
    "num_experts",  # MoE family: expert count is a weight-layout fact
    # Gemma-family knobs: they change the parameter SET (post/qk norms) or
    # the stored-weight semantics ((1+w) zero-centered norms, GeGLU,
    # scaled embeddings) — a mismatch must fail loudly, not serve garbage
    "norm_offset",
    "hidden_activation",
    "embed_scale",
    "post_norms",
    "qk_norm",
    # a looped stack: the passes change the K and V a token holds and the
    # parameter set (the exit gate)
    "loop_steps",
)


def _config_dict(cfg: llama.LlamaConfig) -> Dict[str, Any]:
    d = dataclasses.asdict(cfg)
    d["dtype"] = str(cfg.dtype.__name__ if hasattr(cfg.dtype, "__name__") else cfg.dtype)
    return d


def save_params(
    directory: str, cfg: llama.LlamaConfig, params: Dict[str, Any]
) -> None:
    """Write params + config to `directory` (created; must not already hold
    a checkpoint)."""
    import orbax.checkpoint as ocp

    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(os.path.join(directory, PARAMS_DIR), params)
        ckptr.wait_until_finished()
    with open(os.path.join(directory, CONFIG_FILE), "w") as f:
        json.dump(_config_dict(cfg), f, indent=2, sort_keys=True)
    # Content manifest (offline, so load pays nothing): flat key -> digest
    # over host copies of exactly what was written. Orbax restore is
    # bit-exact, so these identify the restored leaves too — the tiered
    # pool dedupes sibling fine-tune checkpoints and the delta-swap moves
    # only differing leaves on the strength of this file.
    from ..engine.chunk_store import digest_tree

    # digest_tree hashes leaf by leaf (leaf_digest np.asarray's each one),
    # so peak extra host memory is one leaf's copy, never a second full
    # model tree
    with open(os.path.join(directory, MANIFEST_FILE), "w") as f:
        json.dump({"format": 1, "digests": digest_tree(params)}, f, indent=2)


def validate_config(directory: str, cfg: llama.LlamaConfig) -> None:
    path = os.path.join(directory, CONFIG_FILE)
    try:
        with open(path) as f:
            saved = json.load(f)
    except OSError as e:
        raise FileNotFoundError(f"no checkpoint config at {path}") from e
    # A key absent from an older checkpoint's config.json means the
    # checkpoint predates the field: its weights carry the field's
    # then-implicit DEFAULT semantics, so compare against the dataclass
    # default — not the engine's value, which would accept any engine
    # setting and silently serve weights under the wrong convention.
    field_defaults = {
        f.name: f.default for f in dataclasses.fields(type(cfg))
    }
    mismatches = {
        k: (saved.get(k, field_defaults.get(k)), getattr(cfg, k, None))
        for k in _SHAPE_FIELDS
        if saved.get(k, field_defaults.get(k)) != getattr(cfg, k, None)
    }
    if mismatches:
        raise ValueError(
            f"checkpoint {directory} was written for a different model shape: "
            + ", ".join(
                f"{k}: ckpt={a} engine={b}" for k, (a, b) in mismatches.items()
            )
        )


def load_params(
    directory: str,
    cfg: llama.LlamaConfig,
    mesh: Optional[jax.sharding.Mesh] = None,
    stats_out: Optional[Dict[str, Any]] = None,
) -> Dict[str, Any]:
    """Restore params from `directory`, directly into their serving
    placement (sharded over `mesh` when given, committed to the default
    device otherwise). Checkpoints are always the bf16 form: a quantized
    serving config restores bf16 and quantizes on the way in (runtime
    quantization, models/quant.py).

    ``stats_out`` (a dict, filled in place) records ``restore_s`` (the
    disk->device restore wall — Orbax lands each leaf straight in its
    placement, so read and H2D are one window) and ``bytes`` — the
    cold-load accounting the engine's swap metrics report on pool
    misses."""
    import time

    import orbax.checkpoint as ocp

    serve_cfg = cfg
    if getattr(cfg, "quantization", ""):
        import dataclasses

        cfg = dataclasses.replace(cfg, quantization="")

    validate_config(directory, cfg)
    directory = os.path.abspath(directory)

    # Build the target pytree abstractly: shapes/dtypes from init logic
    # without materializing weights (eval_shape), shardings from the same
    # logical-axis rules the engine serves with.
    from .registry import init_params_for

    abstract = jax.eval_shape(
        lambda: init_params_for(jax.random.key(0), cfg)
    )
    if mesh is not None:
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ..parallel.mesh import named_sharding

        from .registry import logical_axes_for

        axes = logical_axes_for(cfg)

        def to_target(a, ax):
            sh = NamedSharding(mesh, P()) if ax is None else named_sharding(mesh, ax)
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh)

        target = jax.tree.map(
            to_target,
            abstract,
            axes,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct),
        )
    else:
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        target = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sharding),
            abstract,
        )
    t0 = time.monotonic()
    with ocp.StandardCheckpointer() as ckptr:
        params = ckptr.restore(os.path.join(directory, PARAMS_DIR), target)
    if stats_out is not None:
        stats_out["restore_s"] = time.monotonic() - t0
        stats_out["bytes"] = sum(
            x.nbytes for x in jax.tree.leaves(params)
        )
        # content manifest written at save time (flat key -> digest):
        # the restored tree's identity without hashing device arrays;
        # a checkpoint predating the manifest just yields no digests
        mpath = os.path.join(directory, MANIFEST_FILE)
        if os.path.isfile(mpath):
            try:
                with open(mpath) as f:
                    stats_out["digests"] = json.load(f).get("digests") or {}
            except (OSError, ValueError):
                stats_out["digests"] = {}
    if serve_cfg is not cfg:
        from .registry import logical_axes_for, maybe_quantize

        params = maybe_quantize(serve_cfg, params)
        if mesh is not None:
            # re-pin: the eager quantize ops don't all preserve the serving
            # sharding (scale reductions in particular)
            from ..parallel.mesh import shard_pytree

            params = shard_pytree(params, mesh, logical_axes_for(serve_cfg))
    return params


def main(argv=None) -> int:
    """Seed a checkpoint directory (`python -m ...models.checkpoint --model
    bench-1b --out /ckpts/bench-1b`): random-init weights in the serving
    layout — deployments replace this with converted real weights."""
    import argparse
    import time

    p = argparse.ArgumentParser(prog="fma-seed-checkpoint")
    p.add_argument("--model", required=True, help="MODEL_CONFIGS key")
    p.add_argument("--out", required=True)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    from ..engine.server import MODEL_CONFIGS

    cfg = MODEL_CONFIGS[args.model]()
    t0 = time.monotonic()
    params = llama.init_params(jax.random.key(args.seed), cfg)
    params = jax.block_until_ready(params)
    save_params(args.out, cfg, params)
    nbytes = sum(x.nbytes for x in jax.tree.leaves(params))
    print(
        f"wrote {args.model} ({nbytes / 2**30:.2f} GiB) to {args.out} "
        f"in {time.monotonic() - t0:.1f}s"
    )
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
