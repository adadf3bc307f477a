"""Model-family dispatch: init/sharding by config type.

The forward path (prefill/decode_step in models/llama.py) is shared across
families — the scanned layer body dispatches its FFN on the config
(`llama._ffn`), so the engine never branches. Only initialization and the
logical-axes pytree differ per family.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax

from . import exaone_moe, kimi_linear, llama, moe, nemotron_h, olmo_hybrid


def init_params_for(key: jax.Array, cfg: llama.LlamaConfig) -> Dict[str, Any]:
    if isinstance(cfg, kimi_linear.KimiLinearConfig):
        params = kimi_linear.init_params(key, cfg)
    elif isinstance(cfg, exaone_moe.ExaoneMoeConfig):
        params = exaone_moe.init_params(key, cfg)
    elif isinstance(cfg, nemotron_h.NemotronHConfig):
        params = nemotron_h.init_params(key, cfg)
    elif isinstance(cfg, moe.MoeConfig):
        params = moe.init_params(key, cfg)
    elif isinstance(cfg, olmo_hybrid.OlmoHybridConfig):
        params = olmo_hybrid.init_params(key, cfg)
    else:
        params = llama.init_params(key, cfg)
    return maybe_quantize(cfg, params)


def init_params_placed(
    key: jax.Array, cfg: llama.LlamaConfig, mesh: Any = None
) -> Dict[str, Any]:
    """Random-init params created directly in their serving placement:
    sharded over `mesh` per the logical-axis rules, or committed to the
    default device. One jitted init with ``out_shardings`` — each device
    generates only its own shards, so a model that needs the mesh (16 GB of
    bf16 at tp=4) never materializes whole on the first chip."""
    from ..parallel.mesh import logical_shardings

    if mesh is not None:
        shardings = logical_shardings(mesh, logical_axes_for(cfg))
    else:
        shardings = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    return jax.jit(_init_fn(cfg), out_shardings=shardings)(key)


@functools.lru_cache(maxsize=None)
def _init_fn(cfg: llama.LlamaConfig):
    """One function object per config, so repeated builds of a config share
    jit's trace and compile caches (keyed on function identity). Holds the
    config only — never a device or a mesh, which would outlive a device
    release (engine/device.py)."""
    return lambda key: init_params_for(key, cfg)


def logical_axes_for(cfg: llama.LlamaConfig) -> Dict[str, Any]:
    if isinstance(cfg, kimi_linear.KimiLinearConfig):
        axes = kimi_linear.param_logical_axes(cfg)
    elif isinstance(cfg, exaone_moe.ExaoneMoeConfig):
        axes = exaone_moe.param_logical_axes(cfg)
    elif isinstance(cfg, nemotron_h.NemotronHConfig):
        axes = nemotron_h.param_logical_axes(cfg)
    elif isinstance(cfg, moe.MoeConfig):
        axes = moe.param_logical_axes(cfg)
    elif isinstance(cfg, olmo_hybrid.OlmoHybridConfig):
        axes = olmo_hybrid.param_logical_axes(cfg)
    else:
        axes = llama.param_logical_axes(cfg)
    if getattr(cfg, "quantization", "") == "int8":
        from .quant import quantized_axes

        axes = quantized_axes(axes)
    return axes


def maybe_quantize(cfg: llama.LlamaConfig, params: Dict[str, Any]) -> Dict[str, Any]:
    """Apply the config's weight quantization (runtime quantization: bf16
    checkpoints stay bf16 on disk; HBM holds the int8 form)."""
    q = getattr(cfg, "quantization", "")
    if not q:
        return params
    if q != "int8":
        raise ValueError(f"unknown quantization {q!r}")
    from .quant import quantize_params

    return quantize_params(params)
