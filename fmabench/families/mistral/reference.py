"""The plain reference of Mistral: RMSNorm, grouped-query attention with
rotate-half RoPE over the full causal mask, SwiGLU. Straightforward
``jax.numpy`` in float32 (the caller sets ``highest`` precision), no
kernels, no cache, no batching; imports nothing of the program.

The weights are made here from the seed, by the initialisation the program
documents (normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``,
cast to bfloat16, norms at one), and are the program's numbers only because
the same seed goes through the same public ``jax.random`` calls.

``init_weights`` and ``forward_logits`` take the FFN as an argument, so that
the family which shares this trunk (a routed FFN in its place) brings only
what differs.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Sequence

import jax
import jax.numpy as jnp

#: what the int8 control rounds, as paths into the weights
MATMUL_WEIGHTS = (
    "layers.wq", "layers.wk", "layers.wv", "layers.wo",
    "layers.w_gate", "layers.w_up", "layers.w_down", "lm_head",
)


def dense(key, shape, fan_in):
    return (
        jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
    ).astype(jnp.bfloat16)


def swiglu_weights(d: Dict[str, Any], ks: Sequence[Any]) -> Dict[str, Any]:
    h, L, f = d["hidden_size"], d["num_layers"], d["intermediate_size"]
    return {
        "w_gate": dense(ks[0], (L, h, f), h),
        "w_up": dense(ks[1], (L, h, f), h),
        "w_down": dense(ks[2], (L, f, h), f),
    }


def init_weights(
    seed, d: Dict[str, Any], ffn_keys: int = 3,
    ffn_weights: Callable[..., Dict[str, Any]] = swiglu_weights,
) -> Dict[str, Any]:
    """Seeded bf16 weights, layer-stacked. See the module docstring."""
    h, L = d["hidden_size"], d["num_layers"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 4 + ffn_keys)
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layers = {
        "attn_norm": ones((L, h)),
        "wq": dense(ks[0], (L, h, q), h),
        "wk": dense(ks[1], (L, h, kv), h),
        "wv": dense(ks[2], (L, h, kv), h),
        "wo": dense(ks[3], (L, q, h), q),
        "mlp_norm": ones((L, h)),
        **ffn_weights(d, ks[4:]),
    }
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": layers,
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def rope(x, positions, theta):
    """Rotate-half RoPE. x: [s, heads, hd]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def swiglu(x, gate, up, down):
    f32 = jnp.float32
    return (jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))) @ down.astype(f32)


def swiglu_ffn(d, lp, x):
    return swiglu(x, lp["w_gate"], lp["w_up"], lp["w_down"])


def forward_logits(d, weights, ids, length, rows, ffn=swiglu_ffn):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    S = ids.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    pos = jnp.arange(S)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    x = weights["embed"][ids].astype(f32)

    def layer(x, lp):
        h = rms(x, lp["attn_norm"], d["rms_eps"])
        q = (h @ lp["wq"].astype(f32)).reshape(S, heads, hd)
        k = (h @ lp["wk"].astype(f32)).reshape(S, kvh, hd)
        v = (h @ lp["wv"].astype(f32)).reshape(S, kvh, hd)
        q, k = rope(q, pos, d["rope_theta"]), rope(k, pos, d["rope_theta"])
        g = heads // kvh
        qg = q.reshape(S, kvh, g, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        x = x + attn.reshape(S, heads * hd) @ lp["wo"].astype(f32)
        h = rms(x, lp["mlp_norm"], d["rms_eps"])
        return x + ffn(d, lp, h), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = rms(x[rows], weights["final_norm"], d["rms_eps"])
    return x @ weights["lm_head"].astype(f32)
