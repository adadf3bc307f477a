"""The example family's plain reference, whole in this file: float32
``jax.numpy`` (the caller sets ``highest`` precision), no kernels, no
cache, no batching; imports nothing of the program and nothing of another
family. Per layer:

    h = rms(x, attn_norm);  q, k, v = h wq, h wk, h wv
    q, k = rope(rms(q, q_norm)), rope(rms(k, k_norm))     per head, before RoPE
    x = x + rms(causal_attention(q, k, v) wo, post_attn_norm)
    h = rms(x, mlp_norm)
    x = x + rms((gelu_tanh(h w_gate) * (h w_up)) w_down, post_ffn_norm)

Weights from the seed by the initialisation the program documents:
normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``, bfloat16,
every norm at one.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

MATMUL_WEIGHTS = (
    "layers.wq", "layers.wk", "layers.wv", "layers.wo",
    "layers.w_gate", "layers.w_up", "layers.w_down", "lm_head",
)


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    h, L, f, hd = d["hidden_size"], d["num_layers"], d["ffn_size"], d["head_dim"]
    q, kv = d["num_heads"] * hd, d["num_kv_heads"] * hd
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 7)

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(jnp.bfloat16)

    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layers = {
        "attn_norm": ones((L, h)), "mlp_norm": ones((L, h)),
        "post_attn_norm": ones((L, h)), "post_ffn_norm": ones((L, h)),
        "q_norm": ones((L, hd)), "k_norm": ones((L, hd)),
        "wq": dense(ks[0], (L, h, q), h),
        "wk": dense(ks[1], (L, h, kv), h),
        "wv": dense(ks[2], (L, h, kv), h),
        "wo": dense(ks[3], (L, q, h), q),
        "w_gate": dense(ks[4], (L, h, f), h),
        "w_up": dense(ks[5], (L, h, f), h),
        "w_down": dense(ks[6], (L, f, h), f),
    }
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": layers,
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def _rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def _rope(x, positions, theta):
    """Rotate-half RoPE. x: [s, heads, hd]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    S = ids.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    eps, theta = d["rms_eps"], d["rope_theta"]
    pos = jnp.arange(S)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)
    x = weights["embed"][ids].astype(f32)

    def layer(x, lp):
        h = _rms(x, lp["attn_norm"], eps)
        q = (h @ lp["wq"].astype(f32)).reshape(S, heads, hd)
        k = (h @ lp["wk"].astype(f32)).reshape(S, kvh, hd)
        v = (h @ lp["wv"].astype(f32)).reshape(S, kvh, hd)
        q = _rope(_rms(q, lp["q_norm"], eps), pos, theta)
        k = _rope(_rms(k, lp["k_norm"], eps), pos, theta)
        qg = q.reshape(S, kvh, heads // kvh, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        y = attn.reshape(S, heads * hd) @ lp["wo"].astype(f32)
        x = x + _rms(y, lp["post_attn_norm"], eps)
        h = _rms(x, lp["mlp_norm"], eps)
        gate = jax.nn.gelu(h @ lp["w_gate"].astype(f32), approximate=True)
        y = (gate * (h @ lp["w_up"].astype(f32))) @ lp["w_down"].astype(f32)
        return x + _rms(y, lp["post_ffn_norm"], eps), None

    x, _ = jax.lax.scan(layer, x, weights["layers"])
    x = _rms(x[rows], weights["final_norm"], eps)
    return x @ weights["lm_head"].astype(f32)
