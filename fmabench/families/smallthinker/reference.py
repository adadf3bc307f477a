"""The plain reference of SmallThinker, layer l of the depth held:

    r = x . W_r                      64 logits from the layer's INPUT x
    w = softmax(the 6 largest of r)  zero off the top 6
    a = x + W_o . Attn(q, k, v)      q, k, v from RMSNorm(x); layout 1:
                                     rotate-half RoPE and a window, query i
                                     sees keys j with i - window < j <= i;
                                     layout 0: no positional encoding, the
                                     full causal mask
    y = a + sum_e w_e . W_down,e (relu(W_gate,e . h) * (W_up,e . h)),
                                     h = RMSNorm(a), summed over ALL experts

Straightforward ``jax.numpy`` in float32 (the caller sets ``highest``
precision), no kernels, no cache, no routing of rows, no batching; imports
nothing of the program. Two concessions to the size of a 13k-token request
at eight layers, neither of which changes a number: attention is computed a
block of query rows at a time (28 x 13,312^2 float32 scores would be
19.8 GB whole), and the weights stay the bfloat16 values they are and are
raised to float32 where they are used (3.97 B x 4 bytes would not fit).

The weights are made here from the seed, by the initialisation the program
documents (normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``,
cast to bfloat16, norms at one), and are the program's numbers only because
the same seed goes through the same public ``jax.random`` calls.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

#: what the int8 control rounds, as paths into the weights. The control
#: holds a second copy of every weight it rounds beside the first, and two
#: whole copies of 7.9 GB do not fit the chip the comparison runs on: the
#: router stays as it is (as in every family), and of the experts' three
#: matrices the gate and the down projection are rounded and the up
#: projection is not.
MATMUL_WEIGHTS = (
    "layers.wq", "layers.wk", "layers.wv", "layers.wo",
    "layers.w_gate", "layers.w_down", "lm_head",
)

#: query rows whose scores exist at once
Q_BLOCK = 256


def dense(key, shape, fan_in):
    return (
        jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
    ).astype(jnp.bfloat16)


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded bf16 weights, layer-stacked. See the module docstring."""
    h, L, f, E = d["hidden_size"], d["num_layers"], d["expert_size"], d["num_experts"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 8)
    ones = functools.partial(jnp.ones, dtype=jnp.bfloat16)
    layers = {
        "attn_norm": ones((L, h)),
        "wq": dense(ks[0], (L, h, q), h),
        "wk": dense(ks[1], (L, h, kv), h),
        "wv": dense(ks[2], (L, h, kv), h),
        "wo": dense(ks[3], (L, q, h), q),
        "mlp_norm": ones((L, h)),
        "router": dense(ks[4], (L, h, E), h),
        "w_gate": dense(ks[5], (L, E, h, f), h),
        "w_up": dense(ks[6], (L, E, h, f), h),
        "w_down": dense(ks[7], (L, E, f, h), f),
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


def attention(d, q, k, v, length, windowed):
    """q [S, heads, hd], k, v [S, kvh, hd] -> [S, heads * hd]; ``windowed``
    (a traced 0/1) picks the mask. A block of query rows at a time."""
    S, heads, hd = q.shape
    kvh = k.shape[1]
    block = min(Q_BLOCK, S)
    keys = jnp.arange(S)

    def rows(i):
        pos = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        qg = qb.reshape(block, kvh, heads // kvh, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        mask = (keys[None, :] <= pos[:, None]) & (keys[None, :] < length)
        in_window = keys[None, :] > pos[:, None] - d["window"]
        mask = mask & (in_window | (windowed == 0))
        # a padded row past the sequence's end sees itself, so that no row's
        # softmax is empty (its NaN would reach real rows through 0 * NaN)
        mask = mask | (keys[None, :] == pos[:, None])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(block, heads * hd)

    return jax.lax.map(rows, jnp.arange(S // block)).reshape(S, heads * hd)


def experts(d, stacks, layer, h, weights):
    """sum over ALL experts of weights[:, e] . ReGLU_e(h); weights [S, E].
    ``stacks``: the model's [L, E, ...] expert matrices, read one expert of
    one layer at a time (a layer's 64 experts sliced out whole would be
    three 240 MB temporaries beside two copies of the weights)."""
    f32 = jnp.float32
    gates, ups, downs = stacks

    def one_expert(acc, ew):
        e, w = ew
        gate, up, down = gates[layer, e], ups[layer, e], downs[layer, e]
        y = (jax.nn.relu(h @ gate.astype(f32)) * (h @ up.astype(f32))) @ down.astype(f32)
        return acc + y * w[:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(h),
        (jnp.arange(d["num_experts"]), weights.T),
    )
    return out


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    S = ids.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    pos = jnp.arange(S)
    x = weights["embed"][ids].astype(f32)
    layout = (
        jnp.asarray(d["window_layout"], jnp.int32),
        jnp.asarray(d["rope_layout"], jnp.int32),
    )

    small = {k: v for k, v in weights["layers"].items() if not k.startswith("w_")}
    stacks = tuple(weights["layers"][k] for k in ("w_gate", "w_up", "w_down"))

    def layer(x, scanned):
        lp, l, windowed, roped = scanned
        # the router reads the layer's input, before the attention norm
        top_vals, top_idx = jax.lax.top_k(
            x @ lp["router"].astype(f32), d["experts_per_token"]
        )
        expert_weights = jnp.sum(
            jax.nn.one_hot(top_idx, d["num_experts"], dtype=f32)
            * jax.nn.softmax(top_vals, axis=-1)[..., None], axis=-2,
        )  # [S, E], zero off the top k
        h = rms(x, lp["attn_norm"], d["rms_eps"])
        q = (h @ lp["wq"].astype(f32)).reshape(S, heads, hd)
        k = (h @ lp["wk"].astype(f32)).reshape(S, kvh, hd)
        v = (h @ lp["wv"].astype(f32)).reshape(S, kvh, hd)
        q = jnp.where(roped == 1, rope(q, pos, d["rope_theta"]), q)
        k = jnp.where(roped == 1, rope(k, pos, d["rope_theta"]), k)
        x = x + attention(d, q, k, v, length, windowed) @ lp["wo"].astype(f32)
        h = rms(x, lp["mlp_norm"], d["rms_eps"])
        return x + experts(d, stacks, l, h, expert_weights), None

    x, _ = jax.lax.scan(
        layer, x, (small, jnp.arange(d["num_layers"])) + layout
    )
    x = rms(x[rows], weights["final_norm"], d["rms_eps"])
    return x @ weights["lm_head"].astype(f32)
