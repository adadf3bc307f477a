"""The plain reference of K-EXAONE's main stack (``model_type: exaone_moe``).
Layer l of the depth held:

    h = x + RMSNorm_a(Attn_l(x))          norms on each sub-layer's OUTPUT, none
    y = h + RMSNorm_f(FFN_l(h))           on its input; eps 1e-5, plain weights,
                                          no bias; a final RMSNorm, an untied head

    Attn: q = W_q x (64 x 128), k, v = W_k x, W_v x (8 x 128); q and k
        RMS-normalised over a head's 128 with a learned weight, BEFORE any
        rotary; a sliding layer (``layer_windows`` 1) then takes rotate-half
        RoPE (theta 1e6) and key j is visible to query i iff i - 128 < j <= i;
        a full layer takes the whole causal mask and NO positional encoding;
        scores q . k / sqrt(128), 8 query heads a KV head; W_o (P v)

    FFN: layer 0 SwiGLU 18,432 wide; every later layer
        s = sigmoid(W_r h) over all 128; the top 8 of s + bias;
        w_e = 2.5 s_e / sum_top8 s
        out = shared(h) + sum_{e in top 8, e HELD} w_e expert_e(h)
    experts and the shared expert SwiGLU 2,048 wide, with the experts
    ``share_index * held ...`` held here: what the absent experts would add is
    left out, as the program leaves it out.

What the comparison sees is the MAIN path: the served tokens are the main
model's whether a draft was accepted or not, so the prediction module (its
block, projection and norms) is not computed here, and nothing of it is
drawn: the program's stacks hold its block LAST, and an element of a draw is
a function of the key and of its own row-major index alone, so the first
``num_layers`` entries of a stack drawn one entry shorter are the program's
(``tests/fmabench/test_fmabench_exaone_moe.py`` holds that bit for bit). The
module's arithmetic is held to the repo's own reference on the CPU
(``tests/test_exaone_moe.py``).

Straightforward ``jax.numpy`` in float32 (the caller sets ``highest``
precision), no kernels, no cache, no batching; imports nothing of the
program. Three concessions to the size of a 4,096-token request, none of which
changes a number: attention is computed a block of query rows at a time; the
weights stay the bfloat16 values they are and are raised to float32 where
they are used; and the held experts' three stacks, 4.2 of the 8.79 GB, are no
leaves: each expert's matrices are drawn where they are used
(``nemotron_h``'s ``normal_rows``), since the comparison's control keeps two
whole copies of every leaf and two of 8.79 GB do not fit the chip.

The weights are made here from the seed, by the initialisation the program
documents (normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``, cast
to bfloat16, norms at one, the selection bias normal(0, 0.01) in float32;
``wq`` and ``wk`` drawn [layers, out, in], as the program stores them), and
are the program's numbers only because the same seed goes through the same
public ``jax.random`` calls.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from fmabench import spec

_nemotron = spec.sibling_part(__file__, "nemotron_h", "reference")
normal_rows = _nemotron.normal_rows
dense = _nemotron.dense
rms = _nemotron.rms

#: what the int8 control rounds, as paths into the weights: every attention
#: matrix, the dense layer, the shared experts and the head, 3.26 of the 3.50
#: GB of leaves. The held experts' matrices are no leaves (``expert_matrix``
#: draws them where they are used) and are not rounded, the embedding is not,
#: and the router stays as it is, as in every family.
MATMUL_WEIGHTS = (
    "layers.wq", "layers.wk", "layers.wv", "layers.wo",
    "dense.w_gate", "dense.w_up", "dense.w_down",
    "experts.s_gate", "experts.s_up", "experts.s_down", "lm_head",
)

#: query rows whose scores exist at once
Q_BLOCK = 256
#: what draws the held experts' three stacks, [expert layers + 1, held, ., .]
EXPERT_KEYS = ("w_gate_key", "w_up_key", "w_down_key")


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights of the main stack. See the module docstring."""
    h, L = d["hidden_size"], d["num_layers"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    ne, nd = d["expert_layers"], d["first_dense"]
    W, fd = d["router_width"], d["dense_size"]
    fs = d["shared_experts"] * d["expert_size"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 16)
    ones = lambda shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": {
            "wq": dense(ks[0], (L, q, h), h),
            "wk": dense(ks[1], (L, kv, h), h),
            "wv": dense(ks[2], (L, h, kv), h),
            "wo": dense(ks[3], (L, q, h), q),
            "q_norm": ones((L, d["head_dim"])),
            "k_norm": ones((L, d["head_dim"])),
            "post_attn_norm": ones((L, h)),
            "post_ffn_norm": ones((L, h)),
        },
        "dense": {
            "w_gate": dense(ks[4], (nd, h, fd), h),
            "w_up": dense(ks[5], (nd, h, fd), h),
            "w_down": dense(ks[6], (nd, fd, h), fd),
        },
        "experts": {
            "router": dense(ks[7], (ne, h, W), h),
            "router_bias": 0.01 * jax.random.normal(ks[8], (ne, W), jnp.float32),
            "w_gate_key": ks[9],
            "w_up_key": ks[10],
            "w_down_key": ks[11],
            "s_gate": dense(ks[12], (ne, h, fs), h),
            "s_up": dense(ks[13], (ne, h, fs), h),
            "s_down": dense(ks[14], (ne, fs, h), fs),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def rope(x, pos, theta):
    """Rotate-half RoPE: x [S, n, hd] at positions ``pos`` [S]."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = pos.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(d, p, x, length, window):
    """x [S, h] -> [S, h], a block of query rows at a time. ``window``: 0
    for a full layer (no positional encoding), else the sliding window."""
    f32 = jnp.float32
    S = x.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    eps = d["rms_eps"]
    q = rms((x @ p["wq"].astype(f32).T).reshape(S, heads, hd), p["q_norm"], eps)
    k = rms((x @ p["wk"].astype(f32).T).reshape(S, kvh, hd), p["k_norm"], eps)
    v = (x @ p["wv"].astype(f32)).reshape(S, kvh, hd)
    keys = jnp.arange(S)
    if window:
        q, k = rope(q, keys, d["rope_theta"]), rope(k, keys, d["rope_theta"])
    q = q.reshape(S, kvh, heads // kvh, hd)
    block = min(Q_BLOCK, S)

    def rows(i):
        pos = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        scores = jnp.einsum("skgd,tkd->kgst", qb, k) * hd**-0.5
        mask = (keys[None, :] <= pos[:, None]) & (keys[None, :] < length)
        if window:
            mask = mask & (keys[None, :] > pos[:, None] - window)
        # a padded row past the sequence's end sees itself, so that no row's
        # softmax is empty
        mask = mask | (keys[None, :] == pos[:, None])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(block, heads * hd)

    attn = jax.lax.map(rows, jnp.arange(S // block)).reshape(S, heads * hd)
    return attn @ p["wo"].astype(f32)


def swiglu(x, gate, up, down):
    f32 = jnp.float32
    return (
        jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))
    ) @ down.astype(f32)


def expert_matrix(d, key, layer, e, n_in, n_out):
    """Expert ``e``'s matrix of expert layer ``layer`` in float32, as the
    program's initialisation draws it: ONE normal(0, fan_in^-1/2) draw a
    stack, [expert layers + the module's, held, in, out], cast to bfloat16;
    the expert's elements alone are drawn (``normal_rows``)."""
    stack = (d["expert_layers"] + d["nextn_layers"]) * d["experts_held"]
    if stack * n_in * n_out >= 2**32:
        raise NotImplementedError("a stack of 2**32 elements or more")
    first = (
        jnp.asarray(e, jnp.uint32) + layer * d["experts_held"]
    ) * jnp.uint32(n_in * n_out)
    draw = normal_rows(key, first, n_in * n_out).reshape(n_in, n_out)
    return (draw * n_in**-0.5).astype(jnp.bfloat16).astype(jnp.float32)


def experts(d, p, layer, x):
    """x [S, h] -> [S, h]: the shared expert, and this share's part of the
    routed sum, the held experts one after another on every row, each
    weighted by the router's weight for it (zero off a token's top k)."""
    f32 = jnp.float32
    held, width, k = d["experts_held"], d["router_width"], d["experts_per_token"]
    h, f = d["hidden_size"], d["expert_size"]
    s = jax.nn.sigmoid(x @ p["router"].astype(f32))
    _, idx = jax.lax.top_k(s + p["router_bias"], k)
    kept = jnp.take_along_axis(s, idx, axis=-1)
    w = d["routed_scaling"] * kept / jnp.sum(kept, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, width, dtype=f32) * w[..., None], axis=-2)
    mine = jax.lax.dynamic_slice_in_dim(
        weights, d["share_index"] * held, held, axis=1
    )  # [S, held], zero off a token's top k

    def one(acc, e):
        gate = expert_matrix(d, p["w_gate_key"], layer, e, h, f)
        up = expert_matrix(d, p["w_up_key"], layer, e, h, f)
        down = expert_matrix(d, p["w_down_key"], layer, e, f, h)
        w_e = jax.lax.dynamic_index_in_dim(mine, e, axis=1, keepdims=False)
        y = (jax.nn.silu(x @ gate) * (x @ up)) @ down
        return acc + w_e[:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return swiglu(x, p["s_gate"], p["s_up"], p["s_down"]) + routed


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    eps = d["rms_eps"]
    x = weights["embed"][ids].astype(f32)
    for l, is_window in enumerate(d["layer_windows"]):
        lp = {k: a[l] for k, a in weights["layers"].items()}
        a = attention(d, lp, x, length, d["window"] * is_window)
        x = x + rms(a, lp["post_attn_norm"], eps)
        if l < d["first_dense"]:
            dp = {k: a[l] for k, a in weights["dense"].items()}
            y = swiglu(x, dp["w_gate"], dp["w_up"], dp["w_down"])
        else:
            i = l - d["first_dense"]
            p = {
                k: a if k in EXPERT_KEYS else a[i]
                for k, a in weights["experts"].items()
            }
            y = experts(d, p, i, x)
        x = x + rms(y, lp["post_ffn_norm"], eps)
    x = rms(x[rows], weights["final_norm"], eps)
    return x @ weights["lm_head"].astype(f32)
