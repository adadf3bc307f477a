"""The plain reference of Olmo-Hybrid, layer l of the depth held:

    h = x + RMSNorm(Mixer(x))          the norm on the sub-layer's OUTPUT,
    y = h + RMSNorm(W_down(silu(W_gate h) * W_up h))   the mixer on the raw x

    full attention (l % 4 == 3):
        q = RMSNorm(W_q x), k = RMSNorm(W_k x) over all channels, v = W_v x,
        split into heads; causal softmax at head_dim^-1/2; NO positional
        encoding; W_o

    linear attention (the gated delta rule), per head, d_k x d_v:
        u_t = W_qkv x_t
        c_t = silu(sum_{i<K} conv_i * u_{t-K+1+i})       depthwise, causal
        q_t = c^q_t / |c^q_t| * d_k^-1/2, k_t = c^k_t / |c^k_t|, v_t = c^v_t
        beta_t = 2 sigmoid(w_b . x_t)        (2: linear_allow_neg_eigval)
        alpha_t = exp(-exp(A_log) softplus(w_a . x_t + dt_bias))
        S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
        o_t = S_t^T q_t,   S_0 = 0
        y_t = W_o [RMSNorm_dv(o_t) * silu(W_g x_t)]

The recurrence is computed TOKEN BY TOKEN, exactly as written (a
``lax.scan`` over the positions): the program's chunkwise form is judged
against the definition. Straightforward ``jax.numpy`` in float32 (the caller
sets ``highest`` precision), no kernels, no cache, no chunks, no batching;
imports nothing of the program. Two concessions to the size of a 4,096-token
request at 16 layers, neither of which changes a number: attention is
computed a block of query rows at a time, and the weights stay the bfloat16
values they are and are raised to float32 where they are used (4.1 B x 4
bytes would not fit).

The weights are made here from the seed, by the initialisation the program
documents (normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``, cast
to bfloat16, norms at one; A_log = log of 1..16 over the heads and
softplus(dt_bias) from 0.001 to 0.1 over the heads, float32, the same in
every layer), and are the program's numbers only because the same seed goes
through the same public ``jax.random`` calls.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: what the int8 control rounds, as paths into the weights. The control
#: holds a second copy of every weight it rounds beside the first, and two
#: whole copies of 8.2 GB do not fit the chip the comparison runs on: every
#: mixer's matrices (the new mechanism's and the full layers'), the head and
#: the FFN's down projection are rounded, 4.7 of the 8.2 GB; the FFN's gate
#: and up projections and the embedding are not.
MATMUL_WEIGHTS = (
    "linear.w_qkv", "linear.w_g", "linear.wo",
    "full.wq", "full.wk", "full.wv", "full.wo",
    "layers.w_down", "lm_head",
)

#: query rows whose scores exist at once
Q_BLOCK = 256
L2_EPS = 1e-6


def dense(key, shape, fan_in):
    return (
        jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
    ).astype(jnp.bfloat16)


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights in three stacks: every layer's FFN and output norms,
    the linear mixers, the full mixers. See the module docstring."""
    h, f, L = d["hidden_size"], d["intermediate_size"], d["num_layers"]
    nl, nf = d["linear_layers"], d["full_layers"]
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    H, C, K = d["linear_heads"], d["conv_dim"], d["conv_kernel"]
    gd = H * d["linear_value_dim"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 13)
    ones = lambda shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), H))
    per_head = lambda x: jnp.asarray(  # noqa: E731
        np.tile(x[None], (nl, 1)), jnp.float32
    )
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": {
            "post_mixer_norm": ones((L, h)),
            "post_ffn_norm": ones((L, h)),
            "w_gate": dense(ks[0], (L, h, f), h),
            "w_up": dense(ks[1], (L, h, f), h),
            "w_down": dense(ks[2], (L, f, h), f),
        },
        "full": {
            "wq": dense(ks[3], (nf, h, q), h),
            "wk": dense(ks[4], (nf, h, kv), h),
            "wv": dense(ks[5], (nf, h, kv), h),
            "wo": dense(ks[6], (nf, q, h), q),
            "q_norm": ones((nf, q)),
            "k_norm": ones((nf, kv)),
        },
        "linear": {
            "w_qkv": dense(ks[7], (nl, h, C), h),
            "conv": dense(ks[8], (nl, K, C), K),
            "w_beta": dense(ks[9], (nl, h, H), h),
            "w_alpha": dense(ks[10], (nl, h, H), h),
            "w_g": dense(ks[11], (nl, h, gd), h),
            "wo": dense(ks[12], (nl, gd, h), gd),
            "a_log": per_head(np.log(np.linspace(1.0, 16.0, H))),
            "dt_bias": per_head(dt + np.log(-np.expm1(-dt))),
            "out_norm": ones((nl, d["linear_value_dim"])),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def full_attention(d, p, x, length):
    """x [S, h] -> [S, h]; a block of query rows at a time."""
    f32 = jnp.float32
    S = x.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    eps = d["rms_eps"]
    q = rms(x @ p["wq"].astype(f32), p["q_norm"], eps).reshape(S, heads, hd)
    k = rms(x @ p["wk"].astype(f32), p["k_norm"], eps).reshape(S, kvh, hd)
    v = (x @ p["wv"].astype(f32)).reshape(S, kvh, hd)
    block = min(Q_BLOCK, S)
    keys = jnp.arange(S)

    def rows(i):
        pos = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        qg = qb.reshape(block, kvh, heads // kvh, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        mask = (keys[None, :] <= pos[:, None]) & (keys[None, :] < length)
        # a padded row past the sequence's end sees itself, so that no row's
        # softmax is empty
        mask = mask | (keys[None, :] == pos[:, None])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(block, heads * hd)

    attn = jax.lax.map(rows, jnp.arange(S // block)).reshape(S, heads * hd)
    return attn @ p["wo"].astype(f32)


def linear_attention(d, p, x):
    """x [S, h] -> [S, h]: the gated delta rule, one position at a time.
    Padded positions lie after every valid one and change nothing before
    them. The step takes the position's convolved channels whole and gives
    its normed output whole, so that nothing a head wide is laid out for all
    positions at once (192 is no multiple of the 128 lanes)."""
    f32 = jnp.float32
    S = x.shape[0]
    H, dk, dv = d["linear_heads"], d["linear_key_dim"], d["linear_value_dim"]
    K = d["conv_kernel"]
    u = x @ p["w_qkv"].astype(f32)
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), f32), u])
    conv = p["conv"].astype(f32)
    c = jax.nn.silu(sum(conv[i] * ext[i : i + S] for i in range(K)))
    beta = jax.nn.sigmoid(x @ p["w_beta"].astype(f32))
    if d["allow_neg_eigval"]:
        beta = 2.0 * beta
    alpha = jnp.exp(-jnp.exp(p["a_log"]) * jax.nn.softplus(
        x @ p["w_alpha"].astype(f32) + p["dt_bias"]))

    def token(state, t):  # state [H, dk, dv]
        c, beta, alpha = t
        q, k, v = jnp.split(c, [H * dk, 2 * H * dk])
        q = unit(q.reshape(H, dk)) * dk**-0.5
        k = unit(k.reshape(H, dk))
        v = v.reshape(H, dv)
        state = alpha[:, None, None] * state
        delta = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[:, :, None] * delta[:, None, :]
        o = jnp.einsum("hkv,hk->hv", state, q)
        return state, rms(o, p["out_norm"], d["rms_eps"]).reshape(H * dv)

    _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), f32), (c, beta, alpha))
    return (o * jax.nn.silu(x @ p["w_g"].astype(f32))) @ p["wo"].astype(f32)


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    kinds = d["layer_kinds"]
    x = weights["embed"][ids].astype(f32)

    def period(x, pi):
        seen = {"linear": 0, "full": 0}
        for j, kind in enumerate(kinds):
            lp = {k: a[pi * len(kinds) + j] for k, a in weights["layers"].items()}
            nth = pi * kinds.count(kind) + seen[kind]
            seen[kind] += 1
            mp = {k: a[nth] for k, a in weights[kind].items()}
            if kind == "linear":
                mixed = linear_attention(d, mp, x)
            else:
                mixed = full_attention(d, mp, x, length)
            x = x + rms(mixed, lp["post_mixer_norm"], d["rms_eps"])
            gate = jax.nn.silu(x @ lp["w_gate"].astype(f32))
            y = (gate * (x @ lp["w_up"].astype(f32))) @ lp["w_down"].astype(f32)
            x = x + rms(y, lp["post_ffn_norm"], d["rms_eps"])
        return x, None

    x, _ = jax.lax.scan(period, x, jnp.arange(d["num_layers"] // len(kinds)))
    x = rms(x[rows], weights["final_norm"], d["rms_eps"])
    return x @ weights["lm_head"].astype(f32)
