"""The plain reference of Kimi-Linear, layer l (1-based) of the depth held:

    h = x + Mixer(RMSNorm(x)),  y = h + FFN(RMSNorm(h))      pre-norm, eps 1e-5

    KDA (l % 4 != 0), per head, d_k = d_v = 128:
        q, k, v = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
                  depthwise, causal, 4 taps, no bias
        q_t = q / |q| * 128^-1/2,  k_t = k / |k|
        a_t = -exp(A_log) softplus(f_b(f_a x_t) + dt_bias)    a vector of 128
        alpha_t = exp(a_t),  beta_t = sigmoid(w_b . x_t)      a scalar
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t,  S_0 = 0
        out = W_o [RMSNorm_128(o_t) * sigmoid(g_b(g_a x_t))]

    MLA (l % 4 == 0), 32 heads:
        q = W_q x -> 32 x (128 | 64);  [c | k_pe] = W_kva x  (512 | 64)
        c <- RMSNorm(c);  [k_nope | v] = W_kvb c -> 32 x (128 | 128)
        k = [k_nope | k_pe], k_pe shared by the heads, NO rotary on either part
        causal softmax of q . k / sqrt(192);  out = W_o (P v)

    FFN: layer 1 SwiGLU, 9,216 wide. Every later layer
        s = sigmoid(W_r h);  top 8 of s + bias over all 256;  w_e = s_e / sum
        shared(h) + 2.446 sum_{e in top 8, e HELD} w_e expert_e(h)
    with the experts ``share_index * held ...`` held here: what the absent
    experts would add is left out, as the program leaves it out.

The recurrence is computed TOKEN BY TOKEN, exactly as written (a
``lax.scan`` over the positions), latent attention in its plain form (no
absorption: W_kvb expands every row), the held experts one after another on
every row, each weighted by the router's weight for it (zero off a token's
top 8). Straightforward ``jax.numpy`` in float32 (the caller sets
``highest`` precision), no kernels, no cache, no chunks, no batching;
imports nothing of the program. Two concessions to the size of a
4,096-token request, neither of which changes a number: attention is
computed a block of query rows at a time, and the weights stay the bfloat16
values they are and are raised to float32 where they are used (3.77 B x 4
bytes would not fit).

The weights are made here from the seed, by the initialisation the program
documents (normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``, cast
to bfloat16, norms at one; A_log = log of 1..16 over the heads and
softplus(dt_bias) from 0.001 to 0.1 over a head's channels, the selection
bias normal(0, 0.01), all three float32), and are the program's numbers only
because the same seed goes through the same public ``jax.random`` calls.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: what the int8 control rounds, as paths into the weights. The control
#: holds a second copy of every weight it rounds beside the first, and two
#: whole copies of 7.54 GB do not fit the chip the comparison runs on. Every
#: mixer's matrices (the two new mechanisms'), the dense layer, the shared
#: experts, the head, and of the held experts' three matrices the up and the
#: down projection are rounded, 5.2 of the 7.54 GB; the experts' gate
#: projection and the embedding are not, and the router stays as it is, as
#: in every family.
MATMUL_WEIGHTS = (
    "kda.w_qkv", "kda.f_b", "kda.g_b", "kda.wo",
    "mla.wq", "mla.w_kva", "mla.w_kvb", "mla.wo",
    "dense.w_gate", "dense.w_up", "dense.w_down",
    "experts.s_gate", "experts.s_up", "experts.s_down",
    "experts.w_up", "experts.w_down", "lm_head",
)

#: query rows whose scores exist at once
Q_BLOCK = 256
L2_EPS = 1e-6


def dense(key, shape, fan_in):
    return (
        jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
    ).astype(jnp.bfloat16)


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights in five stacks: every layer's norms, the leading dense
    FFN, the expert layers, the KDA mixers, the MLA mixers. See the module
    docstring."""
    h, f, L = d["hidden_size"], d["expert_size"], d["num_layers"]
    nk, nm, ne, nd = (
        d["kda_layers"], d["mla_layers"], d["expert_layers"], d["first_dense"],
    )
    H, hd, C, K = d["kda_heads"], d["kda_head_dim"], d["conv_dim"], d["conv_kernel"]
    D = H * hd
    E, W, fs, fd = (
        d["experts_held"], d["router_width"], d["shared_experts"] * f,
        d["dense_size"],
    )
    heads, rank = d["num_heads"], d["kv_lora_rank"]
    q = heads * (d["qk_nope_head_dim"] + d["qk_rope_head_dim"])
    kvb = heads * (d["qk_nope_head_dim"] + d["v_head_dim"])
    od = heads * d["v_head_dim"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 23)
    ones = lambda shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), hd))
    tile = lambda x: jnp.asarray(np.tile(x[None], (nk, 1)), jnp.float32)  # noqa: E731
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": {"attn_norm": ones((L, h)), "mlp_norm": ones((L, h))},
        "dense": {
            "w_gate": dense(ks[0], (nd, h, fd), h),
            "w_up": dense(ks[1], (nd, h, fd), h),
            "w_down": dense(ks[2], (nd, fd, h), fd),
        },
        "experts": {
            "router": dense(ks[3], (ne, h, W), h),
            "router_bias": 0.01 * jax.random.normal(ks[4], (ne, W), jnp.float32),
            "w_gate": dense(ks[5], (ne, E, h, f), h),
            "w_up": dense(ks[6], (ne, E, h, f), h),
            "w_down": dense(ks[7], (ne, E, f, h), f),
            "s_gate": dense(ks[8], (ne, h, fs), h),
            "s_up": dense(ks[9], (ne, h, fs), h),
            "s_down": dense(ks[10], (ne, fs, h), fs),
        },
        "kda": {
            "w_qkv": dense(ks[11], (nk, h, C), h),
            "conv": dense(ks[12], (nk, K, C), K),
            "f_a": dense(ks[13], (nk, h, hd), h),
            "f_b": dense(ks[14], (nk, hd, D), hd),
            "g_a": dense(ks[15], (nk, h, hd), h),
            "g_b": dense(ks[16], (nk, hd, D), hd),
            "w_beta": dense(ks[17], (nk, h, H), h),
            "wo": dense(ks[18], (nk, D, h), D),
            "a_log": tile(np.log(np.linspace(1.0, 16.0, H))),
            "dt_bias": tile(np.tile(dt + np.log(-np.expm1(-dt)), H)),
            "out_norm": ones((nk, hd)),
        },
        "mla": {
            "wq": dense(ks[19], (nm, h, q), h),
            "w_kva": dense(ks[20], (nm, h, d["latent_dim"]), h),
            "kv_norm": ones((nm, rank)),
            "w_kvb": dense(ks[21], (nm, rank, kvb), rank),
            "wo": dense(ks[22], (nm, od, h), od),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def unit(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def swiglu(x, gate, up, down):
    f32 = jnp.float32
    return (jax.nn.silu(x @ gate.astype(f32)) * (x @ up.astype(f32))) @ down.astype(f32)


def kda(d, p, x):
    """x [S, h] -> [S, h]: the delta rule with a decay a channel, one
    position at a time. Padded positions lie after every valid one and
    change nothing before them."""
    f32 = jnp.float32
    S = x.shape[0]
    H, hd, K = d["kda_heads"], d["kda_head_dim"], d["conv_kernel"]
    u = x @ p["w_qkv"].astype(f32)
    ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), f32), u])
    conv = p["conv"].astype(f32)
    c = jax.nn.silu(sum(conv[i] * ext[i : i + S] for i in range(K)))
    beta = jax.nn.sigmoid(x @ p["w_beta"].astype(f32))
    a = -jnp.exp(p["a_log"])[:, None] * jax.nn.softplus(
        (x @ p["f_a"].astype(f32)) @ p["f_b"].astype(f32) + p["dt_bias"]
    ).reshape(S, H, hd)

    def token(state, t):  # state [H, hd, hd]
        c, beta, a = t
        q, k, v = (y.reshape(H, hd) for y in jnp.split(c, 3))
        q, k = unit(q) * hd**-0.5, unit(k)
        state = jnp.exp(a)[:, :, None] * state
        delta = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", state, k))
        state = state + k[:, :, None] * delta[:, None, :]
        o = jnp.einsum("hkv,hk->hv", state, q)
        return state, rms(o, p["out_norm"], d["rms_eps"]).reshape(H * hd)

    _, o = jax.lax.scan(token, jnp.zeros((H, hd, hd), f32), (c, beta, a))
    gate = jax.nn.sigmoid((x @ p["g_a"].astype(f32)) @ p["g_b"].astype(f32))
    return (o * gate) @ p["wo"].astype(f32)


def mla(d, p, x, length):
    """x [S, h] -> [S, h]: the plain form, a block of query rows at a time."""
    f32 = jnp.float32
    S = x.shape[0]
    heads, nope, rank = d["num_heads"], d["qk_nope_head_dim"], d["kv_lora_rank"]
    rope = d["qk_rope_head_dim"]
    q = (x @ p["wq"].astype(f32)).reshape(S, heads, nope + rope)
    kva = x @ p["w_kva"].astype(f32)
    c = rms(kva[:, :rank], p["kv_norm"], d["rms_eps"])
    kv = (c @ p["w_kvb"].astype(f32)).reshape(S, heads, -1)
    k_pe = jnp.broadcast_to(kva[:, None, rank:], (S, heads, rope))
    k, v = jnp.concatenate([kv[..., :nope], k_pe], axis=-1), kv[..., nope:]
    block = min(Q_BLOCK, S)
    keys = jnp.arange(S)

    def rows(i):
        pos = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        scores = jnp.einsum("shd,thd->hst", qb, k) * (nope + rope) ** -0.5
        mask = (keys[None, :] <= pos[:, None]) & (keys[None, :] < length)
        # a padded row past the sequence's end sees itself, so that no row's
        # softmax is empty
        mask = mask | (keys[None, :] == pos[:, None])
        scores = jnp.where(mask[None], scores, -jnp.inf)
        out = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(block, -1)

    attn = jax.lax.map(rows, jnp.arange(S // block)).reshape(S, -1)
    return attn @ p["wo"].astype(f32)


#: the held experts' three matrices, [expert layers, held, ., .]
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def experts(d, p, stacks, layer, x):
    """x [S, h] -> [S, h]: the shared expert and this share's part of the
    routed sum. ``p``: the layer's small matrices; ``stacks``: the held
    experts' matrices of EVERY expert layer, of which ``layer``'s are read."""
    f32 = jnp.float32
    held, width, k = d["experts_held"], d["router_width"], d["experts_per_token"]
    s = jax.nn.sigmoid(x @ p["router"].astype(f32))
    _, idx = jax.lax.top_k(s + p["router_bias"], k)
    kept = jnp.take_along_axis(s, idx, axis=-1)
    w = d["routed_scaling"] * kept / jnp.sum(kept, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, width, dtype=f32) * w[..., None], axis=-2)
    mine = jax.lax.dynamic_slice_in_dim(
        weights, d["share_index"] * held, held, axis=1
    )  # [S, held], zero off a token's top k

    def one(acc, e):
        # the expert's matrices are cut out of the stacks HERE, so that only
        # one expert's are ever raised to float32
        # (a layer of a stack sliced out beforehand is 302 MB, and the
        # compiler keeps all 21 of them at once)
        gate, up, down = (
            jax.lax.dynamic_slice(
                stacks[name], (layer, e, 0, 0), (1, 1) + stacks[name].shape[2:]
            )[0, 0]
            for name in EXPERT_STACKS
        )
        w_e = jax.lax.dynamic_index_in_dim(mine, e, axis=1, keepdims=False)
        return acc + w_e[:, None] * swiglu(x, gate, up, down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return swiglu(x, p["s_gate"], p["s_up"], p["s_down"]) + routed


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    kinds = d["layer_kinds"]
    x = weights["embed"][ids].astype(f32)
    seen = {"kda": 0, "mla": 0}
    for l in range(d["num_layers"]):
        kind = kinds[l % len(kinds)]
        lp = {k: a[l] for k, a in weights["layers"].items()}
        mp = {k: a[seen[kind]] for k, a in weights[kind].items()}
        seen[kind] += 1
        h = rms(x, lp["attn_norm"], d["rms_eps"])
        x = x + (kda(d, mp, h) if kind == "kda" else mla(d, mp, h, length))
        h = rms(x, lp["mlp_norm"], d["rms_eps"])
        if l < d["first_dense"]:
            dp = {k: a[l] for k, a in weights["dense"].items()}
            x = x + swiglu(h, dp["w_gate"], dp["w_up"], dp["w_down"])
        else:
            e = l - d["first_dense"]
            ep = {k: a[e] for k, a in weights["experts"].items()
                  if k not in EXPERT_STACKS}
            x = x + experts(d, ep, weights["experts"], e, h)
    x = rms(x[rows], weights["final_norm"], d["rms_eps"])
    return x @ weights["lm_head"].astype(f32)
