"""The plain reference of Nemotron-H. Layer l of the depth held is ONE
sub-layer, named by character l of ``hybrid_override_pattern``:

    x <- x + Mixer_l(RMSNorm(x))        eps 1e-5, plain weights, no bias but
                                        the convolution's; a final RMSNorm,
                                        an untied head

    M, Mamba-2: 128 heads of 64 channels, 8 groups of 128 state channels,
    head h using group h // 16:
        [z | xBC | dt] = W_in u                      8,192 | 10,240 | 128
        xBC = silu(conv4(xBC) + b)                   depthwise, causal, 4 taps
        x [128, 64], B, C [8, 128] = split(xBC)
        dt = softplus(dt + dt_bias) a head (no clamp),  A = -exp(A_log)
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t   [64, 128] a head, h_0 = 0
        y_t = h_t C_t + D x_t
        out = W_out RMSNorm_8groups(y * silu(z))     the norm over each group's
                                                     1,024 channels, gate first

    *, attention: q 32 x 128, k and v 2 x 128 (16 query heads a KV head), NO
        positional encoding; causal softmax of q . k / sqrt(128); W_o (P v)

    E, experts: s = sigmoid(W_r u) over all 512; the top 22 of s + bias;
        w_e = 5 s_e / sum_top22 s;  v = W_down u  (1,024)
        out = W_up sum_{e in top 22, e HELD} w_e W2_e relu(W1_e v)^2
              + S2 relu(S1 u)^2
    with the experts ``share_index * held ...`` held here: what the absent
    experts would add is left out, as the program leaves it out.

The recurrence is computed TOKEN BY TOKEN, exactly as written (a ``lax.scan``
over the positions), the held experts one after another on every row, each
weighted by the router's weight for it (zero off a token's top 22), each
expert's two matrices drawn where they are used.
Straightforward ``jax.numpy`` in float32 (the caller sets ``highest``
precision), no kernels, no cache, no chunks, no batching; imports nothing of
the program. Two concessions to the size of a 3,072-token request, neither of
which changes a number: attention is computed a block of query rows at a
time, and the weights stay the bfloat16 values they are and are raised to
float32 where they are used (4.65 B x 4 bytes would not fit).

The weights are made here from the seed, by the initialisation the program
documents (normal(0, fan_in^-1/2) per tensor from ``split(key(seed))``, cast
to bfloat16, norms at one, the convolution's bias normal(0, 0.1); A_log = log
of 1..16 and softplus(dt_bias) log-spaced from time_step_min 0.001 to
time_step_max 0.1 over the heads, D at one, the selection bias normal(0,
0.01), all four float32), and are the program's numbers only because the same
seed goes through the same public ``jax.random`` calls.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

#: what the int8 control rounds, as paths into the weights. The control holds
#: a second copy of EVERY leaf beside the first, rounded or not (the
#: comparison's jit returns what it passes through as new buffers), and two
#: whole copies of 9.30 GB do not fit the chip the comparison runs on (the
#: chip refused it: 2.98 GiB were free where a stack's 3.28 were asked for;
#: PERF.md section 2). Every Mamba-2 mixer's two matrices and the attention layer's four (the
#: mixers), the shared experts, the projections into and out of the latent
#: space (through which every routed expert's input and output go) and the
#: head are rounded, 1.96 of the 2.25 GB of leaves; the held experts' own two
#: matrices are no leaves (``expert_matrices`` draws them where they are
#: used) and are not rounded, the embedding is not, and the router stays as
#: it is, as in every family.
MATMUL_WEIGHTS = (
    "mamba.w_in", "mamba.wo",
    "attn.wq", "attn.wk", "attn.wv", "attn.wo",
    "experts.s_up", "experts.s_down", "experts.latent_down",
    "experts.latent_up", "lm_head",
)

#: query rows whose scores exist at once
Q_BLOCK = 256
STACKS = {"M": "mamba", "E": "experts", "*": "attn"}
#: what draws the held experts' two stacks, [expert layers, held, ., .]
EXPERT_KEYS = ("w_up_key", "w_down_key")


def dense(key, shape, fan_in):
    return (
        jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
    ).astype(jnp.bfloat16)


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    """Seeded weights: every layer's norm, and a stack a kind of layer. See
    the module docstring."""
    h, f, L = d["hidden_size"], d["expert_size"], d["num_layers"]
    nm, ne, na = d["ssm_layers"], d["expert_layers"], d["attn_layers"]
    H, C, inner, K = (
        d["mamba_heads"], d["conv_dim"], d["inner_size"], d["conv_kernel"],
    )
    E, W, lat, fs = (
        d["experts_held"], d["router_width"], d["moe_latent_size"],
        d["shared_size"],
    )
    q, kv = d["num_heads"] * d["head_dim"], d["num_kv_heads"] * d["head_dim"]
    k_embed, k_layers, k_head = jax.random.split(jax.random.key(seed), 3)
    ks = jax.random.split(k_layers, 16)
    ones = lambda shape: jnp.ones(shape, jnp.bfloat16)  # noqa: E731
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), H))
    tile = lambda x: jnp.asarray(np.tile(x[None], (nm, 1)), jnp.float32)  # noqa: E731
    return {
        "embed": dense(k_embed, (d["vocab_size"], h), h),
        "layers": {"norm": ones((L, h))},
        "mamba": {
            "w_in": dense(ks[0], (nm, h, d["in_proj_size"]), h),
            "conv": dense(ks[1], (nm, K, C), K),
            "conv_bias": (
                0.1 * jax.random.normal(ks[2], (nm, C), jnp.float32)
            ).astype(jnp.bfloat16),
            "a_log": tile(np.log(np.linspace(1.0, 16.0, H))),
            "dt_bias": tile(dt + np.log(-np.expm1(-dt))),
            "d": jnp.ones((nm, H), jnp.float32),
            "out_norm": ones((nm, inner)),
            "wo": dense(ks[3], (nm, inner, h), inner),
        },
        "experts": {
            "router": dense(ks[4], (ne, h, W), h),
            "router_bias": 0.01 * jax.random.normal(ks[5], (ne, W), jnp.float32),
            "latent_down": dense(ks[6], (ne, h, lat), h),
            "latent_up": dense(ks[7], (ne, lat, h), lat),
            # the held experts' two stacks, 3.52 GB each, are NOT held: what
            # draws them is, and ``expert_matrices`` cuts one expert's out of
            # the draw where it is used (the comparison's control keeps a
            # second copy of every leaf here, rounded or not, and two copies
            # of 9.30 GB do not fit the chip; two of 2.25 GB do)
            "w_up_key": ks[8],
            "w_down_key": ks[9],
            "s_up": dense(ks[10], (ne, h, fs), h),
            "s_down": dense(ks[11], (ne, fs, h), fs),
        },
        "attn": {
            "wq": dense(ks[12], (na, h, q), h),
            "wk": dense(ks[13], (na, h, kv), h),
            "wv": dense(ks[14], (na, h, kv), h),
            "wo": dense(ks[15], (na, q, h), q),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, d["vocab_size"]), h),
    }


def rms(x, w, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x / jnp.sqrt(var + eps) * w.astype(jnp.float32)


def relu2(x):
    return jnp.square(jax.nn.relu(x))


def mamba(d, p, u):
    """u [S, h] -> [S, h]: the state-space recurrence, one position at a
    time. Padded positions lie after every valid one and change nothing
    before them."""
    f32 = jnp.float32
    S = u.shape[0]
    H, P, G, N, K = (
        d["mamba_heads"], d["mamba_head_dim"], d["ssm_groups"],
        d["ssm_state_size"], d["conv_kernel"],
    )
    inner, conv_dim = d["inner_size"], d["conv_dim"]
    zxd = u @ p["w_in"].astype(f32)
    z, xbc, dt = zxd[:, :inner], zxd[:, inner : inner + conv_dim], zxd[:, inner + conv_dim :]
    ext = jnp.concatenate([jnp.zeros((K - 1, conv_dim), f32), xbc])
    conv = p["conv"].astype(f32)
    c = jax.nn.silu(
        sum(conv[i] * ext[i : i + S] for i in range(K)) + p["conv_bias"].astype(f32)
    )
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["a_log"])

    def token(state, t):  # state [H, P, N]
        c, dt = t
        x = c[:inner].reshape(H, P)
        # head h reads group h // (H / G)
        B = jnp.repeat(c[inner : inner + G * N].reshape(G, N), H // G, axis=0)
        C = jnp.repeat(c[inner + G * N :].reshape(G, N), H // G, axis=0)
        state = jnp.exp(dt * A)[:, None, None] * state + (
            dt[:, None, None] * x[:, :, None] * B[:, None, :]
        )
        y = jnp.einsum("hpn,hn->hp", state, C) + p["d"][:, None] * x
        return state, y.reshape(H * P)

    _, y = jax.lax.scan(token, jnp.zeros((H, P, N), f32), (c, dt))
    y = (y * jax.nn.silu(z)).reshape(S, G, inner // G)
    y = y / jnp.sqrt(jnp.mean(y * y, axis=-1, keepdims=True) + d["rms_eps"])
    return (y.reshape(S, inner) * p["out_norm"].astype(f32)) @ p["wo"].astype(f32)


def attention(d, p, u, length):
    """u [S, h] -> [S, h]: grouped-query causal attention without positional
    encoding, a block of query rows at a time."""
    f32 = jnp.float32
    S = u.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    q = (u @ p["wq"].astype(f32)).reshape(S, kvh, heads // kvh, hd)
    k = (u @ p["wk"].astype(f32)).reshape(S, kvh, hd)
    v = (u @ p["wv"].astype(f32)).reshape(S, kvh, hd)
    block = min(Q_BLOCK, S)
    keys = jnp.arange(S)

    def rows(i):
        pos = i * block + jnp.arange(block)
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block)
        scores = jnp.einsum("skgd,tkd->kgst", qb, k) * hd**-0.5
        mask = (keys[None, :] <= pos[:, None]) & (keys[None, :] < length)
        # a padded row past the sequence's end sees itself, so that no row's
        # softmax is empty
        mask = mask | (keys[None, :] == pos[:, None])
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        out = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return out.reshape(block, heads * hd)

    attn = jax.lax.map(rows, jnp.arange(S // block)).reshape(S, heads * hd)
    return attn @ p["wo"].astype(f32)


def normal_rows(key, first, count):
    """Elements ``first`` .. ``first + count - 1``, in row-major order, of
    ``jax.random.normal(key, shape, float32)`` for ANY shape that has them,
    without the rest of the draw. With jax's default generator
    (``jax_threefry_partitionable``) an element is a function of the key and
    of its own row-major index alone: the Threefry hash of the index as a
    (high, low) pair of 32-bit counters, its two words XORed, the mantissa
    trick of ``jax.random.uniform`` on (-1, 1), then sqrt(2) erf_inv. This
    writes those four steps out for a run of indices; written as a slice of
    the whole draw the chip's compiler keeps the whole draw (7 GB a stack).
    ``tests/fmabench/test_fmabench_nemotron_h.py`` holds it to the
    program's own ``jax.random.normal`` bit for bit."""
    from jax.extend.random import threefry2x32_p

    if not jax.config.jax_threefry_partitionable:
        raise NotImplementedError(
            "normal_rows writes out jax's partitionable Threefry draw; the "
            "other generator numbers its elements in another way"
        )
    u32 = jnp.uint32
    k1, k2 = jax.random.key_data(key)
    low = jnp.asarray(first, u32) + jnp.arange(count, dtype=u32)
    b1, b2 = threefry2x32_p.bind(k1, k2, jnp.zeros_like(low), low)
    mantissa = jax.lax.shift_right_logical(b1 ^ b2, u32(32 - 23))
    one_to_two = jax.lax.bitcast_convert_type(
        mantissa | u32(np.float32(1.0).view(np.uint32)), jnp.float32
    )
    lo = np.nextafter(np.float32(-1.0), np.float32(0.0))
    u = jnp.maximum(lo, (one_to_two - 1.0) * (np.float32(1.0) - lo) + lo)
    return np.float32(np.sqrt(2)) * jax.lax.erf_inv(u)


def expert_matrices(d, p, layer, e):
    """Expert ``e``'s two matrices of expert layer ``layer`` in float32, as
    the program's initialisation draws them: ONE normal(0, fan_in^-1/2) draw
    a stack, [expert layers, held, in, out], cast to bfloat16; the expert's
    elements alone are drawn (``normal_rows``)."""
    ne, E, lat, f = (
        d["expert_layers"], d["experts_held"], d["moe_latent_size"],
        d["expert_size"],
    )
    if ne * E * lat * f >= 2**32:
        raise NotImplementedError("a stack of 2**32 elements or more")

    def one(key, n_in, n_out):
        first = (jnp.asarray(e, jnp.uint32) + layer * E) * jnp.uint32(n_in * n_out)
        draw = normal_rows(key, first, n_in * n_out).reshape(n_in, n_out)
        return (draw * n_in**-0.5).astype(jnp.bfloat16).astype(jnp.float32)

    return one(p["w_up_key"], lat, f), one(p["w_down_key"], f, lat)


def experts(d, p, layer, u):
    """u [S, h] -> [S, h]: the shared expert on u, and W_up of this share's
    part of the routed sum over W_down u. ``p``: the layer's small matrices
    and what draws the held experts' (``expert_matrices``)."""
    f32 = jnp.float32
    held, width, k = d["experts_held"], d["router_width"], d["experts_per_token"]
    s = jax.nn.sigmoid(u @ p["router"].astype(f32))
    _, idx = jax.lax.top_k(s + p["router_bias"], k)
    kept = jnp.take_along_axis(s, idx, axis=-1)
    w = d["routed_scaling"] * kept / jnp.sum(kept, axis=-1, keepdims=True)
    weights = jnp.sum(jax.nn.one_hot(idx, width, dtype=f32) * w[..., None], axis=-2)
    mine = jax.lax.dynamic_slice_in_dim(
        weights, d["share_index"] * held, held, axis=1
    )  # [S, held], zero off a token's top k
    v = u @ p["latent_down"].astype(f32)

    def one(acc, e):
        up, down = expert_matrices(d, p, layer, e)
        w_e = jax.lax.dynamic_index_in_dim(mine, e, axis=1, keepdims=False)
        return acc + w_e[:, None] * (relu2(v @ up) @ down), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(v), jnp.arange(held))
    shared = relu2(u @ p["s_up"].astype(f32)) @ p["s_down"].astype(f32)
    return shared + routed @ p["latent_up"].astype(f32)


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    x = weights["embed"][ids].astype(f32)
    seen = dict.fromkeys(STACKS, 0)
    for l, char in enumerate(d["layer_kinds"]):
        i = seen[char]
        seen[char] += 1
        u = rms(x, weights["layers"]["norm"][l], d["rms_eps"])
        stack = weights[STACKS[char]]
        if char == "M":
            x = x + mamba(d, {k: a[i] for k, a in stack.items()}, u)
        elif char == "*":
            x = x + attention(d, {k: a[i] for k, a in stack.items()}, u, length)
        else:
            p = {k: a if k in EXPERT_KEYS else a[i] for k, a in stack.items()}
            x = x + experts(d, p, i, u)
    x = rms(x[rows], weights["final_norm"], d["rms_eps"])
    return x @ weights["lm_head"].astype(f32)
