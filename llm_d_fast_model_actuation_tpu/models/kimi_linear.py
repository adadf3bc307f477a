"""Kimi-Linear (``model_type: kimi_linear``, moonshotai; arXiv:2510.26692) —
the family whose layers differ in mixer kind AND in FFN kind: a period of
three KDA layers (Kimi Delta Attention: the gated delta rule with a decay a
CHANNEL, not a head) and one MLA layer (latent attention, no positional
encoding), a leading dense FFN and sigmoid-routed experts with a shared
expert after it, of which one chip holds a share.

Pre-norm blocks, ``h = x + Mixer(RMSNorm(x))``, ``y = h + FFN(RMSNorm(h))``,
no bias, untied head. Per layer:

  * a KDA layer keeps no K and V. Its sequence state is PR 36's third kind
    (engine/kv_cache.py:KVLayout): a float32 matrix a head, ``S [32, 128,
    128]``, and the last 3 inputs of its convolutions, per slot. Per token
    and head (d_k = d_v = 128)::

        q, k, v = silu(conv4(W_q x)), silu(conv4(W_k x)), silu(conv4(W_v x))
        q_t = q / |q| * d_k^-1/2,  k_t = k / |k|
        a_t = -exp(A_log) softplus(f_b(f_a x_t) + dt_bias)   a VECTOR of d_k
        alpha_t = exp(a_t),  beta_t = sigmoid(w_b x_t)       a scalar
        S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T
        o_t = S_t^T q_t;  y_t = W_o [RMSNorm(o_t) * sigmoid(g_b(g_a x_t))]

    A prompt segment runs it chunkwise (:func:`kda_chunk_scan`), a decode
    step one step a slot (``olmo_hybrid.recurrence_step`` with a decay a
    channel);
  * an MLA layer keeps the fourth kind, a LATENT page: per token ``[c |
    k_pe]``, the RMS-normed compressed vector (512) and the key part every
    head shares (64), stored 640 wide (engine/kv_cache.py). A prompt
    segment computes the PLAIN form (``[k_nope | v] = W_kvb c`` for every
    visible row, 32 heads of 192 | 128); a decode step the ABSORBED one
    (``W_kvb`` folded into the query and the output, the page's row read
    once as key and as value by ops/pallas/decode.py). The same function;
  * the FFN of layer 1 is SwiGLU; every later layer is ``shared(h) +
    scale * sum_{e in top8, e held} w_e expert_e(h)`` with sigmoid scores, a
    selection bias, weights renormalised over the 8 kept
    (models/moe.py:route) and the chip's share of the experts
    (models/moe.py:routed_ffn: ``num_experts`` HELD of ``router_width``).

Parameters: ``layers`` (every layer's two norms), ``dense`` (the leading
FFN), ``experts`` (router, bias, shared expert and the held expert stacks of
every later layer, by ordinal), ``kda`` and ``mla`` (the mixers by ordinal).
The first period, which holds the dense layer, is traced once by itself; the
others are one scan over periods whose body unrolls the period's layers, as
models/olmo_hybrid.py's. The entry points keep the trunk's signatures and
``llama.prefill`` / ``prefill_continue`` / ``decode_step`` hand the config
here (``llama.patterned``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import NEG_INF, latent_decode_attention_inline
from ..ops.norm import rms_norm
from . import llama, moe
from . import olmo_hybrid as gdn
from .quant import qmat

#: rows of a sub-block of a chunk: decays inside one are taken pair by pair,
#: between two of them through the later one's first row (kda_chunk_scan)
SUB = 16
#: lanes a latent page's row is stored in are whole tiles of this many
LANES = 128
#: the seeded selection bias's standard deviation. Small beside the spread of
#: the sigmoid scores (about 0.2), so that it breaks near-ties and is
#: exercised without choosing the experts by itself: at 0.1 it left 110 of
#: the router's 256 outputs unused, and how many of a chip's 64 experts a
#: decode step touches, hence the step's weight reads, went by the seed
#: (PERF.md section 6, PR 40)
SELECTION_BIAS_STD = 0.01
_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class KimiLinearConfig(moe.MoeConfig):
    """``num_experts`` is what this chip HOLDS of ``router_width``;
    ``intermediate_size`` the experts' width; ``num_heads`` the MLA heads.
    ``head_dim`` and ``num_kv_heads`` are carried as published and size
    nothing: the pages are latent (:attr:`latent_cache`)."""

    #: (llama.patterned) the module of this package that is its forward
    forward_module = "kimi_linear"

    routed_experts: bool = True
    router_scoring: str = "sigmoid"
    #: per layer of one period: "kda" or "mla"
    layer_kinds: Tuple[str, ...] = ("kda", "kda", "kda", "mla")
    #: leading layers whose FFN is dense, and its width
    first_dense: int = 1
    dense_intermediate_size: int = 9216
    shared_experts: int = 1
    kda_heads: int = 32
    kda_head_dim: int = 128
    conv_kernel: int = 4
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128

    def __post_init__(self) -> None:
        super().__post_init__()
        if set(self.layer_kinds) - {"kda", "mla"} or not self.layer_kinds:
            raise ValueError(f"layer_kinds {self.layer_kinds}: kda or mla")
        if self.num_layers % len(self.layer_kinds):
            raise ValueError(
                f"{self.num_layers} layers are not whole periods of "
                f"{len(self.layer_kinds)}"
            )
        if not 0 < self.first_dense <= len(self.layer_kinds):
            raise ValueError(
                f"first_dense {self.first_dense}: the dense layers lie in "
                "the first period, which is traced by itself"
            )
        if self.kv_lora_rank % LANES:
            raise ValueError(
                f"kv_lora_rank {self.kv_lora_rank}: the value of a latent "
                f"row is a slice of whole {LANES}-lane tiles"
            )
        if self.quantization:
            raise ValueError(
                "KimiLinearConfig: weight quantization is not carried by "
                "this forward"
            )

    @classmethod
    def kimi_linear_48b_a3b(cls) -> "KimiLinearConfig":
        """One chip's share of Kimi-Linear-48B-A3B-Instruct as published
        (moonshotai ``config.json``): four chips share each layer, this one
        holds 64 of the 256 routed experts and 40,960 of the 163,840
        vocabulary rows, and the first 8 of the 27 layers (the whole model
        is 98 GB); ``max_seq_len`` is a serving choice below the published
        1,048,576 positions."""
        return cls(
            vocab_size=40960, hidden_size=2304, num_layers=8, num_heads=32,
            num_kv_heads=32, head_dim=72, intermediate_size=1024,
            rms_eps=1e-5, max_seq_len=4096, num_experts=64,
            experts_per_token=8, router_width=256, routed_scaling=2.446,
        )

    @classmethod
    def tiny_kimi_linear(cls, vocab: int = 256) -> "KimiLinearConfig":
        """CPU test size: two periods, 4 of 16 experts held, a latent row of
        128 + 16 values stored in 256 lanes."""
        return cls(
            vocab_size=vocab, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=4, head_dim=16, intermediate_size=32, rms_eps=1e-5,
            max_seq_len=256, num_experts=4, experts_per_token=3,
            router_width=16, routed_scaling=2.446, dense_intermediate_size=96,
            kda_heads=4, kda_head_dim=16, kv_lora_rank=128,
            qk_nope_head_dim=16, qk_rope_head_dim=16, v_head_dim=16,
        )

    @property
    def periods(self) -> int:
        return self.num_layers // len(self.layer_kinds)

    @property
    def kda_layers(self) -> int:
        return self.periods * self.layer_kinds.count("kda")

    @property
    def cache_layers(self) -> int:
        """Layers of latent pages a token holds: the MLA layers alone."""
        return self.periods * self.layer_kinds.count("mla")

    @property
    def expert_layers(self) -> int:
        return self.num_layers - self.first_dense

    @property
    def conv_dim(self) -> int:
        """Channels of the three convolutions together: q, k, v of every head."""
        return 3 * self.kda_dim

    @property
    def kda_dim(self) -> int:
        return self.kda_heads * self.kda_head_dim

    @property
    def q_dim(self) -> int:
        return self.num_heads * (self.qk_nope_head_dim + self.qk_rope_head_dim)

    @property
    def latent_dim(self) -> int:
        """Values a token of an MLA layer holds, as counted."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_cache(self) -> Tuple[int, int]:
        """(llama.latent_cache) a latent row as counted, and the lanes it is
        stored in: the shared key part gets whole 128-lane tiles of its own
        (engine/kv_cache.py)."""
        pe = -(-self.qk_rope_head_dim // LANES) * LANES
        return self.latent_dim, self.kv_lora_rank + pe

    @property
    def recurrent_state(self):
        """(llama.recurrent_state) the KDA layers, and the shapes a slot
        holds for one of them."""
        return (
            self.kda_layers,
            (self.kda_heads, self.kda_head_dim, self.kda_head_dim),
            (self.conv_kernel - 1, self.conv_dim),
        )

    def num_params(self) -> int:
        h, f, H, d = (
            self.hidden_size, self.intermediate_size, self.kda_heads,
            self.kda_head_dim,
        )
        D, K = self.kda_dim, self.conv_kernel
        kda = (
            3 * h * D + 3 * K * D + 2 * (h * d + d * D) + h * H + H + D + d
            + D * h
        )
        kvb = self.num_heads * (self.qk_nope_head_dim + self.v_head_dim)
        mla = (
            h * self.q_dim + h * self.latent_dim + self.kv_lora_rank
            + self.kv_lora_rank * kvb + self.num_heads * self.v_head_dim * h
        )
        expert = 3 * h * f
        ffn = (
            (self.num_experts + self.shared_experts) * expert
            + h * self.router_outputs + self.router_outputs
        )
        return (
            self.num_layers * 2 * h
            + self.first_dense * 3 * h * self.dense_intermediate_size
            + self.expert_layers * ffn + self.kda_layers * kda
            + self.cache_layers * mla + 2 * self.vocab_size * h + h
        )


# -- parameters -----------------------------------------------------------------


def _decay_init(cfg: KimiLinearConfig):
    """(A_log [kda layers, heads], dt_bias [kda layers, heads * d_k])
    float32, the same in every layer: A from 1 to 16 over the heads and
    softplus(dt_bias) from 0.001 to 0.1 over a head's channels, so that a
    head's channels forget over tens to thousands of tokens."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    a_log = np.log(np.linspace(1.0, 16.0, H))
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), d))
    dt_bias = np.tile(dt + np.log(-np.expm1(-dt)), H)  # softplus^-1
    tile = lambda x: jnp.asarray(  # noqa: E731
        np.tile(x[None], (cfg.kda_layers, 1)), jnp.float32
    )
    return tile(a_log), tile(dt_bias)


def init_params(key: jax.Array, cfg: KimiLinearConfig) -> Dict[str, Any]:
    """Random-init parameters: normal(0, fan_in^-1/2) per tensor from
    ``split(key)``, cast to the model's dtype; norms at one; the decay's two
    vectors as :func:`_decay_init` gives them and the router's selection
    bias normal(0, ``SELECTION_BIAS_STD``), both float32."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 23)
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nk, nm, ne, nd = (
        cfg.kda_layers, cfg.cache_layers, cfg.expert_layers, cfg.first_dense,
    )
    H, d, D, C = cfg.kda_heads, cfg.kda_head_dim, cfg.kda_dim, cfg.conv_dim
    E, W, fs = cfg.num_experts, cfg.router_outputs, cfg.shared_experts * f
    fd = cfg.dense_intermediate_size
    kvb = cfg.num_heads * (cfg.qk_nope_head_dim + cfg.v_head_dim)
    od = cfg.num_heads * cfg.v_head_dim

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(cfg.dtype)

    ones = lambda shape: jnp.ones(shape, cfg.dtype)  # noqa: E731
    a_log, dt_bias = _decay_init(cfg)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, h), h),
        "layers": {"attn_norm": ones((L, h)), "mlp_norm": ones((L, h))},
        "dense": {
            "w_gate": dense(ks[0], (nd, h, fd), h),
            "w_up": dense(ks[1], (nd, h, fd), h),
            "w_down": dense(ks[2], (nd, fd, h), fd),
        },
        "experts": {
            "router": dense(ks[3], (ne, h, W), h),
            "router_bias": SELECTION_BIAS_STD * jax.random.normal(
                ks[4], (ne, W), jnp.float32
            ),
            "w_gate": dense(ks[5], (ne, E, h, f), h),
            "w_up": dense(ks[6], (ne, E, h, f), h),
            "w_down": dense(ks[7], (ne, E, f, h), f),
            "s_gate": dense(ks[8], (ne, h, fs), h),
            "s_up": dense(ks[9], (ne, h, fs), h),
            "s_down": dense(ks[10], (ne, fs, h), fs),
        },
        "kda": {
            "w_qkv": dense(ks[11], (nk, h, C), h),
            "conv": dense(ks[12], (nk, cfg.conv_kernel, C), cfg.conv_kernel),
            "f_a": dense(ks[13], (nk, h, d), h),
            "f_b": dense(ks[14], (nk, d, D), d),
            "g_a": dense(ks[15], (nk, h, d), h),
            "g_b": dense(ks[16], (nk, d, D), d),
            "w_beta": dense(ks[17], (nk, h, H), h),
            "wo": dense(ks[18], (nk, D, h), D),
            "a_log": a_log,
            "dt_bias": dt_bias,
            "out_norm": ones((nk, d)),
        },
        "mla": {
            "wq": dense(ks[19], (nm, h, cfg.q_dim), h),
            "w_kva": dense(ks[20], (nm, h, cfg.latent_dim), h),
            "kv_norm": ones((nm, cfg.kv_lora_rank)),
            "w_kvb": dense(ks[21], (nm, cfg.kv_lora_rank, kvb), cfg.kv_lora_rank),
            "wo": dense(ks[22], (nm, od, h), od),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, cfg.vocab_size), h),
    }


def param_logical_axes(cfg: KimiLinearConfig) -> Dict[str, Any]:
    """The embedding and the head by the trunk's rules; every stack
    replicated (the share of the experts a chip holds is the config's, not
    a mesh's: ROADMAP M1)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    axes = jax.tree.map(lambda s: ("layers",) + (None,) * (s.ndim - 1), shapes)
    axes.update(
        embed=("vocab", "embed"), final_norm=("embed",),
        lm_head=("embed", "vocab"),
    )
    return axes


# -- the delta rule with a decay a channel -----------------------------------------


def kda_chunk_scan(q, k, v, beta, g, S0, chunk: int = gdn.CHUNK, sub: int = SUB):
    """The recurrence over a segment, chunkwise. q, k, g = log(alpha)
    [b, s, H, d_k], v [b, s, H, d_v], beta [b, s, H], all float32; S0
    [b, H, d_k, d_v]. Returns (o [b, s, H, d_v], S after the last row).

    As ``olmo_hybrid.chunk_scan``, with Gamma_i = exp(G_i), G the running sum
    of the chunk's g, now a vector of d_k: S_i = Diag(alpha_i) S_{i-1} +
    k_i u_i^T with u_i = beta_i (v_i - (Diag(alpha_i) S_{i-1})^T k_i), so
    (I + A) U = beta V - (beta Gamma K) S with the strictly lower A_ij =
    beta_i P_ij(k) (solved once for all chunks by blocks, in matmuls:
    ``olmo_hybrid.unit_lower_solve``), O = (Gamma Q) S + M U with the lower
    M = P(q), where

        P_ij(x) = sum_d x_i[d] k_j[d] exp(G_i[d] - G_j[d])        (j <= i)

    and the chunk leaves Gamma_C S + (Gamma_C / Gamma K)^T U. The decay no
    longer comes out of the sum over d as one number a pair, and no row of
    ``exp(-G)`` may be formed: a channel whose alpha is near 0 makes it
    overflow inside the chunk. So P is built from exponents that are never
    positive. The chunk is cut into sub-blocks of ``sub`` rows. For i in a
    LATER sub-block than j the decay goes through r, the running sum before
    the first row of i's block, exp(G_i - r) exp(r - G_j), two factors of at
    most one, and P is a matrix product of the two scaled operands. Inside
    a sub-block the exponent is taken pair by pair, where it belongs to the
    lower triangle. A row with beta = 0 and g = 0 (a padded one) changes
    nothing after it.
    """
    b, s, H, dk = q.shape
    dv = v.shape[-1]
    C = min(chunk, s)
    n = s // C
    assert n * C == s, (s, C)
    B = min(sub, C)
    m = C // B
    assert m * B == C, (C, B)

    def chunks(x):  # [b, s, H, ...] -> [n, b, H, C, ...]
        x = x.reshape(b, n, C, H, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v, beta, g = map(chunks, (q, k, v, beta, g))
    G = jnp.cumsum(g, axis=-2)  # [n, b, H, C, dk], never above zero
    lead = G.shape[:-2]
    blocks = lambda x: x.reshape(*x.shape[:-2], m, B, dk)  # noqa: E731
    x = jnp.stack([k, q])  # [2, ...]: both P(k) and P(q) against the same k
    xs, ks, Gs = blocks(x), blocks(k), blocks(G)
    # the running sum before each sub-block's first row: [..., m, 1, dk]
    ref = Gs[..., :1, :] - blocks(g)[..., :1, :]
    # j in an earlier sub-block than i: exp(G_i - ref) and exp(ref - G_j)
    left = xs * jnp.exp(Gs - ref)
    right = k[..., None, :, :] * jnp.exp(
        jnp.minimum(ref - G[..., None, :, :], 0.0)
    )  # [..., m, C, dk]
    off = jnp.einsum("...id,...jd->...ij", left, right, precision=_HI)
    earlier = (jnp.arange(C)[None, :] // B) < jnp.arange(m)[:, None]  # [m, C]
    off = jnp.where(earlier[:, None, :], off, 0.0).reshape(2, *lead, C, C)
    # i and j in one sub-block: the exponent of each pair, lower triangle
    tri = jnp.tril(jnp.ones((B, B), bool))
    pair = jnp.where(
        tri[..., None], Gs[..., :, None, :] - Gs[..., None, :, :], -jnp.inf
    )  # [..., m, B, B, dk]
    diag = jnp.sum(
        xs[..., :, None, :] * ks[..., None, :, :] * jnp.exp(pair), axis=-1
    )  # [2, ..., m, B, B]
    diag = (
        diag[..., :, :, None, :] * jnp.eye(m)[:, None, :, None]
    ).reshape(2, *lead, C, C)
    P_k, M = off + diag
    A = beta[..., None] * P_k * jnp.tril(jnp.ones((C, C)), -1)
    rhs = jnp.concatenate(
        [beta[..., None] * v, beta[..., None] * jnp.exp(G) * k], axis=-1
    )
    W = gdn.unit_lower_solve(A, rhs)
    w_v, w_k = W[..., :dv], W[..., dv:]
    q_dec = q * jnp.exp(G)
    k_dec = k * jnp.exp(G[..., -1:, :] - G)
    g_end = jnp.exp(G[..., -1, :])[..., None]  # [n, b, H, dk, 1]

    def step(S, xs):
        w_v, w_k, M, q_dec, k_dec, g_end = xs
        U = w_v - jnp.einsum("...ck,...kv->...cv", w_k, S, precision=_HI)
        o = jnp.einsum("...ck,...kv->...cv", q_dec, S, precision=_HI)
        o = o + jnp.einsum("...ij,...jv->...iv", M, U, precision=_HI)
        S = g_end * S + jnp.einsum(
            "...ck,...cv->...kv", k_dec, U, precision=_HI
        )
        return S, o

    S, o = jax.lax.scan(step, S0, (w_v, w_k, M, q_dec, k_dec, g_end))
    o = jnp.moveaxis(o, (0, 2), (1, 3)).reshape(b, s, H, dv)
    return o, S


def _gates(cfg, lp, x):
    """x [..., h] -> (u [..., conv_dim] in the model's dtype, beta [..., H]
    and g = log(alpha) [..., H, d_k] in float32)."""
    f32 = jnp.float32
    H, d = cfg.kda_heads, cfg.kda_head_dim
    u = qmat(x, lp["w_qkv"])
    b_logit = jnp.einsum("...h,hn->...n", x, lp["w_beta"], preferred_element_type=f32)
    low = jnp.einsum("...h,hr->...r", x, lp["f_a"], preferred_element_type=f32)
    a_logit = jnp.einsum(
        "...r,rn->...n", low.astype(x.dtype), lp["f_b"],
        preferred_element_type=f32,
    )
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
        a_logit + lp["dt_bias"]
    ).reshape(*a_logit.shape[:-1], H, d)
    return u, jax.nn.sigmoid(b_logit), g


def _heads(cfg, c):
    """The convolutions' output [..., 3 * H * d] -> q, k (normalised, q
    scaled), v, each [..., H, d]."""
    H, d = cfg.kda_heads, cfg.kda_head_dim
    q, k, v = (
        x.reshape(*x.shape[:-1], H, d) for x in jnp.split(c, 3, axis=-1)
    )

    return gdn.unit(q) * d**-0.5, gdn.unit(k), v


def _mixer_out(cfg, lp, o, x):
    """W_o [RMSNorm(o) * sigmoid(g_b(g_a x))]: o float32 [..., H, d]."""
    o = rms_norm(o, lp["out_norm"], cfg.rms_eps)
    low = qmat(x, lp["g_a"])
    gate = jax.nn.sigmoid(qmat(low, lp["g_b"]).astype(jnp.float32))
    y = o.reshape(*o.shape[:-2], -1) * gate
    return qmat(y.astype(x.dtype), lp["wo"])


# -- latent attention ---------------------------------------------------------------


def _latent_rows(cfg, fp, x):
    """x [..., h] -> (q_nope [..., heads, nope], q_pe [..., heads, rope],
    the token's latent row [..., stored width]: the normed compressed vector,
    the shared key part as projected (no positional encoding), zeros)."""
    heads, nope = cfg.num_heads, cfg.qk_nope_head_dim
    q = qmat(x, fp["wq"]).reshape(*x.shape[:-1], heads, -1)
    kva = qmat(x, fp["w_kva"])
    c = rms_norm(kva[..., : cfg.kv_lora_rank], fp["kv_norm"], cfg.rms_eps)
    pad = cfg.latent_cache[1] - cfg.latent_dim
    row = jnp.concatenate(
        [c, kva[..., cfg.kv_lora_rank :], jnp.zeros((*c.shape[:-1], pad), c.dtype)],
        axis=-1,
    )
    return q[..., :nope], q[..., nope:], row


def _split_kvb(cfg, fp):
    """W_kvb [rank, heads * (nope + v)] -> (W_uk [rank, heads, nope], W_uv
    [rank, heads, v])."""
    w = fp["w_kvb"].reshape(cfg.kv_lora_rank, cfg.num_heads, -1)
    return w[..., : cfg.qk_nope_head_dim], w[..., cfg.qk_nope_head_dim :]


def _scale(cfg) -> float:
    return (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5


def mla_plain_attention(cfg, fp, q_nope, q_pe, rows, qpos, q_block=0):
    """The plain form: q_nope, q_pe [b, s, heads, .], ``rows`` [b, ctx,
    stored width] the latent rows of positions 0..ctx-1 (a cold segment's
    own, or a sequence's pages gathered), ``qpos`` [b, s] the queries'
    positions. ``[k_nope | v] = W_kvb c`` for every row, a query sees the
    rows at positions <= its own -> [b, s, heads * v]."""
    b, s = qpos.shape
    rank = cfg.kv_lora_rank
    w_uk, w_uv = _split_kvb(cfg, fp)
    c, k_pe = rows[..., :rank], rows[..., rank : cfg.latent_dim]
    k_nope = jnp.einsum("bkc,chd->bkhd", c, w_uk)
    v = jnp.einsum("bkc,chd->bkhd", c, w_uv)
    kpos = jnp.arange(rows.shape[1])[None, None, :]
    scale = _scale(cfg)

    def attend(q_nope, q_pe, qpos):  # [b, n, heads, .], [b, n]
        f32 = jnp.float32
        logits = jnp.einsum(
            "bqhd,bkhd->bhqk", q_nope, k_nope, preferred_element_type=f32
        ) + jnp.einsum("bqhr,bkr->bhqk", q_pe, k_pe, preferred_element_type=f32)
        mask = kpos <= qpos[:, :, None]  # [b, n, ctx]
        logits = jnp.where(mask[:, None], logits * scale, NEG_INF)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum(
            "bhqk,bkhd->bqhd", probs.astype(v.dtype), v,
            preferred_element_type=f32,
        )

    if q_block and s > q_block and s % q_block == 0:
        nb = s // q_block
        split = lambda x: x.reshape(  # noqa: E731
            b, nb, q_block, *x.shape[2:]
        ).swapaxes(0, 1)
        out = jax.lax.map(
            lambda blk: attend(*blk), (split(q_nope), split(q_pe), split(qpos))
        ).swapaxes(0, 1)
    else:
        out = attend(q_nope, q_pe, qpos)
    return out.reshape(b, s, -1).astype(q_nope.dtype)


def mla_absorbed_decode(cfg, fp, q_nope, q_pe, row, pool, table, positions, li):
    """The absorbed form, one decode step: q_nope, q_pe [b, heads, .],
    ``row`` [b, stored width] the new token's latent row, the cache holding
    the positions before it. ``W_uk`` goes into the query, every head then
    scores the ONE row a token has, whose first ``kv_lora_rank`` lanes are
    the value, and ``W_uv`` comes out of the result -> [b, heads * v]."""
    b = q_nope.shape[0]
    w_uk, w_uv = _split_kvb(cfg, fp)
    q_lat = jnp.einsum("bhd,chd->bhc", q_nope, w_uk)
    pad = cfg.latent_cache[1] - cfg.latent_dim
    q_row = jnp.concatenate(
        [q_lat, q_pe, jnp.zeros((*q_pe.shape[:-1], pad), q_pe.dtype)], axis=-1
    )
    o_lat = latent_decode_attention_inline(
        q_row, pool, row, table, positions, li, latent=cfg.kv_lora_rank,
        scale=_scale(cfg), impl=cfg.attention_impl,
    )
    return jnp.einsum("bhc,chd->bhd", o_lat, w_uv).reshape(b, -1)


# -- the period ---------------------------------------------------------------------


def _plan(cfg: KimiLinearConfig):
    """Per layer of a period: (kind, its index among the period's layers of
    that kind); and how many of each kind a period has."""
    layers, count = [], {"kda": 0, "mla": 0}
    for kind in cfg.layer_kinds:
        layers.append((kind, count[kind]))
        count[kind] += 1
    return tuple(layers), count["kda"], count["mla"]


def _stack(params, name, i, whole=()):
    """Layer ``i`` of the stack ``name``, as slices that fuse into the
    matmuls that read them; the matrices named in ``whole`` stay stacks."""
    return {k: a if k in whole else a[i] for k, a in params[name].items()}


def _ffn(cfg, params, layer, h):
    """The FFN of ``layer``: dense (a Python int below ``first_dense``: the
    first period is traced by itself), else the shared expert plus this
    chip's share of the routed ones: the grouped matmul over the stacks read
    whole (models/moe.py:_grouped), or, for a family whose programs of few
    rows compute every held expert on every row (``dense_max_rows``;
    models/exaone_moe.py), that form over the layer's own matrices
    (models/moe.py:takes_grouped)."""
    with jax.named_scope("ffn"):
        if isinstance(layer, int) and layer < cfg.first_dense:
            dp = _stack(params, "dense", layer)
            return llama._mlp(cfg, h, dp["w_gate"], dp["w_up"], dp["w_down"])
        grouped = moe.takes_grouped(
            cfg, math.prod(h.shape[:-1]), moe.stored_expert_stack(cfg, params)
        )
        e = jnp.asarray(layer - cfg.first_dense, jnp.int32)
        ep = _stack(
            params, "experts", e, whole=moe.EXPERT_STACKS if grouped else ()
        )
        shared = llama._mlp(cfg, h, ep["s_gate"], ep["s_up"], ep["s_down"])
        with jax.named_scope("moe.share"):
            logits = jnp.einsum(
                "...h,he->...e", h, ep["router"],
                preferred_element_type=jnp.float32,
            )
            if grouped:
                return shared + moe.routed_ffn(cfg, ep, h, logits, layer=e)
            return shared + moe.held_dense_ffn(cfg, ep, h, logits)


def _periods(cfg, carry, period):
    """``period(carry, pi)`` over the periods: the first, whose leading
    layers are dense, by itself with ``pi`` the Python int 0 (its layer
    indices are static), the others as one scan. Returns (carry, ys stacked
    over all periods)."""
    carry, ys = period(carry, 0)
    ys = jax.tree.map(lambda y: y[None], ys)
    if cfg.periods > 1:
        carry, rest = jax.lax.scan(
            period, carry, jnp.arange(1, cfg.periods, dtype=jnp.int32)
        )
        ys = jax.tree.map(lambda a, r: jnp.concatenate([a, r]), ys, rest)
    return carry, ys


def _segment(
    params, cfg, tokens, positions, valid, lens, fresh, cache, page_table,
    cold,
):
    """The forward of one prefill segment [b, s] shared by the cold and the
    continued program. ``lens`` [b]: the rows' valid tokens; ``fresh`` [b]:
    the row starts its sequence, from S = 0 and a zero tail, whatever its
    slot holds; ``cold``: an MLA layer attends over the segment's own rows,
    else over the sequence's pages, into which the segment has been
    written."""
    b, s = tokens.shape
    f32 = jnp.float32
    layers, n_kda, n_mla = _plan(cfg)
    lat, state, tail = cache
    gtable, slots = page_table[:, :-1], page_table[:, -1]
    page_size = lat.shape[2]
    K1 = cfg.conv_kernel - 1
    x = llama._embed_tokens(cfg, params, tokens)
    keep = valid[..., None]

    def load(arr, li, dtype):
        return gdn.load_slots(arr, li, slots, fresh, dtype)

    def store(arr, li, new):
        return gdn.store_slots(arr, li, slots, new)

    def kda(x, lp, li, state, tail):
        u, beta, g = _gates(cfg, lp, x)
        with jax.named_scope("kda.conv"):
            ext = jnp.concatenate([load(tail, li, u.dtype), u], axis=1)
            q, k, v = _heads(cfg, gdn._conv(lp, ext, s))
            # the last K - 1 inputs of the valid rows: ext row len + i
            new_tail = jax.vmap(
                lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K1, 0)
            )(ext, lens)
        with jax.named_scope("kda.chunk_scan"):
            # a padded row leaves the state alone: beta = 0, alpha = 1
            o, S = kda_chunk_scan(
                q, k, v, jnp.where(keep, beta, 0.0),
                jnp.where(keep[..., None], g, 0.0), load(state, li, f32),
            )
        return (
            _mixer_out(cfg, lp, o, x), store(state, li, S),
            store(tail, li, new_tail),
        )

    def period(carry, pi):
        x, lat, state, tail = carry
        for j, (kind, nth) in enumerate(layers):
            layer = pi * len(layers) + j
            lp = _stack(params, "layers", layer)
            h = llama._norm(cfg, x, lp["attn_norm"])
            if kind == "kda":
                li = pi * n_kda + nth
                with jax.named_scope("attn.kda"):
                    mixed, state, tail = kda(
                        h, _stack(params, "kda", li), li, state, tail
                    )
            else:
                li = pi * n_mla + nth
                fp = _stack(params, "mla", li)
                with jax.named_scope("attn.mla"):
                    q_nope, q_pe, rows = _latent_rows(cfg, fp, h)
                with jax.named_scope("kv_write"):
                    lat = llama._scatter_prefill(
                        lat, li, rows, gtable, positions, valid, page_size)
                with jax.named_scope("attn.mla"), jax.named_scope("mla.prefill"):
                    if not cold:
                        g = lat[li, gtable]  # [b, pages, page, width]
                        rows = g.reshape(b, -1, g.shape[-1])
                    attn = mla_plain_attention(
                        cfg, fp, q_nope, q_pe, rows, positions,
                        q_block=llama.SUFFIX_Q_BLOCK,
                    )
                    mixed = qmat(attn, fp["wo"])
            x = x + mixed
            h = llama._norm(cfg, x, lp["mlp_norm"])
            x = x + _ffn(cfg, params, layer, h)
        return (x, lat, state, tail), ()

    (x, lat, state, tail), _ = _periods(cfg, (x, lat, state, tail), period)
    return llama.lm_logits(cfg, params, x), (lat, state, tail)


def prefill(params, cfg, tokens, seq_lens, cache, page_table, mesh=None):
    """``llama.prefill`` for this family: a cold first segment. MLA layers
    attend over the segment's own latent rows; KDA layers start from zero."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = positions < seq_lens[:, None]
    return _segment(
        params, cfg, tokens, positions, valid, seq_lens,
        jnp.ones((b,), bool), cache, page_table, cold=True,
    )


def prefill_continue(
    params, cfg, tokens, start, suffix_lens, cache, page_table
):
    """``llama.prefill_continue`` for this family: a later segment of a
    chunked prefill RESUMES from its slot's recurrent state and tail (a
    segment at ``start`` 0 is the first, and starts from zero); MLA layers
    attend over the sequence's latent pages."""
    b, s = tokens.shape
    offs = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    return _segment(
        params, cfg, tokens, start[:, None] + offs,
        offs < suffix_lens[:, None], suffix_lens, start == 0, cache,
        page_table, cold=False,
    )


def decode_step(
    params, cfg, tokens, positions, cache, page_table, active=None, mesh=None
):
    """``llama.decode_step`` for this family. Row i of the decode batch IS
    slot i, so a KDA layer reads and writes its layer of the state whole and
    in place; an inactive row's state and tail stay as they are. MLA layers
    take the deferred write: attention reads the pool for positions before
    the token's and the token's own row inline, and ONE scatter after the
    periods writes every MLA layer's new row."""
    b = tokens.shape[0]
    layers, n_kda, n_mla = _plan(cfg)
    lat, state, tail = cache
    if state.shape[1] != b:
        raise ValueError(
            f"decode batch of {b} rows over {state.shape[1]} slots of "
            "recurrent state: a decode row is its slot"
        )
    gtable = page_table[:, :-1]
    page_size = lat.shape[2]
    live = jnp.ones((b,), bool) if active is None else active
    x = llama._embed_tokens(cfg, params, tokens)  # [b, h]

    def kda(x, lp, li, state, tail):
        u, beta, g = _gates(cfg, lp, x)
        old_tail, S = tail[li], state[li]
        with jax.named_scope("kda.conv"):
            ext = jnp.concatenate([old_tail, u[:, None]], axis=1)
            q, k, v = _heads(cfg, gdn._conv(lp, ext, 1)[:, 0])
        with jax.named_scope("kda.step"):
            o, S_new = gdn.recurrence_step(q, k, v, beta, jnp.exp(g), S)
            S_new = jnp.where(live[:, None, None, None], S_new, S)
        new_tail = jnp.where(live[:, None, None], ext[:, 1:], old_tail)
        return (
            _mixer_out(cfg, lp, o, x), state.at[li].set(S_new),
            tail.at[li].set(new_tail),
        )

    def period(carry, pi):
        x, state, tail = carry
        new_rows = []
        for j, (kind, nth) in enumerate(layers):
            layer = pi * len(layers) + j
            lp = _stack(params, "layers", layer)
            h = llama._norm(cfg, x, lp["attn_norm"])
            if kind == "kda":
                li = pi * n_kda + nth
                with jax.named_scope("attn.kda"):
                    mixed, state, tail = kda(
                        h, _stack(params, "kda", li), li, state, tail
                    )
            else:
                li = pi * n_mla + nth
                fp = _stack(params, "mla", li)
                with jax.named_scope("attn.mla"), jax.named_scope("mla.decode"):
                    q_nope, q_pe, row = _latent_rows(cfg, fp, h)
                    attn = mla_absorbed_decode(
                        cfg, fp, q_nope, q_pe, row, lat, gtable, positions, li
                    )
                    mixed = qmat(attn, fp["wo"])
                new_rows.append(row)
            x = x + mixed
            h = llama._norm(cfg, x, lp["mlp_norm"])
            x = x + _ffn(cfg, params, layer, h)
        return (x, state, tail), jnp.stack(new_rows)

    # rows: [periods, MLA layers of a period, b, width]
    (x, state, tail), rows = _periods(cfg, (x, state, tail), period)
    with jax.named_scope("kv_write"):
        lat = llama.scatter_decode_rows(
            lat, rows, gtable, positions, active, page_size)
    return llama.lm_logits(cfg, params, x), (lat, state, tail)


# -- the plain reference ---------------------------------------------------------


def reference_logits(
    params: Dict[str, Any], cfg: KimiLinearConfig, tokens: jnp.ndarray,
    share: "int | None" = None,
) -> jnp.ndarray:
    """The repo's plain reference of this family: float32, ``highest``, no
    cache, no chunks, no absorption, the recurrence TOKEN BY TOKEN exactly as
    the module docstring writes it, every expert of the router's width that
    ``params`` holds computed densely and weighted by the router's top k.
    tokens [s] -> logits [s, vocab]. ``share``: the experts in ``params``
    are the share of that index (None: the config's); with ``num_experts``
    == ``router_outputs`` it is the uncut layer."""
    f32 = jnp.float32
    s = tokens.shape[0]
    pos = jnp.arange(s)
    H, d, K = cfg.kda_heads, cfg.kda_head_dim, cfg.conv_kernel
    share = cfg.share_index if share is None else share

    def rms(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + cfg.rms_eps) * w.astype(f32)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + gdn.L2_EPS)

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def kda(x, lp):
        u = x @ lp["w_qkv"]
        ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), f32), u])
        c = jax.nn.silu(sum(lp["conv"][i] * ext[i : i + s] for i in range(K)))
        q, k, v = (a.reshape(s, H, d) for a in jnp.split(c, 3, axis=-1))
        q, k = unit(q) * d**-0.5, unit(k)
        beta = jax.nn.sigmoid(x @ lp["w_beta"])
        alpha = jnp.exp(
            -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(
                (x @ lp["f_a"]) @ lp["f_b"] + lp["dt_bias"]
            ).reshape(s, H, d)
        )

        def token(S, t):  # S [H, d, d]
            q, k, v, beta, alpha = t
            S = alpha[:, :, None] * S
            u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
            S = S + k[:, :, None] * u[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, q)

        _, o = jax.lax.scan(token, jnp.zeros((H, d, d), f32), (q, k, v, beta, alpha))
        gate = jax.nn.sigmoid((x @ lp["g_a"]) @ lp["g_b"])
        return (rms(o, lp["out_norm"]).reshape(s, H * d) * gate) @ lp["wo"]

    def mla(x, fp):
        heads, nope, rank = cfg.num_heads, cfg.qk_nope_head_dim, cfg.kv_lora_rank
        q = (x @ fp["wq"]).reshape(s, heads, -1)
        kva = x @ fp["w_kva"]
        c, k_pe = rms(kva[:, :rank], fp["kv_norm"]), kva[:, rank:]
        kv = (c @ fp["w_kvb"]).reshape(s, heads, -1)
        k = jnp.concatenate(
            [kv[..., :nope], jnp.broadcast_to(k_pe[:, None], (s, heads, k_pe.shape[-1]))],
            axis=-1,
        )
        scores = jnp.einsum("shd,thd->hst", q, k) * _scale(cfg)
        scores = jnp.where(pos[None, :] <= pos[:, None], scores, -jnp.inf)
        attn = jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), kv[..., nope:])
        return attn.reshape(s, -1) @ fp["wo"]

    def experts(x, ep):
        w, idx = moe.route(cfg, x @ ep["router"], ep["router_bias"])
        weights = jnp.sum(
            jax.nn.one_hot(idx, cfg.router_outputs, dtype=f32) * w[..., None], axis=-2
        )  # [s, router width], zero off the top k
        held = jax.lax.dynamic_slice_in_dim(
            weights, share * cfg.num_experts, cfg.num_experts, axis=1
        )
        g = jnp.einsum("sh,ehf->sef", x, ep["w_gate"])
        u = jnp.einsum("sh,ehf->sef", x, ep["w_up"])
        y = jnp.einsum("sef,efh->seh", jax.nn.silu(g) * u, ep["w_down"])
        return swiglu(x, ep["s_gate"], ep["s_up"], ep["s_down"]) + jnp.einsum(
            "seh,se->sh", y, held
        )

    x = params["embed"][tokens].astype(f32)
    as_f32 = lambda name, i: jax.tree.map(  # noqa: E731
        lambda a: a[i].astype(f32), params[name]
    )
    with jax.default_matmul_precision("highest"):
        count = {"kda": 0, "mla": 0}
        for l in range(cfg.num_layers):
            kind = cfg.layer_kinds[l % len(cfg.layer_kinds)]
            mp = as_f32(kind, count[kind])
            count[kind] += 1
            lp = as_f32("layers", l)
            h = rms(x, lp["attn_norm"])
            x = x + (kda(h, mp) if kind == "kda" else mla(h, mp))
            h = rms(x, lp["mlp_norm"])
            if l < cfg.first_dense:
                dp = as_f32("dense", l)
                x = x + swiglu(h, dp["w_gate"], dp["w_up"], dp["w_down"])
            else:
                x = x + experts(h, as_f32("experts", l - cfg.first_dense))
        x = rms(x, params["final_norm"])
        return x @ params["lm_head"].astype(f32)
