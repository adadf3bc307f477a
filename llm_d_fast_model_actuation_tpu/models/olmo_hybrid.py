"""Olmo-Hybrid (``model_type: olmo_hybrid``, allenai) — the family whose
layers differ in KIND: a period of three linear-attention layers (the gated
delta rule, arXiv:2412.06464) and one full-attention layer.

What differs from the Llama trunk (models/llama.py), per layer:

  * the block: the norm is on each sub-layer's OUTPUT and the mixer reads
    the raw residual, ``h = x + RMSNorm(Mixer(x))``, ``y = h +
    RMSNorm(SwiGLU(h))``;
  * a full-attention layer: q and k RMS-normed over all their channels
    before the split into heads, no positional encoding, K and V in the
    paged pool, whose layer index is the full layer's ORDINAL (a model of
    16 layers keeps a pool of 4);
  * a linear-attention layer keeps no K and V. Its sequence state is a
    matrix a head, ``S [heads, d_k, d_v]`` in float32, overwritten every
    token, and the last ``conv_kernel - 1`` inputs of a depthwise causal
    convolution (engine/kv_cache.py:KVLayout, the third kind of state:
    ``[linear layers, slots, ...]``, a slot found by the static column the
    engine appends to every page-table row). Per token and head::

        u_t = W_qkv x_t;  c_t = silu(sum_i conv_i * u_{t-K+1+i})
        q_t = c^q_t / |c^q_t| * d_k^-1/2,  k_t = c^k_t / |c^k_t|,  v_t = c^v_t
        beta_t = 2 sigmoid(w_b x_t),  alpha_t = exp(-exp(A_log) softplus(w_a x_t + dt_bias))
        S_t = alpha_t S_{t-1} + beta_t k_t (v_t - alpha_t S_{t-1}^T k_t)^T
        o_t = S_t^T q_t;  y_t = W_o [RMSNorm(o_t) * silu(W_g x_t)]

    A prompt segment runs the recurrence CHUNKWISE (:func:`chunk_scan`:
    whatever does not depend on the carried state is computed for all
    chunks of the segment at once, and only ``S`` goes chunk to chunk); a
    decode step is one recurrence step a slot (:func:`recurrence_step`).

The parameters are three stacks: ``layers`` (every layer: the two output
norms and the FFN), ``linear`` and ``full`` (the mixers of each kind, by
ordinal). The forward scans over PERIODS; the body is the period's layers
unrolled statically, as models/smallthinker.py's, and shares its helpers
(``llama.cold_segment`` / ``suffix_segment`` / ``scatter_decode_rows`` /
``lm_logits``). The entry points keep the trunk's signatures and
``llama.prefill`` / ``prefill_continue`` / ``decode_step`` hand the config
here (``llama.patterned``).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import paged_decode_attention_inline
from ..ops.norm import rms_norm
from . import llama
from .quant import qmat

#: tokens of one chunk of the chunkwise recurrence
CHUNK = 64
#: rows of a diagonal block of a chunk's triangular system (unit_lower_solve)
SOLVE_BLOCK = 16
#: inside the sum of squares of the q and k normalisation
L2_EPS = 1e-6
_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class OlmoHybridConfig(llama.LlamaConfig):
    #: (llama.patterned) the module of this package that is its forward
    forward_module = "olmo_hybrid"

    #: per layer of one period: "linear" or "full"
    layer_kinds: Tuple[str, ...] = ("linear", "linear", "linear", "full")
    linear_heads: int = 30
    linear_key_dim: int = 96
    linear_value_dim: int = 192
    conv_kernel: int = 4
    #: beta in (0, 2): the state's transition may have negative eigenvalues
    allow_neg_eigval: bool = True

    def __post_init__(self) -> None:
        super().__post_init__()
        if set(self.layer_kinds) - {"linear", "full"} or not self.layer_kinds:
            raise ValueError(f"layer_kinds {self.layer_kinds}: linear or full")
        if self.num_layers % len(self.layer_kinds):
            raise ValueError(
                f"{self.num_layers} layers are not whole periods of "
                f"{len(self.layer_kinds)}"
            )
        if self.quantization:
            raise ValueError(
                "OlmoHybridConfig: weight quantization is not carried by "
                "this forward"
            )

    @classmethod
    def olmo_hybrid_7b(cls) -> "OlmoHybridConfig":
        """Olmo-Hybrid-7B as published (allenai/Olmo-Hybrid-7B
        ``config.json``); ``max_seq_len`` is a serving choice below the
        published 65,536 positions."""
        return cls(
            vocab_size=100352, hidden_size=3840, num_layers=32, num_heads=30,
            num_kv_heads=30, head_dim=128, intermediate_size=11008,
            rms_eps=1e-6, max_seq_len=4096,
        )

    @classmethod
    def tiny_olmo_hybrid(cls, vocab: int = 256) -> "OlmoHybridConfig":
        """CPU test size: two periods, head sizes that are no lane multiple."""
        return cls(
            vocab_size=vocab, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=4, head_dim=16, intermediate_size=96, rms_eps=1e-6,
            max_seq_len=256, linear_heads=4, linear_key_dim=12,
            linear_value_dim=24,
        )

    @property
    def periods(self) -> int:
        return self.num_layers // len(self.layer_kinds)

    @property
    def linear_layers(self) -> int:
        return self.periods * self.layer_kinds.count("linear")

    @property
    def cache_layers(self) -> int:
        """Layers of K and V a token holds: the full-attention layers."""
        return self.periods * self.layer_kinds.count("full")

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: q, k and v of every head."""
        return self.linear_heads * (
            2 * self.linear_key_dim + self.linear_value_dim
        )

    @property
    def recurrent_state(self):
        """(llama.recurrent_state) the linear layers, and the shapes a slot
        holds for one of them: S in float32, the convolution's tail in the
        model's dtype."""
        return (
            self.linear_layers,
            (self.linear_heads, self.linear_key_dim, self.linear_value_dim),
            (self.conv_kernel - 1, self.conv_dim),
        )

    def num_params(self) -> int:
        h, H, dv = self.hidden_size, self.linear_heads, self.linear_value_dim
        ffn = 3 * h * self.intermediate_size + 2 * h
        linear = (
            h * self.conv_dim + self.conv_kernel * self.conv_dim
            + 2 * h * H + 2 * H + dv + 2 * h * H * dv
        )
        full = 2 * h * self.q_dim + 2 * h * self.kv_dim + self.q_dim + self.kv_dim
        return (
            self.num_layers * ffn + self.linear_layers * linear
            + self.cache_layers * full + 2 * self.vocab_size * h + h
        )


# -- parameters -----------------------------------------------------------------


def _decay_init(cfg: OlmoHybridConfig):
    """(A_log, dt_bias) [linear layers, heads] float32, the same in every
    layer: A from 1 to 16 over the heads and softplus(dt_bias) from 0.001 to
    0.1 (the ranges state-space layers are initialised in), so that a head's
    state forgets over tens to thousands of tokens."""
    H = cfg.linear_heads
    a_log = np.log(np.linspace(1.0, 16.0, H))
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), H))
    dt_bias = dt + np.log(-np.expm1(-dt))  # softplus^-1
    tile = lambda x: jnp.asarray(  # noqa: E731
        np.tile(x[None], (cfg.linear_layers, 1)), jnp.float32
    )
    return tile(a_log), tile(dt_bias)


def init_params(key: jax.Array, cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """Random-init parameters: normal(0, fan_in^-1/2) per tensor from
    ``split(key)``, cast to the model's dtype; norms at one; the decay's two
    vectors as :func:`_decay_init` gives them, in float32."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 13)
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nl, nf = cfg.linear_layers, cfg.cache_layers
    H, C, gd = cfg.linear_heads, cfg.conv_dim, cfg.linear_heads * cfg.linear_value_dim

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(cfg.dtype)

    ones = lambda shape: jnp.ones(shape, cfg.dtype)  # noqa: E731
    a_log, dt_bias = _decay_init(cfg)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, h), h),
        "layers": {
            "post_mixer_norm": ones((L, h)),
            "post_ffn_norm": ones((L, h)),
            "w_gate": dense(ks[0], (L, h, f), h),
            "w_up": dense(ks[1], (L, h, f), h),
            "w_down": dense(ks[2], (L, f, h), f),
        },
        "full": {
            "wq": dense(ks[3], (nf, h, cfg.q_dim), h),
            "wk": dense(ks[4], (nf, h, cfg.kv_dim), h),
            "wv": dense(ks[5], (nf, h, cfg.kv_dim), h),
            "wo": dense(ks[6], (nf, cfg.q_dim, h), cfg.q_dim),
            "q_norm": ones((nf, cfg.q_dim)),
            "k_norm": ones((nf, cfg.kv_dim)),
        },
        "linear": {
            "w_qkv": dense(ks[7], (nl, h, C), h),
            "conv": dense(ks[8], (nl, cfg.conv_kernel, C), cfg.conv_kernel),
            "w_beta": dense(ks[9], (nl, h, H), h),
            "w_alpha": dense(ks[10], (nl, h, H), h),
            "w_g": dense(ks[11], (nl, h, gd), h),
            "wo": dense(ks[12], (nl, gd, h), gd),
            "a_log": a_log,
            "dt_bias": dt_bias,
            "out_norm": ones((nl, cfg.linear_value_dim)),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, cfg.vocab_size), h),
    }


def param_logical_axes(cfg: OlmoHybridConfig) -> Dict[str, Any]:
    """The FFN, the full-attention mixers, the embedding and the head by the
    trunk's rules; the linear mixers replicated (the recurrence over a tp
    mesh, heads over ``tp``, is not built: ROADMAP M4)."""
    rep2, rep3 = ("layers", None), ("layers", None, None)
    return {
        "embed": ("vocab", "embed"),
        "layers": {
            "post_mixer_norm": ("layers", "embed"),
            "post_ffn_norm": ("layers", "embed"),
            "w_gate": ("layers", "embed", "mlp"),
            "w_up": ("layers", "embed", "mlp"),
            "w_down": ("layers", "mlp", "embed"),
        },
        "full": {
            "wq": ("layers", "embed", "heads"),
            "wk": ("layers", "embed", "kv_heads"),
            "wv": ("layers", "embed", "kv_heads"),
            "wo": ("layers", "heads", "embed"),
            "q_norm": ("layers", "heads"),
            "k_norm": ("layers", "kv_heads"),
        },
        "linear": {
            "w_qkv": rep3, "conv": rep3, "w_beta": rep3, "w_alpha": rep3,
            "w_g": rep3, "wo": rep3, "a_log": rep2, "dt_bias": rep2,
            "out_norm": rep2,
        },
        "final_norm": ("embed",),
        "lm_head": ("embed", "vocab"),
    }


# -- the gated delta rule ---------------------------------------------------------


def unit_lower_solve(A, rhs):
    """W of (I + A) W = rhs for strictly lower A [..., C, C] and rhs
    [..., C, m], float32, by blocked substitution in whole C x C matmuls:
    nothing runs row after row over the chunk. The inverse T of I + A is
    built exactly, bottom up, and W = T rhs is one matmul:

      * the diagonal blocks of ``SOLVE_BLOCK`` rows, taken together as one
        block-diagonal D, are nilpotent, D^16 = 0, so (I + D)^-1 =
        (I - D)(I + D^2)(I + D^4)(I + D^8), no series cut short;
      * T holding the inverses of the diagonal blocks of b rows and E the
        blocks of A that join two neighbours of them, T - T E T holds the
        inverses of the blocks of 2b rows, [[T1, 0], [-T2 A21 T1, T2]] a
        pair: 16 -> 32 -> 64.

    The zeros outside the blocks are multiplied along: a 64 x 64 tile suits
    the chip's matrix unit where sixteen rows do not, and a row of zeros in
    A (a padded one) keeps its row of rhs bit for bit."""
    C = A.shape[-1]
    i = jnp.arange(C)
    mm = lambda a, b: jnp.matmul(a, b, precision=_HI)  # noqa: E731
    # rows and columns in one diagonal block of b rows
    same = lambda b: (i[:, None] // b) == (i[None, :] // b)  # noqa: E731
    with jax.named_scope("chunk_solve"):
        b = min(SOLVE_BLOCK, C)
        D = jnp.where(same(b), A, 0.0)
        T = jnp.eye(C, dtype=A.dtype) - D
        P, k = D, 1
        while 2 * k < b:
            P, k = mm(P, P), 2 * k
            T = T + mm(T, P)
        while b < C:
            E = jnp.where(same(2 * b) & ~same(b), A, 0.0)
            T, b = T - mm(T, mm(E, T)), 2 * b
        return mm(T, rhs)


def chunk_scan(q, k, v, beta, g, S0, chunk: int = CHUNK):
    """The recurrence over a segment, chunkwise. q, k [b, s, H, d_k], v
    [b, s, H, d_v], beta and g = log(alpha) [b, s, H], all float32; S0
    [b, H, d_k, d_v]. Returns (o [b, s, H, d_v], S after the last row).

    Within a chunk write S_i = alpha_i S_{i-1} + k_i u_i^T with the
    pseudo-value u_i = beta_i (v_i - alpha_i S_{i-1}^T k_i) and gamma_i the
    product of the chunk's alphas up to i. Then, S the state the chunk starts
    from, (I + A) U = beta V - (beta gamma K) S with the strictly lower
    A_ij = beta_i (gamma_i / gamma_j) k_i.k_j, so U = W_v - W_k S where
    [W_v | W_k] solves the unit triangular system once for all chunks
    (:func:`unit_lower_solve`: the inverse of I + A by blocks, in matmuls);
    O = (gamma Q) S + M U with the lower M_ij = (gamma_i / gamma_j) q_i.k_j;
    and the chunk leaves gamma_C S + (gamma_C / gamma K)^T U. A row with
    beta = 0 and g = 0 (a padded one) changes nothing after it.
    """
    b, s, H, _ = q.shape
    dv = v.shape[-1]
    C = min(chunk, s)
    n = s // C
    assert n * C == s, (s, C)

    def chunks(x):  # [b, s, H, ...] -> [n, b, H, C, ...]
        x = x.reshape(b, n, C, H, *x.shape[3:])
        return jnp.moveaxis(x, (1, 3), (0, 2))

    q, k, v, beta, g = map(chunks, (q, k, v, beta, g))
    gc = jnp.cumsum(g, axis=-1)  # log gamma_i, [n, b, H, C]
    lower = jnp.tril(jnp.ones((C, C), bool))
    # gamma_i / gamma_j for j <= i: never above one
    decay = jnp.exp(
        jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf)
    )
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HI)
    A = beta[..., None] * decay * kk * jnp.tril(jnp.ones((C, C)), -1)
    rhs = jnp.concatenate(
        [beta[..., None] * v, (beta * jnp.exp(gc))[..., None] * k], axis=-1
    )
    W = unit_lower_solve(A, rhs)
    w_v, w_k = W[..., :dv], W[..., dv:]
    M = jnp.einsum("...id,...jd->...ij", q, k, precision=_HI) * decay
    q_dec = q * jnp.exp(gc)[..., None]
    k_dec = k * jnp.exp(gc[..., -1:] - gc)[..., None]
    g_end = jnp.exp(gc[..., -1])[..., None, None]  # [n, b, H, 1, 1]

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


def recurrence_step(q, k, v, beta, alpha, S):
    """One token: q, k [b, H, d_k], v [b, H, d_v], beta and alpha [b, H], S
    [b, H, d_k, d_v], all float32 -> (o [b, H, d_v], the new S). S is read
    twice and written once: S^T k and S^T q in one pass, then the update, and
    o = alpha S^T q + (k.q) u is S_t^T q without a pass over the new S.

    ``alpha`` [b, H, d_k], a decay a CHANNEL (models/kimi_linear.py): the
    state decays row by row, S' = Diag(alpha) S, so the decay goes into the
    two vectors the pass contracts with, S'^T k = S^T (alpha k), and the
    update scales each row of S by its own alpha."""
    if alpha.ndim == k.ndim:
        s_k = jnp.sum(S * (alpha * k)[..., None], axis=-2)
        s_q = jnp.sum(S * (alpha * q)[..., None], axis=-2)
        u = beta[..., None] * (v - s_k)
        S = alpha[..., None] * S + k[..., None] * u[..., None, :]
        o = s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
        return o, S
    s_k = jnp.sum(S * k[..., None], axis=-2)
    s_q = jnp.sum(S * q[..., None], axis=-2)
    a = alpha[..., None]
    u = beta[..., None] * (v - a * s_k)
    S = a[..., None] * S + k[..., None] * u[..., None, :]
    o = a * s_q + jnp.sum(k * q, axis=-1, keepdims=True) * u
    return o, S


def _gates(cfg, lp, x):
    """x [..., h] -> (u [..., conv_dim] in the model's dtype, beta and
    g = log(alpha) [..., H] in float32)."""
    f32 = jnp.float32
    u = qmat(x, lp["w_qkv"])
    b_logit = jnp.einsum("...h,hn->...n", x, lp["w_beta"], preferred_element_type=f32)
    a_logit = jnp.einsum("...h,hn->...n", x, lp["w_alpha"], preferred_element_type=f32)
    beta = jax.nn.sigmoid(b_logit) * (2.0 if cfg.allow_neg_eigval else 1.0)
    g = -jnp.exp(lp["a_log"]) * jax.nn.softplus(a_logit + lp["dt_bias"])
    return u, beta, g


def _conv(lp, ext, rows: int):
    """Depthwise causal convolution and SiLU: ``ext`` [b, K - 1 + rows, C]
    is the tail followed by the rows' inputs -> float32 [b, rows, C]. A
    family whose convolution has a bias (models/nemotron_h.py) brings
    ``conv_bias`` [C]."""
    w = lp["conv"].astype(jnp.float32)
    acc = sum(
        w[i] * ext[:, i : i + rows].astype(jnp.float32)
        for i in range(w.shape[0])
    )
    if "conv_bias" in lp:
        acc = acc + lp["conv_bias"].astype(jnp.float32)
    return jax.nn.silu(acc)


def unit(x):
    """x over its L2 norm along the last axis (L2_EPS inside the root)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def _heads(cfg, c):
    """The convolution's output [..., C] -> q, k [..., H, d_k] (normalised,
    q scaled), v [..., H, d_v]."""
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    q, k, v = jnp.split(c, [H * dk, 2 * H * dk], axis=-1)
    q = q.reshape(*q.shape[:-1], H, dk)
    k = k.reshape(*k.shape[:-1], H, dk)
    return unit(q) * dk**-0.5, unit(k), v.reshape(*v.shape[:-1], H, dv)


def _mixer_out(cfg, lp, o, x):
    """W_o [RMSNorm(o) * silu(W_g x)]: o float32 [..., H, d_v]."""
    o = rms_norm(o, lp["out_norm"], cfg.rms_eps)
    gate = jax.nn.silu(qmat(x, lp["w_g"]).astype(jnp.float32))
    y = o.reshape(*o.shape[:-2], -1) * gate
    return qmat(y.astype(x.dtype), lp["wo"])


def _project_full(cfg, fp, x):
    """x [b, s, h] -> q [b, s, heads, hd], k, v [b, s, kvh, hd]: q and k
    normed over all their channels, no positional encoding."""
    b, s, _ = x.shape
    q = rms_norm(qmat(x, fp["wq"]), fp["q_norm"], cfg.rms_eps)
    k = rms_norm(qmat(x, fp["wk"]), fp["k_norm"], cfg.rms_eps)
    v = qmat(x, fp["wv"])
    return (
        q.reshape(b, s, cfg.num_heads, cfg.head_dim),
        k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim),
        v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim),
    )


# -- the period ---------------------------------------------------------------------


def _plan(cfg: OlmoHybridConfig):
    """Per layer of a period: (kind, its index among the period's layers of
    that kind); and how many of each kind a period has."""
    layers, count = [], {"linear": 0, "full": 0}
    for kind in cfg.layer_kinds:
        layers.append((kind, count[kind]))
        count[kind] += 1
    return tuple(layers), count["linear"], count["full"]


def _stack(params, name, i):
    """Layer ``i`` of the stack ``name``, as slices that fuse into the
    matmuls that read them."""
    return {k: a[i] for k, a in params[name].items()}


def _close(cfg, lp, x, mixed):
    """The block's two residual adds around a mixer's output."""
    x = x + llama._norm(cfg, mixed, lp["post_mixer_norm"])
    with jax.named_scope("ffn"):
        y = llama._mlp(cfg, x, lp["w_gate"], lp["w_up"], lp["w_down"])
    return x + llama._norm(cfg, y, lp["post_ffn_norm"])


def load_slots(arr, li, slots, fresh, dtype):
    """Layer ``li`` of a per-slot array [layers, slots, ...] for the rows'
    ``slots`` [b] -> [b, ...] in ``dtype``; a ``fresh`` row reads zero,
    whatever its slot holds."""
    b = slots.shape[0]
    rows = jnp.concatenate([
        jax.lax.dynamic_slice(
            arr, (li, slots[r]) + (0,) * (arr.ndim - 2),
            (1, 1) + arr.shape[2:],
        )[0]
        for r in range(b)
    ]).astype(dtype)
    return jnp.where(fresh.reshape(b, *[1] * (rows.ndim - 1)), 0, rows)


def store_slots(arr, li, slots, new):
    """``new`` [b, ...] written to layer ``li`` of the rows' ``slots``."""
    for r in range(slots.shape[0]):
        arr = jax.lax.dynamic_update_slice(
            arr, new[r][None, None].astype(arr.dtype),
            (li, slots[r]) + (0,) * (arr.ndim - 2),
        )
    return arr


def _segment(
    params, cfg, tokens, positions, valid, lens, fresh, cache, page_table,
    attend,
):
    """The forward of one prefill segment [b, s] shared by the cold and the
    continued program. ``lens`` [b]: the rows' valid tokens; ``fresh`` [b]:
    the row starts its sequence, from S = 0 and a zero tail, whatever its
    slot holds; every other row resumes from its slot's state."""
    b, s = tokens.shape
    f32 = jnp.float32
    layers, n_lin, n_full = _plan(cfg)
    kp, vp, state, tail = cache
    gtable, slots = page_table[:, :-1], page_table[:, -1]
    page_size = kp.shape[2]
    K1 = cfg.conv_kernel - 1
    x = llama._embed_tokens(cfg, params, tokens)
    keep = valid[..., None]

    def load(arr, li, dtype):
        return load_slots(arr, li, slots, fresh, dtype)

    def store(arr, li, new):
        return store_slots(arr, li, slots, new)

    def linear(x, lp, li, state, tail):
        u, beta, g = _gates(cfg, lp, x)
        with jax.named_scope("gdn.conv"):
            ext = jnp.concatenate([load(tail, li, u.dtype), u], axis=1)
            q, k, v = _heads(cfg, _conv(lp, ext, s))
            # the last K - 1 inputs of the valid rows: ext row len + i
            new_tail = jax.vmap(
                lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K1, 0)
            )(ext, lens)
        with jax.named_scope("gdn.chunk_scan"):
            # a padded row leaves the state alone: beta = 0, alpha = 1
            o, S = chunk_scan(
                q, k, v, jnp.where(keep, beta, 0.0), jnp.where(keep, g, 0.0),
                load(state, li, f32),
            )
        return (
            _mixer_out(cfg, lp, o, x), store(state, li, S),
            store(tail, li, new_tail),
        )

    def period(carry, pi):
        x, kp, vp, state, tail = carry
        for j, (kind, nth) in enumerate(layers):
            lp = _stack(params, "layers", pi * len(layers) + j)
            if kind == "linear":
                li = pi * n_lin + nth
                with jax.named_scope("attn.linear"):
                    mixed, state, tail = linear(
                        x, _stack(params, "linear", li), li, state, tail
                    )
            else:
                li = pi * n_full + nth
                fp = _stack(params, "full", li)
                with jax.named_scope("attn.global"):
                    q, k, v = _project_full(cfg, fp, x)
                with jax.named_scope("kv_write"):
                    kp = llama._scatter_prefill(
                        kp, li, k, gtable, positions, valid, page_size)
                    vp = llama._scatter_prefill(
                        vp, li, v, gtable, positions, valid, page_size)
                with jax.named_scope("attn.global"):
                    attn = attend(q, k, v, (kp, vp), gtable, li, 0)
                    mixed = qmat(attn.reshape(b, s, cfg.q_dim), fp["wo"])
            x = _close(cfg, lp, x, mixed)
        return (x, kp, vp, state, tail), None

    (x, kp, vp, state, tail), _ = jax.lax.scan(
        period, (x, kp, vp, state, tail),
        llama.period_indices(cfg, len(layers)),
    )
    return llama.lm_logits(cfg, params, x), (kp, vp, state, tail)


def prefill(params, cfg, tokens, seq_lens, cache, page_table, mesh=None):
    """``llama.prefill`` for this family: a cold first segment. Full layers
    attend over the segment's own K and V; linear layers start from zero."""
    positions, valid, attend = llama.cold_segment(cfg, tokens, seq_lens, mesh)
    fresh = jnp.ones(tokens.shape[:1], bool)
    return _segment(
        params, cfg, tokens, positions, valid, seq_lens, fresh, cache,
        page_table, attend,
    )


def prefill_continue(
    params, cfg, tokens, start, suffix_lens, cache, page_table
):
    """``llama.prefill_continue`` for this family: a later segment of a
    chunked prefill RESUMES from its slot's recurrent state and tail (a
    segment at ``start`` 0 is the first, and starts from zero); full layers
    attend over the sequence's pages."""
    positions, valid, attend = llama.suffix_segment(tokens, start, suffix_lens)
    return _segment(
        params, cfg, tokens, positions, valid, suffix_lens, start == 0,
        cache, page_table, attend,
    )


def decode_step(
    params, cfg, tokens, positions, cache, page_table, active=None, mesh=None
):
    """``llama.decode_step`` for this family. Row i of the decode batch IS
    slot i (the engine's table has a row a slot), so a linear layer reads
    and writes its layer of the state whole and in place; an inactive row's
    state and tail stay as they are. Full layers take the deferred write:
    attention reads the pool for positions before the token's, and ONE
    scatter a direction after the scan writes every full layer's new row."""
    b = tokens.shape[0]
    layers, n_lin, n_full = _plan(cfg)
    kp, vp, state, tail = cache
    if state.shape[1] != b:
        raise ValueError(
            f"decode batch of {b} rows over {state.shape[1]} slots of "
            "recurrent state: a decode row is its slot"
        )
    gtable = page_table[:, :-1]
    page_size = kp.shape[2]
    live = jnp.ones((b,), bool) if active is None else active
    x = llama._embed_tokens(cfg, params, tokens)  # [b, h]

    def linear(x, lp, li, state, tail):
        u, beta, g = _gates(cfg, lp, x)
        old_tail, S = tail[li], state[li]
        with jax.named_scope("gdn.conv"):
            ext = jnp.concatenate([old_tail, u[:, None]], axis=1)
            q, k, v = _heads(cfg, _conv(lp, ext, 1)[:, 0])
        with jax.named_scope("gdn.step"):
            o, S_new = recurrence_step(q, k, v, beta, jnp.exp(g), S)
            S_new = jnp.where(live[:, None, None, None], S_new, S)
        new_tail = jnp.where(live[:, None, None], ext[:, 1:], old_tail)
        return (
            _mixer_out(cfg, lp, o, x), state.at[li].set(S_new),
            tail.at[li].set(new_tail),
        )

    def period(carry, pi):
        x, state, tail = carry
        new_k, new_v = [], []
        for j, (kind, nth) in enumerate(layers):
            lp = _stack(params, "layers", pi * len(layers) + j)
            if kind == "linear":
                li = pi * n_lin + nth
                with jax.named_scope("attn.linear"):
                    mixed, state, tail = linear(
                        x, _stack(params, "linear", li), li, state, tail
                    )
            else:
                li = pi * n_full + nth
                fp = _stack(params, "full", li)
                with jax.named_scope("attn.global"):
                    q, k, v = _project_full(cfg, fp, x[:, None, :])
                    q, k, v = q[:, 0], k[:, 0], v[:, 0]
                    attn = paged_decode_attention_inline(
                        q, kp, vp, k, v, gtable, positions, li,
                        impl=cfg.attention_impl, mesh=mesh,
                    )
                    mixed = qmat(attn.reshape(b, cfg.q_dim), fp["wo"])
                new_k.append(k)
                new_v.append(v)
            x = _close(cfg, lp, x, mixed)
        return (x, state, tail), (jnp.stack(new_k), jnp.stack(new_v))

    # k_all, v_all: [periods, full layers of a period, b, kvh, hd]
    (x, state, tail), (k_all, v_all) = jax.lax.scan(
        period, (x, state, tail), llama.period_indices(cfg, len(layers))
    )
    with jax.named_scope("kv_write"):
        kp = llama.scatter_decode_rows(
            kp, k_all, gtable, positions, active, page_size)
        vp = llama.scatter_decode_rows(
            vp, v_all, gtable, positions, active, page_size)
    return llama.lm_logits(cfg, params, x), (kp, vp, state, tail)


# -- the plain reference ---------------------------------------------------------


def reference_logits(
    params: Dict[str, Any], cfg: OlmoHybridConfig, tokens: jnp.ndarray
) -> jnp.ndarray:
    """The repo's plain reference of this family: float32, ``highest``, no
    cache, no chunks, the recurrence TOKEN BY TOKEN exactly as the module
    docstring writes it (a ``lax.scan`` over positions), the attention mask
    written out. tokens [s] -> logits [s, vocab]. What the chunkwise,
    cached, kernel-backed path above is tested against."""
    f32 = jnp.float32
    s = tokens.shape[0]
    pos = jnp.arange(s)
    H, dk, dv = cfg.linear_heads, cfg.linear_key_dim, cfg.linear_value_dim
    K = cfg.conv_kernel

    def rms(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + cfg.rms_eps) * w.astype(f32)

    def unit(x):
        return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)

    def linear(x, lp):
        u = x @ lp["w_qkv"]
        ext = jnp.concatenate([jnp.zeros((K - 1, u.shape[1]), f32), u])
        c = jax.nn.silu(sum(lp["conv"][i] * ext[i : i + s] for i in range(K)))
        q, k, v = jnp.split(c, [H * dk, 2 * H * dk], axis=-1)
        q = unit(q.reshape(s, H, dk)) * dk**-0.5
        k = unit(k.reshape(s, H, dk))
        v = v.reshape(s, H, dv)
        beta = jax.nn.sigmoid(x @ lp["w_beta"]) * (
            2.0 if cfg.allow_neg_eigval else 1.0
        )
        alpha = jnp.exp(
            -jnp.exp(lp["a_log"]) * jax.nn.softplus(x @ lp["w_alpha"] + lp["dt_bias"])
        )

        def token(S, t):  # S [H, dk, dv]
            q, k, v, beta, alpha = t
            S = alpha[:, None, None] * S
            u = beta[:, None] * (v - jnp.einsum("hkv,hk->hv", S, k))
            S = S + k[:, :, None] * u[:, None, :]
            return S, jnp.einsum("hkv,hk->hv", S, q)

        _, o = jax.lax.scan(token, jnp.zeros((H, dk, dv), f32), (q, k, v, beta, alpha))
        y = rms(o, lp["out_norm"]).reshape(s, H * dv) * jax.nn.silu(x @ lp["w_g"])
        return y @ lp["wo"]

    def full(x, fp):
        heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = rms(x @ fp["wq"], fp["q_norm"]).reshape(s, heads, hd)
        k = rms(x @ fp["wk"], fp["k_norm"]).reshape(s, kvh, hd)
        v = (x @ fp["wv"]).reshape(s, kvh, hd)
        qg = q.reshape(s, kvh, heads // kvh, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        mask = pos[None, :] <= pos[:, None]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return attn.reshape(s, heads * hd) @ fp["wo"]

    x = params["embed"][tokens].astype(f32)
    with jax.default_matmul_precision("highest"):
        count = {"linear": 0, "full": 0}
        for l in range(cfg.num_layers):
            kind = cfg.layer_kinds[l % len(cfg.layer_kinds)]
            mp = jax.tree.map(
                lambda a: a[count[kind]].astype(f32), params[kind]
            )
            count[kind] += 1
            lp = jax.tree.map(lambda a: a[l].astype(f32), params["layers"])
            mixed = linear(x, mp) if kind == "linear" else full(x, mp)
            x = x + rms(mixed, lp["post_mixer_norm"])
            y = (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]
            x = x + rms(y, lp["post_ffn_norm"])
        x = rms(x, params["final_norm"])
        return x @ params["lm_head"].astype(f32)
