"""Nemotron-H (``model_type: nemotron_h``, nvidia; Nemotron-3-Super) — the
family whose layers are ONE sub-layer each: ``x <- x + Mixer_l(RMSNorm(x))``,
where the mixer of layer l is a Mamba-2 layer (``M``), an expert layer
(``E``) or an attention layer (``*``) as the config's pattern says, one
character a layer. The pattern is data and need not repeat (the published 88
layers have their attention layers 8 to 11 apart), so the layers are
unrolled in the trace, each reading its own slice of its kind's parameter
stack. Plain RMSNorm weights, no bias but the convolution's, a final norm and
an untied head. Per kind:

  * ``M``, Mamba-2 (state-space duality, arXiv:2405.21060). H heads of P
    channels (H x P = the inner width), G groups of N state channels, head h
    using group ``h // (H / G)``::

        [z | xBC | dt] = W_in u
        xBC = silu(conv4(xBC) + b)        depthwise, causal, x, B, C together
        dt = softplus(dt + dt_bias),  A = -exp(A_log)      a scalar a head
        h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t       [P, N] a head
        y_t = h_t C_t + D x_t
        out = W_out RMSNorm_G(y * silu(z))   the norm over each group's channels

    This is no delta rule (nothing of h is read back into its update), but
    its sequence state is PR 36's third kind all the same: h in float32 and
    the last 3 rows of ``xBC`` a slot (engine/kv_cache.py:KVLayout), stored
    ``[H, P, N]`` so that the 128-wide N is the minor axis. A prompt segment
    runs it chunkwise (:func:`ssd_chunk_scan`: a masked decay matrix and
    matmuls, no triangular solve), a decode step one step a slot
    (:func:`ssd_step`);
  * ``*``, attention: grouped queries over a few KV heads (32 over 2), NO
    positional encoding, K and V in the paged pool, whose layer index is
    the attention layer's ordinal;
  * ``E``, experts in a LATENT space: the router (sigmoid scores, a
    selection bias, the top k renormalised and scaled: models/moe.py:route)
    and the shared expert read the layer's input ``u``; the routed experts
    read ``W_down u``, are two matrices each with the squared ReLU between
    them, and their weighted sum goes through ``W_up``::

        out = W_up sum_{e in top k, e held} w_e W2_e relu(W1_e W_down u)^2
              + S2 relu(S1 u)^2

    The chip holds a share of the router's experts (``num_experts`` of
    ``router_width``: models/moe.py:routed_ffn for a prompt segment, each
    assignment a row of a grouped matmul; models/moe.py:held_dense_ffn for a
    decode batch, every held expert on every row). ``W_up`` is linear, so
    the shares' routed parts still add up to the whole layer's.

Parameters: ``layers`` (every layer's norm) and a stack a kind, by ordinal:
``mamba``, ``experts`` (the held experts' stacks ``[layers, held, ...]`` read
whole by the grouped matmul), ``attn``. The entry points keep the trunk's
signatures and ``llama.prefill`` / ``prefill_continue`` / ``decode_step``
hand the config here (``llama.patterned``: the config names this module).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.attention import paged_decode_attention_inline
from . import llama, moe
from . import olmo_hybrid as gdn
from .kimi_linear import SELECTION_BIAS_STD
from .quant import qmat

#: the kinds a pattern's characters name, as ``/v1/stats`` counts them
KINDS = {"M": "ssm", "E": "moe", "*": "attn"}
#: the parameter stack of each kind
STACKS = {"M": "mamba", "E": "experts", "*": "attn"}
#: the seeded convolution bias's standard deviation: non-zero, so that it is
#: exercised, and small beside the convolution's output (about one)
CONV_BIAS_STD = 0.1
_HI = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class NemotronHConfig(moe.MoeConfig):
    """``num_experts`` is what this chip HOLDS of ``router_width``;
    ``intermediate_size`` the routed experts' width, in the latent space."""

    #: (llama.patterned) the module of this package that is its forward
    forward_module = "nemotron_h"
    #: (engine.py:slot_state_kinds) what a refusal calls the recurrent layers
    recurrent_kind = "state-space"

    routed_experts: bool = True
    router_scoring: str = "sigmoid"
    expert_activation: str = "relu2"
    expert_gated: bool = False
    #: the chip's ridge, peak / bandwidth = 197e12 / 819e9 = 240 rows of
    #: bfloat16: up to there every held expert's matrices are read either
    #: way and the dense form over them is bound by those reads alone
    #: (models/moe.py:held_dense_ffn): the decode batch, the smallest buckets
    dense_max_rows: int = 240
    #: one character a layer: M (Mamba-2), E (experts), * (attention)
    layer_kinds: Tuple[str, ...] = tuple("MEMEMEM*EME")
    #: the width the routed experts work in, and the shared expert's own
    latent_size: int = 1024
    shared_intermediate_size: int = 5376
    mamba_heads: int = 128
    mamba_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    #: tokens of one chunk of the chunkwise recurrence
    chunk_size: int = 128

    def __post_init__(self) -> None:
        super().__post_init__()
        if set(self.layer_kinds) - set(KINDS) or not self.layer_kinds:
            raise ValueError(
                f"layer_kinds {self.layer_kinds}: each of {sorted(KINDS)}"
            )
        if len(self.layer_kinds) != self.num_layers:
            raise ValueError(
                f"{len(self.layer_kinds)} layer kinds for {self.num_layers} "
                "layers: the pattern names every layer"
            )
        if self.mamba_heads % self.ssm_groups:
            raise ValueError(
                f"{self.mamba_heads} Mamba heads are not whole groups of "
                f"{self.ssm_groups}"
            )
        if self.quantization:
            raise ValueError(
                "NemotronHConfig: weight quantization is not carried by "
                "this forward"
            )

    @classmethod
    def nemotron_3_super_120b_a12b(cls) -> "NemotronHConfig":
        """One chip's share of NVIDIA-Nemotron-3-Super-120B-A12B as published
        (nvidia ``config.json``): four chips share each layer, this one holds
        128 of the 512 routed experts and 32,768 of the 131,072 vocabulary
        rows, and the first 11 of the 88 layers (the whole model is 241 GB);
        ``max_seq_len`` is a serving choice below the published 262,144
        positions."""
        return cls(
            vocab_size=32768, hidden_size=4096, num_layers=11, num_heads=32,
            num_kv_heads=2, head_dim=128, intermediate_size=2688,
            rms_eps=1e-5, max_seq_len=4096, num_experts=128,
            experts_per_token=22, router_width=512, routed_scaling=5.0,
        )

    @classmethod
    def tiny_nemotron_h(cls, vocab: int = 256) -> "NemotronHConfig":
        """CPU test size: the published first 11 layers' pattern, 4 of 16
        experts held, head and state sizes that are no lane multiple; a
        decode batch of up to 4 slots takes the dense form over the held
        experts, every prefill bucket the grouped one."""
        return cls(
            vocab_size=vocab, hidden_size=64, num_layers=11, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=40, rms_eps=1e-5,
            max_seq_len=256, num_experts=4, experts_per_token=3,
            router_width=16, routed_scaling=5.0, dense_max_rows=4,
            latent_size=24,
            shared_intermediate_size=80, mamba_heads=8, mamba_head_dim=12,
            ssm_groups=2, ssm_state_size=24, chunk_size=16,
        )

    def _count(self, char: str) -> int:
        return self.layer_kinds.count(char)

    @property
    def mamba_layers(self) -> int:
        return self._count("M")

    @property
    def expert_layers(self) -> int:
        return self._count("E")

    @property
    def cache_layers(self) -> int:
        """Layers of K and V a token holds: the attention layers alone."""
        return self._count("*")

    @property
    def layer_kind_counts(self) -> Dict[str, int]:
        """(``/v1/stats.stack.layer_kinds``) layers of each kind."""
        return {name: self._count(char) for char, name in KINDS.items()}

    @property
    def expert_input_size(self) -> int:
        return self.latent_size

    @property
    def inner_size(self) -> int:
        """The Mamba mixer's inner width: every head's channels."""
        return self.mamba_heads * self.mamba_head_dim

    @property
    def bc_size(self) -> int:
        """Channels of B, and of C: every group's state channels."""
        return self.ssm_groups * self.ssm_state_size

    @property
    def conv_dim(self) -> int:
        """Channels of the convolution: x, B and C together."""
        return self.inner_size + 2 * self.bc_size

    @property
    def in_proj_size(self) -> int:
        """Columns of ``W_in``: z, xBC and dt."""
        return self.inner_size + self.conv_dim + self.mamba_heads

    @property
    def recurrent_state(self):
        """(llama.recurrent_state) the Mamba-2 layers, and the shapes a slot
        holds for one of them: h in float32 with the state channels minor,
        the convolution's tail in the model's dtype."""
        return (
            self.mamba_layers,
            (self.mamba_heads, self.mamba_head_dim, self.ssm_state_size),
            (self.conv_kernel - 1, self.conv_dim),
        )

    def num_params(self) -> int:
        h, H = self.hidden_size, self.mamba_heads
        mamba = (
            h * self.in_proj_size + (self.conv_kernel + 1) * self.conv_dim
            + 3 * H + self.inner_size + self.inner_size * h
        )
        attn = 2 * h * self.q_dim + 2 * h * self.kv_dim
        experts = (
            self.num_experts * 2 * self.latent_size * self.intermediate_size
            + 2 * h * self.shared_intermediate_size + 2 * h * self.latent_size
            + h * self.router_outputs + self.router_outputs
        )
        return (
            self.num_layers * h + self.mamba_layers * mamba
            + self.expert_layers * experts + self.cache_layers * attn
            + 2 * self.vocab_size * h + h
        )


# -- parameters -----------------------------------------------------------------


def _decay_init(cfg: NemotronHConfig):
    """(A_log, dt_bias) [Mamba layers, heads] float32, the same in every
    layer, by Mamba-2's own initialisation laid out over the heads: A from 1
    to 16 and softplus(dt_bias) log-uniform from ``time_step_min`` 0.001 to
    ``time_step_max`` 0.1 (both over ``time_step_floor``), so that a head's
    state forgets over tens to thousands of tokens."""
    H = cfg.mamba_heads
    a_log = np.log(np.linspace(1.0, 16.0, H))
    dt = np.exp(np.linspace(np.log(1e-3), np.log(1e-1), H))
    dt_bias = dt + np.log(-np.expm1(-dt))  # softplus^-1
    tile = lambda x: jnp.asarray(  # noqa: E731
        np.tile(x[None], (cfg.mamba_layers, 1)), jnp.float32
    )
    return tile(a_log), tile(dt_bias)


def init_params(key: jax.Array, cfg: NemotronHConfig) -> Dict[str, Any]:
    """Random-init parameters: normal(0, fan_in^-1/2) per tensor from
    ``split(key)``, cast to the model's dtype; norms at one, the
    convolution's bias normal(0, ``CONV_BIAS_STD``); A_log and dt_bias as
    :func:`_decay_init` gives them, D at one and the router's selection bias
    normal(0, ``SELECTION_BIAS_STD``), all four float32."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 16)
    h, f, L = cfg.hidden_size, cfg.intermediate_size, cfg.num_layers
    nm, ne, na = cfg.mamba_layers, cfg.expert_layers, cfg.cache_layers
    H, C, inner = cfg.mamba_heads, cfg.conv_dim, cfg.inner_size
    E, W, lat, fs = (
        cfg.num_experts, cfg.router_outputs, cfg.latent_size,
        cfg.shared_intermediate_size,
    )

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(cfg.dtype)

    ones = lambda shape: jnp.ones(shape, cfg.dtype)  # noqa: E731
    a_log, dt_bias = _decay_init(cfg)
    return {
        "embed": dense(k_embed, (cfg.vocab_size, h), h),
        "layers": {"norm": ones((L, h))},
        "mamba": {
            "w_in": dense(ks[0], (nm, h, cfg.in_proj_size), h),
            "conv": dense(ks[1], (nm, cfg.conv_kernel, C), cfg.conv_kernel),
            "conv_bias": (
                CONV_BIAS_STD * jax.random.normal(ks[2], (nm, C), jnp.float32)
            ).astype(cfg.dtype),
            "a_log": a_log,
            "dt_bias": dt_bias,
            "d": jnp.ones((nm, H), jnp.float32),
            "out_norm": ones((nm, inner)),
            "wo": dense(ks[3], (nm, inner, h), inner),
        },
        "experts": {
            "router": dense(ks[4], (ne, h, W), h),
            "router_bias": SELECTION_BIAS_STD * jax.random.normal(
                ks[5], (ne, W), jnp.float32
            ),
            "latent_down": dense(ks[6], (ne, h, lat), h),
            "latent_up": dense(ks[7], (ne, lat, h), lat),
            "w_up": dense(ks[8], (ne, E, lat, f), lat),
            "w_down": dense(ks[9], (ne, E, f, lat), f),
            "s_up": dense(ks[10], (ne, h, fs), h),
            "s_down": dense(ks[11], (ne, fs, h), fs),
        },
        "attn": {
            "wq": dense(ks[12], (na, h, cfg.q_dim), h),
            "wk": dense(ks[13], (na, h, cfg.kv_dim), h),
            "wv": dense(ks[14], (na, h, cfg.kv_dim), h),
            "wo": dense(ks[15], (na, cfg.q_dim, h), cfg.q_dim),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, cfg.vocab_size), h),
    }


def param_logical_axes(cfg: NemotronHConfig) -> Dict[str, Any]:
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


# -- the state-space recurrence -----------------------------------------------------


def ssd_chunk_scan(x, dt, g, B, C, h0, chunk: int):
    """The recurrence over a segment, chunkwise. x [b, s, H, P], dt and
    g = dt A = log of the decay [b, s, H], B and C [b, s, G, N] (head h reads
    group ``h // (H / G)``), all float32; h0 [b, H, P, N]. Returns
    (y [b, s, H, P] WITHOUT the ``D x`` term, h after the last row).

    With Gamma_i = exp(sum of the chunk's g up to i), h the state the chunk
    starts from and v_j = dt_j x_j::

        y_i = Gamma_i (h C_i) + sum_{j <= i} (Gamma_i / Gamma_j) (C_i . B_j) v_j
        h'  = Gamma_C h + sum_j (Gamma_C / Gamma_j) v_j (x) B_j

    The second term of y is a lower-triangular matrix a head,
    M = (C B^T, a group's) * decay, times V, computed for all chunks of the
    segment at once; only h goes chunk to chunk. Nothing is solved for: the
    update never reads h back. Every exponent is a difference of running
    sums that is never positive. A row with dt = 0 (hence g = 0: a padded
    one) changes nothing after it.
    """
    b, s, H, P = x.shape
    G, N = B.shape[2:]
    Hg = H // G
    Cn = min(chunk, s)
    n = s // Cn
    assert n * Cn == s, (s, Cn)

    def chunks(a):  # [b, s, k, ...] -> [n, b, k, Cn, ...]
        a = a.reshape(b, n, Cn, *a.shape[2:])
        return jnp.moveaxis(a, (1, 3), (0, 2))

    v = chunks(x * dt[..., None]).reshape(n, b, G, Hg, Cn, P)
    gc = jnp.cumsum(chunks(g), axis=-1).reshape(n, b, G, Hg, Cn)
    B, C = chunks(B), chunks(C)  # [n, b, G, Cn, N]
    lower = jnp.tril(jnp.ones((Cn, Cn), bool))
    # Gamma_i / Gamma_j for j <= i: never above one
    decay = jnp.exp(
        jnp.where(lower, gc[..., :, None] - gc[..., None, :], -jnp.inf)
    )
    cb = jnp.einsum("...in,...jn->...ij", C, B, precision=_HI)
    y_in = jnp.einsum(
        "...hij,...hjp->...hip", cb[..., None, :, :] * decay, v, precision=_HI
    )
    c_dec = jnp.exp(gc)  # Gamma_i, what the carried state has decayed by
    v_dec = v * jnp.exp(gc[..., -1:] - gc)[..., None]
    g_end = jnp.exp(gc[..., -1])[..., None, None]  # [n, b, G, Hg, 1, 1]

    def step(h, xs):  # h [b, G, Hg, P, N]
        y_in, c_dec, v_dec, g_end, B, C = xs
        y = jnp.einsum("...cn,...hpn->...hcp", C, h, precision=_HI)
        y = y_in + c_dec[..., None] * y
        h = g_end * h + jnp.einsum(
            "...hcp,...cn->...hpn", v_dec, B, precision=_HI
        )
        return h, y

    h, y = jax.lax.scan(
        step, h0.reshape(b, G, Hg, P, N), (y_in, c_dec, v_dec, g_end, B, C)
    )
    y = jnp.moveaxis(y.reshape(n, b, H, Cn, P), (0, 2), (1, 3))
    return y.reshape(b, s, H, P), h.reshape(b, H, P, N)


def ssd_step(x, dt, g, B, C, h):
    """One token: x [b, H, P], dt and g = dt A [b, H], B and C [b, G, N], h
    [b, H, P, N], all float32 -> (y [b, H, P] without the ``D x`` term, the
    new h). h is read once and written once: ``h_t C = exp(g) (h C) + dt x
    (B . C)`` comes from the pass over the OLD state that also writes the
    new one, not from a second pass over the new."""
    b, H, P, N = h.shape
    G = B.shape[1]
    h = h.reshape(b, G, H // G, P, N)
    decay = jnp.exp(g).reshape(b, G, H // G, 1)
    v = (x * dt[..., None]).reshape(b, G, H // G, P)
    Bg, Cg = B[:, :, None, None, :], C[:, :, None, None, :]
    hc = jnp.sum(h * Cg, axis=-1)  # [b, G, Hg, P]
    new = decay[..., None] * h + v[..., None] * Bg
    bc = jnp.sum(B * C, axis=-1)[:, :, None, None]
    y = decay * hc + v * bc
    return y.reshape(b, H, P), new.reshape(b, H, P, N)


def _in_proj(cfg, lp, u):
    """u [..., h] -> (z [..., inner], xBC [..., conv_dim] in the model's
    dtype, the raw dt [..., H] float32): one matrix, split by columns."""
    zxd = qmat(u, lp["w_in"])
    z, xbc, dt = jnp.split(
        zxd, [cfg.inner_size, cfg.inner_size + cfg.conv_dim], axis=-1
    )
    return z, xbc, dt.astype(jnp.float32)


def _ssm_inputs(cfg, lp, conv, dt_raw):
    """The convolution's output [..., conv_dim] float32 and the raw dt ->
    x [..., H, P], dt, g = dt A [..., H], B, C [..., G, N]."""
    lead = conv.shape[:-1]
    x, B, C = jnp.split(
        conv, [cfg.inner_size, cfg.inner_size + cfg.bc_size], axis=-1
    )
    dt = jax.nn.softplus(dt_raw + lp["dt_bias"])
    shape = (*lead, cfg.ssm_groups, cfg.ssm_state_size)
    return (
        x.reshape(*lead, cfg.mamba_heads, cfg.mamba_head_dim), dt,
        -jnp.exp(lp["a_log"]) * dt, B.reshape(shape), C.reshape(shape),
    )


def _mixer_out(cfg, lp, y, x, z):
    """W_out RMSNorm_G((y + D x) * silu(z)): y, x float32 [..., H, P], the
    norm over each group's channels, one weight a channel."""
    lead = y.shape[:-2]
    y = (y + lp["d"][:, None] * x).reshape(*lead, cfg.inner_size)
    y = y * jax.nn.silu(z.astype(jnp.float32))
    yg = y.reshape(*lead, cfg.ssm_groups, -1)
    yg = yg * jax.lax.rsqrt(
        jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_eps
    )
    y = yg.reshape(*lead, cfg.inner_size) * lp["out_norm"].astype(jnp.float32)
    return qmat(y.astype(z.dtype), lp["wo"])


# -- the expert layer -------------------------------------------------------------


def _relu2_mlp(x, up, down):
    """``down(relu(up x)^2)``: the two-matrix FFN of this family."""
    a = jnp.square(jax.nn.relu(qmat(x, up).astype(jnp.float32)))
    return qmat(a.astype(x.dtype), down)


def _experts(cfg, params, e: int, u):
    """Expert layer ``e`` (its ordinal) on ``u`` [..., h]: the shared expert
    on ``u`` itself plus ``W_up`` of this chip's share of the routed experts
    on ``W_down u``. A program of more than ``dense_max_rows`` rows sorts its
    assignments and the grouped matmul reads the stacks whole
    (models/moe.py:_grouped); a smaller one (the decode batch) computes every
    held expert on every row (models/moe.py:takes_grouped)."""
    grouped = moe.takes_grouped(
        cfg, math.prod(u.shape[:-1]), moe.stored_expert_stack(cfg, params)
    )
    whole = moe.expert_stacks(cfg) if grouped else ()
    ep = {
        k: a if k in whole else a[e] for k, a in params["experts"].items()
    }
    with jax.named_scope("ffn"):
        with jax.named_scope("moe.shared"):
            shared = _relu2_mlp(u, ep["s_up"], ep["s_down"])
        with jax.named_scope("moe.latent_down"):
            v = qmat(u, ep["latent_down"])
        with jax.named_scope("moe.share"):
            logits = jnp.einsum(
                "...h,he->...e", u, ep["router"],
                preferred_element_type=jnp.float32,
            )
            if grouped:
                routed = moe.routed_ffn(
                    cfg, ep, v, logits, layer=jnp.asarray(e, jnp.int32)
                )
            else:
                routed = moe.held_dense_ffn(cfg, ep, v, logits)
        with jax.named_scope("moe.latent_up"):
            return shared + qmat(routed, ep["latent_up"])


# -- the layers -----------------------------------------------------------------------


def _plan(cfg: NemotronHConfig):
    """Per layer: (its character, its ordinal among the layers of its kind)."""
    seen = dict.fromkeys(KINDS, 0)
    plan = []
    for char in cfg.layer_kinds:
        plan.append((char, seen[char]))
        seen[char] += 1
    return tuple(plan)


def _stack(params, name: str, i: int):
    """Layer ``i`` of the stack ``name``, as slices that fuse into the
    matmuls that read them."""
    return {k: a[i] for k, a in params[name].items()}


def _segment(
    params, cfg, tokens, positions, valid, lens, fresh, cache, page_table,
    attend,
):
    """The forward of one prefill segment [b, s] shared by the cold and the
    continued program. ``lens`` [b]: the rows' valid tokens; ``fresh`` [b]:
    the row starts its sequence, from h = 0 and a zero tail, whatever its
    slot holds; every other row resumes from its slot's state."""
    b, s = tokens.shape
    f32 = jnp.float32
    kp, vp, state, tail = cache
    gtable, slots = page_table[:, :-1], page_table[:, -1]
    page_size = kp.shape[2]
    K1 = cfg.conv_kernel - 1
    x = llama._embed_tokens(cfg, params, tokens)
    keep = valid[..., None]

    def mamba(u, lp, li, state, tail):
        z, xbc, dt_raw = _in_proj(cfg, lp, u)
        with jax.named_scope("ssm.conv"):
            ext = jnp.concatenate(
                [gdn.load_slots(tail, li, slots, fresh, xbc.dtype), xbc], axis=1
            )
            xs, dt, g, B, C = _ssm_inputs(
                cfg, lp, gdn._conv(lp, ext, s), dt_raw
            )
            # the last K - 1 inputs of the valid rows: ext row len + i
            new_tail = jax.vmap(
                lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, K1, 0)
            )(ext, lens)
        with jax.named_scope("ssm.chunk_scan"):
            # a padded row leaves the state alone: dt = 0, decay 1
            dt = jnp.where(keep, dt, 0.0)
            y, h = ssd_chunk_scan(
                xs, dt, jnp.where(keep, g, 0.0), B, C,
                gdn.load_slots(state, li, slots, fresh, f32), cfg.chunk_size,
            )
        return (
            _mixer_out(cfg, lp, y, xs, z),
            gdn.store_slots(state, li, slots, h),
            gdn.store_slots(tail, li, slots, new_tail),
        )

    for layer, (char, li) in enumerate(_plan(cfg)):
        u = llama._norm(cfg, x, params["layers"]["norm"][layer])
        if char == "M":
            with jax.named_scope("ssm"):
                mixed, state, tail = mamba(
                    u, _stack(params, "mamba", li), li, state, tail
                )
        elif char == "E":
            mixed = _experts(cfg, params, li, u)
        else:
            ap = _stack(params, "attn", li)
            with jax.named_scope("attn.global"):
                q, k, v = llama._project_qkv(
                    cfg, ap, u, positions, None, None, rope=False
                )
            with jax.named_scope("kv_write"):
                kp = llama._scatter_prefill(
                    kp, li, k, gtable, positions, valid, page_size)
                vp = llama._scatter_prefill(
                    vp, li, v, gtable, positions, valid, page_size)
            with jax.named_scope("attn.global"):
                attn = attend(q, k, v, (kp, vp), gtable, li, 0)
                mixed = qmat(attn.reshape(b, s, cfg.q_dim), ap["wo"])
        x = x + mixed
    return llama.lm_logits(cfg, params, x), (kp, vp, state, tail)


def prefill(params, cfg, tokens, seq_lens, cache, page_table, mesh=None):
    """``llama.prefill`` for this family: a cold first segment. The attention
    layers attend over the segment's own K and V; the Mamba-2 layers start
    from zero."""
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
    segment at ``start`` 0 is the first, and starts from zero); the
    attention layers attend over the sequence's pages."""
    positions, valid, attend = llama.suffix_segment(tokens, start, suffix_lens)
    return _segment(
        params, cfg, tokens, positions, valid, suffix_lens, start == 0,
        cache, page_table, attend,
    )


def decode_step(
    params, cfg, tokens, positions, cache, page_table, active=None, mesh=None
):
    """``llama.decode_step`` for this family. Row i of the decode batch IS
    slot i (the engine's table has a row a slot), so a Mamba-2 layer reads
    and writes its layer of the state whole and in place; an inactive row's
    state and tail stay as they are. The attention layers take the deferred
    write: attention reads the pool for positions before the token's, and
    ONE scatter a direction after the layers writes every attention layer's
    new row."""
    b = tokens.shape[0]
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
    new_k, new_v = [], []

    def mamba(u, lp, li, state, tail):
        z, xbc, dt_raw = _in_proj(cfg, lp, u)
        old_tail, h = tail[li], state[li]
        with jax.named_scope("ssm.conv"):
            ext = jnp.concatenate([old_tail, xbc[:, None]], axis=1)
            xs, dt, g, B, C = _ssm_inputs(
                cfg, lp, gdn._conv(lp, ext, 1)[:, 0], dt_raw
            )
        with jax.named_scope("ssm.step"):
            y, h_new = ssd_step(xs, dt, g, B, C, h)
            h_new = jnp.where(live[:, None, None, None], h_new, h)
        new_tail = jnp.where(live[:, None, None], ext[:, 1:], old_tail)
        return (
            _mixer_out(cfg, lp, y, xs, z), state.at[li].set(h_new),
            tail.at[li].set(new_tail),
        )

    for layer, (char, li) in enumerate(_plan(cfg)):
        u = llama._norm(cfg, x, params["layers"]["norm"][layer])
        if char == "M":
            with jax.named_scope("ssm"):
                mixed, state, tail = mamba(
                    u, _stack(params, "mamba", li), li, state, tail
                )
        elif char == "E":
            mixed = _experts(cfg, params, li, u)
        else:
            ap = _stack(params, "attn", li)
            with jax.named_scope("attn.global"):
                q, k, v = llama._project_qkv(
                    cfg, ap, u[:, None, :], None, None, None, rope=False
                )
                q, k, v = q[:, 0], k[:, 0], v[:, 0]
                attn = paged_decode_attention_inline(
                    q, kp, vp, k, v, gtable, positions, li,
                    impl=cfg.attention_impl, mesh=mesh,
                )
                mixed = qmat(attn.reshape(b, cfg.q_dim), ap["wo"])
            new_k.append(k)
            new_v.append(v)
        x = x + mixed
    with jax.named_scope("kv_write"):
        # rows: [attention layers, b, kvh, hd], in the pool's layer order
        kp = llama.scatter_decode_rows(
            kp, jnp.stack(new_k), gtable, positions, active, page_size)
        vp = llama.scatter_decode_rows(
            vp, jnp.stack(new_v), gtable, positions, active, page_size)
    return llama.lm_logits(cfg, params, x), (kp, vp, state, tail)


# -- the plain reference ---------------------------------------------------------


def reference_logits(
    params: Dict[str, Any], cfg: NemotronHConfig, tokens: jnp.ndarray,
    share: "int | None" = None,
) -> jnp.ndarray:
    """The repo's plain reference of this family: float32, ``highest``, no
    cache, no chunks, the recurrence TOKEN BY TOKEN exactly as the module
    docstring writes it (a ``lax.scan`` over positions), the attention mask
    written out, every expert that ``params`` holds computed densely and
    weighted by the router's top k. tokens [s] -> logits [s, vocab].
    ``share``: the experts in ``params`` are the share of that index (None:
    the config's); with ``num_experts`` == ``router_outputs`` it is the uncut
    layer."""
    f32 = jnp.float32
    s = tokens.shape[0]
    pos = jnp.arange(s)
    H, P, G, N, K = (
        cfg.mamba_heads, cfg.mamba_head_dim, cfg.ssm_groups,
        cfg.ssm_state_size, cfg.conv_kernel,
    )
    share = cfg.share_index if share is None else share

    def rms(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + cfg.rms_eps) * w.astype(f32)

    relu2 = lambda a: jnp.square(jax.nn.relu(a))  # noqa: E731

    def mamba(u, lp):
        z, xbc, dt = jnp.split(
            u @ lp["w_in"], [cfg.inner_size, cfg.inner_size + cfg.conv_dim],
            axis=-1,
        )
        ext = jnp.concatenate([jnp.zeros((K - 1, xbc.shape[1]), f32), xbc])
        c = jax.nn.silu(
            sum(lp["conv"][i] * ext[i : i + s] for i in range(K))
            + lp["conv_bias"]
        )
        x, B, C = jnp.split(
            c, [cfg.inner_size, cfg.inner_size + cfg.bc_size], axis=-1
        )
        x = x.reshape(s, H, P)
        # head h reads group h // (H / G)
        B = jnp.repeat(B.reshape(s, G, N), H // G, axis=1)
        C = jnp.repeat(C.reshape(s, G, N), H // G, axis=1)
        dt = jax.nn.softplus(dt + lp["dt_bias"])
        A = -jnp.exp(lp["a_log"])

        def token(h, t):  # h [H, P, N]
            x, B, C, dt = t
            h = jnp.exp(dt * A)[:, None, None] * h + (
                dt[:, None, None] * x[:, :, None] * B[:, None, :]
            )
            return h, jnp.einsum("hpn,hn->hp", h, C) + lp["d"][:, None] * x

        _, y = jax.lax.scan(token, jnp.zeros((H, P, N), f32), (x, B, C, dt))
        y = y.reshape(s, H * P) * jax.nn.silu(z)
        yg = y.reshape(s, G, -1)
        yg = yg / jnp.sqrt(jnp.mean(yg * yg, axis=-1, keepdims=True) + cfg.rms_eps)
        return (yg.reshape(s, H * P) * lp["out_norm"]) @ lp["wo"]

    def attention(u, ap):
        heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        q = (u @ ap["wq"]).reshape(s, kvh, heads // kvh, hd) * hd**-0.5
        k = (u @ ap["wk"]).reshape(s, kvh, hd)
        v = (u @ ap["wv"]).reshape(s, kvh, hd)
        scores = jnp.einsum("skgd,tkd->kgst", q, k)
        mask = pos[None, :] <= pos[:, None]
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return attn.reshape(s, heads * hd) @ ap["wo"]

    def experts(u, ep):
        w, idx = moe.route(cfg, u @ ep["router"], ep["router_bias"])
        weights = jnp.sum(
            jax.nn.one_hot(idx, cfg.router_outputs, dtype=f32) * w[..., None], axis=-2
        )  # [s, router width], zero off the top k
        held = jax.lax.dynamic_slice_in_dim(
            weights, share * cfg.num_experts, cfg.num_experts, axis=1
        )
        v = u @ ep["latent_down"]
        a = relu2(jnp.einsum("sl,elf->sef", v, ep["w_up"]))
        y = jnp.einsum("sef,efl->sel", a, ep["w_down"])
        routed = jnp.einsum("sel,se->sl", y, held) @ ep["latent_up"]
        return relu2(u @ ep["s_up"]) @ ep["s_down"] + routed

    mixers = {"M": mamba, "E": experts, "*": attention}
    x = params["embed"][tokens].astype(f32)
    with jax.default_matmul_precision("highest"):
        for layer, (char, li) in enumerate(_plan(cfg)):
            mp = jax.tree.map(
                lambda a: a[li].astype(f32), params[STACKS[char]]
            )
            u = rms(x, params["layers"]["norm"][layer])
            x = x + mixers[char](u, mp)
        x = rms(x, params["final_norm"])
        return x @ params["lm_head"].astype(f32)
