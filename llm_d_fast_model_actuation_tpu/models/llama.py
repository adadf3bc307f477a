"""Llama-family decoder (Llama 2/3, TinyLlama, Qwen2-style GQA) — TPU-first.

Design choices for the TPU/XLA compilation model:
  * layer params are **stacked** on a leading layer axis and the forward is a
    single ``lax.scan`` over layers — one compiled layer body regardless of
    depth (compile time O(1) in layers, the win that matters for wake-up);
  * the paged KV cache is one array for all layers
    (engine/kv_cache.py:PagePool.pool_shape) that the scan carries WHOLE and
    indexes by layer — ``pool.at[layer, page, slot]`` writes, ``pool[layer,
    page_table]`` reads — so cache updates are in-place (donated) scatters
    and no layer of it is ever sliced out or stacked back;
  * all matmuls bf16 on the MXU, softmax/norm math fp32;
  * tensor-parallel sharding is expressed via logical axes only
    (`param_logical_axes`); GSPMD inserts the all-reduces.

The flagship config mirrors Llama-3-8B (the reference's north-star model for
wake_up->TTFT, BASELINE.md).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import (
    SUFFIX_Q_BLOCK,
    causal_prefill_attention,
    paged_decode_attention,
    paged_decode_attention_inline,
    ragged_paged_attention,
)
from ..ops.norm import rms_norm
from ..ops.rope import apply_rope, rope_table
from .quant import qmat


@dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 14336
    rope_theta: float = 500000.0
    #: HF-style rope_scaling as a hashable tuple (ops/rope.py):
    #: ("linear", factor) or ("llama3", factor, low_ff, high_ff, orig_max).
    #: None = plain RoPE.
    rope_scaling: Any = None
    rms_eps: float = 1e-5
    max_seq_len: int = 8192
    dtype: Any = jnp.bfloat16
    tie_embeddings: bool = False
    #: Attention implementation ("reference" | "pallas"); per-model so two
    #: engines in one process can't clobber each other's choice.
    attention_impl: str = "reference"
    #: Weight-only quantization: "" (bf16) or "int8" (W8A16 per-output-
    #: channel, models/quant.py) — halves decode's weight-read bytes.
    quantization: str = ""
    # -- Gemma-family knobs (llama-neutral defaults; one shared forward) --
    #: MLP gate activation: "silu" (llama/mixtral) or "gelu" (gemma GeGLU)
    hidden_activation: str = "silu"
    #: RMSNorm weight offset: 0.0 (llama) or 1.0 (gemma's (1+w) convention)
    norm_offset: float = 0.0
    #: sandwich norms: normalize attention/FFN outputs before the residual
    post_norms: bool = False
    #: scale embeddings by sqrt(hidden_size) (gemma)
    embed_scale: bool = False
    #: per-head RMSNorm on q and k before RoPE (gemma-3 style)
    qk_norm: bool = False
    #: biases on the q/k/v projections (Qwen2 convention)
    attn_bias: bool = False
    #: a looped stack (Ouro): the SAME ``num_layers`` layers applied this
    #: many times a token, the final norm closing every pass and opening the
    #: next; pass u's layer l keeps its own K and V, cache layer
    #: ``u * num_layers + l`` (:attr:`cache_layers`). 1 = the stack once.
    loop_steps: int = 1
    #: the looped family's published exit threshold: a token leaves the loop
    #: once its gate's cumulated exit probability reaches it. At 1 no token
    #: leaves early; a step whose depth depends on the data is not built.
    early_exit_threshold: float = 1.0

    def __post_init__(self) -> None:
        if self.loop_steps < 1:
            raise ValueError(f"loop_steps {self.loop_steps}: at least one pass")
        if self.early_exit_threshold < 1:
            raise ValueError(
                f"early_exit_threshold {self.early_exit_threshold}: every "
                "token takes all loop_steps passes here; adaptive exit below "
                "a threshold of 1 is not built"
            )
        if self.loop_steps > 1 and type(self) is not LlamaConfig:
            raise ValueError(
                f"{type(self).__name__}: loop_steps {self.loop_steps}; the "
                "looped stack is the dense family's"
            )

    @classmethod
    def tiny_gemma(cls, vocab: int = 256) -> "LlamaConfig":
        """Gemma-3-style tiny config: GeGLU, (1+w) norms, sandwich norms,
        scaled embeddings, QK-norm, tied embeddings."""
        base = cls.tiny(vocab)
        import dataclasses

        return dataclasses.replace(
            base,
            hidden_activation="gelu",
            norm_offset=1.0,
            post_norms=True,
            embed_scale=True,
            qk_norm=True,
            tie_embeddings=True,
        )

    @classmethod
    def gemma3_4b(cls) -> "LlamaConfig":
        return cls(
            vocab_size=262144,
            hidden_size=2560,
            num_layers=34,
            num_heads=8,
            num_kv_heads=4,
            head_dim=256,
            intermediate_size=10240,
            rope_theta=1e6,
            max_seq_len=32768,
            tie_embeddings=True,
            hidden_activation="gelu",
            norm_offset=1.0,
            post_norms=True,
            embed_scale=True,
            qk_norm=True,
        )

    @classmethod
    def ouro_2_6b(cls) -> "LlamaConfig":
        """Ouro-2.6B as published (ByteDance/Ouro-2.6B ``config.json``):
        48 layers applied four times a token, multi-head attention,
        sandwich norms. ``max_seq_len`` is a serving choice below the
        published 65,536 positions: a token holds 1.5 MiB of K and V."""
        return cls(
            vocab_size=49152,
            hidden_size=2048,
            num_layers=48,
            num_heads=16,
            num_kv_heads=16,
            head_dim=128,
            intermediate_size=5632,
            rope_theta=1e6,
            rms_eps=1e-6,
            max_seq_len=4096,
            post_norms=True,
            loop_steps=4,
        )

    @classmethod
    def tiny_ouro(cls, vocab: int = 256) -> "LlamaConfig":
        """CPU test size of the looped family: 3 layers x 3 passes."""
        import dataclasses

        return dataclasses.replace(
            cls.tiny(vocab), num_layers=3, num_kv_heads=4, rms_eps=1e-6,
            post_norms=True, loop_steps=3,
        )

    @classmethod
    def llama3_8b(cls) -> "LlamaConfig":
        return cls()

    @classmethod
    def llama3_70b(cls) -> "LlamaConfig":
        return cls(
            hidden_size=8192,
            num_layers=80,
            num_heads=64,
            num_kv_heads=8,
            intermediate_size=28672,
        )

    @classmethod
    def tiny(cls, vocab: int = 256) -> "LlamaConfig":
        """CPU-mesh test size."""
        return cls(
            vocab_size=vocab,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=128,
            rope_theta=10000.0,
            max_seq_len=128,
        )

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def cache_layers(self) -> int:
        """Layers of K and V a token holds: the ONE number every KV size in
        the engine comes from (engine/kv_cache.py). A looped stack keeps a
        layer for each (pass, layer)."""
        return self.loop_steps * self.num_layers

    def num_params(self) -> int:
        per_layer = (
            2 * self.hidden_size  # norms
            + self.hidden_size * self.q_dim
            + 2 * self.hidden_size * self.kv_dim
            + self.q_dim * self.hidden_size
            + 3 * self.hidden_size * self.intermediate_size
        )
        if self.post_norms:
            per_layer += 2 * self.hidden_size
        if self.qk_norm:
            per_layer += 2 * self.head_dim
        head = 0 if self.tie_embeddings else self.hidden_size * self.vocab_size
        # the looped family's exit gate, Linear(hidden -> 1) with bias
        gate = self.hidden_size + 1 if self.loop_steps > 1 else 0
        return (
            self.vocab_size * self.hidden_size
            + self.num_layers * per_layer
            + self.hidden_size
            + head
            + gate
        )


def init_params(key: jax.Array, cfg: LlamaConfig) -> Dict[str, Any]:
    """Random-init bf16 params (serving loads checkpoints; random init is for
    tests/benchmarks and shape-defining)."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    h, L = cfg.hidden_size, cfg.num_layers

    def norm_init(shape):
        # Gemma's (1+w) convention stores zero-centered weights: identity
        # norm is w=0 there, w=1 for the plain convention
        fill = 0.0 if cfg.norm_offset else 1.0
        return jnp.full(shape, fill, dtype=cfg.dtype)

    def dense_init(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 7)
    layers = {
        "attn_norm": norm_init((L, h)),
        "wq": dense_init(ks[0], (L, h, cfg.q_dim), h),
        "wk": dense_init(ks[1], (L, h, cfg.kv_dim), h),
        "wv": dense_init(ks[2], (L, h, cfg.kv_dim), h),
        "wo": dense_init(ks[3], (L, cfg.q_dim, h), cfg.q_dim),
        "mlp_norm": norm_init((L, h)),
        "w_gate": dense_init(ks[4], (L, h, cfg.intermediate_size), h),
        "w_up": dense_init(ks[5], (L, h, cfg.intermediate_size), h),
        "w_down": dense_init(ks[6], (L, cfg.intermediate_size, h), cfg.intermediate_size),
    }
    if cfg.post_norms:
        layers["post_attn_norm"] = norm_init((L, h))
        layers["post_ffn_norm"] = norm_init((L, h))
    if cfg.qk_norm:
        layers["q_norm"] = norm_init((L, cfg.head_dim))
        layers["k_norm"] = norm_init((L, cfg.head_dim))
    if cfg.attn_bias:
        layers["bq"] = jnp.zeros((L, cfg.q_dim), dtype=cfg.dtype)
        layers["bk"] = jnp.zeros((L, cfg.kv_dim), dtype=cfg.dtype)
        layers["bv"] = jnp.zeros((L, cfg.kv_dim), dtype=cfg.dtype)
    params = {
        "embed": dense_init(k_embed, (cfg.vocab_size, h), h),
        "layers": layers,
        "final_norm": norm_init((h,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (h, cfg.vocab_size), h)
    if cfg.loop_steps > 1:
        # the exit gate read on each pass's output. Held (sleep, swap and an
        # importer carry it) and never evaluated: at the threshold this
        # config admits (1) it changes no served token.
        params["early_exit_gate"] = {
            "w": dense_init(jax.random.fold_in(k_head, 1), (h, 1), h),
            "b": jnp.zeros((1,), dtype=cfg.dtype),
        }
    return params


def param_logical_axes(cfg: LlamaConfig) -> Dict[str, Any]:
    """Pytree of logical axis names matching `init_params`' structure."""
    layers = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "w_gate": ("layers", "embed", "mlp"),
        "w_up": ("layers", "embed", "mlp"),
        "w_down": ("layers", "mlp", "embed"),
    }
    if cfg.post_norms:
        layers["post_attn_norm"] = ("layers", "embed")
        layers["post_ffn_norm"] = ("layers", "embed")
    if cfg.qk_norm:
        layers["q_norm"] = ("layers", None)
        layers["k_norm"] = ("layers", None)
    if cfg.attn_bias:
        layers["bq"] = ("layers", "heads")
        layers["bk"] = ("layers", "kv_heads")
        layers["bv"] = ("layers", "kv_heads")
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    if cfg.loop_steps > 1:
        axes["early_exit_gate"] = {"w": ("embed", None), "b": (None,)}
    return axes


# -- forward -----------------------------------------------------------------


def _norm(cfg: "LlamaConfig", x, w):
    return rms_norm(x, w, cfg.rms_eps, offset=cfg.norm_offset)


def _post(cfg: "LlamaConfig", lp, name: str, y):
    """Sandwich (post) norm on a block output, when the family has them."""
    if cfg.post_norms:
        return _norm(cfg, y, lp[name])
    return y


def _embed_tokens(cfg: "LlamaConfig", params, tokens):
    x = params["embed"][tokens].astype(cfg.dtype)
    if cfg.embed_scale:
        x = x * jnp.asarray(cfg.hidden_size**0.5, cfg.dtype)
    return x


def _mlp(cfg, x, gate, up, down):
    g = qmat(x, gate)
    u = qmat(x, up)
    if cfg.hidden_activation == "gelu":
        a = jax.nn.gelu(g.astype(jnp.float32), approximate=True)
    else:
        a = jax.nn.silu(g.astype(jnp.float32))
    return qmat((a.astype(x.dtype) * u), down)


def _ffn(
    cfg: "LlamaConfig", lp, x, router_logits=None, layer=None, stacks=None,
    mesh=None,
):
    """Dense SwiGLU or an expert layer, by config family (models/moe.py:
    ``expert_ffn`` computes every expert for every token or only each
    token's own, by the config and by the rows ``x`` is traced with).
    ``router_logits``: a family whose router reads something else than
    ``x`` brings them; ``layer``: the STACK's layer index (not the cache
    layer); ``stacks``: the stacked layer parameters, whose expert matrices
    the grouped form reads whole; ``mesh``: the mesh the program runs on."""
    with jax.named_scope("ffn"):
        if getattr(cfg, "num_experts", 0) > 1:
            from .moe import expert_ffn

            return expert_ffn(cfg, lp, x, router_logits, layer, stacks, mesh)
        return _mlp(cfg, x, lp["w_gate"], lp["w_up"], lp["w_down"])


def _scanned_ffn(cfg: "LlamaConfig", params, lp, li, h, mesh):
    """The FFN block of a layer function of :func:`_scan_layers`: ``lp``
    the scanned slice of the stack, ``li`` the cache layer it was scanned
    with, which under ``loop_steps`` > 1 counts the passes too, where the
    stack's layer (what the whole expert stacks are indexed by) does not."""
    layer = li if cfg.loop_steps == 1 else li % cfg.num_layers
    y = _ffn(cfg, lp, h, layer=layer, stacks=params["layers"], mesh=mesh)
    return _post(cfg, lp, "post_ffn_norm", y)


def layer_pattern(cfg) -> "Tuple[Tuple[int, bool], ...] | None":
    """A family whose layers differ gives the period of its pattern as
    data: per layer, (attention window in tokens or 0 for the full causal
    mask, RoPE or no positional encoding). None: every layer is the one
    block of this file. (models/smallthinker.py is the patterned family.)"""
    windows = getattr(cfg, "window_pattern", None)
    if not windows:
        return None
    return tuple(zip(windows, cfg.rope_pattern))


def window_layers(cfg) -> Tuple[int, int]:
    """(number of sliding-window layers, their window in tokens): what the
    engine sizes the rings of its KV state from (engine/kv_cache.py:
    KVLayout). (0, 0) for a model whose every layer sees its whole context."""
    pattern = layer_pattern(cfg)
    if pattern is None:
        return 0, 0
    windows = [w for w, _ in pattern if w]
    if not windows:
        return 0, 0
    return len(windows) * (cfg.num_layers // len(pattern)), max(windows)


def recurrent_state(cfg) -> "Tuple[int, Tuple[int, ...], Tuple[int, ...]] | None":
    """A family with linear-attention layers keeps a third kind of sequence
    state (engine/kv_cache.py:KVLayout): (its layers, the shape of one
    layer's recurrent state for one slot, the shape of its convolution tail).
    None for a model whose every layer keeps K and V alone.
    (models/olmo_hybrid.py, models/kimi_linear.py and models/nemotron_h.py
    are those families.)"""
    return getattr(cfg, "recurrent_state", None)


def latent_cache(cfg) -> "Tuple[int, int] | None":
    """A family with latent attention keeps a fourth kind of sequence state
    (engine/kv_cache.py:KVLayout), the latent page, one array and no K and V:
    (the values a token of a layer holds as the algorithm counts them, the
    lanes its row is stored in). None for a model whose pages hold K and V.
    (models/kimi_linear.py is that family.)"""
    return getattr(cfg, "latent_cache", None)


def patterned(cfg):
    """The module whose forward carries a config whose layers are of more
    than one kind, on the trunk's signatures; None for the one block of this
    file. The config names it (``forward_module``, a module of this
    package): which kinds of sequence state a family keeps does not tell its
    forward, since two families keep recurrent state beside pages of K and
    V."""
    name = getattr(cfg, "forward_module", None)
    if name is None:
        return None
    import importlib

    return importlib.import_module(f"{__package__}.{name}")


def _project_qkv(
    cfg: LlamaConfig, lp, x, positions, cos_tab, sin_tab, rope: bool = True
):
    """x: [b, s, h] -> q [b,s,heads,hd], k/v [b,s,kvh,hd], roped (or,
    for a layer without positional encoding, as projected)."""
    b, s, _ = x.shape
    q, k, v = qmat(x, lp["wq"]), qmat(x, lp["wk"]), qmat(x, lp["wv"])
    if cfg.attn_bias:
        q = q + lp["bq"].astype(q.dtype)
        k = k + lp["bk"].astype(k.dtype)
        v = v + lp["bv"].astype(v.dtype)
    q = q.reshape(b, s, cfg.num_heads, cfg.head_dim)
    k = k.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
    if cfg.qk_norm:
        # per-head RMSNorm before RoPE (gemma-3 convention)
        q = rms_norm(q, lp["q_norm"], cfg.rms_eps, offset=cfg.norm_offset)
        k = rms_norm(k, lp["k_norm"], cfg.rms_eps, offset=cfg.norm_offset)
    if not rope:
        return q, k, v
    q = apply_rope(q, positions, cos_tab, sin_tab)
    k = apply_rope(k, positions, cos_tab, sin_tab)
    return q, k, v


# The writers below take the WHOLE pool [L, P, page_size, kvh * hd] and a
# layer index; what they fuse (the minor two axes of the new rows) is
# token-sized, and the scatter updates the carried pool in place.


def _scatter_prefill(
    pages, layer, new, page_table, positions, valid, page_size
):
    """Write prefill K or V [b,s,kvh,hd] into ``pages[layer]``.

    Invalid (padding) positions scatter to an out-of-bounds page -> dropped.
    """
    b, s = positions.shape
    num_pages = pages.shape[1]
    page_of = positions // page_size  # [b, s] logical page per token
    slot_of = positions % page_size
    phys = jnp.take_along_axis(page_table, page_of, axis=1)  # [b, s]
    phys = jnp.where(valid, phys, num_pages)
    return pages.at[layer, phys.reshape(-1), slot_of.reshape(-1)].set(
        new.reshape(b * s, -1), mode="drop"
    )


def _scatter_rows(
    pages, layer, new, page_table, row_slot, positions, page_size
):
    """Write a flat packed buffer's K or V [T, kvh, hd] into
    ``pages[layer]``: token t goes to its OWN sequence's page
    (``page_table[row_slot[t]]``) at its own position. Padding rows
    (``row_slot < 0``) scatter to an out-of-bounds page -> dropped."""
    num_pages = pages.shape[1]
    page_of = positions // page_size  # [T] logical page per token
    slot_of = positions % page_size
    safe = jnp.clip(row_slot, 0, page_table.shape[0] - 1)
    phys = page_table[safe, page_of]  # [T]
    phys = jnp.where(row_slot >= 0, phys, num_pages)
    return pages.at[layer, phys, slot_of].set(
        new.reshape(new.shape[0], -1), mode="drop"
    )


def _scatter_decode(pages, layer, new, page_table, positions, page_size):
    """Write one token's K or V [b,kvh,hd] at `positions` [b] of
    ``pages[layer]``."""
    page_of = positions // page_size
    slot_of = positions % page_size
    phys = jnp.take_along_axis(page_table, page_of[:, None], axis=1)[:, 0]
    return pages.at[layer, phys, slot_of].set(
        new.reshape(new.shape[0], -1), mode="drop"
    )


def _scan_layers(cfg: LlamaConfig, layer_fn, carry, params):
    """Scan ``layer_fn(carry, (layer_params, cache_layer))`` over the stacked
    layers and close with the final norm on ``carry[0]``, the hidden state
    (a carry that holds the whole pool keeps it beside). Returns (carry,
    ys), ys stacked by cache layer.

    A looped stack (``cfg.loop_steps`` > 1) scans the SAME layers
    ``loop_steps`` times, as one outer scan over the passes: pass u reads
    and writes cache layer ``u * num_layers + l``, and the final norm closes
    every pass, its output opening the next."""
    L = cfg.num_layers
    layers = jnp.arange(L, dtype=jnp.int32)

    def close(carry):
        return (_norm(cfg, carry[0], params["final_norm"]), *carry[1:])

    if cfg.loop_steps == 1:
        carry, ys = jax.lax.scan(layer_fn, carry, (params["layers"], layers))
        return close(carry), ys

    def one_pass(carry, u):
        carry, ys = jax.lax.scan(
            layer_fn, carry, (params["layers"], u * L + layers)
        )
        return close(carry), ys

    with jax.named_scope("loop"):
        carry, ys = jax.lax.scan(
            one_pass, carry, jnp.arange(cfg.loop_steps, dtype=jnp.int32)
        )
    return carry, jax.tree.map(lambda y: y.reshape(-1, *y.shape[2:]), ys)


# -- what the patterned forwards share (models/smallthinker.py,
# models/olmo_hybrid.py): a scan over PERIODS of layers whose body unrolls
# the period's layers statically, each kind with its own state ------------

def period_indices(cfg, period: int):
    """The period indices, the scan's xs. The body indexes the stacked
    parameters by layer itself: a layer's matrices as slices that fuse into
    the matmuls that read them."""
    return jnp.arange(cfg.num_layers // period, dtype=jnp.int32)


def lm_logits(cfg, params, x):
    """The final norm and the head, float32 logits."""
    x = _norm(cfg, x, params["final_norm"])
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return qmat(x, head).astype(jnp.float32)


def cold_segment(cfg, tokens, seq_lens, mesh):
    """(positions, valid, attend) of a cold first segment [b, s]: it attends
    over its own K and V (the flash kernel, with the layer's window)."""
    b, s = tokens.shape
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = positions < seq_lens[:, None]

    def attend(q, k, v, pools, table, layer, window):
        return causal_prefill_attention(
            q, k, v, seq_lens, impl=cfg.attention_impl, mesh=mesh,
            window=window,
        )

    return positions, valid, attend


def suffix_segment(tokens, start, suffix_lens):
    """(positions, valid, attend) of a later segment of a chunked prefill:
    it attends over the layer's own cache, into which the segment has just
    been written."""
    from ..ops.attention import paged_suffix_attention

    b, s = tokens.shape
    offs = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    positions = start[:, None] + offs
    valid = offs < suffix_lens[:, None]

    def attend(q, k, v, pools, table, layer, window):
        return paged_suffix_attention(
            q, pools[0], pools[1], table, start, layer, window=window,
            q_block=SUFFIX_Q_BLOCK,
        )

    return positions, valid, attend


def scatter_decode_rows(
    pool, rows, table, pos, active, page_size, first_layer: int = 0
):
    """The deferred write of a decode step, ONE scatter: ``rows``
    [layers of the pool in order ..., b, kvh, hd] at ``pos`` of ``table``;
    inactive rows go to the out-of-bounds page and are dropped. ``pos`` and
    ``active`` [b, n] with ``rows`` [..., b, n, kvh, hd]: n positions a slot
    (a verify step). ``first_layer``: the pool layer the first of ``rows``
    belongs to."""
    if pos.ndim == 2:
        n = pos.shape[1]
        table = jnp.repeat(table, n, axis=0)
        pos = pos.reshape(-1)
        active = None if active is None else active.reshape(-1)
    b = pos.shape[0]
    L = rows.size // (b * pool.shape[-1])
    phys = jnp.take_along_axis(
        table, (pos // page_size)[:, None], axis=1
    )[:, 0]
    if active is not None:
        phys = jnp.where(active, phys, pool.shape[1])
    li = jnp.broadcast_to(
        jnp.arange(first_layer, first_layer + L)[:, None], (L, b)
    ).reshape(-1)
    pi = jnp.broadcast_to(phys[None, :], (L, b)).reshape(-1)
    si = jnp.broadcast_to((pos % page_size)[None, :], (L, b)).reshape(-1)
    return pool.at[li, pi, si].set(
        rows.reshape(L * b, pool.shape[-1]), mode="drop"
    )


def prefill(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, s] int32, right-padded
    seq_lens: jnp.ndarray,  # [b] int32
    cache: Tuple[jnp.ndarray, jnp.ndarray],  # k/v pools [L, P, ps, kvh*hd]
    page_table: jnp.ndarray,  # [b, pages_per_seq] int32
    mesh=None,  # tp mesh: the pallas attention impl runs under shard_map
):
    """Prefill a batch of prompts, writing KV into the paged cache.

    Returns (logits [b, s, vocab], new_cache). The caller reads logits at
    seq_lens-1 to sample the first generated token.
    """
    family = patterned(cfg)
    if family is not None:
        return family.prefill(
            params, cfg, tokens, seq_lens, cache, page_table, mesh=mesh
        )
    b, s = tokens.shape
    page_size = cache[0].shape[2]
    cos_tab, sin_tab = rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    valid = positions < seq_lens[:, None]

    x = _embed_tokens(cfg, params, tokens)

    def layer(carry, scanned):
        x, kp, vp = carry
        lp, li = scanned
        h = _norm(cfg, x, lp["attn_norm"])
        with jax.named_scope("attn"):
            q, k, v = _project_qkv(cfg, lp, h, positions, cos_tab, sin_tab)
        with jax.named_scope("kv_write"):
            kp = _scatter_prefill(kp, li, k, page_table, positions, valid, page_size)
            vp = _scatter_prefill(vp, li, v, page_table, positions, valid, page_size)
        with jax.named_scope("attn"):
            attn = causal_prefill_attention(
                q, k, v, seq_lens, impl=cfg.attention_impl, mesh=mesh
            )
            x = x + _post(cfg, lp, "post_attn_norm", qmat(attn.reshape(b, s, cfg.q_dim), lp["wo"]))
        h = _norm(cfg, x, lp["mlp_norm"])
        x = x + _scanned_ffn(cfg, params, lp, li, h, mesh)
        return (x, kp, vp), None

    (x, *cache), _ = _scan_layers(cfg, layer, (x, *cache), params)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = qmat(x, head).astype(jnp.float32)
    return logits, tuple(cache)


def prefill_continue(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, s] int32 suffix tokens, right-padded
    start: jnp.ndarray,  # [b] int32 — absolute position of tokens[:, 0]
    suffix_lens: jnp.ndarray,  # [b] int32 — valid suffix length per row
    cache: Tuple[jnp.ndarray, jnp.ndarray],
    page_table: jnp.ndarray,  # [b, pages_per_seq] int32
    mesh=None,  # the mesh the program runs on (the expert layer's form)
):
    """Prefill a prompt SUFFIX against a cache whose first `start` tokens
    are already present (the prefix-caching hit path,
    engine/prefix_cache.py). Scatters only the suffix's KV; attention runs
    over the paged cache so suffix queries see the shared prefix.

    Returns (logits [b, s, vocab], new_cache); the caller samples at
    suffix_lens-1.
    """
    from ..ops.attention import paged_suffix_attention

    family = patterned(cfg)
    if family is not None:
        return family.prefill_continue(
            params, cfg, tokens, start, suffix_lens, cache, page_table
        )
    b, s = tokens.shape
    page_size = cache[0].shape[2]
    cos_tab, sin_tab = rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    positions = start[:, None] + jnp.broadcast_to(
        jnp.arange(s, dtype=jnp.int32), (b, s)
    )
    valid = jnp.arange(s, dtype=jnp.int32)[None, :] < suffix_lens[:, None]

    x = _embed_tokens(cfg, params, tokens)

    def layer(carry, scanned):
        x, kp, vp = carry
        lp, li = scanned
        h = _norm(cfg, x, lp["attn_norm"])
        with jax.named_scope("attn"):
            q, k, v = _project_qkv(cfg, lp, h, positions, cos_tab, sin_tab)
        with jax.named_scope("kv_write"):
            kp = _scatter_prefill(kp, li, k, page_table, positions, valid, page_size)
            vp = _scatter_prefill(vp, li, v, page_table, positions, valid, page_size)
        with jax.named_scope("attn"):
            attn = paged_suffix_attention(q, kp, vp, page_table, start, li)
            x = x + _post(cfg, lp, "post_attn_norm", qmat(attn.reshape(b, s, cfg.q_dim), lp["wo"]))
        h = _norm(cfg, x, lp["mlp_norm"])
        x = x + _scanned_ffn(cfg, params, lp, li, h, mesh)
        return (x, kp, vp), None

    (x, *cache), _ = _scan_layers(cfg, layer, (x, *cache), params)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = qmat(x, head).astype(jnp.float32)
    return logits, tuple(cache)


def mixed_step(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [T] int32 — flat packed token buffer
    row_slot: jnp.ndarray,  # [T] int32 — page_table row per token; -1 = pad
    positions: jnp.ndarray,  # [T] int32 — absolute position per token
    cache: Tuple[jnp.ndarray, jnp.ndarray],
    page_table: jnp.ndarray,  # [rows, pages_per_seq] int32
    mesh=None,  # tp mesh: the pallas ragged impl runs under shard_map
):
    """One token-packed mixed-batch step: prefill segments, suffix
    continuations, and decode steps for MANY sequences in one forward
    over a flat ``[token_budget]`` buffer (the packed serving path,
    engine/engine.py). Each token's KV is scattered into its own
    sequence's pages first, then ragged paged attention masks every row
    to its own sequence at positions <= its own — causal prefill, suffix
    continuation, and decode are all the same mask.

    ``page_table`` arrives already sliced to the step's KV width — the
    mixed program slices the device-resident full-width table with a
    static ``kv_pages_bucket`` bound before calling here (bit-exact:
    the dropped entries were hard-masked exact zeros for every row).
    Under a sharded mesh the gather/scatter and einsums GSPMD-partition
    over the kv_heads/heads shards; the ragged op routes per
    ops/attention.py:ragged_paged_attention — the pallas kernel runs under
    ``shard_map`` over ``mesh``'s tp axis, the XLA twin partitions
    without it.

    Returns (logits [T, vocab], new_cache); the caller gathers the rows
    that sample (each segment's last token / each decode row). Padding
    rows write nothing and produce garbage logits.
    """
    if patterned(cfg) is not None:
        raise NotImplementedError(
            f"{type(cfg).__name__}: the packed mixed_step path addresses "
            "sequence state as pages of K and V alone and has neither a ring "
            "for sliding-window layers, a slot's recurrent state, nor the "
            "latent pages of latent-attention layers; serve this model on "
            "the bucketed path"
        )
    (T,) = tokens.shape
    page_size = cache[0].shape[2]
    cos_tab, sin_tab = rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    x = _embed_tokens(cfg, params, tokens)  # [T, h]

    def layer(carry, scanned):
        x, kp, vp = carry
        lp, li = scanned
        h = _norm(cfg, x, lp["attn_norm"])
        with jax.named_scope("attn"):
            q, k, v = _project_qkv(
                cfg, lp, h[None], positions[None], cos_tab, sin_tab
            )
            q, k, v = q[0], k[0], v[0]  # [T, heads/kvh, hd]
        with jax.named_scope("kv_write"):
            kp = _scatter_rows(kp, li, k, page_table, row_slot, positions, page_size)
            vp = _scatter_rows(vp, li, v, page_table, row_slot, positions, page_size)
        with jax.named_scope("attn"):
            attn = ragged_paged_attention(
                q, kp, vp, page_table, row_slot, positions, li,
                impl=cfg.attention_impl, mesh=mesh,
            )
            x = x + _post(
                cfg, lp, "post_attn_norm",
                qmat(attn.reshape(T, cfg.q_dim), lp["wo"]),
            )
        h = _norm(cfg, x, lp["mlp_norm"])
        x = x + _scanned_ffn(cfg, params, lp, li, h, mesh)
        return (x, kp, vp), None

    (x, *cache), _ = _scan_layers(cfg, layer, (x, *cache), params)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = qmat(x, head).astype(jnp.float32)
    return logits, tuple(cache)


def decode_step(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b] int32 — the latest token per sequence
    positions: jnp.ndarray,  # [b] int32 — its position (seq_len - 1)
    cache: Tuple[jnp.ndarray, jnp.ndarray],
    page_table: jnp.ndarray,  # [b, pages_per_seq]
    active: "jnp.ndarray | None" = None,  # [b] bool; inactive rows write nothing
    mesh=None,  # tp mesh: the pallas attention impl runs under shard_map
):
    """One decode step for the whole running batch.

    Returns (logits [b, vocab], new_cache).

    Two cache-write strategies, selected by ``cfg.attention_impl``:
      * ``reference`` — scatter each layer's new K/V into the pool *before*
        attending (2 scatters x num_layers; the baseline semantics).
      * ``grouped`` / ``pallas`` — the serving fast path: attention reads the
        pool for positions < pos and takes the new token's K/V inline, so all
        layers' writes defer to ONE scatter after the layer scan (on TPU
        each XLA pool scatter costs far more than the bytes it writes). The
        scan closes over the whole pool and hands attention a layer index,
        so nothing pool-sized is produced per layer (PERF.md section 6, PR 26).

    ``active`` masks rows of a frozen slot (budget exhausted mid-chunk): their
    K/V writes drop (scatter to the out-of-bounds page) so replayed steps
    can't corrupt the cache; their logits are garbage the caller ignores.
    """
    family = patterned(cfg)
    if family is not None:
        return family.decode_step(
            params, cfg, tokens, positions, cache, page_table, active,
            mesh=mesh,
        )
    if cfg.attention_impl == "reference":
        return _decode_step_scatter_first(
            params, cfg, tokens, positions, cache, page_table, active, mesh
        )
    b = tokens.shape[0]
    k_pages, v_pages = cache
    page_size = k_pages.shape[2]
    num_pages = k_pages.shape[1]
    cos_tab, sin_tab = rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )

    x = _embed_tokens(cfg, params, tokens)  # [b, h]

    L = cfg.cache_layers

    def layer(carry, scanned):
        (x,), (lp, li) = carry, scanned
        h = _norm(cfg, x, lp["attn_norm"])
        with jax.named_scope("attn"):
            q, k, v = _project_qkv(
                cfg, lp, h[:, None, :], positions[:, None], cos_tab, sin_tab
            )
            q, k, v = q[:, 0], k[:, 0], v[:, 0]  # [b, heads/kvh, hd]
            attn = paged_decode_attention_inline(
                q, k_pages, v_pages, k, v, page_table, positions, li,
                impl=cfg.attention_impl, mesh=mesh,
            )
            x = x + _post(cfg, lp, "post_attn_norm", qmat(attn.reshape(b, cfg.q_dim), lp["wo"]))
        h = _norm(cfg, x, lp["mlp_norm"])
        x = x + _scanned_ffn(cfg, params, lp, li, h, mesh)
        return (x,), (k, v)

    (x,), (k_all, v_all) = _scan_layers(cfg, layer, (x,), params)
    # One scatter for all cache layers: k_all/v_all are [L, b, kvh, hd].
    with jax.named_scope("kv_write"):
        page_of = positions // page_size
        slot_of = positions % page_size
        phys = jnp.take_along_axis(page_table, page_of[:, None], axis=1)[:, 0]
        if active is not None:
            phys = jnp.where(active, phys, num_pages)  # drop inactive rows
        li = jnp.broadcast_to(jnp.arange(L)[:, None], (L, b)).reshape(-1)
        pi = jnp.broadcast_to(phys[None, :], (L, b)).reshape(-1)
        si = jnp.broadcast_to(slot_of[None, :], (L, b)).reshape(-1)
        flat = (L * b, cfg.kv_dim)
        new_k = k_pages.at[li, pi, si].set(k_all.reshape(flat), mode="drop")
        new_v = v_pages.at[li, pi, si].set(v_all.reshape(flat), mode="drop")

    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = qmat(x, head).astype(jnp.float32)
    return logits, (new_k, new_v)


def _decode_step_scatter_first(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    positions: jnp.ndarray,
    cache: Tuple[jnp.ndarray, jnp.ndarray],
    page_table: jnp.ndarray,
    active: "jnp.ndarray | None" = None,
    mesh=None,
):
    """The baseline decode step: per-layer scatter-then-attend."""
    b = tokens.shape[0]
    _, num_pages, page_size, _ = cache[0].shape
    cos_tab, sin_tab = rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )
    seq_lens = positions + 1
    table = page_table
    if active is not None:
        # Route inactive rows' writes to the out-of-bounds page (dropped);
        # masking the table also keeps their (ignored) reads harmless.
        table = jnp.where(active[:, None], page_table, num_pages)

    x = _embed_tokens(cfg, params, tokens)  # [b, h]

    def layer(carry, scanned):
        x, kp, vp = carry
        lp, li = scanned
        h = _norm(cfg, x, lp["attn_norm"])
        q, k, v = _project_qkv(
            cfg, lp, h[:, None, :], positions[:, None], cos_tab, sin_tab
        )
        q, k, v = q[:, 0], k[:, 0], v[:, 0]  # [b, heads/kvh, hd]
        with jax.named_scope("kv_write"):
            kp = _scatter_decode(kp, li, k, table, positions, page_size)
            vp = _scatter_decode(vp, li, v, table, positions, page_size)
        with jax.named_scope("attn"):
            attn = paged_decode_attention(
                q, kp, vp, page_table, seq_lens, li, impl=cfg.attention_impl
            )
            x = x + _post(cfg, lp, "post_attn_norm", qmat(attn.reshape(b, cfg.q_dim), lp["wo"]))
        h = _norm(cfg, x, lp["mlp_norm"])
        x = x + _scanned_ffn(cfg, params, lp, li, h, mesh)
        return (x, kp, vp), None

    (x, *cache), _ = _scan_layers(cfg, layer, (x, *cache), params)
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = qmat(x, head).astype(jnp.float32)
    return logits, tuple(cache)
