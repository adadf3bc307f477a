"""K-EXAONE (``model_type: exaone_moe``, LGAI-EXAONE) — a patterned family
with a multi-token-prediction module: periods of three sliding-window layers
(rotate-half RoPE) and one full-attention layer WITHOUT positional encoding,
a leading dense FFN and sigmoid-routed experts with a shared expert after
it, of which one chip holds a share, and ONE further block that drafts the
token after next from the main stack's output.

The block is EXAONE 4.0's (arXiv:2507.11407; ``transformers``'
``modeling_exaone4.py``): norms on each sub-layer's OUTPUT and none on its
input, ``h = x + RMSNorm_a(Attn(x))``, ``y = h + RMSNorm_f(FFN(h))``; q and k
RMS-normalised over a head's width with a learned weight before any rotary;
no bias; a final RMSNorm before the untied head. The router is DeepSeek-V3's
(models/moe.py:route: sigmoid scores, a selection bias that chooses and does
not weigh, the kept scores over their sum times ``routed_scaling``).

The forward is models/smallthinker.py's: its period scan, rings beside pages
(engine/kv_cache.py:KVLayout), and deferred decode writes, handed this
family's :data:`BLOCK` (where the norms sit, the FFN by layer, every
period traced by itself). The FFN is models/kimi_linear.py's.

The prediction module (``num_nextn_predict_layers`` 1; DeepSeek-V3,
arXiv:2412.19437 section 2.2): with ``h_i`` the main stack's output at
position i BEFORE the final norm and ``t_{i+1}`` the next token::

    u_i = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]     (2h -> h)
    z   = Block_mtp(u)        one full-attention block with experts,
                              its own K and V
    logits^mtp_i = Head(RMSNorm_m(z_i))        which predicts t_{i+2}

Embedding and head are the main model's. The module's block is the LAST
entry of the ``layers`` and ``experts`` stacks (layer ``num_layers``) and,
where it is served (``serve_mtp``, the engine's ``--speculative-mtp``), its K
and V are the last layer of the pages: the layer functions of
models/smallthinker.py take it as one more full-attention layer.

Serving it (engine/engine.py:ProgramSet, the decode chunk): a slot holds a
draft ``d`` of the token after its last. One step runs the main stack over
the two positions ``[last, d]`` (:func:`verify_step`: the decode path, a
slot's pages and ring read once for both), keeps the second position's token
iff the first's is ``d``, and runs the module over the one or two new
positions (:func:`draft_step`) for the next draft. The prompt programs run
the module over the prompt shifted by one (:func:`draft_segment`), so that
its pages are whole and the first step has a draft.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from ..ops.norm import rms_norm
from ..ops.rope import rope_table
from . import kimi_linear, llama, moe
from . import smallthinker as trunk
from .kimi_linear import SELECTION_BIAS_STD
from .quant import qmat


@dataclass(frozen=True)
class ExaoneMoeConfig(moe.MoeConfig):
    """``num_experts`` is what this chip HOLDS of ``router_width``;
    ``intermediate_size`` the experts' width; ``num_layers`` the main stack's
    layers, the prediction module's block not among them."""

    #: (llama.patterned) the module of this package that is its forward
    forward_module = "exaone_moe"

    routed_experts: bool = True
    router_scoring: str = "sigmoid"
    #: programs of this many rows or fewer (the decode batch, at two rows a
    #: slot too) compute every held expert on every row: 96 rows x 8 of 128
    #: touch every one of 8 held experts, so every matrix is read either way
    #: (models/moe.py:held_dense_ffn)
    dense_max_rows: int = 240
    qk_norm: bool = True
    #: per layer of one period: attention window in tokens, 0 = full causal
    window_pattern: Tuple[int, ...] = (128, 128, 128, 0)
    #: per layer of one period: rotate-half RoPE, or no positional encoding
    rope_pattern: Tuple[bool, ...] = (True, True, True, False)
    #: leading layers whose FFN is dense, and its width
    first_dense: int = 1
    dense_intermediate_size: int = 18432
    shared_experts: int = 1
    #: blocks of the multi-token-prediction module the weights hold (0 or 1)
    nextn_layers: int = 1
    #: the engine serves the module (``--speculative-mtp 1``): its K and V
    #: are a layer of the pages
    serve_mtp: bool = False

    def __post_init__(self) -> None:
        super().__post_init__()
        period = len(self.window_pattern)
        if len(self.rope_pattern) != period:
            raise ValueError("window_pattern and rope_pattern differ in length")
        if self.num_layers % period:
            raise ValueError(
                f"{self.num_layers} layers are not whole periods of {period}"
            )
        if len({w for w in self.window_pattern if w}) > 1:
            raise ValueError("window layers of one model share one window")
        if not 0 < self.first_dense <= period:
            raise ValueError(
                f"first_dense {self.first_dense}: the dense layers lie in "
                "the first period, which is traced by itself"
            )
        if self.nextn_layers not in (0, 1):
            raise ValueError(
                f"nextn_layers {self.nextn_layers}: one prediction module, "
                "or none"
            )
        if self.serve_mtp and not self.nextn_layers:
            raise ValueError("serve_mtp: this model has no prediction module")
        if self.quantization:
            raise ValueError(
                "ExaoneMoeConfig: weight quantization is not carried by "
                "this forward"
            )

    @classmethod
    def k_exaone_236b_a23b(cls) -> "ExaoneMoeConfig":
        """One chip's share of K-EXAONE-236B-A23B as published (LGAI-EXAONE
        ``config.json``): a v5e-64 as 4 stages of 16 chips, this one holds 8
        of the 128 routed experts, 19,200 of the 153,600 vocabulary rows and
        the first 8 of the 48 layers (the whole model is 473 GB), and the
        prediction module; ``max_seq_len`` is a serving choice below the
        published 262,144 positions."""
        return cls(
            vocab_size=19200, hidden_size=6144, num_layers=8, num_heads=64,
            num_kv_heads=8, head_dim=128, intermediate_size=2048,
            rope_theta=1e6, rms_eps=1e-5, max_seq_len=4096, num_experts=8,
            experts_per_token=8, router_width=128, routed_scaling=2.5,
        )

    @classmethod
    def tiny_exaone_moe(cls, vocab: int = 256) -> "ExaoneMoeConfig":
        """CPU test size: two periods, a window shorter than the contexts, 4
        of 16 experts held."""
        return cls(
            vocab_size=vocab, hidden_size=64, num_layers=8, num_heads=4,
            num_kv_heads=2, head_dim=16, intermediate_size=32,
            rope_theta=10000.0, rms_eps=1e-5, max_seq_len=256, num_experts=4,
            experts_per_token=3, router_width=16, routed_scaling=2.5,
            dense_intermediate_size=96, dense_max_rows=4,
            window_pattern=(24, 24, 24, 0),
        )

    @property
    def periods(self) -> int:
        return self.num_layers // len(self.window_pattern)

    @property
    def expert_layers(self) -> int:
        """The main stack's layers with experts."""
        return self.num_layers - self.first_dense

    @property
    def cache_layers(self) -> int:
        """Layers of K and V a token holds: the main stack's, and the
        prediction module's where it is served."""
        return self.num_layers + (self.nextn_layers if self.serve_mtp else 0)

    def num_params(self) -> int:
        h, f = self.hidden_size, self.intermediate_size
        blocks = self.num_layers + self.nextn_layers
        attn = (
            h * self.q_dim + 2 * h * self.kv_dim + self.q_dim * h
            + 2 * self.head_dim + 2 * h
        )
        ffn = (
            (self.num_experts + self.shared_experts) * 3 * h * f
            + h * self.router_outputs + self.router_outputs
        )
        return (
            blocks * attn
            + self.first_dense * 3 * h * self.dense_intermediate_size
            + (blocks - self.first_dense) * ffn
            + self.nextn_layers * (2 * h * h + 3 * h)
            + 2 * self.vocab_size * h + h
        )


# -- parameters -----------------------------------------------------------------


def init_params(key: jax.Array, cfg: ExaoneMoeConfig) -> Dict[str, Any]:
    """Random-init parameters: normal(0, fan_in^-1/2) per tensor from
    ``split(key)``, cast to the model's dtype; norms at one; the router's
    selection bias normal(0, ``SELECTION_BIAS_STD``) in float32. The
    prediction module's block is the last entry of ``layers`` and
    ``experts``. ``wq`` and ``wk`` are stored [layers, out, in]: a decode
    step's few rows contract over the minor axis of both, and stored [in,
    out] the chip's compiler copied both stacks into that layout once a
    chunk (0.97 GB of temps at the published widths, PERF.md section 6,
    PR 47); :func:`_layer_params` hands the forward their transposes, which
    fold into the matmuls."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    ks = jax.random.split(k_layers, 16)
    h, f = cfg.hidden_size, cfg.intermediate_size
    L = cfg.num_layers + cfg.nextn_layers
    ne, nd = L - cfg.first_dense, cfg.first_dense
    E, W, fs = cfg.num_experts, cfg.router_outputs, cfg.shared_experts * f
    fd = cfg.dense_intermediate_size

    def dense(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(cfg.dtype)

    ones = lambda shape: jnp.ones(shape, cfg.dtype)  # noqa: E731
    params = {
        "embed": dense(k_embed, (cfg.vocab_size, h), h),
        "layers": {
            "wq": dense(ks[0], (L, cfg.q_dim, h), h),
            "wk": dense(ks[1], (L, cfg.kv_dim, h), h),
            "wv": dense(ks[2], (L, h, cfg.kv_dim), h),
            "wo": dense(ks[3], (L, cfg.q_dim, h), cfg.q_dim),
            "q_norm": ones((L, cfg.head_dim)),
            "k_norm": ones((L, cfg.head_dim)),
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
            "router_bias": SELECTION_BIAS_STD * jax.random.normal(
                ks[8], (ne, W), jnp.float32
            ),
            "w_gate": dense(ks[9], (ne, E, h, f), h),
            "w_up": dense(ks[10], (ne, E, h, f), h),
            "w_down": dense(ks[11], (ne, E, f, h), f),
            "s_gate": dense(ks[12], (ne, h, fs), h),
            "s_up": dense(ks[13], (ne, h, fs), h),
            "s_down": dense(ks[14], (ne, fs, h), fs),
        },
        "final_norm": ones((h,)),
        "lm_head": dense(k_head, (h, cfg.vocab_size), h),
    }
    if cfg.nextn_layers:
        params["mtp"] = {
            "w_eh": dense(ks[15], (2 * h, h), 2 * h),
            "e_norm": ones((h,)),
            "h_norm": ones((h,)),
            "final_norm": ones((h,)),
        }
    return params


def param_logical_axes(cfg: ExaoneMoeConfig) -> Dict[str, Any]:
    """The embedding and the head by the trunk's rules; every stack
    replicated (the share of the experts a chip holds is the config's, not
    a mesh's: ROADMAP M1)."""
    shapes = jax.eval_shape(lambda: init_params(jax.random.key(0), cfg))
    axes = jax.tree.map(lambda s: ("layers",) + (None,) * (s.ndim - 1), shapes)
    axes.update(
        embed=("vocab", "embed"), final_norm=("embed",),
        lm_head=("embed", "vocab"),
    )
    if cfg.nextn_layers:
        axes["mtp"] = jax.tree.map(lambda s: (None,) * s.ndim, shapes["mtp"])
    return axes


# -- the block ------------------------------------------------------------------


def _layer_params(cfg, params, pi, j):
    """(layer index, the attention matrices and norms of layer j of period
    pi): slices that fuse into the matmuls that read them."""
    li = pi * len(cfg.window_pattern) + j
    return li, _block_params(params, li)


def _block_params(params, li):
    """Block ``li`` of the ``layers`` stack as models/llama.py:_project_qkv
    reads it: ``wq`` and ``wk`` [in, out] (:func:`init_params`)."""
    lp = kimi_linear._stack(params, "layers", li)
    return {**lp, "wq": lp["wq"].T, "wk": lp["wk"].T}


def _ffn(cfg, params, lp, layer, kept, x):
    return llama._norm(
        cfg, kimi_linear._ffn(cfg, params, layer, x), lp["post_ffn_norm"]
    )


def _periods(cfg, period, carry):
    """Every period traced by itself, its layer indices Python ints: a
    held expert stack [expert layers, held, in, out] indexed by a TRACED
    layer is sliced out before the batched matmuls that read it (600 MB a
    layer a step on the chip, where a static slice is the stored array
    read in place), and the first period holds the dense layer anyway. One
    chip's stage of this model is a few periods deep."""
    ys = []
    for pi in range(cfg.periods):
        carry, y = period(carry, pi)
        ys.append(y)
    return carry, jax.tree.map(lambda *a: jnp.stack(a), *ys)


#: models/smallthinker.py's forward with this family's block: no norm on a
#: sub-layer's input, one on its output; the FFN by layer (dense, or a shared
#: expert and the held share of the routed ones); the first period by itself
BLOCK = trunk.Block(
    layer_params=_layer_params,
    mixer_in=lambda cfg, lp, x: x,
    mixer_out=lambda cfg, lp, y: llama._norm(cfg, y, lp["post_attn_norm"]),
    ffn_in=lambda cfg, lp, x: None,
    ffn=_ffn,
    periods=_periods,
)


def prefill(
    params, cfg, tokens, seq_lens, cache, page_table, mesh=None, hidden=False
):
    """``llama.prefill`` for this family (models/smallthinker.py:prefill).
    ``hidden``: the stream before the final norm in the logits' place (a
    program that drafts makes the logits of the rows it needs,
    ``llama.lm_logits``, and hands the stream to :func:`draft_segment`)."""
    return trunk.prefill(
        params, cfg, tokens, seq_lens, cache, page_table, mesh=mesh,
        block=BLOCK, hidden=hidden,
    )


def prefill_continue(
    params, cfg, tokens, start, suffix_lens, cache, page_table, hidden=False
):
    """``llama.prefill_continue`` for this family."""
    return trunk.prefill_continue(
        params, cfg, tokens, start, suffix_lens, cache, page_table,
        block=BLOCK, hidden=hidden,
    )


def decode_step(
    params, cfg, tokens, positions, cache, page_table, active=None, mesh=None
):
    """``llama.decode_step`` for this family: the main path alone, one
    position a slot."""
    return trunk.decode_step(
        params, cfg, tokens, positions, cache, page_table, active, mesh=mesh,
        block=BLOCK,
    )


# -- the prediction module --------------------------------------------------------


def _module_input(cfg, params, hidden, next_tokens):
    """u = W_eh [RMSNorm_e(Emb(t_{i+1})) ; RMSNorm_h(h_i)]."""
    mp = params["mtp"]
    e = rms_norm(
        llama._embed_tokens(cfg, params, next_tokens), mp["e_norm"],
        cfg.rms_eps,
    )
    g = rms_norm(hidden, mp["h_norm"], cfg.rms_eps)
    return qmat(jnp.concatenate([e, g], axis=-1), mp["w_eh"])


def _module_logits(cfg, params, z):
    """Head(RMSNorm_m(z)): the main model's head, float32 logits."""
    z = rms_norm(z, params["mtp"]["final_norm"], cfg.rms_eps)
    return qmat(z, params["lm_head"]).astype(jnp.float32)


def _module_layer(cfg, params):
    """(the module's layer index among the stacks, its attention matrices
    and norms, its cache layer among the pages)."""
    if not cfg.serve_mtp:
        raise ValueError(
            "the prediction module is not served: its K and V have no layer "
            "of the pages (--speculative-mtp 1)"
        )
    layer = cfg.num_layers
    n_global = sum(1 for w in cfg.window_pattern if not w)
    return layer, _block_params(params, layer), cfg.periods * n_global


def draft_segment(
    params, cfg, hidden, next_tokens, start, lens, cache, page_table,
    cold: bool, mesh=None,
):
    """The module over one prefill segment: ``hidden`` [b, s, h] the main
    stack's output before the final norm, ``next_tokens`` [b, s] the token
    AFTER each position (the prompt shifted by one; after the prompt's last
    position, the token just sampled). It writes its K and V for the
    segment, and attends over the segment's own (``cold``) or over the
    sequence's pages. -> (logits [b, vocab] at each row's last valid
    position: the draft of the token after next, the cache)."""
    b, s = next_tokens.shape
    with jax.named_scope("mtp.draft"):
        if cold:
            positions, valid, attend = llama.cold_segment(
                cfg, next_tokens, lens, mesh
            )
        else:
            positions, valid, attend = llama.suffix_segment(
                next_tokens, start, lens
            )
        layer, lp, li = _module_layer(cfg, params)
        c = trunk._Caches(*trunk._split_cache(cache, page_table))
        z, c = trunk.segment_layer(
            cfg, params, BLOCK, trunk.rope_tables(cfg),
            _module_input(cfg, params, hidden, next_tokens), c, lp, layer, 0,
            False, li, positions, positions, valid, attend,
        )
        last = jnp.take_along_axis(z, (lens - 1)[:, None, None], axis=1)[:, 0]
        return _module_logits(cfg, params, last), trunk._join_cache(
            cache, c.kp, c.vp, c.kr, c.vr
        )


def verify_step(
    params, cfg, tokens, positions, cache, page_table, active, mesh=None
):
    """The main stack over TWO positions a slot, ``tokens`` [b, 2] = [the
    last token, its draft] at ``positions`` and ``positions + 1``, through
    the decode path (models/smallthinker.py:decode_step). ``active`` [b, 2]:
    whose K and V are written. -> (logits [b, 2, vocab], the stream before
    the final norm [b, 2, h], the cache)."""
    with jax.named_scope("mtp.verify"):
        x, cache = trunk.decode_step(
            params, cfg, tokens, positions, cache, page_table, active,
            mesh=mesh, block=BLOCK, hidden=True,
        )
        return llama.lm_logits(cfg, params, x), x, cache


def draft_step(
    params, cfg, hidden, next_tokens, positions, cache, page_table, active,
    take, mesh=None,
):
    """The module over the two positions of a verify step: ``hidden`` [b, 2,
    h] what :func:`verify_step` gave, ``next_tokens`` [b, 2] the tokens the
    step emitted after each. ``active`` [b, 2]: whose K and V are written (a
    second row whose draft was rejected is masked: it writes nothing, and
    the first never sees it); ``take`` [b]: the row the next draft comes
    from. -> (logits [b, vocab] of the token after the slot's new last, the
    cache)."""
    with jax.named_scope("mtp.draft"):
        layer, lp, li = _module_layer(cfg, params)
        c = trunk._Caches(*trunk._split_cache(cache, page_table))
        page_size = c.kp.shape[2]
        z, k, v = trunk.step_layer(
            cfg, params, BLOCK, trunk.rope_tables(cfg),
            _module_input(cfg, params, hidden, next_tokens), c, lp, layer, 0,
            False, li, positions, mesh,
        )
        pos = positions[:, None] + jnp.arange(2, dtype=positions.dtype)
        with jax.named_scope("kv_write"):
            kp, vp = (
                llama.scatter_decode_rows(
                    pool, new, c.gtable, pos, active, page_size,
                    first_layer=li,
                )
                for pool, new in ((c.kp, k), (c.vp, v))
            )
        z = jnp.take_along_axis(z, take[:, None, None], axis=1)[:, 0]
        return _module_logits(cfg, params, z), trunk._join_cache(
            cache, kp, vp, c.kr, c.vr
        )


# -- the plain reference ---------------------------------------------------------


def reference_logits(
    params: Dict[str, Any], cfg: ExaoneMoeConfig, tokens: jnp.ndarray,
    share: "int | None" = None, mtp: bool = False,
):
    """The repo's plain reference of this family: float32, ``highest``, no
    cache, no kernels, masks written out, every expert that ``params`` holds
    computed densely and weighted by the router's top k. tokens [s] ->
    logits [s, vocab]. ``share``: the experts in ``params`` are the share of
    that index (None: the config's); with ``num_experts`` ==
    ``router_outputs`` it is the uncut layer. ``mtp``: also the prediction
    module's logits [s - 1, vocab], row i from ``h_i`` and ``tokens[i + 1]``,
    which predicts token i + 2 -> (logits, module logits)."""
    f32 = jnp.float32
    s = tokens.shape[0]
    heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    share = cfg.share_index if share is None else share
    cos_tab, sin_tab = rope_table(cfg.max_seq_len, hd, cfg.rope_theta)
    pattern = llama.layer_pattern(cfg)

    def rms(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + cfg.rms_eps) * w.astype(f32)

    def rope(x, pos):  # [n, heads, hd]
        half = hd // 2
        cos, sin = cos_tab[pos][:, None, :], sin_tab[pos][:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )

    def swiglu(x, gate, up, down):
        return (jax.nn.silu(x @ gate) * (x @ up)) @ down

    def attention(x, lp, window, roped):
        n = x.shape[0]
        pos = jnp.arange(n)
        q = rms((x @ lp["wq"].T).reshape(n, heads, hd), lp["q_norm"])
        k = rms((x @ lp["wk"].T).reshape(n, kvh, hd), lp["k_norm"])
        v = (x @ lp["wv"]).reshape(n, kvh, hd)
        if roped:
            q, k = rope(q, pos), rope(k, pos)
        mask = pos[None, :] <= pos[:, None]
        if window:
            mask = mask & (pos[None, :] > pos[:, None] - window)
        qg = q.reshape(n, kvh, heads // kvh, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        return attn.reshape(n, heads * hd) @ lp["wo"]

    def experts(x, ep):
        w, idx = moe.route(cfg, x @ ep["router"], ep["router_bias"])
        weights = jnp.sum(
            jax.nn.one_hot(idx, cfg.router_outputs, dtype=f32) * w[..., None],
            axis=-2,
        )  # [n, router width], zero off the top k
        held = jax.lax.dynamic_slice_in_dim(
            weights, share * cfg.num_experts, cfg.num_experts, axis=1
        )
        g = jnp.einsum("sh,ehf->sef", x, ep["w_gate"])
        u = jnp.einsum("sh,ehf->sef", x, ep["w_up"])
        y = jnp.einsum("sef,efh->seh", jax.nn.silu(g) * u, ep["w_down"])
        return swiglu(x, ep["s_gate"], ep["s_up"], ep["s_down"]) + jnp.einsum(
            "seh,se->sh", y, held
        )

    as_f32 = lambda name, i: jax.tree.map(  # noqa: E731
        lambda a: a[i].astype(f32), params[name]
    )

    def block(x, l, window, roped):
        lp = as_f32("layers", l)
        x = x + rms(attention(x, lp, window, roped), lp["post_attn_norm"])
        if l < cfg.first_dense:
            dp = as_f32("dense", l)
            y = swiglu(x, dp["w_gate"], dp["w_up"], dp["w_down"])
        else:
            y = experts(x, as_f32("experts", l - cfg.first_dense))
        return x + rms(y, lp["post_ffn_norm"])

    embed = params["embed"].astype(f32)
    head = params["lm_head"].astype(f32)
    with jax.default_matmul_precision("highest"):
        x = embed[tokens]
        for l in range(cfg.num_layers):
            x = block(x, l, *pattern[l % len(pattern)])
        logits = rms(x, params["final_norm"]) @ head
        if not mtp:
            return logits
        mp = jax.tree.map(lambda a: a.astype(f32), params["mtp"])
        u = jnp.concatenate(
            [rms(embed[tokens[1:]], mp["e_norm"]), rms(x[:-1], mp["h_norm"])],
            axis=-1,
        ) @ mp["w_eh"]
        z = block(u, cfg.num_layers, 0, False)
        return logits, rms(z, mp["final_norm"]) @ head
