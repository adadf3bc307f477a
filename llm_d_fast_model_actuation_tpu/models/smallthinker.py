"""SmallThinker (``SmallThinkerForCausalLM``, PowerInfer) — the patterned
family: the layers of a model are not one block repeated but a PERIOD of
blocks repeated, and the period is data.

What differs from the Llama trunk (models/llama.py), per layer:

  * the layer pattern, ``window_pattern`` / ``rope_pattern``, one entry per
    layer of a period (published: 4 layers, the first with the full causal
    mask and NO positional encoding, the other three with a 4096-token
    sliding window and rotate-half RoPE);
  * two kinds of KV state (engine/kv_cache.py:KVLayout). Full-attention
    layers write the paged pool ``[global layers, pages, page, kvh*hd]``
    through the sequence's page table; window layers write a RING per
    sequence, ``[window layers, slots, ring pages, page, kvh*hd]``,
    position p at slot ``p % ring_len``, addressed through the ring columns
    the engine appends to every page-table row — so every attention op
    reads either kind through one interface, a table row and a layer index;
  * the router reads the layer's INPUT (the residual stream before the
    attention norm), and its top-k softmax weights the routed experts after
    attention (models/moe.py:routed_ffn; ReLU gate, no shared expert).

The forward scans over PERIODS; the body is the period's layers, unrolled
statically, each with its own static window and RoPE flag: compile time
stays O(1) in depth and no ``lax.cond`` picks a layer's kind at run time.
The entry points keep the trunk's signatures, and ``llama.prefill`` /
``prefill_continue`` / ``decode_step`` hand a patterned config here, so
engine/engine.py's ``ProgramSet`` never branches on the family.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Callable, Dict, NamedTuple, Tuple

import jax
import jax.numpy as jnp

from ..ops.attention import paged_decode_attention_inline
from ..ops.rope import rope_table
from . import llama, moe
from .quant import qmat


@dataclass(frozen=True)
class SmallThinkerConfig(moe.MoeConfig):
    #: (llama.patterned) the module of this package that is its forward
    forward_module = "smallthinker"

    routed_experts: bool = True
    #: the experts' gate activation (sparse ReGLU)
    expert_activation: str = "relu"
    #: per layer of one period: attention window in tokens, 0 = full causal
    window_pattern: Tuple[int, ...] = (0, 4096, 4096, 4096)
    #: per layer of one period: rotate-half RoPE, or no positional encoding
    rope_pattern: Tuple[bool, ...] = (False, True, True, True)

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
        if self.quantization:
            raise ValueError(
                "SmallThinkerConfig: weight quantization is not carried by "
                "the patterned forward"
            )

    @classmethod
    def smallthinker_21b_a3b(cls) -> "SmallThinkerConfig":
        """SmallThinker-21BA3B-Instruct as published."""
        return cls(
            vocab_size=151936,
            hidden_size=2560,
            num_layers=52,
            num_heads=28,
            num_kv_heads=4,
            head_dim=128,
            intermediate_size=768,
            rope_theta=1.5e6,
            rms_eps=1e-6,
            max_seq_len=16384,
            num_experts=64,
            experts_per_token=6,
        )

    @classmethod
    def tiny_smallthinker(cls, vocab: int = 256) -> "SmallThinkerConfig":
        """CPU test size: two periods, a window shorter than the contexts."""
        return cls(
            vocab_size=vocab,
            hidden_size=64,
            num_layers=8,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=32,
            rope_theta=10000.0,
            rms_eps=1e-6,
            max_seq_len=128,
            num_experts=8,
            experts_per_token=3,
            window_pattern=(0, 24, 24, 24),
        )


#: the stacked layer parameters are the expert family's (models/moe.py):
#: same names, shapes and logical axes, so init, sharding rules and the
#: registry need nothing of their own
init_params = moe.init_params
param_logical_axes = moe.param_logical_axes


# -- the period ---------------------------------------------------------------


def _plan(cfg: SmallThinkerConfig):
    """Static facts of one period: per layer (window, rope, index among the
    period's layers of its kind), and how many of each kind a period has."""
    layers, n_global, n_window = [], 0, 0
    for window, rope in llama.layer_pattern(cfg):
        if window:
            layers.append((window, rope, n_window))
            n_window += 1
        else:
            layers.append((0, rope, n_global))
            n_global += 1
    return tuple(layers), n_global, n_window


def _split_cache(cache, page_table):
    """(global k, v pools, ring k, v pools as [layers, slots * ring pages,
    page, fused], the table's global columns, its ring columns)."""
    kp, vp, kr, vr = cache
    ring_pages = kr.shape[2]
    flat = (kr.shape[0], kr.shape[1] * ring_pages) + kr.shape[3:]
    split = page_table.shape[1] - ring_pages
    return (
        kp, vp, kr.reshape(flat), vr.reshape(flat),
        page_table[:, :split], page_table[:, split:],
    )


def _join_cache(cache, kp, vp, kr, vr):
    """The cache tuple again, the rings back in their stored shape."""
    return kp, vp, kr.reshape(cache[2].shape), vr.reshape(cache[3].shape)


def _periods(cfg, period, carry):
    """One scan over the periods (a layer's small matrices are sliced by
    the body, the expert stacks read whole)."""
    return jax.lax.scan(
        period, carry, llama.period_indices(cfg, len(cfg.window_pattern))
    )


def _layer_params(cfg, params, pi, j):
    """(layer index, the parameters of layer j of period pi)."""
    li = pi * len(cfg.window_pattern) + j
    return li, {
        # the expert matrices are never sliced by layer: the grouped matmul
        # takes the whole stack and the layer's index (models/moe.py:_grouped)
        name: a if name in moe.EXPERT_STACKS else a[li]
        for name, a in params["layers"].items()
    }


def _router_logits(cfg, lp, x):
    """The layer's router, read from its input: float32 out of the MXU's
    accumulator, so that near-ties among 64 logits are not rounded twice."""
    with jax.named_scope("router"):
        return jnp.einsum(
            "...h,he->...e", x, lp["router"],
            preferred_element_type=jnp.float32,
        )


def _routed_ffn(cfg, params, lp, layer, router, x):
    h = llama._norm(cfg, x, lp["mlp_norm"])
    return llama._ffn(cfg, lp, h, router_logits=router, layer=layer)


class Block(NamedTuple):
    """What a family that shares this file's period scan, rings and deferred
    writes brings of its own (models/exaone_moe.py is the other): where its
    norms sit, what its FFN is, and which periods are traced by
    themselves."""

    #: (cfg, params, pi, j) -> (layer index, the parameters of layer j of
    #: period pi)
    layer_params: Callable
    #: (cfg, lp, x) -> what the q, k and v projections read of the stream
    mixer_in: Callable
    #: (cfg, lp, y) -> what joins the stream of the attention's output
    mixer_out: Callable
    #: (cfg, lp, x) -> what the FFN keeps of the LAYER's input, computed
    #: before attention (this family's router reads it)
    ffn_in: Callable
    #: (cfg, params, lp, layer, kept, x) -> what joins the stream of the FFN
    #: sub-layer, ``x`` the stream after attention
    ffn: Callable
    #: (cfg, period, carry) -> (carry, ys stacked over all periods)
    periods: Callable


BLOCK = Block(
    layer_params=_layer_params,
    mixer_in=lambda cfg, lp, x: llama._norm(cfg, x, lp["attn_norm"]),
    mixer_out=lambda cfg, lp, y: y,
    ffn_in=_router_logits,
    ffn=_routed_ffn,
    periods=_periods,
)


#: the pools of :class:`_Caches`, as a scan carries them
_POOLS = ("kp", "vp", "kr", "vr")


class _Caches(NamedTuple):
    """A step's view of the sequence state: the page pools of the
    full-attention layers and the ring pools of the window layers, with the
    table columns that address each."""

    kp: Any
    vp: Any
    kr: Any
    vr: Any
    gtable: Any
    rtable: Any

    def of(self, window):
        """(K pool, V pool, table) of a layer's kind."""
        if window:
            return self.kr, self.vr, self.rtable
        return self.kp, self.vp, self.gtable

    def put(self, window, k, v):
        if window:
            return self._replace(kr=k, vr=v)
        return self._replace(kp=k, vp=v)


def _cache_layer(li):
    """The cache layer of (period, layers of the kind a period has, index
    among them), made where it is used; or ``li`` itself."""
    if isinstance(li, tuple):
        pi, count, nth = li
        return pi * count + nth
    return li


def rope_tables(cfg):
    """The rotary tables of a forward, made once outside its scan."""
    return rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )


def segment_layer(
    cfg, params, block, tabs, x, c, lp, layer, window, rope, li, positions,
    at, valid, attend,
):
    """One layer of a prefill segment, ``x`` [b, s, h]: project, write K and
    V into cache layer ``li`` of the layer's own kind of cache at ``at``
    (the positions, or their ring slots), ``attend(q, k, v, pools, table,
    layer, window)``, the FFN. ``tabs``: :func:`rope_tables`; ``li``: (period,
    layers of the kind a period has, index among them), or the cache layer
    itself. -> (x, the caches)."""
    b, s = positions.shape
    page_size = c.kp.shape[2]
    cos_tab, sin_tab = tabs
    kept = block.ffn_in(cfg, lp, x)
    h = block.mixer_in(cfg, lp, x)
    scope = "attn.window" if window else "attn.global"
    with jax.named_scope(scope):
        q, k, v = llama._project_qkv(
            cfg, lp, h, positions, cos_tab, sin_tab, rope=rope
        )
    with jax.named_scope("kv_write"):
        li = _cache_layer(li)
        kpool, vpool, table = c.of(window)
        kpool = llama._scatter_prefill(
            kpool, li, k, table, at, valid, page_size)
        vpool = llama._scatter_prefill(
            vpool, li, v, table, at, valid, page_size)
        c = c.put(window, kpool, vpool)
    with jax.named_scope(scope):
        attn = attend(q, k, v, (kpool, vpool), table, li, window)
        x = x + block.mixer_out(
            cfg, lp, qmat(attn.reshape(b, s, cfg.q_dim), lp["wo"])
        )
    return x + block.ffn(cfg, params, lp, layer, kept, x), c


def _segment(
    params, cfg, tokens, positions, valid, cache, page_table, attend,
    block=BLOCK,
):
    """The forward of one prefill segment [b, s] shared by the cold and the
    continued program, over the periods -> (the stream before the final
    norm, the four pools)."""
    layers, n_global, n_window = _plan(cfg)
    base = _Caches(*_split_cache(cache, page_table))
    ring_pos = positions % (base.rtable.shape[1] * base.kp.shape[2])
    tabs = rope_tables(cfg)
    x = llama._embed_tokens(cfg, params, tokens)

    def period(carry, pi):
        x, c = carry[0], base._replace(**dict(zip(_POOLS, carry[1:])))
        for j, (window, rope, nth) in enumerate(layers):
            layer, lp = block.layer_params(cfg, params, pi, j)
            li = (pi, n_window if window else n_global, nth)
            x, c = segment_layer(
                cfg, params, block, tabs, x, c, lp, layer, window, rope, li,
                positions, ring_pos if window else positions, valid, attend,
            )
        return (x, c.kp, c.vp, c.kr, c.vr), None

    (x, *pools), _ = block.periods(
        cfg, period, (x, base.kp, base.vp, base.kr, base.vr)
    )
    return x, pools


def _outputs(cfg, params, x, cache, pools, hidden):
    """What a forward returns: the logits, or if ``hidden`` the stream
    before the final norm in their place (the caller makes the logits of
    the rows it needs), and the cache tuple with ``pools`` in it."""
    if hidden:
        return x, _join_cache(cache, *pools)
    logits = llama.lm_logits(cfg, params, x)
    return logits, _join_cache(cache, *pools)


def prefill(
    params, cfg, tokens, seq_lens, cache, page_table, mesh=None, block=BLOCK,
    hidden=False,
):
    """``llama.prefill`` for a patterned model: a cold segment attends over
    its own K and V (the flash kernel, with the layer's window), and writes
    both kinds of cache for what follows. ``hidden``: the stream before the
    final norm comes back in the logits' place."""
    positions, valid, attend = llama.cold_segment(cfg, tokens, seq_lens, mesh)
    x, pools = _segment(
        params, cfg, tokens, positions, valid, cache, page_table, attend,
        block,
    )
    return _outputs(cfg, params, x, cache, pools, hidden)


def prefill_continue(
    params, cfg, tokens, start, suffix_lens, cache, page_table, block=BLOCK,
    hidden=False,
):
    """``llama.prefill_continue`` for a patterned model: a later segment of
    a chunked prefill. Full-attention layers attend over the sequence's
    pages, window layers over its ring, into which the segment has just
    been written (a ring holds window + one segment, so nothing a query of
    the segment still sees has been overwritten)."""
    positions, valid, attend = llama.suffix_segment(tokens, start, suffix_lens)
    x, pools = _segment(
        params, cfg, tokens, positions, valid, cache, page_table, attend,
        block,
    )
    return _outputs(cfg, params, x, cache, pools, hidden)


def step_layer(
    cfg, params, block, tabs, x, c, lp, layer, window, rope, li, positions,
    mesh,
):
    """One layer of a decode step by the deferred write: attention reads
    cache layer ``li`` (as :func:`segment_layer` takes it) of the layer's
    kind for the positions before ``positions`` and takes the new K and V
    inline. ``x`` [b, h], or [b, n,
    h] for n positions a slot, ``positions + 0 .. n - 1`` (a verify step).
    -> (x, the new K, the new V [b, (n,) kvh, hd])."""
    lead = x.shape[:-1]
    cos_tab, sin_tab = tabs
    kept = block.ffn_in(cfg, lp, x)
    h = block.mixer_in(cfg, lp, x)
    with jax.named_scope("attn.window" if window else "attn.global"):
        if x.ndim == 2:
            q, k, v = llama._project_qkv(
                cfg, lp, h[:, None, :], positions[:, None], cos_tab,
                sin_tab, rope=rope,
            )
            q, k, v = q[:, 0], k[:, 0], v[:, 0]  # [b, heads/kvh, hd]
        else:
            offs = jnp.arange(x.shape[1], dtype=positions.dtype)
            q, k, v = llama._project_qkv(
                cfg, lp, h, positions[:, None] + offs, cos_tab, sin_tab,
                rope=rope,
            )
        kpool, vpool, table = c.of(window)
        attn = paged_decode_attention_inline(
            q, kpool, vpool, k, v, table, positions, _cache_layer(li),
            impl=cfg.attention_impl, mesh=mesh,
            **({"window": window} if window else {}),
        )
        x = x + block.mixer_out(
            cfg, lp, qmat(attn.reshape(*lead, cfg.q_dim), lp["wo"])
        )
    return x + block.ffn(cfg, params, lp, layer, kept, x), k, v


def decode_step(
    params, cfg, tokens, positions, cache, page_table, active=None, mesh=None,
    block=BLOCK, hidden=False,
):
    """``llama.decode_step`` for a patterned model, always by the deferred
    write: attention reads each layer's own cache for positions before the
    token's and takes the token's K and V inline; after the scan, ONE
    scatter per kind of cache and direction writes every layer's new row
    (pages of the full-attention layers, ring slot ``position % ring_len``
    of the window layers). Window layers read only the pages of the ring
    that hold a visible key (ops/pallas/decode.py).

    ``tokens`` [b, n] (and ``active`` [b, n]): n positions a slot, ``positions
    + 0 .. n - 1``, through the same layers and the same two scatters; a
    slot's pages and ring are read once for all n (a verify step of
    speculative decoding: the last token and its drafts). ``hidden``: the
    stream before the final norm comes back in the logits' place."""
    layers, n_global, n_window = _plan(cfg)
    c = _Caches(*_split_cache(cache, page_table))
    page_size = c.kp.shape[2]
    ring_len = c.rtable.shape[1] * page_size
    tabs = rope_tables(cfg)
    x = llama._embed_tokens(cfg, params, tokens)  # [b, (n,) h]

    def period(x, pi):
        new_k, new_v = [], []
        for j, (window, rope, nth) in enumerate(layers):
            layer, lp = block.layer_params(cfg, params, pi, j)
            li = (pi, n_window if window else n_global, nth)
            x, k, v = step_layer(
                cfg, params, block, tabs, x, c, lp, layer, window, rope, li,
                positions, mesh,
            )
            new_k.append(k)
            new_v.append(v)
        return x, (jnp.stack(new_k), jnp.stack(new_v))

    # k_all, v_all: [periods, layers of a period, b, (n,) kvh, hd]
    x, (k_all, v_all) = block.periods(cfg, period, x)
    pos = positions
    if tokens.ndim == 2:
        pos = positions[:, None] + jnp.arange(
            tokens.shape[1], dtype=positions.dtype
        )

    def write(pool, new, table, pos, js):
        """The rows ``new[:, js]`` of every layer of one kind, one scatter."""
        rows = new[:, jnp.asarray(js)]  # [periods, kind's layers, b, ...]
        return llama.scatter_decode_rows(
            pool, rows, table, pos, active, page_size
        )

    kp, vp, kr, vr = c.kp, c.vp, c.kr, c.vr
    with jax.named_scope("kv_write"):
        global_js = [j for j, (w, _, _) in enumerate(layers) if not w]
        window_js = [j for j, (w, _, _) in enumerate(layers) if w]
        if global_js:
            kp = write(kp, k_all, c.gtable, pos, global_js)
            vp = write(vp, v_all, c.gtable, pos, global_js)
        if window_js:
            ring_pos = pos % ring_len
            kr = write(kr, k_all, c.rtable, ring_pos, window_js)
            vr = write(vr, v_all, c.rtable, ring_pos, window_js)
    return _outputs(cfg, params, x, cache, (kp, vp, kr, vr), hidden)


def reference_logits(
    params: Dict[str, Any], cfg: SmallThinkerConfig, tokens: jnp.ndarray
) -> jnp.ndarray:
    """The repo's plain reference of this family: float32, no cache, no
    kernels, the expert sum computed densely over all experts and weighted
    by the top-k softmax, masks written out. tokens [s] -> logits [s, vocab].
    What the cached, routed, kernel-backed path above is tested against."""
    f32 = jnp.float32
    s = tokens.shape[0]
    pos = jnp.arange(s)
    heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    cos_tab, sin_tab = rope_table(cfg.max_seq_len, hd, cfg.rope_theta)
    pattern = llama.layer_pattern(cfg)

    def rms(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + cfg.rms_eps) * w.astype(f32)

    def rope(x):  # [s, n, hd]
        half = hd // 2
        cos, sin = cos_tab[pos][:, None, :], sin_tab[pos][:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate(
            [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
        )

    x = params["embed"][tokens].astype(f32)
    with jax.default_matmul_precision("highest"):
        for l in range(cfg.num_layers):
            window, roped = pattern[l % len(pattern)]
            lp = jax.tree.map(lambda a: a[l].astype(f32), params["layers"])
            probs, idx = moe.route(cfg, x @ lp["router"])
            weights = jnp.sum(
                jax.nn.one_hot(idx, cfg.num_experts, dtype=f32)
                * probs[..., None], axis=-2,
            )  # [s, E], zero off the top k
            h = rms(x, lp["attn_norm"])
            q = (h @ lp["wq"]).reshape(s, heads, hd)
            k = (h @ lp["wk"]).reshape(s, kvh, hd)
            v = (h @ lp["wv"]).reshape(s, kvh, hd)
            if roped:
                q, k = rope(q), rope(k)
            mask = pos[None, :] <= pos[:, None]
            if window:
                mask = mask & (pos[None, :] > pos[:, None] - window)
            qg = q.reshape(s, kvh, heads // kvh, hd) * hd**-0.5
            scores = jnp.einsum("skgd,tkd->kgst", qg, k)
            scores = jnp.where(mask[None, None], scores, -jnp.inf)
            attn = jnp.einsum(
                "kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v
            )
            x = x + attn.reshape(s, heads * hd) @ lp["wo"]
            h = rms(x, lp["mlp_norm"])
            g = jnp.einsum("sh,ehf->sef", h, lp["w_gate"])
            u = jnp.einsum("sh,ehf->sef", h, lp["w_up"])
            y = jnp.einsum("sef,efh->seh", jax.nn.relu(g) * u, lp["w_down"])
            x = x + jnp.einsum("seh,se->sh", y, weights)
        x = rms(x, params["final_norm"])
        return x @ params["lm_head"].astype(f32)
