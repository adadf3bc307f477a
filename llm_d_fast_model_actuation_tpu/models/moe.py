"""Mixtral-style sparse-MoE decoder — the second model family.

Same attention trunk as the Llama family (models/llama.py — one scanned
layer body, paged KV, GQA); the FFN is a top-k router over E experts.

TPU/SPMD design:
  * expert weights are stacked ``[layers, experts, ...]`` and the experts
    axis carries the ``expert -> ep`` logical sharding rule
    (parallel/mesh.py LOGICAL_RULES): each ep shard holds E/ep experts;
  * two forms of the one expert layer (a token's top-k experts, weighted
    by the router's renormalized probabilities). :func:`moe_ffn` is
    DENSE-compute, sparse-weight: every expert runs on every token and the
    router's top-k probabilities weight the sum. Under ep sharding each
    device computes only its local experts and the weighted sum's
    contraction over E becomes one psum over ep — no scatter/gather, no
    capacity factors, no dynamic shapes. :func:`routed_ffn` is the routed,
    dropless one: every (token, expert) assignment is sorted by expert and
    the three matmuls are grouped ones (``jax.lax.ragged_dot``, which the
    TPU compiler lowers to one kernel that computes each row against its
    own expert only) — static shapes, no capacity factor, nothing dropped
    however uneven the routing;
  * :func:`expert_ffn` picks the form, by what a trace can see
    (:func:`takes_grouped`). A family whose E/k makes the dense form's
    cost the layer's says so in its config (``routed_experts``: 64/6 for
    SmallThinker) and always routes. Every other family (Mixtral, 8/2)
    goes by the row count the program is traced with: under
    ``GROUPED_MIN_ROWS`` every expert's weights are read either way and
    the dense form is bound by those reads (the decode chunk, prefill and
    suffix segments of up to 256 rows: the program is the dense one, to
    the instruction); at or over it the dense form is bound by arithmetic
    of which (E - k)/E is multiplied by a router weight of zero, and the
    rows go to their own experts (the prefill and suffix programs of 512
    and 1,024 rows). The grouped form reads the expert stacks WHOLE,
    ``[L, E, ...]`` with the layer's index (:func:`_grouped`): a custom
    call cannot fuse the slice a layer scan hands it, and a layer's
    experts copied out every layer is what made Mixtral on ``routed_ffn``
    41% slower in PR 28. A quantized stack, a mesh that shards the
    ``expert`` or ``mlp`` axis, and a caller that brings no stacks
    (models/train.py) keep the dense form;
  * the grouped matmul itself is the Pallas one (megablox ``gmm``) where
    the program runs Pallas kernels and the expert widths are whole tiles
    (:func:`_gmm_fits`: Mixtral's 4,096 x 14,336), and XLA's
    ``ragged_dot`` lowering everywhere else (SmallThinker's 2,560 x 768,
    every other backend): at Mixtral's widths the XLA lowering runs at 30%
    of the MXU's peak and loses to the dense form under 1,024 rows.

Reference parity: the reference serves MoE through vLLM's Mixtral support
(SURVEY §2.9 model families); this is the TPU-native equivalent.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Dict

import jax
import jax.numpy as jnp

from ..parallel.mesh import LOGICAL_RULES
from . import llama
from .quant import is_quantized


@dataclass(frozen=True)
class MoeConfig(llama.LlamaConfig):
    num_experts: int = 8
    experts_per_token: int = 2
    #: True: this family always routes (:func:`routed_ffn`, each token's
    #: top-k only, whatever the row count), but for programs traced with
    #: ``dense_max_rows`` rows or fewer. False: :func:`expert_ffn` goes
    #: by the rows of the trace (:func:`takes_grouped`)
    routed_experts: bool = False
    #: a routing family's programs of this many rows or fewer compute every
    #: HELD expert on every row (:func:`held_dense_ffn`); 0: none does
    dense_max_rows: int = 0
    #: the experts' activation in :func:`routed_ffn`: "silu", "relu" or
    #: "relu2" (the squared ReLU)
    expert_activation: str = "silu"
    #: True: an expert is three matrices, ``act(x w_gate) * (x w_up)`` into
    #: ``w_down``. False: two, ``act(x w_up)`` into ``w_down``
    #: (:func:`expert_stacks` names them)
    expert_gated: bool = True
    #: the router's outputs, where ``num_experts`` is the SHARE of them this
    #: config holds (one chip of several that share each layer: experts
    #: ``share_index * num_experts ...`` of ``router_width``); 0: all of them
    router_width: int = 0
    share_index: int = 0
    #: :func:`route`: "softmax" over the k kept logits, or "sigmoid" scores
    #: renormalised over the k kept and scaled by ``routed_scaling``
    router_scoring: str = "softmax"
    routed_scaling: float = 1.0

    def __post_init__(self) -> None:
        super().__post_init__()
        if (self.share_index + 1) * self.num_experts > self.router_outputs:
            raise ValueError(
                f"share {self.share_index} of {self.num_experts} experts lies "
                f"outside a router of {self.router_outputs}"
            )

    @property
    def router_outputs(self) -> int:
        """The experts the router chooses among, held here or not."""
        return self.router_width or self.num_experts

    @property
    def expert_input_size(self) -> int:
        """The width the routed experts read and write: the residual
        stream's, unless the family routes in a narrower space
        (models/nemotron_h.py)."""
        return self.hidden_size

    @classmethod
    def mixtral_8x7b(cls) -> "MoeConfig":
        return cls(
            vocab_size=32000,
            hidden_size=4096,
            num_layers=32,
            num_heads=32,
            num_kv_heads=8,
            head_dim=128,
            intermediate_size=14336,
            rope_theta=1e6,
            max_seq_len=32768,
            num_experts=8,
            experts_per_token=2,
        )

    @classmethod
    def tiny_moe(cls, vocab: int = 256) -> "MoeConfig":
        return cls(
            vocab_size=vocab,
            hidden_size=64,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            head_dim=16,
            intermediate_size=96,
            rope_theta=10000.0,
            max_seq_len=128,
            num_experts=4,
            experts_per_token=2,
        )

    def num_params(self) -> int:
        h, f, e = self.hidden_size, self.intermediate_size, self.num_experts
        per_layer = (
            2 * h  # norms
            + h * self.q_dim
            + 2 * h * self.kv_dim
            + self.q_dim * h
            + h * e  # router
            + e * 3 * h * f  # experts
        )
        head = 0 if self.tie_embeddings else h * self.vocab_size
        return (
            self.vocab_size * h + self.num_layers * per_layer + h + head
        )


def init_params(key: jax.Array, cfg: MoeConfig) -> Dict[str, Any]:
    """Random-init params in the Llama layout, with per-layer expert stacks
    (``[L, E, ...]``) and a router replacing the dense FFN."""
    k_embed, k_layers, k_head = jax.random.split(key, 3)
    h, L, E, f = (
        cfg.hidden_size,
        cfg.num_layers,
        cfg.num_experts,
        cfg.intermediate_size,
    )

    def norm_init(shape):
        return jnp.ones(shape, dtype=cfg.dtype)

    def dense_init(key, shape, fan_in):
        return (
            jax.random.normal(key, shape, dtype=jnp.float32) * fan_in**-0.5
        ).astype(cfg.dtype)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "attn_norm": norm_init((L, h)),
        "wq": dense_init(ks[0], (L, h, cfg.q_dim), h),
        "wk": dense_init(ks[1], (L, h, cfg.kv_dim), h),
        "wv": dense_init(ks[2], (L, h, cfg.kv_dim), h),
        "wo": dense_init(ks[3], (L, cfg.q_dim, h), cfg.q_dim),
        "mlp_norm": norm_init((L, h)),
        "router": dense_init(ks[4], (L, h, E), h),
        "w_gate": dense_init(ks[5], (L, E, h, f), h),
        "w_up": dense_init(ks[6], (L, E, h, f), h),
        "w_down": dense_init(ks[7], (L, E, f, h), f),
    }
    params = {
        "embed": dense_init(k_embed, (cfg.vocab_size, h), h),
        "layers": layers,
        "final_norm": norm_init((h,)),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = dense_init(k_head, (h, cfg.vocab_size), h)
    return params


def param_logical_axes(cfg: MoeConfig) -> Dict[str, Any]:
    layers = {
        "attn_norm": ("layers", "embed"),
        "wq": ("layers", "embed", "heads"),
        "wk": ("layers", "embed", "kv_heads"),
        "wv": ("layers", "embed", "kv_heads"),
        "wo": ("layers", "heads", "embed"),
        "mlp_norm": ("layers", "embed"),
        "router": ("layers", "embed", None),  # router replicated (tiny)
        "w_gate": ("layers", "expert", "embed", "mlp"),
        "w_up": ("layers", "expert", "embed", "mlp"),
        "w_down": ("layers", "expert", "mlp", "embed"),
    }
    axes = {
        "embed": ("vocab", "embed"),
        "layers": layers,
        "final_norm": ("embed",),
    }
    if not cfg.tie_embeddings:
        axes["lm_head"] = ("embed", "vocab")
    return axes


def _qeinsum(spec: str, x: jnp.ndarray, w: Any) -> jnp.ndarray:
    """Expert einsum for a plain or int8-quantized weight (models/quant.py):
    the per-expert per-output-channel scale ([E, 1, out] after the layer
    slice) rescales the einsum RESULT, so no dequantized expert stack ever
    materializes — the same fusion argument as qmat."""
    if is_quantized(w):
        out = jnp.einsum(spec, x, w["q"].astype(x.dtype))
        return out * jnp.squeeze(w["s"], axis=-2).astype(out.dtype)
    return jnp.einsum(spec, x, w)


def moe_ffn(cfg: MoeConfig, lp: Dict[str, Any], x: jnp.ndarray) -> jnp.ndarray:
    """Top-k routed expert FFN, dense-compute sparse-weight.

    x: [..., hidden]; lp["router"]: [h, E]; experts [E, h, f]/[E, f, h].
    """
    k = cfg.experts_per_token
    with jax.named_scope("router"):
        logits = (x @ lp["router"]).astype(jnp.float32)  # [..., E]
        top_vals, top_idx = jax.lax.top_k(logits, k)  # [..., k]
        top_probs = jax.nn.softmax(top_vals, axis=-1)  # renormalized over top-k
        # scatter the k probabilities back to a dense [.., E] weight vector
        onehot = jax.nn.one_hot(top_idx, cfg.num_experts, dtype=jnp.float32)
        weights = jnp.einsum("...k,...ke->...e", top_probs, onehot)

    g = _qeinsum("...h,ehf->...ef", x, lp["w_gate"])
    u = _qeinsum("...h,ehf->...ef", x, lp["w_up"])
    act = (jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype)) * u
    y = _qeinsum("...ef,efh->...eh", act, lp["w_down"])
    # contraction over E: with experts ep-sharded this is the one psum
    out = jnp.einsum("...eh,...e->...h", y.astype(jnp.float32), weights)
    return out.astype(x.dtype)


def route(cfg: MoeConfig, logits: jnp.ndarray, bias=None):
    """Router logits [..., router width] (any float dtype) -> (the k kept
    experts' weights [..., k] float32, their indices [..., k]). "softmax":
    the k largest logits, a softmax over them. "sigmoid": scores
    sigmoid(logits); the k largest of score + ``bias`` (a selection bias
    [router width], which chooses and does not weigh) over the router's
    whole width, one group; the kept scores over their sum, times
    ``routed_scaling``."""
    logits = logits.astype(jnp.float32)
    if cfg.router_scoring == "softmax":
        top_vals, top_idx = jax.lax.top_k(logits, cfg.experts_per_token)
        return jax.nn.softmax(top_vals, axis=-1), top_idx
    if cfg.router_scoring != "sigmoid":
        raise ValueError(f"unknown router scoring {cfg.router_scoring!r}")
    scores = jax.nn.sigmoid(logits)
    _, top_idx = jax.lax.top_k(
        scores if bias is None else scores + bias, cfg.experts_per_token
    )
    kept = jnp.take_along_axis(scores, top_idx, axis=-1)
    weights = kept / jnp.sum(kept, axis=-1, keepdims=True)
    return weights * cfg.routed_scaling, top_idx


def _activate(cfg, u: jnp.ndarray, g: "jnp.ndarray | None") -> jnp.ndarray:
    """What goes into an expert's down projection, in ``u``'s dtype: ``act(g)
    * u`` for a gated expert, ``act(u)`` for one of two matrices (``g`` None)."""
    if g is None:
        return _gate_act(cfg, u).astype(u.dtype)
    return _gate_act(cfg, g).astype(u.dtype) * u


def _gate_act(cfg, g: jnp.ndarray) -> jnp.ndarray:
    """The experts' activation, float32 math: SiLU (Mixtral), ReLU
    (SmallThinker's sparse ReGLU) or the squared ReLU (Nemotron-H)."""
    gf = g.astype(jnp.float32)
    if cfg.expert_activation == "relu":
        return jax.nn.relu(gf)
    if cfg.expert_activation == "relu2":
        return jnp.square(jax.nn.relu(gf))
    if cfg.expert_activation == "silu":
        return jax.nn.silu(gf)
    raise ValueError(f"unknown expert activation {cfg.expert_activation!r}")


#: (rows, in, out) tiles of the Pallas grouped matmul (megablox ``gmm``).
#: A [2048, 1024] tile of an expert matrix is 4 MB and is read once for
#: every 256-row tile of the rows its expert was given: at 128 assignment
#: rows an expert and more (a 512-token segment of a top-2 of 8) a layer's
#: matrices are read about once. On the chip at Mixtral's widths, a 1,024-
#: token segment of two layers: dense 33.3 ms of expert matmuls,
#: ``jax.lax.ragged_dot`` 24.8, this kernel 15.4 at these tiles (16.3 at
#: (256, 1024, 1024), 22 at 128 or 512 rows a tile; PERF.md section 6,
#: PR 35). 12 MB of the 16 MB of VMEM a kernel may take.
GMM_TILES = (256, 2048, 1024)


def _gmm_fits(cfg: MoeConfig) -> bool:
    """Whether the expert matmuls of a program of ``cfg`` run as the Pallas
    grouped matmul: the program runs Pallas kernels at all (the config's
    resolved ``attention_impl``), and both widths are whole tiles either
    way round (a ragged width it would mask, at tiles tuned for none).
    Every other shape (SmallThinker's 2,560 x 768 experts) and every other
    backend goes to ``jax.lax.ragged_dot``."""
    _, tk, tn = GMM_TILES
    h, f = cfg.expert_input_size, cfg.intermediate_size
    return cfg.attention_impl == "pallas" and all(
        width % tk == 0 and width % tn == 0 for width in (h, f)
    )


def _grouped(x: jnp.ndarray, w: Any, sizes: jnp.ndarray, layer, kernel=False):
    """Rows of ``x``, sorted by expert, each against its own expert's
    matrix: ``w`` is [E, in, out], ``sizes`` [E] the rows per expert. With
    ``layer`` (an int32 scalar) ``w`` is the WHOLE stack [L, E, in, out]:
    it goes to the grouped matmul as L * E groups of which only the
    layer's own are given rows, so the kernel reads the layer's experts
    where they are stored and no layer of the stack is sliced out first (a
    copy of every expert of the layer, each step, on the chip).
    ``kernel``: the Pallas grouped matmul (:func:`_gmm_fits`; the rows a
    multiple of its row tile), else XLA's."""
    if is_quantized(w):
        raise ValueError("the routed expert layer takes no quantized stack")
    if layer is not None:
        L, E = w.shape[:2]
        sizes = jax.lax.dynamic_update_slice(
            jnp.zeros((L * E,), sizes.dtype), sizes, (layer * E,)
        )
        w = w.reshape((L * E,) + w.shape[2:])
    if kernel:
        from jax.experimental.pallas.ops.tpu.megablox import gmm

        from ..ops.attention import _pallas_interpret

        return gmm(
            x, w, sizes, preferred_element_type=x.dtype, tiling=GMM_TILES,
            interpret=_pallas_interpret(),
        )
    return jax.lax.ragged_dot(x, w, sizes)


def routed_ffn(
    cfg: MoeConfig,
    lp: Dict[str, Any],
    x: jnp.ndarray,
    router_logits: "jnp.ndarray | None" = None,
    layer: "jnp.ndarray | None" = None,
) -> jnp.ndarray:
    """Top-k routed expert FFN that computes only the routed experts.

    x: [..., the experts' input width]. Each of a token's k assignments
    becomes one row; the rows are sorted by expert (a stable argsort of N*k
    small integers), the gate (where the experts have one:
    ``cfg.expert_gated``), up and down projections are grouped matmuls over
    the sorted rows, and the k results of a token are gathered back and
    summed with the router's probabilities in float32. Dropless: a row is never discarded,
    whether an expert gets every token or none. ``router_logits`` [..., E]
    are given by a family whose router reads something else than ``x``
    (SmallThinker: the layer's input, before attention; Nemotron-H: the
    layer's input, where ``x`` is its projection into the latent space the
    experts work in). ``layer``: the
    expert matrices in ``lp`` are whole stacks [L, E, ...] and this is the
    layer to compute (:func:`_grouped`).

    A config that holds a SHARE of the router's experts (``num_experts`` E
    of ``router_width``, from ``share_index * E``) routes over the whole
    width and computes the terms of its own experts: an assignment to an
    expert that is not here is sorted past the last group, belongs to no
    group of the matmuls (their sizes are the held experts' alone) and adds
    nothing to its token's sum. What the absent experts would add is left
    out. With every expert held this is the layer it was.
    """
    k, E = cfg.experts_per_token, cfg.num_experts
    share = E < cfg.router_outputs
    lead, h = x.shape[:-1], x.shape[-1]
    with jax.named_scope("router"):
        if router_logits is None:
            router_logits = x @ lp["router"]
        probs, idx = route(cfg, router_logits, lp.get("router_bias"))  # [..., k]
    with jax.named_scope("experts"):
        xf = x.reshape(-1, h)  # [N, h]
        n = xf.shape[0]
        expert = idx.reshape(n * k)
        if share:
            expert = expert - cfg.share_index * E
            here = (expert >= 0) & (expert < E)
            # E is no expert's index: past every group, one-hot of nothing
            expert = jnp.where(here, expert, E)
        order = jnp.argsort(expert, stable=True)  # rows sorted by expert
        sizes = jnp.sum(
            jax.nn.one_hot(expert, E, dtype=jnp.int32), axis=0
        )  # [E] rows per expert
        kernel = _gmm_fits(cfg)
        # the kernel takes whole row tiles: the rows past the last expert's
        # are given to no expert, stored by none and gathered by nobody
        pad = -(n * k) % GMM_TILES[0] if kernel else 0
        padded = jnp.pad(order, (0, pad)) if pad else order
        rows = xf[padded // k]  # [N*k (+ pad), h]: row r is token order[r] // k
        g = (
            _grouped(rows, lp["w_gate"], sizes, layer, kernel)
            if cfg.expert_gated else None
        )
        u = _grouped(rows, lp["w_up"], sizes, layer, kernel)
        act = _activate(cfg, u, g)
        y = _grouped(act, lp["w_down"], sizes, layer, kernel)  # [N*k (+), h]
        # back to token order: a gather by the inverse permutation
        inverse = jnp.zeros_like(order).at[order].set(
            jnp.arange(n * k, dtype=order.dtype)
        )
        y = y[inverse].reshape(n, k, h)
        if share:
            # a row of no group holds whatever the matmul left there
            y = jnp.where(here.reshape(n, k, 1), y, 0)
        out = jnp.einsum(
            "nkh,nk->nh", y.astype(jnp.float32), probs.reshape(n, k)
        )
    return out.astype(x.dtype).reshape(*lead, h)


def held_dense_ffn(
    cfg: MoeConfig, lp: Dict[str, Any], x: jnp.ndarray,
    router_logits: jnp.ndarray,
) -> jnp.ndarray:
    """The layer :func:`routed_ffn` computes, DENSE-compute and
    sparse-weight over the experts held here: every held expert runs on
    every row and the router's weights for it, zero off a row's top k and
    for an expert that is not here, weight the sum. ``lp`` holds the
    layer's own expert matrices [E, ...]. For a decode batch far smaller
    than the assignments it makes: 128 rows x 22 of 512 touch every one of
    128 held experts, so every matrix is read either way, and the grouped
    matmul, handed 2,816 assignment rows of which a quarter lie in a group,
    took 2.8 times the reads' time on the chip where this form is bound by
    them (PERF.md section 6, PR 42). The same mathematics: the share test
    holds for both."""
    E = cfg.num_experts
    with jax.named_scope("router"):
        probs, idx = route(cfg, router_logits, lp.get("router_bias"))
        # an expert that is not here is no column of the one-hot
        onehot = jax.nn.one_hot(
            idx - cfg.share_index * E, E, dtype=jnp.float32
        )
        weights = jnp.einsum("...k,...ke->...e", probs, onehot)
    with jax.named_scope("experts"):
        # the experts are the matmuls' BATCH axis, on both operands: the
        # stacks are read as they are stored, [E, in, out]. (With the rows
        # as the free axis of one operand alone, "nh,ehf->nef", the chip's
        # compiler makes a convolution over the experts and copies every
        # matrix into another layout first, 3.6 GB of temps a step.)
        lead, h = x.shape[:-1], x.shape[-1]
        xb = jnp.broadcast_to(x.reshape(-1, h), (E, math.prod(lead), h))
        u = jnp.einsum("enh,ehf->enf", xb, lp["w_up"])
        g = (
            jnp.einsum("enh,ehf->enf", xb, lp["w_gate"])
            if cfg.expert_gated else None
        )
        y = jnp.einsum("enf,efh->enh", _activate(cfg, u, g), lp["w_down"])
        out = jnp.einsum(
            "enh,ne->nh", y.astype(jnp.float32), weights.reshape(-1, E)
        )
    return out.astype(x.dtype).reshape(*lead, h)


#: the names of a gated expert's matrices in a layer's parameters
EXPERT_STACKS = ("w_gate", "w_up", "w_down")


def expert_stacks(cfg: MoeConfig):
    """The names of the matrices an expert of ``cfg`` has."""
    return EXPERT_STACKS if cfg.expert_gated else EXPERT_STACKS[1:]


def stored_expert_stack(cfg: MoeConfig, params: Dict[str, Any]) -> Any:
    """One of the expert stacks as ``params`` store it (:func:`takes_grouped`
    asks whether it is quantized): among the layers' parameters, or in a
    stack of the expert layers' own (``experts``) for a family whose layers
    do not all have experts."""
    home = params["experts"] if "experts" in params else params["layers"]
    return home[expert_stacks(cfg)[-1]]

#: rows (tokens of one traced program) from which a family that does not
#: always route takes the grouped form. The reckoning, per layer of E
#: experts of 3hf weights each, k kept a token: the dense form costs
#: max(weight reads, 2 * rows * E * 3hf / peak), the grouped one
#: max(weight reads, 2 * rows * k * 3hf / peak) plus a sort and two
#: gathers. Up to the row count at which the dense form leaves the
#: weight-read floor, rows = peak / bandwidth = 197e12 / 819e9 = 240 for
#: bfloat16 on a TPU v5e, the two read the same bytes and the dense form
#: has no sort; past it the dense form's time grows with E * rows and the
#: grouped one's stays near the floor until rows = 240 * E / k. The chip
#: (TPU v5e, Mixtral's widths, two layers' expert matmuls a segment;
#: PERF.md section 6, PR 35) puts the crossing at about 300 rows, because
#: the grouped kernel's floor stands a tenth over the dense form's: 128
#: rows 8.1 ms dense / 8.6 grouped, 256 rows 8.9 / 9.6, 512 rows 17.3 /
#: 11.2, 1,024 rows 33.3 / 15.4.
GROUPED_MIN_ROWS = 320


def _shards_experts(mesh) -> bool:
    """Whether ``mesh`` splits an expert stack's ``expert`` or ``mlp`` axis
    (parallel/mesh.py LOGICAL_RULES: ``ep``, ``tp``)."""
    if mesh is None:
        return False
    return any(
        mesh.shape.get(LOGICAL_RULES[axis], 1) > 1 for axis in ("expert", "mlp")
    )


def takes_grouped(cfg: MoeConfig, rows: int, w: Any, mesh=None) -> bool:
    """THE rule: whether the expert layers of a program traced with
    ``rows`` tokens run as grouped matmuls (:func:`routed_ffn`) or densely
    (:func:`moe_ffn`). ``w`` is one of the expert stacks as stored, ``mesh``
    the mesh the program runs on. The model step asks it while tracing
    (:func:`expert_ffn`), the engine for its counter with the bucket it
    dispatched (``/v1/stats.moe.routed_tokens``)."""
    if cfg.routed_experts:
        return rows > cfg.dense_max_rows
    # a grouped matmul over int8 stacks, or over a sharded group axis, is
    # not built
    if is_quantized(w) or _shards_experts(mesh):
        return False
    return rows >= GROUPED_MIN_ROWS


def expert_ffn(
    cfg: MoeConfig,
    lp: Dict[str, Any],
    x: jnp.ndarray,
    router_logits: "jnp.ndarray | None" = None,
    layer: "jnp.ndarray | None" = None,
    stacks: "Dict[str, Any] | None" = None,
    mesh=None,
) -> jnp.ndarray:
    """The expert layer of every family: the form :func:`takes_grouped`
    names for ``x``'s rows. ``lp`` holds the layer's parameters; a family
    that always routes puts its whole expert stacks there already
    (models/smallthinker.py). Any other brings ``stacks`` (the stacked
    layer parameters, ``[L, ...]``) and ``layer``, the STACK's layer index:
    where the grouped form is taken the expert matrices are read from
    there whole, and the slices in ``lp`` are dead."""
    if not cfg.routed_experts:
        rows = math.prod(x.shape[:-1])
        if stacks is None or not takes_grouped(cfg, rows, lp["w_gate"], mesh):
            return moe_ffn(cfg, lp, x)
        lp = {**lp, **{name: stacks[name] for name in EXPERT_STACKS}}
    return routed_ffn(cfg, lp, x, router_logits, layer)
