"""Training step (next-token LM loss) for the model zoo.

The serving framework's main job is inference actuation, but the full
training step exists for two reasons: (a) the multi-chip dry-run contract
compiles it over a real dp/sp/tp mesh, exercising every sharding the engine
uses plus gradient collectives; (b) it makes the model zoo usable for
fine-tune-then-serve loops.

All control flow is compiler-friendly: one `lax.scan` over layers, masked
loss (no dynamic shapes), optional `jax.checkpoint` on the layer body to
trade FLOPs for HBM at long sequence lengths.
"""

from __future__ import annotations

from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import optax

from ..ops.attention import causal_prefill_attention
from .llama import (  # noqa: F401
    LlamaConfig,
    _embed_tokens,
    _ffn,
    _norm,
    _post,
    _project_qkv,
    _scan_layers,
    param_logical_axes,
)
from ..ops.rope import rope_table


def forward_train(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,  # [b, s]
    seq_lens: jnp.ndarray,  # [b]
    remat: bool = True,
    mesh: Optional[Any] = None,
) -> jnp.ndarray:
    """Dense causal forward (no KV cache), logits fp32 [b, s, vocab].

    With a `mesh` whose ``sp`` axis is > 1, attention runs as RING attention
    (ops/ring_attention.py): K/V chunks rotate the sp ring instead of GSPMD
    all-gathering the whole sequence — the long-context path."""
    b, s = tokens.shape
    cos_tab, sin_tab = rope_table(
        cfg.max_seq_len, cfg.head_dim, cfg.rope_theta, cfg.rope_scaling
    )
    positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
    # the Gemma-family helpers keep training numerically identical to the
    # serving forward ((1+w) norms, sandwich norms, scaled embeddings)
    x = _embed_tokens(cfg, params, tokens)
    use_ring = mesh is not None and mesh.shape.get("sp", 1) > 1
    if use_ring:
        from ..ops.ring_attention import ring_prefill_attention

    def layer(x, lp):
        h = _norm(cfg, x, lp["attn_norm"])
        q, k, v = _project_qkv(cfg, lp, h, positions, cos_tab, sin_tab)
        if use_ring:
            attn = ring_prefill_attention(q, k, v, seq_lens, mesh)
        else:
            attn = causal_prefill_attention(q, k, v, seq_lens)
        x = x + _post(cfg, lp, "post_attn_norm", attn.reshape(b, s, cfg.q_dim) @ lp["wo"])
        h = _norm(cfg, x, lp["mlp_norm"])
        x = x + _post(cfg, lp, "post_ffn_norm", _ffn(cfg, lp, h))
        return x, None

    body = jax.checkpoint(layer) if remat else layer
    # the serving forward's stack: once, or a looped config's passes with
    # the final norm closing each (no cache here, so no cache layer)
    (x,), _ = _scan_layers(
        cfg, lambda c, scanned: ((body(c[0], scanned[0])[0],), None), (x,), params
    )
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return (x @ head).astype(jnp.float32)


def lm_loss(
    params: Dict[str, Any],
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    seq_lens: jnp.ndarray,
    mesh: Optional[Any] = None,
) -> jnp.ndarray:
    """Masked next-token cross-entropy."""
    logits = forward_train(params, cfg, tokens, seq_lens, mesh=mesh)
    targets = tokens[:, 1:]
    logits = logits[:, :-1]
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    b, sm1 = targets.shape
    mask = (jnp.arange(sm1)[None, :] < (seq_lens - 1)[:, None]).astype(jnp.float32)
    return (nll * mask).sum() / jnp.maximum(mask.sum(), 1.0)


class TrainState(NamedTuple):
    step: jnp.ndarray
    params: Dict[str, Any]
    opt_state: Any


def make_optimizer(lr: float = 3e-4) -> optax.GradientTransformation:
    return optax.adamw(lr, weight_decay=0.01)


def make_train_state(
    params: Dict[str, Any], optimizer: Optional[optax.GradientTransformation] = None
) -> TrainState:
    optimizer = optimizer or make_optimizer()
    return TrainState(
        step=jnp.zeros((), jnp.int32),
        params=params,
        opt_state=optimizer.init(params),
    )


def train_step(
    state: TrainState,
    cfg: LlamaConfig,
    tokens: jnp.ndarray,
    seq_lens: jnp.ndarray,
    optimizer: Optional[optax.GradientTransformation] = None,
    mesh: Optional[Any] = None,
) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
    """One optimizer step. Under a mesh, data arrays sharded (dp, sp) and
    params sharded per `param_logical_axes` make GSPMD insert the grad
    all-reduces — except attention under sp>1, which runs as explicit ring
    attention (pass `mesh`); no other hand-written collectives."""
    optimizer = optimizer or make_optimizer()
    loss, grads = jax.value_and_grad(lm_loss)(
        state.params, cfg, tokens, seq_lens, mesh
    )
    updates, opt_state = optimizer.update(grads, state.opt_state, state.params)
    params = optax.apply_updates(state.params, updates)
    return (
        TrainState(step=state.step + 1, params=params, opt_state=opt_state),
        {"loss": loss},
    )
