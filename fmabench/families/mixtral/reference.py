"""The plain reference of Mixtral: Mistral's trunk (``families/mistral/
reference.py``) with, in place of the dense FFN, a top-k router over E
SwiGLU experts whose top-k scores are renormalised by a softmax. Every
expert is computed for every token and weighted, zero off the top k.
float32, no kernels; imports nothing of the program."""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from fmabench import spec

_trunk = spec.sibling_part(__file__, "mistral", "reference")

#: the router stays as it is: the control rounds the matmuls of the trunk
#: and of the experts
MATMUL_WEIGHTS = _trunk.MATMUL_WEIGHTS


def _expert_weights(d: Dict[str, Any], ks: Sequence[Any]) -> Dict[str, Any]:
    h, L, f = d["hidden_size"], d["num_layers"], d["intermediate_size"]
    E = d["num_experts"]
    return {
        "router": _trunk.dense(ks[0], (L, h, E), h),
        "w_gate": _trunk.dense(ks[1], (L, E, h, f), h),
        "w_up": _trunk.dense(ks[2], (L, E, h, f), h),
        "w_down": _trunk.dense(ks[3], (L, E, f, h), f),
    }


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    return _trunk.init_weights(seed, d, 4, _expert_weights)


def _routed_ffn(d, lp, x):
    logits = x @ lp["router"].astype(jnp.float32)  # [s, E]
    top_vals, top_idx = jax.lax.top_k(logits, d["experts_per_token"])
    probs = jax.nn.softmax(top_vals, axis=-1)
    weights = jnp.sum(
        jax.nn.one_hot(top_idx, d["num_experts"], dtype=jnp.float32)
        * probs[..., None], axis=-2,
    )  # [s, E], zero off the top k

    def one_expert(acc, ew):
        gate, up, down, w = ew
        return acc + _trunk.swiglu(x, gate, up, down) * w[:, None], None

    out, _ = jax.lax.scan(
        one_expert, jnp.zeros_like(x),
        (lp["w_gate"], lp["w_up"], lp["w_down"], weights.T),
    )
    return out


def forward_logits(d, weights, ids, length, rows):
    return _trunk.forward_logits(d, weights, ids, length, rows, ffn=_routed_ffn)
