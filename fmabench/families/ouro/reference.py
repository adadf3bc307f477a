"""The plain reference of Ouro, U = ``loop_steps`` passes over L layers:

    h_0 = E[tokens]
    for u in 0..U-1:                    the SAME L layers, U times
      x = h_u
      for l in 0..L-1:
        a = Attn_l(RMS(x; g1_l))        rotate-half RoPE on q, k at the token's
                                        position, the same in every pass; the
                                        full causal mask; 16 heads, 16 KV heads
        x = x + RMS(a; g2_l)            the block's output is normed before the
                                        residual
        m = SwiGLU_l(RMS(x; g3_l))
        x = x + RMS(m; g4_l)
      h_{u+1} = RMS(x; g_final)         the final norm closes EVERY pass
    logits = h_U . W_head

Straightforward ``jax.numpy`` in float32 (the caller sets ``highest``
precision), no kernels, no cache (so no cache layer: pass u's keys come from
pass u's hidden states), no batching; imports nothing of the program. One
concession to 2.67 B parameters, which changes no number: the weights stay
the bfloat16 values they are and a layer's are raised to float32 where it is
used (10.7 GB whole), so both loops are scans over the stacked layers.

The weights are the trunk's (``families/mistral/reference.py``: the
initialisation the program documents, the same seed through the same public
``jax.random`` calls) with the two sandwich norms at one, and the exit gate,
made as the program documents it too (so that every leaf the program holds
is here to compare) and read by nothing: at the published threshold of 1 no
served logit depends on it.
"""

from __future__ import annotations

from typing import Any, Dict, Sequence

import jax
import jax.numpy as jnp

from fmabench import spec

_trunk = spec.sibling_part(__file__, "mistral", "reference")

#: what the int8 control rounds, as paths into the weights
MATMUL_WEIGHTS = _trunk.MATMUL_WEIGHTS


def _ffn_weights(d: Dict[str, Any], ks: Sequence[Any]) -> Dict[str, Any]:
    ones = jnp.ones((d["num_layers"], d["hidden_size"]), dtype=jnp.bfloat16)
    return {
        **_trunk.swiglu_weights(d, ks),
        "post_attn_norm": ones, "post_ffn_norm": ones,
    }


def init_weights(seed, d: Dict[str, Any]) -> Dict[str, Any]:
    h = d["hidden_size"]
    k_head = jax.random.split(jax.random.key(seed), 3)[2]
    gate = {
        "w": _trunk.dense(jax.random.fold_in(k_head, 1), (h, 1), h),
        "b": jnp.zeros((1,), dtype=jnp.bfloat16),
    }
    return {**_trunk.init_weights(seed, d, 3, _ffn_weights), "early_exit_gate": gate}


def forward_logits(d, weights, ids, length, rows):
    """float32 logits at the positions ``rows`` of one sequence.

    ids: [S] padded token ids; length: valid tokens; rows: [R] positions.
    """
    f32 = jnp.float32
    S = ids.shape[0]
    heads, kvh, hd = d["num_heads"], d["num_kv_heads"], d["head_dim"]
    eps, theta = d["rms_eps"], d["rope_theta"]
    rms, rope = _trunk.rms, _trunk.rope
    pos = jnp.arange(S)
    mask = (pos[None, :] <= pos[:, None]) & (pos[None, :] < length)

    def layer(x, lp):
        a = rms(x, lp["attn_norm"], eps)
        q = rope((a @ lp["wq"].astype(f32)).reshape(S, heads, hd), pos, theta)
        k = rope((a @ lp["wk"].astype(f32)).reshape(S, kvh, hd), pos, theta)
        v = (a @ lp["wv"].astype(f32)).reshape(S, kvh, hd)
        qg = q.reshape(S, kvh, heads // kvh, hd) * hd**-0.5
        scores = jnp.einsum("skgd,tkd->kgst", qg, k)
        scores = jnp.where(mask[None, None], scores, -jnp.inf)
        attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
        attn = attn.reshape(S, heads * hd) @ lp["wo"].astype(f32)
        x = x + rms(attn, lp["post_attn_norm"], eps)
        m = _trunk.swiglu_ffn(d, lp, rms(x, lp["mlp_norm"], eps))
        return x + rms(m, lp["post_ffn_norm"], eps), None

    def one_pass(h, _):
        x, _ = jax.lax.scan(layer, h, weights["layers"])
        return rms(x, weights["final_norm"], eps), None

    h, _ = jax.lax.scan(
        one_pass, weights["embed"][ids].astype(f32), None, length=d["loop_steps"]
    )
    return h[rows] @ weights["lm_head"].astype(f32)
