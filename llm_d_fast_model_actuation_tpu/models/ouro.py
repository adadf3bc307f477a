"""The plain reference of the looped family (Ouro, ByteDance): one stack of
L decoder layers applied U = ``loop_steps`` times a token, written as its
equations read. The served forward is ``models/llama.py`` (``loop_steps``
on ``LlamaConfig``); nothing here is taken from it.

    h_0 = E[tokens]
    for u in 0..U-1:                      the SAME L layers, U times
      x = h_u
      for l in 0..L-1:
        a = Attn_l(RMS(x; g1_l))          q, k, v = a.Wq, Wk, Wv; rotate-half
                                          RoPE on q, k at the token's position,
                                          the same in every pass; causal
                                          softmax(q.kT / sqrt(hd)).v; .Wo
        x = x + RMS(a; g2_l)              sandwich norm: the block's output is
                                          normed before the residual
        m = Wdown_l(silu(Wgate_l.RMS(x; g3_l)) * (Wup_l.RMS(x; g3_l)))
        x = x + RMS(m; g4_l)
      h_{u+1} = RMS(x; g_final)           the final norm closes EVERY pass and
                                          its output opens the next
    logits = h_U . W_head

float32 throughout at ``highest`` matmul precision, two Python loops,
whole-sequence causal attention, no cache (so no cache layer: pass u's keys
are computed from pass u's hidden states, which is what a cache layer for
each (pass, layer) serves), no scan, no kernels. The exit gate
(``early_exit_gate``) is not read: at the published ``early_exit_threshold``
of 1 no token leaves before the last pass and the logits are those of h_U.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp


def reference_logits(
    params: Dict[str, Any], cfg: Any, tokens: jnp.ndarray
) -> jnp.ndarray:
    """tokens [s] -> logits [s, vocab]."""
    f32 = jnp.float32
    s = tokens.shape[0]
    heads, kvh, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    pos = jnp.arange(s)
    half = hd // 2
    freqs = cfg.rope_theta ** (-jnp.arange(half, dtype=f32) / half)
    angles = pos.astype(f32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
    causal = pos[None, :] <= pos[:, None]

    def rms(x, w):
        var = jnp.mean(x * x, axis=-1, keepdims=True)
        return x / jnp.sqrt(var + cfg.rms_eps) * w.astype(f32)

    def rope(x):  # [s, n, hd]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)

    with jax.default_matmul_precision("highest"):
        x = params["embed"][tokens].astype(f32)
        for _ in range(cfg.loop_steps):
            for l in range(cfg.num_layers):
                lp = jax.tree.map(lambda a: a[l].astype(f32), params["layers"])
                a = rms(x, lp["attn_norm"])
                q = rope((a @ lp["wq"]).reshape(s, heads, hd))
                k = rope((a @ lp["wk"]).reshape(s, kvh, hd))
                v = (a @ lp["wv"]).reshape(s, kvh, hd)
                qg = q.reshape(s, kvh, heads // kvh, hd) * hd**-0.5
                scores = jnp.einsum("skgd,tkd->kgst", qg, k)
                scores = jnp.where(causal[None, None], scores, -jnp.inf)
                attn = jnp.einsum("kgst,tkd->skgd", jax.nn.softmax(scores, axis=-1), v)
                x = x + rms(attn.reshape(s, heads * hd) @ lp["wo"], lp["post_attn_norm"])
                m = rms(x, lp["mlp_norm"])
                m = (jax.nn.silu(m @ lp["w_gate"]) * (m @ lp["w_up"])) @ lp["w_down"]
                x = x + rms(m, lp["post_ffn_norm"])
            x = rms(x, params["final_norm"])
        return x @ params["lm_head"].astype(f32)
