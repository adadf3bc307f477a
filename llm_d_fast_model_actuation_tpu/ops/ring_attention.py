"""Ring attention: causal attention over a sequence-sharded batch.

Long-context prefill/training shards the SEQUENCE axis over the mesh's
``sp`` axis. Plain GSPMD would all-gather K/V (O(S) memory per device,
defeating the sharding); ring attention instead rotates K/V chunks around
the ``sp`` ring with `ppermute` while every device accumulates
online-softmax partial results for its local Q chunk — peak memory O(S/n)
per device and the transfers ride ICI neighbor links (the "How to Scale
Your Model" recipe; same algorithm as Liu et al.'s Ring Attention).

Semantics match `ops.attention.causal_prefill_attention` exactly (causal +
right-padding mask from `seq_lens`, fp32 softmax, GQA without materialized
repeat); a parity test pins it on the virtual CPU mesh.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _chunk_attend(q, k, v, q_pos, k_pos, seq_lens, m, l, acc):
    """Fold one K/V chunk into the online-softmax state for the local Q.

    q: [b, Cq, h, d]   k/v: [b, Ck, kvh, d]   q_pos: [Cq]  k_pos: [Ck]
    m, l: [b, kvh, g, Cq, 1]   acc: [b, kvh, g, Cq, d]
    """
    b, cq, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qg = (q.astype(jnp.float32) * (d**-0.5)).astype(q.dtype)
    qg = qg.reshape(b, cq, kvh, g, d)
    logits = jnp.einsum(
        "bqngd,bknd->bngqk", qg, k, preferred_element_type=jnp.float32
    )  # [b, kvh, g, Cq, Ck]
    causal = q_pos[:, None] >= k_pos[None, :]  # [Cq, Ck]
    valid = k_pos[None, :] < seq_lens[:, None]  # [b, Ck]
    mask = causal[None, :, :] & valid[:, None, :]  # [b, Cq, Ck]
    logits = jnp.where(mask[:, None, None, :, :], logits, NEG_INF)

    m_new = jnp.maximum(m, logits.max(axis=-1, keepdims=True))
    alpha = jnp.exp(m - m_new)
    probs = jnp.exp(logits - m_new)
    l_new = l * alpha + probs.sum(axis=-1, keepdims=True)
    pv = jnp.einsum(
        "bngqk,bknd->bngqd",
        probs.astype(v.dtype),
        v,
        preferred_element_type=jnp.float32,
    )
    acc_new = acc * alpha + pv
    return m_new, l_new, acc_new


def ring_prefill_attention(
    q: jnp.ndarray,  # [b, s, heads, d], roped, sequence-sharded over `axis`
    k: jnp.ndarray,  # [b, s, kv_heads, d]
    v: jnp.ndarray,  # [b, s, kv_heads, d]
    seq_lens: jnp.ndarray,  # [b] int32 (replicated)
    mesh: Mesh,
    axis_name: str = "sp",
) -> jnp.ndarray:
    """Causal prefill attention with the sequence axis sharded over
    ``axis_name``; K/V rotate around the ring, Q stays put."""
    n = mesh.shape[axis_name]
    if n == 1:
        from .attention import causal_prefill_attention

        return causal_prefill_attention(q, k, v, seq_lens)
    b, s, h, d = q.shape
    kvh = k.shape[2]
    g = h // kvh
    assert s % n == 0, f"seq {s} must divide over {axis_name}={n}"
    chunk = s // n

    def local(q, k, v, seq_lens):
        idx = jax.lax.axis_index(axis_name)
        cq = q.shape[1]
        q_pos = idx * chunk + jnp.arange(cq, dtype=jnp.int32)

        m0 = jnp.full((b, kvh, g, cq, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, kvh, g, cq, 1), jnp.float32)
        acc0 = jnp.zeros((b, kvh, g, cq, d), jnp.float32)

        def step(t, carry):
            kv, m, l, acc = carry
            kc, vc = kv
            src = jax.lax.rem(idx - t + n, n)
            k_pos = src * chunk + jnp.arange(chunk, dtype=jnp.int32)
            m, l, acc = _chunk_attend(q, kc, vc, q_pos, k_pos, seq_lens, m, l, acc)
            # rotate the K/V chunk to the next device (neighbor link on ICI)
            kv = jax.tree.map(
                lambda x: jax.lax.ppermute(
                    x, axis_name, [(i, (i + 1) % n) for i in range(n)]
                ),
                (kc, vc),
            )
            return kv, m, l, acc

        (_, m, l, acc) = jax.lax.fori_loop(0, n, step, ((k, v), m0, l0, acc0))
        out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
        # [b, kvh, g, cq, d] -> [b, cq, h, d]
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, cq, h, d)
        return out.astype(q.dtype)

    seq = P(None, axis_name, None, None)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(seq, seq, seq, P()),
        out_specs=seq,
        check_vma=False,
    )(q, k, v, seq_lens)
