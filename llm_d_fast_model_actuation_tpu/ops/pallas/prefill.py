"""Blockwise causal (flash) prefill attention as a Pallas TPU kernel.

The XLA reference materializes the full [batch, heads, seq, seq] logits
tensor — O(S^2) HBM traffic and VMEM pressure. This kernel runs the
online-softmax recurrence over a (batch, head, q-block, k-block) grid: only
one [block, head_dim] K tile and V tile are VMEM-resident per step (O(S)
footprint, so long contexts fit), the running max / denominator / output
accumulator live in VMEM scratch that persists across the k-block steps, and
K blocks strictly above the causal diagonal skip their compute entirely.

GQA is handled in the index map: query head h reads KV head h // group, so
repeated KV heads are never materialized.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _prefill_kernel(
    seq_lens_ref,  # [batch] SMEM (scalar prefetch)
    q_ref,  # [1, 1, Bq, d] VMEM
    k_ref,  # [1, 1, Bk, d] VMEM
    v_ref,  # [1, 1, Bk, d] VMEM
    o_ref,  # [1, 1, Bq, d] VMEM (revisited across k blocks)
    m_scr,  # [Bq, 1] f32 VMEM scratch
    l_scr,  # [Bq, 1] f32 VMEM scratch
    acc_scr,  # [Bq, d] f32 VMEM scratch
    *,
    block_q: int,
    block_k: int,
    head_dim: int,
    window: int = 0,
):
    b = pl.program_id(0)
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    num_k = pl.num_programs(3)
    seq_len = seq_lens_ref[b]

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full((block_q, 1), NEG_INF, jnp.float32)
        l_scr[:] = jnp.zeros((block_q, 1), jnp.float32)
        acc_scr[:] = jnp.zeros((block_q, head_dim), jnp.float32)

    # causal: this K block contributes only if its first position can be seen
    # by the last query position of the q block
    live = ki * block_k <= qi * block_q + block_q - 1
    if window:
        # sliding window: and if its last position is still inside the
        # window of the q block's first query (which counts itself)
        live = live & (ki * block_k + block_k - 1 > qi * block_q - window)

    @pl.when(live)
    def _():
        q = q_ref[0, 0].astype(jnp.float32) * (head_dim**-0.5)  # [Bq, d]
        k = k_ref[0, 0].astype(jnp.float32)  # [Bk, d]
        v = v_ref[0, 0].astype(jnp.float32)
        logits = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # [Bq, Bk]
        q_pos = qi * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0
        )
        k_pos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1
        )
        mask = (k_pos <= q_pos) & (k_pos < seq_len)
        if window:
            mask = mask & (k_pos > q_pos - window)
        logits = jnp.where(mask, logits, NEG_INF)

        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, logits.max(axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        probs = jnp.exp(logits - m_new)
        m_scr[:] = m_new
        l_scr[:] = l_scr[:] * alpha + probs.sum(axis=-1, keepdims=True)
        pv = jax.lax.dot_general(
            probs, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        acc_scr[:] = acc_scr[:] * alpha + pv

    @pl.when(ki == num_k - 1)
    def _():
        l = l_scr[:]
        out = jnp.where(l > 0, acc_scr[:] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_q", "interpret", "window"))
def causal_prefill_attention_pallas(
    q: jnp.ndarray,  # [batch, seq, heads, head_dim]
    k: jnp.ndarray,  # [batch, seq, kv_heads, head_dim]
    v: jnp.ndarray,
    seq_lens: jnp.ndarray,  # [batch] int32
    block_q: int = 128,
    interpret: bool = False,
    window: int = 0,  # > 0: query i sees keys j with i - window < j <= i
) -> jnp.ndarray:
    batch, seq, num_heads, head_dim = q.shape
    num_kv_heads = k.shape[2]
    group = num_heads // num_kv_heads
    block_q = min(block_q, seq)
    block_k = block_q
    if seq % block_q != 0:
        raise ValueError(f"seq ({seq}) must be a multiple of block_q ({block_q})")

    kernel = functools.partial(
        _prefill_kernel, block_q=block_q, block_k=block_k, head_dim=head_dim,
        **({"window": int(window)} if window else {}),
    )
    # head-major layout so the tiled (last two) dims are [seq, head_dim]
    qt = q.transpose(0, 2, 1, 3)  # [b, h, s, d]
    kt = k.transpose(0, 2, 1, 3)  # [b, kvh, s, d]
    vt = v.transpose(0, 2, 1, 3)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(batch, num_heads, seq // block_q, seq // block_k),
        in_specs=[
            pl.BlockSpec(
                (1, 1, block_q, head_dim),
                lambda b, h, i, j, *_: (b, h, i, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, head_dim),
                lambda b, h, i, j, *_: (b, h // group, j, 0),
                memory_space=pltpu.VMEM,
            ),
            pl.BlockSpec(
                (1, 1, block_k, head_dim),
                lambda b, h, i, j, *_: (b, h // group, j, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=pl.BlockSpec(
            (1, 1, block_q, head_dim),
            lambda b, h, i, j, *_: (b, h, i, 0),
            memory_space=pltpu.VMEM,
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, head_dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid_spec=grid_spec,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(seq_lens.astype(jnp.int32), qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
