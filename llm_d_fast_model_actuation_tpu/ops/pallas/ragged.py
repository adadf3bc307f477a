"""Ragged paged attention as a Pallas TPU kernel.

Attention for a token-packed mixed batch (PAPERS.md "Ragged Paged
Attention"): one flat ``[tokens]`` buffer whose rows are drawn from many
sequences — prefill segments, suffix continuations, and decode steps
together — each row attending over its OWN sequence's paged KV at
positions <= its own. The XLA twin in ``ops/attention.py`` gathers every
row's full ``ctx = pages_per_seq * page_size`` context (O(tokens * ctx)
HBM traffic regardless of real lengths); this kernel walks only the
``ceil((pos + 1) / page_size)`` pages each row block actually needs,
double-buffering the HBM->VMEM page DMA behind the per-page
flash-attention accumulation — the same discipline as the decode kernel
(ops/pallas/decode.py), generalized from one query row to a block.

Packing contract (the engine's packer upholds it, engine/engine.py):

  * rows belonging to one sequence are CONTIGUOUS in the buffer and
    carry consecutive positions (a segment is one run of tokens);
  * every sequence's run starts on a ``block_rows`` boundary, so each
    kernel block belongs to AT MOST ONE sequence — that alignment is
    what turns "ragged" into a regular grid: block metadata is just
    (page-table row, first position, valid rows), scalar-prefetched;
  * padding rows (``row_slot < 0``) fill alignment gaps and the buffer
    tail; a fully-padded block does no page DMA and writes zeros.

Grid: one program per row block. GQA reads each KV head's page tile once
per block and loops the query heads of its group over it — repeated KV
heads are never materialized, mirroring the decode kernel. As there, the
kernel takes the whole stored pool ``[layers, num_pages, page_size,
kv_heads * head_dim]`` and a layer index and DMAs ``pool[layer, page]``
tiles (same Mosaic constraint, ``decode.check_kernel_shape``); q / the
output are passed head-major ``[heads, tokens, head_dim]`` so a query head
is a leading-axis index, not a sublane-strided slice of a 3-D tile (which
Mosaic cannot lay out at head_dim 64).

Meshes: the kernel body is a single-device program (it walks the page
pool with raw HBM DMA), and :func:`ragged_paged_attention_pallas_sharded`
ports it to tp meshes by wrapping it in ``shard_map`` over the ``tp``
axis — the axis the engine already shards KV heads and the page pool's
fused axis over (``ops/attention.py:POOL_SPEC``). Each shard walks its
OWN head slice of the page pool with the
same replicated block metadata; head-sharded GQA needs no cross-shard
softmax, because every query head's softmax completes inside the shard
that owns its KV-head group. Routing between the two entry points (and
the XLA twin) lives in ``ops/attention.py:ragged_paged_attention``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from ..attention import POOL_SPEC
from .decode import check_kernel_shape, shard_over_tp

NEG_INF = -1e30


def _ragged_kernel(
    # scalar prefetch
    meta_ref,  # [num_blocks, 3] SMEM — (row_slot, pos0, nvalid) per block
    page_table_ref,  # [rows, pages_per_seq] SMEM
    layer_ref,  # [1] SMEM — the layer of the pool this call reads
    # inputs
    q_ref,  # [heads, block_rows, head_dim] VMEM
    k_hbm,  # [layers, num_pages, page_size, kv_heads * head_dim] HBM/ANY
    v_hbm,  # same
    # output
    o_ref,  # [heads, block_rows, head_dim] VMEM
    # scratch
    k_buf,  # [2, page_size, kv_heads * head_dim] VMEM
    v_buf,  # same
    sems,  # DMA sems [2, 2]
    *,
    block_rows: int,
    page_size: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
):
    i = pl.program_id(0)
    group = num_heads // num_kv_heads
    slot = jnp.maximum(meta_ref[i, 0], 0)  # clamped; nvalid=0 masks all
    pos0 = meta_ref[i, 1]
    nvalid = meta_ref[i, 2]
    layer = layer_ref[0]
    # pages holding cache entries [0, pos_last + 1): the block's last
    # valid row sits at absolute position pos0 + nvalid - 1, and its own
    # KV was scattered before the kernel ran (scatter-first semantics)
    num_pages = jax.lax.div(pos0 + nvalid + page_size - 1, page_size)

    def page_dma(buf, hbm, buf_slot, p, sem_row):
        return pltpu.make_async_copy(
            hbm.at[layer, page_table_ref[slot, p]],
            buf.at[buf_slot],
            sems.at[sem_row, buf_slot],
        )

    @pl.when(num_pages > 0)
    def _():
        page_dma(k_buf, k_hbm, 0, 0, 0).start()
        page_dma(v_buf, v_hbm, 0, 0, 1).start()

    scale = head_dim**-0.5
    row = jax.lax.broadcasted_iota(jnp.int32, (block_rows, 1), 0)
    q_pos = pos0 + row  # [B, 1] absolute position per row
    row_valid = row < nvalid  # [B, 1]

    # Online-softmax state carried per QUERY head (python tuples over the
    # static head axis — in-kernel scatter is not lowerable on TPU,
    # whole-array replacement is). Each KV head's page tile is read once
    # per page and reused by every query head of its group.
    def body(p, carry):
        ms, ls, accs = carry  # tuples of [B, 1], [B, 1], [B, d]
        buf_slot = jax.lax.rem(p, 2)

        @pl.when(p + 1 < num_pages)
        def _():
            nxt = jax.lax.rem(p + 1, 2)
            page_dma(k_buf, k_hbm, nxt, p + 1, 0).start()
            page_dma(v_buf, v_hbm, nxt, p + 1, 1).start()

        page_dma(k_buf, k_hbm, buf_slot, p, 0).wait()
        page_dma(v_buf, v_hbm, buf_slot, p, 1).wait()

        tok0 = p * page_size
        tok_idx = tok0 + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1
        )
        # causal over the row's own sequence: cache entry <= own position
        mask = (tok_idx <= q_pos) & row_valid  # [B, page_size]

        new_ms = list(ms)
        new_ls = list(ls)
        new_accs = list(accs)
        for g in range(num_kv_heads):
            lanes = pl.ds(g * head_dim, head_dim)
            kg = k_buf[buf_slot, :, lanes].astype(jnp.float32)  # [page, d]
            vg = v_buf[buf_slot, :, lanes].astype(jnp.float32)
            for j in range(group):
                h = g * group + j
                qh = q_ref[h].astype(jnp.float32) * scale  # [B, d]
                logits = jax.lax.dot_general(
                    qh, kg, (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [B, page_size]
                logits = jnp.where(mask, logits, NEG_INF)
                m_cur = jnp.maximum(
                    new_ms[h], logits.max(axis=-1, keepdims=True)
                )
                alpha = jnp.exp(new_ms[h] - m_cur)
                probs = jnp.exp(logits - m_cur)
                l_cur = new_ls[h] * alpha + probs.sum(axis=-1, keepdims=True)
                pv = jax.lax.dot_general(
                    probs, vg, (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32,
                )  # [B, d]
                new_ms[h] = m_cur
                new_ls[h] = l_cur
                new_accs[h] = new_accs[h] * alpha + pv
        return tuple(new_ms), tuple(new_ls), tuple(new_accs)

    m0 = tuple(
        jnp.full((block_rows, 1), NEG_INF, jnp.float32)
        for _ in range(num_heads)
    )
    l0 = tuple(
        jnp.zeros((block_rows, 1), jnp.float32) for _ in range(num_heads)
    )
    acc0 = tuple(
        jnp.zeros((block_rows, head_dim), jnp.float32)
        for _ in range(num_heads)
    )
    ms, ls, accs = jax.lax.fori_loop(0, num_pages, body, (m0, l0, acc0))

    for h in range(num_heads):
        l = ls[h]
        out = jnp.where(l > 0, accs[h] / jnp.where(l > 0, l, 1.0), 0.0)
        o_ref[h] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_rows", "interpret"))
def ragged_paged_attention_pallas(
    q: jnp.ndarray,  # [tokens, heads, head_dim] — flat packed buffer
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [rows, pages_per_seq] int32
    row_slot: jnp.ndarray,  # [tokens] int32; -1 = padding row
    positions: jnp.ndarray,  # [tokens] int32 absolute positions
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    block_rows: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    tokens, num_heads, head_dim = q.shape
    _, _, page_size, fused = k_pages.shape
    num_kv_heads = fused // head_dim
    if tokens % block_rows != 0:
        raise ValueError(
            f"tokens ({tokens}) must be a multiple of block_rows "
            f"({block_rows}) — the engine pads the packed buffer"
        )
    if not interpret:  # the interpreter has no tiling to satisfy
        check_kernel_shape(num_kv_heads, head_dim)
    nb = tokens // block_rows

    # Per-block metadata from the per-row arrays, relying on the packing
    # contract (module docstring): a block's valid rows are a prefix, all
    # of one sequence, position-consecutive — so (first slot, first
    # position, count) describes the whole block.
    rs = row_slot.reshape(nb, block_rows).astype(jnp.int32)
    nvalid = (rs >= 0).sum(axis=1).astype(jnp.int32)
    pos0 = positions.reshape(nb, block_rows)[:, 0].astype(jnp.int32)
    meta = jnp.stack(
        [rs[:, 0], jnp.where(nvalid > 0, pos0, 0), nvalid], axis=1
    )

    kernel = functools.partial(
        _ragged_kernel,
        block_rows=block_rows,
        page_size=page_size,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
    )
    block_spec = pl.BlockSpec(
        (num_heads, block_rows, head_dim),
        lambda i, *_: (0, i, 0),
        memory_space=pltpu.VMEM,
    )
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(nb,),
        in_specs=[
            block_spec,
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=block_spec,
        scratch_shapes=[
            pltpu.VMEM((2, page_size, fused), k_pages.dtype),
            pltpu.VMEM((2, page_size, fused), v_pages.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
        ],
    )
    qt = q.transpose(1, 0, 2)  # head-major: [heads, tokens, head_dim]
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        grid_spec=grid_spec,
        interpret=interpret,
        name="ragged_paged_attention",
    )(
        meta,
        page_table.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        qt,
        k_pages,
        v_pages,
    )
    return out.transpose(1, 0, 2)


def ragged_paged_attention_pallas_sharded(
    mesh,
    q: jnp.ndarray,  # [tokens, heads, head_dim]
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [rows, pages_per_seq] int32
    row_slot: jnp.ndarray,  # [tokens] int32; -1 = padding row
    positions: jnp.ndarray,  # [tokens] int32 absolute positions
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    block_rows: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """The kernel above on a tp mesh (``decode.shard_over_tp``): each
    shard runs the unmodified single-device kernel over its own head slice
    of the pool; the page table and the per-row (slot, position) metadata
    are replicated, and the per-block scalar-prefetch metadata is
    recomputed identically on every shard.

    Composes with jit: the mixed program calls this inside its traced
    body and GSPMD reshards inputs to the declared specs (a no-op for
    activations already sharded over heads). ``interpret=True`` runs the
    per-shard kernel in interpreter mode — how CPU tp-meshes validate
    bit-exactness against the XLA twin (tests/test_ragged.py).
    """
    kernel = functools.partial(
        ragged_paged_attention_pallas,
        block_rows=block_rows,
        interpret=interpret,
    )
    return shard_over_tp(
        mesh,
        kernel,
        in_specs=(
            P(None, "tp", None),  # q: query heads sharded
            POOL_SPEC,  # k_pages: fused kv-head axis sharded
            POOL_SPEC,  # v_pages
            P(None, None),  # page_table: replicated
            P(None),  # row_slot: replicated
            P(None),  # positions: replicated
            P(),  # layer: replicated
        ),
        out_specs=P(None, "tp", None),
    )(q, k_pages, v_pages, page_table, row_slot, positions, layer)
