"""Paged decode attention as a Pallas TPU kernel.

One decode step against the paged KV cache. The XLA reference in
`ops/attention.py` gathers ALL `pages_per_seq` pages for every sequence and
materializes GQA-repeated K/V — O(batch * ctx_max) HBM traffic regardless of
actual sequence lengths. This kernel reads only the pages each sequence
actually occupies (`ceil(seq_len / page_size)` of them), double-buffering the
HBM->VMEM page DMA behind the per-tile flash-attention accumulation, and
never materializes repeated KV heads. Decode is HBM-bandwidth-bound, so
bytes-not-read is time-not-spent.

The walk's pipeline. The grid is ``(batch,)``, one sequence a grid step, run
one after another; a step of a sequence's walk is one tile (a page, or
``block_pages`` pages). While tile p is in the softmax update, tile p + 1 is
in flight into the other half of a two-half VMEM buffer — and the pipeline
does not drain where a sequence ends: a live sequence's LAST tile step starts
the FIRST tile of the next live sequence (a scalar look-ahead over the
lengths, past empty slots and slots whose query sees no cached position), so
only the first live sequence of a call waits for a copy with nothing to
compute meanwhile. What persists across grid steps is the scratch: the two
buffer halves, the DMA semaphores, and one SMEM count of the tiles the call
has walked so far, from which a grid step knows which half its first tile is
in and whether a sequence before it has already started that copy (the
count is not 0). The last live sequence starts nothing, so every copy
started is waited for inside the call (PERF.md section 6, PR 48).

The dots take float32 operands, the tile's K and V slices cast from the
pool's dtype. On the v5e that is already one bfloat16 pass of the MXU with
float32 accumulation: handing the dots a bfloat16 pool's slices as stored
served the same tokens and was 0-4% slower (PERF.md section 6, PR 48).

Layout contract (the engine's KV pool as it is stored,
engine/kv_cache.py:PagePool.pool_shape):
  k_pages, v_pages: [num_layers, num_pages, page_size, kv_heads * head_dim]
                    (HBM; the WHOLE pool, never a layer cut out of it)
  layer:            int32 scalar  (scalar-prefetched)
  page_table:       [batch, pages_per_seq] int32  (scalar-prefetched)
  seq_lens:         [batch] int32, length INCLUDING the new token
  q:                [batch, heads, head_dim]

The kernel DMAs ``pool[layer, page]``, a ``[page_size, kv_heads * head_dim]``
tile, straight from the stored array: the lane axis is kv_heads*head_dim
wide and a KV head is a static lane slice of it. Mosaic refuses to DMA or
slice a memref whose minor dim is narrower than the 128-lane tile, so a pool
with head_dim minor could not serve head_dim 64; the fused one serves every
shape with ``kv_heads * head_dim % 128 == 0`` (:func:`check_kernel_shape`,
a constraint of these kernels, not of the stored layout).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

#: TPU lane width: the fused kv_heads*head_dim page row must fill whole lanes.
LANES = 128

#: tokens a step of the inline decode kernel's walk holds in one VMEM tile
DECODE_TILE_TOKENS = 128


def decode_block_pages(page_size: int) -> int:
    """Pages a step of the inline decode kernel's walk keeps in flight: as
    many as fill a 128-token tile of the pool it is handed (16-token pages:
    8). At a page a step the walk is bound by the latency of one page's DMA
    and a step's fixed cost, not by bytes, at every context a cell has
    (PERF.md section 6, PRs 28 and 31); a context shorter than a tile costs
    at most the spare pages of one step, read again and masked."""
    return max(1, DECODE_TILE_TOKENS // page_size)


def pallas_shape_ok(num_kv_heads: int, head_dim: int) -> bool:
    """Can Mosaic compile the paged kernels (decode and ragged) for this
    per-device KV shape?"""
    return num_kv_heads > 0 and (num_kv_heads * head_dim) % LANES == 0


def check_kernel_shape(num_kv_heads: int, head_dim: int) -> None:
    if not pallas_shape_ok(num_kv_heads, head_dim):
        raise ValueError(
            f"pallas paged attention needs kv_heads * head_dim (per device) "
            f"to be a multiple of {LANES}; got {num_kv_heads} * {head_dim}"
        )


def shard_over_tp(mesh, kernel, in_specs, out_specs):
    """``kernel`` (a per-device Pallas call) on a tp mesh; itself when
    ``mesh`` is None. GSPMD cannot partition a Mosaic kernel, so it runs
    under ``shard_map`` over the ``tp`` axis — the axis the engine shards
    query heads, KV heads and the page pool's fused axis over — each shard
    seeing its own head slice and the replicated page table / lengths.
    Head-sharded GQA needs no cross-shard softmax: every query head's
    softmax completes inside the shard that owns its KV-head group
    (``kv_heads % tp == 0``, as the NamedSharding placement already
    requires)."""
    if mesh is None:
        return kernel
    # the pallas body is opaque to the varying-axes checker; the out_specs
    # are the contract the caller relies on
    return jax.shard_map(
        kernel, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def _decode_kernel(
    # scalar prefetch
    page_table_ref,  # [batch, pages_per_seq] SMEM
    len_ref,  # [batch] SMEM — cache entries attended (positions < len)
    layer_ref,  # [1] SMEM — the layer of the pool this call reads
    # inputs
    q_ref,  # [1, heads, head_dim] VMEM
    *refs,
    page_size: int,
    num_heads: int,
    num_kv_heads: int,
    head_dim: int,
    inline: bool,
    window: int = 0,
    block_pages: int = 1,
    latent: int = 0,
    scale: float = 0.0,
    queries: int = 1,
):
    """Online-softmax over the sequence's pages. With ``inline`` the new
    token's K/V arrive as two extra inputs ([1, 1, kv_heads * head_dim]
    VMEM, not yet in the cache — the engine defers cache scatters; see
    ops/attention.py:paged_decode_attention_inline) and are folded into the
    running (m, l, acc) state after the page walk.

    The walk is ONE software pipeline over the call's live sequences (the
    module docstring): step p of this sequence's walk lives in buffer half
    ``(walked + p) % 2``, ``walked`` the SMEM count of tiles the grid steps
    before this one walked; every step starts the copies of the walk's next
    tile into the other half — this sequence's step p + 1, or from its last
    step the next live sequence's step 0 — and then waits for its own. Only
    a sequence with none live before it (``walked`` 0) starts its own step
    0. A wait rebuilds the descriptor its start built, from the same table
    row. Every mode below changes what a tile is or where a walk starts,
    not this.

    With ``window`` (a layer of sliding-window attention) the query sees
    only the last ``window`` positions, its own among them: the walk STARTS
    at the first page that holds a visible key, and a row of the page table
    is read as a ring, logical page p at column ``p % pages_per_seq`` — a
    plain full-length row is the ring that never wraps.

    With ``block_pages`` > 1 a step of the walk is that many pages: their
    DMAs are in flight together, each into its own rows of one
    ``[block_pages * page_size, ...]`` tile, and the softmax update runs
    once over the tile. A context of a thousand tokens is scores of
    16-token pages; at one page a step the walk is bound by the latency of
    a 16 KB DMA and the fixed cost of a step, not by bytes. The serving
    path's value is :func:`decode_block_pages`.

    With ``latent`` (latent attention in its absorbed form: ONE shared "KV
    head" whose key is the page's whole row, ``head_dim`` lanes, and whose
    value is the first ``latent`` lanes of the same row) there is no V pool,
    no V tile and no V copy: a page is read once and serves both, the new
    token arrives as one row, the output is ``latent`` wide, and ``scale``
    is the model's (the row's width is not the width the scores are scaled
    by).

    With ``queries`` n > 1 (inline only: a verify step of speculative
    decoding) a slot brings n new positions, ``kv_len + 0 .. n - 1``: q_ref
    holds, a KV head, the n * group query rows of its group, query by query,
    the new K and V are n rows, and o_ref is laid out as q_ref. The pages
    are walked ONCE for all n: they hold the positions before ``kv_len``,
    which every query sees (a window layer's query i from ``lo + i`` on, so
    the walk starts at query 0's first page); new row j is folded into the
    rows of queries j and later."""
    if latent:
        # k_hbm: [layers, num_pages, page_size, head_dim]; o_ref: [1, heads,
        # latent]; k_buf: [2, block_pages * page_size, head_dim]
        if inline:
            knew_ref, *refs = refs
        k_hbm, o_ref, k_buf, sems, walked = refs
        pools = ((k_buf, k_hbm),)
    else:
        if inline:
            knew_ref, vnew_ref, *refs = refs
        # k_hbm, v_hbm: [layers, num_pages, page_size, kv_heads * head_dim] HBM/ANY
        # o_ref: [1, heads, head_dim] VMEM
        # k_buf, v_buf: [2, block_pages * page_size, kv_heads * head_dim] VMEM
        # sems: DMA [2, 2] (one page a step) or [2, 2, block_pages]
        # walked: SMEM [1], tiles the call has walked before this grid step
        k_hbm, v_hbm, o_ref, k_buf, v_buf, sems, walked = refs
        pools = ((k_buf, k_hbm), (v_buf, v_hbm))
    value_dim = latent or head_dim
    blocked = block_pages > 1
    tile = block_pages * page_size
    batch = len_ref.shape[0]
    b = pl.program_id(0)
    group = num_heads // num_kv_heads
    rows = queries * group  # query rows a KV head
    if queries > 1:
        # [rows, 1]: which of the slot's queries a row of a group belongs to
        row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        query_of = sum(
            (row >= j * group).astype(jnp.int32) for j in range(1, queries)
        )
    layer = layer_ref[0]
    width = page_table_ref.shape[1]

    def walk_of(slot):
        """(first visible position, first page, pages, steps) of a slot's
        walk; no step where the slot is empty or holds no cached position
        its query sees."""
        slot_len = len_ref[slot]
        pages = jax.lax.div(slot_len + page_size - 1, page_size)
        lo = first = 0
        if window:
            # the query's own position is slot_len (inline) or slot_len - 1
            lo = jnp.maximum(slot_len + (1 if inline else 0) - window, 0)
            first = jax.lax.div(lo, page_size)
            pages = pages - first  # pages walked
        steps = (
            jax.lax.div(pages + block_pages - 1, block_pages)
            if blocked else pages
        )
        return lo, first, pages, steps

    kv_len = len_ref[b]
    lo, first_page, num_pages, num_steps = walk_of(b)
    mine = (b, first_page, num_pages)

    def page_dma(buf, hbm, half, seq, p, sem_row, j=0):
        slot, first, pages = seq
        if blocked:
            # the last step's spare pages read its last page again: their
            # positions lie past kv_len and are masked
            p = jnp.minimum(p, pages - 1)
        if window:
            p = jax.lax.rem(first + p, width)
        return pltpu.make_async_copy(
            hbm.at[layer, page_table_ref[slot, p]],
            buf.at[half, pl.ds(j * page_size, page_size)] if blocked
            else buf.at[half],
            sems.at[sem_row, half, j] if blocked else sems.at[sem_row, half],
        )

    def step_dmas(half, seq, step):
        """The K and V copies of one step of sequence ``seq``'s walk."""
        if not blocked:
            return [page_dma(buf, hbm, half, seq, step, row)
                    for row, (buf, hbm) in enumerate(pools)]
        return [
            page_dma(buf, hbm, half, seq, step * block_pages + j, row, j)
            for j in range(block_pages)
            for row, (buf, hbm) in enumerate(pools)
        ]

    @pl.when(b == 0)
    def _():
        walked[0] = 0

    # Tiles walked by the call's sequences before this one. Step p of this
    # walk lives in buffer half (done + p) % 2, and any live sequence before
    # this one has already started this one's step 0 there.
    done = walked[0]

    @pl.when((num_steps > 0) & (done == 0))
    def _():
        for dma in step_dmas(0, mine, 0):
            dma.start()

    # The next live sequence, whose step 0 this walk's last step starts
    # (scalar work, under the wait for this walk's own first tile; a grid
    # step that walks nothing looks for none).
    upcoming = jax.lax.while_loop(
        lambda s: (num_steps > 0) & (s < batch)
        & (walk_of(jnp.minimum(s, batch - 1))[3] <= 0),
        lambda s: s + 1,
        b + 1,
    )
    has_next = upcoming < batch
    upcoming = jnp.minimum(upcoming, batch - 1)
    ahead = (upcoming, *walk_of(upcoming)[1:3])

    # [heads, head_dim]
    q = q_ref[0].astype(jnp.float32) * (scale or head_dim**-0.5)

    # Online-softmax state is carried per KV head (tuples over the static
    # kv-head axis) — in-kernel scatter is not lowerable on TPU, whole-array
    # replacement is.
    def body(p, carry):
        ms, ls, accs = carry  # tuples of [group,1], [group,1], [group,d]
        half = jax.lax.rem(done + p, 2)
        last = p + 1 == num_steps

        # the walk's next tile: this sequence's, or step 0 of the next live
        # sequence's, in flight under this tile's softmax update
        @pl.when(jnp.logical_not(last) | has_next)
        def _():
            seq = tuple(jnp.where(last, a, m) for a, m in zip(ahead, mine))
            for dma in step_dmas(1 - half, seq, jnp.where(last, 0, p + 1)):
                dma.start()

        for dma in step_dmas(half, mine, p):
            dma.wait()

        # tokens beyond kv_len in the (last) page are masked out
        page0 = p * block_pages if blocked else p
        tok0 = (first_page + page0 if window else page0) * page_size
        tok_idx = tok0 + jax.lax.broadcasted_iota(jnp.int32, (1, tile), 1)
        valid = tok_idx < kv_len  # [1, tile]
        if window:
            # [rows, tile] for several queries: each from its own edge
            valid = valid & (
                tok_idx >= (lo if queries == 1 else jnp.maximum(
                    kv_len + 1 + query_of - window, 0))
            )

        new_ms, new_ls, new_accs = [], [], []
        for g in range(num_kv_heads):
            lanes = pl.ds(g * head_dim, head_dim)
            qg = q[g * rows : (g + 1) * rows]  # [rows, head_dim]
            kg = k_buf[half, :, lanes].astype(jnp.float32)  # [tile, head_dim]
            if latent:
                vg = k_buf[half, :, pl.ds(0, latent)].astype(jnp.float32)
            else:
                vg = v_buf[half, :, lanes].astype(jnp.float32)
            logits = jax.lax.dot_general(
                qg,
                kg,
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, tile]
            logits = jnp.where(valid, logits, NEG_INF)

            m_cur = jnp.maximum(ms[g], logits.max(axis=-1, keepdims=True))
            alpha = jnp.exp(ms[g] - m_cur)
            probs = jnp.exp(logits - m_cur)
            l_cur = ls[g] * alpha + probs.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(
                probs,
                vg,
                (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            )  # [group, head_dim]
            new_ms.append(m_cur)
            new_ls.append(l_cur)
            new_accs.append(accs[g] * alpha + pv)
        return tuple(new_ms), tuple(new_ls), tuple(new_accs)

    m0 = tuple(jnp.full((rows, 1), NEG_INF, jnp.float32) for _ in range(num_kv_heads))
    l0 = tuple(jnp.zeros((rows, 1), jnp.float32) for _ in range(num_kv_heads))
    acc0 = tuple(
        jnp.zeros((rows, value_dim), jnp.float32) for _ in range(num_kv_heads)
    )
    ms, ls, accs = jax.lax.fori_loop(0, num_steps, body, (m0, l0, acc0))
    walked[0] = done + jnp.maximum(num_steps, 0)

    if inline:
        # Fold the inline token (always valid; guarantees l > 0 at pos == 0).
        ms, ls, accs = list(ms), list(ls), list(accs)
        for g in range(num_kv_heads):
            lanes = pl.ds(g * head_dim, head_dim)
            qg = q[g * rows : (g + 1) * rows]
            # new row 0 first: every query sees it, so m is finite after it
            for j in range(queries):
                new = pl.ds(j, 1)
                kn = knew_ref[0, new, lanes].astype(jnp.float32)  # [1, head_dim]
                if latent:
                    vn = knew_ref[0, new, pl.ds(0, latent)].astype(jnp.float32)
                else:
                    vn = vnew_ref[0, new, lanes].astype(jnp.float32)
                logit = (qg * kn).sum(axis=-1, keepdims=True)  # [rows, 1]
                if j:
                    logit = jnp.where(query_of >= j, logit, NEG_INF)
                m_cur = jnp.maximum(ms[g], logit)
                alpha = jnp.exp(ms[g] - m_cur)
                p_self = jnp.exp(logit - m_cur)
                ls[g] = ls[g] * alpha + p_self
                accs[g] = accs[g] * alpha + p_self * vn
                ms[g] = m_cur

    l = jnp.concatenate(ls, axis=0)  # [heads, 1]
    acc = jnp.concatenate(accs, axis=0)  # [heads, value_dim]
    out = jnp.where(l > 0, acc / jnp.where(l > 0, l, 1.0), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def _paged_decode(
    q, k_pages, v_pages, page_table, kv_lens, layer, new_kv, interpret,
    window=0, block_pages=1, latent=0, scale=0.0,
):
    """``latent`` > 0: ``k_pages`` are latent pages, ``v_pages`` is None and
    ``new_kv`` one row (:func:`_decode_kernel`). q [batch, n, heads,
    head_dim] with ``new_kv`` of n rows a slot: n query positions
    (:func:`_decode_kernel`, ``queries``)."""
    queries = q.shape[1] if q.ndim == 4 else 1
    num_heads, head_dim = q.shape[-2:]
    batch = q.shape[0]
    _, _, page_size, fused = k_pages.shape
    num_kv_heads = fused // head_dim
    if q.ndim == 4:
        # a KV head's rows together, query by query: [batch, kvh, n, group]
        group = num_heads // num_kv_heads
        q = q.reshape(batch, queries, num_kv_heads, group, head_dim)
        q = q.swapaxes(1, 2).reshape(batch, queries * num_heads, head_dim)
    if not interpret:  # the interpreter has no tiling to satisfy
        check_kernel_shape(num_kv_heads, head_dim)
        if latent % LANES:
            raise ValueError(
                f"the value of a latent page is a slice of whole {LANES}-lane "
                f"tiles; got {latent} lanes"
            )
    pools = (k_pages,) if latent else (k_pages, v_pages)

    kernel = functools.partial(
        _decode_kernel,
        page_size=page_size,
        num_heads=num_heads,
        num_kv_heads=num_kv_heads,
        head_dim=head_dim,
        inline=bool(new_kv),
        **({"window": int(window)} if window else {}),
        **({"block_pages": int(block_pages)} if block_pages > 1 else {}),
        **({"latent": int(latent), "scale": float(scale)} if latent else {}),
        **({"queries": queries} if queries > 1 else {}),
    )
    tile = block_pages * page_size
    row_spec = lambda shape: pl.BlockSpec(  # noqa: E731
        shape, lambda b, *_: (b,) + (0,) * (len(shape) - 1), memory_space=pltpu.VMEM
    )
    out_shape = (batch, queries * num_heads, latent or head_dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(batch,),
        in_specs=[
            row_spec((1, queries * num_heads, head_dim)),
            *(row_spec((1, queries, fused)) for _ in new_kv),
            *(pl.BlockSpec(memory_space=pl.ANY) for _ in pools),
        ],
        out_specs=row_spec((1,) + out_shape[1:]),
        scratch_shapes=[
            *(pltpu.VMEM((2, tile, fused), pool.dtype) for pool in pools),
            pltpu.SemaphoreType.DMA(
                (2, 2, block_pages) if block_pages > 1 else (2, 2)
            ),
            pltpu.SMEM((1,), jnp.int32),
        ],
    )
    name = "paged_decode_inline" if new_kv else "paged_decode"
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(out_shape, q.dtype),
        grid_spec=grid_spec,
        # the walk's pipeline runs ACROSS grid steps: one after another
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)
        ),
        interpret=interpret,
        name="latent_decode_inline" if latent else name,
    )(
        page_table.astype(jnp.int32),
        kv_lens.astype(jnp.int32),
        jnp.asarray(layer, jnp.int32).reshape(1),
        q,
        *(x.reshape(batch, queries, fused) for x in new_kv),
        *pools,
    )
    if queries == 1:
        return out
    out = out.reshape(batch, num_kv_heads, queries, -1, out.shape[-1])
    return out.swapaxes(1, 2).reshape(batch, queries, num_heads, -1)


@functools.partial(
    jax.jit, static_argnames=("latent", "scale", "interpret", "block_pages")
)
def latent_decode_attention_inline_pallas(
    q: jnp.ndarray,  # [batch, heads, width]: the absorbed queries, padded
    pages: jnp.ndarray,  # [layers, num_pages, page_size, width] latent pages
    new: jnp.ndarray,  # [batch, width] — the new token's row
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    positions: jnp.ndarray,  # [batch] int32 — cache holds entries < position
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    latent: int,  # lanes of a row that are its value (kv_lora_rank)
    scale: float,  # what the scores are scaled by
    interpret: bool = False,
    block_pages: "int | None" = None,
) -> jnp.ndarray:
    """Absorbed latent attention for one decode step, on the walk of
    :func:`paged_decode_attention_inline_pallas`: every query head against
    the one shared row a token has, read ONCE as key and as value ->
    [batch, heads, latent]."""
    if block_pages is None:
        block_pages = decode_block_pages(pages.shape[2])
    return _paged_decode(
        q, pages, None, page_table, positions, layer, (new,), interpret,
        0, block_pages, latent, scale,
    )


@functools.partial(
    jax.jit, static_argnames=("interpret", "window", "block_pages")
)
def paged_decode_attention_inline_pallas(
    q: jnp.ndarray,  # [batch, heads, head_dim], or [batch, n, heads, head_dim]
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,
    k_new: jnp.ndarray,  # [batch, (n,) kv_heads, head_dim]
    v_new: jnp.ndarray,
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    positions: jnp.ndarray,  # [batch] int32 — cache holds entries < position
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    interpret: bool = False,
    window: int = 0,  # > 0: sliding window; table rows are read as rings
    # pages a step of the walk reads together; None: a 128-token tile of
    # this pool's pages, the one value the serving path has
    block_pages: "int | None" = None,
) -> jnp.ndarray:
    if block_pages is None:
        block_pages = decode_block_pages(k_pages.shape[2])
    return _paged_decode(
        q, k_pages, v_pages, page_table, positions, layer, (k_new, v_new),
        interpret, window, block_pages,
    )


@functools.partial(jax.jit, static_argnames=("interpret", "window"))
def paged_decode_attention_pallas(
    q: jnp.ndarray,  # [batch, heads, head_dim]
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    seq_lens: jnp.ndarray,  # [batch] int32
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    interpret: bool = False,
    window: int = 0,  # > 0: sliding window; table rows are read as rings
) -> jnp.ndarray:
    return _paged_decode(
        q, k_pages, v_pages, page_table, seq_lens, layer, (), interpret,
        window,
    )
