"""Attention ops: causal prefill and paged decode.

The serving engine keeps the KV cache *paged*: a global pool of fixed-size
pages per layer, with per-sequence page tables — the vLLM paged-KV idea laid
out for TPU: a page's minor axis holds the KV heads lane-fused, that axis is
sharded over the `tp` mesh axis, and the gather by page table lowers to a
dynamic-slice-friendly pattern XLA handles well (a Pallas ragged kernel can
replace it behind the same signature; see `ops/pallas/`).

Shapes (the pool as stored, engine/kv_cache.py:PagePool.pool_shape):
  k_pages, v_pages: [num_layers, num_pages, page_size, kv_heads * head_dim]
                    — every paged op takes the WHOLE pool and a layer
  layer:            int32 scalar; ops index ``pool[layer, page_table]``
                    inside their gather or DMA, never slicing a layer out
                    first (a pool-sized copy on the chip)
  page_table:       [batch, pages_per_seq] int32 (entries past the sequence
                    end are arbitrary; masked by seq_lens)
  seq_lens:         [batch] int32 — tokens currently in cache per sequence

The XLA paths split the fused axis of what they GATHERED (context-sized)
into [kv_heads, head_dim]; kv_heads comes from the fused width over q's
head_dim.

Sliding-window layers (``window`` > 0 on an op): query i sees keys j with
i - window < j <= i, its own position among them. Their table rows are
RINGS: position p lives in logical page ``(p // page_size) % pages_per_seq``
of the row, so a row holds the last ``pages_per_seq * page_size`` positions
and a row as long as the context is the ring that never wraps
(engine/kv_cache.py:KVLayout sizes them). ``window`` = 0 is the full causal
mask over a plain row, and traces exactly what it traced before windows.

All softmax math is fp32 regardless of the io dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

NEG_INF = -1e30

#: shard_map specs of the Pallas kernels' operands on a tp mesh
#: (ops/pallas/decode.py:shard_over_tp): heads / KV heads sharded, page
#: tables and lengths replicated
_HEADS3 = P(None, "tp", None)  # [batch|tokens, heads, head_dim]
_HEADS4 = P(None, None, "tp", None)  # [batch, seq, heads, head_dim]
#: the KV pool on a tp mesh, as stored (PagePool.create) and as the kernels'
#: shard_map takes it: the lane-fused KV-head axis sharded, so a shard's
#: slice is its kv_heads/tp heads, contiguous
POOL_SPEC = P(None, None, None, "tp")

#: Selected implementation: "reference" (pure XLA) or "pallas" (TPU
#: kernels). Read at trace time — switch before (re-)jitting.
_IMPL = "reference"

#: Pallas interpreter mode. Off unless a CPU test harness turns it on
#: (tests/conftest.py): it is never inferred from the backend, so a serving
#: process cannot end up emulating its kernels.
_INTERPRET = False


def set_attention_impl(impl: str) -> None:
    global _IMPL
    if impl not in ("reference", "grouped", "pallas"):
        raise ValueError(f"unknown attention impl {impl!r}")
    _IMPL = impl


def get_attention_impl() -> str:
    return _IMPL


def set_pallas_interpret(on: bool) -> None:
    global _INTERPRET
    _INTERPRET = bool(on)


def _pallas_interpret() -> bool:
    if _INTERPRET and jax.default_backend() == "tpu":
        raise RuntimeError(
            "Pallas interpret mode is on in a process whose backend is tpu; "
            "kernels must compile for the chip"
        )
    return _INTERPRET


def _gather_context(pages, layer, page_table, head_dim):
    """[n, pages_per_seq] page ids of ``pages[layer]`` ->
    [n, ctx, kv_heads, head_dim]: the gather reads the pool in place and the
    heads are split out of what it returned."""
    with jax.named_scope("kv_gather"):
        g = pages[layer, page_table]  # [n, pages_per_seq, page_size, fused]
        n, pps, ps, fused = g.shape
        return g.reshape(n, pps * ps, fused // head_dim, head_dim)


def _ring_positions(ring_len: int, last: jnp.ndarray) -> jnp.ndarray:
    """The position each slot of a ring holds once positions 0..``last``
    ([n] int32) have been written in order: slot r holds the largest
    p <= last with p % ring_len == r (negative: never written).
    -> [n, ring_len]."""
    r = jnp.arange(ring_len, dtype=jnp.int32)[None, :]
    return last[:, None] - jnp.mod(last[:, None] - r, ring_len)


def _repeat_kv(x: jnp.ndarray, n_rep: int, axis: int) -> jnp.ndarray:
    """GQA: repeat kv heads to match query heads."""
    if n_rep == 1:
        return x
    return jnp.repeat(x, n_rep, axis=axis)


def causal_prefill_attention(
    q: jnp.ndarray,  # [batch, seq, heads, head_dim]
    k: jnp.ndarray,  # [batch, seq, kv_heads, head_dim]
    v: jnp.ndarray,  # [batch, seq, kv_heads, head_dim]
    seq_lens: jnp.ndarray,  # [batch] int32: valid prefix length per row
    impl: "str | None" = None,  # None -> module default
    mesh=None,  # tp mesh: the pallas impl runs under shard_map
    window: int = 0,  # > 0: sliding window (see the module docstring)
) -> jnp.ndarray:
    """Causal self-attention over a (right-padded) prefill batch."""
    if (impl or _IMPL) == "pallas":
        from .pallas import causal_prefill_attention_pallas
        from .pallas.decode import shard_over_tp

        # Right-pad the sequence to whole kernel blocks (a multiple of the
        # bf16 sublane tile): padded keys sit past every seq_len, so they are
        # masked, and padded query rows are sliced off.
        s = q.shape[1]
        block_q = min(128, -(-s // 16) * 16)
        pad = -s % block_q
        if pad:
            q, k, v = (
                jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                for x in (q, k, v)
            )
        kernel = functools.partial(
            causal_prefill_attention_pallas,
            block_q=block_q, interpret=_pallas_interpret(),
            **({"window": window} if window else {}),
        )
        out = shard_over_tp(
            mesh, kernel, (_HEADS4, _HEADS4, _HEADS4, P(None)), _HEADS4
        )(q, k, v, seq_lens)
        return out[:, :s]
    b, s, h, d = q.shape
    kvh = k.shape[2]
    k = _repeat_kv(k, h // kvh, axis=2)
    v = _repeat_kv(v, h // kvh, axis=2)

    qf = q.astype(jnp.float32) * (d**-0.5)
    logits = jnp.einsum("bqhd,bkhd->bhqk", qf, k.astype(jnp.float32))

    pos = jnp.arange(s)
    causal = pos[None, :, None] >= pos[None, None, :]  # [1, q, k]
    if window:
        causal = causal & (pos[None, None, :] > pos[None, :, None] - window)
    valid = pos[None, None, :] < seq_lens[:, None, None]  # [b, 1, k]
    mask = (causal & valid)[:, None, :, :]  # [b, 1, q, k]
    logits = jnp.where(mask, logits, NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


def paged_decode_attention_inline(
    q: jnp.ndarray,  # [batch, heads, head_dim] — the new token's queries;
    #                  [batch, n, heads, head_dim]: n new positions a slot
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,  # same
    k_new: jnp.ndarray,  # [batch, (n,) kv_heads, head_dim] — the new K
    v_new: jnp.ndarray,  # [batch, (n,) kv_heads, head_dim] — the new V
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    positions: jnp.ndarray,  # [batch] int32 — position of the new token;
    #                          cache entries < position are attended
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    impl: "str | None" = None,
    mesh=None,  # tp mesh: the pallas impl runs under shard_map
    window: int = 0,  # > 0: sliding window; table rows are rings
) -> jnp.ndarray:
    """Decode attention where the new token's K/V are passed *inline* instead
    of having been scattered into the cache first.

    This is the serving fast path: per-layer cache scatters are the dominant
    non-matmul cost of a decode step on TPU (each XLA scatter on the pool
    re-materializes it), so the engine defers all layers' KV writes to ONE
    scatter after the layer scan and attention reads cache[< position] plus
    the inline (k_new, v_new) as a virtual final cache entry. Numerically
    identical to scatter-then-attend (same softmax over the same set).

    GQA is handled by *grouping* query heads [b, kvh, group, d] — no
    materialized `repeat` of K/V, matmuls run bf16 on the MXU with fp32
    accumulation. The pallas kernel walks the pages a 128-token tile a step
    for every caller (ops/pallas/decode.py:decode_block_pages).

    With n new positions a slot (q [batch, n, heads, head_dim], a verify step
    of speculative decoding) the cache is read ONCE a slot for all n queries,
    n * group query rows a KV head; query i sits at ``positions + i`` and
    takes the new rows 0..i inline.
    """
    if (impl or _IMPL) == "pallas":
        from .pallas import paged_decode_attention_inline_pallas
        from .pallas.decode import shard_over_tp

        kernel = functools.partial(
            paged_decode_attention_inline_pallas,
            interpret=_pallas_interpret(),
            **({"window": window} if window else {}),
        )
        heads = _HEADS3 if q.ndim == 3 else _HEADS4
        return shard_over_tp(
            mesh, kernel,
            (heads, POOL_SPEC, POOL_SPEC, heads, heads, P(None, None),
             P(None), P()),
            heads,
        )(q, k_pages, v_pages, k_new, v_new, page_table, positions, layer)
    if q.ndim == 4:
        return _inline_rows(
            q, k_pages, v_pages, k_new, v_new, page_table, positions, layer,
            window,
        )
    b, h, d = q.shape
    k = _gather_context(k_pages, layer, page_table, d)
    v = _gather_context(v_pages, layer, page_table, d)
    ctx, kvh = k.shape[1:3]
    g = h // kvh
    qg = (q.astype(jnp.float32) * (d**-0.5)).astype(q.dtype).reshape(b, kvh, g, d)
    logits = jnp.einsum(
        "bngd,bknd->bngk", qg, k, preferred_element_type=jnp.float32
    )
    if window:
        # the row is a ring of ctx slots holding positions <= position - 1
        kpos = _ring_positions(ctx, positions - 1)
        valid = (kpos >= 0) & (kpos > positions[:, None] - window)
    else:
        valid = jnp.arange(ctx)[None, :] < positions[:, None]  # strictly past
    logits = jnp.where(valid[:, None, None, :], logits, NEG_INF)
    self_logit = jnp.einsum(
        "bngd,bnd->bng", qg, k_new.astype(qg.dtype),
        preferred_element_type=jnp.float32,
    )
    all_logits = jnp.concatenate([logits, self_logit[..., None]], axis=-1)
    probs = jax.nn.softmax(all_logits, axis=-1)
    out = jnp.einsum(
        "bngk,bknd->bngd", probs[..., :ctx].astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = out + probs[..., ctx:] * v_new.reshape(b, kvh, 1, d).astype(jnp.float32)
    return out.reshape(b, h, d).astype(q.dtype)


def _inline_rows(q, k_pages, v_pages, k_new, v_new, page_table, positions,
                 layer, window):
    """The XLA twin of :func:`paged_decode_attention_inline` for SEVERAL new
    positions a slot (a verify step: the last token and its drafts). q
    [b, n, heads, d] at ``positions + 0 .. n - 1``, k_new / v_new [b, n,
    kv_heads, d]: the cache is gathered ONCE a slot for all n queries, and
    query i takes the new rows 0..i inline. A window layer's query i sees
    the ring's keys after ``positions + i - window``."""
    b, n, h, d = q.shape
    k = _gather_context(k_pages, layer, page_table, d)
    v = _gather_context(v_pages, layer, page_table, d)
    ctx, kvh = k.shape[1:3]
    g = h // kvh
    qg = (q.astype(jnp.float32) * (d**-0.5)).astype(q.dtype).reshape(
        b, n, kvh, g, d
    )
    logits = jnp.einsum(
        "bqngd,bknd->bqngk", qg, k, preferred_element_type=jnp.float32
    )
    qpos = positions[:, None] + jnp.arange(n)[None, :]  # [b, n]
    if window:
        kpos = _ring_positions(ctx, positions - 1)[:, None, :]  # [b, 1, ctx]
        valid = (kpos >= 0) & (kpos > qpos[:, :, None] - window)
    else:
        valid = jnp.broadcast_to(
            jnp.arange(ctx)[None, None, :] < positions[:, None, None],
            (b, n, ctx),
        )
    logits = jnp.where(valid[:, :, None, None, :], logits, NEG_INF)
    new_logits = jnp.einsum(
        "bqngd,bjnd->bqngj", qg, k_new.astype(qg.dtype),
        preferred_element_type=jnp.float32,
    )
    idx = jnp.arange(n)
    seen = idx[None, :] <= idx[:, None]  # [q, j]: its own row and the earlier
    if window:
        seen = seen & (idx[None, :] > idx[:, None] - window)
    new_logits = jnp.where(seen[None, :, None, None, :], new_logits, NEG_INF)
    probs = jax.nn.softmax(
        jnp.concatenate([logits, new_logits], axis=-1), axis=-1
    )
    out = jnp.einsum(
        "bqngk,bknd->bqngd", probs[..., :ctx].astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    out = out + jnp.einsum(
        "bqngj,bjnd->bqngd", probs[..., ctx:], v_new.astype(jnp.float32)
    )
    return out.reshape(b, n, h, d).astype(q.dtype)


def latent_decode_attention_inline(
    q: jnp.ndarray,  # [batch, heads, width] — absorbed queries, padded as rows
    pages: jnp.ndarray,  # [layers, num_pages, page_size, width] latent pages
    new: jnp.ndarray,  # [batch, width] — the new token's latent row
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    positions: jnp.ndarray,  # [batch] int32 — cache entries < position count
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    latent: int,  # lanes of a row that are its value
    scale: float,  # what the scores are scaled by
    impl: "str | None" = None,
) -> jnp.ndarray:
    """Latent attention in its absorbed form, one decode step with the new
    token's row inline (:func:`paged_decode_attention_inline`'s contract):
    every query head scores the ONE row a token of the layer holds
    (engine/kv_cache.py, the latent page), and that row's first ``latent``
    lanes are its value. -> [batch, heads, latent]. The pallas kernel reads a
    page once for both (ops/pallas/decode.py); this XLA twin gathers the
    table's pages."""
    if (impl or _IMPL) == "pallas":
        from .pallas.decode import latent_decode_attention_inline_pallas

        return latent_decode_attention_inline_pallas(
            q, pages, new, page_table, positions, layer, latent=latent,
            scale=scale, interpret=_pallas_interpret(),
        )
    b = q.shape[0]
    with jax.named_scope("kv_gather"):
        rows = pages[layer, page_table].reshape(b, -1, pages.shape[-1])
    ctx = rows.shape[1]
    qs = (q.astype(jnp.float32) * scale).astype(q.dtype)
    logits = jnp.einsum(
        "bhw,bkw->bhk", qs, rows, preferred_element_type=jnp.float32
    )
    valid = jnp.arange(ctx)[None, :] < positions[:, None]  # strictly past
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)
    self_logit = jnp.einsum(
        "bhw,bw->bh", qs, new.astype(qs.dtype),
        preferred_element_type=jnp.float32,
    )
    probs = jax.nn.softmax(
        jnp.concatenate([logits, self_logit[..., None]], axis=-1), axis=-1
    )
    out = jnp.einsum(
        "bhk,bkc->bhc", probs[..., :ctx].astype(rows.dtype),
        rows[..., :latent], preferred_element_type=jnp.float32,
    )
    out = out + probs[..., ctx:] * new[:, None, :latent].astype(jnp.float32)
    return out.astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,  # [batch, heads, head_dim] — one new token per sequence
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,  # same
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    seq_lens: jnp.ndarray,  # [batch] int32 (length INCLUDING the new token)
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    impl: "str | None" = None,  # None -> module default
    mesh=None,  # tp mesh: the pallas impl runs under shard_map
) -> jnp.ndarray:
    """One decode step of attention against the paged cache.

    Reference implementation: gather each sequence's pages of the layer,
    flatten to a [batch, ctx, kv_heads, head_dim] view, mask past seq_len. ctx =
    pages_per_seq * page_size is static, so the whole step is one fused
    region under jit — no dynamic shapes.
    """
    if (impl or _IMPL) == "pallas":
        from .pallas import paged_decode_attention_pallas
        from .pallas.decode import shard_over_tp

        kernel = functools.partial(
            paged_decode_attention_pallas, interpret=_pallas_interpret()
        )
        return shard_over_tp(
            mesh, kernel,
            (_HEADS3, POOL_SPEC, POOL_SPEC, P(None, None), P(None), P()),
            _HEADS3,
        )(q, k_pages, v_pages, page_table, seq_lens, layer)
    b, h, d = q.shape
    k = _gather_context(k_pages, layer, page_table, d)  # [b, ctx, kvh, d]
    v = _gather_context(v_pages, layer, page_table, d)
    ctx, kvh = k.shape[1:3]
    k = _repeat_kv(k, h // kvh, axis=2)  # [b, ctx, h, d]
    v = _repeat_kv(v, h // kvh, axis=2)

    qf = q.astype(jnp.float32) * (d**-0.5)
    logits = jnp.einsum("bhd,bkhd->bhk", qf, k.astype(jnp.float32))
    valid = jnp.arange(ctx)[None, :] < seq_lens[:, None]  # [b, ctx]
    logits = jnp.where(valid[:, None, :], logits, NEG_INF)

    probs = jnp.exp(logits - logits.max(axis=-1, keepdims=True))
    probs = probs / probs.sum(axis=-1, keepdims=True)
    out = jnp.einsum("bhk,bkhd->bhd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


#: Row-block size of the ragged packing layout (ops/pallas/ragged.py): the
#: engine's packer aligns every sequence's contiguous run of rows in the
#: flat [token_budget] buffer to this boundary, so each kernel block
#: belongs to at most one sequence. Waste per packed segment is < this
#: many rows — against up to 2x for the power-of-two prefill buckets.
RAGGED_BLOCK = 8


def ragged_paged_attention(
    q: jnp.ndarray,  # [tokens, heads, head_dim] — flat packed token buffer
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,  # same
    page_table: jnp.ndarray,  # [rows, pages_per_seq] int32
    row_slot: jnp.ndarray,  # [tokens] int32 — page_table row per token;
    #                         -1 marks a padding row (output is garbage)
    positions: jnp.ndarray,  # [tokens] int32 — absolute position per token
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    impl: "str | None" = None,  # None -> module default
    mesh=None,  # tp mesh for the pallas impl's shard_map port; the XLA
    #            twin never needs it (GSPMD partitions it in place)
) -> jnp.ndarray:
    """Attention for a token-packed mixed batch over the paged cache.

    One flat ``[tokens]`` buffer holds rows drawn from MANY sequences —
    prefill segments, suffix continuations, and decode steps together
    (the mixed-batch serving path, engine/engine.py). Each token carries
    its own (sequence slot, absolute position); its KV has already been
    scattered into the pages (scatter-first, like ``prefill_continue``),
    and it attends over every cache entry of its OWN sequence at
    positions <= its own — which is simultaneously the causal prefill
    mask, the suffix-continuation mask, and the decode mask (the token
    itself is the newest cache entry).

    This XLA twin is the CPU-runnable parity baseline: a gather of each
    token's pages (a dynamic-slice-friendly pattern XLA fuses, exactly
    like ``paged_suffix_attention``) that materializes [tokens, ctx] —
    fine for tests and CPU serving, O(tokens * ctx) HBM traffic on TPU.
    The Pallas kernel behind the same signature (ops/pallas/ragged.py)
    reads only the pages each row block actually needs; it additionally
    requires the packing contract that rows of one sequence are
    contiguous, position-consecutive, and aligned to ``RAGGED_BLOCK``.

    Padding rows (``row_slot < 0``) write nothing (the model's scatter
    drops them) and read row 0's pages fully masked — their output is
    finite garbage the caller ignores.
    """
    if (impl or _IMPL) == "pallas":
        # the kernel raises on a buffer that is not whole RAGGED_BLOCKs;
        # the engine's token budget is rounded to it (packed_token_budget)
        if mesh is not None:
            from .pallas import ragged_paged_attention_pallas_sharded

            return ragged_paged_attention_pallas_sharded(
                mesh, q, k_pages, v_pages, page_table, row_slot,
                positions, layer, block_rows=RAGGED_BLOCK,
                interpret=_pallas_interpret(),
            )
        from .pallas import ragged_paged_attention_pallas

        return ragged_paged_attention_pallas(
            q, k_pages, v_pages, page_table, row_slot, positions, layer,
            block_rows=RAGGED_BLOCK, interpret=_pallas_interpret(),
        )
    t, h, d = q.shape
    safe = jnp.clip(row_slot, 0, page_table.shape[0] - 1)
    pt = page_table[safe]  # [t, pages_per_seq]
    k = _gather_context(k_pages, layer, pt, d)  # [t, ctx, kvh, d]
    v = _gather_context(v_pages, layer, pt, d)
    ctx, kvh = k.shape[1:3]
    g = h // kvh
    qg = (q.astype(jnp.float32) * (d**-0.5)).astype(q.dtype).reshape(
        t, kvh, g, d
    )
    logits = jnp.einsum(
        "tngd,tknd->tngk", qg, k, preferred_element_type=jnp.float32
    )
    mask = jnp.arange(ctx)[None, :] <= positions[:, None]  # [t, ctx]
    mask = mask & (row_slot >= 0)[:, None]
    logits = jnp.where(mask[:, None, None, :], logits, NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum(
        "tngk,tknd->tngd", probs.astype(v.dtype), v,
        preferred_element_type=jnp.float32,
    )
    return out.reshape(t, h, d).astype(q.dtype)


#: query rows the XLA suffix attention scores at a time (the patterned
#: families' suffix segments, models/llama.py:suffix_segment, and every
#: walked row): a 1,024-row segment against a 16k-token table row would
#: otherwise hold 1.9 GB of float32 scores, and 117 MB against one column
#: block of it where 128 rows hold 15
SUFFIX_Q_BLOCK = 128

#: columns the suffix attention scores a step of its walk (a multiple of
#: the page size: 64 pages of 16 tokens). A table row no longer than one
#: step is scored in one shot, as it always was.
SUFFIX_COL_BLOCK = 1024


def _suffix_step(page_size: int, col_block: int):
    """(pages, columns) of one step of the walk over a row of
    ``page_size``-token pages: whole pages, at least one."""
    pages = max(1, col_block // page_size)
    return pages, pages * page_size


def suffix_columns(
    start: int, s: int, width: int, page_size: int, ring: bool = False,
    col_block: int = SUFFIX_COL_BLOCK,
) -> int:
    """The columns :func:`paged_suffix_attention` scores for a segment of
    ``s`` rows (its bucket) at ``start`` over a row of ``width`` columns, a
    ring's or a plain one: the host's twin of the walk's trip count."""
    _, step = _suffix_step(page_size, col_block)
    end = start + s
    if width <= step or (ring and end > width):
        return width
    return min(width, -(-end // step) * step)


def paged_suffix_attention(
    q: jnp.ndarray,  # [batch, s, heads, head_dim] — suffix queries
    k_pages: jnp.ndarray,  # [layers, num_pages, page_size, kv_heads*head_dim]
    v_pages: jnp.ndarray,  # same
    page_table: jnp.ndarray,  # [batch, pages_per_seq] int32
    start: jnp.ndarray,  # [batch] int32 — absolute position of query 0
    layer: jnp.ndarray,  # int32 scalar — the pool layer to read
    window: int = 0,  # > 0: sliding window; table rows are rings
    q_block: int = 0,  # > 0: score this many query rows at a time
    col_block: int = SUFFIX_COL_BLOCK,  # columns a step of the walk
) -> jnp.ndarray:
    """Causal attention for a prompt SUFFIX over the paged cache.

    The prefix-caching continue path (engine/prefix_cache.py): the cached
    prefix's KV already lives in shared pages, the suffix's KV has just
    been scattered in, and query i at absolute position start+i attends
    to every cache slot <= its own position. Padding queries (past the
    real suffix) produce garbage the caller ignores — same convention as
    the right-padded full prefill. GQA by head grouping (no repeated
    K/V), XLA gather over the table.

    A row of at most ``col_block`` columns is gathered whole and scored in
    one shot. A longer one is WALKED in blocks of ``col_block`` columns: a
    step gathers the block's pages alone and folds its scores into a
    running float32 (max, sum, accumulator) a query row, ``q_block`` rows
    (``SUFFIX_Q_BLOCK`` if the caller names none) at a time, and the walk
    stops at the last block that can hold a key some query of the batch
    sees — position ``start + s`` of a plain row, and of a ring that has
    not wrapped (slot = position); every block of a ring that has. The
    pages past it are never read.
    """
    b, s, h, d = q.shape
    ps = k_pages.shape[2]
    ctx = page_table.shape[1] * ps
    kvh = k_pages.shape[3] // d
    g = h // kvh
    step_pages, step = _suffix_step(ps, col_block)
    walked = ctx > step
    if not walked:
        k = _gather_context(k_pages, layer, page_table, d)  # [b, ctx, kvh, d]
        v = _gather_context(v_pages, layer, page_table, d)
    qg = (q.astype(jnp.float32) * (d**-0.5)).astype(q.dtype).reshape(
        b, s, kvh, g, d
    )
    qpos = start[:, None] + jnp.arange(s)[None, :]  # [b, s] absolute
    if window:
        # the row is a ring of ctx slots into which the segment (all s
        # positions of its bucket: a slot of a padded position reads as
        # that position, later than every real query) has been written
        kpos = _ring_positions(ctx, start + s - 1)[:, None, :]  # [b, 1, ctx]
    else:
        kpos = jnp.arange(ctx, dtype=jnp.int32)[None, None, :]

    def scores(qg, qpos, k, kpos):  # -> [b, n, kvh, g, cols] float32
        logits = jnp.einsum(
            "bsngd,bknd->bsngk", qg, k, preferred_element_type=jnp.float32
        )
        mask = kpos <= qpos[:, :, None]  # [b, n, cols]
        if window:
            mask = mask & (kpos >= 0) & (kpos > qpos[:, :, None] - window)
        return jnp.where(mask[:, :, None, None, :], logits, NEG_INF)

    def blocks(x, n):  # [b, s, ...] -> [s // n, b, n, ...]
        return x.reshape(b, s // n, n, *x.shape[2:]).swapaxes(0, 1)

    if not walked:

        def attend(blk):  # [b, n, kvh, g, d], [b, n] -> [b, n, kvh, g, d]
            probs = jax.nn.softmax(scores(*blk, k, kpos), axis=-1)
            return jnp.einsum(
                "bsngk,bknd->bsngd", probs.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            )

        if q_block and s > q_block and s % q_block == 0:
            # query rows in blocks, one after another: the [rows, ctx]
            # scores of a long context exist for one block at a time
            out = jax.lax.map(
                attend, (blocks(qg, q_block), blocks(qpos, q_block))
            ).swapaxes(0, 1)  # [b, s // q_block, q_block, kvh, g, d]
        else:
            out = attend((qg, qpos))
        return out.reshape(b, s, h, d).astype(q.dtype)

    n = q_block or SUFFIX_Q_BLOCK
    if s <= n or s % n:
        n = s
    qg, qpos = blocks(qg, n), blocks(qpos, n)
    steps = -(-ctx // step)
    pad = steps * step - ctx
    if pad:
        # a last block of spare columns: they hold no position
        page_table = jnp.pad(page_table, ((0, 0), (0, pad // ps)))
        kpos = jnp.pad(
            kpos, ((0, 0), (0, 0), (0, pad)),
            constant_values=-1 if window else jnp.iinfo(jnp.int32).max,
        )
    # the last column a query sees is its own; a ring that has wrapped
    # holds visible keys in any of its slots
    trips = jnp.minimum(steps, (jnp.max(start) + s - 1) // step + 1)
    if window:
        trips = jnp.where(jnp.max(start) + s > ctx, steps, trips)

    def fold(j, state):
        cols = jax.lax.dynamic_slice_in_dim(
            page_table, j * step_pages, step_pages, axis=1
        )
        k = _gather_context(k_pages, layer, cols, d)  # [b, step, kvh, d]
        v = _gather_context(v_pages, layer, cols, d)
        kp = jax.lax.dynamic_slice_in_dim(kpos, j * step, step, axis=2)

        def rows(blk):
            qg, qpos, m, l, acc = blk
            logits = scores(qg, qpos, k, kp)
            m_new = jnp.maximum(m, logits.max(axis=-1))
            # m starts above NEG_INF: a masked score weighs 0 also in a row
            # that has seen no key yet
            p = jnp.exp(logits - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bsngk,bknd->bsngd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32,
            )
            return m_new, alpha * l + p.sum(axis=-1), acc

        return jax.lax.map(rows, (qg, qpos, *state))

    lead = (s // n, b, n, kvh, g)
    _, l, acc = jax.lax.fori_loop(
        0, trips, fold,
        (
            jnp.full(lead, 0.5 * NEG_INF, jnp.float32),
            jnp.zeros(lead, jnp.float32),
            jnp.zeros((*lead, d), jnp.float32),
        ),
    )
    out = (acc / l[..., None]).swapaxes(0, 1)  # [b, s // n, n, kvh, g, d]
    return out.reshape(b, s, h, d).astype(q.dtype)
