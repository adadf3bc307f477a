"""Paged KV cache: device page pools + host-side page allocator.

Pool layout (per k and v): ``[num_layers, num_pages, page_size, kv_heads *
head_dim]`` — the layout the attention kernels read. A page of one layer is
a ``[page_size, kv_heads * head_dim]`` tile whose minor (lane) axis holds
the KV heads one after another, so a KV head is a static lane slice and a
kernel DMAs ``pool[layer, page]`` straight from the stored array: nothing
pool-sized is sliced or re-laid-out per layer (ops/pallas/decode.py). One
array for all layers, so the forward indexes it by layer and the whole pool
moves HBM<->host in one transfer on sleep/wake. The fused axis is sharded
over `tp` (:data:`POOL_SPEC`: a shard holds its ``kv_heads / tp`` heads,
contiguous); everything else is replicated (pages are a node-local pool,
like vLLM's block allocator, not a distributed object). Readers that want
heads apart gather a context's pages first and split the minor axis of what
they gathered.

Page size defaults to 16 tokens: a (16, kvh_shard * head_dim) page tile.

A model with sliding-window layers (models/llama.py:window_layers) has two
kinds of KV state (:class:`KVLayout`): the pool above for its
full-attention layers alone, and for its window layers a RING per sequence
slot, ``[window layers, slots, ring pages, page_size, kv_heads * head_dim]``
— a page of it is the same tile, so the same kernels read it. A ring holds
the window plus the longest prefill segment that is written before it is
read, position p at ``p % ring_len``; it is addressed through static ring
columns the engine appends to every page-table row (slot i owns pages
``i * ring_pages ...`` of the rings' flat page axis), so an attention op
reads either kind through a table row and a layer index. The rings are
sized from ``max_batch``, the model's window and the segment limit; no
allocator hands them out and no option sizes them.

A model with linear-attention layers (models/llama.py:recurrent_state) has a
third kind: RECURRENT state, small, of fixed size, not addressed by pages and
overwritten in place every token. Per slot and linear layer, a float32 matrix
a head, ``state [linear layers, slots, heads, d_k, d_v]``, and the last
inputs of the layer's causal convolution, ``conv_tail [linear layers, slots,
kernel - 1, channels]`` in the model's dtype. Sized from ``max_batch`` like
the rings; a program finds its slot by the one static column the engine
appends to every page-table row after the ring columns (the slot's index).

A model with latent attention (models/llama.py:latent_cache) has a fourth
kind, the LATENT page: ONE array a page, not a K and a V. A token of a layer
holds its compressed key-value vector and the key part every head shares
(``kv_lora_rank + qk_rope_head_dim`` values: 512 + 64 = 576, 1,152 B in
bfloat16, "as counted"), which the decode kernel reads once as key (the whole
row) and as value (its first ``kv_lora_rank`` lanes). 576 is no multiple of
the 128 lanes a kernel's DMA and lane slices take, so the row is STORED 640
wide, ``[c | k_pe | 64 zeros]`` (1,280 B "as laid out", a ninth more): the
shared key part keeps a 128-lane tile of its own, the zeros score nothing
against a query padded alike, and the value is the lane-aligned slice
``[:512]``. ``[latent layers, pages, page_size, 640]``, indexed by the same
page table and allocator as K-and-V pages; such a model keeps no K-and-V
pages at all.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..ops.attention import POOL_SPEC


@dataclass(frozen=True)
class KVLayout:
    """How an engine's KV state divides between the paged pool and the
    rings; the ONE definition shared by the live build (engine/engine.py),
    the AOT warm-up's avals (engine/exec_pool.py) and the stats."""

    #: layers whose K and V live in the paged pool (all of them for a model
    #: without window layers)
    global_layers: int
    #: layers whose K and V live in the rings
    window_layers: int
    #: their window, tokens (0 without window layers)
    window: int
    #: pages of one sequence's ring (0 without window layers)
    ring_pages: int
    #: page-table columns of the paged pool
    pages_per_seq: int
    #: layers that keep recurrent state (0 without linear-attention layers)
    state_layers: int = 0
    #: what ONE slot holds for one of them: the state's shape (float32) and
    #: the convolution tail's (the model's dtype)
    state_shape: Tuple[int, ...] = ()
    tail_shape: Tuple[int, ...] = ()
    #: lanes of a latent page's row as stored (0: the pages hold K and V),
    #: and the values of it the algorithm needs (``latent_cache``)
    latent_width: int = 0
    latent_counted: int = 0

    @property
    def table_width(self) -> int:
        """Columns of a page-table row: the sequence's pages, then its
        ring's, then (recurrent state) its slot."""
        return self.pages_per_seq + self.ring_pages + bool(self.state_layers)

    @classmethod
    def plan(
        cls, num_layers: int, window_layers: int, window: int,
        page_size: int, seq_len: int, segment: int, recurrent=None,
        latent=None,
    ) -> "KVLayout":
        """``num_layers``: the layers that keep K and V; ``segment``: the
        most positions one program writes before it reads (the largest
        prefill bucket). A ring as long as the context never wraps, so it
        is never longer than that. ``recurrent``: what
        ``llama.recurrent_state`` gives; ``latent``: what
        ``llama.latent_cache`` gives."""
        pps = -(-seq_len // page_size)
        layers, state, tail = recurrent or (0, (), ())
        counted, stored = latent or (0, 0)
        kinds = dict(
            state_layers=layers, state_shape=state, tail_shape=tail,
            latent_width=stored, latent_counted=counted,
        )
        if not window_layers:
            return cls(num_layers, 0, 0, 0, pps, **kinds)
        ring_len = min(window + segment, seq_len)
        return cls(
            num_layers - window_layers, window_layers, window,
            -(-ring_len // page_size), pps, **kinds,
        )

    def ring_shape(
        self, slots: int, page_size: int, num_kv_heads: int, head_dim: int
    ) -> Tuple[int, int, int, int, int]:
        return (
            self.window_layers, slots, self.ring_pages, page_size,
            num_kv_heads * head_dim,
        )

    def ring_columns(self, slots: int):
        """[slots, ring_pages] int32: the static ring part of the page
        table, ids into the rings' flat (slots * ring_pages) page axis."""
        import numpy as np

        return np.arange(slots * self.ring_pages, dtype=np.int32).reshape(
            slots, self.ring_pages
        )

    def static_columns(self, slots: int):
        """[slots, table_width - pages_per_seq] int32: what follows a row's
        pages and is never rewritten, the ring columns and then the slot's
        own index for a model with recurrent state."""
        import numpy as np

        cols = [self.ring_columns(slots)]
        if self.state_layers:
            cols.append(np.arange(slots, dtype=np.int32)[:, None])
        return np.concatenate(cols, axis=1)

    def state_shapes(self, slots: int):
        """(shape of the recurrent state, shape of the convolution tails)
        for ``slots`` slots, or None without recurrent layers."""
        if not self.state_layers:
            return None
        lead = (self.state_layers, slots)
        return lead + tuple(self.state_shape), lead + tuple(self.tail_shape)

    def state_nbytes(self, slots: int, itemsize: int) -> int:
        """Bytes of the recurrent state and the tails (``itemsize``: the
        model's dtype, the tail's)."""
        return recurrent_nbytes(
            (self.state_layers, self.state_shape, self.tail_shape),
            slots, itemsize,
        )


def recurrent_nbytes(recurrent, slots: int, itemsize: int) -> int:
    """Device bytes of the recurrent state (float32) and the convolution
    tails (``itemsize`` bytes an element) of ``slots`` slots, from what
    ``llama.recurrent_state`` gives (None: no such layers, 0) — the ONE
    count shared by the stats and the cost oracle's cold-tier prediction
    (engine/server.py:_kv_pool_nbytes), beside
    :meth:`PagePool.estimate_nbytes` for the pages."""
    import math

    layers, state, tail = recurrent or (0, (), ())
    return layers * slots * (4 * math.prod(state) + itemsize * math.prod(tail))


#: the rings on a tp mesh: the lane-fused KV-head axis sharded, as POOL_SPEC
RING_SPEC = jax.sharding.PartitionSpec(None, None, None, None, "tp")
#: the recurrent state and its tails on a mesh: replicated, as the linear
#: mixers' weights are (models/olmo_hybrid.py:param_logical_axes)
STATE_SPEC = jax.sharding.PartitionSpec()


@dataclass
class PagePool:
    #: K pages; the latent pages of a pool of that kind (``"latent"`` in
    #: :attr:`kinds`), which has no ``v_pages``
    k_pages: jnp.ndarray
    v_pages: Optional[jnp.ndarray]
    #: the window layers' rings (None for a model without window layers)
    k_ring: Optional[jnp.ndarray] = None
    v_ring: Optional[jnp.ndarray] = None
    #: the linear-attention layers' recurrent state and convolution tails
    #: (None for a model without them)
    state: Optional[jnp.ndarray] = None
    conv_tail: Optional[jnp.ndarray] = None
    #: what this pool carries beside or instead of pages of K and V
    #: ("latent", "ring", "state"), kept across :meth:`drop` (a woken
    #: engine's tuple is split by it)
    kinds: Tuple[str, ...] = ()

    def __post_init__(self) -> None:
        if not self.kinds:
            self.kinds = (("ring",) if self.k_ring is not None else ()) + (
                ("state",) if self.state is not None else ()
            )

    @staticmethod
    def pool_shape(
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        latent_width: int = 0,
    ) -> Tuple[int, int, int, int]:
        """The per-direction (k or v) pool array shape — the ONE
        definition shared by :meth:`create`, :meth:`estimate_nbytes` (the
        cost oracle sizes a not-yet-built pool from it) and the AOT
        warm-up's avals (engine/exec_pool.py). With ``latent_width``
        (:attr:`KVLayout.latent_width`) it is the shape of the one array a
        latent page is, whatever the heads."""
        return (
            num_layers, num_pages, page_size,
            latent_width or num_kv_heads * head_dim,
        )

    @classmethod
    def estimate_nbytes(
        cls,
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        latent_width: int = 0,
    ) -> int:
        """Device bytes a :meth:`create` with these arguments allocates
        (k + v, or the one array of latent pages), without allocating —
        what the actuation cost oracle counts into cold-tier predictions
        (engine/server.py _kv_pool_nbytes), kept here so a pool-layout
        change can never silently drift the prediction from the build's
        bytes_in."""
        import numpy as np

        shape = cls.pool_shape(
            num_layers, num_pages, page_size, num_kv_heads, head_dim,
            latent_width,
        )
        elems = 1
        for d in shape:
            elems *= int(d)
        arrays = 1 if latent_width else 2
        return arrays * elems * int(np.dtype(dtype).itemsize)

    @classmethod
    def page_nbytes(
        cls,
        num_layers: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        latent_width: int = 0,
    ) -> int:
        """Device bytes ONE page occupies across all layers, k and v —
        what the zero-drain park (engine/parked.py) and its pre-transfer
        pricing multiply by the live page count, kept next to
        :meth:`estimate_nbytes` so both derive from the one pool layout."""
        return cls.estimate_nbytes(
            num_layers, 1, page_size, num_kv_heads, head_dim, dtype=dtype,
            latent_width=latent_width,
        )

    @classmethod
    def create(
        cls,
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
        mesh: Optional[Mesh] = None,
        ring_shape: Optional[Tuple[int, ...]] = None,
        state_shapes: Optional[Tuple[Tuple[int, ...], ...]] = None,
        latent_width: int = 0,
    ) -> "PagePool":
        """``num_layers`` counts the layers the paged pool serves;
        ``ring_shape`` (:meth:`KVLayout.ring_shape`) adds the rings,
        ``state_shapes`` (:meth:`KVLayout.state_shapes`) the recurrent
        state (float32) and its convolution tails; with ``latent_width``
        (:attr:`KVLayout.latent_width`) the pages are latent ones, one
        array in ``k_pages`` and no ``v_pages``."""

        def zeros(shape, spec, dtype=dtype):
            if mesh is None:
                return jnp.zeros(shape, dtype)
            return jax.jit(
                lambda: jnp.zeros(shape, dtype),
                out_shardings=NamedSharding(mesh, spec),
            )()

        shape = cls.pool_shape(
            num_layers, num_pages, page_size, num_kv_heads, head_dim,
            latent_width,
        )
        if latent_width:
            # replicated on a mesh, as the one "KV head" it is read as
            pool = cls(
                k_pages=zeros(shape, STATE_SPEC), v_pages=None,
                kinds=("latent",),
            )
        else:
            pool = cls(
                k_pages=zeros(shape, POOL_SPEC),
                v_pages=zeros(shape, POOL_SPEC),
            )
        if ring_shape is not None and ring_shape[0]:
            pool.k_ring = zeros(ring_shape, RING_SPEC)
            pool.v_ring = zeros(ring_shape, RING_SPEC)
            pool.kinds += ("ring",)
        if state_shapes is not None:
            pool.state = zeros(state_shapes[0], STATE_SPEC, jnp.float32)
            pool.conv_tail = zeros(state_shapes[1], STATE_SPEC)
            pool.kinds += ("state",)
        return pool

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    def nbytes(self) -> int:
        return (
            sum(a.nbytes for a in self._pages()) + self.ring_nbytes()
            + self.state_nbytes()
        )

    def _pages(self) -> Tuple[jnp.ndarray, ...]:
        """The page arrays: (k, v), or the one array of latent pages."""
        if "latent" in self.kinds:
            return (self.k_pages,)
        return (self.k_pages, self.v_pages)

    def ring_nbytes(self) -> int:
        if self.k_ring is None:
            return 0
        return self.k_ring.nbytes + self.v_ring.nbytes

    def state_nbytes(self) -> int:
        if self.state is None:
            return 0
        return self.state.nbytes + self.conv_tail.nbytes

    def as_tuple(self) -> Tuple[jnp.ndarray, ...]:
        """The cache as the programs take it, and as sleep and wake move
        it: (k, v) pages or the one array of latent pages, then the (k, v)
        rings where the model has window layers, then (state, conv_tail)
        where it has recurrent ones."""
        out = self._pages()
        if "ring" in self.kinds:
            out += (self.k_ring, self.v_ring)
        if "state" in self.kinds:
            out += (self.state, self.conv_tail)
        return out

    def replace(self, kv: Tuple[jnp.ndarray, ...]) -> None:
        n = len(self._pages())
        self.k_pages, rest = kv[0], kv[n:]
        if n == 2:
            self.v_pages = kv[1]
        if "ring" in self.kinds:
            self.k_ring, self.v_ring, rest = rest[0], rest[1], rest[2:]
        if "state" in self.kinds:
            self.state, self.conv_tail = rest

    def drop(self) -> None:
        """Let go of every device array (a sleeping engine holds no HBM)."""
        self.k_pages = self.v_pages = self.k_ring = self.v_ring = None
        self.state = self.conv_tail = None


class OutOfPages(Exception):
    """Page pool exhausted — the scheduler must preempt or queue."""


@dataclass
class PageAllocator:
    """Host-side free-list allocator over the pool's page indices.

    Page 0 is reserved as the null page (page tables are initialized to it),
    so sequences never alias a live page before assignment.
    """

    num_pages: int
    _free: List[int] = field(default_factory=list)
    #: monotonic mutation counter: bumps whenever the free list changes, so
    #: blocked-admission memos can key on "did anything move" exactly
    version: int = 0

    def __post_init__(self) -> None:
        if not self._free:
            self._free = list(range(self.num_pages - 1, 0, -1))

    @property
    def available(self) -> int:
        return len(self._free)

    def alloc(self, n: int) -> List[int]:
        if n > len(self._free):
            raise OutOfPages(f"need {n} pages, have {len(self._free)}")
        out = [self._free.pop() for _ in range(n)]
        if out:
            self.version += 1
        return out

    def free(self, pages: List[int]) -> None:
        returned = False
        for p in pages:
            if p == 0:
                continue
            self._free.append(p)
            returned = True
        if returned:
            self.version += 1

    @staticmethod
    def pages_needed(num_tokens: int, page_size: int) -> int:
        return -(-num_tokens // page_size)
