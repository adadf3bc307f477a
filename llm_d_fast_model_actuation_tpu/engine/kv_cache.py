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
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding

from ..ops.attention import POOL_SPEC


@dataclass
class PagePool:
    k_pages: jnp.ndarray
    v_pages: jnp.ndarray

    @staticmethod
    def pool_shape(
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
    ) -> Tuple[int, int, int, int]:
        """The per-direction (k or v) pool array shape — the ONE
        definition shared by :meth:`create`, :meth:`estimate_nbytes` (the
        cost oracle sizes a not-yet-built pool from it) and the AOT
        warm-up's avals (engine/exec_pool.py)."""
        return (num_layers, num_pages, page_size, num_kv_heads * head_dim)

    @classmethod
    def estimate_nbytes(
        cls,
        num_layers: int,
        num_pages: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
    ) -> int:
        """Device bytes a :meth:`create` with these arguments allocates
        (k + v), without allocating — what the actuation cost oracle
        counts into cold-tier predictions (engine/server.py
        _kv_pool_nbytes), kept here so a pool-layout change can never
        silently drift the prediction from the build's bytes_in."""
        import numpy as np

        shape = cls.pool_shape(
            num_layers, num_pages, page_size, num_kv_heads, head_dim
        )
        elems = 1
        for d in shape:
            elems *= int(d)
        return 2 * elems * int(np.dtype(dtype).itemsize)

    @classmethod
    def page_nbytes(
        cls,
        num_layers: int,
        page_size: int,
        num_kv_heads: int,
        head_dim: int,
        dtype: Any = jnp.bfloat16,
    ) -> int:
        """Device bytes ONE page occupies across all layers, k and v —
        what the zero-drain park (engine/parked.py) and its pre-transfer
        pricing multiply by the live page count, kept next to
        :meth:`estimate_nbytes` so both derive from the one pool layout."""
        return cls.estimate_nbytes(
            num_layers, 1, page_size, num_kv_heads, head_dim, dtype=dtype
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
    ) -> "PagePool":
        shape = cls.pool_shape(
            num_layers, num_pages, page_size, num_kv_heads, head_dim
        )
        if mesh is not None:
            sharding = NamedSharding(mesh, POOL_SPEC)
            zeros = jax.jit(
                lambda: jnp.zeros(shape, dtype), out_shardings=sharding
            )
        else:
            zeros = lambda: jnp.zeros(shape, dtype)  # noqa: E731
        return cls(k_pages=zeros(), v_pages=zeros())

    @property
    def num_pages(self) -> int:
        return self.k_pages.shape[1]

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[2]

    def nbytes(self) -> int:
        return self.k_pages.nbytes + self.v_pages.nbytes

    def as_tuple(self) -> Tuple[jnp.ndarray, jnp.ndarray]:
        return self.k_pages, self.v_pages

    def replace(self, kv: Tuple[jnp.ndarray, jnp.ndarray]) -> None:
        self.k_pages, self.v_pages = kv


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
