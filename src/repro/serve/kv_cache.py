"""Paged KV-cache block pool for continuous batching.

The serving decode state is one flat *pool* per layer — tensors of shape
``(L, num_blocks * block_size, nkv, hd)`` created by
``models.transformer.init_paged_caches`` — plus per-request *block
tables* mapping logical token positions onto pool slots: position ``p``
of a request whose table is ``[b0, b1, ...]`` lives at flat slot
``b[p // block_size] * block_size + p % block_size``.

This module owns the host side of that contract:

* :class:`BlockPool` — the allocator.  Blocks are handed out lazily as a
  request's context grows and returned wholesale when it retires.  Block
  0 is the reserved **trash block**: unallocated block-table entries and
  inactive decode slots point there, so the jitted decode step writes
  unconditionally (masked slots land in trash) and never branches on
  occupancy.  The allocator therefore hands out blocks ``1..num_blocks-1``
  and guarantees no block is ever *writable* by two requests at once.

  Blocks are **refcounted** so the prefix cache (``serve/prefix_cache``)
  can share read-only prompt blocks across requests: :meth:`alloc` gives
  the owner the sole reference, :meth:`share` joins an existing live
  block to another request's table (read-only by contract — sharers
  write suffix/generated tokens into their own blocks), and
  :meth:`retain`/:meth:`release` carry the cache's own reference.  A
  block returns to the free list only when its last reference drops;
  :meth:`defrag` compacts every referenced block, owned or cache-held.
* Index helpers (:func:`flat_slots`, :func:`table_row`) shared by the
  batcher and the property tests.
* Device-side data movement (:func:`scatter_prefill`,
  :func:`apply_defrag`) — pure jnp, no model knowledge.

The device read/write side (gather to position order + masked attention)
lives in ``models/common.mha_decode_paged``; gathering the pages into
position order first is what makes the paged read bitwise-equal to a
contiguous cache (pinned in tests/test_kv_pool.py).
"""
from __future__ import annotations

from typing import Any, Dict, List, Sequence

import numpy as np

TRASH_BLOCK = 0


class PoolExhausted(RuntimeError):
    """Raised when an allocation cannot be satisfied from the free list."""


class BlockPool:
    """Host-side block allocator over ``num_blocks`` fixed-size blocks.

    Block :data:`TRASH_BLOCK` is reserved; ``num_blocks - 1`` blocks are
    allocatable.  Per-request block lists keep allocation order, which is
    logical position order (the batcher allocates as the context grows),
    so ``blocks_of`` can be written straight into a block table.
    """

    def __init__(self, num_blocks: int, block_size: int):
        if num_blocks < 2:
            raise ValueError("need >= 2 blocks (block 0 is the trash block)")
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        self.num_blocks, self.block_size = num_blocks, block_size
        # LIFO free list, lowest ids popped first (keeps the pool compact)
        self._free: List[int] = list(range(num_blocks - 1, 0, -1))
        self._owned: Dict[int, List[int]] = {}
        self._ref: Dict[int, int] = {}  # block -> refcount (only > 0 entries)

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        """Distinct blocks with at least one reference."""
        return len(self._ref)

    def blocks_of(self, request_id: int) -> List[int]:
        return list(self._owned.get(request_id, ()))

    def refcount(self, block: int) -> int:
        return self._ref.get(block, 0)

    def alloc(self, request_id: int, n: int = 1) -> List[int]:
        """Allocate ``n`` blocks for ``request_id`` (appended in order)."""
        if n > len(self._free):
            raise PoolExhausted(
                f"request {request_id} needs {n} block(s), only "
                f"{len(self._free)}/{self.num_blocks - 1} free")
        blocks = [self._free.pop() for _ in range(n)]
        for b in blocks:
            self._ref[b] = 1
        self._owned.setdefault(request_id, []).extend(blocks)
        return blocks

    def share(self, request_id: int, blocks: Sequence[int]) -> None:
        """Join live blocks to ``request_id``'s table, read-only.

        Each block gains a reference; it appears in ``blocks_of`` so the
        request can address it via its block table, but by contract the
        sharer never writes into it (shared prefix blocks are fully
        written before they are shared).
        """
        for b in blocks:
            if self._ref.get(b, 0) < 1:
                raise ValueError(f"cannot share dead block {b}")
        for b in blocks:
            self._ref[b] += 1
        self._owned.setdefault(request_id, []).extend(blocks)

    def retain(self, blocks: Sequence[int]) -> None:
        """Add a bare reference (no owner) to each live block."""
        for b in blocks:
            if self._ref.get(b, 0) < 1:
                raise ValueError(f"cannot retain dead block {b}")
        for b in blocks:
            self._ref[b] += 1

    def release(self, blocks: Sequence[int]) -> List[int]:
        """Drop one reference per block; blocks reaching zero are freed.

        Returns the blocks actually returned to the free list.
        """
        freed = []
        for b in blocks:
            r = self._ref.get(b, 0)
            if r < 1:
                raise ValueError(f"releasing dead block {b}")
            if r == 1:
                del self._ref[b]
                freed.append(b)
            else:
                self._ref[b] = r - 1
        self._free.extend(sorted(freed, reverse=True))
        return freed

    def free_request(self, request_id: int) -> List[int]:
        """Drop ``request_id``'s reference on every block it holds.

        Blocks whose last reference this was return to the free list;
        blocks still referenced elsewhere (prefix-cache entries, other
        sharers) stay live.  Returns the request's full block list.
        """
        blocks = self._owned.pop(request_id, [])
        self.release(blocks)
        return blocks

    def defrag(self) -> Dict[int, int]:
        """Compact live blocks onto the lowest ids (trash stays put).

        Live means refcount > 0 — owned by a request *or* held by the
        prefix cache.  Returns the ``{old: new}`` remap (identity entries
        omitted) and rewrites the internal ownership/refcount maps.  The
        caller must apply the same remap to the device pool
        (:func:`apply_defrag`), to its block tables, and to the prefix
        cache (``PrefixCache.apply_defrag``) before the next decode step.
        """
        live = sorted(self._ref)
        remap = {old: new for new, old in enumerate(live, start=1)
                 if old != new}
        if remap:
            for rid, bl in self._owned.items():
                self._owned[rid] = [remap.get(b, b) for b in bl]
            self._ref = {remap.get(b, b): r for b, r in self._ref.items()}
            self._free = list(range(self.num_blocks - 1, len(live), -1))
        return remap


def flat_slots(blocks: Sequence[int], length: int, block_size: int) -> np.ndarray:
    """Flat pool slots of logical positions ``0..length-1``."""
    if length > len(blocks) * block_size:
        raise ValueError(f"{length} positions exceed {len(blocks)} block(s) "
                         f"x {block_size}")
    pos = np.arange(length)
    b = np.asarray(blocks, np.int32)
    return (b[pos // block_size] * block_size + pos % block_size).astype(np.int32)


def table_row(blocks: Sequence[int], max_blocks: int) -> np.ndarray:
    """Pad a request's block list into a fixed-width table row (trash-filled)."""
    if len(blocks) > max_blocks:
        raise ValueError(f"{len(blocks)} blocks exceed table width {max_blocks}")
    row = np.full((max_blocks,), TRASH_BLOCK, np.int32)
    row[:len(blocks)] = blocks
    return row


def scatter_prefill(pool: Dict[str, Any], kv: Dict[str, Any],
                    flat_idx: np.ndarray) -> Dict[str, Any]:
    """Write a prefill's K/V rows ``(L, P, nkv, hd)`` into pool slots
    ``flat_idx`` (P,), in the pool's row shape (``(nkv, hd)`` or
    ``(nkv*hd,)``).  Values are cast to the pool dtype — the same cast
    the contiguous serve cache applies, keeping the paged read bitwise
    equal to the contiguous one."""
    def rows(x, t):
        return x.reshape(x.shape[:2] + t.shape[2:]).astype(t.dtype)

    return {name: pool[name].at[:, flat_idx].set(rows(kv[name], pool[name]))
            for name in pool}


def apply_defrag(pool: Dict[str, Any], remap: Dict[int, int],
                 num_blocks: int, block_size: int) -> Dict[str, Any]:
    """Permute pool contents per a :meth:`BlockPool.defrag` remap."""
    if not remap:
        return pool
    perm = np.arange(num_blocks)
    for old, new in remap.items():
        perm[new] = old

    def move(t):
        blocked = t.reshape((t.shape[0], num_blocks, block_size) + t.shape[2:])
        return blocked[:, perm].reshape(t.shape)

    return {name: move(t) for name, t in pool.items()}
