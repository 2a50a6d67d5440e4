"""The daemon's shared-memory payload arena.

Same-node sessions get read payloads through one
``multiprocessing.shared_memory`` block instead of the socket: the
daemon copies the bytes into a slot and replies with an
``("shm", offset, length)`` descriptor, the client copies them out and
piggybacks the slot's free on its next request.  :class:`ShmArena`
owns the block; :class:`RegionAllocator` is the first-fit free list
over it, driven only by the daemon (one writer, so no cross-process
locking).
"""
from __future__ import annotations

import bisect
from typing import List, Tuple

__all__ = ["RegionAllocator", "ShmArena"]


class RegionAllocator:
    """First-fit free-list allocator over ``[offset, offset + length)``.
    Frees coalesce with adjacent free intervals."""

    def __init__(self, offset: int, length: int) -> None:
        self._free: List[Tuple[int, int]] = ([(offset, length)]
                                             if length > 0 else [])

    def alloc(self, n: int) -> int:
        """Absolute offset of an ``n``-byte slot, or -1 when exhausted."""
        if n <= 0:
            return -1
        for i, (off, length) in enumerate(self._free):
            if length >= n:
                if length == n:
                    self._free.pop(i)
                else:
                    self._free[i] = (off + n, length - n)
                return off
        return -1

    def free(self, offset: int, n: int) -> None:
        if n <= 0:
            return
        i = bisect.bisect_left(self._free, (offset, n))
        self._free.insert(i, (offset, n))
        # coalesce with right then left neighbour
        if i + 1 < len(self._free):
            off, length = self._free[i]
            noff, nlen = self._free[i + 1]
            if off + length == noff:
                self._free[i] = (off, length + nlen)
                self._free.pop(i + 1)
        if i > 0:
            poff, plen = self._free[i - 1]
            off, length = self._free[i]
            if poff + plen == off:
                self._free[i - 1] = (poff, plen + length)
                self._free.pop(i)

    def free_bytes(self) -> int:
        return sum(length for _, length in self._free)


class ShmArena:
    """One ``multiprocessing.shared_memory`` block of ``size`` bytes.
    Clients attach by ``name``."""

    def __init__(self, size: int) -> None:
        from multiprocessing import shared_memory
        self.size = size
        self.shm = shared_memory.SharedMemory(create=True, size=size)
        self.name = self.shm.name
        self._closed = False

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        try:
            self.shm.close()
        except BufferError:
            # live views into the block remain: the mapping can only
            # drop when they are collected.  Silence the destructor's
            # doomed re-close (it would print an ignored BufferError at
            # interpreter exit) — the OS reclaims the mapping with the
            # process either way.
            self.shm.close = lambda: None
        try:
            self.shm.unlink()
        except FileNotFoundError:  # pragma: no cover - double unlink
            pass
