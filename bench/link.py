"""The remote store on the wall clock: every fetch the cache sends to it
costs request latency and then a turn on one shared FIFO link.

The arithmetic is a copy of ``repro.storage.object_store.TransferModel``
and ``repro.sim.link.SharedLink`` (150 ms per request, 125 MB/s shared,
from the paper's testbed), applied to real time instead of the
simulator's virtual clock:

* a request issued at ``t`` arrives at ``t + latency_s``; latencies of
  requests issued from different threads overlap;
* its bytes then wait their turn on the one link, first come first
  served, and occupy it for ``bytes / bandwidth_Bps``;
* the caller sleeps until its bytes have crossed.

The cache kernel keeps whole blocks resident, so a remote fetch of any
range charges the link with every block the range touches.  The client
keeps no block payload: it serves a hit by asking the store for the hit
ranges again (``CacheClient._fetch_hits``).  :func:`link_client` marks
that path local, so hits cost no link time, and checks that it is there.

File content is made by the benchmark from the seed (:class:`Content`),
so the reference check can recompute every byte that was served.
"""
from __future__ import annotations

import contextlib
import hashlib
import threading
import time
import zlib
from typing import Callable, Tuple

import numpy as np

from repro.storage.object_store import RemoteStore, TransferModel


class Link:
    """One shared remote link.  ``reserve`` is pure arithmetic on the
    given clock reading, so it can be checked on a fake clock."""

    def __init__(self, latency_s: float, bandwidth_Bps: float,
                 clock: Callable[[], float] = time.monotonic,
                 sleep: Callable[[float], None] = time.sleep) -> None:
        self.latency_s = latency_s
        self.bandwidth_Bps = bandwidth_Bps
        self.clock = clock
        self.sleep = sleep
        self.sleep_on = True
        self._lock = threading.Lock()
        self._free_at = 0.0
        self.requests = 0
        self.bytes = 0
        self.queue_wait_s = 0.0

    def reserve(self, nbytes: int, now: float) -> float:
        """Book a request issued at ``now``; returns when its bytes have
        crossed the link."""
        with self._lock:
            arrive = now + self.latency_s
            start = max(arrive, self._free_at)
            self._free_at = start + nbytes / self.bandwidth_Bps
            self.requests += 1
            self.bytes += nbytes
            self.queue_wait_s += start - arrive
            return self._free_at

    def transfer(self, nbytes: int) -> None:
        if not self.sleep_on:
            with self._lock:
                self.requests += 1
                self.bytes += nbytes
            return
        done = self.reserve(nbytes, self.clock())
        wait = done - self.clock()
        if wait > 0:
            self.sleep(wait)

    def counters(self) -> Tuple[int, int, float]:
        with self._lock:
            return self.requests, self.bytes, self.queue_wait_s


class Content:
    """Deterministic file bytes from a seed: a pool of random bytes, and
    each file a window into it at an offset hashed from its path."""

    def __init__(self, seed: int, pool_bytes: int = 128 << 20,
                 max_read: int = 8 << 20) -> None:
        rng = np.random.default_rng(seed)
        self.pool_bytes = pool_bytes
        self.max_read = max_read
        pool = np.frombuffer(rng.bytes(pool_bytes), np.uint8)
        self._pool = np.concatenate([pool, pool[:max_read]])
        self._seed = seed

    def _shift(self, path) -> int:
        h = hashlib.blake2b(f"{self._seed}:{'/'.join(path)}".encode(),
                            digest_size=8).digest()
        return int.from_bytes(h, "little") % self.pool_bytes

    def range(self, path, offset: int, length: int) -> np.ndarray:
        start = (self._shift(path) + offset) % self.pool_bytes
        if length <= self.max_read:
            return self._pool[start:start + length].copy()
        idx = (start + np.arange(length, dtype=np.int64)) % self.pool_bytes
        return self._pool[idx]


class LinkStore(RemoteStore):
    """``RemoteStore`` metadata (what the cache kernel plans with) over
    :class:`Content` bytes that cross a :class:`Link`.  ``local_bytes``
    counts the bytes served from the host."""

    def __init__(self, link: Link, content: Content) -> None:
        super().__init__(TransferModel(link.latency_s, link.bandwidth_Bps))
        self.link = link
        self.content = content
        self.local_bytes = 0
        self._local_lock = threading.Lock()
        self._tls = threading.local()

    @contextlib.contextmanager
    def local_reads(self):
        """Fetches made inside are cache hits served from the host."""
        self._tls.local = True
        try:
            yield
        finally:
            self._tls.local = False

    def _charged_bytes(self, file_path, offset: int, length: int) -> int:
        bs, size = self.block_size, self.file_size(file_path)
        first, last = offset // bs, (offset + max(length, 1) - 1) // bs
        return sum(min(bs, size - b * bs) for b in range(first, last + 1))

    def fetch_range(self, path, offset: int, length: int) -> np.ndarray:
        file_path, abs_off = self._absolute_range(path, offset, length)
        data = self.content.range(file_path, abs_off, length)
        if getattr(self._tls, "local", False):
            with self._local_lock:
                self.local_bytes += length
        else:
            self.link.transfer(self._charged_bytes(file_path, abs_off,
                                                   length))
        return data


def link_client(client, store: LinkStore):
    """Mark the hit path of ``client`` (an ``open_cache`` client over
    ``store``) local on the store, and give it a ``log``: while that is a
    list, every served extent is recorded as ``(path, offset, length,
    crc32)``."""
    fetch_hits = getattr(client, "_fetch_hits", None)
    if not callable(fetch_hits):
        raise RuntimeError("the cache client has no hit path "
                           "(_fetch_hits) to serve from the host")
    read_batch, read = client.read_batch, client.read

    def local_hits(plans, fetched):
        with store.local_reads():
            fetch_hits(plans, fetched)

    def logged_read_batch(requests, now=None, *, fetch=None):
        results = read_batch(requests, now, fetch=fetch)
        if client.log is not None:
            client.log.append([(fp, off, n, zlib.crc32(r.data))
                               for (fp, off, n), r in zip(requests, results)])
        return results

    def logged_read(file_path, offset, size, now=None, *, fetch=None):
        result = read(file_path, offset, size, now, fetch=fetch)
        if client.log is not None and result.data is not None:
            client.log.append([(file_path, offset, result.data.nbytes,
                                zlib.crc32(result.data))])
        return result

    client._fetch_hits = local_hits
    client.read_batch, client.read = logged_read_batch, logged_read
    client.log = None
    return client


def mismatched_reads(content: Content, log) -> Tuple[int, int]:
    """(reads checked, reads whose served bytes differ from the store's)
    over a :func:`link_client` log."""
    n = bad = 0
    for batch in log:
        for fp, off, length, crc in batch:
            n += 1
            bad += zlib.crc32(content.range(fp, off, length)) != crc
    return n, bad
