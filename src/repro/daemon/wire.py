"""Framed binary wire protocol for the cache daemon.

Frames are length-prefixed pickles over a stream socket:

* request:  ``(op, frees, payload)`` — ``frees`` is the piggybacked
  list of ``(offset, length)`` arena slots the client has finished
  reading (a free never needs its own round trip);
* reply:    ``("ok", result)`` or ``("err", exc)``.

Read replies carry outcomes in a compact codec (:func:`encode_outcome`
/ :class:`WireOutcome`) plus one payload descriptor per request:
``("shm", offset, length)`` when the bytes sit in the daemon's
shared-memory arena (same-node clients), or ``("raw", bytes)`` streamed
inline (remote clients / arena spills).

The framing itself is deliberately dumb: a 4-byte big-endian length
then the pickle.  Protocol agreement is checked once at ``hello`` time
(``PROTO_VERSION``), and a frame larger than ``MAX_FRAME`` is treated
as a protocol violation rather than an allocation request.
"""
from __future__ import annotations

import pickle
import struct
from typing import List, Optional

from ..core.cache import path_key
from ..core.igtcache import BlockResult
from ..core.types import PathT, block_key

__all__ = ["ConnectionClosed", "MAX_FRAME", "PROTO_VERSION",
           "ProtocolError", "WireOutcome", "encode_outcome", "recv_msg",
           "send_msg"]

PROTO_VERSION = 1
_HEADER = struct.Struct("!I")
MAX_FRAME = 512 * 1024 * 1024


class ConnectionClosed(ConnectionError):
    """Peer went away (EOF mid-frame or before one started)."""


class ProtocolError(RuntimeError):
    """Frame that cannot be ours (oversized length prefix)."""


def send_msg(sock, obj) -> None:
    payload = pickle.dumps(obj, protocol=pickle.HIGHEST_PROTOCOL)
    # one sendall: header+payload coalesced so small commands are one
    # segment on the wire
    sock.sendall(_HEADER.pack(len(payload)) + payload)


def _recv_exact(sock, n: int) -> bytes:
    buf = bytearray()
    while len(buf) < n:
        chunk = sock.recv(n - len(buf))
        if not chunk:
            raise ConnectionClosed(
                f"peer closed mid-frame ({len(buf)}/{n} bytes)")
        buf += chunk
    return bytes(buf)


def recv_msg(sock):
    (n,) = _HEADER.unpack(_recv_exact(sock, _HEADER.size))
    if n > MAX_FRAME:
        raise ProtocolError(f"frame of {n} bytes exceeds MAX_FRAME")
    return pickle.loads(_recv_exact(sock, n))


# ---------------------------------------------------------------------------
# read-reply outcome codec
# ---------------------------------------------------------------------------
#
# Block **keys never cross the wire**.  The kernel serves an extent as
# consecutive blocks ``first..first+n-1`` and the receiver still holds
# the request that produced the outcome, so every key is reconstructible
# from ``(file_path, first_block + i)``.  What travels is plain ints and
# the prefetch candidate list (pickle's C fast path); :class:`WireOutcome`
# materializes ``BlockResult`` objects lazily so metadata-only callers
# never pay for the reconstruction.

def encode_outcome(out, first_block: int) -> tuple:
    """Compact wire form of one outcome: ``(first_block, sizes, hit
    mask, prefetched-hit mask, prefetches)`` — **no block keys**.

    ``out`` is anything with the ``ReadOutcome`` duck type (``blocks`` /
    ``prefetches``); a :class:`WireOutcome` re-encodes for free — its
    original tuple is returned as-is."""
    if isinstance(out, WireOutcome):
        return out._enc
    hits = pf = 0
    sizes = []
    for i, b in enumerate(out.blocks):
        sizes.append(b.size)
        if b.hit:
            hits |= 1 << i
        if b.prefetched_hit:
            pf |= 1 << i
    return first_block, sizes, hits, pf, out.prefetches


class WireOutcome:
    """Receiver-side view of a wire-encoded ``ReadOutcome``: same duck
    type (``blocks`` / ``prefetches`` / ``cached_bytes`` /
    ``remote_bytes``), block objects (and their key strings)
    materialized on first access from the originating request."""

    __slots__ = ("_enc", "_path", "_blocks", "prefetches")

    def __init__(self, enc: tuple, file_path: PathT) -> None:
        self._enc = enc
        self._path = file_path
        self._blocks: Optional[List] = None
        self.prefetches = enc[4]

    @property
    def blocks(self) -> List:
        got = self._blocks
        if got is None:
            first, sizes, hits, pf, _ = self._enc
            path = self._path
            got = [BlockResult(path_key(block_key(path, first + i)), s,
                               bool(hits >> i & 1), bool(pf >> i & 1))
                   for i, s in enumerate(sizes)]
            self._blocks = got
        return got

    @property
    def remote_bytes(self) -> int:
        _, sizes, hits, _, _ = self._enc
        return sum(s for i, s in enumerate(sizes) if not hits >> i & 1)

    @property
    def cached_bytes(self) -> int:
        _, sizes, hits, _, _ = self._enc
        return sum(s for i, s in enumerate(sizes) if hits >> i & 1)
