"""Fault-tolerance vocabulary shared by the cache daemon's client and
its supervisor:

  * ``DaemonUnavailableError`` — the typed error a ``RemoteCacheClient``
    raises while its daemon is away (and it may not read degraded).
  * ``RestartBudget`` — sliding-window restart rate limit: a daemon
    that keeps dying stops being respawned and is marked permanently
    DOWN.
  * lifecycle state constants (``SHARD_UP`` / ``SHARD_RESTARTING`` /
    ``SHARD_DOWN``) used by ``daemon.supervisor.DaemonSupervisor``.

See docs/RELIABILITY.md for the full failure model.
"""
from __future__ import annotations

from collections import deque
from dataclasses import dataclass, field
from typing import Deque

__all__ = [
    "DaemonUnavailableError", "RestartBudget", "SHARD_DOWN",
    "SHARD_RESTARTING", "SHARD_UP",
]

# Lifecycle states of the supervised daemon (strings: cheap to report
# through stats dicts and its events log).
SHARD_UP = "up"                  # alive, serving requests
SHARD_RESTARTING = "restarting"  # died, respawn in progress
SHARD_DOWN = "down"              # restart budget exhausted: permanently out


class DaemonUnavailableError(ConnectionError):
    """The cache daemon behind a ``RemoteCacheClient`` is unreachable —
    crashed, draining, or gone past its restart budget.

    Subclasses ``ConnectionError`` so pre-existing handlers (which
    matched the raw socket errors the old client surfaced) keep working;
    new callers catch this type.  With ``degraded=True`` (the client
    default) readers never see it — reads are served straight from the
    backing store until the daemon returns; it surfaces only for
    ``degraded=False`` clients and for operations that *need* the daemon
    (stats, snapshots, flush-with-result).

    ``state`` is the client's view of the connection
    (``"down"`` while reconnecting, ``"closed"`` after ``close()``).
    """

    def __init__(self, message: str, *, state: str = "down") -> None:
        super().__init__(message)
        self.state = state


@dataclass
class RestartBudget:
    """Sliding-window restart rate limit.

    ``allow(now)`` consumes one restart token if fewer than
    ``max_restarts`` fired within the trailing ``window_s`` seconds;
    otherwise returns ``False`` — the caller marks the daemon
    permanently DOWN.  A crash-looping daemon (poisoned journal, bad
    disk) thus converges to a stable degraded state instead of
    flapping forever.

    Timestamps are caller-supplied (wall or virtual clock) so tests are
    deterministic.
    """

    max_restarts: int = 3
    window_s: float = 60.0
    history: Deque[float] = field(default_factory=deque)

    def allow(self, now: float) -> bool:
        while self.history and now - self.history[0] > self.window_s:
            self.history.popleft()
        if len(self.history) >= self.max_restarts:
            return False
        self.history.append(now)
        return True

    @property
    def used(self) -> int:
        return len(self.history)
