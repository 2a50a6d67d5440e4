"""Chaos harness: seeded failure injection for the cache daemon.

The recovery machinery of ``repro.daemon`` is only as trustworthy as
the failures it has been marched through.  This module is the
controlled failure source: a :class:`ChaosMonkey` with one strike per
failure domain, a deterministic strike planner (``plan_strikes`` — same
seed, same schedule), and a :class:`ChaosSchedule` that fires the
planned strikes as a trace driver advances through its steps.  The
cluster simulator accepts the same strikes as virtual-time events
(``ClusterSim(chaos_events=...)``), so a mixed-workload trace can lose a
client or its daemon mid-run and the whole arc plays out inside one
test.

Strikes are *count-driven* (fire at step N), not wall-clock-driven:
schedules replay bit-identically regardless of machine speed, which is
what lets the drills assert exact bookkeeping (conservation identities,
zero lost reads) instead of sampling a race.

* ``client_kill`` strikes a registered daemon client (anything with a
  ``kill()`` — a ``repro.daemon.RemoteCacheClient`` dies silently,
  socket held open, so only the daemon's session lease can notice):
  the fault-of-the-client arc.
* ``daemon_kill`` crashes a supervised cache daemon abruptly (sockets
  die mid-conversation, no final snapshot — the SIGKILL stand-in; the
  ``repro.daemon.DaemonSupervisor`` notices and respawns on the same
  socket path, warm-starting from the journal), and ``daemon_restart``
  rolls it gracefully (SIGTERM shape: drain → ``going_down`` to
  sessions → final snapshot → respawn).  Register the supervisor via
  ``ChaosMonkey(daemon=...)``; the drill asserts the full kill →
  degraded reads → respawn → reconnect → CHR re-convergence arc.
"""
from __future__ import annotations

import random
import time
from collections import defaultdict
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence

__all__ = ["ChaosMonkey", "ChaosSchedule", "ChaosStrike", "plan_strikes"]

KINDS = ("client_kill", "daemon_kill", "daemon_restart")


@dataclass(frozen=True)
class ChaosStrike:
    """One planned failure: at trace step ``step``, do ``kind`` to
    registered client ``sid``.  The daemon strikes ignore ``sid`` —
    there is one supervised daemon."""

    step: int
    kind: str          # one of KINDS
    sid: int


class ChaosMonkey:
    """Failure injector over daemon clients and a supervised daemon.

    ``clients`` registers daemon-client victims for the ``client_kill``
    strike (index = sid): each must expose ``kill()`` — the
    ``RemoteCacheClient`` drill that goes silent without closing the
    socket, so the daemon's *lease*, not EOF, must reclaim the session.
    ``daemon`` is the ``DaemonSupervisor`` the daemon strikes hit.

    Every strike lands in ``self.strikes`` (kind, sid, pid, wall time)
    for post-run audit.
    """

    def __init__(self, clients: Sequence = (), daemon=None) -> None:
        if daemon is not None and (
                not hasattr(daemon, "kill_daemon")
                or not hasattr(daemon, "drain_restart")):
            raise TypeError(
                "daemon= needs a DaemonSupervisor (kill_daemon/"
                f"drain_restart); got {type(daemon).__name__} — an "
                "unsupervised daemon would stay dead after the strike")
        if not clients and daemon is None:
            raise TypeError("ChaosMonkey needs at least one registered "
                            "client victim or a supervised daemon")
        self.clients = list(clients)
        self.daemon = daemon
        self.strikes: List[dict] = []

    # ------------------------------------------------------------- strikes
    def _log(self, kind: str, sid: int, pid: Optional[int]) -> None:
        self.strikes.append({"kind": kind, "sid": sid, "pid": pid,
                             "at": time.monotonic()})

    def client_kill(self, sid: int) -> None:
        """Kill registered client ``sid`` the crashed-process way: it
        goes silent (heartbeats stop, socket stays open), so the
        daemon's session lease — not EOF — must notice and reclaim."""
        victim = self.clients[sid]
        victim.kill()
        self._log("client_kill", sid, getattr(victim, "pid", None))

    def _require_daemon(self, kind: str) -> None:
        if self.daemon is None:
            raise RuntimeError(f"strike {kind!r} needs a supervised "
                               "daemon (ChaosMonkey(daemon=...))")

    def daemon_kill(self, sid: int = 0) -> None:
        """Crash the supervised daemon abruptly (SIGKILL stand-in):
        every session socket dies mid-conversation, no final snapshot.
        The supervisor respawns within its restart budget; clients see
        EOF, serve degraded reads, and reconnect to the same path."""
        self._require_daemon("daemon_kill")
        self.daemon.kill_daemon()
        self._log("daemon_kill", sid, None)

    def daemon_restart(self, sid: int = 0) -> None:
        """Roll the daemon gracefully (SIGTERM shape): drain — sessions
        get ``going_down``, executor flushed, final snapshot written —
        then respawn immediately on the same socket path."""
        self._require_daemon("daemon_restart")
        self.daemon.drain_restart()
        self._log("daemon_restart", sid, None)

    def strike(self, kind: str, sid: int) -> None:
        if kind not in KINDS:
            raise ValueError(f"unknown strike kind {kind!r}; "
                             f"expected one of {KINDS}")
        getattr(self, kind)(sid)


def plan_strikes(n_steps: int, *, seed: int = 0, n_strikes: int = 1,
                 kinds: Sequence[str] = ("client_kill",),
                 min_step: int = 1,
                 n_clients: int = 0) -> List[ChaosStrike]:
    """Deterministic strike schedule: ``n_strikes`` failures at distinct
    pseudo-random steps in ``[min_step, n_steps)``, kinds and victims
    drawn from the same seeded stream.  ``client_kill`` strikes draw
    their victim from ``range(n_clients)``.  Same (seed, shape) → same
    schedule, always."""
    for k in kinds:
        if k not in KINDS:
            raise ValueError(f"plannable kinds are {'/'.join(KINDS)}, "
                             f"got {k!r}")
    if "client_kill" in kinds and n_clients <= 0:
        raise ValueError("client_kill strikes need n_clients > 0")
    if n_steps <= min_step:
        raise ValueError("trace too short for the requested strike window")
    rng = random.Random(seed)
    span = range(min_step, n_steps)
    steps = sorted(rng.sample(span, min(n_strikes, len(span))))
    out: List[ChaosStrike] = []
    for step in steps:
        kind = kinds[rng.randrange(len(kinds))]
        sid = rng.randrange(n_clients) if kind == "client_kill" else 0
        out.append(ChaosStrike(step, kind, sid))
    return out


class ChaosSchedule:
    """Binds a strike plan to a monkey: the trace driver calls
    ``on_step(i)`` once per step and every strike planned at step ``i``
    fires.  ``fired`` is the executed subset (each plan entry fires at
    most once)."""

    def __init__(self, monkey: ChaosMonkey,
                 strikes: Sequence[ChaosStrike]) -> None:
        self.monkey = monkey
        self._by_step: Dict[int, List[ChaosStrike]] = defaultdict(list)
        for s in strikes:
            self._by_step[s.step].append(s)
        self.fired: List[ChaosStrike] = []

    def on_step(self, step: int) -> List[ChaosStrike]:
        due = self._by_step.pop(step, [])
        for s in due:
            self.monkey.strike(s.kind, s.sid)
            self.fired.append(s)
        return due
