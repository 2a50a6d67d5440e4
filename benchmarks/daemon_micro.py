"""Cache-daemon micro-benchmarks (``daemon_path`` + ``daemon_recovery``).

The daemon's scale-out claim: the serve path adds one framed round-trip
per batch but removes the per-process kernel, so N client *processes*
sharing one daemon should deliver aggregate metadata throughput that
scales with N.

Protocol: one ``CacheDaemon`` on a temp UDS over a seeded RemoteStore
world; for N in {1, 2, 4}, fork N client processes that each
``open_cache("cache://...")`` and drive seeded metadata ``read_batch``
loops (no byte fetches — this is the command-path number, matching the
other axes) through a start barrier; aggregate accesses/s is the total
access count over the slowest client's wall time.  Results merge into
``BENCH_overhead.json`` under ``daemon_path`` (``--smoke`` → the smoke
file; exercised by tests/test_bench_smoke.py).

``--recovery`` runs the PR 10 survivability axis instead
(``daemon_recovery`` section): warm a journaled daemon, kill it under a
:class:`~repro.daemon.DaemonSupervisor`, and record the whole recovery
arc — degraded-read latency while the daemon is away, supervisor
respawn time (including journal restore), client reconnect time, and
the ramp back to a fully-hitting pass.  The acceptance number is the
warm-vs-cold contrast: a warm restart (journal restore) reaches a
100 %-hit pass in one pass, where a cold daemon must re-learn the
working set over several.
"""
from __future__ import annotations

import argparse
import multiprocessing as mp
import tempfile
import time

import numpy as np

# .common bootstraps sys.path with REPO_ROOT/src — must import before repro
from .common import csv_row, merge_overhead_section

from repro.core import CacheConfig, open_cache
from repro.core.types import MB
from repro.daemon import CacheDaemon, DaemonSupervisor, RemoteCacheClient
from repro.storage import RemoteStore, make_dataset

CLIENT_COUNTS = (1, 2, 4)


def _world(n_datasets: int, files_per_dir: int):
    store = RemoteStore()
    for i in range(n_datasets):
        store.add(make_dataset(f"job{i}", "dir_tree", n_dirs=4,
                               files_per_dir=files_per_dir,
                               small_file_size=256 * 1024))
    return store


def _client_proc(uri, files, n_steps, batch, seed, barrier, q):
    """One forked client: seeded metadata read_batch loop, wall time
    measured from the shared start barrier."""
    rng = np.random.default_rng(seed)
    steps = []
    for _ in range(n_steps):
        picks = rng.integers(0, len(files), batch)
        steps.append([(files[int(j)][0], 0, files[int(j)][1])
                      for j in picks])
    with open_cache(f"{uri}?label=bench{seed}") as client:
        # connection + a warm-up batch outside the timed region
        client.read_batch(steps[0])
        barrier.wait()
        t0 = time.perf_counter()
        for reqs in steps:
            client.read_batch(reqs)
        dt = time.perf_counter() - t0
    q.put((n_steps * batch, dt))


def _measure(uri, files, n_clients, n_steps, batch, seed):
    ctx = mp.get_context("fork")
    barrier = ctx.Barrier(n_clients)
    q = ctx.SimpleQueue()
    procs = [ctx.Process(target=_client_proc,
                         args=(uri, files, n_steps, batch, seed + 31 * c,
                               barrier, q))
             for c in range(n_clients)]
    for p in procs:
        p.start()
    # results are tiny tuples, so the queue pipe can't fill: join first
    # and fail loudly on a dead child instead of hanging in get()
    for p in procs:
        p.join(120)
        if p.exitcode != 0:
            raise RuntimeError(f"bench client exited {p.exitcode}")
    results = [q.get() for _ in procs]
    total = sum(n for n, _ in results)
    wall = max(dt for _, dt in results)     # aggregate over the slowest
    return {"accesses": total,
            "accesses_per_s": round(total / wall, 1),
            "us_per_access": round(wall / total * 1e6, 1)}


def main(smoke: bool = False, seed: int = 0, json_path=None):
    n_steps = 8 if smoke else 64
    batch = 8 if smoke else 64
    files_per_dir = 4 if smoke else 8
    store = _world(4, files_per_dir)
    cfg = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                      window=40, reanalyze_every=20, node_cap=2000)
    section = {"smoke": smoke, "batch": batch, "seed": seed,
               "n_accesses_per_client": n_steps * batch}
    with CacheDaemon(store, 96 * MB, cfg=cfg) as daemon:
        files = [(f.path, f.size)
                 for ds in store.datasets.values() for f in ds.files]
        for n in CLIENT_COUNTS:
            section[f"daemon_{n}"] = _measure(daemon.uri, files, n,
                                              n_steps, batch, seed)
        st = daemon.daemon_stats()
        section["daemon_stats"] = {
            "served_reads": st["served_reads"], "byes": st["byes"],
            "spills": st["spills"], "reaped": st["reaped"]}

    r1 = section["daemon_1"]["accesses_per_s"]
    r4 = section["daemon_4"]["accesses_per_s"]
    section["scaling_4_vs_1"] = round(r4 / r1, 2)

    rows = [
        csv_row("daemon_path.daemon_1_accesses_per_s", r1,
                f"us_per_access={section['daemon_1']['us_per_access']}"),
        csv_row("daemon_path.daemon_4_accesses_per_s", r4,
                f"us_per_access={section['daemon_4']['us_per_access']}"),
        csv_row("daemon_path.scaling_4_vs_1", section["scaling_4_vs_1"],
                f"daemon_2={section['daemon_2']['accesses_per_s']}"),
    ]
    merge_overhead_section("daemon_path", section, json_path)
    return rows


def _hit_pass(cli, pass_files, now, rng):
    """One shuffled read pass over the working set: (hits, blocks,
    wall_s).  Shuffled, not in-order — a sequential scan classifies as
    an eager-eviction stream whose blocks are consumed on read, which
    leaves nothing resident for the snapshot to carry across a
    restart.  The random pattern is the cache-*keeping* workload the
    warm/cold contrast is about."""
    hits = total = 0
    order = rng.permutation(len(pass_files))
    t0 = time.perf_counter()
    for i, j in enumerate(order):
        fp, size = pass_files[int(j)]
        r = cli.read(fp, 0, size, now + i)
        for blk in r.blocks:
            hits += bool(blk.hit)
            total += 1
    return hits, total, time.perf_counter() - t0


def _ramp(cli, pass_files, now, rng, max_passes=12):
    """Passes (and wall seconds) until a pass hits on every block —
    the time-to-rewarmed number the warm/cold contrast is about."""
    wall = 0.0
    for p in range(1, max_passes + 1):
        hits, total, dt = _hit_pass(cli, pass_files, now + p * 1000, rng)
        wall += dt
        if hits == total:
            return p, round(wall, 4), 1.0
    return max_passes, round(wall, 4), hits / max(1, total)


def run_recovery(smoke: bool = False, seed: int = 0, json_path=None):
    """The ``daemon_recovery`` axis: kill → degraded → respawn →
    warm-restore → reconnect, each leg timed."""
    n_pass_files = 16 if smoke else 64
    store = _world(2, 4 if smoke else 8)
    files = [(f.path, f.size)
             for ds in store.datasets.values() for f in ds.files]
    pass_files = files[:n_pass_files]
    cfg = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                      window=40, reanalyze_every=20, node_cap=2000)
    root = tempfile.mkdtemp(prefix="igt-recovery-")
    sock = f"{root}/d.sock"
    jdir = f"{root}/journal"

    def factory():
        return CacheDaemon(store, 96 * MB, cfg=cfg, uds=sock,
                           journal_dir=jdir,
                           snapshot_every_s=0.2).start()

    section = {"smoke": smoke, "seed": seed,
               "n_pass_files": n_pass_files}
    rng = np.random.default_rng(seed)
    sup = DaemonSupervisor(factory, restart_budget=4)
    cli = RemoteCacheClient(sup.uri, fetch_bytes=True, backing=store,
                            max_backoff_s=0.25)
    try:
        # cold ramp: a fresh daemon re-learns the working set over
        # repeated passes — the baseline the warm restart must beat
        passes, wall, chr_ = _ramp(cli, pass_files, 0.0, rng)
        section["cold_ramp"] = {"passes": passes, "wall_s": wall,
                                "final_pass_chr": chr_}
        # pin the pre-fault manifest: the drill measures restore cost,
        # not snapshot cadence (the periodic snapshot may race the ramp)
        sup.daemon.write_snapshot()
        # --- kill drill: degraded latency + recovery + reconnect time
        t_kill = time.perf_counter()
        sup.kill_daemon()
        lat = []
        for i, (fp, size) in enumerate(pass_files):
            t0 = time.perf_counter()
            r = cli.read(fp, 0, size, 5000.0 + i)
            lat.append(time.perf_counter() - t0)
            assert r.data is not None       # degraded reads always serve
        section["degraded"] = {
            "reads": len(lat),
            "us_per_read": round(sum(lat) / len(lat) * 1e6, 1),
            "worst_us": round(max(lat) * 1e6, 1),
        }
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            try:
                cli.heartbeat()
                break
            except ConnectionError:
                time.sleep(0.01)
        section["reconnect_s"] = round(time.perf_counter() - t_kill, 4)
        done = [e for e in sup.events if e["kind"] == "respawn_done"]
        section["respawn_s"] = round(done[-1]["recovery_s"], 4)
        section["restore"] = {
            k: done[-1]["restore"].get(k)
            for k in ("mode", "blocks", "bytes", "restore_s")}
        # warm ramp: the respawned daemon restored its manifest from
        # the journal — the working set should hit on the first pass
        passes, wall, chr_ = _ramp(cli, pass_files, 10_000.0, rng)
        section["warm_ramp"] = {"passes": passes, "wall_s": wall,
                                "final_pass_chr": chr_}
        cs = cli.connection_stats()
        section["client"] = {
            "reconnects": cs["reconnects"],
            "disconnects": cs["disconnects"],
            "degraded_reads": cs["client_stats"]["degraded_reads"],
            "degraded_bytes": cs["client_stats"]["degraded_bytes"],
        }
    finally:
        cli.close()
        sup.close()

    rows = [
        csv_row("daemon_recovery.respawn_s", section["respawn_s"],
                f"restore_mode={section['restore']['mode']}"),
        csv_row("daemon_recovery.reconnect_s", section["reconnect_s"],
                f"reconnects={section['client']['reconnects']}"),
        csv_row("daemon_recovery.degraded_us_per_read",
                section["degraded"]["us_per_read"],
                f"reads={section['degraded']['reads']}"),
        csv_row("daemon_recovery.warm_ramp_passes",
                section["warm_ramp"]["passes"],
                f"cold={section['cold_ramp']['passes']}"),
    ]
    merge_overhead_section("daemon_recovery", section, json_path)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="down-scaled run for the test job")
    ap.add_argument("--recovery", action="store_true",
                    help="run the daemon_recovery axis instead")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    if args.recovery:
        run_recovery(smoke=args.smoke, seed=args.seed)
    else:
        main(smoke=args.smoke, seed=args.seed)
