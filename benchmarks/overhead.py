"""Fig. 17 — IGTCache management overhead vs AccessStreamTree node count:
per-access CPU time (µs) and tree memory (MB).  The paper reports 47.6 µs and
73.2 MB at the 10 000-node default (Go implementation; ours is Python —
the shape of the curves, O(log N) time / O(N) memory, is the claim).

Methodology (documented in docs/PERF.md): each configuration runs the same
seeded trace ``repeats`` times and reports the best run (standard practice
for CPU-overhead microbenchmarks — the minimum is the least noise-polluted
sample); the cyclic GC is paused during the timed region so the number
measures the engine, not the allocator's global heap scans.  Results are
printed as CSV rows and persisted to ``BENCH_overhead.json`` so the perf
trajectory is tracked across PRs.  ``--smoke`` runs a single down-scaled
configuration in a couple of seconds for the test job.

``--shards 1,4,8,16`` (the default) additionally measures the path-hash
sharded facade (``ShardedIGTCache``) at the 10k cap over an 8-dataset
layout, with the shard counts interleaved run-by-run so the set is
same-protocol comparable; the points land in the JSON's ``sharded``
section.  The same shard counts (>1) drive the ``rebalance_path`` axis:
the scaled paper-suite cluster sim per shard count under both
``quantum_policy`` settings, recording CHR gap vs unsharded, summary
bytes/round shipped by the sketch-based demand summaries, and
rounds-to-converge (the round after which the planner goes quiet).
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

from repro.core import CacheConfig, IGTCache, ShardedIGTCache
from repro.core.types import MB
from repro.storage import RemoteStore, make_dataset

from .common import csv_row, emit_json, merge_overhead_section

# Historical reference points for the speedup bookkeeping in the JSON:
#   * "pr1_start": what this benchmark printed on the seed engine when PR 1
#     began (seed harness: single run, default GC) — the number the PR's
#     ≥5× target was calibrated against;
#   * "same_protocol": the seed engine re-measured at PR 1 end with THIS
#     harness (best-of-3, GC paused) interleaved with the new engine on the
#     same machine — the apples-to-apples baseline.  The container's CPU
#     throughput varies by >2× over hours, so only interleaved same-protocol
#     pairs are comparable; see docs/PERF.md.
SEED_US_PER_ACCESS_10K = {
    "pr1_start": 221.6,
    "same_protocol": 74.4,
}


def tree_memory_bytes(tree) -> int:
    total = 0
    for node in tree.iter_nodes():
        total += sys.getsizeof(node)
        total += node.ring_memory_bytes()
        total += sys.getsizeof(node.child_hits)
    return total


def _timed_trace(eng, files, n_accesses: int, seed: int) -> float:
    """The shared measurement protocol: seeded random 64 KiB reads with
    inline prefetch completion, timed with the cyclic GC paused.  One copy
    for both the unsharded and the sharded axis — the interleaved
    same-protocol comparison is only meaningful if both run exactly this.
    Returns µs/access."""
    rng = np.random.default_rng(seed)
    idx = rng.integers(0, len(files), n_accesses)
    offs = rng.integers(0, 2, n_accesses)
    gc_was_enabled = gc.isenabled()
    gc.collect()
    gc.disable()
    try:
        t0 = time.perf_counter()
        for i, j in enumerate(idx):
            f = files[int(j)]
            out = eng.read(f.path, int(offs[i]) * 4 * MB, 64 * 1024,
                           i * 0.001)
            for p, s in out.prefetches:
                eng.complete_prefetch(p, s, i * 0.001)
        dt = time.perf_counter() - t0
    finally:
        if gc_was_enabled:
            gc.enable()
    return dt / n_accesses * 1e6


def _run_once(node_cap: int, n_accesses: int, seed: int):
    # Deep layout (multi-block files → file nodes materialize) so the tree
    # genuinely grows toward the cap: ~1 + 80 dirs + 80×120 file nodes
    # reachable under the paper's window-100 child pruning.
    store = RemoteStore()
    store.add(make_dataset("ds", "dir_tree", n_dirs=80, files_per_dir=120,
                           small_file_size=9 * MB))
    cfg = CacheConfig(node_cap=node_cap, min_share=8 * MB,
                      rebalance_quantum=8 * MB)
    eng = IGTCache(store, 512 * MB, cfg=cfg)
    us = _timed_trace(eng, store.datasets["ds"].files, n_accesses, seed)
    mem = tree_memory_bytes(eng.tree)
    return us, mem, eng.tree.node_count()


def measure(node_cap: int, n_accesses: int = 30_000, seed: int = 0,
            repeats: int = 3):
    """Best-of-``repeats`` µs/access (the trace and final engine state are
    identical across repeats, so mem/nodes are taken from the fastest run)."""
    best = None
    for _ in range(max(1, repeats)):
        got = _run_once(node_cap, n_accesses, seed)
        if best is None or got[0] < best[0]:
            best = got
    return best


def _run_once_sharded(node_cap: int, n_accesses: int, seed: int,
                      n_shards: int):
    """One timed run of the path-hash sharded facade.

    Multi-dataset layout (sharding routes on the top-level component, so a
    single-dataset trace would land on one shard): 8 dir_tree datasets with
    the same total dir/file population as the unsharded Fig.-17 layout.
    Every shard count replays the identical seeded trace, so the
    ``n_shards`` axis isolates routing + partitioning overhead.
    """
    store = RemoteStore()
    for i in range(8):
        store.add(make_dataset(f"ds{i}", "dir_tree", n_dirs=10,
                               files_per_dir=120, small_file_size=9 * MB))
    cfg = CacheConfig(node_cap=node_cap, min_share=8 * MB,
                      rebalance_quantum=8 * MB)
    eng = ShardedIGTCache(store, 512 * MB, cfg=cfg, n_shards=n_shards)
    files = [f for ds in store.datasets.values() for f in ds.files]
    us = _timed_trace(eng, files, n_accesses, seed)
    mem = sum(tree_memory_bytes(s.tree) for s in eng.shards)
    return us, mem, eng.node_count()


def measure_shards(shard_counts, node_cap: int, n_accesses: int,
                   seed: int, repeats: int):
    """Interleaved same-protocol sweep over shard counts: repeats alternate
    between configurations so the container's CPU drift (>2×/hour, see
    docs/PERF.md) hits every configuration equally; best run per count."""
    best = {n: None for n in shard_counts}
    for _ in range(max(1, repeats)):
        for n in shard_counts:
            got = _run_once_sharded(node_cap, n_accesses, seed, n)
            if best[n] is None or got[0] < best[n][0]:
                best[n] = got
    return best


def main(scale: float = 1.0, seed: int = 0, smoke: bool = False,
         json_path=None, shard_counts=(1, 4, 8, 16)):
    caps = (10_000,) if smoke else (100, 1000, 10_000, 100_000)
    n_accesses = 6_000 if smoke else 30_000
    repeats = 2 if smoke else 3
    rows = []
    results = {}
    for cap in caps:
        us, mem, nodes = measure(cap, n_accesses=n_accesses, seed=seed,
                                 repeats=repeats)
        results[str(cap)] = {
            "us_per_access": round(us, 1),
            "tree_mb": round(mem / 2**20, 2),
            "nodes": nodes,
        }
        rows.append(csv_row(f"fig17.nodecap_{cap}.us_per_access",
                            round(us, 1),
                            f"mem_mb={mem/2**20:.1f} nodes={nodes} "
                            f"paper@10k=47.6us/73.2MB"))
    # ---- sharded-facade axis (interleaved, same protocol, 10k cap) ----
    sharded = {}
    if shard_counts:
        shard_accesses = 4_000 if smoke else 30_000
        got = measure_shards(tuple(shard_counts), 10_000, shard_accesses,
                             seed, repeats)
        for n in shard_counts:
            us, mem, nodes = got[n]
            sharded[str(n)] = {
                "us_per_access": round(us, 1),
                "tree_mb": round(mem / 2**20, 2),
                "nodes": nodes,
            }
            rows.append(csv_row(f"sharded.shards_{n}.us_per_access",
                                round(us, 1),
                                f"mem_mb={mem/2**20:.1f} nodes={nodes} "
                                f"interleaved-protocol"))
    payload = {
        "n_accesses": n_accesses,
        "repeats": repeats,
        "smoke": smoke,
        "results": results,
        "sharded": sharded,
        "paper_reference": {"us_per_access_at_10k": 47.6,
                            "tree_mb_at_10k": 73.2},
        "seed_reference": dict(SEED_US_PER_ACCESS_10K),
    }
    at10k = results.get("10000")
    if at10k:
        payload["speedup_vs_pr1_start_seed"] = round(
            SEED_US_PER_ACCESS_10K["pr1_start"] / at10k["us_per_access"], 2)
        payload["speedup_same_protocol"] = round(
            SEED_US_PER_ACCESS_10K["same_protocol"] / at10k["us_per_access"],
            2)
    # smoke runs must not clobber the canonical full-sweep record
    name = "overhead_smoke" if smoke else "overhead"
    # ... and a full run must not clobber the axes other benchmarks
    # merged into the shared file (client_path / store_path / ...):
    # carry unknown sections over before rewriting
    from .common import REPO_ROOT
    prev_path = (Path(json_path) if json_path is not None
                 else REPO_ROOT / f"BENCH_{name}.json")
    if prev_path.exists():
        try:
            prev = json.loads(prev_path.read_text())
        except ValueError:
            prev = {}
        for key, val in prev.items():
            if key not in ("bench", "generated_unix"):
                payload.setdefault(key, val)
    out_path = emit_json(name, payload, path=json_path)
    # ---- cross-shard rebalance axis (cluster sim, both policies) ------
    reb_counts = tuple(n for n in shard_counts if n > 1)
    if reb_counts:
        rows.extend(run_rebalance_axis(reb_counts, seed=seed, smoke=smoke,
                                       json_path=json_path or out_path))
    return rows


def run_rebalance_axis(shard_counts=(4, 8, 16), seed: int = 0,
                       smoke: bool = False, json_path=None):
    """Measure + record the ``rebalance_path`` section: the scaled
    paper-suite cluster sim (the tier-1 convergence scenario) per shard
    count under both move-sizing policies, against one unsharded
    reference run.  Reported per configuration:

    * ``chr`` / ``chr_gap_pp`` — block hit ratio and its gap vs the
      unsharded engine (positive = sharded worse);
    * ``rounds`` / ``rounds_to_converge`` — cross-shard rounds run, and
      the (1-based) index of the last round that still moved bytes —
      after it the planner is quiet, i.e. converged;
    * ``summary_bytes_round_max/mean`` — wire size of all shards'
      demand summaries per round (exact top-k rows + CMS/SpaceSaving
      payloads), the number that must stay O(KB)/shard;
    * ``moves`` / ``bytes_moved_mb`` — total planner activity.
    """
    from repro.sim import ClusterSim, make_paper_suite

    scale = 0.08 if smoke else 0.15
    if smoke:
        shard_counts = shard_counts[:1]
    suite = make_paper_suite(scale=scale, seed=seed,
                             job_filter=[2, 8, 9, 14, 16])
    store = RemoteStore()
    for ds in suite.datasets.values():
        store.add(ds)
    cap = int(0.35 * suite.total_bytes())

    def sim_cfg(policy):
        share = max(16 * MB, cap // 128)
        return CacheConfig(min_share=share, rebalance_quantum=share,
                           rebalance_period=10.0,
                           prefetch_budget_bytes=max(64 * MB, cap // 8),
                           quantum_policy=policy)

    mono = ClusterSim(suite, IGTCache(store, cap, cfg=sim_cfg("adaptive"))
                      ).run()
    rows = []
    section = {"smoke": smoke, "scale": scale,
               "unsharded_chr": round(mono.hit_ratio, 4)}
    for n in shard_counts:
        for policy in ("adaptive", "fixed"):
            eng = ShardedIGTCache(store, cap, cfg=sim_cfg(policy),
                                  n_shards=n)
            res = ClusterSim(suite, eng).run()
            trace = res.rebalance_trace
            sb = [r["summary_bytes"] for r in trace]
            active = [i for i, r in enumerate(trace) if r["moves"]]
            key = f"{policy}_{n}"
            section[key] = {
                "chr": round(res.hit_ratio, 4),
                "chr_gap_pp": round(
                    (mono.hit_ratio - res.hit_ratio) * 100, 2),
                "rounds": len(trace),
                "rounds_to_converge": (active[-1] + 1) if active else 0,
                "moves": sum(r["moves"] for r in trace),
                "bytes_moved_mb": round(
                    sum(r["bytes_moved"] for r in trace) / 2**20, 1),
                "summary_bytes_round_max": max(sb, default=0),
                "summary_bytes_round_mean": (round(sum(sb) / len(sb), 1)
                                             if sb else 0),
            }
            rows.append(csv_row(
                f"rebalance_path.{key}.chr_gap_pp",
                section[key]["chr_gap_pp"],
                f"chr={section[key]['chr']} "
                f"rounds_to_converge={section[key]['rounds_to_converge']} "
                f"summary_bytes_max={section[key]['summary_bytes_round_max']}"
            ))
    merge_overhead_section("rebalance_path", section, json_path=json_path)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--smoke", action="store_true",
                    help="single down-scaled configuration for the test job")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--shards", default="1,4,8,16",
                    help="comma-separated shard counts for the sharded-"
                         "facade axis and (counts >1) the rebalance_path "
                         "axis ('' disables both)")
    args = ap.parse_args()
    counts = tuple(int(x) for x in args.shards.split(",") if x.strip())
    main(seed=args.seed, smoke=args.smoke, shard_counts=counts)
