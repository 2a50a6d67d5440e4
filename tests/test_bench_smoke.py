"""Fast --smoke run of the overhead benchmark: keeps the perf-tracking
pipeline (BENCH_overhead.json emission) exercised in the test job."""
import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def test_overhead_smoke_emits_json(tmp_path):
    from benchmarks import overhead

    out = tmp_path / "BENCH_overhead.json"
    rows = overhead.main(smoke=True, json_path=out)
    assert rows, "smoke run produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["smoke"] is True
    at10k = payload["results"]["10000"]
    assert at10k["us_per_access"] > 0
    assert at10k["nodes"] > 0
    assert "seed_reference" in payload
    assert "speedup_vs_pr1_start_seed" in payload
    # sharded-facade axis: every default shard count measured
    # (interleaved) into the perf trajectory
    for n in ("1", "4", "8", "16"):
        point = payload["sharded"][n]
        assert point["us_per_access"] > 0
        assert point["nodes"] > 0
    # rebalance_path axis (merged section; smoke runs the first shard
    # count >1 only): sketch-based demand summaries drive the planner —
    # CHR gap vs unsharded recorded for both quantum policies, and the
    # per-round summary payload stays KB-scale
    reb = payload["rebalance_path"]
    assert reb["smoke"] is True
    assert reb["unsharded_chr"] > 0
    for key in ("adaptive_4", "fixed_4"):
        point = reb[key]
        assert point["chr"] > 0
        assert point["rounds"] > 0
        assert point["summary_bytes_round_max"] > 0
        assert point["summary_bytes_round_max"] <= 4 * 4096
    # the adaptive policy is the one that converges: never (meaningfully)
    # worse than the fixed-quantum legacy path on the same trace
    assert reb["adaptive_4"]["chr"] >= reb["fixed_4"]["chr"] - 0.01


def test_sketch_micro_smoke(tmp_path):
    """--smoke sketch_path axis: the PR-7 demand-tracking pipeline
    (update + per-stream query + ship/merge) — sketch vs exact
    ghost-counter path, merged into the shared overhead JSON.  The
    strict per-access crossover is a 1M-distinct full-scale claim; smoke
    checks the pipeline runs, stays in the same cost ballpark, and the
    wire payload is O(KB) while the exact dump is O(MB)."""
    from benchmarks import allocation_micro

    out = tmp_path / "BENCH_overhead.json"
    out.write_text(json.dumps({"results": {"10000": {"us_per_access": 1}}}))
    rows = allocation_micro.run_sketch_micro(smoke=True, json_path=out)
    assert rows, "sketch_path smoke produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["results"]["10000"]["us_per_access"] == 1  # preserved
    axis = payload["sketch_path"]
    assert axis["smoke"] is True
    for name in ("exact", "sketch"):
        assert axis[name]["us_per_access"] > 0
        assert axis[name]["wire_bytes"] > 0
    assert axis["sketch"]["wire_bytes"] <= 24 * 1024
    assert axis["exact"]["wire_bytes"] > 100 * 1024
    assert axis["wire_reduction"] > 10


def test_store_micro_smoke(tmp_path):
    """--smoke store_path axis: ranged vs whole-block over-fetch (sim +
    real-file store), batched vs serial demand fetches, and the
    synthesis-under-transfer guard, merged into the shared overhead JSON
    without clobbering other sections."""
    from benchmarks import store_micro

    out = tmp_path / "BENCH_overhead.json"
    out.write_text(json.dumps({"results": {"10000": {"us_per_access": 1}}}))
    rows = store_micro.main(smoke=True, json_path=out)
    assert rows, "store_path smoke produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["results"]["10000"]["us_per_access"] == 1  # preserved
    axis = payload["store_path"]
    assert axis["smoke"] is True
    for name in ("ranged_sim", "ranged_fs"):
        assert axis[name]["ranged_us"] > 0
        assert axis[name]["overfetch_us"] > 0
        assert axis[name]["bytes_moved_ratio"] > 1
    bd = axis["batched_demand"]
    assert bd["batched_us_per_req"] > 0 and bd["serial_us_per_req"] > 0
    # the satellite guard: synthesis must stay under the simulated
    # transfer budget (store_micro asserts it; the flag records it)
    assert axis["synthesis"]["synth_under_transfer"] is True
    assert axis["synthesis"]["synth_4mb_ms"] < \
        axis["synthesis"]["transfer_4mb_ms"]


def test_daemon_micro_smoke(tmp_path):
    """--smoke daemon_path axis: N forked ``open_cache("cache://...")``
    client processes against one UDS daemon; aggregate metadata
    throughput per client count merged into the shared overhead JSON
    without clobbering other sections.  Scaling ordering is the full
    run's claim — smoke asserts the pipeline and the accounting."""
    from benchmarks import daemon_micro

    out = tmp_path / "BENCH_overhead.json"
    out.write_text(json.dumps({"results": {"10000": {"us_per_access": 1}}}))
    rows = daemon_micro.main(smoke=True, json_path=out)
    assert rows, "daemon_path smoke produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["results"]["10000"]["us_per_access"] == 1  # preserved
    axis = payload["daemon_path"]
    assert axis["smoke"] is True
    for n in (1, 2, 4):
        point = axis[f"daemon_{n}"]
        assert point["accesses_per_s"] > 0
        assert point["us_per_access"] > 0
        assert point["accesses"] == n * axis["n_accesses_per_client"]
    assert axis["scaling_4_vs_1"] > 0
    # every bench client said goodbye; nothing was lease-reaped or spilled
    assert axis["daemon_stats"]["byes"] == 7
    assert axis["daemon_stats"]["reaped"] == 0
    assert axis["daemon_stats"]["served_reads"] > 0


def test_daemon_recovery_smoke(tmp_path):
    """--smoke daemon_recovery axis: kill a journaled daemon under its
    supervisor and record the recovery arc — degraded-read latency,
    respawn + journal restore, client reconnect, and the warm-vs-cold
    ramp back to a fully-hitting pass — merged into the shared overhead
    JSON without clobbering other sections."""
    from benchmarks import daemon_micro

    out = tmp_path / "BENCH_overhead.json"
    out.write_text(json.dumps({"results": {"10000": {"us_per_access": 1}}}))
    rows = daemon_micro.run_recovery(smoke=True, json_path=out)
    assert rows, "daemon_recovery smoke produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["results"]["10000"]["us_per_access"] == 1  # preserved
    axis = payload["daemon_recovery"]
    assert axis["smoke"] is True
    # the daemon died, the supervisor respawned it, the journal restored
    # the manifest, and the client reconnected — each leg timed
    assert axis["respawn_s"] > 0
    assert axis["reconnect_s"] > 0
    assert axis["restore"]["mode"] == "warm"
    assert axis["restore"]["blocks"] > 0
    # reads flowed (degraded) the whole time the daemon was away
    assert axis["degraded"]["reads"] > 0
    assert axis["degraded"]["us_per_read"] > 0
    assert axis["client"]["degraded_reads"] == axis["degraded"]["reads"]
    assert axis["client"]["reconnects"] >= 1
    # the acceptance contrast: a warm restart reaches a fully-hitting
    # pass at least as fast as the cold ramp did, and both converge
    assert axis["warm_ramp"]["final_pass_chr"] == 1.0
    assert axis["cold_ramp"]["final_pass_chr"] == 1.0
    assert axis["warm_ramp"]["passes"] <= axis["cold_ramp"]["passes"]


def test_tier_micro_smoke(tmp_path):
    """--smoke tier_path axis: flat-RAM vs RAM+disk at equal total
    capacity on the down-scaled paper suite, plus the bytes-mode
    spill/promote throughput micro, merged into the shared overhead JSON
    without clobbering other sections.  The tiered-wins ordering is the
    full run's claim — smoke asserts the pipeline and the accounting."""
    from benchmarks import tier_micro

    out = tmp_path / "BENCH_overhead.json"
    out.write_text(json.dumps({"results": {"10000": {"us_per_access": 1}}}))
    rows = tier_micro.main(smoke=True, json_path=out)
    assert rows, "tier_path smoke produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["results"]["10000"]["us_per_access"] == 1  # preserved
    axis = payload["tier_path"]
    assert axis["smoke"] is True
    assert axis["flat"]["kernel_chr"] > 0
    assert axis["flat"]["capacity_mb"] == axis["tiered"]["capacity_mb"]
    assert axis["tiered"]["combined_chr"] >= axis["tiered"]["kernel_chr"]
    assert axis["tiered"]["tier"]["disk_hits"] > 0
    assert axis["flat"]["link_mb"] > 0 and axis["tiered"]["link_mb"] > 0
    micro = axis["spill_micro"]
    assert micro["spilled"] == micro["blocks"] - 1   # one block stays in RAM
    assert micro["disk_hits"] > 0
    assert micro["spill_MBps"] > 0 and micro["promote_MBps"] > 0


def test_prefetch_micro_client_axis_smoke(tmp_path):
    """--smoke client-path axis: kernel loop vs SimExecutor client vs
    ThreadedExecutor client, merged into the shared overhead JSON without
    clobbering existing sections."""
    from benchmarks import prefetch_micro

    out = tmp_path / "BENCH_overhead.json"
    out.write_text(json.dumps({"results": {"10000": {"us_per_access": 1}}}))
    rows = prefetch_micro.main(smoke=True, json_path=out)
    assert rows, "client-axis smoke produced no CSV rows"
    payload = json.loads(out.read_text())
    assert payload["results"]["10000"]["us_per_access"] == 1  # preserved
    axis = payload["client_path"]
    assert axis["smoke"] is True
    for proto in ("kernel_loop", "client_sim", "client_threaded"):
        assert axis[proto]["us_per_access"] > 0
    assert "client_overhead_pct" in axis
