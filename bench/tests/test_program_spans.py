"""The program's spans in a traced window (``bench/program_spans.py``):
gap naming by the window thread's spans, per-thread times, the shares,
and the benchmark's own reduction where the program emits no span."""
from pathlib import Path

import pytest

from bench import program_spans, xplane

DATA = Path(__file__).resolve().parents[1] / "testdata"
MS = 1_000_000


def _threaded_events():
    """A window thread ``w`` whose batch read waits on a worker ``x``,
    and a scan thread ``s`` reading through the same client."""
    spans = [("bench.window", 0, 100 * MS, "w"),
             ("bench.read", 0, 60 * MS, "w"),
             ("igt.pipeline.batch", 1 * MS, 59 * MS, "w"),
             ("igt.client.read_batch", 2 * MS, 58 * MS, "w"),
             ("igt.kernel.lock_wait", 2 * MS, 4 * MS, "w"),
             ("igt.kernel.read", 4 * MS, 6 * MS, "w"),
             ("igt.client.demand_queued", 6 * MS, 30 * MS, "w"),
             ("igt.client.demand_fetch", 30 * MS, 55 * MS, "w"),
             ("igt.client.hits", 55 * MS, 57 * MS, "w"),
             ("bench.step", 60 * MS, 100 * MS, "w"),
             ("igt.executor.prefetch", -10 * MS, 30 * MS, "x"),
             ("igt.executor.demand", 30 * MS, 55 * MS, "x"),
             ("igt.store.fetch_many", 30 * MS, 55 * MS, "x"),
             ("igt.client.read_batch", 3 * MS, 100 * MS, "s")]
    return spans, [[("fusion.1", 65 * MS, 100 * MS)]]


def test_window_thread_program_spans_name_the_gaps_and_others_none():
    got = program_spans.reduce_events(*_threaded_events())
    assert got["gaps"] == {
        "bench.read": pytest.approx(0.002),
        "igt.pipeline.batch": pytest.approx(0.002),
        "igt.kernel.lock_wait": pytest.approx(0.002),
        "igt.kernel.read": pytest.approx(0.002),
        "igt.client.demand_queued": pytest.approx(0.024),
        "igt.client.demand_fetch": pytest.approx(0.025),
        "igt.client.hits": pytest.approx(0.002),
        "igt.client.read_batch": pytest.approx(0.001),
        "bench.step": pytest.approx(0.005)}
    assert got["busy_s"] + sum(got["gaps"].values()) == pytest.approx(0.1)


def test_program_holds_each_threads_span_times_clipped_to_the_window():
    prog = program_spans.reduce_events(*_threaded_events())["program"]
    assert set(prog) == {"window", "x", "s"}
    assert prog["window"]["igt.client.demand_queued"] == [
        pytest.approx(0.024), 1]
    assert "bench.read" not in prog["window"]
    # the worker's background fetch began before the window opened
    assert prog["x"] == {"igt.executor.prefetch": [pytest.approx(0.03), 1],
                         "igt.executor.demand": [pytest.approx(0.025), 1],
                         "igt.store.fetch_many": [pytest.approx(0.025), 1]}
    assert prog["s"] == {"igt.client.read_batch": [pytest.approx(0.097), 1]}


def test_without_program_spans_the_reduction_is_the_benchmarks_own():
    spans, devices = _threaded_events()
    bench_only = [s for s in spans if s[0].startswith("bench.")]
    got = program_spans.reduce_events(bench_only, devices)
    assert got.pop("program") == {}
    assert got == xplane.reduce_events([s[:3] for s in bench_only], devices)
    assert got["gaps"] == {"bench.read": pytest.approx(0.06),
                           "bench.step": pytest.approx(0.005)}
    path = str(DATA / "mamba2_2layer.xplane.pb.xz")
    recorded = program_spans.reduce_trace(path)
    assert recorded.pop("program") == {}
    assert recorded == xplane.reduce_trace(path)


SHARES = {"kernel_share.train": 4.0, "demand_queue_share.train": 24.0,
          "demand_fetch_share.train": 25.0,
          "executor_busy_share.train": 55.0}


@pytest.mark.parametrize("name", sorted(SHARES))
def test_shares_of_the_window(name):
    assert set(SHARES) == set(program_spans.SHARES)
    names, busiest = program_spans.SHARES[name]
    spans, devices = _threaded_events()

    def share(kept):
        return program_spans.share(
            program_spans.reduce_events(kept, devices), names, busiest)

    assert share(spans) == pytest.approx(SHARES[name])
    # program spans present, but not the ones this share reads
    assert share([s for s in spans if not s[0].startswith("igt.")
                  or s[0] == "igt.pipeline.batch"]) == 0
    # no program span at all, as on a program that emits none
    assert share([s for s in spans if s[0].startswith("bench.")]) is None
    assert program_spans.share(None, names, busiest) is None


def test_a_traced_tiny_cell_reports_the_program_split(tiny_root,
                                                      monkeypatch):
    from bench import run
    load = xplane.load_events
    # the CPU trace has no TPU plane: an idle device 0 keeps the reduction
    monkeypatch.setattr(xplane, "load_events",
                        lambda path: (load(path)[0], [[]]))
    _, _, cell, cj = run.load_cell("tiny.train.oversub-scan", tiny_root)
    out = program_spans.measure(cell, cj, 2 ** 33 + 5, 1.0)
    assert out["correct"]
    assert all(v is not None for v in out["shares"].values())
    assert out["shares"]["executor_busy_share.train"] > 0
    gaps = dict(out["idle_gaps"])
    assert "igt.pipeline.batch" in out["program"]["window"]
    assert any(n.startswith("igt.") for n in gaps)
    assert not any(n.startswith("igt.executor.") for n in gaps)
