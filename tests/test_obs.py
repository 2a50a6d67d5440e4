"""Spans of the input path (``repro.core.obs``): where each one opens and
on which thread, under a recording stand-in for the annotation, and in a
real ``jax.profiler`` trace on the CPU backend."""
import os
import subprocess
import sys
import threading
import time
from pathlib import Path

import pytest

from repro.core import CacheClient, CacheConfig, IGTCache, ThreadedExecutor
from repro.core.types import MB, block_key
from repro.storage import RemoteStore, make_dataset

REPO = Path(__file__).resolve().parents[1]
CFG = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                  window=40, reanalyze_every=20)


class Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: every span's name,
    thread and host-clock interval."""

    def __init__(self):
        self.spans = []
        self._lock = threading.Lock()

    def __call__(self, name):
        rec = self

        class Span:
            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *exc):
                with rec._lock:
                    rec.spans.append((name, threading.current_thread().name,
                                      self.t0, time.perf_counter()))

        return Span()

    def named(self, name):
        return [s for s in self.spans if s[0] == name]


class SlowStore:
    """The backing store with a slow background path: a capped
    prefetch fetch (``fetch_range``) takes ``prefetch_s`` and signals
    ``busy`` as it starts; a demand ``fetch_many`` takes ``demand_s``."""

    def __init__(self, store, prefetch_s, demand_s):
        self.store = store
        self.prefetch_s = prefetch_s
        self.demand_s = demand_s
        self.busy = threading.Event()

    def fetch_range(self, path, offset, length):
        self.busy.set()
        time.sleep(self.prefetch_s)
        return self.store.fetch_range(path, offset, length)

    def fetch_many(self, requests):
        time.sleep(self.demand_s)
        return [self.store.fetch_range(*r) for r in requests]

    def __getattr__(self, name):
        return getattr(self.store, name)


@pytest.fixture
def recorder(monkeypatch):
    jax = pytest.importorskip("jax")
    rec = Recorder()
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", rec)
    return rec


def _world():
    store = RemoteStore()
    store.add(make_dataset("big", "big_files", n_files=4, file_size=16 * MB))
    return store, sorted(f.path for f in store.datasets["big"].files)


def test_read_batch_behind_a_busy_worker_nests_its_spans(recorder):
    store, files = _world()
    slow = SlowStore(store, prefetch_s=0.3, demand_s=0.05)
    client = CacheClient(IGTCache(store, 64 * MB, cfg=CFG), backing=slow,
                         executor=ThreadedExecutor(), fetch_bytes=True)
    try:
        client.executor.submit([(block_key(files[-1], 3), 4 * MB)],
                               time.monotonic())
        assert slow.busy.wait(5.0), "the worker never took the candidate"
        results = client.read_batch([(f, 0, 4096) for f in files[:3]])
        assert client.flush(timeout=5.0)
    finally:
        client.close()
    assert all(r.data.nbytes == 4096 for r in results)
    caller = threading.current_thread().name
    (top,) = recorder.named("igt.client.read_batch")
    assert top[1] == caller
    for name in ("igt.kernel.lock_wait", "igt.kernel.read",
                 "igt.client.submit", "igt.client.demand_queued",
                 "igt.client.demand_fetch", "igt.client.hits"):
        (sp,) = recorder.named(name)
        assert sp[1] == caller, name
        assert top[2] <= sp[2] <= sp[3] <= top[3], name
    (queued,) = recorder.named("igt.client.demand_queued")
    (fetch,) = recorder.named("igt.client.demand_fetch")
    background = min(recorder.named("igt.executor.prefetch"),
                     key=lambda sp: sp[2])
    (demand,) = recorder.named("igt.executor.demand")
    # the demand batch waited out the worker's background fetch
    assert background[2] < queued[2]
    assert queued[3] - queued[2] >= background[3] - queued[2]
    for sp in (background, demand):
        assert sp[1] == "igt-prefetch-0" != caller
    # then it waited out the worker's fetch of its misses
    assert background[3] <= demand[2]
    assert queued[3] <= fetch[2] and demand[3] <= fetch[3]
    # the worker's store call sits inside its demand span
    (store_call,) = [s for s in recorder.named("igt.store.fetch_many")
                     if s[1] == "igt-prefetch-0"]
    assert demand[2] <= store_call[2] <= store_call[3] <= demand[3]
    # the other two misses are slices on fetch helpers, fetched while the
    # reader waits on its batch: a helper may open its span before the
    # reader's thread gets to close demand_queued, so the start is held
    # to the reader's wait and the end to demand_fetch
    helpers = [s for s in recorder.named("igt.store.fetch_many")
               if s[1].startswith("igt-fetch-")]
    assert len(helpers) == 2
    for sp in helpers:
        assert queued[2] <= sp[2] and fetch[2] <= sp[3] <= fetch[3]


def test_single_read_and_the_pipeline_batch_open_one_span_each(recorder):
    from repro.data.pipeline import CachedTokenPipeline
    store, files = _world()
    client = CacheClient(IGTCache(store, 64 * MB, cfg=CFG), backing=store,
                         executor=ThreadedExecutor(), fetch_bytes=True)
    try:
        client.read(files[0], 0, 4096)
        pipe = CachedTokenPipeline(store, client, "big", seq_len=31,
                                   batch=4, vocab=1000)
        batches = pipe.batches()
        for _ in range(3):
            next(batches)
    finally:
        client.close()
    assert len(recorder.named("igt.client.read")) == 1
    assert len(recorder.named("igt.pipeline.batch")) == 3
    assert len(recorder.named("igt.client.read_batch")) == 3
    # one kernel span per client call, never one per block or sample
    assert len(recorder.named("igt.kernel.read")) == 4
    assert len(recorder.named("igt.kernel.lock_wait")) == 4


def test_importing_the_cache_and_the_pipeline_leaves_jax_unloaded():
    code = ("import sys, repro.core, repro.data.pipeline; "
            "from repro.core.obs import span; "
            "span('igt.x').__enter__(); "
            "sys.exit('jax' in sys.modules)")
    env = dict(os.environ, PYTHONPATH=str(REPO / "src"))
    got = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert got.returncode == 0, got.stderr


def test_a_real_cpu_trace_carries_the_spans_per_thread(tmp_path):
    jax = pytest.importorskip("jax")
    from bench import program_spans, xplane
    store, files = _world()
    client = CacheClient(IGTCache(store, 64 * MB, cfg=CFG), backing=store,
                         executor=ThreadedExecutor(), fetch_bytes=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            with jax.profiler.TraceAnnotation("bench.window"):
                client.read_batch([(f, 0, 4096) for f in files[:2]])
        finally:
            jax.profiler.stop_trace()
    finally:
        client.close()
    spans = program_spans.load_spans(
        xplane.newest_trace(str(tmp_path)))
    line = {s[0]: s[3] for s in spans}
    for name in ("igt.client.read_batch", "igt.kernel.read",
                 "igt.client.demand_queued", "igt.client.demand_fetch",
                 "igt.executor.demand", "igt.store.fetch_many"):
        assert name in line, name
    assert line["igt.client.read_batch"] == line["bench.window"]
    assert line["igt.executor.demand"] != line["bench.window"]
    # with an idle device the window thread's spans name the gaps
    got = program_spans.reduce_events(spans, [[]])
    assert "igt.client.demand_fetch" in got["gaps"]
    assert "igt.executor.demand" not in got["gaps"]
    assert got["program"]["window"]["igt.client.read_batch"][1] == 1
    # the worker is the thread that carries the demand batch; the other
    # miss is a slice on a fetch helper, which opens no span but the
    # store call
    (worker,) = [v for v in got["program"].values()
                 if "igt.executor.demand" in v]
    assert worker["igt.executor.demand"][1] == 1
    assert worker["igt.store.fetch_many"][1] == 1
    helpers = [v for k, v in got["program"].items()
               if k != "window" and v is not worker]
    assert helpers
    for helper in helpers:
        assert set(helper) == {"igt.store.fetch_many"}
