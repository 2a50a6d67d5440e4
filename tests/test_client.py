"""CacheClient / PrefetchExecutor semantics (the PR-3 caller layer).

Covers the executor contract the ISSUE pins: cancellation on queue
overflow and on shutdown (never silently dropping a candidate the kernel
is tracking), in-queue candidate dedup, demand-miss priority, racing
``complete_prefetch`` against demand misses under the ThreadedExecutor,
per-shard worker routing, the client byte path against the backing
store, and the pipeline's executor-visible prefetch accounting.
"""
import threading
import time

import numpy as np
import pytest

from repro.core import (CacheClient, CacheConfig, IGTCache, NullExecutor,
                        ShardedIGTCache, SimExecutor, ThreadedExecutor,
                        path_key, open_cache)
from repro.core.types import MB
from repro.data.pipeline import CachedTokenPipeline, make_token_dataset
from repro.storage import RemoteStore, make_dataset

CFG = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                  window=40, reanalyze_every=20)


def mk_store():
    store = RemoteStore()
    store.add(make_dataset("flat", "flat_files", n_files=120,
                           small_file_size=256 * 1024))
    store.add(make_dataset("big", "big_files", n_files=6, file_size=24 * MB))
    return store


class GatedStore:
    """BackingStore wrapper whose fetches block until released — makes
    worker progress controllable so queue overflow/shutdown/dedup tests
    are deterministic."""

    def __init__(self, store):
        self.store = store
        self.gate = threading.Event()
        self.fetches = 0

    def fetch_block(self, path, size):
        self.gate.wait(timeout=10.0)
        self.fetches += 1
        return self.store.fetch_block(path, size)

    # StoreMeta passthrough so the engine can also be built on it if needed
    def __getattr__(self, name):
        return getattr(self.store, name)


def seq_candidates(store, engine, n=64):
    """Kernel-issued prefetch candidates: drive a sequential whole-file
    scan until the engine classifies the stream (window=40) and emits
    readahead, and return the issued candidates (kernel pending-table
    entries included)."""
    cands = []
    t = 0.0
    for f in store.datasets["flat"].files:
        out = engine.read(f.path, 0, f.size, t)
        cands.extend(out.prefetches)
        t += 0.01
        if len(cands) >= n:
            break
    return cands


def executor_identity(stats):
    return stats.completed + stats.cancelled + stats.deduped


# ---------------------------------------------------------------------------
# cancellation: overflow + shutdown
# ---------------------------------------------------------------------------

def test_overflow_cancels_on_kernel_not_drops():
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    gated = GatedStore(store)
    ex = ThreadedExecutor(queue_depth=2, max_fetch_bytes=4096)
    client = CacheClient(engine, backing=gated, executor=ex)
    cands = seq_candidates(store, engine, n=24)
    assert len(cands) >= 8, "workload failed to generate candidates"
    issued = {path_key(p) for p, _ in cands}
    assert issued <= engine._pending_prefetch

    ex.submit(cands, 1.0)      # worker blocked: 1 in flight + 2 queued max
    assert ex.stats.cancelled >= len(cands) - 3
    # cancelled candidates must be released from the kernel pending table
    # (a silently dropped candidate would block that block's re-issue)
    gated.gate.set()
    assert client.flush(timeout=10.0)
    client.close()
    assert executor_identity(ex.stats) == ex.stats.submitted
    leaked = engine._pending_prefetch & issued
    assert not leaked, f"pending-table leak: {sorted(leaked)[:3]}"


def test_shutdown_cancels_queued_candidates():
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    gated = GatedStore(store)
    ex = ThreadedExecutor(queue_depth=4096, max_fetch_bytes=4096)
    client = CacheClient(engine, backing=gated, executor=ex)
    cands = seq_candidates(store, engine, n=24)
    assert len(cands) >= 8
    ex.submit(cands, 1.0)
    assert ex.stats.cancelled == 0          # deep queue: nothing overflowed
    gated.gate.set()                        # let the in-flight one finish
    client.close(cancel_pending=True)       # everything still queued: cancel
    assert ex.stats.cancelled > 0
    assert executor_identity(ex.stats) == ex.stats.submitted
    issued = {path_key(p) for p, _ in cands}
    assert not (engine._pending_prefetch & issued)


def test_dedup_drops_requeued_candidate():
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    gated = GatedStore(store)
    ex = ThreadedExecutor(queue_depth=4096, max_fetch_bytes=4096)
    client = CacheClient(engine, backing=gated, executor=ex)
    cands = seq_candidates(store, engine, n=8)[:4]
    ex.submit(cands, 1.0)
    ex.submit(cands, 1.1)       # same blocks, still queued → dedup
    assert ex.stats.deduped >= len(cands) - 1   # first may be in flight
    gated.gate.set()
    assert client.flush(timeout=10.0)
    client.close()
    assert executor_identity(ex.stats) == ex.stats.submitted


def test_null_executor_cancels_everything():
    store = mk_store()
    client = open_cache(store, 128 * MB, cfg=CFG, executor="none")
    engine = client.engine
    t = 0.0
    for f in store.datasets["flat"].files:
        client.read(f.path, 0, f.size, t)
        t += 0.01
    st = client.executor.stats
    assert st.submitted > 0
    assert st.cancelled == st.submitted
    assert not engine._pending_prefetch


def test_open_cache_rejects_unknown_executor():
    store = mk_store()
    with pytest.raises(ValueError):
        open_cache(store, 64 * MB, cfg=CFG, executor="warp-drive")


def test_open_cache_takes_no_driver_knobs(monkeypatch):
    """The executor strings are the three in-process ones, checked
    before any engine is built, and driver knobs are no parameters."""
    import repro.core.client as client_mod
    built = []
    real = client_mod.make_engine
    monkeypatch.setattr(client_mod, "make_engine",
                        lambda *a, **kw: built.append(1) or real(*a, **kw))
    store = mk_store()
    with pytest.raises(ValueError, match="unknown executor"):
        open_cache(store, 64 * MB, cfg=CFG, executor="process")
    assert built == []
    with pytest.raises(TypeError):
        open_cache(store, 64 * MB, cfg=CFG, driver="thread")
    assert built == []
    for name in ("sim", "threaded", "none"):
        open_cache(store, 64 * MB, cfg=CFG, executor=name).close()
    assert len(built) == 3


def test_submit_after_close_raises_and_releases():
    """Close-vs-submit race (ISSUE 5 satellite): a submit that loses the
    race against close() must raise cleanly instead of enqueueing into a
    dead queue — but only after releasing every candidate on the kernel
    (the pending table must not leak just because the caller was late)."""
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    ex = ThreadedExecutor(queue_depth=64)
    client = CacheClient(engine, backing=store, executor=ex)
    cands = seq_candidates(store, engine, n=8)
    client.close()
    before = ex.stats.cancelled
    with pytest.raises(RuntimeError):
        ex.submit(cands, 1.0)   # late offer: executor is closed
    assert ex.stats.cancelled >= before + len(cands)
    assert executor_identity(ex.stats) == ex.stats.submitted
    issued = {path_key(p) for p, _ in cands}
    assert not (engine._pending_prefetch & issued)


class FailingStore:
    """BackingStore that errors until told otherwise (real object-store
    adapters fail; the shard worker must survive and the blocked reader
    must see the error)."""

    def __init__(self, store):
        self.store = store
        self.fail = True

    def fetch_block(self, path, size):
        if self.fail:
            raise IOError("backend down")
        return self.store.fetch_block(path, size)


def test_client_shard_queue_wait_idle_reports_closed_promptly():
    """A closed per-shard queue with outstanding work can only drain
    through close()'s cancellation sweep — wait_idle must report False
    at once, not burn the caller's timeout."""
    from repro.core.client import _ShardQueue
    q = _ShardQueue(depth=8)
    q.outstanding = 2
    q.closed = True
    t0 = time.monotonic()
    assert q.wait_idle(30.0) is False
    assert time.monotonic() - t0 < 1.0


def test_demand_fetch_after_close_raises_instead_of_hanging():
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    ex = ThreadedExecutor()
    client = CacheClient(engine, backing=store, executor=ex,
                         fetch_bytes=True)
    client.close()
    f = store.datasets["big"].files[0]
    with pytest.raises(RuntimeError):
        client.read(f.path, 0, 1 * MB, 1.0)


def test_demand_fetch_error_propagates_without_killing_worker():
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    failing = FailingStore(store)
    ex = ThreadedExecutor()
    client = CacheClient(engine, backing=failing, executor=ex,
                         fetch_bytes=True)
    f = store.datasets["big"].files[0]
    with pytest.raises(IOError):
        client.read(f.path, 0, 1 * MB, 1.0)
    assert all(w.is_alive() for w in ex._workers)
    failing.fail = False                     # store recovers
    res = client.read(f.path, 0, 1 * MB, 2.0)
    assert len(res.data) == 1 * MB
    client.close()


# ---------------------------------------------------------------------------
# demand priority + racing complete_prefetch vs demand miss
# ---------------------------------------------------------------------------

def test_demand_fetch_preempts_queued_prefetches():
    store = mk_store()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    gated = GatedStore(store)
    ex = ThreadedExecutor(queue_depth=4096, max_fetch_bytes=4096)
    client = CacheClient(engine, backing=gated, executor=ex,
                         fetch_bytes=True)
    cands = seq_candidates(store, engine, n=16)
    ex.submit(cands, 1.0)       # queue full of background work, worker gated
    gated.gate.set()
    f = store.datasets["big"].files[0]          # untouched → demand miss
    res = client.read(f.path, 0, 1 * MB, 2.0)   # needs bytes NOW
    assert res.data is not None and len(res.data) == 1 * MB
    assert ex.stats.demand_fetches >= 1
    client.close()


def test_racing_complete_prefetch_vs_demand_miss():
    """Demand reads hammer the same blocks the background workers are
    completing; the per-shard guard serializes kernel access, so counters
    and residency must stay consistent (no lost updates, no over-capacity
    admission)."""
    store = mk_store()
    client = open_cache(store, 96 * MB, cfg=CFG, executor="threaded",
                        queue_depth=4096, max_fetch_bytes=256)
    engine = client.engine
    files = store.datasets["big"].files
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        try:
            for i in range(300):
                f = files[int(rng.integers(0, len(files)))]
                b = int(rng.integers(0, f.size // CFG.block_size))
                client.read(f.path, b * CFG.block_size, 64 * 1024)
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    threads = [threading.Thread(target=reader, args=(s,)) for s in range(3)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert client.flush(timeout=15.0)
    client.close()
    assert not errors
    st = engine.stats
    assert st.hits + st.misses == st.accesses == 900
    ex = client.executor.stats
    assert executor_identity(ex) == ex.submitted
    assert engine.cache.used_bytes() <= engine.cache.capacity


# ---------------------------------------------------------------------------
# per-shard workers
# ---------------------------------------------------------------------------

def test_threaded_executor_runs_one_worker_per_shard():
    store = RemoteStore()
    for i in range(4):
        store.add(make_dataset(f"ds{i}", "flat_files", n_files=80,
                               small_file_size=256 * 1024))
    client = open_cache(store, 128 * MB, cfg=CFG, n_shards=4,
                        executor="threaded")
    assert isinstance(client.engine, ShardedIGTCache)
    ex = client.executor
    assert len(ex._workers) == 4 and len(ex._queues) == 4
    t = 0.0
    for ds in store.datasets.values():
        for f in ds.files:
            client.read(f.path, 0, f.size, t)
            t += 0.01
    assert client.flush(timeout=15.0)
    client.close()
    st = ex.stats
    assert st.submitted > 0
    assert executor_identity(st) == st.submitted
    for shard in client.engine.shards:
        assert not shard._pending_prefetch


# ---------------------------------------------------------------------------
# byte path
# ---------------------------------------------------------------------------

def test_client_bytes_match_backing_store():
    store = mk_store()
    client = open_cache(store, 128 * MB, cfg=CFG, executor="sim",
                        fetch_bytes=True)
    f = store.datasets["big"].files[0]
    bs = CFG.block_size
    res = client.read(f.path, 3 * MB, 6 * MB, 1.0)   # spans blocks 0..2
    ref = np.concatenate([store.fetch_block(f.path + (f"#{b}",), bs)
                          for b in range(3)])
    assert np.array_equal(res.data, ref[3 * MB: 9 * MB])
    # second read: cache hits, identical bytes
    res2 = client.read(f.path, 3 * MB, 6 * MB, 2.0)
    assert all(b.hit for b in res2.blocks)
    assert np.array_equal(res2.data, res.data)
    # oversized request clamps to the file
    small = store.datasets["flat"].files[0]
    res3 = client.read(small.path, 100, small.size * 10, 3.0)
    assert len(res3.data) == small.size - 100


def test_sim_executor_moves_no_bytes_by_default():
    store = mk_store()
    counting = GatedStore(store)
    counting.gate.set()
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    client = CacheClient(engine, backing=counting, executor=SimExecutor())
    for f in store.datasets["flat"].files:
        client.read(f.path, 0, f.size)
    assert client.executor.stats.completed > 0
    assert counting.fetches == 0            # virtual-clock: sizes only


# ---------------------------------------------------------------------------
# pipeline accounting (satellite: cancels visible in PipelineStats)
# ---------------------------------------------------------------------------

def _token_world():
    store = RemoteStore()
    store.add(make_token_dataset("corpus", n_shards=4, shard_bytes=2 * MB))
    ccfg = CacheConfig(min_share=2 * MB, rebalance_quantum=2 * MB,
                       rebalance_period=5.0, block_size=1 * MB,
                       window=40, reanalyze_every=20)
    return store, ccfg


class SlowPrefetchStore:
    """BackingStore wrapper that stalls the small, capped fetches of
    prefetch candidates (``max_fetch_bytes``) and serves the pipeline's
    full-sample demand reads at once: the shard worker is busy while a
    batch submits its candidates, so a depth-1 queue must overflow."""

    def __init__(self, store, small: int, delay_s: float):
        self.store = store
        self.small = small
        self.delay_s = delay_s

    def fetch_block(self, path, size):
        if size <= self.small:
            time.sleep(self.delay_s)
        return self.store.fetch_block(path, size)

    def __getattr__(self, name):
        return getattr(self.store, name)


def test_pipeline_stats_expose_cancelled_vs_completed():
    # one sample per small file → a sequential epoch is a file scan that
    # keeps issuing file-level readahead candidates
    store = RemoteStore()
    store.add(make_dataset("corpus", "flat_files", n_files=200,
                           small_file_size=64 * 1024))
    ccfg = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                       window=40, reanalyze_every=20)
    engine = IGTCache(store, 64 * MB, cfg=ccfg)
    slow = SlowPrefetchStore(store, small=512, delay_s=0.02)
    ex = ThreadedExecutor(queue_depth=1, max_fetch_bytes=512)
    client = CacheClient(engine, backing=slow, executor=ex)
    pipe = CachedTokenPipeline(store, client, "corpus", seq_len=32, batch=4,
                               vocab=1000, sample_bytes=64 * 1024,
                               access_pattern="sequential")
    for _ in pipe.batches(epochs=1):
        pass
    pipe.flush(timeout=10.0)
    client.close()
    pipe.close()
    s = client.executor.stats
    assert s.submitted > 0, "sequential scan issued no candidates"
    assert s.cancelled > 0, \
        "depth-1 queue behind a stalled worker must overflow-cancel"
    assert s.completed + s.cancelled <= s.submitted
    assert not engine._pending_prefetch    # nothing silently dropped


def test_pipeline_threaded_hit_ratio_matches_inline_within_2pct():
    """Acceptance: CachedTokenPipeline under the ThreadedExecutor matches
    the deterministic inline-completion path within 2% CHR on the seeded
    token workload (the old PrefetchWorker semantics, minus the lost
    candidates)."""

    def run(background):
        store, ccfg = _token_world()
        engine = IGTCache(store, 64 * MB, cfg=ccfg)   # corpus (8MB) fits
        pipe = CachedTokenPipeline(store, engine, "corpus", seq_len=32,
                                   batch=4, vocab=1000, seed=0,
                                   sample_bytes=4096,
                                   background_prefetch=background)
        for _ in pipe.batches(epochs=2):
            pipe.flush(timeout=10.0)   # epoch-deterministic completion
        hr = pipe.stats.hit_ratio
        pipe.close()
        return hr

    inline, threaded = run(False), run(True)
    assert inline > 0.4                     # epoch 2 ~fully cached
    assert abs(threaded - inline) <= 0.02, (threaded, inline)
