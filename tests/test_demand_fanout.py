"""The ThreadedExecutor's demand fan-out: one demand batch is fetched in
up to the backing store's declared ``concurrency`` slices at once, the
worker taking one and the fetch helpers the rest.  Concurrency is shown
with a ``threading.Barrier`` inside the store's ``fetch_many``, never
with wall-clock timing."""
import sys
import threading

import numpy as np
import pytest

from repro.core import CacheClient, CacheConfig, IGTCache, ThreadedExecutor
from repro.core.types import block_key
from repro.storage import MemStore, RetryPolicy
from repro.storage.api import (StoreCapabilities, StoreError,
                               TransientStoreError)

BS = 64 * 1024
CFG = CacheConfig(min_share=BS, rebalance_quantum=BS, block_size=BS,
                  window=40, reanalyze_every=20)
N_FILES = 24


def _mem_store():
    store = MemStore(block_size=BS)
    rng = np.random.default_rng(0)
    for i in range(N_FILES):
        store.add_file(("ds", f"{i:02d}.bin"),
                       rng.integers(0, 256, 4 * BS, dtype=np.uint8).tobytes())
    return store


class Recording:
    """v2 backing store over a ``MemStore`` that declares ``concurrency``
    (``None``: no ``capabilities`` method at all) and records every
    ``fetch_many`` call as (thread name, local flag, requests).  ``hook``
    runs inside each call before the bytes are read."""

    def __init__(self, inner, concurrency=None, hook=None):
        self.inner = inner
        self.concurrency = concurrency
        self.hook = hook
        self.calls = []
        self._lock = threading.Lock()
        self._tls = threading.local()

    def local_reads(self):
        store = self

        class Local:
            def __enter__(self):
                store._tls.local = True

            def __exit__(self, *exc):
                store._tls.local = False

        return Local()

    def fetch_range(self, path, offset, length):
        return self.inner.fetch_range(path, offset, length)

    def fetch_many(self, requests):
        with self._lock:
            self.calls.append((threading.current_thread().name,
                               getattr(self._tls, "local", False),
                               list(requests)))
        if self.hook is not None:
            self.hook(list(requests))
        return [self.inner.fetch_range(*r) for r in requests]

    def remote_calls(self):
        return [c for c in self.calls if not c[1]]


class Declared(Recording):
    def capabilities(self):
        return StoreCapabilities(ranges=True, batching=False,
                                 concurrency=self.concurrency)


def _client(backing, store, **kw):
    return CacheClient(IGTCache(store, 64 * BS * N_FILES, cfg=CFG),
                       backing=backing, executor=ThreadedExecutor(),
                       fetch_bytes=True, **kw)


def _reqs(files):
    return [(("ds", f"{i:02d}.bin"), 0, 4096) for i in files]


def _want(store, reqs):
    return [np.asarray(store.fetch_range(fp, off, n)) for fp, off, n in reqs]


def _check(results, store, reqs):
    for res, want in zip(results, _want(store, reqs)):
        assert np.array_equal(res.data, want)


def test_concurrency_4_serves_an_8_range_batch_with_4_calls_in_flight():
    store = _mem_store()
    barrier = threading.Barrier(4, timeout=10.0)
    backing = Declared(store, concurrency=4,
                       hook=lambda reqs: barrier.wait())
    client = _client(backing, store)
    try:
        reqs = _reqs(range(8))
        results = client.read_batch(reqs)
    finally:
        client.close()
    # the four slices met at the barrier: all four were in flight at once
    _check(results, store, reqs)
    calls = backing.remote_calls()
    assert sorted(len(c[2]) for c in calls) == [2, 2, 2, 2]
    # contiguous slices in request order, the first on the worker
    ranges = [(block_key(fp, 0), off, n) for fp, off, n in reqs]
    by_slice = sorted(calls, key=lambda c: ranges.index(c[2][0]))
    assert [r for c in by_slice for r in c[2]] == ranges
    assert by_slice[0][0] == "igt-prefetch-0"
    assert {c[0] for c in by_slice[1:]} <= {f"igt-fetch-{i}"
                                            for i in range(3)}
    assert len({c[0] for c in by_slice[1:]}) == 3
    snap = client.snapshot()["executor"]
    assert snap["demand_batches"] == 1 and snap["demand_slices"] == 4
    assert snap["demand_fetches"] == 8


@pytest.mark.parametrize("cls", [Declared, Recording],
                         ids=["concurrency-1", "no-capabilities"])
def test_concurrency_1_or_none_makes_one_call_on_the_worker(cls):
    store = _mem_store()
    backing = cls(store, concurrency=1)
    client = _client(backing, store)
    try:
        reqs = _reqs(range(8))
        results = client.read_batch(reqs)
        assert client.executor.fan_out == 1
        assert client.executor._helpers == []
    finally:
        client.close()
    _check(results, store, reqs)
    (call,) = backing.remote_calls()
    assert call[0] == "igt-prefetch-0" and len(call[2]) == 8
    st = client.executor.stats
    assert st.demand_batches == 1 and st.demand_slices == 1


def test_demand_counters_count_batches_and_slices():
    store = _mem_store()
    backing = Declared(store, concurrency=3)
    client = _client(backing, store)
    try:
        client.read_batch(_reqs(range(8)))          # 3 slices
        client.read_batch(_reqs(range(8, 10)))      # 2 slices
        client.read(("ds", "10.bin"), 0, 4096)      # 1 range, 1 slice
        st = client.executor.stats
        assert (st.demand_batches, st.demand_slices) == (3, 6)
        assert len(backing.remote_calls()) == 6
        assert client.snapshot()["executor"]["demand_slices"] == 6
    finally:
        client.close()


def test_a_permanent_error_in_one_slice_reaches_the_reader():
    store = _mem_store()
    bad = block_key(("ds", "05.bin"), 0)

    def fail_on_bad(reqs):
        if any(r[0] == bad for r in reqs):
            raise StoreError("object gone")

    backing = Declared(store, concurrency=4, hook=fail_on_bad)
    client = _client(backing, store)
    try:
        with pytest.raises(StoreError, match="object gone"):
            client.read_batch(_reqs(range(8)))
        # every slice ran; only the one holding the bad range failed
        assert len(backing.remote_calls()) == 4
        assert client.executor.stats.fetch_errors == 1
        assert all(w.is_alive() for w in client.executor._workers)
        reqs = _reqs(range(8, 16))                  # the next batch
        _check(client.read_batch(reqs), store, reqs)
    finally:
        client.close()


def test_a_transient_error_retries_only_its_own_slice():
    store = _mem_store()
    flaky = block_key(("ds", "06.bin"), 0)
    failed = []

    def fail_once(reqs):
        if any(r[0] == flaky for r in reqs) and not failed:
            failed.append(reqs)
            raise TransientStoreError("throttled")

    backing = Declared(store, concurrency=4, hook=fail_once)
    client = _client(backing, store,
                     retry=RetryPolicy(max_attempts=3, sleep=lambda s: None))
    try:
        reqs = _reqs(range(8))
        _check(client.read_batch(reqs), store, reqs)
    finally:
        client.close()
    calls = [c[2] for c in backing.remote_calls()]
    assert len(calls) == 5
    assert calls.count(failed[0]) == 2               # the retried slice
    assert all(calls.count(c) == 1 for c in calls if c != failed[0])
    st = client.executor.stats
    assert st.retries == 1 and st.fetch_errors == 0
    assert st.demand_slices == 4


def test_close_finishes_an_inflight_batch_and_joins_the_helpers():
    store = _mem_store()
    gate = threading.Event()
    entered = threading.Semaphore(0)

    def hold(reqs):
        entered.release()
        gate.wait(10.0)

    backing = Declared(store, concurrency=4, hook=hold)
    client = _client(backing, store)
    reqs = _reqs(range(8))
    got = []
    reader = threading.Thread(
        target=lambda: got.append(client.read_batch(reqs)))
    reader.start()
    for _ in range(4):
        assert entered.acquire(timeout=10.0), "a slice never started"
    closer = threading.Thread(target=client.close)
    closer.start()
    gate.set()
    closer.join(10.0)
    reader.join(10.0)
    assert not closer.is_alive() and not reader.is_alive()
    _check(got[0], store, reqs)
    ex = client.executor
    assert len(ex._helpers) == 3
    assert not any(h.is_alive() for h in ex._helpers + ex._workers)


def test_a_batch_after_close_fails_instead_of_waiting_on_the_helpers():
    store = _mem_store()
    backing = Declared(store, concurrency=4)
    client = _client(backing, store)
    client.close()
    ex = client.executor
    ranges = [(block_key(fp, 0), off, n) for fp, off, n in _reqs(range(8))]
    with pytest.raises(RuntimeError, match="closed"):
        ex._fetch_batch(ranges)


def test_hits_are_fetched_on_the_readers_thread_marked_local():
    """The benchmark's link store tells a hit from a miss by a
    thread-local flag set around ``_fetch_hits``: the fan-out must keep
    every hit range on the reader's own thread."""
    store = _mem_store()
    backing = Declared(store, concurrency=8)
    client = _client(backing, store)
    fetch_hits = client._fetch_hits

    def local_hits(plans, fetched):
        with backing.local_reads():
            fetch_hits(plans, fetched)

    client._fetch_hits = local_hits
    try:
        client.read_batch(_reqs(range(0, 8)))
        n0 = len(backing.calls)
        reqs = _reqs(range(4, 12))                  # 4 hits, 4 misses
        results = client.read_batch(reqs)
    finally:
        client.close()
    _check(results, store, reqs)
    ranges = [(block_key(fp, 0), off, n) for fp, off, n in reqs]
    hits = {r for r, res in zip(ranges, results) if res.blocks[0].hit}
    assert hits and len(hits) < len(ranges)
    caller = threading.current_thread().name
    second = backing.calls[n0:]
    on_caller = {r for name, _, rs in second if name == caller for r in rs}
    local = {r for _, is_local, rs in second if is_local for r in rs}
    assert on_caller == local == hits
    assert {r for name, _, rs in second if name != caller
            for r in rs} == set(ranges) - hits


def test_shards_share_the_helpers_under_contention():
    """Four shard workers and four readers over one pool of helpers,
    with a short switch interval: every byte right, and one store call
    counted per slice (a lost update would break the count)."""
    from repro.core import ShardedIGTCache
    store = _mem_store()
    backing = Declared(store, concurrency=3)
    engine = ShardedIGTCache(store, 64 * BS * N_FILES, cfg=CFG, n_shards=4)
    client = CacheClient(engine, backing=backing,
                         executor=ThreadedExecutor(), fetch_bytes=True)
    errors = []

    def reader(seed):
        rng = np.random.default_rng(seed)
        try:
            for _ in range(30):
                files = rng.choice(N_FILES, 6, replace=False)
                blocks = rng.integers(0, 4, 6)
                reqs = [(("ds", f"{i:02d}.bin"), int(b) * BS + 100, 900)
                        for i, b in zip(files, blocks)]
                _check(client.read_batch(reqs), store, reqs)
        except Exception as e:  # pragma: no cover - failure path
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=reader, args=(s,))
                   for s in range(4)]
        for th in threads:
            th.start()
        for th in threads:
            th.join(60.0)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
        client.close()
    assert not errors, errors[0]
    st = client.executor.stats
    assert st.demand_batches > 0
    assert st.demand_slices == len([c for c in backing.calls
                                    if c[0].startswith("igt-")])
    assert st.demand_slices > st.demand_batches
