"""Guarantees of the in-process stack that ``open_cache`` builds.

``open_cache(..., n_shards=n)`` puts the plain ``IGTCache`` behind the
client at one shard and the ``ShardedIGTCache`` facade at four, where
the ``ThreadedExecutor`` runs one worker per shard.  Every guarantee
here is one test parametrised over both shard counts and over the
executors for which it holds: background prefetch failures are retried
or cancelled on the kernel without leaking a pending entry, ``close()``
is bounded, idempotent and conserves every candidate, the client
reports the store's capabilities, metadata invalidation reaches the
kernel, ``backing=`` is where bytes come from, and a permanent demand
error reaches the reader.
"""
import time

import numpy as np
import pytest

from repro.core import CacheConfig, SimExecutor, open_cache
from repro.core.types import MB, block_key
from repro.storage import (FaultyStore, LegacyStoreAdapter, RemoteStore,
                           RetryPolicy, StoreCapabilities, StoreError,
                           make_dataset)

CFG = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                  window=40, reanalyze_every=20, node_cap=500)

SHARDS = [1, 4]


def fetching_executor(name):
    """The executors that move background bytes: the ThreadedExecutor's
    shard workers, or the inline SimExecutor with a fetch cap."""
    return "threaded" if name == "threaded" else SimExecutor(
        max_fetch_bytes=512)


def mk_flat_store():
    """Four sequential-scan datasets: long single-directory streams clear
    the observation window and emit readahead candidates, and at four
    shards the top-level names spread over several shards."""
    store = RemoteStore()
    for i in range(4):
        store.add(make_dataset(f"flat{i}", "flat_files", n_files=60,
                               small_file_size=256 * 1024))
    return store


def drive(client, store):
    t = 0.0
    for ds in store.datasets.values():
        for f in ds.files:
            client.read(f.path, 0, f.size, t)
            t += 0.01


def pending(engine):
    """Kernel pending-prefetch entries over every shard."""
    return sum(len(s._pending_prefetch)
               for s in getattr(engine, "shards", [engine]))


def assert_conserved(st):
    assert st.submitted == st.completed + st.cancelled + st.deduped, \
        st.snapshot()


def test_sharded_stack_routes_flat_datasets_to_several_shards():
    """Guard for the four-shard cases: their datasets do not all land on
    one shard, so the sharded paths really run."""
    store = mk_flat_store()
    with open_cache(store, 64 * MB, cfg=CFG, n_shards=4) as client:
        sids = {client.engine.shard_id(ds.files[0].path)
                for ds in store.datasets.values()}
    assert len(sids) > 1


# ---------------------------------------------------------------------------
# background failures
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("executor", ["threaded", "sim"])
@pytest.mark.parametrize("n_shards", SHARDS)
def test_transient_prefetch_errors_retried_without_leak(n_shards, executor):
    store = mk_flat_store()
    flaky = FaultyStore(store, fail_rate=0.3, seed=11)
    retry = RetryPolicy(max_attempts=12, sleep=lambda s: None)
    client = open_cache(flaky, 64 * MB, cfg=CFG, n_shards=n_shards,
                        executor=fetching_executor(executor),
                        max_fetch_bytes=512, retry=retry)
    drive(client, store)
    assert client.flush(timeout=30.0)
    client.close()
    st = client.executor.stats
    assert st.submitted > 0, "the scans issued no prefetch candidates"
    assert st.retries > 0, "a 30% transient rate produced no retries"
    assert st.completed > 0
    assert_conserved(st)
    assert pending(client.engine) == 0


@pytest.mark.parametrize("executor", ["threaded", "sim"])
@pytest.mark.parametrize("n_shards", SHARDS)
def test_permanent_prefetch_failures_cancel_candidates(n_shards, executor):
    store = mk_flat_store()
    broken = FaultyStore(store, permanent_rate=1.0, seed=5)
    client = open_cache(broken, 64 * MB, cfg=CFG, n_shards=n_shards,
                        executor=fetching_executor(executor),
                        max_fetch_bytes=512)
    drive(client, store)
    assert client.flush(timeout=30.0)
    client.close()
    st = client.executor.stats
    assert st.submitted > 0
    assert st.fetch_errors > 0
    assert st.completed == 0, "every prefetch fetch should have failed"
    assert_conserved(st)
    assert pending(client.engine) == 0, "pending-table leak"


# ---------------------------------------------------------------------------
# lifecycle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_shards", SHARDS)
def test_close_with_inflight_prefetches_is_bounded(n_shards):
    """close() without a flush, while the shard workers still have a
    backlog of slow background fetches: it returns promptly, cancels the
    queued candidates on the kernel, and loses none."""
    store = mk_flat_store()
    slow = FaultyStore(store, jitter_s=0.02, seed=3)
    client = open_cache(slow, 64 * MB, cfg=CFG, n_shards=n_shards,
                        executor="threaded", max_fetch_bytes=512)
    drive(client, store)
    ex = client.executor
    backlog = sum(q.outstanding for q in ex._queues)
    assert backlog > 0, "no prefetch was in flight at close()"
    t0 = time.monotonic()
    client.close()
    assert time.monotonic() - t0 < 10.0
    st = ex.stats
    assert st.cancelled > 0
    assert_conserved(st)
    assert pending(client.engine) == 0
    assert not any(w.is_alive() for w in ex._workers + ex._helpers)


@pytest.mark.parametrize("executor", ["sim", "threaded", "none"])
@pytest.mark.parametrize("n_shards", SHARDS)
def test_close_is_idempotent_and_context_manager(n_shards, executor):
    store = mk_flat_store()
    f = store.datasets["flat0"].files[0]
    closes = []
    with open_cache(store, 64 * MB, cfg=CFG, n_shards=n_shards,
                    executor=executor) as client:
        assert client.read(f.path, 0, f.size, 0.0).blocks
        ex = client.executor
        real_close = ex.close
        ex.close = lambda *a, **kw: (closes.append(1), real_close(*a, **kw))
    assert closes == [1], "leaving the with-block must close the client"
    client.close()
    client.close()
    assert closes == [1], "a second close() must not close again"
    if executor == "threaded":
        assert not any(w.is_alive() for w in ex._workers)
        with pytest.raises(RuntimeError):
            ex.submit([(block_key(f.path, 0), f.size)], 1.0)


# ---------------------------------------------------------------------------
# store plumbing
# ---------------------------------------------------------------------------

def _tree(root):
    (root / "ds").mkdir(parents=True)
    (root / "ds" / "a.bin").write_bytes(b"x" * 4096)
    return CacheConfig(min_share=1 * MB, rebalance_quantum=1 * MB,
                       block_size=64 * 1024)


@pytest.mark.parametrize("executor", ["sim", "threaded"])
@pytest.mark.parametrize("n_shards", SHARDS)
def test_client_reports_store_capabilities(tmp_path, n_shards, executor):
    """The client reports what its backing store negotiated: a
    ``file://`` store's native ranges, batching and fan-out, and the
    bare defaults a legacy one-method store is adapted to."""
    cfg = _tree(tmp_path)
    with open_cache(f"file://{tmp_path}", 8 * MB, cfg=cfg,
                    n_shards=n_shards, executor=executor) as client:
        caps = client.store_capabilities()
        assert caps == StoreCapabilities(ranges=True, batching=True,
                                         concurrency=4)
        assert client.snapshot()["store"]["capabilities"] == \
            caps.snapshot()
        if executor == "threaded":
            assert client.executor.fan_out == 4

    class OneMethod:
        def fetch_block(self, path, size):
            return np.zeros(size, dtype=np.uint8)

    with open_cache(f"file://{tmp_path}", 8 * MB, cfg=cfg,
                    n_shards=n_shards, executor=executor,
                    backing=OneMethod()) as client:
        assert isinstance(client.backing, LegacyStoreAdapter)
        assert client.store_capabilities() == StoreCapabilities()


@pytest.mark.parametrize("n_shards", SHARDS)
def test_invalidate_meta_cache_reaches_kernel_snapshots(tmp_path,
                                                        n_shards):
    """LocalFSStore's mid-run refresh workflow: after ``refresh()`` and
    ``engine.invalidate_meta_cache()`` the kernel resolves paths against
    the new listing (its memoized directory levels are rebuilt) and the
    new file serves its bytes."""
    cfg = _tree(tmp_path)
    client = open_cache(f"file://{tmp_path}", 8 * MB, cfg=cfg,
                        n_shards=n_shards, fetch_bytes=True)
    a, b = ("ds", "a.bin"), ("ds", "b.bin")
    assert bytes(client.read(a, 0, 4096, 1.0).data) == b"x" * 4096
    eng = client.engine
    kernel = eng.shards[eng.shard_id(a)] if n_shards > 1 else eng
    assert kernel.levels.dir_levels(a)[-1] == ("a.bin", 0, 1)
    (tmp_path / "ds" / "b.bin").write_bytes(b"y" * 2048)   # tree changed
    client.meta.refresh()
    eng.invalidate_meta_cache()
    assert kernel.levels.dir_levels(a)[-1] == ("a.bin", 0, 2)
    assert bytes(client.read(b, 0, 2048, 2.0).data) == b"y" * 2048
    assert bytes(client.read(a, 0, 4096, 3.0).data) == b"x" * 4096
    client.close()


@pytest.mark.parametrize("executor", ["sim", "threaded"])
@pytest.mark.parametrize("n_shards", SHARDS)
def test_backing_override_is_the_byte_source(n_shards, executor):
    """With ``backing=`` the bytes come from that store and the first
    argument only serves metadata: a permanently failing primary still
    yields the right bytes, and a permanently failing ``backing=``
    fails the read although the primary is sound."""
    store = mk_flat_store()
    broken = FaultyStore(store, permanent_rate=1.0, seed=1)
    f = store.datasets["flat1"].files[0]
    want = store.fetch_range(block_key(f.path, 0), 0, f.size)
    with open_cache(broken, 64 * MB, cfg=CFG, n_shards=n_shards,
                    executor=executor, backing=store,
                    fetch_bytes=True) as client:
        assert np.array_equal(client.read(f.path, 0, f.size, 1.0).data,
                              want)
    with open_cache(store, 64 * MB, cfg=CFG, n_shards=n_shards,
                    executor=executor, backing=broken,
                    fetch_bytes=True) as client:
        with pytest.raises(StoreError):
            client.read(f.path, 0, f.size, 1.0)


@pytest.mark.parametrize("n_shards", SHARDS)
def test_demand_fetch_permanent_error_raises_in_reader(n_shards):
    """The SimExecutor fetches demand misses on the reader's own thread:
    a permanent store error reaches ``read`` and ``read_batch``, and the
    client keeps serving metadata reads afterwards."""
    store = mk_flat_store()
    broken = FaultyStore(store, permanent_rate=1.0, seed=5)
    client = open_cache(broken, 64 * MB, cfg=CFG, n_shards=n_shards,
                        executor="sim", fetch_bytes=True)
    f0 = store.datasets["flat0"].files[0]
    f2 = store.datasets["flat2"].files[0]
    with pytest.raises(StoreError):
        client.read(f0.path, 0, f0.size, 1.0)
    with pytest.raises(StoreError):
        client.read_batch([(f0.path, 0, f0.size), (f2.path, 0, f2.size)],
                          2.0)
    assert client.read(f0.path, 0, f0.size, 3.0, fetch=False).blocks
    client.close()
