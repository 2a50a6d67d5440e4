"""The unified storage API (this PR's tentpole).

Covers the v2 ``BackingStore`` protocol (ranged reads, batched
``fetch_many``, capability negotiation), the URI scheme registry
(``sim:// / file:// / mem:// / faulty+...``), the real ``LocalFSStore``
round-trip against an on-disk tree, the legacy one-method shim, and the
fault contract the client layer promises: transient errors retried with
accounting, permanent errors propagated with clean candidate
cancellation (no kernel pending-table leak).
"""
import os
import threading
import time

import numpy as np
import pytest

from repro.core import CacheConfig, IGTCache, CacheClient, open_cache
from repro.core.client import SimExecutor, ThreadedExecutor
from repro.core.types import MB, block_key, split_block_key
from repro.storage import (FaultyStore, LegacyStoreAdapter, LocalFSStore,
                           MemStore, RemoteStore, RetryPolicy,
                           StoreCapabilities, StoreError, TransientStoreError,
                           as_backing_store, make_dataset, open_store,
                           registered_schemes)
from repro.storage.api import DeadlineError, store_spec

CFG = CacheConfig(min_share=4 * MB, rebalance_quantum=4 * MB,
                  window=40, reanalyze_every=20)


# ---------------------------------------------------------------------------
# block-key helpers (satellite: one construction point)
# ---------------------------------------------------------------------------

def test_block_key_roundtrip():
    p = ("ds", "train", "a.bin")
    assert block_key(p, 3) == ("ds", "train", "a.bin", "#3")
    assert split_block_key(block_key(p, 3)) == (p, 3)
    assert split_block_key(p) == (p, None)
    assert split_block_key(()) == ((), None)
    # a real file can be named "#something" — that is not a block key
    assert split_block_key(("ds", "#notes")) == (("ds", "#notes"), None)


# ---------------------------------------------------------------------------
# URI registry
# ---------------------------------------------------------------------------

def test_open_store_registry_schemes():
    assert {"sim", "file", "mem"} <= set(registered_schemes())
    sim = open_store("sim://default?latency_s=0.2")
    assert isinstance(sim, RemoteStore)
    assert sim.transfer.latency_s == pytest.approx(0.2)
    mem = open_store("mem://?block_size=65536")
    assert isinstance(mem, MemStore) and mem.block_size == 65536
    with pytest.raises(ValueError):
        open_store("warp://nope")
    with pytest.raises(ValueError):
        open_store("no-scheme-at-all")


def test_cache_scheme_registry_roundtrip():
    """cache:// resolves through the same registry as the stores, but
    yields a daemon *address* (not a store): open_cache dispatches on it
    to build a RemoteCacheClient instead of a kernel."""
    from repro.daemon import DaemonAddress, format_cache_uri

    assert "cache" in registered_schemes()
    uds = open_store("cache:///tmp/igt.sock")
    assert isinstance(uds, DaemonAddress)
    assert uds.is_cache_address
    assert uds.kind == "uds" and uds.path == "/tmp/igt.sock"
    assert uds.connect_args() == ("uds", "/tmp/igt.sock")
    tcp = open_store("cache://127.0.0.1:7171?label=trainer")
    assert tcp.kind == "tcp" and tcp.connect_args() == \
        ("tcp", ("127.0.0.1", 7171))
    assert tcp.params == {"label": "trainer"}
    # the address remembers its URI, and format round-trips
    assert uds.uri.startswith("cache://")
    assert open_store(format_cache_uri(uds)).connect_args() == \
        uds.connect_args()
    with pytest.raises(ValueError):
        open_store("cache://")            # no endpoint at all


def test_open_store_faulty_wrapper():
    st = open_store("faulty+sim://default?fail_rate=1.0&seed=3")
    assert isinstance(st, FaultyStore)
    assert isinstance(st.inner, RemoteStore)
    st.inner.add(make_dataset("d", "big_files", n_files=1, file_size=8 * MB))
    bp = block_key(st.inner.datasets["d"].files[0].path, 0)
    with pytest.raises(TransientStoreError):
        st.fetch_range(bp, 0, 16)
    assert st.injected_transient == 1
    # metadata passes through untouched
    assert st.subtree_bytes(("d",)) == 8 * MB


# ---------------------------------------------------------------------------
# RemoteStore v2: ranged synthesis (satellite: hoisted digest)
# ---------------------------------------------------------------------------

def test_remote_store_ranged_synthesis_consistent():
    store = RemoteStore()
    store.add(make_dataset("big", "big_files", n_files=2, file_size=9 * MB))
    f = store.datasets["big"].files[0]
    bp = block_key(f.path, 1)
    whole = store.fetch_block(bp, 1 * MB)
    # any sub-range equals the sliced prefix — no over-synthesis needed
    for off, ln in ((0, 17), (3, 64), (1000, 4096), (1 * MB - 5, 5)):
        assert np.array_equal(store.fetch_range(bp, off, ln),
                              whole[off:off + ln]), (off, ln)
    # distinct blocks and files produce distinct content
    assert not np.array_equal(store.fetch_block(block_key(f.path, 0), 256),
                              store.fetch_block(bp, 256))
    other = store.datasets["big"].files[1]
    assert not np.array_equal(
        store.fetch_block(block_key(other.path, 1), 256),
        store.fetch_block(bp, 256))
    # deterministic across store instances (the seed cache is pure)
    fresh = RemoteStore()
    assert np.array_equal(fresh.fetch_range(bp, 100, 100),
                          store.fetch_range(bp, 100, 100))
    # file-path and block-path addressing are coherent (one content
    # stream per file, like the real stores)
    assert np.array_equal(store.fetch_range(f.path, 4 * MB + 100, 16),
                          store.fetch_range(bp, 100, 16))
    # fetch_many preserves request order
    reqs = [(bp, 5, 10), (block_key(f.path, 0), 0, 10), (bp, 0, 10)]
    got = store.fetch_many(reqs)
    for (p, o, n), data in zip(reqs, got):
        assert np.array_equal(data, store.fetch_range(p, o, n))
    assert store.capabilities().ranges


# ---------------------------------------------------------------------------
# LocalFSStore round-trip (satellite)
# ---------------------------------------------------------------------------

def _make_tree(root):
    """Real directory tree: two 'datasets', nested dirs, multi-block and
    tail-odd file sizes (block_size=4096 in the tests below)."""
    rng = np.random.default_rng(42)
    layout = {
        ("alpha", "a.bin"): 10_000,        # 3 blocks, short tail
        ("alpha", "sub", "b.bin"): 4096,   # exactly one block
        ("alpha", "sub", "c.bin"): 100,    # sub-block file
        ("beta", "d.bin"): 13_000,
    }
    contents = {}
    for rel, size in layout.items():
        fs = os.path.join(str(root), *rel)
        os.makedirs(os.path.dirname(fs), exist_ok=True)
        data = rng.integers(0, 256, size, dtype=np.uint8).tobytes()
        with open(fs, "wb") as f:
            f.write(data)
        contents[rel] = data
    return contents


def test_local_fs_meta_matches_real_tree(tmp_path):
    contents = _make_tree(tmp_path)
    store = LocalFSStore(str(tmp_path), block_size=4096)
    # listings: sorted names, dirs and files interleaved
    assert store.listing(()) == ["alpha", "beta"]
    assert store.listing(("alpha",)) == ["a.bin", "sub"]
    assert store.child_index(("alpha",), "sub") == 1
    assert store.is_file(("alpha", "a.bin"))
    assert not store.is_file(("alpha", "sub"))
    # sizes and subtree totals agree with the filesystem
    for rel, data in contents.items():
        assert store.file_size(rel) == len(data)
    assert store.subtree_bytes(()) == sum(map(len, contents.values()))
    assert store.subtree_bytes(("alpha",)) == 10_000 + 4096 + 100
    # block enumeration covers every byte exactly once
    keys = list(store.iter_block_keys(("alpha",)))
    assert sum(sz for _, sz in keys) == store.subtree_bytes(("alpha",))
    assert (block_key(("alpha", "a.bin"), 2), 10_000 - 8192) in keys
    # flat index spans the dataset
    ordinal, total = store.flat_block_index(("alpha", "sub", "b.bin"), 0)
    assert 0 <= ordinal < total == 3 + 1 + 1


def test_local_fs_serves_real_bytes(tmp_path):
    contents = _make_tree(tmp_path)
    store = LocalFSStore(str(tmp_path), block_size=4096)
    data = contents[("alpha", "a.bin")]
    # ranged reads address block-relative offsets
    got = store.fetch_range(block_key(("alpha", "a.bin"), 2), 10, 100)
    assert bytes(got) == data[8192 + 10:8192 + 110]
    # file-path addressing works too
    assert bytes(store.fetch_range(("alpha", "a.bin"), 0, 64)) == data[:64]
    # batched fetch groups by file, results in request order
    reqs = [(block_key(("beta", "d.bin"), 1), 0, 50),
            (("alpha", "sub", "c.bin"), 5, 20),
            (block_key(("beta", "d.bin"), 0), 100, 10)]
    got = store.fetch_many(reqs)
    assert bytes(got[0]) == contents[("beta", "d.bin")][4096:4146]
    assert bytes(got[1]) == contents[("alpha", "sub", "c.bin")][5:25]
    assert bytes(got[2]) == contents[("beta", "d.bin")][100:110]
    # error taxonomy: missing file is permanent, bad components rejected
    with pytest.raises(StoreError):
        store.fetch_range(("alpha", "missing.bin"), 0, 1)
    with pytest.raises(StoreError):
        store.fetch_range(("..", "escape"), 0, 1)
    caps = store.capabilities()
    assert caps.ranges and caps.batching


@pytest.mark.parametrize("executor", ["sim", "threaded"])
def test_local_fs_end_to_end_open_cache(tmp_path, executor):
    """Acceptance: open_cache over a real directory → read(fetch=True)
    returns the on-disk bytes, second pass is served as cache hits —
    under both the inline SimExecutor and the ThreadedExecutor."""
    contents = _make_tree(tmp_path)
    cfg = CacheConfig(min_share=64 * 1024, rebalance_quantum=64 * 1024,
                      block_size=4096, window=40, reanalyze_every=20)
    client = open_cache(f"file://{tmp_path}", 8 * MB, cfg=cfg,
                        executor=executor, fetch_bytes=True)
    assert isinstance(client.meta, LocalFSStore)
    assert client.meta.block_size == 4096      # synced from cfg
    try:
        t = 0.0
        for rel, data in sorted(contents.items()):
            res = client.read(rel, 0, len(data), t)
            assert bytes(res.data) == data, rel
            t += 0.01
        # partial-extent read: exact sub-range, spanning a block boundary
        res = client.read(("alpha", "a.bin"), 4000, 300, t)
        assert bytes(res.data) == contents[("alpha", "a.bin")][4000:4300]
        # second pass: all hits, identical bytes
        for rel, data in sorted(contents.items()):
            res = client.read(rel, 0, len(data), t)
            assert all(b.hit for b in res.blocks), rel
            assert bytes(res.data) == data, rel
            t += 0.01
        # batched read with a mix of hits and fresh misses
        batch = [(("alpha", "a.bin"), 0, 10_000),
                 (("beta", "d.bin"), 4096, 4096)]
        results = client.read_batch(batch, t, fetch=True)
        assert bytes(results[0].data) == contents[("alpha", "a.bin")]
        assert bytes(results[1].data) == \
            contents[("beta", "d.bin")][4096:8192]
        assert client.flush(timeout=10.0)
    finally:
        client.close()
    st = client.executor.stats
    assert st.completed + st.cancelled + st.deduped == st.submitted
    assert st.fetch_errors == 0


# ---------------------------------------------------------------------------
# mem:// store
# ---------------------------------------------------------------------------

def test_mem_store_roundtrip_and_client():
    store = MemStore(block_size=1024)
    payload = bytes(range(256)) * 20        # 5120 bytes = 5 blocks
    store.add_file(("ds", "x.bin"), payload)
    store.add_file(("ds", "y.bin"), b"tiny")
    assert store.listing(()) == ["ds"]
    assert store.listing(("ds",)) == ["x.bin", "y.bin"]
    assert store.file_size(("ds", "x.bin")) == 5120
    assert bytes(store.fetch_range(block_key(("ds", "x.bin"), 1), 10, 20)) \
        == payload[1034:1054]
    with pytest.raises(StoreError):
        store.fetch_range(("ds", "x.bin"), 5000, 1000)   # past the end
    cfg = CacheConfig(min_share=1 * MB, rebalance_quantum=1 * MB,
                      block_size=1024, window=40)
    client = open_cache(store, 4 * MB, cfg=cfg, fetch_bytes=True)
    res = client.read(("ds", "x.bin"), 100, 2000, 1.0)
    assert bytes(res.data) == payload[100:2100]


# ---------------------------------------------------------------------------
# legacy shim
# ---------------------------------------------------------------------------

class _OneMethodStore:
    """A third-party PR-3 style store: fetch_block only."""

    def __init__(self, inner):
        self.inner = inner
        self.calls = []

    def fetch_block(self, path, size):
        self.calls.append((path, size))
        return self.inner.fetch_block(path, size)


def test_legacy_fetch_block_store_adapts():
    store = RemoteStore()
    store.add(make_dataset("big", "big_files", n_files=1, file_size=8 * MB))
    legacy = _OneMethodStore(store)
    adapted = as_backing_store(legacy)
    assert isinstance(adapted, LegacyStoreAdapter)
    assert adapted.capabilities() == StoreCapabilities(
        ranges=False, batching=False, concurrency=1)
    bp = block_key(store.datasets["big"].files[0].path, 0)
    got = adapted.fetch_range(bp, 100, 50)
    assert np.array_equal(got, store.fetch_range(bp, 100, 50))
    # the adapter over-fetched the prefix through the one legacy method
    assert legacy.calls == [(bp, 150)]
    # a v2 store passes through untouched; meta-only objects stay None
    assert as_backing_store(store) is store
    assert as_backing_store(object()) is None
    assert as_backing_store(None) is None


def test_legacy_store_through_client_bytes():
    store = RemoteStore()
    store.add(make_dataset("big", "big_files", n_files=1, file_size=8 * MB))
    legacy = _OneMethodStore(store)
    client = open_cache(store, 64 * MB, cfg=CFG, backing=legacy,
                        fetch_bytes=True)
    f = store.datasets["big"].files[0]
    res = client.read(f.path, 1 * MB, 2 * MB, 1.0)
    ref = np.concatenate([store.fetch_block(block_key(f.path, 0), 4 * MB)])
    assert np.array_equal(res.data, ref[1 * MB:3 * MB])


# ---------------------------------------------------------------------------
# retry policy
# ---------------------------------------------------------------------------

def test_retry_policy_semantics():
    sleeps = []
    policy = RetryPolicy(max_attempts=4, backoff_s=0.01, multiplier=2.0,
                         sleep=sleeps.append)
    attempts = {"n": 0}

    def flaky():
        attempts["n"] += 1
        if attempts["n"] < 3:
            raise TransientStoreError("blip")
        return "ok"

    retried = []
    assert policy.call(flaky, on_retry=lambda a, e: retried.append(a)) == "ok"
    assert attempts["n"] == 3 and retried == [1, 2]
    assert sleeps == [0.01, 0.02]            # exponential backoff

    def always_transient():
        raise TransientStoreError("down")

    with pytest.raises(TransientStoreError):
        policy.call(always_transient)

    def permanent():
        attempts["n"] += 1
        raise StoreError("gone")

    attempts["n"] = 0
    with pytest.raises(StoreError):
        policy.call(permanent)
    assert attempts["n"] == 1                # no retry on permanent errors


# ---------------------------------------------------------------------------
# fault injection through the client (satellite)
# ---------------------------------------------------------------------------

def _sim_world():
    store = RemoteStore()
    store.add(make_dataset("flat", "flat_files", n_files=120,
                           small_file_size=256 * 1024))
    store.add(make_dataset("big", "big_files", n_files=4, file_size=16 * MB))
    return store


def test_store_hang_hits_retry_deadline():
    """An endlessly-flaky, hanging store costs a *bounded* wait: the
    retry deadline converts the stall into DeadlineError instead of
    sleeping through the full backoff ladder."""
    inner = MemStore(block_size=1 * MB)
    inner.add_file(("a", "f"), np.zeros(1 * MB, dtype=np.uint8))
    flaky = FaultyStore(inner, fail_rate=1.0, hang_rate=1.0, hang_s=0.05,
                        seed=3)
    pol = RetryPolicy(max_attempts=100, backoff_s=0.01,
                      deadline_s=0.25)
    t0 = time.monotonic()
    with pytest.raises(DeadlineError):
        pol.call(flaky.fetch_range, ("a", "f#b0"), 0, 1024)
    assert time.monotonic() - t0 < 5.0, "deadline did not bound the wait"


def test_store_spec_roundtrip():
    # object spec: a populated RemoteStore must travel as itself
    store = _sim_world()
    kind, payload = store_spec(store)
    assert kind == "object" and payload is store
    # URI spec: strings re-open in the receiving process
    assert store_spec("sim://default") == ("uri", "sim://default")


def test_transient_faults_absorbed_with_retry_accounting():
    """Seeded transient faults on the demand path: reads still return
    correct bytes, and the executor's retry counter matches the
    injector's transient count exactly."""
    store = _sim_world()
    faulty = FaultyStore(store, fail_rate=0.3, seed=11)
    retry = RetryPolicy(max_attempts=10, sleep=lambda s: None)
    client = open_cache(store, 128 * MB, cfg=CFG, backing=faulty,
                        retry=retry, fetch_bytes=True, executor="sim")
    f = store.datasets["big"].files[0]
    t = 1.0
    for off in range(0, 8 * MB, 1 * MB):
        res = client.read(f.path, off, 64 * 1024, t)
        ref = store.fetch_range(block_key(f.path, off // (4 * MB)),
                                off % (4 * MB), 64 * 1024)
        assert np.array_equal(res.data, ref)
        t += 0.01
    st = client.executor.stats
    assert st.retries > 0, "a 30% fail rate over 8 fetches must retry"
    assert st.retries == faulty.injected_transient
    assert st.fetch_errors == 0


def test_permanent_failure_no_pending_table_leak():
    """Acceptance for the fault contract: with a permanently failing
    backend, demand reads raise, background candidates are *cancelled*
    (never silently dropped), the executor identity holds, and the
    kernel's pending table is empty after close."""
    store = _sim_world()
    faulty = FaultyStore(store, permanent_rate=1.0, seed=5)
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    ex = ThreadedExecutor(queue_depth=4096, max_fetch_bytes=4096)
    retry = RetryPolicy(max_attempts=3, sleep=lambda s: None)
    client = CacheClient(engine, backing=faulty, executor=ex, retry=retry)
    # a demand read that needs bytes propagates the permanent error
    f = store.datasets["big"].files[0]
    with pytest.raises(StoreError):
        client.read(f.path, 0, 64 * 1024, 0.5, fetch=True)
    assert all(w.is_alive() for w in ex._workers)
    # drive a sequential scan so the kernel issues prefetch candidates;
    # every background fetch fails permanently → cancel, not drop
    t = 1.0
    for fl in store.datasets["flat"].files:
        client.read(fl.path, 0, fl.size, t)
        t += 0.01
    assert client.flush(timeout=15.0)
    client.close()
    st = ex.stats
    assert st.submitted > 0, "scan generated no candidates"
    assert st.cancelled > 0 and st.completed == 0
    assert st.completed + st.cancelled + st.deduped == st.submitted
    assert st.fetch_errors > 0
    assert not engine._pending_prefetch, "pending-table leak"


def test_transient_faults_under_threaded_executor():
    """Background candidates retried through the shard workers; the
    identity and the pending table stay clean under a flaky backend."""
    store = _sim_world()
    faulty = FaultyStore(store, fail_rate=0.4, seed=7)
    engine = IGTCache(store, 128 * MB, cfg=CFG)
    ex = ThreadedExecutor(queue_depth=4096, max_fetch_bytes=2048)
    retry = RetryPolicy(max_attempts=12, sleep=lambda s: None)
    client = CacheClient(engine, backing=faulty, executor=ex, retry=retry)
    t = 1.0
    for fl in store.datasets["flat"].files:
        client.read(fl.path, 0, fl.size, t)
        t += 0.01
    assert client.flush(timeout=20.0)
    client.close()
    st = ex.stats
    assert st.submitted > 0 and st.completed > 0
    assert st.completed + st.cancelled + st.deduped == st.submitted
    assert st.retries > 0
    assert not engine._pending_prefetch


# ---------------------------------------------------------------------------
# batched demand funnel
# ---------------------------------------------------------------------------

class _CountingStore:
    """v2 wrapper counting fetch_many calls and their sizes."""

    def __init__(self, inner):
        self.inner = inner
        self.many_calls = []
        self.lock = threading.Lock()

    def capabilities(self):
        return self.inner.capabilities()

    def fetch_range(self, path, offset, length):
        return self.inner.fetch_range(path, offset, length)

    def fetch_many(self, requests):
        with self.lock:
            self.many_calls.append(len(requests))
        return self.inner.fetch_many(requests)

    def __getattr__(self, name):
        return getattr(self.inner, name)


def test_read_batch_funnels_demand_through_one_fetch_many():
    store = _sim_world()
    counting = _CountingStore(store)
    client = open_cache(store, 128 * MB, cfg=CFG, backing=counting,
                        fetch_bytes=True, executor="sim")
    f0, f1 = store.datasets["big"].files[:2]
    reqs = [(f0.path, 0, 64 * 1024), (f1.path, 0, 64 * 1024),
            (f0.path, 4 * MB, 64 * 1024)]
    results = client.read_batch(reqs, 1.0)
    for (fp, off, sz), res in zip(reqs, results):
        b = off // (4 * MB)
        ref = store.fetch_range(block_key(fp, b), off % (4 * MB), sz)
        assert np.array_equal(res.data, ref)
    # all three demand misses travelled in ONE batched fetch_many call
    assert counting.many_calls == [3]
    assert client.executor.stats.demand_fetches == 3
