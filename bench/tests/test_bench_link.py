"""The link's timing arithmetic on a fake clock, and what the store
charges to it."""
import threading

import numpy as np
import pytest

from bench.link import Content, Link, LinkStore, mismatched_reads
from repro.storage.datasets import make_dataset

MB = 1 << 20


class FakeClock:
    def __init__(self):
        self.t = 100.0
        self.slept = []

    def __call__(self):
        return self.t

    def sleep(self, s):
        self.slept.append(s)


def test_latencies_overlap_and_bytes_queue_fifo():
    link = Link(latency_s=0.15, bandwidth_Bps=100.0)
    # three requests issued together: each waits its latency in parallel,
    # then their bytes cross one after another
    assert link.reserve(50, now=0.0) == pytest.approx(0.65)
    assert link.reserve(100, now=0.0) == pytest.approx(1.65)
    assert link.reserve(0, now=0.0) == pytest.approx(1.65)
    assert link.queue_wait_s == pytest.approx(0.5 + 1.5)
    # a request issued once the link is idle pays latency and its bytes
    assert link.reserve(10, now=10.0) == pytest.approx(10.25)
    assert link.counters() == (4, 160, pytest.approx(2.0))


def test_a_later_request_waits_for_the_queue_ahead():
    link = Link(latency_s=1.0, bandwidth_Bps=10.0)
    assert link.reserve(100, now=0.0) == pytest.approx(11.0)
    # arrives at 1.5 while the first still occupies the link until 11
    assert link.reserve(10, now=0.5) == pytest.approx(12.0)


def test_transfer_sleeps_until_the_bytes_have_crossed():
    clock = FakeClock()
    link = Link(0.15, 1e6, clock=clock, sleep=clock.sleep)
    link.transfer(300_000)
    assert clock.slept == [pytest.approx(0.45)]
    link.sleep_on = False
    link.transfer(300_000)
    assert len(clock.slept) == 1 and link.counters()[:2] == (2, 600_000)


def _store(link):
    store = LinkStore(link, Content(seed=3, pool_bytes=1 * MB,
                                    max_read=1 * MB))
    store.add(make_dataset("ds", "big_files", n_files=2, file_size=10 * MB))
    store.block_size = 4 * MB
    return store


def test_remote_fetch_charges_every_block_it_touches():
    clock = FakeClock()
    link = Link(0.0, 1.0, clock=clock, sleep=clock.sleep)
    store = _store(link)
    path = store.datasets["ds"].files[0].path
    store.fetch_range(path, 4 * MB - 10, 20)          # blocks 0 and 1
    assert link.bytes == 8 * MB
    store.fetch_range(path, 9 * MB, 100)              # the 2 MiB tail block
    assert link.bytes == 10 * MB
    with store.local_reads():                         # a cache hit
        store.fetch_range(path, 0, 100)
    assert link.counters()[:2] == (2, 10 * MB)


def test_hits_on_one_thread_do_not_free_another_threads_fetch():
    link = Link(0.0, 1e12)
    store = _store(link)
    path = store.datasets["ds"].files[0].path
    other = threading.Thread(target=store.fetch_range, args=(path, 0, 8))
    with store.local_reads():
        other.start()
        other.join(10)
    assert not other.is_alive() and link.requests == 1


def test_content_is_a_deterministic_function_of_seed_path_and_range():
    a, b = Content(5, pool_bytes=1 * MB), Content(5, pool_bytes=1 * MB)
    p, q = ("ds", "x"), ("ds", "y")
    whole = a.range(p, 0, 3 * MB)                     # wraps the pool
    assert np.array_equal(whole[1000:5000], b.range(p, 1000, 4000))
    assert not np.array_equal(a.range(p, 0, 4096), a.range(q, 0, 4096))
    assert not np.array_equal(a.range(p, 0, 4096),
                              Content(6, pool_bytes=1 * MB).range(p, 0, 4096))


def test_mismatched_reads_counts_altered_bytes():
    import zlib
    c = Content(1, pool_bytes=1 * MB)
    good = c.range(("f",), 10, 100)
    bad = good.copy()
    bad[7] ^= 1
    log = [[(("f",), 10, 100, zlib.crc32(good)),
            (("f",), 10, 100, zlib.crc32(bad))]]
    assert mismatched_reads(c, log) == (2, 1)
