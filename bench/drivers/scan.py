"""A preprocessing scan that shares the trainer's cache: a copy of job 5
of the paper's suite, ``preprocess_icoads`` (``repro.sim.workloads``).

It reads the files of its dataset in order, ``files_per_step`` per
step through the cache client, then spends ``compute_s`` on its own
work, and starts over at the end.  The work stands for a separate
process, so it waits instead of holding the interpreter.
"""
from __future__ import annotations

import threading
import time
from typing import List, Tuple


class ScanJob:
    def __init__(self, client, store, dataset: str, files_per_step: int,
                 compute_s: float) -> None:
        self.client = client
        self.files = [(f.path, f.size) for f in store.datasets[dataset].files]
        self.files_per_step = files_per_step
        self.compute_s = compute_s
        self._pos = 0
        self._stop = threading.Event()
        self._thread = None
        # (finish time, bytes, block hits, block misses) of every step
        self.steps: List[Tuple[float, int, int, int]] = []

    def step(self, compute: bool = True) -> None:
        k, n = self.files_per_step, len(self.files)
        reqs = [(p, 0, size) for p, size in
                (self.files[(self._pos + i) % n] for i in range(k))]
        self._pos = (self._pos + k) % n
        results = self.client.read_batch(reqs, fetch=True)
        hits = sum(b.hit for r in results for b in r.blocks)
        misses = sum(not b.hit for r in results for b in r.blocks)
        self.steps.append((time.perf_counter(),
                           sum(r.data.nbytes for r in results), hits, misses))
        if compute:
            time.sleep(self.compute_s)

    def _loop(self) -> None:
        while not self._stop.is_set():
            self.step()

    def start(self) -> None:
        self._thread = threading.Thread(target=self._loop, name="bench-scan",
                                        daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 120.0) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout)
            if self._thread.is_alive():
                raise RuntimeError("the scan job did not stop")

    def window(self, t0: float, t1: float) -> dict:
        """Counters of the steps that finished inside ``[t0, t1]``."""
        inside = [s for s in self.steps if t0 <= s[0] <= t1]
        return {"scan_steps": len(inside),
                "scan_bytes": sum(s[1] for s in inside),
                "scan_hits": sum(s[2] for s in inside),
                "scan_misses": sum(s[3] for s in inside)}

    @staticmethod
    def end_to_end(counters: dict) -> dict:
        """Bytes the scan received in the window, per second."""
        return {"scan_MB_per_s": counters["scan_bytes"] / 1e6
                / counters["window_s"]}


Job = ScanJob
