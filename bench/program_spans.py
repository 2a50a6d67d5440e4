"""The program's own spans in a traced window: where the input path's
``igt.*`` spans (``repro.core.obs``, listed in docs/API.md) spend it.

    python3 -m bench.program_spans --workload <cell> --seed <n> --seconds <s>

runs a cell as ``bench/run.py --trace 1`` does and prints one JSON line:
the cell's end-to-end numbers and ``correct``, the window, the idle gaps
as this module names them, ``program`` and the four ``SHARES``.

The benchmark's reduction (``bench/xplane.py``) keeps only ``bench.*``
host spans.  This module reads the same trace file for the ``igt.*``
spans too, each with the line (thread) it ran on.  Each Python thread's
spans land on a line of their own in the host plane, and the lines all
carry the process's name, so a thread is told by its line.

* Idle gaps are named as ``xplane.reduce_events`` names them, among the
  ``bench.*`` spans and the ``igt.*`` spans of the window thread, the
  thread that holds ``bench.window``.  Spans of other threads name no
  gap.  Without ``igt.*`` spans the reduction is ``xplane``'s own.
* ``program`` holds, per thread, the seconds and count of each ``igt.*``
  span name, clipped to the window: under ``"window"`` for the window
  thread, and under the line's key for each other thread.
"""
from __future__ import annotations

import argparse
import importlib
import json
import lzma
from typing import Dict, List, Optional, Tuple

from bench import xplane

PROGRAM_PREFIX = "igt."
WINDOW_THREAD = "window"

# share of the window -> (span names, read on the busiest other thread)
SHARES = {
    "kernel_share.train": (("igt.kernel.lock_wait", "igt.kernel.read"),
                           False),
    "demand_queue_share.train": (("igt.client.demand_queued",), False),
    "demand_fetch_share.train": (("igt.client.demand_fetch",), False),
    "executor_busy_share.train": (("igt.executor.demand",
                                   "igt.executor.prefetch"), True),
}


def load_spans(path: str) -> List[Tuple[str, float, float, str]]:
    """Host ``bench.*`` and ``igt.*`` events of a trace file, each
    ``(name, start_ns, end_ns, line)`` with ``line`` ``"<plane>#<index>"``.
    ``path`` may be xz-compressed (``.xz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        with lzma.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    return [(n, s, e, f"{plane.name}#{i}") for plane in pd.planes
            if plane.name.startswith("/host:")
            for i, line in enumerate(plane.lines)
            for n, s, e in xplane._events(line)
            if n.startswith((xplane.SPAN_PREFIX, PROGRAM_PREFIX))]


def _program(spans, window_thread, t0, t1) -> Dict[str, Dict[str, list]]:
    out: Dict[str, Dict[str, list]] = {}
    for name, s, e, line in spans:
        if not name.startswith(PROGRAM_PREFIX) or e <= t0 or s >= t1:
            continue
        key = WINDOW_THREAD if line == window_thread else line
        acc = out.setdefault(key, {}).setdefault(name, [0.0, 0])
        acc[0] += (min(e, t1) - max(s, t0)) / 1e9
        acc[1] += 1
    return out


def reduce_events(spans, devices) -> Optional[dict]:
    """``xplane.reduce_events`` over the ``bench.*`` spans and the window
    thread's ``igt.*`` spans, with ``program`` added.  ``spans`` are
    ``load_spans``'s; ``None`` when there is no window or no device."""
    windows = [s for s in spans if s[0] == xplane.WINDOW_SPAN]
    if not windows:
        return None
    _, t0, t1, window_thread = windows[0]
    # xplane names a piece by the latest-starting open span, the first
    # listed on a tie: list a shared start's inner (earlier-ending) first
    named = sorted((s[:3] for s in spans
                    if s[0].startswith(xplane.SPAN_PREFIX)
                    or s[3] == window_thread),
                   key=lambda s: (s[1], s[2]))
    out = xplane.reduce_events(named, devices)
    if out is not None:
        out["program"] = _program(spans, window_thread, t0, t1)
    return out


def reduce_trace(path: str) -> Optional[dict]:
    _, devices = xplane.load_events(path)
    return reduce_events(load_spans(path), devices)


def share(reduced: Optional[dict], names, busiest: bool = False
          ) -> Optional[float]:
    """Percent of the traced window covered by the spans ``names`` on the
    window thread, or with ``busiest`` on the other thread where they
    cover most.  ``None`` where the trace holds no ``igt.*`` span at all;
    0 where it holds none of ``names`` there."""
    program = (reduced or {}).get("program")
    if not program or reduced["window_s"] <= 0:
        return None
    if busiest:
        threads = [v for k, v in program.items() if k != WINDOW_THREAD]
    else:
        threads = [program.get(WINDOW_THREAD, {})]
    secs = max((sum(th.get(n, (0.0, 0))[0] for n in names)
                for th in threads), default=0.0)
    return 100.0 * secs / reduced["window_s"]


def measure(cell, cj, seed: int, seconds: float) -> dict:
    """Drive the cell with its window traced, as ``bench/run.py`` does,
    and reduce the trace with the program's spans."""
    from bench import run
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    tracer = run.Tracer(True)
    try:
        res = driver.run(cell, cj, seed, seconds, tracer)
        path = xplane.newest_trace(tracer.dir)
        reduced = reduce_trace(path) if path else None
    finally:
        tracer.close()
    out = {"correct": bool(res["correct"]), "end_to_end": res["end_to_end"],
           "shares": {n: share(reduced, *a) for n, a in SHARES.items()}}
    if reduced is not None:
        out.update(window_s=reduced["window_s"], busy_s=reduced["busy_s"],
                   idle_gaps=xplane.top(reduced["gaps"], 16),
                   program=reduced["program"])
    return out


def main(argv=None) -> int:
    from bench import run
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    spec, entry, cell, cj = run.load_cell(args.workload)
    run.require_chips(entry["chips"])
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    out = measure(cell, cj, args.seed, args.seconds)
    print(json.dumps(dict(out, workload=args.workload, seed=args.seed)),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
