"""Reduction of a JAX profiler trace (``*.xplane.pb``) to what the
per-layer metrics read.

* The window is the host span ``bench.window`` that the harness's tracer
  opens around the traced part of the measured loop.
* A device's busy time is the union of the intervals of its ``XLA Ops``
  events inside the window; ``busy_s`` is its mean over the devices.
* Each device operation is labelled ``name opcode result-shape`` (see
  :func:`op_label`).  Its time is the self time of its events inside the
  window (a ``while`` loop's time less the operations inside it), and its
  count the number of its events, both averaged over the devices.
* Idle gaps are the parts of the window in which device 0 runs nothing.
  Each piece of a gap between host span edges is named by the innermost
  other ``bench.*`` span open over it (``"none"`` where there is none).

Host spans are ``jax.profiler.TraceAnnotation``s, so they share the
trace's clock with the device events.
"""
from __future__ import annotations

import glob
import lzma
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

WINDOW_SPAN = "bench.window"
SPAN_PREFIX = "bench."
DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"


def newest_trace(trace_dir: str) -> Optional[str]:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(found, key=os.path.getmtime) if found else None


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns, e.start_ns + e.duration_ns


def op_label(text: str) -> str:
    """``name opcode result-shape`` of an HLO instruction's text
    (``%fusion.3 = f32[8,128]{1,0} fusion(...), ...``), layouts dropped;
    a custom call also gets its target.  Other names pass unchanged."""
    m = re.match(r"%?([\w.-]+) = ", text)
    if not m:
        return text
    rest, depth, i = text[m.end():], 0, 0
    for i, ch in enumerate(rest):
        depth += ch in "([{"
        depth -= ch in ")]}"
        if ch == " " and depth == 0:
            break
    shape = re.sub(r"\{[^{}]*\}", "", rest[:i])
    opcode = re.match(r"[\w-]*", rest[i + 1:]).group(0)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    label = f"{m.group(1)} {opcode} {shape}"
    return f"{label} {target.group(1)}" if target else label


def _self_times(events):
    """(name, self time) of possibly nested events: a parent's time less
    the parts its children cover."""
    out, stack = [], []
    for n, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and out[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            out[stack[-1]][3] -= min(e, out[stack[-1]][2]) - s
        out.append([n, s, e, e - s])
        stack.append(len(out) - 1)
    return [(n, t) for n, _, _, t in out]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def load_events(path: str):
    """(host ``bench.*`` spans, per-device op events) of a trace file,
    each event ``(name, start_ns, end_ns)``.  ``path`` may be
    xz-compressed (``.xz``)."""
    from jax.profiler import ProfileData
    if path.endswith(".xz"):
        with lzma.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    spans: List[Tuple[str, float, float]] = []
    devices: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PLANE):
            devices.append([(op_label(n), s, e) for line in plane.lines
                            if line.name == OPS_LINE
                            for n, s, e in _events(line)])
        elif plane.name.startswith("/host:"):
            spans.extend(ev for line in plane.lines for ev in _events(line)
                         if ev[0].startswith(SPAN_PREFIX))
    return spans, devices


def reduce_events(spans, devices) -> Optional[dict]:
    """``None`` when there is no window or no device."""
    windows = [s for s in spans if s[0] == WINDOW_SPAN]
    if not windows or not devices:
        return None
    _, t0, t1 = windows[0]
    inner = [s for s in spans if s[0] != WINDOW_SPAN and s[2] > t0
             and s[1] < t1]
    op_ns: Dict[str, float] = defaultdict(float)
    op_n: Dict[str, float] = defaultdict(float)
    busy_ns = []
    first_busy: List[Tuple[float, float]] = []
    for i, ops in enumerate(devices):
        clipped = [(n, max(s, t0), min(e, t1)) for n, s, e in ops
                   if e > t0 and s < t1]
        for n, t in _self_times(clipped):
            op_ns[n] += t / len(devices)
            op_n[n] += 1 / len(devices)
        busy = _union([(s, e) for _, s, e in clipped])
        busy_ns.append(sum(e - s for s, e in busy))
        if i == 0:
            first_busy = busy
    gaps: Dict[str, float] = defaultdict(float)
    edges = sorted({t for sp in inner for t in sp[1:]})
    cursor = t0
    for s, e in first_busy + [(t1, t1)]:
        if s > cursor:
            cuts = [cursor] + [t for t in edges if cursor < t < s] + [s]
            for a, b in zip(cuts, cuts[1:]):
                mid = (a + b) / 2
                open_ = [sp for sp in inner if sp[1] <= mid < sp[2]]
                name = (max(open_, key=lambda sp: sp[1])[0] if open_
                        else "none")
                gaps[name] += (b - a) / 1e9
        cursor = max(cursor, e)
    return {"window_s": (t1 - t0) / 1e9,
            "busy_s": sum(busy_ns) / len(busy_ns) / 1e9,
            "n_devices": len(devices),
            "ops": {n: v / 1e9 for n, v in op_ns.items()},
            "op_counts": dict(op_n),
            "gaps": dict(gaps)}


def reduce_trace(path: str) -> Optional[dict]:
    return reduce_events(*load_events(path))


def top(d: Dict[str, float], k: int = 10) -> List[list]:
    return [[n, v] for n, v in sorted(d.items(), key=lambda kv: -kv[1])[:k]]
