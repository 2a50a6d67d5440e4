#!/usr/bin/env python3
"""Cell benchmark of the cache-fed jobs on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell is an entry of ``BENCHMARK.json`` and a file
``bench/workloads/<cell>.json`` (its driver, job, traffic, cache and link);
its configuration is ``bench/configs/<config>.json``.  The driver
``bench/drivers/<driver>.py`` sets up, warms up, measures for ``--seconds``
and checks the timed path's outputs against the plain reference.  With
``--trace 0`` the result carries the cell's end-to-end metrics; with
``--trace 1`` the window is traced and the result carries its per-layer
metrics, each read by ``bench/metrics/<metric>.py``.

The last line of standard output is the result as one JSON object; the
numbers compared for ``correct`` close standard error.  Any backend other
than a TPU, or fewer chips than the cell asks for, exits non-zero with no
result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


class BenchError(RuntimeError):
    pass


def load_cell(name: str, root: Path = None) -> tuple:
    """(BENCHMARK.json, its entry for the cell, cell file, config file)."""
    root = root or ROOT
    spec = json.loads((root / "BENCHMARK.json").read_text())
    entry = next((w for w in spec["workloads"] if w["name"] == name), None)
    if entry is None:
        raise BenchError(f"no cell {name!r} in BENCHMARK.json")
    cell = json.loads((root / "bench" / "workloads" / f"{name}.json")
                      .read_text())
    cj = json.loads((root / "bench" / "configs" / f"{entry['config']}.json")
                    .read_text())
    cell["chips"] = entry["chips"]
    return spec, entry, cell, cj


def metrics_for(spec: dict, cell_name: str, kind: str) -> list:
    """The cell's metrics of ``kind`` (``end_to_end`` or ``per_layer``).
    A per-layer metric without ``workloads`` belongs to every cell that
    reports the end-to-end metric it moves."""
    e2e = [m["name"] for m in metrics_for(spec, cell_name, "end_to_end")] \
        if kind == "per_layer" else None
    out = []
    for m in spec[kind]:
        if "workloads" in m:
            if cell_name in m["workloads"]:
                out.append(m)
        elif kind == "end_to_end" or m["moves"] in e2e:
            out.append(m)
    return out


def metric_reader(name: str, root: Path = None):
    path = (root or ROOT) / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


class Tracer:
    """Profiles the first ``TRACE_SECONDS`` of the window when on, inside
    the ``bench.window`` span that the trace reduction reads.  A driver
    calls :meth:`start` as its window opens, :meth:`tick` as it goes and
    :meth:`stop` as it closes.  ``stop_s`` is the time that collecting the
    trace took inside the window, in which no work ran.  The trace goes
    to a temporary directory that is removed once reduced."""

    TRACE_SECONDS = 20.0

    def __init__(self, on: bool) -> None:
        self.on = on
        self.dir = tempfile.mkdtemp(prefix="bench-trace-") if on else None
        self._span = None
        self._t0 = 0.0
        self.stop_s = 0.0

    def start(self) -> None:
        if self.on:
            import jax
            jax.profiler.start_trace(self.dir)
            self._span = jax.profiler.TraceAnnotation("bench.window")
            self._span.__enter__()
            self._t0 = time.perf_counter()

    def tick(self) -> None:
        if self._span is not None and \
                time.perf_counter() - self._t0 >= self.TRACE_SECONDS:
            t = time.perf_counter()
            self.stop()
            self.stop_s = time.perf_counter() - t

    def stop(self) -> None:
        if self._span is not None:
            import jax
            self._span.__exit__(None, None, None)
            self._span = None
            jax.profiler.stop_trace()

    def reduce(self):
        from bench import xplane
        path = xplane.newest_trace(self.dir)
        return xplane.reduce_trace(path) if path else None

    def close(self) -> None:
        if self.dir:
            shutil.rmtree(self.dir, ignore_errors=True)


def _num(x):
    return x if isinstance(x, (int, float)) and math.isfinite(x) else None


def require_chips(chips: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform!r} "
                         f"({devices[0].device_kind})")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    return devices


def measure(spec, entry, cell, cj, seed: int, seconds: float,
            trace: bool, devices, root: Path = None) -> dict:
    """Drive the cell and assemble the result object."""
    from bench.peaks import peaks
    driver = importlib.import_module(f"bench.drivers.{cell['driver']}")
    tracer = Tracer(trace)
    try:
        res = driver.run(cell, cj, seed, seconds, tracer)
        reduced = tracer.reduce() if trace else None
    finally:
        tracer.close()
    dev = devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices),
              "memory_peak_bytes": res["memory_peak_bytes"]}
    metrics = {}
    name = entry["name"]
    if trace:
        ctx = SimpleNamespace(counters=dict(res["counters"],
                                            trace_stop_s=tracer.stop_s),
                              trace=reduced,
                              cell=cell, cj=cj, chips=cell["chips"],
                              peaks=peaks(dev.device_kind),
                              end_to_end=res["end_to_end"])
        for m in metrics_for(spec, name, "per_layer"):
            v = metric_reader(m["name"], root)(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        values = dict(res["end_to_end"], setup_s=res["t_start"] - T0)
        for m in metrics_for(spec, name, "end_to_end"):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    out = {"correct": bool(res["correct"]), "attempted": res["attempted"],
           "failed": res["failed"], "metrics": metrics, "device": device}
    if trace and reduced is not None:
        from bench.xplane import top
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
        out["breakdown"] = {"device_ops": top(reduced["ops"]),
                            "idle_gaps": top(reduced["gaps"])}
    out["checks"] = {k: {"value": _num(v), "limit": lim}
                     for k, (v, lim) in res["checks"].items()}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        spec, entry, cell, cj = load_cell(args.workload)
        devices = require_chips(entry["chips"])
    except (BenchError, OSError, KeyError, ValueError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    out = measure(spec, entry, cell, cj, args.seed, args.seconds,
                  bool(args.trace), devices)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
