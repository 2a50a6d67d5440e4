#!/usr/bin/env python3
"""Record the small chip trace that ``test_bench_trace.py`` reduces.

    python3 bench/testdata/record.py [out_dir]

On one TPU chip: the resident mamba2 cell cut to 2 layers (every width as
published), traced for a 2-second window through the harness's own
driver.  Writes ``mamba2_2layer.xplane.pb.xz`` and the reduction of it
that the chip's run computed (``mamba2_2layer.reduced.json``) to
``out_dir``, by default beside this file."""
import lzma
import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parents[1] / "src"), str(HERE.parents[1])]


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    out = Path(argv[0]) if argv else HERE
    out.mkdir(parents=True, exist_ok=True)
    from bench import run, xplane
    from repro.launch.compile_cache import use_compile_cache
    root = HERE.parents[1]
    cell = json.loads((root / "bench/workloads/mamba2-370m.train.resident"
                       ".json").read_text())
    cj = json.loads((root / "bench/configs/mamba2-370m.json").read_text())
    cell["chips"] = 1
    run.require_chips(1)
    use_compile_cache()
    cj = dict(cj, n_layer=2)
    cell["fill"]["train_batches"] = 200
    driver = __import__("bench.drivers.train", fromlist=["run"])
    tracer = run.Tracer(True)
    try:
        driver.run(cell, cj, 20261016, 2.0, tracer, reference=False)
        path = xplane.newest_trace(tracer.dir)
        with open(path, "rb") as src, \
                lzma.open(out / "mamba2_2layer.xplane.pb.xz", "wb",
                          preset=9 | lzma.PRESET_EXTREME) as dst:
            shutil.copyfileobj(src, dst)
        reduced = xplane.reduce_trace(path)
    finally:
        tracer.close()
    (out / "mamba2_2layer.reduced.json").write_text(
        json.dumps(reduced, indent=1, sort_keys=True))
    print(json.dumps({k: reduced[k] for k in ("window_s", "busy_s")}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
