#!/usr/bin/env python3
"""Readings that the limits of a cell's checks are set from.

    python3 bench/calibrate.py --workload <cell> --seeds 1 2 3 ... [--out f.json]

For a training cell, for each seed, in one process: the program's
checked steps through the cell's own set-up and loop (no fill, no
window), then the reference on the same rows.  The gaps of three
stand-ins are read against the same reference on the same seed:

* ``program``: the program itself (the lower reading);
* ``control``: the reference with its products' operands, its residual
  stream and their gradients in float8 (one precision step below the
  configuration's bfloat16);
* ``half_batch``: the reference on the first half of each batch's rows.

Each reading carries ``correct``: the verdict of the cell's own limits.

``leaves`` keeps, per seed, every loss and per-leaf norm that the gaps
were taken from, and the reference's time.

A state left unchanged reads 1 on ``change_gap`` by its measure and
needs no run.  Needs the chips the cell asks for, like ``run.py``.
"""
import argparse
import functools
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def readings(cell: dict, cj: dict, seeds, log=print) -> dict:
    import jax
    from bench import check
    from bench.drivers.train import TrainJob
    out = {"program": [], "control": [], "half_batch": []}
    limits = cell["check"]
    refs = None
    for seed in seeds:
        t0 = time.perf_counter()
        job = TrainJob(cell, cj, seed)
        try:
            prog = job.checked_steps(cell["job"]["checked_steps"])
        finally:
            job.close()
        rows = job.rows(job.check_log)
        if refs is None:
            refs = {q: job.ref.Reference(cj, q) for q in ("none", "fp8")}
        w0 = jax.jit(functools.partial(job.ref.init_weights, cj))(
            job.ref.seed_key(seed))
        t1 = time.perf_counter()
        ref = refs["none"].train(w0, rows, job.opt)
        t_ref = time.perf_counter() - t1
        half = [{k: v[:v.shape[0] // 2] for k, v in b.items()} for b in rows]
        got = {"program": prog,
               "control": refs["fp8"].train(w0, rows, job.opt),
               "half_batch": refs["none"].train(w0, half, job.opt)}
        out.setdefault("leaves", []).append(
            {"seed": seed, "reference_s": t_ref,
             **{f"{kind}_{part}": r[part] for kind, r in
                dict(got, reference=ref).items()
                for part in ("losses", "grad_norms", "layer_grad_norms",
                             "change_norms")}})
        for kind, r in got.items():
            gaps = check.train_gaps(r, ref)
            ok, _ = check.judge(dict(gaps, bytes_mismatch=0,
                                     nonfinite_losses=0), limits)
            out[kind].append(dict(gaps, seed=seed, correct=ok))
            log(f"seed {seed} {kind}: " + ", ".join(
                f"{k} {v:.4g}" for k, v in gaps.items()) + f", correct {ok}")
        log(f"seed {seed}: {time.perf_counter() - t0:.1f} s")
        del w0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    from bench.run import load_cell, require_chips
    from repro.launch.compile_cache import use_compile_cache
    _, entry, cell, cj = load_cell(args.workload)
    require_chips(entry["chips"])
    use_compile_cache()
    out = readings(cell, cj, args.seeds)
    text = json.dumps(out, indent=1)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
