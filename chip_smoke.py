#!/usr/bin/env python3
"""Smoke run of the cache-fed trainer and RAG server on a TPU.

Drives the repo's own entry points at full model width, with random weights
made from fixed seeds:

  kernels  each Pallas kernel at the widths the two models use, against its
           jnp reference on the chip: forward and gradient;
  train    ``repro.launch.train`` with mamba2-370m (48 layers, d_model 1024),
           batch 8 x seq 2048, 8 steps; tokens are the bytes that
           ``open_cache``'s client returned;
  serve    ``ServingEngine`` with qwen3-1.7b (28 layers, d_model 2048):
           8 requests at batch 4, RAG passage reads through an
           ``open_cache`` client.

``--chips 4`` runs only the sharded trainer instead: one step of qwen3-1.7b
cut to 2 layers on one chip and on the (4, 1) mesh from the same params and
batch (loss and grad norm must agree), then full-depth steps on the four
chips.

The times it prints are one smoke run's, not benchmark figures.  It needs a
TPU: on any other backend it exits non-zero before any phase.  Any failed
check or error exits non-zero.  The last stdout line is the JSON result.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # four chips, sharded trainer only
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.core import CacheConfig, open_cache  # noqa: E402
from repro.core.types import MB  # noqa: E402
from repro.kernels.flash_attention import (flash_attention_pallas,  # noqa: E402
                                           flash_attention_ref)
from repro.kernels.rmsnorm import rmsnorm_pallas, rmsnorm_ref  # noqa: E402
from repro.kernels.ssd import ssd_chunk_pallas  # noqa: E402
from repro.kernels.ssd.ref import ssd_chunk_ref  # noqa: E402
from repro.launch import train  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.launch.mesh import make_local_mesh  # noqa: E402
from repro.models.config import ShapeSpec  # noqa: E402
from repro.models.transformer import init_params  # noqa: E402
from repro.serve.engine import Request, ServingEngine  # noqa: E402
from repro.storage import RemoteStore, make_dataset  # noqa: E402
from repro.train.optimizer import AdamWConfig  # noqa: E402

# relative L2 error allowed between two bf16-precision computations of the
# same quantity (a few bf16 ulps: 2**-8 ≈ 3.9e-3)
BF16_RTOL = 2e-2


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want), 1e-30))


# ----------------------------------------------------------------- kernels

def _kernel_cases():
    """(name, Pallas fn, reference fn, argument shapes/dtypes) at the widths
    mamba2-370m and qwen3-1.7b use in the phases below."""
    bf, f32 = jnp.bfloat16, jnp.float32
    return [
        ("rmsnorm d=1024", rmsnorm_pallas, rmsnorm_ref,
         [((16384, 1024), bf), ((1024,), bf)]),
        ("rmsnorm d=2048", rmsnorm_pallas, rmsnorm_ref,
         [((16384, 2048), bf), ((2048,), bf)]),
        ("flash_attention", flash_attention_pallas, flash_attention_ref,
         [((2, 2048, 16, 128), bf), ((2, 2048, 8, 128), bf),
          ((2, 2048, 8, 128), bf)]),
        ("ssd_chunk", ssd_chunk_pallas, ssd_chunk_ref,
         [((8, 8, 256, 32, 64), f32), ((8, 8, 256, 32), f32),
          ((8, 8, 256, 128), f32), ((8, 8, 256, 128), f32)]),
    ]


def kernel_phase() -> None:
    for name, kernel, ref, shapes in _kernel_cases():
        keys = jax.random.split(jax.random.PRNGKey(1), len(shapes) + 1)
        args = [jax.random.normal(k, s, jnp.float32).astype(dt)
                for k, (s, dt) in zip(keys, shapes)]
        if name == "ssd_chunk":          # decays are negative, and small
            args[1] = -jnp.abs(args[1]) * 0.05

        def both(*a, kernel=kernel, ref=ref, key=keys[-1]):
            outs = []
            for fn in (kernel, ref):
                out, vjp = jax.vjp(fn, *a)
                cts = jax.tree.map(
                    lambda o: jax.random.normal(key, o.shape, o.dtype), out)
                outs.append((out, vjp(cts)))
            return outs

        (k_out, k_grads), (r_out, r_grads) = jax.jit(both)(*args)
        errs = [rel_err(g, w) for g, w in zip(jax.tree.leaves(k_out),
                                              jax.tree.leaves(r_out))]
        gerrs = [rel_err(g, w) for g, w in zip(k_grads, r_grads)]
        log(f"kernel {name}: forward rel err {max(errs):.2e}, "
            f"grad rel err {max(gerrs):.2e} vs jnp reference")
        check(all(math.isfinite(e) and e < BF16_RTOL for e in errs + gerrs),
              f"{name}: Pallas kernel disagrees with its reference")


# ------------------------------------------------------------------- train

def train_phase(argv) -> train.TrainReport:
    ckpt = tempfile.mkdtemp(prefix="chip-smoke-ckpt-")
    try:
        rep = train.run(argv + ["--ckpt-dir", ckpt])
    finally:
        shutil.rmtree(ckpt, ignore_errors=True)
    log(f"train: compile {rep.compile_s:.1f}s, "
        f"{rep.kernel_calls} Pallas call sites in the step program")
    for i, (dt, t_in) in enumerate(zip(rep.step_s, rep.input_s)):
        log(f"train: step {i + 1} {dt:.3f}s host clock "
            f"(batch read {t_in:.3f}s), loss {rep.losses[i]:.4f}")
    log(f"train: first loss {rep.losses[0]:.4f}, last {rep.losses[-1]:.4f}; "
        f"cache CHR {rep.hit_ratio:.3f}, {rep.bytes_read} token bytes read "
        f"through the client; peak device bytes {rep.peak_bytes} "
        f"(allocated buffers), step scratch {rep.temp_bytes} bytes "
        f"(compiled memory analysis)")
    check(rep.kernel_calls > 0, "no Pallas kernel in the train step program")
    check(all(math.isfinite(x) for x in rep.losses), "non-finite loss")
    check(rep.bytes_read > 0, "the trainer read no bytes through the cache")
    return rep


# ------------------------------------------------------------------- serve

def serve_phase(cfg, *, n_requests: int = 8, batch: int = 4,
                prompt_len: int = 16, max_new: int = 16) -> None:
    t0 = time.perf_counter()
    params = jax.jit(functools.partial(init_params, cfg))(
        jax.random.PRNGKey(0))
    jax.block_until_ready(params)
    log(f"serve: {cfg.name} params initialised in "
        f"{time.perf_counter() - t0:.1f}s")
    store = RemoteStore()
    store.add(make_dataset("knowledge", "flat_files", n_files=500,
                           small_file_size=64 * 1024))
    cache = open_cache(store, 16 * MB,
                       cfg=CacheConfig(min_share=2 * MB,
                                       rebalance_quantum=2 * MB),
                       executor="threaded", fetch_bytes=True)
    try:
        srv = ServingEngine(params, cfg, batch=batch, max_seq=256,
                            cache_engine=cache,
                            knowledge_dataset="knowledge", retrieval_k=4)
        rng = np.random.default_rng(0)
        for rid in range(n_requests):
            srv.submit(Request(rid, rng.integers(0, cfg.vocab, prompt_len,
                                                 dtype=np.int32),
                               max_new=max_new))
        t0 = time.perf_counter()
        done = srv.run()
        dt = time.perf_counter() - t0
        cache.flush(timeout=5.0)
        snap = cache.snapshot()
    finally:
        cache.close()
    n_tok = sum(len(r.output) for r in done)
    kernel_calls = srv.decode_compiled.as_text().count("tpu_custom_call")
    log(f"serve: decode step compiled in {srv.compile_s:.1f}s, "
        f"{kernel_calls} Pallas call sites")
    log(f"serve: {len(done)} requests done, {n_tok} tokens generated in "
        f"{dt:.2f}s host clock (compile included); retrieval CHR "
        f"{snap['hit_ratio']:.3f} over {snap['hits'] + snap['misses']} "
        f"passage block reads")
    check(kernel_calls > 0, "no Pallas kernel in the decode step program")
    check(len(done) == n_requests, "not every request finished")
    check(all(len(r.output) == max_new and
              all(0 <= t < cfg.vocab for t in r.output) for r in done),
          "a request's output is malformed")
    check(snap["hits"] + snap["misses"] > 0,
          "no passage read went through the cache")


# ------------------------------------------------------------ four chips

def _one_step(cfg, shape, mesh, batch) -> dict:
    ts = train.setup_training(cfg, shape, mesh, AdamWConfig())
    _, _, m = ts.step(ts.params, ts.opt_state,
                      jax.device_put(batch, ts.batch_shardings))
    return {"loss": float(m["loss"]), "grad_norm": float(m["grad_norm"]),
            "kernel_calls": ts.kernel_calls, "compile_s": ts.compile_s}


def sharded_phase(devices) -> None:
    cfg = dataclasses.replace(get_config("qwen3-1.7b"), n_layers=2)
    shape = ShapeSpec("compare", 2048, 4, "train")
    toks = np.random.default_rng(0).integers(
        0, cfg.vocab, (shape.global_batch, shape.seq_len + 1), dtype=np.int32)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    got = {}
    for n in (1, len(devices)):
        got[n] = _one_step(cfg, shape, make_local_mesh(devices[:n]), batch)
        log(f"sharded: {cfg.name} cut to {cfg.n_layers} layers, "
            f"batch {shape.global_batch} x seq {shape.seq_len} on {n} "
            f"chip(s): loss {got[n]['loss']:.6f}, grad norm "
            f"{got[n]['grad_norm']:.6f} (compile {got[n]['compile_s']:.1f}s, "
            f"{got[n]['kernel_calls']} Pallas call sites)")
    one, many = got[1], got[len(devices)]
    for key in ("loss", "grad_norm"):
        err = abs(many[key] - one[key]) / abs(one[key])
        log(f"sharded: {key} relative difference {err:.2e}")
        check(math.isfinite(err) and err < BF16_RTOL,
              f"{key} differs between 1 and {len(devices)} chips")
    check(many["kernel_calls"] > 0, "no Pallas kernel in the sharded step")
    train_phase(["--arch", "qwen3-1.7b", "--batch", "8", "--seq", "2048",
                 "--steps", "4", "--log-every", "1", "--ckpt-every", "0"])


# -------------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU; JAX found platform "
                         f"{dev.platform!r} ({dev.device_kind})")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}")
    log(f"smoke run, not a benchmark: {len(devices)} x {dev.device_kind}, "
        f"compile cache {use_compile_cache()}")
    if args.chips == 4:
        sharded_phase(devices[:4])
    else:
        kernel_phase()
        train_phase(["--arch", "mamba2-370m", "--batch", "8", "--seq",
                     "2048", "--steps", "8", "--log-every", "1"])
        serve_phase(get_config("qwen3-1.7b"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
